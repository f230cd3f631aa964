"""Pallas TPU kernel: one-token linear-attention decode step.

The serving hot loop: update the running state with the new key/value and
read out the attention for the G query heads of each kv head —

    S' = S + Ψ(k)ᵀ v        (m x dv, fp32, in-place)
    z' = z + Ψ(k)           (m,     fp32, in-place)
    y_g = (q_g S') / (q_g z' + δ)      for g = 1..G

All operands for one kv head fit comfortably in VMEM (m·dv fp32 ≈ 192 KB at
m=384, dv=128), so the step is a single fused VMEM-resident kernel: one HBM
read-modify-write of the state per token instead of separate outer-product /
matvec / reduction kernels. The state buffers are donated
(input_output_aliased) — the update is truly in place in HBM.

Grid: (BK,) — one program per kv head; the G query heads of that kv head
are processed together as a (G, m) x (m, dv) MXU matmul.

Differentiable: the public entry point carries a custom VJP so the decode
step composes with `jax.grad` (e.g. RL-style losses over generated tokens).
The backward is O(m·dv) closed-form math on one token — far below Pallas
dispatch granularity — so it is plain jnp (DESIGN.md §3).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import row_to_col


def _step_body(qf_ref, kf_ref, v_ref, s_ref, z_ref, y_ref, s_out, z_out,
               delta: float):
    """Shared per-kv-head step: state RMW + grouped-query readout."""
    kf = kf_ref[0].astype(jnp.float32)                       # (1, m)
    v = v_ref[0].astype(jnp.float32)                         # (1, dv)
    s = s_ref[0] + row_to_col(kf) * v                        # (m, dv)
    z = z_ref[0] + kf                                        # (1, m)
    q = qf_ref[0].astype(jnp.float32)                        # (G, m)
    num = jax.lax.dot(q, s, preferred_element_type=jnp.float32)   # (G, dv)
    # VPU row-dot: Mosaic refuses an MXU matvec whose result feeds `+ δ`
    # (it folds δ into the accumulator, which must be zero).
    den = jnp.sum(q * z, axis=-1, keepdims=True)                  # (G, 1)
    y_ref[0] = (num / (den + delta)).astype(y_ref.dtype)
    s_out[0] = s
    z_out[0] = z


def _kernel(qf_ref, kf_ref, v_ref, s_ref, z_ref, y_ref, s_out, z_out, *,
            delta: float):
    """Refs (per kv head): qf (1, G, m), kf (1, 1, m), v (1, 1, dv),
    s (1, m, dv) fp32, z (1, 1, m) fp32; outs y (1, G, dv), s', z'. The
    per-row vectors carry a unit middle axis so every block's last two
    dims equal the array's (Mosaic's (8, 128) tiling rule)."""
    _step_body(qf_ref, kf_ref, v_ref, s_ref, z_ref, y_ref, s_out, z_out,
               delta)


def _kernel_masked(a_ref, qf_ref, kf_ref, v_ref, s_ref, z_ref, y_ref,
                   s_out, z_out, *, delta: float):
    """Active-slot-masked step for the continuous-batching pool.

    a (BK,) int32 in SMEM, whole: nonzero = kv row i serves a live
    request. Drained slots skip the feature/MXU work and the state RMW
    entirely — the state block passes through unchanged and the output row
    is zero — so an idle slot costs only the block pipeline, no compute.
    """
    active = a_ref[pl.program_id(0)] != 0

    @pl.when(active)
    def _():
        _step_body(qf_ref, kf_ref, v_ref, s_ref, z_ref, y_ref, s_out,
                   z_out, delta)

    @pl.when(jnp.logical_not(active))
    def _():
        y_ref[0] = jnp.zeros_like(y_ref[0])
        s_out[0] = s_ref[0]
        z_out[0] = z_ref[0]


class DecodeStatics(NamedTuple):
    delta: float
    interpret: bool


@functools.partial(jax.jit, static_argnames=("delta", "interpret"))
def decode_linear_attention(qf: jnp.ndarray, kf: jnp.ndarray, v: jnp.ndarray,
                            s: jnp.ndarray, z: jnp.ndarray,
                            active: jnp.ndarray | None = None, *,
                            delta: float = 1e-6,
                            interpret: bool = False):
    """qf (BH, m), kf (BK, m), v (BK, dv), s (BK, m, dv) f32, z (BK, m) f32
    -> (y (BH, dv), s', z'). BH must be a multiple of BK (GQA).
    Differentiable (custom VJP) when ``active`` is None.

    ``active`` (BK,) int/bool masks continuous-batching pool rows: inactive
    (drained) kv rows skip the state update and MXU readout — y rows are 0
    and (s, z) pass through unchanged — so an idle serving slot costs no
    compute. The masked path is forward-only: it is the serving decode
    tick, dispatched from the engine's jitted macro-step via
    ``attention.decode_step`` → ``ops.decode_linear_step`` whenever
    ``spec.use_pallas`` is set (jnp reference off-TPU, same semantics).
    """
    bh, m = qf.shape
    bk = v.shape[0]
    if bh % bk:
        raise ValueError(f"q rows {bh} not divisible by kv rows {bk}")
    st = DecodeStatics(delta=delta, interpret=interpret)
    if active is None:
        return _decode(st, qf, kf, v, s, z)
    if active.shape != (bk,):
        raise ValueError(f"active shape {active.shape} != ({bk},)")
    return _decode_masked(st, qf, kf, v, s, z,
                          active.astype(jnp.int32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _decode(st: DecodeStatics, qf, kf, v, s, z):
    return _decode_impl(st, qf, kf, v, s, z)


def _specs(bk, g, m, dv, y_dtype):
    in_specs = [
        pl.BlockSpec((1, g, m), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, 1, m), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, 1, dv), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, m, dv), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, 1, m), lambda i: (i, 0, 0)),
    ]
    out_specs = [
        pl.BlockSpec((1, g, dv), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, m, dv), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, 1, m), lambda i: (i, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((bk, g, dv), y_dtype),
        jax.ShapeDtypeStruct((bk, m, dv), jnp.float32),
        jax.ShapeDtypeStruct((bk, 1, m), jnp.float32),
    ]
    return in_specs, out_specs, out_shape


def _decode_impl(st: DecodeStatics, qf, kf, v, s, z):
    bh, m = qf.shape
    bk, dv = v.shape
    g = bh // bk
    qg = qf.reshape(bk, g, m)
    in_specs, out_specs, out_shape = _specs(bk, g, m, dv, v.dtype)

    y, s2, z2 = pl.pallas_call(
        functools.partial(_kernel, delta=st.delta),
        grid=(bk,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases={3: 1, 4: 2},   # s, z updated in place
        interpret=st.interpret,
    )(qg, kf[:, None], v[:, None], s, z[:, None])
    return y.reshape(bh, dv), s2, z2.reshape(bk, m)


def _decode_masked(st: DecodeStatics, qf, kf, v, s, z, active):
    bh, m = qf.shape
    bk, dv = v.shape
    g = bh // bk
    qg = qf.reshape(bk, g, m)
    in_specs, out_specs, out_shape = _specs(bk, g, m, dv, v.dtype)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + in_specs

    y, s2, z2 = pl.pallas_call(
        functools.partial(_kernel_masked, delta=st.delta),
        grid=(bk,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases={4: 1, 5: 2},   # s, z updated in place
        interpret=st.interpret,
    )(active, qg, kf[:, None], v[:, None], s, z[:, None])
    return y.reshape(bh, dv), s2, z2.reshape(bk, m)


def _decode_fwd(st: DecodeStatics, qf, kf, v, s, z):
    y, s2, z2 = _decode_impl(st, qf, kf, v, s, z)
    # NOTE: s/z are donated to s2/z2 by the kernel; save the *updated* state
    # (s2 = s + kfᵀv, z2 = z + kf) and the inputs needed to reconstruct.
    return (y, s2, z2), (qf, kf, v, s2, z2, y)


def _decode_bwd(st: DecodeStatics, res, cts):
    """Closed-form one-token backward (jnp; below kernel granularity).

    y_g = (q_g S') / (q_g z' + δ) with S' = S + kᵀv, z' = z + k.
    Cotangents arrive for all three outputs (y, S', z').
    """
    qf, kf, v, s2, z2, y = res
    dy, ds2_in, dz2_in = cts
    bh, m = qf.shape
    bk, dv = v.shape
    g = bh // bk
    f32 = jnp.float32
    qg = qf.reshape(bk, g, m).astype(f32)
    dyg = dy.reshape(bk, g, dv).astype(f32)
    yg = y.reshape(bk, g, dv).astype(f32)
    den = jnp.einsum("kgm,km->kg", qg, z2) + st.delta          # (bk, g)
    gg = dyg / den[..., None]                                  # dnum
    hh = -jnp.sum(dyg * yg, axis=-1) / den                     # dden (bk, g)
    dqg = (jnp.einsum("kgd,kmd->kgm", gg, s2)
           + hh[..., None] * z2[:, None, :])
    ds2 = ds2_in.astype(f32) + jnp.einsum("kgm,kgd->kmd", qg, gg)
    dz2 = dz2_in.astype(f32) + jnp.einsum("kgm,kg->km", qg, hh)
    # S' = S + kfᵀ v, z' = z + kf.
    vf = v.astype(f32)
    kff = kf.astype(f32)
    dkf = jnp.einsum("kmd,kd->km", ds2, vf) + dz2
    dvv = jnp.einsum("km,kmd->kd", kff, ds2)
    return (dqg.reshape(bh, m).astype(qf.dtype), dkf.astype(kf.dtype),
            dvv.astype(v.dtype), ds2, dz2)


_decode.defvjp(_decode_fwd, _decode_bwd)
