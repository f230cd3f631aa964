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

Serving pool: the state arrives as the decode cache holds it, stacked over
layers — S (L, BK, m, dv) and z (L, BK, m). The layer index rides in SMEM
as a scalar-prefetch operand next to the active-row mask, and the index
maps pick layer ``layer``'s blocks, so the kernel reads and writes that
layer's rows where they live in the pool: no per-layer slice, write-back or
copy of the stack around it. Two layout choices keep the pool's bytes where
they are. A row's S is held as Sᵀ (dv, m): the TPU's default layout for an
fp32 (..., m, dv) array with dv < 128 keeps m on the lanes, so the
transpose is a relabelling, and no lane is padded. z is read in blocks of
one slot's ``kv_heads`` rows, (kv_heads, m), which is the cache's own
(..., Hkv, m) tiling; each program reads and writes its row of the block.

Differentiable: the public entry point carries a custom VJP so the decode
step composes with `jax.grad` (e.g. RL-style losses over generated tokens).
The forward is the same kernel with every row live; the backward is
O(m·dv) closed-form math on one token — far below Pallas dispatch
granularity — so it is plain jnp (DESIGN.md §3).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import row_to_col

# The name the device trace gives the kernel's op: a profile reader finds
# the decode step by it.
KERNEL_NAME = "decode_linear_attention"


def _step_body(qf_ref, kf_ref, v_ref, s_ref, z, y_ref, s_out, delta: float):
    """Shared per-kv-head step: state RMW + grouped-query readout. z is the
    row's (1, m) normalizer; returns z'."""
    kf = kf_ref[0].astype(jnp.float32)                       # (1, m)
    v = v_ref[0].astype(jnp.float32)                         # (1, dv)
    s = s_ref[0] + row_to_col(v) * kf                        # Sᵀ (dv, m)
    z = z + kf                                               # (1, m)
    q = qf_ref[0].astype(jnp.float32)                        # (G, m)
    num = jax.lax.dot_general(q, s, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (G, dv)
    # VPU row-dot: Mosaic refuses an MXU matvec whose result feeds `+ δ`
    # (it folds δ into the accumulator, which must be zero).
    den = jnp.sum(q * z, axis=-1, keepdims=True)                  # (G, 1)
    y_ref[0] = (num / (den + delta)).astype(y_ref.dtype)
    s_out[0] = s
    return z


def _kernel(l_ref, a_ref, qf_ref, kf_ref, v_ref, s_ref, z_ref, y_ref, s_out,
            z_out, *, delta: float, kv_heads: int):
    """One kv row of the pool. l (1,) and a (BK,) int32 are
    scalar-prefetched into SMEM: l is the layer the index maps select (the
    body never reads it); a nonzero = row i serves a live request. Blocks:
    qf (1, G, m), kf (1, 1, m), v (1, 1, dv), Sᵀ (1, dv, m) fp32 and z
    (kv_heads, m) fp32, the slot's rows, of which this program owns row
    i % kv_heads; outs y (1, G, dv), Sᵀ', z'. The per-row vectors carry a
    unit axis so every block's last two dims equal the array's (Mosaic's
    (8, 128) tiling rule).

    Drained rows skip the feature/MXU work and the state RMW entirely —
    the state passes through unchanged and the output row is zero — so an
    idle slot costs only the block pipeline, no compute. A slot's kv_heads
    programs are consecutive, so its z block stays resident across them
    and is written back once, whole.
    """
    del l_ref
    i = pl.program_id(0)
    row = pl.ds(i % kv_heads, 1)

    @pl.when(a_ref[i] != 0)
    def _():
        z_out[row, :] = _step_body(qf_ref, kf_ref, v_ref, s_ref,
                                   z_ref[row, :], y_ref, s_out, delta)

    @pl.when(a_ref[i] == 0)
    def _():
        y_ref[0] = jnp.zeros_like(y_ref[0])
        s_out[0] = s_ref[0]
        z_out[row, :] = z_ref[row, :]


class DecodeStatics(NamedTuple):
    delta: float
    interpret: bool


@functools.partial(jax.jit,
                   static_argnames=("delta", "interpret", "kv_heads"))
def decode_linear_attention(qf: jnp.ndarray, kf: jnp.ndarray, v: jnp.ndarray,
                            s: jnp.ndarray, z: jnp.ndarray,
                            active: jnp.ndarray | None = None,
                            layer: jnp.ndarray | None = None, *,
                            delta: float = 1e-6,
                            interpret: bool = False,
                            kv_heads: int = 1):
    """qf (BH, m), kf (BK, m), v (BK, dv), s (BK, m, dv) f32, z (BK, m) f32
    -> (y (BH, dv), s', z'). BH must be a multiple of BK (GQA).
    Differentiable (custom VJP) when ``active`` and ``layer`` are None.

    ``active`` (BK,) int/bool masks continuous-batching pool rows: inactive
    (drained) kv rows skip the state update and MXU readout — y rows are 0
    and (s, z) pass through unchanged — so an idle serving slot costs no
    compute. The masked path is forward-only: it is the serving decode
    tick, dispatched from the engine's jitted macro-step via
    ``attention.decode_step`` → ``ops.decode_linear_step`` whenever
    ``spec.use_pallas`` is set (jnp reference off-TPU, same semantics).

    ``layer`` (int32 scalar) takes the pool state stacked over layers:
    s (L, BK, m, dv), z (L, BK, m). Only layer ``layer``'s rows are read
    and written, in place; every other layer's bytes come back unchanged.
    Forward-only and masked (``active`` None = every row live).
    ``kv_heads`` (a divisor of BK) is the number of consecutive rows per
    slot: z is read a slot at a time, in the cache's own layout.
    """
    bh, m = qf.shape
    bk = v.shape[0]
    if bh % bk:
        raise ValueError(f"q rows {bh} not divisible by kv rows {bk}")
    if bk % kv_heads:
        raise ValueError(f"kv rows {bk} not divisible by kv_heads "
                         f"{kv_heads}")
    st = DecodeStatics(delta=delta, interpret=interpret)
    if active is None and layer is None:
        return _decode(st, qf, kf, v, s, z)
    if active is None:
        active = jnp.ones((bk,), jnp.int32)
    if active.shape != (bk,):
        raise ValueError(f"active shape {active.shape} != ({bk},)")
    active = active.astype(jnp.int32)
    if layer is None:
        return _decode_layer(st, qf, kf, v, s, z, active, kv_heads)
    return _decode_stacked(st, qf, kf, v, s, z,
                           jnp.reshape(layer, (1,)).astype(jnp.int32),
                           active, kv_heads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _decode(st: DecodeStatics, qf, kf, v, s, z):
    return _decode_impl(st, qf, kf, v, s, z)


def _decode_impl(st: DecodeStatics, qf, kf, v, s, z):
    """The plain step: every row live."""
    return _decode_layer(st, qf, kf, v, s, z,
                         jnp.ones((v.shape[0],), jnp.int32), 1)


def _decode_layer(st: DecodeStatics, qf, kf, v, s, z, active, kv_heads: int):
    """One layer's state: the stacked kernel on a stack of one."""
    y, s2, z2 = _decode_stacked(st, qf, kf, v, s[None], z[None],
                                jnp.zeros((1,), jnp.int32), active, kv_heads)
    return y, s2[0], z2[0]


def _decode_stacked(st: DecodeStatics, qf, kf, v, s, z, layer, active,
                    kv_heads: int):
    """s (L, BK, m, dv), z (L, BK, m) fp32; layer (1,), active (BK,) int32."""
    bh, m = qf.shape
    bk, dv = v.shape
    g = bh // bk
    nl = s.shape[0]

    def row(i, l_ref, a_ref):
        return i, 0, 0

    def state(i, l_ref, a_ref):
        return l_ref[0], i, 0, 0

    def slot(i, l_ref, a_ref):
        return l_ref[0], i // kv_heads, 0, 0

    y, s2, z2 = pl.pallas_call(
        functools.partial(_kernel, delta=st.delta, kv_heads=kv_heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bk,),
            in_specs=[
                pl.BlockSpec((1, g, m), row),
                pl.BlockSpec((1, 1, m), row),
                pl.BlockSpec((1, 1, dv), row),
                pl.BlockSpec((pl.squeezed, 1, dv, m), state),
                pl.BlockSpec((pl.squeezed, pl.squeezed, kv_heads, m), slot),
            ],
            out_specs=[
                pl.BlockSpec((1, g, dv), row),
                pl.BlockSpec((pl.squeezed, 1, dv, m), state),
                pl.BlockSpec((pl.squeezed, pl.squeezed, kv_heads, m), slot),
            ]),
        name=KERNEL_NAME,
        out_shape=[
            jax.ShapeDtypeStruct((bk, g, dv), v.dtype),
            jax.ShapeDtypeStruct((nl, bk, dv, m), jnp.float32),
            jax.ShapeDtypeStruct((nl, bk // kv_heads, kv_heads, m),
                                 jnp.float32),
        ],
        # Operand numbers count the two prefetched scalars: the stacked
        # Sᵀ and z (operands 5, 6) are updated in place.
        input_output_aliases={5: 1, 6: 2},
        interpret=st.interpret,
    )(layer, active, qf.reshape(bk, g, m), kf[:, None], v[:, None],
      jnp.swapaxes(s, -1, -2), z.reshape(nl, bk // kv_heads, kv_heads, m))
    return y.reshape(bh, dv), jnp.swapaxes(s2, -1, -2), z2.reshape(z.shape)


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
