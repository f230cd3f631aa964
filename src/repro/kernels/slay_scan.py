"""Pallas TPU kernel: chunked causal linear attention with VMEM-resident state.

TPU-native adaptation of SLAY's causal prefix computation (DESIGN.md §3).
GPU implementations use a per-token recurrence; on TPU we use the
chunk-parallel decomposition

    Y_c = Q_c S_{<c} + tril(Q_c K_cᵀ) V_c          (numerator)
    d_c = Q_c z_{<c} + rowsum(tril(Q_c K_cᵀ))      (denominator)
    S_c = S_{<c} + K_cᵀ V_c,   z_c = z_{<c} + Σ K_c

so every contraction is an MXU-shaped [T×m]·[m×dv] / [T×m]·[m×T] matmul and
the running state (S ∈ m×dv fp32, z ∈ m fp32) lives in VMEM scratch across
the sequential chunk axis of the grid — one HBM round-trip per token block.

Grid: (BH, L // T) with dimension_semantics ("parallel", "arbitrary") — the
chunk axis iterates innermost and sequentially, so scratch carries state.
GQA is expressed in the BlockSpec index maps: q-head row h reads kv row
h // group — the kv features are never materialized per-q-head.

Block shapes: T (chunk) and m (features) should be multiples of 128 for
MXU/VREG lane alignment; dv is typically 128 (head_dim). VMEM footprint per
step ≈ T·m (q,k) + T·dv (v,o) + m·dv + m (state) floats — e.g. T=256, m=384,
dv=128: ~0.9 MB « 16 MB VMEM.

Differentiable: the public entry point carries a custom VJP (DESIGN.md §3
"Backward") so `use_pallas=True` works under `jax.grad`. The forward saves
only the per-token denominator (L floats/head, like flash attention's LSE);
the backward recomputes the intra-chunk scores from the saved features and
runs two scans — a forward scan re-carrying (S, z) for dQ and a reverse scan
carrying the state cotangents (dS, dz) for dK/dV.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (causal_mask as _causal_mask, col_to_row,
                                  row_to_col, tpu_params as _tpu_params,
                                  vmem_scratch as _scratch)


class ScanStatics(NamedTuple):
    chunk_size: int
    delta: float
    interpret: bool


def _kernel(q_ref, k_ref, v_ref, o_ref, den_ref, s_ref, z_ref, *,
            delta: float):
    """One (head, chunk) grid step. Refs hold VMEM blocks:

    q_ref (1, T, m), k_ref (1, T, m), v_ref (1, T, dv); outs o (1, T, dv),
    den (1, 1, T) (lane-dense row); scratch s_ref (m, dv) fp32, z_ref
    (1, m) fp32.
    """
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    q = q_ref[0].astype(jnp.float32)          # (T, m)
    k = k_ref[0].astype(jnp.float32)          # (T, m)
    v = v_ref[0].astype(jnp.float32)          # (T, dv)
    s = s_ref[...]                            # (m, dv)
    z = z_ref[...]                            # (1, m)

    # Inter-chunk: prefix state contribution.
    num = jax.lax.dot(q, s, preferred_element_type=jnp.float32)      # (T, dv)
    den = jax.lax.dot_general(q, z, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)    # (T, 1)

    # Intra-chunk: causal quadratic on features (T×T stays in VMEM).
    scores = _causal_mask(jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32))                          # (T, T)
    num = num + jax.lax.dot(scores, v, preferred_element_type=jnp.float32)
    den = den + jnp.sum(scores, axis=1, keepdims=True)

    o_ref[0] = (num / (den + delta)).astype(o_ref.dtype)
    den_ref[0] = col_to_row(den)

    # Carry the running state to the next chunk.
    s_ref[...] = s + jax.lax.dot_general(k, v, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
    z_ref[...] = z + jnp.sum(k, axis=0, keepdims=True)


def _fwd_impl(st: ScanStatics, qf, kf, v):
    bh, L, m = qf.shape
    bk, _, dv = v.shape
    g = bh // bk
    t = st.chunk_size
    grid = (bh, L // t)
    return pl.pallas_call(
        functools.partial(_kernel, delta=st.delta),
        grid=grid,
        name="slay_scan",
        in_specs=[
            pl.BlockSpec((1, t, m), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, t, m), lambda h, c: (h // g, c, 0)),
            pl.BlockSpec((1, t, dv), lambda h, c: (h // g, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, t, dv), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, 1, t), lambda h, c: (h, 0, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, L, dv), v.dtype),
            jax.ShapeDtypeStruct((bh, 1, L), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((m, dv)),   # S: running ΣKᵀV
            _scratch((1, m)),    # z: running ΣK
        ],
        compiler_params=_tpu_params(),
        interpret=st.interpret,
    )(qf, kf, v)


# ---------------------------------------------------------------------------
# Backward kernels (feature-level; see slay_fused.py for the raw-q/k fused
# variant that also backprops through Ψ).
# ---------------------------------------------------------------------------


def _bwd_q_kernel(q_ref, k_ref, v_ref, dy_ref, y_ref, den_ref, dq_ref,
                  s_ref, z_ref, *, delta: float):
    """Forward chunk scan: dQ = G S_{<c}ᵀ + h z_{<c}ᵀ + tril(G Vᵀ + h 1ᵀ) K."""
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    dy = dy_ref[0].astype(jnp.float32)
    y = y_ref[0].astype(jnp.float32)
    e = row_to_col(den_ref[0]) + delta                # (T, 1)
    s = s_ref[...]
    z = z_ref[...]                                    # (1, m)

    gg = dy / e
    hh = -jnp.sum(dy * y, axis=-1, keepdims=True) / e
    dp = _causal_mask(
        jax.lax.dot_general(gg, v, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) + hh)
    dq = (jax.lax.dot_general(gg, s, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
          + hh * z
          + jax.lax.dot(dp, k, preferred_element_type=jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)

    s_ref[...] = s + jax.lax.dot_general(k, v, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
    z_ref[...] = z + jnp.sum(k, axis=0, keepdims=True)


def _bwd_kv_kernel(q_ref, k_ref, v_ref, dy_ref, y_ref, den_ref, dk_ref,
                   dv_ref, ds_ref, dz_ref, *, delta: float):
    """Reverse chunk scan carrying (dS, dz):
    dK = dPᵀ Q + V dSᵀ + 1 dzᵀ;  dV = Pᵀ G + K dS."""
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dz_ref[...] = jnp.zeros_like(dz_ref)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    dy = dy_ref[0].astype(jnp.float32)
    y = y_ref[0].astype(jnp.float32)
    e = row_to_col(den_ref[0]) + delta                # (T, 1)
    ds = ds_ref[...]
    dz = dz_ref[...]                                  # (1, m)

    gg = dy / e
    hh = -jnp.sum(dy * y, axis=-1, keepdims=True) / e
    scores = _causal_mask(jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32))
    dp = _causal_mask(
        jax.lax.dot_general(gg, v, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) + hh)
    dk = (jax.lax.dot_general(dp, q, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
          + jax.lax.dot_general(v, ds, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
          + dz)
    dvv = (jax.lax.dot_general(scores, gg, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
           + jax.lax.dot(k, ds, preferred_element_type=jnp.float32))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dvv.astype(dv_ref.dtype)

    ds_ref[...] = ds + jax.lax.dot_general(
        q, gg, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dz_ref[...] = dz + jnp.sum(q * hh, axis=0, keepdims=True)


def _bwd_impl(st: ScanStatics, qf, kf, v, y, den, dy):
    bh, L, m = qf.shape
    bk, _, dv = v.shape
    g = bh // bk
    t = st.chunk_size
    nc = L // t

    dq = pl.pallas_call(
        functools.partial(_bwd_q_kernel, delta=st.delta),
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, t, m), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, t, m), lambda h, c: (h // g, c, 0)),
            pl.BlockSpec((1, t, dv), lambda h, c: (h // g, c, 0)),
            pl.BlockSpec((1, t, dv), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, t, dv), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, 1, t), lambda h, c: (h, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, t, m), lambda h, c: (h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, L, m), qf.dtype),
        scratch_shapes=[_scratch((m, dv)), _scratch((1, m))],
        compiler_params=_tpu_params(),
        interpret=st.interpret,
    )(qf, kf, v, dy, y, den)

    dk_p, dv_p = pl.pallas_call(
        functools.partial(_bwd_kv_kernel, delta=st.delta),
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, t, m), lambda h, c: (h, nc - 1 - c, 0)),
            pl.BlockSpec((1, t, m), lambda h, c: (h // g, nc - 1 - c, 0)),
            pl.BlockSpec((1, t, dv), lambda h, c: (h // g, nc - 1 - c, 0)),
            pl.BlockSpec((1, t, dv), lambda h, c: (h, nc - 1 - c, 0)),
            pl.BlockSpec((1, t, dv), lambda h, c: (h, nc - 1 - c, 0)),
            pl.BlockSpec((1, 1, t), lambda h, c: (h, 0, nc - 1 - c)),
        ],
        out_specs=[
            pl.BlockSpec((1, t, m), lambda h, c: (h, nc - 1 - c, 0)),
            pl.BlockSpec((1, t, dv), lambda h, c: (h, nc - 1 - c, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, L, m), kf.dtype),
            jax.ShapeDtypeStruct((bh, L, dv), v.dtype),
        ],
        scratch_shapes=[_scratch((m, dv)), _scratch((1, m))],
        compiler_params=_tpu_params(),
        interpret=st.interpret,
    )(qf, kf, v, dy, y, den)

    # GQA: reduce the per-q-head dk/dv partials over each group.
    dk = jnp.sum(dk_p.reshape(bk, g, L, m), axis=1).astype(kf.dtype)
    dvv = jnp.sum(dv_p.reshape(bk, g, L, dv), axis=1).astype(v.dtype)
    return dq, dk, dvv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scan(st: ScanStatics, qf, kf, v):
    y, _den = _fwd_impl(st, qf, kf, v)
    return y


def _scan_fwd(st: ScanStatics, qf, kf, v):
    y, den = _fwd_impl(st, qf, kf, v)
    return y, (qf, kf, v, y, den)


def _scan_bwd(st: ScanStatics, res, dy):
    qf, kf, v, y, den = res
    return _bwd_impl(st, qf, kf, v, y, den, dy)


_scan.defvjp(_scan_fwd, _scan_bwd)


@functools.partial(jax.jit, static_argnames=("chunk_size", "delta",
                                             "interpret"))
def causal_linear_attention(qf: jnp.ndarray, kf: jnp.ndarray, v: jnp.ndarray,
                            *, chunk_size: int = 256, delta: float = 1e-6,
                            interpret: bool = False) -> jnp.ndarray:
    """qf (BH, L, m), kf (BK, L, m), v (BK, L, dv) -> (BH, L, dv).

    BH must be a multiple of BK (GQA group size G = BH // BK); L must be a
    multiple of ``chunk_size``. Differentiable (custom VJP).
    """
    bh, L, m = qf.shape
    bk, _, dv = v.shape
    if bh % bk:
        raise ValueError(f"q rows {bh} not divisible by kv rows {bk}")
    if L % chunk_size:
        raise ValueError(f"L={L} not divisible by chunk={chunk_size}")
    st = ScanStatics(chunk_size=chunk_size, delta=delta, interpret=interpret)
    return _scan(st, qf, kf, v)
