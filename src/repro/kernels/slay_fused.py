"""Pallas TPU megakernel: end-to-end SLAY causal attention with custom VJP.

Fuses the whole SLAY pipeline — normalize → anchor poly → PRF → Kronecker
fusion (Ψ) → chunked causal prefix contraction — into one kernel per pass
(DESIGN.md §3 "Fused megakernel"). The two-dispatch path
(`kernels/feature_map.py` then `kernels/slay_scan.py`) writes Ψ(Q)/Ψ(K) at
m = R·P·D floats per token to HBM and immediately re-reads them; here the
features are (re)computed inside VMEM per chunk and **never touch HBM** —
per-head HBM traffic drops from O(L·m) feature reads+writes to O(L·d) raw
q/k reads. Anchors (P·d) and omegas (D·d) are a few KB and stay
VMEM-resident across the sequential chunk axis.

Forward (grid (BH, C), chunk axis sequential):

    Q_c = Ψ(q_c), K_c = Ψ(k_c)                      (VMEM only)
    Y_c = Q_c S_{<c} + tril(Q_c K_cᵀ) V_c           (numerator)
    e_c = Q_c z_{<c} + rowsum(tril(Q_c K_cᵀ)) + δ   (denominator)
    S_c = S_{<c} + K_cᵀ V_c,   z_c = z_{<c} + Σ K_c (VMEM scratch carry)

The denominator (one float per token, like flash attention's LSE) is saved
as a residual so the backward pass never re-solves the division.

Backward = recompute-everything, two scans (DESIGN.md §3 "Backward"):

* `_bwd_q` runs chunks **forward**, re-carrying (S, z) exactly like the
  forward pass, and emits dq (+ the q-path dA/dΩ partials): dQ feat-grad
  needs only the *prefix* state.
* `_bwd_kv` runs chunks in **reverse**, carrying the state cotangents
  (dS, dz) in VMEM scratch, and emits dk, dv (+ the k-path dA/dΩ
  partials): dK/dV feat-grads need only the *suffix* cotangent state.

Both recompute Ψ and the intra-chunk scores tril(Q_c K_cᵀ) from raw q/k in
VMEM — the classic flash-attention trade: O(T·m) extra FLOPs per chunk
instead of O(L·m) residual HBM traffic. dA/dΩ are accumulated per head in a
revisited output block and reduced across heads (and the q/k paths) by the
wrapper, so `jax.grad` works end to end — including through GQA groups and
the shared random projections.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import quadrature
from repro.core.features import SlayFeatureConfig
from repro.kernels.common import (FeatureStatics, causal_mask as _causal_mask,
                                  col_to_row, features_bwd, features_fwd,
                                  row_to_col, tpu_params as _tpu_params,
                                  vmem_scratch as _scratch)


class FusedStatics(NamedTuple):
    """Hashable static bundle for the custom-VJP boundary."""

    feat: FeatureStatics
    chunk_size: int
    delta: float
    interpret: bool

    @property
    def feature_dim(self) -> int:
        f = self.feat
        return len(f.s_nodes) * f.num_anchors * f.num_prf


def statics_for(cfg: SlayFeatureConfig, *, chunk_size: int, delta: float,
                interpret: bool) -> FusedStatics:
    if cfg.poly_kind != "anchor" or cfg.fusion != "tensor":
        raise ValueError("fused kernel supports anchor+tensor only")
    s_np, w_np = quadrature.yat_quadrature(cfg.num_quad_nodes, cfg.eps)
    feat = FeatureStatics(
        s_nodes=tuple(float(x) for x in s_np),
        sqrt_w=tuple(float(x) for x in np.sqrt(w_np)),
        num_anchors=cfg.num_anchors, num_prf=cfg.num_prf)
    return FusedStatics(feat=feat, chunk_size=chunk_size, delta=delta,
                        interpret=interpret)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, a_ref, w_ref, o_ref, den_ref,
                s_ref, z_ref, *, st: FusedStatics):
    """Blocks: q (1,T,d), k (1,T,d), v (1,T,dv), a (P,d), w (D,d);
    outs o (1,T,dv), den (1,1,T); scratch s (m,dv) fp32, z (1,m) fp32."""
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    a = a_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    qf, _ = features_fwd(q_ref[0].astype(jnp.float32), a, w, st.feat)   # (T, m)
    kf, _ = features_fwd(k_ref[0].astype(jnp.float32), a, w, st.feat)   # (T, m)
    v = v_ref[0].astype(jnp.float32)                                # (T, dv)
    s = s_ref[...]
    z = z_ref[...]                                                  # (1, m)

    num = jax.lax.dot(qf, s, preferred_element_type=jnp.float32)    # (T, dv)
    den = jax.lax.dot_general(qf, z, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)   # (T, 1)
    scores = _causal_mask(jax.lax.dot_general(
        qf, kf, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32))                        # (T, T)
    num = num + jax.lax.dot(scores, v, preferred_element_type=jnp.float32)
    den = den + jnp.sum(scores, axis=1, keepdims=True)

    o_ref[0] = (num / (den + st.delta)).astype(o_ref.dtype)
    den_ref[0] = col_to_row(den)

    s_ref[...] = s + jax.lax.dot_general(kf, v, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
    z_ref[...] = z + jnp.sum(kf, axis=0, keepdims=True)


def _fwd_impl(st: FusedStatics, q, k, v, anchors, omegas):
    bh, L, d = q.shape
    bk, _, dv = v.shape
    g = bh // bk
    t = st.chunk_size
    m = st.feature_dim
    P, D = st.feat.num_anchors, st.feat.num_prf
    grid = (bh, L // t)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, st=st),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, t, d), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, t, d), lambda h, c: (h // g, c, 0)),
            pl.BlockSpec((1, t, dv), lambda h, c: (h // g, c, 0)),
            pl.BlockSpec((P, d), lambda h, c: (0, 0)),
            pl.BlockSpec((D, d), lambda h, c: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, t, dv), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, 1, t), lambda h, c: (h, 0, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, L, dv), v.dtype),
            jax.ShapeDtypeStruct((bh, 1, L), jnp.float32),
        ],
        scratch_shapes=[_scratch((m, dv)), _scratch((1, m))],
        compiler_params=_tpu_params(),
        interpret=st.interpret,
    )(q, k, v, anchors, omegas)


# ---------------------------------------------------------------------------
# Backward kernel 1: forward chunk scan → dq (+ q-path dA/dΩ)
# ---------------------------------------------------------------------------


def _bwd_q_kernel(q_ref, k_ref, v_ref, a_ref, w_ref, dy_ref, y_ref, den_ref,
                  dq_ref, da_ref, dw_ref, s_ref, z_ref, *, st: FusedStatics):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        z_ref[...] = jnp.zeros_like(z_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    a = a_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    qf, qres = features_fwd(q_ref[0].astype(jnp.float32), a, w, st.feat)
    kf, _ = features_fwd(k_ref[0].astype(jnp.float32), a, w, st.feat)
    v = v_ref[0].astype(jnp.float32)
    dy = dy_ref[0].astype(jnp.float32)                        # (T, dv)
    y = y_ref[0].astype(jnp.float32)                          # (T, dv)
    e = row_to_col(den_ref[0]) + st.delta                     # (T, 1)
    s = s_ref[...]
    z = z_ref[...]                                            # (1, m)

    gg = dy / e                                               # dnum (T, dv)
    hh = -jnp.sum(dy * y, axis=-1, keepdims=True) / e         # dden (T, 1)
    # dP = tril(G Vᵀ + h 1ᵀ);  dQfeat = G Sᵀ + h zᵀ + dP K.
    dp = _causal_mask(
        jax.lax.dot_general(gg, v, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) + hh)
    dqf = (jax.lax.dot_general(gg, s, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
           + hh * z
           + jax.lax.dot(dp, kf, preferred_element_type=jnp.float32))
    dq, da, dw = features_bwd(dqf, qres, a, w, st.feat)
    dq_ref[0] = dq.astype(dq_ref.dtype)
    da_ref[0] += da
    dw_ref[0] += dw

    s_ref[...] = s + jax.lax.dot_general(kf, v, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
    z_ref[...] = z + jnp.sum(kf, axis=0, keepdims=True)


# ---------------------------------------------------------------------------
# Backward kernel 2: reverse chunk scan → dk, dv (+ k-path dA/dΩ)
# ---------------------------------------------------------------------------


def _bwd_kv_kernel(q_ref, k_ref, v_ref, a_ref, w_ref, dy_ref, y_ref, den_ref,
                   dk_ref, dv_ref, da_ref, dw_ref, ds_ref, dz_ref, *,
                   st: FusedStatics):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dz_ref[...] = jnp.zeros_like(dz_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    a = a_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    qf, _ = features_fwd(q_ref[0].astype(jnp.float32), a, w, st.feat)
    kf, kres = features_fwd(k_ref[0].astype(jnp.float32), a, w, st.feat)
    v = v_ref[0].astype(jnp.float32)
    dy = dy_ref[0].astype(jnp.float32)
    y = y_ref[0].astype(jnp.float32)
    e = row_to_col(den_ref[0]) + st.delta                     # (T, 1)
    ds = ds_ref[...]                                          # (m, dv)
    dz = dz_ref[...]                                          # (1, m)

    gg = dy / e
    hh = -jnp.sum(dy * y, axis=-1, keepdims=True) / e
    scores = _causal_mask(jax.lax.dot_general(
        qf, kf, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32))                  # (T, T)
    dp = _causal_mask(
        jax.lax.dot_general(gg, v, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) + hh)
    # dKfeat = dPᵀ Q + V dSᵀ + 1 dzᵀ;  dV = Pᵀ G + K dS.
    dkf = (jax.lax.dot_general(dp, qf, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
           + jax.lax.dot_general(v, ds, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
           + dz)
    dvv = (jax.lax.dot_general(scores, gg, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
           + jax.lax.dot(kf, ds, preferred_element_type=jnp.float32))
    dk, da, dw = features_bwd(dkf, kres, a, w, st.feat)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dvv.astype(dv_ref.dtype)
    da_ref[0] += da
    dw_ref[0] += dw

    # Carry state cotangents to the *previous* chunk.
    ds_ref[...] = ds + jax.lax.dot_general(
        qf, gg, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dz_ref[...] = dz + jnp.sum(qf * hh, axis=0, keepdims=True)


def _bwd_impl(st: FusedStatics, q, k, v, anchors, omegas, y, den, dy):
    bh, L, d = q.shape
    bk, _, dv = v.shape
    g = bh // bk
    t = st.chunk_size
    nc = L // t
    m = st.feature_dim
    P, D = st.feat.num_anchors, st.feat.num_prf

    common_in = [
        pl.BlockSpec((1, t, d), lambda h, c: (h, c, 0)),
        pl.BlockSpec((1, t, d), lambda h, c: (h // g, c, 0)),
        pl.BlockSpec((1, t, dv), lambda h, c: (h // g, c, 0)),
        pl.BlockSpec((P, d), lambda h, c: (0, 0)),
        pl.BlockSpec((D, d), lambda h, c: (0, 0)),
        pl.BlockSpec((1, t, dv), lambda h, c: (h, c, 0)),   # dy
        pl.BlockSpec((1, t, dv), lambda h, c: (h, c, 0)),   # y
        pl.BlockSpec((1, 1, t), lambda h, c: (h, 0, c)),    # den
    ]
    dq, da_q, dw_q = pl.pallas_call(
        functools.partial(_bwd_q_kernel, st=st),
        grid=(bh, nc),
        in_specs=common_in,
        out_specs=[
            pl.BlockSpec((1, t, d), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, P, d), lambda h, c: (h, 0, 0)),
            pl.BlockSpec((1, D, d), lambda h, c: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, L, d), q.dtype),
            jax.ShapeDtypeStruct((bh, P, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, D, d), jnp.float32),
        ],
        scratch_shapes=[_scratch((m, dv)), _scratch((1, m))],
        compiler_params=_tpu_params(),
        interpret=st.interpret,
    )(q, k, v, anchors, omegas, dy, y, den)

    # Reverse scan: grid step c processes chunk nc-1-c.
    rev_in = [
        pl.BlockSpec((1, t, d), lambda h, c: (h, nc - 1 - c, 0)),
        pl.BlockSpec((1, t, d), lambda h, c: (h // g, nc - 1 - c, 0)),
        pl.BlockSpec((1, t, dv), lambda h, c: (h // g, nc - 1 - c, 0)),
        pl.BlockSpec((P, d), lambda h, c: (0, 0)),
        pl.BlockSpec((D, d), lambda h, c: (0, 0)),
        pl.BlockSpec((1, t, dv), lambda h, c: (h, nc - 1 - c, 0)),
        pl.BlockSpec((1, t, dv), lambda h, c: (h, nc - 1 - c, 0)),
        pl.BlockSpec((1, 1, t), lambda h, c: (h, 0, nc - 1 - c)),
    ]
    dk_p, dv_p, da_k, dw_k = pl.pallas_call(
        functools.partial(_bwd_kv_kernel, st=st),
        grid=(bh, nc),
        in_specs=rev_in,
        out_specs=[
            pl.BlockSpec((1, t, d), lambda h, c: (h, nc - 1 - c, 0)),
            pl.BlockSpec((1, t, dv), lambda h, c: (h, nc - 1 - c, 0)),
            pl.BlockSpec((1, P, d), lambda h, c: (h, 0, 0)),
            pl.BlockSpec((1, D, d), lambda h, c: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, L, d), k.dtype),
            jax.ShapeDtypeStruct((bh, L, dv), v.dtype),
            jax.ShapeDtypeStruct((bh, P, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, D, d), jnp.float32),
        ],
        scratch_shapes=[_scratch((m, dv)), _scratch((1, m))],
        compiler_params=_tpu_params(),
        interpret=st.interpret,
    )(q, k, v, anchors, omegas, dy, y, den)

    # GQA: dk/dv partials are per q-head; reduce over each group of g.
    dk = jnp.sum(dk_p.reshape(bk, g, L, d), axis=1).astype(k.dtype)
    dvv = jnp.sum(dv_p.reshape(bk, g, L, dv), axis=1).astype(v.dtype)
    da = jnp.sum(da_q + da_k, axis=0).astype(anchors.dtype)
    dw = jnp.sum(dw_q + dw_k, axis=0).astype(omegas.dtype)
    return dq, dk, dvv, da, dw


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused(st: FusedStatics, q, k, v, anchors, omegas):
    y, _den = _fwd_impl(st, q, k, v, anchors, omegas)
    return y


def _fused_fwd(st: FusedStatics, q, k, v, anchors, omegas):
    y, den = _fwd_impl(st, q, k, v, anchors, omegas)
    return y, (q, k, v, anchors, omegas, y, den)


def _fused_bwd(st: FusedStatics, res, dy):
    q, k, v, anchors, omegas, y, den = res
    return _bwd_impl(st, q, k, v, anchors, omegas, y, den, dy)


_fused.defvjp(_fused_fwd, _fused_bwd)


@functools.partial(jax.jit, static_argnames=("cfg", "chunk_size", "delta",
                                             "interpret"))
def fused_causal_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           anchors: jnp.ndarray, omegas: jnp.ndarray,
                           cfg: SlayFeatureConfig, *, chunk_size: int = 256,
                           delta: float = 1e-6,
                           interpret: bool = False) -> jnp.ndarray:
    """q (BH, L, d), k (BK, L, d), v (BK, L, dv) → y (BH, L, dv).

    Raw (pre-feature) q/k; Ψ is computed inside the kernel. Differentiable
    w.r.t. every array input via the custom VJP. BH must be a multiple of
    BK (GQA group G = BH // BK); L must be a multiple of ``chunk_size`` —
    the `ops` wrapper zero-pads arbitrary L.
    """
    bh, L, d = q.shape
    bk = v.shape[0]
    if bh % bk:
        raise ValueError(f"q rows {bh} not divisible by kv rows {bk}")
    if L % chunk_size:
        raise ValueError(f"L={L} not divisible by chunk={chunk_size}")
    st = statics_for(cfg, chunk_size=chunk_size, delta=delta,
                     interpret=interpret)
    return _fused(st, q, k, v, anchors, omegas)
