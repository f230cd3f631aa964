"""Pallas TPU kernel: fused SLAY feature map Ψ(u).

Fuses the whole per-token feature pipeline into one VMEM-resident pass
(DESIGN.md §3 "Feature-map fusion"):

    normalize → anchor poly φ_p = (uᵀa)²/√P → PRF φ_e = exp(√(2s)ωᵀu − s)/√D
              → per-node Kronecker √w_r (φ_p ⊗ φ_e) → concat over r.

On GPU these are 4-5 separate elementwise/matmul kernels with HBM traffic of
~(2R+3)·L·max(P·D, d) floats; fused, each token block makes exactly one HBM
read (T·d) and one write (T·R·P·D). Both matmuls (u·Aᵀ, u·Ωᵀ) are MXU ops.

Grid: (num_token_blocks,) over a flattened token axis. Anchors/omegas are
small (P·d, D·d) and are loaded whole into VMEM for every block (they fit in
a few KB). Quadrature constants (s_r, √w_r) are compile-time Python floats —
R is small (default 3) so the node loop is unrolled.

Differentiable: the public entry point carries a custom VJP whose backward
is itself one Pallas kernel (recompute Ψ intermediates per block, emit du
plus per-block dA/dΩ partials reduced outside), so the two-dispatch
feature→scan pipeline trains end to end (DESIGN.md §3).
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
from repro.kernels.common import FeatureStatics, features_bwd, features_fwd


class _MapStatics(NamedTuple):
    """Hashable static bundle for the feature kernel's custom-VJP boundary."""

    feat: FeatureStatics
    block_tokens: int
    interpret: bool


def _kernel(u_ref, a_ref, w_ref, o_ref, *, feat: FeatureStatics):
    """u_ref (T, d), a_ref (P, d), w_ref (D, d), o_ref (T, R*P*D).

    normalize → anchor poly (paper §2.4.2) → PRF (Eq. 9) → Kronecker
    fusion (Eq. 10), all via ``common.features_fwd`` — the same code the
    backward kernel differentiates, so fwd/bwd can never drift."""
    psi, _ = features_fwd(u_ref[...].astype(jnp.float32),
                          a_ref[...].astype(jnp.float32),
                          w_ref[...].astype(jnp.float32), feat)
    o_ref[...] = psi.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("cfg", "block_tokens",
                                             "interpret"))
def slay_feature_map(u: jnp.ndarray, anchors: jnp.ndarray,
                     omegas: jnp.ndarray, cfg: SlayFeatureConfig, *,
                     block_tokens: int = 256,
                     interpret: bool = False) -> jnp.ndarray:
    """u (N, d) -> Ψ(u) (N, m) with m = R·P·D. N must divide block_tokens.

    Only the default configuration (anchor poly, explicit-tensor fusion) is
    kernelized — it is the hot path; other variants fall back to the jnp
    reference in ``repro.core.features``. Differentiable (custom VJP).
    """
    if cfg.poly_kind != "anchor" or cfg.fusion != "tensor":
        raise ValueError("kernelized path supports anchor+tensor only")
    n, d = u.shape
    if n % block_tokens:
        raise ValueError(f"N={n} not divisible by block={block_tokens}")
    s_np, w_np = quadrature.yat_quadrature(cfg.num_quad_nodes, cfg.eps)
    feat = FeatureStatics(
        s_nodes=tuple(float(x) for x in s_np),
        sqrt_w=tuple(float(x) for x in np.sqrt(w_np)),
        num_anchors=cfg.num_anchors, num_prf=cfg.num_prf)
    st = _MapStatics(feat=feat, block_tokens=block_tokens,
                     interpret=interpret)
    return _fmap(st, u, anchors, omegas)


def _fwd_impl(st: _MapStatics, u, anchors, omegas):
    n, d = u.shape
    f = st.feat
    m = len(f.s_nodes) * f.num_anchors * f.num_prf
    block = st.block_tokens
    return pl.pallas_call(
        functools.partial(_kernel, feat=f),
        grid=(n // block,),
        name="slay_feature_map",
        in_specs=[
            pl.BlockSpec((block, d), lambda i: (i, 0)),
            pl.BlockSpec((f.num_anchors, d), lambda i: (0, 0)),
            pl.BlockSpec((f.num_prf, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, m), u.dtype),
        interpret=st.interpret,
    )(u, anchors, omegas)


def _bwd_kernel(u_ref, a_ref, w_ref, dpsi_ref, du_ref, da_ref, dw_ref, *,
                feat: FeatureStatics):
    """Recompute the Ψ intermediates for this block and backprop dΨ.

    Emits du (T, d) plus per-block dA (P, d) / dΩ (D, d) partials (reduced
    over blocks by the wrapper — keeps every grid step independent)."""
    a = a_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    _, res = features_fwd(u_ref[...].astype(jnp.float32), a, w, feat)
    dpsi = dpsi_ref[...].astype(jnp.float32)
    du, da, dw = features_bwd(dpsi, res, a, w, feat)
    du_ref[...] = du.astype(du_ref.dtype)
    da_ref[0] = da
    dw_ref[0] = dw


def _bwd_impl(st: _MapStatics, u, anchors, omegas, dpsi):
    n, d = u.shape
    f = st.feat
    m = len(f.s_nodes) * f.num_anchors * f.num_prf
    block = st.block_tokens
    P, D = f.num_anchors, f.num_prf
    nb = n // block
    du, da_p, dw_p = pl.pallas_call(
        functools.partial(_bwd_kernel, feat=f),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block, d), lambda i: (i, 0)),
            pl.BlockSpec((P, d), lambda i: (0, 0)),
            pl.BlockSpec((D, d), lambda i: (0, 0)),
            pl.BlockSpec((block, m), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, d), lambda i: (i, 0)),
            pl.BlockSpec((1, P, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, D, d), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), u.dtype),
            jax.ShapeDtypeStruct((nb, P, d), jnp.float32),
            jax.ShapeDtypeStruct((nb, D, d), jnp.float32),
        ],
        interpret=st.interpret,
    )(u, anchors, omegas, dpsi)
    da = jnp.sum(da_p, axis=0).astype(anchors.dtype)
    dw = jnp.sum(dw_p, axis=0).astype(omegas.dtype)
    return du, da, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fmap(st: _MapStatics, u, anchors, omegas):
    return _fwd_impl(st, u, anchors, omegas)


def _fmap_fwd(st: _MapStatics, u, anchors, omegas):
    psi = _fwd_impl(st, u, anchors, omegas)
    return psi, (u, anchors, omegas)


def _fmap_bwd(st: _MapStatics, res, dpsi):
    u, anchors, omegas = res
    return _bwd_impl(st, u, anchors, omegas, dpsi)


_fmap.defvjp(_fmap_fwd, _fmap_bwd)
