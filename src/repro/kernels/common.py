"""Shared helpers for the Pallas kernels: tiling/compile utilities and the
in-VMEM SLAY feature map Ψ with its closed-form VJP.

The feature math is traced *inside* kernel bodies on fp32 VMEM blocks — it
is shared by the standalone feature kernel (`feature_map.py`) and the fused
attention megakernel (`slay_fused.py`) so forward, backward, and the two
call sites can never drift apart (DESIGN.md §3).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

NORM_EPS = 1e-6  # matches repro.core.features.normalize


class FeatureStatics(NamedTuple):
    """Hashable static description of the Ψ pipeline (per head)."""

    s_nodes: tuple      # quadrature nodes s_r
    sqrt_w: tuple       # √w_r
    num_anchors: int    # P
    num_prf: int        # D


def causal_mask(scores):
    """Zero the strict upper triangle of a (T, T) score block."""
    t = scores.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    return jnp.where(rows >= cols, scores, 0.0)


def vmem_scratch(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


def tpu_params():
    """Compiler params for (parallel head, sequential chunk) grids.

    The chunk axis must stay sequential ("arbitrary") so VMEM scratch
    carries state across grid steps; the head axis is embarrassingly
    parallel.
    """
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def col_to_row(x):
    """(T, 1) -> (1, T) without arithmetic: lane-broadcast to (T, 128),
    transpose the aligned tile, keep one row. Mosaic has no relayout for
    a bare (T, 1) column, but transposes (8k, 128)-aligned 32-bit tiles.
    """
    t = x.shape[0]
    return jnp.transpose(jnp.broadcast_to(x, (t, 128)))[:1, :]


def row_to_col(x):
    """(1, T) -> (T, 1); the inverse of :func:`col_to_row`."""
    t = x.shape[1]
    return jnp.transpose(jnp.broadcast_to(x, (128, t)))[:, :1]


def features_fwd(u, a, w, st: FeatureStatics):
    """u (T, d) fp32 -> (Ψ(u) (T, m), intermediates for the VJP).

    normalize → anchor poly φ_p = (ûᵀa)²/√P → PRF
    φ_e = exp(√(2s_r) ωᵀû − s_r)/√D → √w_r (φ_p ⊗ φ_e), concat over r.
    """
    n2 = jnp.sum(u * u, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(n2 + NORM_EPS)                       # (T, 1)
    uh = u * inv                                             # (T, d) unit
    pa = jax.lax.dot_general(uh, a, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (T, P)
    phi_p = (pa * pa) * (1.0 / np.sqrt(st.num_anchors))      # (T, P)
    pw = jax.lax.dot_general(uh, w, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (T, D)
    t = u.shape[0]
    phi_es = []
    chunks = []
    for s, swr in zip(st.s_nodes, st.sqrt_w):
        phi_e = jnp.exp(np.sqrt(2.0 * s) * pw - s) * (1.0 / np.sqrt(st.num_prf))
        phi_es.append(phi_e)                                 # (T, D)
        kron = (phi_p[:, :, None] * phi_e[:, None, :]) * swr
        chunks.append(kron.reshape(t, st.num_anchors * st.num_prf))
    psi = jnp.concatenate(chunks, axis=-1)                   # (T, m)
    return psi, (uh, inv, pa, phi_p, phi_es)


def features_bwd(dpsi, res, a, w, st: FeatureStatics):
    """dΨ (T, m) -> (du (T, d), dA (P, d), dΩ (D, d))."""
    uh, inv, pa, phi_p, phi_es = res
    t = dpsi.shape[0]
    P, D = st.num_anchors, st.num_prf
    dphi_p = jnp.zeros_like(phi_p)                           # (T, P)
    dpw = jnp.zeros((t, D), jnp.float32)
    for r, (s, swr) in enumerate(zip(st.s_nodes, st.sqrt_w)):
        phi_e = phi_es[r]
        # kron = phi_p ⊗ phi_e: split the cotangent. Lane p·D..(p+1)·D of
        # the node's block holds anchor p; 2-D slices only (Mosaic has no
        # in-kernel (T, P·D) -> (T, P, D) relayout).
        base = r * P * D
        m_p = [dpsi[:, base + p * D:base + (p + 1) * D] * swr
               for p in range(P)]                            # P × (T, D)
        dphi_p = dphi_p + jnp.concatenate(
            [jnp.sum(mp * phi_e, axis=-1, keepdims=True) for mp in m_p],
            axis=-1)                                         # (T, P)
        dphi_e = m_p[0] * phi_p[:, 0:1]
        for p in range(1, P):
            dphi_e = dphi_e + m_p[p] * phi_p[:, p:p + 1]     # (T, D)
        # phi_e = exp(√(2s) pw − s)/√D → d pw = √(2s)·phi_e∘dphi_e.
        dpw = dpw + np.sqrt(2.0 * s) * phi_e * dphi_e
    dpa = 2.0 * pa * dphi_p * (1.0 / np.sqrt(P))             # (T, P)
    duh = (jax.lax.dot(dpa, a, preferred_element_type=jnp.float32)
           + jax.lax.dot(dpw, w, preferred_element_type=jnp.float32))
    da = jax.lax.dot_general(dpa, uh, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (P, d)
    dw = jax.lax.dot_general(dpw, uh, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (D, d)
    # û = u·rsqrt(‖u‖²+ε):  du = inv·(dû − û (ûᵀdû)).
    du = inv * (duh - uh * jnp.sum(uh * duh, axis=-1, keepdims=True))
    return du, da, dw
