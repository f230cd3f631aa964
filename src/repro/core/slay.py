"""SLAY attention — the paper's contribution as a composable JAX module.

Ties together: spherical normalization → anchor/poly features → PRFs →
Gauss-Laguerre-weighted tensor fusion (Ψ) → linear attention reordering.

Usage (functional):

    cfg   = SlayConfig(head_dim=64)
    prm   = slay_init(key, cfg)
    y     = slay_attention(prm, q, k, v, cfg, causal=True)

q: (..., L, H, Dh), k/v: (..., L, Hkv, Dh/dv). Decode via `slay_decode_step`.
"""
from __future__ import annotations

import dataclasses

import jax

from repro.core import linear_attention as la
from repro.core.features import (SlayFeatureConfig, init_feature_params,
                                 slay_features)

# Re-export the feature config under the public name.
SlayConfig = SlayFeatureConfig


def slay_init(key: jax.Array, cfg: SlayConfig) -> dict:
    """Random projections (anchors, omegas). Shared across layers/heads by
    default (paper App. H); pass distinct keys to untie."""
    return init_feature_params(key, cfg)


def slay_attention(params: dict, q, k, v, cfg: SlayConfig, *,
                   causal: bool = True, chunk_size: int = 256,
                   delta: float = 1e-6, use_kernel: bool = False,
                   fuse_features: bool = True,
                   interpret: bool | None = None):
    """Full-sequence SLAY attention (training / prefill).

    ``use_kernel`` selects the Pallas path (differentiable — the kernels
    carry custom VJPs, so this works under ``jax.grad``). With
    ``fuse_features`` (default) the causal path runs the end-to-end
    megakernel on raw q/k: Ψ(Q)/Ψ(K) are computed in VMEM and never hit
    HBM. ``fuse_features=False`` keeps the two-dispatch path (feature
    kernel → HBM → scan kernel) for A/B benchmarking.
    """
    if causal and use_kernel:
        from repro.kernels import ops  # lazy: pallas import
        if fuse_features:
            return ops.slay_fused_attention(
                q, k, v, params, cfg, chunk_size=chunk_size, delta=delta,
                interpret=interpret)
        qf = ops.slay_features(q, params, cfg, interpret=interpret)
        kf = ops.slay_features(k, params, cfg, interpret=interpret)
        return ops.slay_causal_attention(qf, kf, v, chunk_size=chunk_size,
                                         delta=delta, interpret=interpret)
    qf = slay_features(q, params, cfg)
    kf = slay_features(k, params, cfg)
    if causal:
        return la.causal_chunked(qf, kf, v, chunk_size=chunk_size, delta=delta)
    return la.noncausal(qf, kf, v, delta=delta)


def slay_cross_attention(params: dict, q, k, v, cfg: SlayConfig,
                         delta: float = 1e-6):
    """Non-causal cross-attention (e.g. Whisper decoder->encoder)."""
    qf = slay_features(q, params, cfg)
    kf = slay_features(k, params, cfg)
    return la.noncausal(qf, kf, v, delta=delta)


def slay_prefill_state(params: dict, k, v, cfg: SlayConfig) -> la.LinearState:
    kf = slay_features(k, params, cfg)
    return la.prefill_state(kf, v)


def slay_decode_step(params: dict, q, k, v, state: la.LinearState,
                     cfg: SlayConfig, delta: float = 1e-6):
    """One token: q (..., H, Dh), k/v (..., Hkv, Dh/dv)."""
    qf = slay_features(q, params, cfg)
    kf = slay_features(k, params, cfg)
    return la.decode_step(qf, kf, v, state, delta=delta)


@dataclasses.dataclass(frozen=True)
class AttentionSpec:
    """Which attention mechanism a model layer uses (first-class feature)."""

    kind: str = "softmax"  # softmax|slay|yat|yat_spherical|favor|cosformer|elu1
    slay: SlayConfig | None = None
    window: int = 0            # sliding window for local softmax layers
    logit_softcap: float = 0.0
    scale: float = 0.0         # exact score scale; 0 = 1/sqrt(head_dim)
    chunk_size: int = 256
    use_pallas: bool = True
    # With use_pallas: run the end-to-end megakernel (Ψ fused into the
    # attention scan, zero feature HBM traffic) instead of the two-dispatch
    # feature-kernel → scan-kernel pipeline.
    fuse_features: bool = True

    @property
    def is_linear(self) -> bool:
        return self.kind in ("slay", "favor", "cosformer", "elu1")
