"""Jit'd public wrappers around the Pallas kernels.

Model code calls these; they translate between the model's
(..., L, H, feat) layout and the kernels' head-major (BH, L, feat) layout,
zero-pad ragged lengths to block multiples (zero features contribute
nothing to the running state, matching ``core.linear_attention``), and run
the jnp reference on non-TPU backends.

``interpret`` semantics (uniform across wrappers):
    None / False — compiled kernel on TPU, jnp reference elsewhere. The
            platform picks the path; the reference is the CPU path, not a
            fallback for a TPU kernel that failed.
    True  — interpret-mode kernel, for the CPU parity tests. Refused on a
            TPU: the chip path only ever runs compiled kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.features import SlayFeatureConfig
from repro.distributed import sharding as shd
from repro.kernels import decode_step as _dk
from repro.kernels import feature_map as _fm
from repro.kernels import ref as _ref
from repro.kernels import slay_fused as _fused
from repro.kernels import slay_scan as _scan


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_kernel(interpret: bool | None) -> bool:
    """Kernel-vs-reference dispatch for the ``interpret`` flag."""
    if interpret:
        if _on_tpu():
            raise ValueError("interpret=True on a TPU: the chip path runs "
                             "compiled kernels only")
        return True
    return _on_tpu()


def _pad_len(L: int, block: int) -> int:
    return (block - L % block) % block


def _headmajor_call(kernel_fn, q, k, v, *, chunk_size: int):
    """Run a head-major (BH, L, feat) kernel from the model layout.

    q (..., L, H, dq), k (..., L, Hkv, dk), v (..., L, Hkv, dv)
    -> (..., L, H, dv). Zero-pads ragged L to a chunk multiple (zero
    features contribute nothing to the running state) and maps q heads
    group-major so q row i reads kv row i // g, matching the kernels'
    index maps.
    """
    *lead, L, H, dq = q.shape
    hkv, dk, dv = k.shape[-2], k.shape[-1], v.shape[-1]
    g = H // hkv
    b = 1
    for x in lead:
        b *= x
    pad = _pad_len(L, chunk_size)
    if pad:
        padding = [(0, 0)] * len(lead) + [(0, pad), (0, 0), (0, 0)]
        q = jnp.pad(q, padding)
        k = jnp.pad(k, padding)
        v = jnp.pad(v, padding)
    Lp = L + pad
    qh = (q.reshape(b, Lp, hkv, g, dq).transpose(0, 2, 3, 1, 4)
          .reshape(b * hkv * g, Lp, dq))
    kh = k.reshape(b, Lp, hkv, dk).transpose(0, 2, 1, 3).reshape(
        b * hkv, Lp, dk)
    vh = v.reshape(b, Lp, hkv, dv).transpose(0, 2, 1, 3).reshape(
        b * hkv, Lp, dv)
    yh = kernel_fn(qh, kh, vh)
    y = (yh.reshape(b, hkv, g, Lp, dv).transpose(0, 3, 1, 2, 4)
         .reshape(*lead, Lp, H, dv))
    return y[..., :L, :, :] if pad else y


def slay_causal_attention(qf: jnp.ndarray, kf: jnp.ndarray, v: jnp.ndarray,
                          *, chunk_size: int = 256, delta: float = 1e-6,
                          interpret: bool | None = None) -> jnp.ndarray:
    """Causal linear attention on fused features.

    qf (..., L, H, m), kf (..., L, Hkv, m), v (..., L, Hkv, dv)
    -> (..., L, H, dv). L may be ragged — zero-padded to a chunk multiple
    (zero features contribute nothing to the running state).
    """
    if not _use_kernel(interpret):
        from repro.core import linear_attention as la
        return la.causal_chunked(qf, kf, v, chunk_size=chunk_size,
                                 delta=delta)
    return _headmajor_call(
        lambda qh, kh, vh: _scan.causal_linear_attention(
            qh, kh, vh, chunk_size=chunk_size, delta=delta,
            interpret=bool(interpret)),
        qf, kf, v, chunk_size=chunk_size)


def slay_fused_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         params: dict, cfg: SlayFeatureConfig, *,
                         chunk_size: int = 256, delta: float = 1e-6,
                         interpret: bool | None = None) -> jnp.ndarray:
    """End-to-end SLAY causal attention on **raw** q/k (no HBM features).

    q (..., L, H, d), k (..., L, Hkv, d), v (..., L, Hkv, dv)
    -> (..., L, H, dv). Ψ is computed inside the megakernel; the only
    per-token HBM traffic is the raw O(L·d) q/k/v reads and the O(L·dv)
    output write. Differentiable (custom VJP); ragged L is zero-padded.

    Runs the jnp reference (features + chunked scan) off-TPU and for
    non-kernelizable feature configs.
    """
    kernelizable = (cfg.poly_kind == "anchor" and cfg.fusion == "tensor")
    if not (_use_kernel(interpret) and kernelizable):
        from repro.core import linear_attention as la
        from repro.core.features import slay_features
        qf = slay_features(q, params, cfg)
        kf = slay_features(k, params, cfg)
        return la.causal_chunked(qf, kf, v, chunk_size=chunk_size,
                                 delta=delta)
    return _headmajor_call(
        lambda qh, kh, vh: _fused.fused_causal_attention(
            qh, kh, vh, params["anchors"], params["omegas"], cfg,
            chunk_size=chunk_size, delta=delta, interpret=bool(interpret)),
        q, k, v, chunk_size=chunk_size)


def decode_linear_step(qf: jnp.ndarray, kf: jnp.ndarray, v: jnp.ndarray,
                       s: jnp.ndarray, z: jnp.ndarray,
                       active: jnp.ndarray | None = None, *,
                       delta: float = 1e-6,
                       interpret: bool | None = None):
    """One-token linear-attention decode step from the *model* layout.

    qf (B, H, m), kf (B, Hkv, m), v (B, Hkv, dv), s (B, Hkv, m, dv) fp32,
    z (B, Hkv, m) fp32 -> (y (B, H, dv), s', z').

    This is the serving decode hot path: the whole slot pool is one fused
    VMEM-resident Pallas dispatch (grid = B·Hkv kv rows, in-place state
    RMW). ``active`` (B,) masks continuous-batching pool rows — drained
    slots skip the state update and MXU readout (y rows zero, (s, z) pass
    through bit-identical), so an idle slot costs only block pipelining.
    Runs the jnp oracle off-TPU, with identical masked semantics.

    Inside a slot-sharded serving pool (``sharding.current_slot_pool``)
    the kernel runs once per shard under ``shard_map`` over the slot dim:
    GSPMD cannot partition a ``pallas_call`` and would otherwise gather
    the whole pool onto every device.
    """
    pool = shd.current_slot_pool()
    if _use_kernel(interpret) and pool is not None and pool[1]:
        mesh, axes = pool
        spec = P(axes[0] if len(axes) == 1 else axes)
        args = (qf, kf, v, s, z) + (() if active is None else (active,))

        def local(*a):
            return _decode_linear_step(*a, delta=delta, interpret=interpret)
        return jax.shard_map(local, mesh=mesh, in_specs=(spec,) * len(args),
                             out_specs=(spec,) * 3, check_vma=False)(*args)
    return _decode_linear_step(qf, kf, v, s, z, active, delta=delta,
                               interpret=interpret)


def _decode_linear_step(qf, kf, v, s, z, active=None, *, delta, interpret):
    B, H, m = qf.shape
    hkv, dv = kf.shape[-2], v.shape[-1]
    g = H // hkv
    qh = qf.reshape(B * hkv * g, m)          # model heads are kv-major
    kh = kf.reshape(B * hkv, m)
    vh = v.reshape(B * hkv, dv)
    sh = s.reshape(B * hkv, m, dv)
    zh = z.reshape(B * hkv, m)
    ah = None
    if active is not None:
        ah = jnp.broadcast_to(active.astype(jnp.int32)[:, None],
                              (B, hkv)).reshape(B * hkv)
    if not _use_kernel(interpret):
        y, s2, z2 = _ref.decode_linear_attention_ref(qh, kh, vh, sh, zh, ah,
                                                     delta=delta)
    else:
        y, s2, z2 = _dk.decode_linear_attention(qh, kh, vh, sh, zh, ah,
                                                delta=delta,
                                                interpret=bool(interpret))
    return (y.reshape(B, H, dv), s2.reshape(B, hkv, m, dv),
            z2.reshape(B, hkv, m))


def slay_features(u: jnp.ndarray, params: dict, cfg: SlayFeatureConfig, *,
                  block_tokens: int = 256,
                  interpret: bool | None = None) -> jnp.ndarray:
    """Fused Ψ(u) over the trailing dim; u (..., d) -> (..., m).

    Ragged token counts are zero-padded to a block multiple and sliced
    (Ψ(0) = 0 for the anchor map, so padding is inert downstream).
    """
    kernelizable = (cfg.poly_kind == "anchor" and cfg.fusion == "tensor")
    *lead, d = u.shape
    n = 1
    for x in lead:
        n *= x
    if not (_use_kernel(interpret) and kernelizable and n > 0):
        return _ref.slay_features_ref(u, params, cfg)
    pad = _pad_len(n, block_tokens)
    uf = u.reshape(n, d)
    if pad:
        uf = jnp.pad(uf, ((0, pad), (0, 0)))
    out = _fm.slay_feature_map(
        uf, params["anchors"], params["omegas"], cfg,
        block_tokens=block_tokens, interpret=bool(interpret))
    if pad:
        out = out[:n]
    return out.reshape(*lead, cfg.feature_dim)
