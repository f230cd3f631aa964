"""Attention dispatch: one entry point, many mechanisms.

The paper's technique (SLAY) is a first-class backend here, selected via
:class:`repro.core.slay.AttentionSpec`. All mechanisms share the model-side
convention q (..., L, H, Dh), k/v (..., L, Hkv, Dh) -> (..., L, H, Dh) and a
uniform decode interface over :class:`AttnCache`.

Backends:
    softmax      — exact quadratic (optionally logit-softcapped / windowed)
    yat          — exact quadratic Yat-kernel attention (paper Eq. 1)
    yat_spherical— exact quadratic spherical Yat (paper Eq. 5)
    slay         — the paper's linear-time mechanism (features + reordering)
    favor | cosformer | elu1 — linear baselines (paper Table 5)

Decode caches:
    softmax/yat* — ring-buffer KV cache (windowed when spec.window > 0)
    linear kinds — constant-size (S, z) running state (the 30x memory win)
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import baselines as bl
from repro.core import kernels as exact
from repro.core import linear_attention as la
from repro.core import slay as slay_mod
from repro.core.slay import AttentionSpec


class AttnCache(NamedTuple):
    """Uniform decode cache. Exactly one of (kv, state) is meaningful.

    kv:    k,v ring buffers (..., S, Hkv, Dh) + write position(s).
    state: linear-attention running state (S = sum psi(k)^T v, z = sum psi(k)).

    ``pos`` counts tokens seen so far. It is *per slot* — shape equal to the
    lead (batch) shape — so a serving slot pool can hold sequences of
    different lengths and a slot overwrite never perturbs its neighbours.
    A scalar pos (rank 0) is still accepted on the decode path for lockstep
    callers where every row shares one position.
    """

    k: jnp.ndarray | None
    v: jnp.ndarray | None
    pos: jnp.ndarray | None          # int32, lead-shaped (or scalar)
    s: jnp.ndarray | None            # (..., Hkv, m, dv) fp32
    z: jnp.ndarray | None            # (..., Hkv, m)     fp32


def init_cache(spec: AttentionSpec, lead_shape, num_kv: int, head_dim: int,
               dv: int, max_len: int, dtype) -> AttnCache:
    if spec.is_linear:
        m = spec.slay.feature_dim if spec.kind == "slay" else _baseline_dim(
            spec, head_dim)
        st = la.init_state(lead_shape, num_kv, m, dv)
        return AttnCache(None, None, jnp.zeros(lead_shape, jnp.int32),
                         st.s, st.z)
    size = min(max_len, spec.window) if spec.window else max_len
    shape = (*lead_shape, size, num_kv, head_dim)
    return AttnCache(jnp.zeros(shape, dtype),
                     jnp.zeros((*lead_shape, size, num_kv, dv), dtype),
                     jnp.zeros(lead_shape, jnp.int32), None, None)


def _baseline_dim(spec: AttentionSpec, head_dim: int) -> int:
    if spec.kind == "favor":
        return 64
    if spec.kind == "cosformer":
        return 2 * head_dim
    return head_dim  # elu1


def full_attention(spec: AttentionSpec, params: dict | None, q, k, v, *,
                   causal: bool = True) -> jnp.ndarray:
    """Full-sequence attention (training / prefill)."""
    if not spec.is_linear and k.shape[-2] != q.shape[-2]:
        # Exact quadratic paths operate head-aligned: broadcast kv over the
        # GQA group (XLA fuses the broadcast into the batched matmul).
        g = q.shape[-2] // k.shape[-2]
        k = jnp.repeat(k, g, axis=-2)
        v = jnp.repeat(v, g, axis=-2)
    if spec.kind == "softmax":
        return exact.softmax_attention(
            q, k, v, causal=causal, logit_softcap=spec.logit_softcap,
            window=spec.window)
    if spec.kind in ("yat", "yat_spherical"):
        return exact.yat_attention(q, k, v, causal=causal,
                                   spherical=spec.kind == "yat_spherical")
    if spec.kind == "slay":
        return slay_mod.slay_attention(
            params, q, k, v, spec.slay, causal=causal,
            chunk_size=spec.chunk_size, use_kernel=spec.use_pallas,
            fuse_features=spec.fuse_features)
    return bl.linear_baseline_attention(
        spec.kind, params, q, k, v, causal=causal, chunk_size=spec.chunk_size)


def cross_attention(spec: AttentionSpec, params: dict | None, q, k, v):
    """Non-causal cross-attention (encoder-decoder)."""
    return full_attention(spec, params, q, k, v, causal=False)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def prefill_cache(spec: AttentionSpec, params: dict | None, k, v,
                  cache: AttnCache, valid=None) -> AttnCache:
    """Absorb a full prompt's keys/values into a fresh decode cache.

    k/v: (..., L, Hkv, *). Linear kinds reduce to the constant-size state;
    KV kinds write the (window-truncated) suffix into the ring buffer.

    ``valid`` (..., L) bool masks a right-padded prompt (length-bucketed
    prefill): invalid positions contribute nothing to the state — linear
    kinds zero their key *features* (exact: the fp32 sums gain literal
    zeros), KV kinds write zeroed k/v rows that ``pos`` (set to the true
    length) keeps outside every later validity horizon.
    """
    L = k.shape[-3]
    lead = k.shape[:-3]
    if valid is None:
        pos = jnp.full(lead, L, jnp.int32)
    else:
        pos = jnp.sum(valid.astype(jnp.int32), axis=-1)
        pos = jnp.broadcast_to(pos, lead)
    if spec.is_linear:
        kf = _features(spec, params, k)
        if valid is not None:
            kf = jnp.where(valid[..., None, None], kf, 0.0)
        st = la.prefill_state(kf, v)
        return AttnCache(None, None, pos, st.s, st.z)
    if valid is not None:
        k = jnp.where(valid[..., None, None], k, 0)
        v = jnp.where(valid[..., None, None], v, 0)
    size = cache.k.shape[-3]
    # Keep the most recent `size` tokens, written at ring positions.
    take = min(L, size)
    ks, vs = k[..., L - take:, :, :], v[..., L - take:, :, :]
    idx = (jnp.arange(take) + (L - take)) % size
    kbuf = cache.k.at[..., idx, :, :].set(ks.astype(cache.k.dtype))
    vbuf = cache.v.at[..., idx, :, :].set(vs.astype(cache.v.dtype))
    return AttnCache(kbuf, vbuf, pos, None, None)


def prefill_chunk(spec: AttentionSpec, params: dict | None, q, k, v,
                  cache: AttnCache) -> tuple[jnp.ndarray, AttnCache]:
    """Absorb one *prompt chunk* into an existing decode cache.

    q (B, Lc, H, Dh), k/v (B, Lc, Hkv, *); ``cache.pos`` is the per-slot
    (B,) count of tokens already absorbed. This is the chunked-prefill
    primitive: feeding a prompt chunk-by-chunk reproduces the whole-prompt
    prefill (linear kinds: exact same fp32 state recurrence; softmax and
    the exact quadratic yat kinds: exact attention against the ring prefix
    + causal intra-chunk scores).

    Supported kinds: every linear kind, softmax (windowed or not), and the
    exact yat kinds (``yat`` / ``yat_spherical`` — same ring-prefix
    continuation, with scores used as nonnegative kernel weights under
    kernel normalization instead of a softmax, DESIGN.md §9).
    """
    B, Lc = q.shape[0], q.shape[1]
    start = cache.pos                                     # (B,)
    if spec.is_linear:
        qf = _features(spec, params, q)
        kf = _features(spec, params, k)
        out, st = la.causal_chunked(
            qf, kf, v, chunk_size=max(min(spec.chunk_size, Lc), 1),
            init_state=la.LinearState(cache.s, cache.z), return_state=True)
        return out, AttnCache(None, None, start + Lc, st.s, st.z)
    if spec.kind not in ("softmax", "yat", "yat_spherical"):
        raise NotImplementedError(
            f"chunked prefill not supported for kind={spec.kind!r}")

    size = cache.k.shape[-3]
    dh = q.shape[-1]
    hkv = k.shape[-2]
    g = q.shape[-2] // hkv
    qg = q.reshape(B, Lc, hkv, g, dh)
    p = start[:, None] + jnp.arange(Lc)[None, :]          # (B, Lc) abs pos
    # Absolute position held by ring slot j *before* this chunk's writes:
    # the newest written position congruent to j (mod size); negative when
    # the slot has never been written.
    j = jnp.arange(size)[None, :]
    a0 = j + ((start[:, None] - 1 - j) // size) * size    # (B, S)
    pre_ok = a0 >= 0
    if spec.window:
        pre_ok = pre_ok & (p[:, :, None] - a0[:, None, :] < spec.window)
    else:
        pre_ok = jnp.broadcast_to(pre_ok[:, None, :], (B, Lc, size))
    rel = jnp.arange(Lc)[:, None] - jnp.arange(Lc)[None, :]
    in_ok = rel >= 0
    if spec.window:
        in_ok = in_ok & (rel < spec.window)
    mask = jnp.concatenate([
        jnp.broadcast_to(pre_ok[:, :, None, None, :], (B, Lc, 1, 1, size)),
        jnp.broadcast_to(in_ok[None, :, None, None, :], (B, Lc, 1, 1, Lc)),
    ], axis=-1)                                           # (B,Lc,1,1,S+Lc)
    k_all = jnp.concatenate([cache.k.astype(q.dtype),
                             k.astype(q.dtype)], axis=1)  # (B,S+Lc,Hkv,Dh)
    v_all = jnp.concatenate([cache.v.astype(q.dtype),
                             v.astype(q.dtype)], axis=1)
    if spec.kind in ("yat", "yat_spherical"):
        # Exact yat continuation: masked positions get zero kernel weight
        # (not -inf — yat normalizes by the weight sum, not a softmax).
        # k_all broadcasts over the Lc query axis via a size-1 dim.
        scores = jnp.where(mask, _yat_scores(spec.kind, qg,
                                             k_all[:, None]), 0.0)
        num = jnp.einsum("blkgs,bskd->blkgd", scores, v_all)
        den = jnp.sum(scores, axis=-1)[..., None] + 1e-6
        y = (num / den).reshape(B, Lc, hkv * g, v.shape[-1])
    else:
        scores = _scaled(spec, jnp.einsum("blkgd,bskd->blkgs", qg, k_all),
                         dh)
        if spec.logit_softcap:
            scores = spec.logit_softcap * jnp.tanh(
                scores / spec.logit_softcap)
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
        probs = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(
            q.dtype)
        y = jnp.einsum("blkgs,bskd->blkgd", probs, v_all)
        y = y.reshape(B, Lc, hkv * g, v.shape[-1])
    # Commit the chunk's keys/values to the ring — only the trailing `size`
    # tokens when the chunk is longer than the ring (duplicate scatter
    # indices would otherwise race).
    take = min(Lc, size)
    b = jnp.arange(B)[:, None]
    idx = (start[:, None] + (Lc - take)
           + jnp.arange(take)[None, :]) % size
    kbuf = cache.k.at[b, idx].set(k[:, Lc - take:].astype(cache.k.dtype))
    vbuf = cache.v.at[b, idx].set(v[:, Lc - take:].astype(cache.v.dtype))
    return y, AttnCache(kbuf, vbuf, start + Lc, None, None)


def _scaled(spec: AttentionSpec, scores, dh: int):
    """Softmax scores times the spec's scale, or over sqrt(dh) when it
    sets none."""
    if spec.scale:
        return scores * jnp.asarray(spec.scale, scores.dtype)
    return scores / jnp.sqrt(jnp.asarray(dh, scores.dtype))


def _yat_scores(kind: str, qg, kb):
    """Exact yat kernel weights (paper Eq. 1 / Eq. 5 with the reference
    eps constants) for grouped queries qg (..., Hkv, G, Dh) against keys
    kb (..., S, Hkv, Dh) -> (..., Hkv, G, S). One source of truth for the
    decode step and the chunked-prefill continuation — callers mask and
    kernel-normalize (weights, not logits: masked-out positions get 0)."""
    if kind == "yat_spherical":
        from repro.core.features import normalize
        x = jnp.einsum("...kgd,...skd->...kgs", normalize(qg),
                       normalize(kb))
        return jnp.square(x) / (2.0 + 1e-3 - 2.0 * x)
    x = jnp.einsum("...kgd,...skd->...kgs", qg, kb)
    q2 = jnp.sum(jnp.square(qg), -1)[..., None]          # (..., Hkv, G, 1)
    k2 = jnp.moveaxis(jnp.sum(jnp.square(kb), -1), -2, -1)[
        ..., :, None, :]                                 # (..., Hkv, 1, S)
    return jnp.square(x) / (jnp.maximum(q2 + k2 - 2 * x, 0.0) + 1e-3)


def decode_step(spec: AttentionSpec, params: dict | None, q, k, v,
                cache: AttnCache, *,
                active=None, layer=None) -> tuple[jnp.ndarray, AttnCache]:
    """One token. q (..., H, Dh), k/v (..., Hkv, *) -> (..., H, dv).

    ``active`` (B,) bool/int masks continuous-batching pool rows: drained
    slots are an exact state passthrough (linear (S, z) and KV ring bytes
    bit-identical, ``pos`` frozen) with a zero output row — the same
    contract as the Pallas decode kernel's active-row mask, so the
    reference path and the kernel path are interchangeable mid-stream.
    Requires per-slot (vector) ``pos`` when given.

    ``layer`` (int32 scalar, linear kinds): ``cache.s``/``cache.z`` hold
    the state of every layer, stacked on a leading axis, and only layer
    ``layer`` is read and updated, in place; the returned cache holds the
    whole stacks. KV kinds ignore it (their leaves are one layer's).
    """
    act = None
    if active is not None:
        if cache.pos is None or cache.pos.ndim == 0:
            raise ValueError("active mask requires per-slot cache.pos")
        act = active.astype(bool)
    if spec.is_linear:
        qf = _features(spec, params, q)
        kf = _features(spec, params, k)
        step = 1 if act is None else act.astype(jnp.int32)
        if spec.use_pallas and qf.ndim == 3:
            # Serving hot path: single fused Pallas dispatch for the pool
            # (jnp oracle off-TPU — identical masked semantics).
            from repro.kernels import ops
            y, s2, z2 = ops.decode_linear_step(qf, kf, v, cache.s, cache.z,
                                               active, layer)
            return y, AttnCache(None, None, cache.pos + step, s2, z2)
        s, z = cache.s, cache.z
        if layer is not None:
            s = jax.lax.dynamic_index_in_dim(s, layer, 0, keepdims=False)
            z = jax.lax.dynamic_index_in_dim(z, layer, 0, keepdims=False)
        y, st = la.decode_step(qf, kf, v, la.LinearState(s, z))
        s2, z2 = st.s, st.z
        if act is not None:
            s2 = jnp.where(act[:, None, None, None], s2, s)
            z2 = jnp.where(act[:, None, None], z2, z)
            y = jnp.where(act[:, None, None], y, 0).astype(y.dtype)
        if layer is not None:
            s2 = jax.lax.dynamic_update_index_in_dim(cache.s, s2, layer, 0)
            z2 = jax.lax.dynamic_update_index_in_dim(cache.z, z2, layer, 0)
        return y, AttnCache(None, None, cache.pos + step, s2, z2)

    size = cache.k.shape[-3]
    ring = cache.pos % size
    n_seen = cache.pos + (1 if act is None else act.astype(jnp.int32))
    if cache.pos.ndim:
        # Per-slot positions (continuous batching): each batch row writes
        # its own ring slot and carries its own validity horizon. The
        # write is a one-hot row select rather than a batch-indexed
        # scatter: elementwise along the slot dim, it partitions cleanly
        # when the pool is slot-sharded (a scatter with explicit batch
        # indices forces GSPMD into all-gather/all-reduce — DESIGN.md §8),
        # and the ring is already fully read by attention each tick, so
        # bandwidth stays O(ring). Drained slots simply don't write.
        kw = k.astype(cache.k.dtype)
        vw = v.astype(cache.v.dtype)
        write = jnp.arange(size)[None, :] == ring[:, None]       # (B, S)
        if act is not None:
            write = write & act[:, None]
        wmask = write[:, :, None, None]               # vs (B, S, Hkv, dh)
        kbuf = jnp.where(wmask, kw[:, None], cache.k)
        vbuf = jnp.where(wmask, vw[:, None], cache.v)
        valid = (jnp.arange(size)[None, :]
                 < jnp.minimum(n_seen, size)[:, None])    # (B, S)
        valid = valid[:, None, None, :]                   # vs (B,Hkv,G,S)
    else:
        kbuf = jax.lax.dynamic_update_index_in_dim(
            cache.k, k.astype(cache.k.dtype), ring, axis=-3)
        vbuf = jax.lax.dynamic_update_index_in_dim(
            cache.v, v.astype(cache.v.dtype), ring, axis=-3)
        # Validity mask: ring slots written so far (inside the window).
        valid = jnp.arange(size) < jnp.minimum(n_seen, size)
    h, dh = q.shape[-2], q.shape[-1]
    hkv, dv = kbuf.shape[-2], vbuf.shape[-1]
    g = h // hkv
    qg = q.reshape(*q.shape[:-2], hkv, g, dh)   # (..., Hkv, G, Dh)
    kb = kbuf.astype(q.dtype)
    vb = vbuf.astype(q.dtype)

    if spec.kind in ("yat", "yat_spherical"):
        scores = jnp.where(valid, _yat_scores(spec.kind, qg, kb), 0.0)
        num = jnp.einsum("...kgs,...skd->...kgd", scores, vb)
        den = jnp.sum(scores, axis=-1)[..., None] + 1e-6
        y = (num / den).reshape(*q.shape[:-1], dv)
        if act is not None:
            y = jnp.where(act[:, None, None], y, 0).astype(y.dtype)
        return y, AttnCache(kbuf, vbuf, n_seen, None, None)

    logits = _scaled(spec, jnp.einsum("...kgd,...skd->...kgs", qg, kb), dh)
    if spec.logit_softcap:
        logits = spec.logit_softcap * jnp.tanh(logits / spec.logit_softcap)
    logits = jnp.where(valid, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(q.dtype)
    y = jnp.einsum("...kgs,...skd->...kgd", probs, vb)
    y = y.reshape(*q.shape[:-1], dv)
    if act is not None:
        y = jnp.where(act[:, None, None], y, 0).astype(y.dtype)
    return y, AttnCache(kbuf, vbuf, n_seen, None, None)


def _features(spec: AttentionSpec, params: dict | None, u):
    if spec.kind == "slay":
        from repro.core.features import slay_features
        return slay_features(u, params, spec.slay)
    if spec.kind == "favor":
        return bl.favor_features(u, params)
    if spec.kind == "elu1":
        return bl.elu1_features(u)
    if spec.kind == "cosformer":
        # Decode: position-dependent reweighting needs absolute positions;
        # we use the large-M limit (cos ~ 1) for the single-token path.
        return jnp.concatenate([jax.nn.relu(u), jnp.zeros_like(u)], axis=-1)
    raise ValueError(spec.kind)
