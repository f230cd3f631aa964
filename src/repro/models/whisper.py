"""Whisper-style encoder-decoder backbone (audio frontend is a stub).

Per the assignment, the conv-mel frontend is NOT modeled: the caller
provides precomputed frame embeddings (B, enc_seq, d_model). The backbone is
faithful: bidirectional encoder self-attention, causal decoder
self-attention, and decoder->encoder cross-attention. Under the SLAY backend
all three linearize (cross-attention uses the plain non-causal reordering,
paper App. I) — self-attn caches are constant-size at decode and the
cross-attention state is a single (m x dv) summary of the whole encoding.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import linear_attention as la
from repro.core.slay import slay_init
from repro.distributed.sharding import constrain
from repro.models import attention as attn
from repro.models.layers import (ParamSpec, axes_of, embed, embed_spec, mlp,
                                 mlp_specs, realize, rmsnorm, rmsnorm_spec,
                                 rope, stack_specs, unembed)
from repro.models.transformer import attn_proj_specs, _merge_cache


def _enc_layer_specs(cfg: ArchConfig) -> dict:
    return {"pre_attn": rmsnorm_spec(cfg.d_model),
            "pre_mlp": rmsnorm_spec(cfg.d_model),
            "attn": attn_proj_specs(cfg),
            "mlp": mlp_specs(cfg.d_model, cfg.d_ff, cfg.gated_mlp)}


def _dec_layer_specs(cfg: ArchConfig) -> dict:
    t = _enc_layer_specs(cfg)
    t["pre_cross"] = rmsnorm_spec(cfg.d_model)
    t["cross"] = attn_proj_specs(cfg)
    return t


def model_specs(cfg: ArchConfig) -> dict:
    return {
        "embed": embed_spec(cfg.vocab_size, cfg.d_model),
        "enc_pos": ParamSpec((cfg.enc_seq, cfg.d_model), (None, "embed"),
                             scale=0.02),
        "enc_layers": stack_specs(_enc_layer_specs(cfg), cfg.enc_layers),
        "enc_norm": rmsnorm_spec(cfg.d_model),
        "dec_layers": stack_specs(_dec_layer_specs(cfg), cfg.num_layers),
        "final_norm": rmsnorm_spec(cfg.d_model),
    }


def init_params(cfg: ArchConfig, key: jax.Array) -> dict:
    k_model, k_slay = jax.random.split(key)
    params = realize(model_specs(cfg), k_model, cfg.activation_dtype)
    if cfg.attn_kind == "slay":
        params["slay"] = slay_init(k_slay, cfg.slay_config())
    return params


def param_axes(cfg: ArchConfig) -> dict:
    axes = axes_of(model_specs(cfg))
    if cfg.attn_kind == "slay":
        axes["slay"] = {"anchors": (None, None), "omegas": (None, None)}
    return axes


_AHEAD = ("act_batch", "act_seq", "act_heads", None)
_ARES = ("act_batch", "act_seq", "act_embed")


def _qkv(lp: dict, x, positions, cfg: ArchConfig, *, use_rope: bool):
    q = constrain(jnp.einsum("bld,dhk->blhk", x, lp["wq"]), _AHEAD)
    k = constrain(jnp.einsum("bld,dhk->blhk", x, lp["wk"]), _AHEAD)
    v = constrain(jnp.einsum("bld,dhk->blhk", x, lp["wv"]), _AHEAD)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def encode(params: dict, cfg: ArchConfig, frame_embeds: jnp.ndarray):
    """frame_embeds (B, T, d) -> encoder output (B, T, d)."""
    x = frame_embeds + params["enc_pos"].astype(frame_embeds.dtype)
    spec = cfg.attention_spec()
    slay_params = jax.lax.stop_gradient(params.get("slay"))
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None]

    def body(x, lp):
        x = constrain(x, _ARES)
        xa = rmsnorm(lp["pre_attn"], x)
        q, k, v = _qkv(lp["attn"], xa, positions, cfg, use_rope=False)
        y = attn.full_attention(spec, slay_params, q, k, v, causal=False)
        x = x + constrain(jnp.einsum("blhk,hkd->bld", y, lp["attn"]["wo"]),
                          _ARES)
        x = x + mlp(lp["mlp"], rmsnorm(lp["pre_mlp"], x), cfg.gated_mlp)
        return x, None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return rmsnorm(params["enc_norm"], x)


def forward(params: dict, cfg: ArchConfig, tokens: jnp.ndarray,
            frame_embeds: jnp.ndarray, *, remat: bool = False):
    """Teacher-forced decoder over encoded audio. Returns (logits, aux=0)."""
    enc = encode(params, cfg, frame_embeds)
    x = embed(params["embed"], tokens).astype(cfg.activation_dtype)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None]
    spec = cfg.attention_spec()
    slay_params = jax.lax.stop_gradient(params.get("slay"))

    def body(x, lp):
        x = constrain(x, _ARES)
        xa = rmsnorm(lp["pre_attn"], x)
        q, k, v = _qkv(lp["attn"], xa, positions, cfg, use_rope=True)
        y = attn.full_attention(spec, slay_params, q, k, v, causal=True)
        x = x + constrain(jnp.einsum("blhk,hkd->bld", y, lp["attn"]["wo"]),
                          _ARES)
        xc = rmsnorm(lp["pre_cross"], x)
        qc = constrain(jnp.einsum("bld,dhk->blhk", xc, lp["cross"]["wq"]),
                       _AHEAD)
        kc = constrain(jnp.einsum("bld,dhk->blhk", enc, lp["cross"]["wk"]),
                       _AHEAD)
        vc = constrain(jnp.einsum("bld,dhk->blhk", enc, lp["cross"]["wv"]),
                       _AHEAD)
        yc = attn.cross_attention(spec, slay_params, qc, kc, vc)
        x = x + constrain(jnp.einsum("blhk,hkd->bld", yc, lp["cross"]["wo"]),
                          _ARES)
        x = x + mlp(lp["mlp"], rmsnorm(lp["pre_mlp"], x), cfg.gated_mlp)
        return x, None

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["dec_layers"])
    x = rmsnorm(params["final_norm"], x)
    return unembed(params["embed"], x), jnp.zeros((), jnp.float32)


def loss_fn(params: dict, cfg: ArchConfig, batch: dict, *,
            remat: bool = False):
    logits, aux = forward(params, cfg, batch["tokens"],
                          batch["frame_embeds"], remat=remat)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None],
                               axis=-1)[..., 0]
    nll = jnp.mean(logz - gold)
    return nll, {"nll": nll, "moe_aux": aux}


class WhisperCache(NamedTuple):
    self_attn: attn.AttnCache        # stacked (num_layers, ...)
    cross_s: jnp.ndarray             # (nl, B, Hkv, m, dv) fp32 (or kv cache)
    cross_z: jnp.ndarray             # (nl, B, Hkv, m)
    pos: jnp.ndarray


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> WhisperCache:
    nl, dh = cfg.num_layers, cfg.resolved_head_dim
    spec = cfg.attention_spec()
    m = (spec.slay.feature_dim if spec.kind == "slay"
         else attn._baseline_dim(spec, dh)) if spec.is_linear else cfg.enc_seq
    if spec.is_linear:
        a = attn.AttnCache(
            None, None, jnp.zeros((nl, batch), jnp.int32),
            jnp.zeros((nl, batch, cfg.num_kv_heads, m, dh), jnp.float32),
            jnp.zeros((nl, batch, cfg.num_kv_heads, m), jnp.float32))
        cs = jnp.zeros((nl, batch, cfg.num_kv_heads, m, dh), jnp.float32)
        cz = jnp.zeros((nl, batch, cfg.num_kv_heads, m), jnp.float32)
    else:
        a = attn.AttnCache(
            jnp.zeros((nl, batch, max_len, cfg.num_kv_heads, dh),
                      cfg.activation_dtype),
            jnp.zeros((nl, batch, max_len, cfg.num_kv_heads, dh),
                      cfg.activation_dtype),
            jnp.zeros((nl, batch), jnp.int32), None, None)
        # Softmax cross: store encoder k/v per layer.
        cs = jnp.zeros((nl, batch, cfg.enc_seq, cfg.num_kv_heads, dh),
                       jnp.float32)
        cz = jnp.zeros((nl, batch, cfg.enc_seq, cfg.num_kv_heads, dh),
                       jnp.float32)
    return WhisperCache(a, cs, cz, jnp.zeros((batch,), jnp.int32))


def reset_slot(cfg: ArchConfig, cache: WhisperCache,
               slot: int) -> WhisperCache:
    """Zero one slot of a pooled decode cache (eviction); see
    transformer.reset_slot."""
    a = jax.tree.map(lambda x: x.at[:, slot].set(0), cache.self_attn)
    return WhisperCache(a, cache.cross_s.at[:, slot].set(0),
                        cache.cross_z.at[:, slot].set(0),
                        cache.pos.at[slot].set(0))


def write_slot(cfg: ArchConfig, cache: WhisperCache, src: WhisperCache,
               slot: int) -> WhisperCache:
    """Install a batch=1 request cache into a pooled cache slot."""
    a = jax.tree.map(lambda dst, s: dst.at[:, slot].set(s[:, 0]),
                     cache.self_attn, src.self_attn)
    return WhisperCache(a, cache.cross_s.at[:, slot].set(src.cross_s[:, 0]),
                        cache.cross_z.at[:, slot].set(src.cross_z[:, 0]),
                        cache.pos.at[slot].set(src.pos[0]))


def slot_state_finite(cfg: ArchConfig, cache: WhisperCache) -> jnp.ndarray:
    """(B,) bool — per-slot finiteness over self-attn state and the cached
    cross-attention summaries; see transformer.slot_state_finite."""
    B = cache.pos.shape[0]
    ok = jnp.ones((B,), bool)
    for leaf in jax.tree.leaves((cache.self_attn, cache.cross_s,
                                 cache.cross_z)):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            continue
        axes = tuple(i for i in range(leaf.ndim) if i != 1)
        ok = ok & jnp.all(jnp.isfinite(leaf), axis=axes)
    return ok


def corrupt_slot(cfg: ArchConfig, cache: WhisperCache,
                 slot: int) -> WhisperCache:
    """NaN one slot's float state (chaos-harness fault injection); see
    transformer.corrupt_slot."""
    def nan_row(x):
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        return x.at[:, slot].set(jnp.nan)

    return WhisperCache(jax.tree.map(nan_row, cache.self_attn),
                        nan_row(cache.cross_s), nan_row(cache.cross_z),
                        cache.pos)


def supports_chunked_prefill(cfg: ArchConfig) -> bool:
    """Encoder-decoder prefill re-encodes audio; no incremental form."""
    return False


def context_capacity(cfg: ArchConfig, max_len: int) -> int | None:
    """Linear decoders carry constant-size state (unbounded context);
    softmax decoders are capped by the self-attention ring."""
    return None if cfg.attention_spec().is_linear else max_len


def supports_masked_prefill(cfg: ArchConfig) -> bool:
    """No ``true_len`` masking for encdec (the audio encoding dominates the
    prefill compile anyway; prompt-length bucketing buys nothing)."""
    return False


def prefill_chunk(params: dict, cfg: ArchConfig, cache: WhisperCache,
                  tokens: jnp.ndarray):
    raise NotImplementedError(
        f"chunked prefill unsupported for {cfg.name}: gate "
        f"family='encdec' — prefill encodes the audio frames whole "
        f"(cross-attention state has no chunk-by-chunk continuation); "
        f"serve encdec via whole-prompt prefill")


def prefill(params: dict, cfg: ArchConfig, tokens: jnp.ndarray,
            frame_embeds: jnp.ndarray, *, max_len: int | None = None,
            true_len=None):
    """Encode audio + absorb the prompt; returns (logits, WhisperCache)."""
    if true_len is not None:
        raise NotImplementedError("true_len-masked prefill unsupported "
                                  "for encdec")
    enc = encode(params, cfg, frame_embeds)
    B, L = tokens.shape
    x = embed(params["embed"], tokens).astype(cfg.activation_dtype)
    positions = jnp.arange(L, dtype=jnp.int32)[None]
    spec = cfg.attention_spec()
    slay_params = params.get("slay")
    cache0 = init_cache(cfg, B, max_len if max_len else L + 64)

    def body(x, scanned):
        lp = scanned["params"]
        x = constrain(x, _ARES)
        xa = rmsnorm(lp["pre_attn"], x)
        q, k, v = _qkv(lp["attn"], xa, positions, cfg, use_rope=True)
        y = attn.full_attention(spec, slay_params, q, k, v, causal=True)
        nac = _merge_cache(scanned["attn"],
                           attn.prefill_cache(spec, slay_params, k, v,
                                              scanned["attn"]))
        x = x + constrain(jnp.einsum("blhk,hkd->bld", y, lp["attn"]["wo"]),
                          _ARES)
        xc = rmsnorm(lp["pre_cross"], x)
        qc = constrain(jnp.einsum("bld,dhk->blhk", xc, lp["cross"]["wq"]),
                       _AHEAD)
        kc = constrain(jnp.einsum("bld,dhk->blhk", enc, lp["cross"]["wk"]),
                       _AHEAD)
        vc = constrain(jnp.einsum("bld,dhk->blhk", enc, lp["cross"]["wv"]),
                       _AHEAD)
        yc = attn.cross_attention(spec, slay_params, qc, kc, vc)
        if spec.is_linear:
            from repro.core.features import slay_features
            kf = (slay_features(kc, slay_params, spec.slay)
                  if spec.kind == "slay" else attn._features(
                      spec, slay_params, kc))
            st = la.prefill_state(kf, vc)
            cs, cz = st.s, st.z
        else:
            cs, cz = kc.astype(jnp.float32), vc.astype(jnp.float32)
        x = x + jnp.einsum("blhk,hkd->bld", yc, lp["cross"]["wo"])
        x = x + mlp(lp["mlp"], rmsnorm(lp["pre_mlp"], x), cfg.gated_mlp)
        return x, {"attn": nac, "cs": cs, "cz": cz}

    x, ys = jax.lax.scan(body, x, {"params": params["dec_layers"],
                                   "attn": cache0.self_attn})
    x = rmsnorm(params["final_norm"], x[:, -1])
    logits = unembed(params["embed"], x)
    return logits[:, None], WhisperCache(ys["attn"], ys["cs"], ys["cz"],
                                         jnp.full((B,), L, jnp.int32))


def decode_step(params: dict, cfg: ArchConfig, cache: WhisperCache,
                tokens: jnp.ndarray, active=None):
    """One decoder token with cached encoder cross-state.

    ``active`` (B,) masks continuous-batching pool slots: drained rows keep
    their self-attention cache and ``pos`` bit-identical (the cross state
    is static — read-only — so it needs no masking).
    """
    x = embed(params["embed"], tokens[:, 0]).astype(cfg.activation_dtype)
    spec = cfg.attention_spec()
    slay_params = params.get("slay")
    pos = cache.pos
    act = None if active is None else active.astype(bool)

    def body(x, scanned):
        lp = scanned["params"]
        xa = rmsnorm(lp["pre_attn"], x)
        q = jnp.einsum("bd,dhk->bhk", xa, lp["attn"]["wq"])
        k = jnp.einsum("bd,dhk->bhk", xa, lp["attn"]["wk"])
        v = jnp.einsum("bd,dhk->bhk", xa, lp["attn"]["wv"])
        p1 = pos[:, None]                     # (B, 1) per-slot positions
        q = rope(q[:, None], p1, cfg.rope_theta)[:, 0]
        k = rope(k[:, None], p1, cfg.rope_theta)[:, 0]
        y, nac = attn.decode_step(spec, slay_params, q, k, v,
                                  scanned["attn"], active=act)
        x = x + jnp.einsum("bhk,hkd->bd", y, lp["attn"]["wo"])
        xc = rmsnorm(lp["pre_cross"], x)
        qc = jnp.einsum("bd,dhk->bhk", xc, lp["cross"]["wq"])
        if spec.is_linear:
            qf = attn._features(spec, slay_params, qc)
            # Read out the fixed cross state (no update — encoder is static).
            st = la.LinearState(scanned["cs"], scanned["cz"])
            hkv = cfg.num_kv_heads
            qg = qf.reshape(*qf.shape[:-2], hkv,
                            qf.shape[-2] // hkv, qf.shape[-1])
            num = jnp.einsum("...kgm,...kmd->...kgd", qg, st.s)
            den = jnp.einsum("...kgm,...km->...kg", qg, st.z)
            yc = (num / (den[..., None] + 1e-6)).reshape(
                *qc.shape[:-1], st.s.shape[-1]).astype(x.dtype)
        else:
            kc, vc = scanned["cs"].astype(x.dtype), scanned["cz"].astype(
                x.dtype)
            dh = qc.shape[-1]
            logits = jnp.einsum("bhd,bshd->bhs", qc, kc) / jnp.sqrt(
                jnp.asarray(dh, x.dtype))
            probs = jax.nn.softmax(logits.astype(jnp.float32), -1
                                   ).astype(x.dtype)
            yc = jnp.einsum("bhs,bshd->bhd", probs, vc)
        x = x + jnp.einsum("bhk,hkd->bd", yc, lp["cross"]["wo"])
        x = x + mlp(lp["mlp"], rmsnorm(lp["pre_mlp"], x), cfg.gated_mlp)
        return x, {"attn": nac}

    x, ys = jax.lax.scan(body, x, {"params": params["dec_layers"],
                                   "attn": cache.self_attn,
                                   "cs": cache.cross_s, "cz": cache.cross_z})
    x = rmsnorm(params["final_norm"], x)
    logits = unembed(params["embed"], x)
    step = 1 if act is None else act.astype(jnp.int32)
    return logits[:, None], WhisperCache(ys["attn"], cache.cross_s,
                                         cache.cross_z, pos + step)
