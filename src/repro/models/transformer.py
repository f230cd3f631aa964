"""Unified decoder LM covering the dense / MoE / SSM / hybrid families.

One parameterized implementation serves phi4-mini, qwen3, granite (MQA),
gemma2 (local/global alternation + softcaps), hymba (parallel attn+mamba),
mamba2 (attention-free), phi3.5-moe and grok-1 (top-2 MoE), and internvl2
(vision-prefix stub). Layers are *stacked* and driven by ``lax.scan`` so the
HLO stays O(1) in depth — essential for 64-80 layer dry-run compiles — and
so XLA's latency-hiding scheduler can overlap layer-i compute with the
weight all-gathers of layer i+1 under FSDP.

The paper's SLAY mechanism is the default attention backend
(cfg.attn_kind == "slay"); every mechanism in repro.models.attention can be
swapped in via config without touching model code.
"""
from __future__ import annotations

import itertools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ArchConfig
from repro.core.slay import slay_init
from repro.distributed.sharding import constrain
from repro.models import attention as attn
from repro.models import ssm
from repro.models.layers import (ParamSpec, axes_of, embed, embed_spec,
                                 mlp, mlp_specs, moe, moe_specs, realize,
                                 rmsnorm, rmsnorm_spec, rope, stack_specs,
                                 unembed)


# ---------------------------------------------------------------------------
# Parameter templates
# ---------------------------------------------------------------------------


def attn_proj_specs(cfg: ArchConfig) -> dict:
    dh = cfg.resolved_head_dim
    t = {
        "wq": ParamSpec((cfg.d_model, cfg.num_heads, dh),
                        ("embed", "heads", None)),
        "wk": ParamSpec((cfg.d_model, cfg.num_kv_heads, dh),
                        ("embed", "kv_heads", None)),
        "wv": ParamSpec((cfg.d_model, cfg.num_kv_heads, dh),
                        ("embed", "kv_heads", None)),
        "wo": ParamSpec((cfg.num_heads, dh, cfg.d_model),
                        ("heads", None, "embed")),
    }
    if cfg.qk_norm:
        t["q_norm"] = ParamSpec((dh,), (None,), init="zeros")
        t["k_norm"] = ParamSpec((dh,), (None,), init="zeros")
    return t


def layer_specs(cfg: ArchConfig) -> dict:
    d = cfg.d_model
    if cfg.layer_types:
        # What every layer of a mixed stack has; its mixer's weights are
        # stacked by kind (_mixed_specs).
        return {"pre_mix": rmsnorm_spec(d), "pre_mlp": rmsnorm_spec(d),
                "mlp": mlp_specs(d, cfg.d_ff, cfg.gated_mlp)}
    if cfg.family == "ssm":
        return {"pre": rmsnorm_spec(d),
                "ssd": ssm.ssd_specs(d, cfg.ssm_state, cfg.ssm_expand,
                                     cfg.ssm_head_dim, cfg.ssm_ngroups,
                                     cfg.ssm_conv_width)}
    t = {"pre_attn": rmsnorm_spec(d), "pre_mlp": rmsnorm_spec(d),
         "attn": attn_proj_specs(cfg)}
    if cfg.moe_experts:
        t["moe"] = moe_specs(d, cfg.d_ff, cfg.moe_experts)
    else:
        t["mlp"] = mlp_specs(d, cfg.d_ff, cfg.gated_mlp)
    if cfg.family == "hybrid":
        t["ssd"] = ssm.ssd_specs(d, cfg.ssm_state, cfg.ssm_expand,
                                 cfg.ssm_head_dim, cfg.ssm_ngroups,
                                 cfg.ssm_conv_width)
    return t


def model_specs(cfg: ArchConfig) -> dict:
    specs = {
        "embed": embed_spec(cfg.vocab_size, cfg.d_model),
        "final_norm": rmsnorm_spec(cfg.d_model),
        "layers": stack_specs(layer_specs(cfg), cfg.num_layers),
    }
    if cfg.layer_types:
        specs.update(_mixed_specs(cfg))
    if not cfg.tie_embeddings:
        specs["unembed"] = ParamSpec((cfg.vocab_size, cfg.d_model),
                                     ("vocab", "embed"), scale=1.0)
    return specs


def init_params(cfg: ArchConfig, key: jax.Array) -> dict:
    k_model, k_slay = jax.random.split(key)
    dtype = cfg.activation_dtype
    params = realize(model_specs(cfg), k_model, dtype)
    if cfg.family != "ssm" and cfg.attn_kind == "slay":
        params["slay"] = slay_init(k_slay, cfg.slay_config())
    elif cfg.family != "ssm" and cfg.attn_kind == "favor":
        from repro.core.baselines import favor_init
        params["slay"] = favor_init(k_slay, cfg.resolved_head_dim)
    return params


def param_axes(cfg: ArchConfig) -> dict:
    axes = axes_of(model_specs(cfg))
    if cfg.family != "ssm" and cfg.attn_kind in ("slay", "favor"):
        # Random projections: tiny, replicated.
        if cfg.attn_kind == "slay":
            axes["slay"] = {"anchors": (None, None), "omegas": (None, None)}
        else:
            axes["slay"] = {"proj": (None, None)}
    return axes


def _layer_kinds(cfg: ArchConfig) -> np.ndarray:
    """Per-layer flag: 1 = local sliding-window softmax, 0 = primary attn."""
    if cfg.local_global_period and cfg.local_window:
        idx = np.arange(cfg.num_layers)
        return (idx % cfg.local_global_period
                != cfg.local_global_period - 1).astype(np.int32)
    return np.zeros(cfg.num_layers, np.int32)


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------


def _attn_full(cfg: ArchConfig, lp: dict, slay_params, x, positions,
               is_local):
    """One layer's attention over the full sequence."""
    xa = rmsnorm(lp["pre_attn"], x)
    _ahead = ("act_batch", "act_seq", "act_heads", None)
    q = constrain(jnp.einsum("bld,dhk->blhk", xa, lp["attn"]["wq"]), _ahead)
    k = constrain(jnp.einsum("bld,dhk->blhk", xa, lp["attn"]["wk"]), _ahead)
    v = constrain(jnp.einsum("bld,dhk->blhk", xa, lp["attn"]["wv"]), _ahead)
    if cfg.qk_norm:
        q = rmsnorm(lp["attn"]["q_norm"], q)
        k = rmsnorm(lp["attn"]["k_norm"], k)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    spec_g = cfg.attention_spec(local=False)
    if cfg.local_global_period and cfg.local_window:
        spec_l = cfg.attention_spec(local=True)
        y = jax.lax.cond(
            is_local == 1,
            lambda: attn.full_attention(spec_l, None, q, k, v, causal=True),
            lambda: attn.full_attention(spec_g, slay_params, q, k, v,
                                        causal=True))
    else:
        y = attn.full_attention(spec_g, slay_params, q, k, v, causal=True)
    y = constrain(y, _ahead)
    return constrain(jnp.einsum("blhk,hkd->bld", y, lp["attn"]["wo"]),
                     ("act_batch", "act_seq", "act_embed"))


def _layer_fwd(cfg: ArchConfig, slay_params, carry, scanned):
    x, aux = carry
    lp, is_local, positions = scanned["params"], scanned["kind"], scanned["pos"]
    if cfg.family == "ssm":
        x = x + ssm.ssd_forward(
            lp["ssd"], rmsnorm(lp["pre"], x), d_state=cfg.ssm_state,
            expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim,
            ngroups=cfg.ssm_ngroups, conv_width=cfg.ssm_conv_width,
            chunk_size=cfg.chunk_size)
        return (x, aux), None
    x = constrain(x, ("act_batch", "act_seq", "act_embed"))
    a = _attn_full(cfg, lp, slay_params, x, positions, is_local)
    # Named for the save-collectives remat policy (§Perf): saving the
    # post-all-reduce tensors lets the backward recompute skip re-running
    # the forward TP collectives.
    a = checkpoint_name(a, "attn_out")
    if cfg.family == "hybrid":
        # Hymba: parallel attention + mamba heads on the same input, averaged.
        m = ssm.ssd_forward(
            lp["ssd"], rmsnorm(lp["pre_attn"], x), d_state=cfg.ssm_state,
            expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim,
            ngroups=cfg.ssm_ngroups, conv_width=cfg.ssm_conv_width,
            chunk_size=cfg.chunk_size)
        a = 0.5 * (a + m)
    x = x + a
    xm = rmsnorm(lp["pre_mlp"], x)
    if cfg.moe_experts:
        y, moe_aux = moe(lp["moe"], xm, cfg.moe_experts, cfg.moe_top_k)
        aux = aux + moe_aux
    else:
        y = mlp(lp["mlp"], xm, cfg.gated_mlp)
    y = checkpoint_name(y, "mlp_out")
    return (x + y, aux), None


def forward(params: dict, cfg: ArchConfig, tokens: jnp.ndarray, *,
            patch_embeds=None, remat: bool = False) -> tuple[jnp.ndarray,
                                                             jnp.ndarray]:
    """tokens (B, Lt) -> logits (B, L, V), aux loss. Vision prefix embeds
    (B, P, d) are concatenated ahead of the token embeddings (stub frontend).
    """
    if cfg.layer_types:
        return _mixed_forward(params, cfg, tokens)
    x = embed(params["embed"], tokens).astype(cfg.activation_dtype)
    if patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
    x = constrain(x, ("act_batch", "act_seq", "act_embed"))
    L = x.shape[1]
    positions = jnp.arange(L, dtype=jnp.int32)[None, :]
    slay_params = jax.lax.stop_gradient(params.get("slay"))
    kinds = jnp.asarray(_layer_kinds(cfg))
    pos_b = jnp.broadcast_to(positions, (cfg.num_layers, *positions.shape))

    def body(carry, scanned):
        return _layer_fwd(cfg, slay_params, carry, scanned)

    if remat:
        # remat may be True/"nothing" (recompute everything) or
        # "save_collectives" (keep post-all-reduce layer outputs so the
        # backward pass does not re-run the forward TP collectives).
        if remat == "save_collectives":
            policy = jax.checkpoint_policies.save_only_these_names(
                "attn_out", "mlp_out")
        else:
            policy = jax.checkpoint_policies.nothing_saveable
        body = jax.checkpoint(body, policy=policy)
    (x, aux), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)),
        {"params": params["layers"], "kind": kinds, "pos": pos_b})
    x = rmsnorm(params["final_norm"], x)
    table = params.get("unembed", params["embed"])
    logits = unembed(table, x, cfg.final_logit_softcap)
    logits = constrain(logits, ("act_batch", "act_seq", "act_vocab"))
    return logits, aux


def loss_fn(params: dict, cfg: ArchConfig, batch: dict, *,
            remat: bool = False) -> tuple[jnp.ndarray, dict]:
    """Next-token cross-entropy (+ MoE load-balance aux)."""
    logits, aux = forward(params, cfg, batch["tokens"],
                          patch_embeds=batch.get("patch_embeds"),
                          remat=remat)
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:   # vision prefix: text tail only
        logits = logits[:, logits.shape[1] - labels.shape[1]:]
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = jnp.mean(logz - gold)
    total = nll + 0.01 * aux
    return total, {"nll": nll, "moe_aux": aux}


# ---------------------------------------------------------------------------
# Serving: prefill + decode with stacked per-layer caches
# ---------------------------------------------------------------------------


class DecodeCache(NamedTuple):
    """Stacked (num_layers leading) per-layer decode state.

    ``pos`` is per slot — (B,) int32 — so a serving slot pool can hold
    sequences of different lengths (continuous batching): each slot's ring
    writes, validity masks, and RoPE phases advance independently.

    ``pages`` is None for ordinary caches. For a *paged* serving pool
    (DESIGN.md §11) it holds a ``serving.pages.PageState`` and the KV ring
    leaves are page-indexed ``(nl, P, page, Hkv, dh)`` instead of
    slot-indexed ``(nl, S, kv_len, Hkv, dh)``; the decode step gathers
    each slot's pages to the dense ring layout, runs the unchanged
    attention update, and scatters back — byte-identical by construction.
    """

    attn: attn.AttnCache | None
    ssm: ssm.SsmState | None
    pos: jnp.ndarray                # (B,) int32 tokens seen per slot
    pages: object | None = None     # serving.pages.PageState when paged


def _pages_mod():
    # Lazy: keeps models -> serving import edges out of module init time
    # (serving imports models.api; the cycle only resolves at call time).
    from repro.serving import pages
    return pages


def _needs_kv(cfg: ArchConfig, max_len: int) -> bool:
    spec = cfg.attention_spec()
    mixed_local = bool(cfg.local_global_period and cfg.local_window)
    return (not spec.is_linear) or mixed_local


def supports_paging(cfg: ArchConfig) -> bool:
    """Whether the pooled decode cache can page its KV rings (§11).

    True only where paging buys anything: a non-windowed exact quadratic
    ring (softmax / exact yat), which is the one state whose per-slot size
    scales with context. Constant-state kinds (linear SLAY — a single
    (S, z) accumulator) and SSM/hybrid scan carries are O(1) per slot, so
    they bypass paging entirely; windowed rings are already bounded by the
    window and wrap in place.
    """
    if cfg.family in ("ssm", "hybrid", "encdec") or cfg.layer_types:
        return False
    if cfg.local_window or cfg.frontend:
        return False
    return not cfg.attention_spec().is_linear


def context_capacity(cfg: ArchConfig, max_len: int) -> int | None:
    """Max context rows (prefix + prompt + decode budget) a slot can hold.

    ``None`` means unbounded: constant-state decode (linear kinds, SSM)
    carries O(1) state regardless of context, and windowed rings wrap
    exactly — only a *non-windowed quadratic* ring hard-caps admission at
    its ``max_len`` allocation. This is what lets oversized linear-vision
    prompts admit (absorbed chunk-by-chunk) instead of being rejected.
    """
    if cfg.family == "ssm" or (cfg.layer_types
                               and "attention" not in cfg.layer_types):
        return None
    if cfg.attention_spec().is_linear or cfg.local_window:
        return None
    return max_len


def init_cache(cfg: ArchConfig, batch: int, max_len: int, *,
               page_size: int = 0, num_pages: int = 0,
               shards: int = 1) -> DecodeCache:
    """Allocate the decode cache (union layout when layers are mixed).

    With ``page_size > 0`` (and a config that :func:`supports_paging`) the
    KV ring leaves are allocated page-indexed — ``(nl, num_pages,
    page_size, Hkv, dh)`` physical pages shared by all ``batch`` slots —
    and a fresh all-free ``PageState`` rides in ``cache.pages``.
    """
    if cfg.layer_types:
        return _mixed_init_cache(cfg, batch, max_len)
    nl = cfg.num_layers
    dh = cfg.resolved_head_dim
    dtype = cfg.activation_dtype
    a_cache = None
    s_cache = None
    page_state = None
    if cfg.family != "ssm":
        spec = cfg.attention_spec()
        kv_len = (min(max_len, cfg.local_window)
                  if cfg.local_window else max_len)
        m = spec.slay.feature_dim if spec.kind == "slay" else \
            attn._baseline_dim(spec, dh)
        lin_needed = spec.is_linear
        paged = page_size > 0 and supports_paging(cfg)
        if paged:
            if kv_len % page_size:
                raise ValueError(
                    f"page_size={page_size} must divide kv_len={kv_len}")
            lp = kv_len // page_size
            np_ = num_pages if num_pages else batch * lp
            k = jnp.zeros((nl, np_, page_size, cfg.num_kv_heads, dh), dtype)
            v = jnp.zeros((nl, np_, page_size, cfg.num_kv_heads, dh), dtype)
            page_state = _pages_mod().init_state(batch, np_, lp,
                                                 shards=shards)
        else:
            k = jnp.zeros((nl, batch, kv_len, cfg.num_kv_heads, dh), dtype) \
                if _needs_kv(cfg, max_len) else None
            v = jnp.zeros((nl, batch, kv_len, cfg.num_kv_heads, dh), dtype) \
                if _needs_kv(cfg, max_len) else None
        s = jnp.zeros((nl, batch, cfg.num_kv_heads, m, dh), jnp.float32) \
            if lin_needed else None
        z = jnp.zeros((nl, batch, cfg.num_kv_heads, m), jnp.float32) \
            if lin_needed else None
        a_cache = attn.AttnCache(k, v, jnp.zeros((nl, batch), jnp.int32),
                                 s, z)
    if cfg.family in ("ssm", "hybrid"):
        st = ssm.ssd_init_state((batch,), cfg.d_model, cfg.ssm_state,
                                cfg.ssm_expand, cfg.ssm_head_dim,
                                cfg.ssm_ngroups, cfg.ssm_conv_width)
        s_cache = ssm.SsmState(jnp.zeros((nl, *st.h.shape), jnp.float32),
                               jnp.zeros((nl, *st.conv.shape), jnp.float32))
    return DecodeCache(a_cache, s_cache, jnp.zeros((batch,), jnp.int32),
                       page_state)


def _state_passthrough(new, old, act):
    """jnp.where-select ``new`` vs ``old`` state leaves on the (B,) active
    mask — the reference-path analogue of the Pallas kernel's masked
    state RMW (drained slots keep their bytes bit-identical)."""
    if act is None:
        return new

    def sel(n, o):
        a = act.reshape(act.shape + (1,) * (n.ndim - 1))
        return jnp.where(a, n, o)

    return jax.tree.map(sel, new, old)


def decode_step(params: dict, cfg: ArchConfig, cache: DecodeCache,
                tokens: jnp.ndarray,
                active: jnp.ndarray | None = None
                ) -> tuple[jnp.ndarray, DecodeCache]:
    """One autoregressive step. tokens (B, 1) -> logits (B, 1, V).

    ``active`` (B,) bool/int is the continuous-batching slot mask: drained
    slots pass their whole per-layer state through unchanged (attention
    caches, SSM carries, per-slot ``pos``) and contribute zero attention/
    SSM output — the jitted pool dispatch stays one fixed-shape call while
    idle slots stop advancing. Their logits rows are meaningless and must
    be masked by the caller (the engine samples only active rows).

    Linear attention's (S, z) state and the K and V rings ride the layer
    scan's carry as whole layer stacks, not its scanned ``xs``/``ys``:
    each layer updates its own entry in place
    (``attention.decode_step(layer=...)``; a ring takes one row per live
    slot), so the pool's state is never sliced out, written back or
    copied per tick. SSM carries, and the rings of a paged pool or of a
    local/global stack (whose ``lax.cond`` would copy a passed-through
    stack whole), stay scanned one layer's at a time; a mixed stack
    carries every kind in place (:func:`_mixed_decode_step`).
    """
    if cfg.layer_types:
        return _mixed_decode_step(params, cfg, cache, tokens, active)
    x = embed(params["embed"], tokens[:, 0]).astype(cfg.activation_dtype)
    pos = cache.pos
    act = None if active is None else active.astype(bool)
    slay_params = params.get("slay")
    kinds = jnp.asarray(_layer_kinds(cfg))
    local_global = bool(cfg.local_global_period and cfg.local_window)
    # The attention-cache leaves that ride the carry as whole stacks.
    carried = ()
    if cache.attn is not None and cache.attn.s is not None:
        carried += ("s", "z")
    if (cache.attn is not None and cache.attn.k is not None
            and cache.pages is None and not local_global):
        carried += ("k", "v")

    def body(carry, scanned):
        x, state = carry
        lp = scanned["params"]
        is_local = scanned["kind"]
        layer = scanned.get("layer")
        new = {}
        if cfg.family == "ssm":
            y, st = ssm.ssd_decode_step(
                lp["ssd"], rmsnorm(lp["pre"], x), scanned["ssm"],
                d_state=cfg.ssm_state, expand=cfg.ssm_expand,
                head_dim=cfg.ssm_head_dim, ngroups=cfg.ssm_ngroups,
                conv_width=cfg.ssm_conv_width)
            new["ssm"] = _state_passthrough(st, scanned["ssm"], act)
            if act is not None:
                y = jnp.where(act[:, None], y, 0).astype(y.dtype)
            return (x + y, state), new
        xa = rmsnorm(lp["pre_attn"], x)
        q = jnp.einsum("bd,dhk->bhk", xa, lp["attn"]["wq"])
        k = jnp.einsum("bd,dhk->bhk", xa, lp["attn"]["wk"])
        v = jnp.einsum("bd,dhk->bhk", xa, lp["attn"]["wv"])
        if cfg.qk_norm:
            q = rmsnorm(lp["attn"]["q_norm"], q)
            k = rmsnorm(lp["attn"]["k_norm"], k)
        p1 = pos[:, None]                     # (B, 1) per-slot positions
        q = rope(q[:, None], p1, cfg.rope_theta)[:, 0]
        k = rope(k[:, None], p1, cfg.rope_theta)[:, 0]
        spec_g = cfg.attention_spec(local=False)
        ac = scanned["attn"]
        if carried:
            ac = ac._replace(**dict(zip(carried, state)))
        if local_global:
            spec_l = cfg.attention_spec(local=True)

            def _local():
                y, c = attn.decode_step(spec_l, None, q, k, v, ac,
                                        active=act)
                return y, _merge_cache(ac, c)

            def _global():
                y, c = attn.decode_step(spec_g, slay_params, q, k, v, ac,
                                        active=act, layer=layer)
                return y, _merge_cache(ac, c)

            y, nac = jax.lax.cond(is_local == 1, _local, _global)
        elif cache.pages is not None:
            # Paged pool (§11): gather this layer's pages to the dense
            # (B, kv_len, Hkv, dh) ring the unpaged path uses, run the
            # unchanged attention update on it, scatter owned pages back.
            # `cache.pages` enters the scan as a constant (closure).
            pg = _pages_mod()
            dense = ac._replace(k=pg.gather_ring(ac.k, cache.pages),
                                v=pg.gather_ring(ac.v, cache.pages))
            y, nd = attn.decode_step(spec_g, slay_params, q, k, v, dense,
                                     active=act)
            nac = nd._replace(
                k=pg.scatter_ring(ac.k, nd.k, cache.pages),
                v=pg.scatter_ring(ac.v, nd.v, cache.pages))
        else:
            y, nac = attn.decode_step(spec_g, slay_params, q, k, v, ac,
                                      active=act, layer=layer)
        a = jnp.einsum("bhk,hkd->bd", y, lp["attn"]["wo"])
        if carried:
            state = tuple(getattr(nac, n) for n in carried)
            nac = nac._replace(**dict.fromkeys(carried))
        new["attn"] = nac
        if cfg.family == "hybrid":
            m, st = ssm.ssd_decode_step(
                lp["ssd"], xa, scanned["ssm"], d_state=cfg.ssm_state,
                expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim,
                ngroups=cfg.ssm_ngroups, conv_width=cfg.ssm_conv_width)
            if act is not None:
                m = jnp.where(act[:, None], m, 0).astype(m.dtype)
            a = 0.5 * (a + m)
            new["ssm"] = _state_passthrough(st, scanned["ssm"], act)
        x = x + a
        xm = rmsnorm(lp["pre_mlp"], x)
        if cfg.moe_experts:
            y2, _ = moe(lp["moe"], xm[:, None, :], cfg.moe_experts,
                        cfg.moe_top_k)
            y2 = y2[:, 0]
        else:
            y2 = mlp(lp["mlp"], xm, cfg.gated_mlp)
        return (x + y2, state), new

    scanned = {"params": params["layers"], "kind": kinds}
    state = None
    if cache.attn is not None:
        scanned["attn"] = cache.attn
    if carried:
        state = tuple(getattr(cache.attn, n) for n in carried)
        scanned["attn"] = cache.attn._replace(**dict.fromkeys(carried))
        scanned["layer"] = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    if cache.ssm is not None:
        scanned["ssm"] = cache.ssm
    (x, state), new = jax.lax.scan(body, (x, state), scanned)
    if carried:
        new["attn"] = new["attn"]._replace(**dict(zip(carried, state)))
    x = rmsnorm(params["final_norm"], x)
    table = params.get("unembed", params["embed"])
    logits = unembed(table, x, cfg.final_logit_softcap)
    step = 1 if act is None else act.astype(jnp.int32)
    return logits[:, None, :], DecodeCache(
        new.get("attn"), new.get("ssm"), pos + step, cache.pages)


def supports_masked_prefill(cfg: ArchConfig) -> bool:
    """Whether prefill accepts ``true_len`` (length-bucketed right-padding).

    Exact for pure-attention decoders: causality keeps the valid prefix's
    activations byte-identical under right padding, and the cache masks pad
    contributions out (zero key features / zero KV rows outside the ``pos``
    horizon). SSM/hybrid carries decay through pad steps (no exact masked
    form) and windowed KV rings would evict in-window history, so those
    fall back to per-length compilation.
    """
    return cfg.family not in ("ssm", "hybrid", "encdec") \
        and not cfg.local_window and not cfg.layer_types


def prefill(params: dict, cfg: ArchConfig, tokens: jnp.ndarray, *,
            patch_embeds=None, max_len: int | None = None,
            true_len: jnp.ndarray | None = None
            ) -> tuple[jnp.ndarray, DecodeCache]:
    """Process a full prompt; return last-token logits + a primed cache.

    ``max_len`` sizes the KV ring buffer exactly when given (so a pooled
    serving cache and a per-request prefill cache agree shape-for-shape);
    when omitted, prompt + 64 tokens of decode headroom. Linear/SSM state
    paths are length-independent either way. Implemented as forward for
    logits + per-layer cache construction in a second scan (keeps the hot
    forward path allocation-free).

    ``true_len`` (B,) int32 (traced) marks the real sequence length of a
    right-padded prompt — the length-bucketed serving fallback compiles
    once per pow-2 bucket instead of once per distinct prompt length.
    Logits are read at ``true_len - 1`` and the cache excludes every pad
    position exactly (see :func:`supports_masked_prefill`).
    """
    if true_len is not None and not supports_masked_prefill(cfg):
        raise NotImplementedError(
            f"true_len-masked prefill unsupported for {cfg.name} "
            f"(family={cfg.family}, local_window={cfg.local_window})")
    if cfg.layer_types:
        cache = init_cache(cfg, tokens.shape[0],
                           max_len if max_len else tokens.shape[1] + 64)
        return prefill_chunk(params, cfg, cache, tokens)
    B = tokens.shape[0]
    x = embed(params["embed"], tokens).astype(cfg.activation_dtype)
    if patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
    L = x.shape[1]
    positions = jnp.arange(L, dtype=jnp.int32)[None, :]
    valid = None if true_len is None else \
        positions < true_len[:, None]                     # (B, L)
    slay_params = params.get("slay")
    kinds = jnp.asarray(_layer_kinds(cfg))
    cache0 = init_cache(cfg, B, max_len if max_len else L + 64)

    def body(carry, scanned):
        x, _aux = carry
        lp, is_local = scanned["params"], scanned["kind"]
        new = {}
        if cfg.family == "ssm":
            xn = rmsnorm(lp["pre"], x)
            y = ssm.ssd_forward(
                lp["ssd"], xn, d_state=cfg.ssm_state, expand=cfg.ssm_expand,
                head_dim=cfg.ssm_head_dim, ngroups=cfg.ssm_ngroups,
                conv_width=cfg.ssm_conv_width, chunk_size=cfg.chunk_size)
            new["ssm"] = _ssd_prefill_state(cfg, lp["ssd"], xn)
            return ((x + y, _aux), new)
        x = constrain(x, ("act_batch", "act_seq", "act_embed"))
        xa = rmsnorm(lp["pre_attn"], x)
        _ahead = ("act_batch", "act_seq", "act_heads", None)
        q = constrain(jnp.einsum("bld,dhk->blhk", xa, lp["attn"]["wq"]),
                      _ahead)
        k = constrain(jnp.einsum("bld,dhk->blhk", xa, lp["attn"]["wk"]),
                      _ahead)
        v = constrain(jnp.einsum("bld,dhk->blhk", xa, lp["attn"]["wv"]),
                      _ahead)
        if cfg.qk_norm:
            q = rmsnorm(lp["attn"]["q_norm"], q)
            k = rmsnorm(lp["attn"]["k_norm"], k)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        spec_g = cfg.attention_spec(local=False)
        ac = scanned["attn"]
        if cfg.local_global_period and cfg.local_window:
            spec_l = cfg.attention_spec(local=True)

            def _local():
                y = attn.full_attention(spec_l, None, q, k, v)
                c = attn.prefill_cache(spec_l, None, k, v, ac, valid)
                return y, _merge_cache(ac, c)

            def _global():
                y = attn.full_attention(spec_g, slay_params, q, k, v)
                c = attn.prefill_cache(spec_g, slay_params, k, v, ac, valid)
                return y, _merge_cache(ac, c)

            y, nac = jax.lax.cond(is_local == 1, _local, _global)
        else:
            y = attn.full_attention(spec_g, slay_params, q, k, v)
            nac = _merge_cache(ac, attn.prefill_cache(spec_g, slay_params,
                                                      k, v, ac, valid))
        y = constrain(y, _ahead)
        a = constrain(jnp.einsum("blhk,hkd->bld", y, lp["attn"]["wo"]),
                      ("act_batch", "act_seq", "act_embed"))
        new["attn"] = nac
        if cfg.family == "hybrid":
            m = ssm.ssd_forward(
                lp["ssd"], xa, d_state=cfg.ssm_state, expand=cfg.ssm_expand,
                head_dim=cfg.ssm_head_dim, ngroups=cfg.ssm_ngroups,
                conv_width=cfg.ssm_conv_width, chunk_size=cfg.chunk_size)
            a = 0.5 * (a + m)
            new["ssm"] = _ssd_prefill_state(cfg, lp["ssd"], xa)
        x = x + a
        xm = rmsnorm(lp["pre_mlp"], x)
        if cfg.moe_experts:
            y2, moe_aux = moe(lp["moe"], xm, cfg.moe_experts, cfg.moe_top_k)
            _aux = _aux + moe_aux
        else:
            y2 = mlp(lp["mlp"], xm, cfg.gated_mlp)
        return ((x + y2, _aux), new)

    scanned = {"params": params["layers"], "kind": kinds}
    if cache0.attn is not None:
        scanned["attn"] = cache0.attn
    if cache0.ssm is not None:
        scanned["ssm"] = cache0.ssm
    (x, _), new = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), scanned)
    if true_len is None:
        x_last = x[:, -1]
        pos = jnp.full((B,), L, jnp.int32)
    else:
        # Last *real* token of each right-padded row (causality guarantees
        # its activations are identical to the unpadded prompt's).
        idx = jnp.maximum(true_len - 1, 0)[:, None, None]
        x_last = jnp.take_along_axis(x, idx, axis=1)[:, 0]
        pos = true_len.astype(jnp.int32)
    x = rmsnorm(params["final_norm"], x_last)
    table = params.get("unembed", params["embed"])
    logits = unembed(table, x, cfg.final_logit_softcap)
    return logits[:, None, :], DecodeCache(
        new.get("attn"), new.get("ssm"), pos)


def reset_slot(cfg: ArchConfig, cache: DecodeCache, slot: int,
               pages=None) -> DecodeCache:
    """Zero one slot of a pooled decode cache (eviction).

    Constant-state path: the (S, z) accumulators zero — a single overwrite,
    the serving asymmetry SLAY buys us. KV path: the slot's ring zeroes and
    its pos resets, which is equivalent to eviction because validity is
    derived from pos. Every other slot's bytes are untouched, so the cache
    sharding (slot-stable by construction) never changes.

    Paged pool: the slot's *owned pages* zero (so a quarantined slot's NaN
    never survives into a page's next owner) and the freed table/owner
    vectors the host allocator computed are installed via ``pages``.
    """
    if cache.pages is not None:
        pg = _pages_mod()
        a = cache.attn._replace(
            k=pg.write_zero_pages(cache.attn.k, slot, cache.pages),
            v=pg.write_zero_pages(cache.attn.v, slot, cache.pages),
            pos=cache.attn.pos.at[:, slot].set(0))
        return DecodeCache(a, cache.ssm, cache.pos.at[slot].set(0),
                           pages if pages is not None else cache.pages)
    z1 = jax.tree.map(lambda x: x.at[:, slot].set(0), cache.attn)
    zs = jax.tree.map(lambda x: x.at[:, slot].set(0), cache.ssm)
    return DecodeCache(z1, zs, cache.pos.at[slot].set(0), cache.pages)


def write_slot(cfg: ArchConfig, cache: DecodeCache, src: DecodeCache,
               slot: int, pages=None) -> DecodeCache:
    """Install a single-sequence cache (batch=1, e.g. a freshly prefilled
    request) into slot ``slot`` of a pooled cache (admission). Pool and
    source must be built from the same cfg/max_len so leaf shapes agree.

    Paged pool: ``pages`` carries the post-allocation ``PageState`` (the
    host allocator assigned this slot its pages at admission); every owned
    page is overwritten in full from the dense batch=1 source ring."""
    if cache.pages is not None:
        pg = _pages_mod()
        st = pages if pages is not None else cache.pages
        a = cache.attn._replace(
            k=pg.write_slot_pages(cache.attn.k, src.attn.k, slot, st),
            v=pg.write_slot_pages(cache.attn.v, src.attn.v, slot, st),
            pos=cache.attn.pos.at[:, slot].set(src.attn.pos[:, 0]))
        return DecodeCache(a, cache.ssm,
                           cache.pos.at[slot].set(src.pos[0]), st)
    wa = jax.tree.map(lambda dst, s: dst.at[:, slot].set(s[:, 0]),
                      cache.attn, src.attn)
    ws = jax.tree.map(lambda dst, s: dst.at[:, slot].set(s[:, 0]),
                      cache.ssm, src.ssm)
    return DecodeCache(wa, ws, cache.pos.at[slot].set(src.pos[0]),
                       cache.pages)


def slot_state_finite(cfg: ArchConfig, cache: DecodeCache) -> jnp.ndarray:
    """(B,) bool — every float decode-state leaf of each slot is finite.

    The NaN/Inf quarantine probe (DESIGN.md §10): reduces each stacked
    ``(num_layers, B, ...)`` float leaf (KV rings, (S, z) accumulators,
    SSM scan/conv carries) over every non-slot axis. Integer leaves
    (positions, ring cursors) cannot be non-finite and are skipped. The
    reduction is per-slot, so under a slot-sharded pool it partitions
    into shard-local work — no collectives enter the §8 decode contract.
    """
    B = cache.pos.shape[0]
    if cache.pages is not None:
        # Per-page finiteness, attributed to the owning slot — free pages
        # (stale bytes from an evicted owner) never taint a live slot.
        return _pages_mod().pages_finite(
            [cache.attn.k, cache.attn.v], cache.pages, B)
    ok = jnp.ones((B,), bool)
    for leaf in jax.tree.leaves((cache.attn, cache.ssm)):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            continue
        axes = tuple(i for i in range(leaf.ndim) if i != 1)
        ok = ok & jnp.all(jnp.isfinite(leaf), axis=axes)
    return ok


def corrupt_slot(cfg: ArchConfig, cache: DecodeCache,
                 slot: int) -> DecodeCache:
    """Overwrite one slot's float state with NaN — the chaos harness's
    fault-injection primitive (``serving.faults``; never on a production
    path). Mirrors :func:`reset_slot`'s slot-stable, shard-local update
    shape; integer leaves (positions) are left intact so the fault is a
    pure numeric corruption, not a bookkeeping one."""
    if cache.pages is not None:
        pg = _pages_mod()
        a = cache.attn._replace(
            k=pg.corrupt_slot_pages(cache.attn.k, slot, cache.pages),
            v=pg.corrupt_slot_pages(cache.attn.v, slot, cache.pages))
        return DecodeCache(a, cache.ssm, cache.pos, cache.pages)

    def nan_row(x):
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        return x.at[:, slot].set(jnp.nan)

    return DecodeCache(jax.tree.map(nan_row, cache.attn),
                       jax.tree.map(nan_row, cache.ssm), cache.pos,
                       cache.pages)


def supports_chunked_prefill(cfg: ArchConfig) -> bool:
    """Chunked prefill continuation covers every decoder-only config:
    linear kinds seed the fp32 (S, z) recurrence, softmax and the exact
    quadratic yat kinds attend ring prefix + masked intra-chunk scores,
    and ssm/hybrid carry the SSD scan state plus the causal-conv tail
    across chunk boundaries (``ssm.ssd_prefill_chunk``, DESIGN.md §9).
    Modality frontends chunk too: the vision patch prefix feeds through
    ``prefill_chunk(embeds=...)`` piece by piece — same continuation, the
    chunk input is just pre-embedded. Encdec is gated in
    ``whisper.supports_chunked_prefill``."""
    return True


def prefill_chunk(params: dict, cfg: ArchConfig, cache: DecodeCache,
                  tokens: jnp.ndarray, *,
                  embeds: jnp.ndarray | None = None
                  ) -> tuple[jnp.ndarray, DecodeCache]:
    """Absorb one prompt chunk into an existing decode cache.

    tokens (B, Lc); ``cache`` holds the state of the previously absorbed
    prefix (per-slot ``pos``). Returns last-token logits (B, 1, V) and the
    advanced cache — so a prompt fed chunk-by-chunk ends in the same state
    (exactly for the fp32 linear/SSM recurrences; up to fp roundoff for
    the quadratic kinds) as a whole-prompt :func:`prefill`, letting the
    serving engine interleave prefill progress with decode ticks instead
    of stalling the pool. SSM/hybrid layers carry their (nh, hd, ds) scan
    state and (W-1, conv_dim) causal-conv tail across chunks
    (DESIGN.md §9).

    ``embeds`` (B, Lc, d_model) feeds a pre-embedded chunk instead of
    token ids — how a vision patch prefix is absorbed chunk-by-chunk
    (``tokens`` is ignored when given). The continuation is position-
    driven, so prefix-embed chunks and token chunks interleave exactly.
    """
    if cfg.layer_types:
        x, cache = _mixed_chunk(params, cfg, cache,
                                _mixed_embed(params, cfg, tokens))
        return _mixed_logits(params, cfg, x[:, -1])[:, None, :], cache
    if embeds is not None:
        x = embeds.astype(cfg.activation_dtype)
        B, Lc = x.shape[0], x.shape[1]
    else:
        B, Lc = tokens.shape
        x = embed(params["embed"], tokens).astype(cfg.activation_dtype)
    positions = cache.pos[:, None] + jnp.arange(Lc, dtype=jnp.int32)[None, :]
    slay_params = params.get("slay")
    kinds = jnp.asarray(_layer_kinds(cfg))

    def _ssd_chunk(lp, xn, st):
        # Clamp the scan tile to the chunk length (exact: the continuation
        # is chunk-size invariant) so short serving chunks don't zero-pad
        # up to cfg.chunk_size — mirrors the linear path's clamp.
        return ssm.ssd_prefill_chunk(
            lp["ssd"], xn, st, d_state=cfg.ssm_state, expand=cfg.ssm_expand,
            head_dim=cfg.ssm_head_dim, ngroups=cfg.ssm_ngroups,
            conv_width=cfg.ssm_conv_width,
            chunk_size=max(min(cfg.chunk_size, Lc), 1))

    def body(x, scanned):
        lp, is_local = scanned["params"], scanned["kind"]
        new = {}
        if cfg.family == "ssm":
            y, st = _ssd_chunk(lp, rmsnorm(lp["pre"], x), scanned["ssm"])
            new["ssm"] = st
            return x + y, new
        xa = rmsnorm(lp["pre_attn"], x)
        q = jnp.einsum("bld,dhk->blhk", xa, lp["attn"]["wq"])
        k = jnp.einsum("bld,dhk->blhk", xa, lp["attn"]["wk"])
        v = jnp.einsum("bld,dhk->blhk", xa, lp["attn"]["wv"])
        if cfg.qk_norm:
            q = rmsnorm(lp["attn"]["q_norm"], q)
            k = rmsnorm(lp["attn"]["k_norm"], k)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        spec_g = cfg.attention_spec(local=False)
        ac = scanned["attn"]
        if cfg.local_global_period and cfg.local_window:
            spec_l = cfg.attention_spec(local=True)

            def _local():
                y, c = attn.prefill_chunk(spec_l, None, q, k, v, ac)
                return y, _merge_cache(ac, c)

            def _global():
                y, c = attn.prefill_chunk(spec_g, slay_params, q, k, v, ac)
                return y, _merge_cache(ac, c)

            y, nac = jax.lax.cond(is_local == 1, _local, _global)
        else:
            y, nac = attn.prefill_chunk(spec_g, slay_params, q, k, v, ac)
        a = jnp.einsum("blhk,hkd->bld", y, lp["attn"]["wo"])
        new["attn"] = nac
        if cfg.family == "hybrid":
            m, st = _ssd_chunk(lp, xa, scanned["ssm"])
            a = 0.5 * (a + m)
            new["ssm"] = st
        x = x + a
        xm = rmsnorm(lp["pre_mlp"], x)
        if cfg.moe_experts:
            y2, _ = moe(lp["moe"], xm, cfg.moe_experts, cfg.moe_top_k)
        else:
            y2 = mlp(lp["mlp"], xm, cfg.gated_mlp)
        return x + y2, new

    scanned = {"params": params["layers"], "kind": kinds}
    if cache.attn is not None:
        scanned["attn"] = cache.attn
    if cache.ssm is not None:
        scanned["ssm"] = cache.ssm
    x, new = jax.lax.scan(body, x, scanned)
    x = rmsnorm(params["final_norm"], x[:, -1])
    table = params.get("unembed", params["embed"])
    logits = unembed(table, x, cfg.final_logit_softcap)
    return logits[:, None, :], DecodeCache(new.get("attn"), new.get("ssm"),
                                           cache.pos + Lc, cache.pages)


def supports_speculative(cfg: ArchConfig) -> bool:
    """Whether a config can be the *verifier* of draft-verify speculative
    decoding (DESIGN.md §13).

    Two structural requirements: (1) rejected-suffix rollback must be a
    pure per-slot ``pos`` rewind, which holds only for a non-windowed
    exact quadratic KV ring (validity is derived from ``pos``; stale rows
    past the accept horizon become invisible and are overwritten in
    place) — linear kinds fold tokens irreversibly into the (S, z)
    accumulator and SSM/hybrid carries cannot un-absorb a step; (2) the
    draft swap (``attn_kind -> "slay"``) must leave the rest of the
    parameter tree identical so one params pytree serves both regimes,
    which rules out encdec and modality frontends. Windowed/mixed-window
    rings are excluded with (1): an in-window eviction is not rewindable.
    """
    if cfg.family in ("ssm", "hybrid", "encdec") or cfg.frontend \
            or cfg.layer_types:
        return False
    if cfg.local_window or cfg.local_global_period:
        return False
    return not cfg.attention_spec().is_linear


def verify_chunk(params: dict, cfg: ArchConfig, cache: DecodeCache,
                 tokens: jnp.ndarray,
                 active: jnp.ndarray | None = None
                 ) -> tuple[jnp.ndarray, DecodeCache]:
    """Score a candidate token block: tokens (B, Lc) -> logits (B, Lc, V).

    The speculative verifier (DESIGN.md §13): same §9-exact chunked
    continuation as :func:`prefill_chunk`, but returning the *full*
    per-position logits — row j is the verifier's next-token distribution
    after absorbing tokens[:, :j+1] on top of the cached prefix — and
    masking per slot like :func:`decode_step`: drained slots pass their
    cache bytes and ``pos`` through untouched (paged slots scatter their
    own gathered rows back unchanged). The advanced cache has absorbed
    all ``Lc`` candidates; the caller rewinds to the accept horizon with
    :func:`rollback_slots`.
    """
    B, Lc = tokens.shape
    x = embed(params["embed"], tokens).astype(cfg.activation_dtype)
    positions = cache.pos[:, None] + jnp.arange(Lc, dtype=jnp.int32)[None, :]
    act = None if active is None else active.astype(bool)
    slay_params = params.get("slay")

    # Verifier configs are single-spec (supports_speculative excludes
    # local/global mixes), so no per-layer kind dispatch here.
    def body(x, scanned):
        lp = scanned["params"]
        new = {}
        xa = rmsnorm(lp["pre_attn"], x)
        q = jnp.einsum("bld,dhk->blhk", xa, lp["attn"]["wq"])
        k = jnp.einsum("bld,dhk->blhk", xa, lp["attn"]["wk"])
        v = jnp.einsum("bld,dhk->blhk", xa, lp["attn"]["wv"])
        if cfg.qk_norm:
            q = rmsnorm(lp["attn"]["q_norm"], q)
            k = rmsnorm(lp["attn"]["k_norm"], k)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        spec_g = cfg.attention_spec(local=False)
        ac = scanned["attn"]
        if cache.pages is not None:
            # Paged pool (§11): gather -> exact chunk update -> per-slot
            # passthrough on the *dense* view (leaves stay (B, ...)) ->
            # scatter. A drained slot's pages get their own gathered rows
            # written back — byte-identical, so "untouched" holds.
            pg = _pages_mod()
            dense = ac._replace(k=pg.gather_ring(ac.k, cache.pages),
                                v=pg.gather_ring(ac.v, cache.pages))
            y, nd = attn.prefill_chunk(spec_g, slay_params, q, k, v, dense)
            nd = _state_passthrough(nd, dense, act)
            nac = nd._replace(
                k=pg.scatter_ring(ac.k, nd.k, cache.pages),
                v=pg.scatter_ring(ac.v, nd.v, cache.pages))
        else:
            y, nac = attn.prefill_chunk(spec_g, slay_params, q, k, v, ac)
            nac = _state_passthrough(nac, ac, act)
        a = jnp.einsum("blhk,hkd->bld", y, lp["attn"]["wo"])
        new["attn"] = nac
        x = x + a
        xm = rmsnorm(lp["pre_mlp"], x)
        if cfg.moe_experts:
            y2, _ = moe(lp["moe"], xm, cfg.moe_experts, cfg.moe_top_k)
        else:
            y2 = mlp(lp["mlp"], xm, cfg.gated_mlp)
        return x + y2, new

    scanned = {"params": params["layers"], "attn": cache.attn}
    x, new = jax.lax.scan(body, x, scanned)
    x = rmsnorm(params["final_norm"], x)
    table = params.get("unembed", params["embed"])
    logits = unembed(table, x, cfg.final_logit_softcap)
    step = Lc if act is None else Lc * act.astype(jnp.int32)
    return logits, DecodeCache(new["attn"], cache.ssm, cache.pos + step,
                               cache.pages)


def rollback_slots(cfg: ArchConfig, cache: DecodeCache,
                   new_pos: jnp.ndarray) -> DecodeCache:
    """Rewind per-slot context horizons to ``new_pos`` (B,) int32 (§13).

    KV-ring validity is derived from ``pos`` alone (attention masks rows
    at or beyond the horizon), so rejecting a speculative suffix moves no
    ring bytes: rows past the accept horizon become invisible and the
    next absorb overwrites them in place. A paged pool's page table is
    untouched — admission sized the slot's pages for the full horizon
    plus verify overshoot, so there is nothing to free (and nothing that
    can leak; the §11 audit checks the table, not row contents).
    """
    new_pos = new_pos.astype(jnp.int32)
    a = cache.attn
    if a is not None:
        a = a._replace(pos=jnp.broadcast_to(new_pos[None, :], a.pos.shape))
    return DecodeCache(a, cache.ssm, new_pos, cache.pages)


def _merge_cache(template: attn.AttnCache, new: attn.AttnCache):
    """Fill unused union-cache slots from the template so pytree structure
    stays constant across mixed local/linear layers."""
    return attn.AttnCache(
        new.k if new.k is not None else template.k,
        new.v if new.v is not None else template.v,
        new.pos if new.pos is not None else template.pos,
        new.s if new.s is not None else template.s,
        new.z if new.z is not None else template.z,
    )


def _ssd_prefill_state(cfg: ArchConfig, lp: dict, xn: jnp.ndarray):
    """Recompute the final SSD state for a prompt (prefill).

    Runs the chunked scan again keeping only the carry — XLA CSEs this with
    the forward pass when fused in the same jit.
    """
    d_model = xn.shape[-1]
    z, xs, b, c, dt, d_inner, nheads = ssm._split_proj(
        lp, xn, d_model, cfg.ssm_state, cfg.ssm_expand, cfg.ssm_head_dim,
        cfg.ssm_ngroups)
    full = jnp.concatenate([xs, b, c], -1)
    xbc, _ = ssm._causal_conv(lp, full, cfg.ssm_conv_width)
    xs, b, c = jnp.split(xbc, [d_inner, d_inner + cfg.ssm_ngroups
                               * cfg.ssm_state], -1)
    B, L = xn.shape[0], xn.shape[1]
    xh = xs.reshape(B, L, nheads, cfg.ssm_head_dim).astype(jnp.float32)
    bh = b.reshape(B, L, cfg.ssm_ngroups, cfg.ssm_state).astype(jnp.float32)
    dtp = jax.nn.softplus(dt.astype(jnp.float32)
                          + lp["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(lp["a_log"].astype(jnp.float32))
    la_ = dtp * a
    # Final state: sum_u exp(sum_{t>u} la_t) dt_u x_u B_u^T
    rev_cum = jnp.cumsum(la_[:, ::-1], axis=1)[:, ::-1] - la_  # tail sums
    w = jnp.exp(rev_cum) * dtp                                  # (B,L,nh)
    g = nheads // cfg.ssm_ngroups
    bg = jnp.repeat(bh, g, axis=-2)
    h = jnp.einsum("blhd,blhs->bhds", xh * w[..., None], bg)
    conv = jax.lax.dynamic_slice_in_dim(
        full, L - (cfg.ssm_conv_width - 1), cfg.ssm_conv_width - 1,
        axis=1).astype(jnp.float32)            # (B, W-1, conv_dim)
    return ssm.SsmState(h, conv)


# ---------------------------------------------------------------------------
# Mixed stack: Mamba-2 and attention layers (cfg.layer_types)
# ---------------------------------------------------------------------------
#
# Every layer is pre-norm mixer, residual, pre-norm gated MLP, residual,
# with each branch scaled by ``residual_multiplier`` before its add
# (granite-4.0-h). The mixer is a Mamba-2 SSD block or GQA softmax
# attention. Mixer weights and decode state are stacked by kind, so a
# model of 36 Mamba and 4 attention layers holds 4 KV rings, not 40.
#
# The layer pattern runs as alternating runs of each kind: a scan over
# (Mamba run, attention run) pairs, each run a loop of its kind's layer
# body with the run's length as its trip count. The program holds one
# body of each kind however the kinds interleave, and no branch between
# them: under ``lax.cond`` the branch that leaves a stack as it is copies
# it whole (XLA copies a conditional's passed-through operands), which at
# 64 slots is a 4.8 GB copy of the SSM state per attention layer. Each
# body reads its kind's weights and state at the layer's index into that
# kind's stack and writes its state back there in place.


def _mixed_counts(cfg: ArchConfig) -> tuple[int, int]:
    n_attn = cfg.layer_types.count("attention")
    return cfg.num_layers - n_attn, n_attn


def _mixed_runs(cfg: ArchConfig) -> dict:
    """The layer pattern as pairs of runs, a run of Mamba layers then a
    run of attention layers, either possibly empty: per pair the lengths
    and where each run starts in the layer stack and in its kind's
    stack."""
    pairs: list[list[int]] = []           # [Mamba run, attention run]
    for is_attn, run in itertools.groupby(t == "attention"
                                          for t in cfg.layer_types):
        n = len(list(run))
        if not is_attn:
            pairs.append([n, 0])
        elif pairs and not pairs[-1][1]:
            pairs[-1][1] = n
        else:
            pairs.append([0, n])
    m_len, a_len = (list(x) for x in zip(*pairs))

    def start(n):
        return [0, *itertools.accumulate(n)][:-1]

    return {"m_len": m_len, "a_len": a_len, "m0": start(m_len),
            "a0": start(a_len),
            "l0": start([m + a for m, a in pairs])}


def _mixed_specs(cfg: ArchConfig) -> dict:
    n_mamba, n_attn = _mixed_counts(cfg)
    specs = {}
    if n_mamba:
        specs["ssd"] = stack_specs(ssm.ssd_specs(
            cfg.d_model, cfg.ssm_state, cfg.ssm_expand, cfg.ssm_head_dim,
            cfg.ssm_ngroups, cfg.ssm_conv_width), n_mamba)
    if n_attn:
        specs["attn"] = stack_specs(attn_proj_specs(cfg), n_attn)
    return specs


def _mixed_init_cache(cfg: ArchConfig, batch: int,
                      max_len: int) -> DecodeCache:
    """SSM state and conv tails for the Mamba layers, a KV ring for each
    attention layer, each stacked over its own kind's layers."""
    n_mamba, n_attn = _mixed_counts(cfg)
    s_cache = a_cache = None
    if n_mamba:
        s_cache = ssm.ssd_init_state((n_mamba, batch), cfg.d_model,
                                     cfg.ssm_state, cfg.ssm_expand,
                                     cfg.ssm_head_dim, cfg.ssm_ngroups,
                                     cfg.ssm_conv_width)
    if n_attn:
        ring = (n_attn, batch, max_len, cfg.num_kv_heads,
                cfg.resolved_head_dim)
        a_cache = attn.AttnCache(
            jnp.zeros(ring, cfg.activation_dtype),
            jnp.zeros(ring, cfg.activation_dtype),
            jnp.zeros((n_attn, batch), jnp.int32), None, None)
    return DecodeCache(a_cache, s_cache, jnp.zeros((batch,), jnp.int32))


def _mixed_embed(params: dict, cfg: ArchConfig, tokens) -> jnp.ndarray:
    x = embed(params["embed"], tokens).astype(cfg.activation_dtype)
    return x * jnp.asarray(cfg.embedding_multiplier, x.dtype)


def _mixed_logits(params: dict, cfg: ArchConfig, x) -> jnp.ndarray:
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params.get("unembed", params["embed"]), x)
    return logits / jnp.asarray(cfg.logits_scaling, logits.dtype)


def _at(tree, i):
    """Entry ``i`` of every leaf of a stacked tree."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


def _put(tree, new, i):
    """Write ``new`` as entry ``i`` of every leaf of a stacked tree."""
    return jax.tree.map(
        lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, i, 0),
        tree, new)


def _ssd_kw(cfg: ArchConfig) -> dict:
    return dict(d_state=cfg.ssm_state, expand=cfg.ssm_expand,
                head_dim=cfg.ssm_head_dim, ngroups=cfg.ssm_ngroups,
                conv_width=cfg.ssm_conv_width, norm_eps=cfg.norm_eps)


def _qkv(cfg: ArchConfig, p: dict, h, positions):
    """GQA projections of normed input h (..., L, d); RoPE unless NoPE."""
    q = jnp.einsum("...d,dhk->...hk", h, p["wq"])
    k = jnp.einsum("...d,dhk->...hk", h, p["wk"])
    v = jnp.einsum("...d,dhk->...hk", h, p["wv"])
    if cfg.position_embedding == "rope":
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mixed_layers(params: dict, cfg: ArchConfig, x, cache: DecodeCache,
                  mamba, attention):
    """Run x (B, ..., d) through every layer of a mixed stack, both kinds'
    stacked state riding the carry. ``mamba(j, h, ssm_state)`` and
    ``attention(j, h, attn_cache)`` give the mixer output for the normed
    input h and their kind's stacks with entry j updated."""
    rm = cfg.residual_multiplier

    def layer(mixer, state_at):
        def body(i, carry):
            l, j = i                      # layer index, index in its kind
            x, st = carry
            lp = _at(params["layers"], l)
            y, new = mixer(j, rmsnorm(lp["pre_mix"], x, cfg.norm_eps),
                           st[state_at])
            st = tuple(new if n == state_at else s for n, s in enumerate(st))
            x = x + y * jnp.asarray(rm, y.dtype)
            y = mlp(lp["mlp"], rmsnorm(lp["pre_mlp"], x, cfg.norm_eps),
                    cfg.gated_mlp)
            return x + y * jnp.asarray(rm, y.dtype), st
        return body

    mamba_layer, attn_layer = layer(mamba, 0), layer(attention, 1)

    def pair(carry, r):
        if cache.ssm is not None:
            carry = jax.lax.fori_loop(
                0, r["m_len"], lambda i, c: mamba_layer(
                    (r["l0"] + i, r["m0"] + i), c), carry)
        if cache.attn is not None:
            carry = jax.lax.fori_loop(
                0, r["a_len"], lambda i, c: attn_layer(
                    (r["l0"] + r["m_len"] + i, r["a0"] + i), c), carry)
        return carry, None

    runs = {k: jnp.asarray(v, jnp.int32) for k, v in _mixed_runs(cfg).items()}
    (x, (st, ac)), _ = jax.lax.scan(pair, (x, (cache.ssm, cache.attn)), runs)
    return x, st, ac


def _mixed_chunk(params: dict, cfg: ArchConfig, cache: DecodeCache, x):
    """Absorb a chunk x (B, Lc, d) into a mixed stack's cache: the Mamba
    layers continue their SSD scan and conv tails
    (``ssm.ssd_prefill_chunk``), the attention layers attend their ring
    prefix and the chunk (``attention.prefill_chunk``). Returns every
    position's hidden state and the advanced cache."""
    Lc = x.shape[1]
    positions = cache.pos[:, None] + jnp.arange(Lc, dtype=jnp.int32)[None, :]
    spec = cfg.attention_spec()

    def mamba(j, h, st):
        y, new = ssm.ssd_prefill_chunk(
            _at(params["ssd"], j), h, _at(st, j),
            chunk_size=max(min(cfg.chunk_size, Lc), 1), **_ssd_kw(cfg))
        return y, _put(st, new, j)

    def attention(j, h, ac):
        p = _at(params["attn"], j)
        q, k, v = _qkv(cfg, p, h, positions)
        y, new = attn.prefill_chunk(spec, None, q, k, v, _at(ac, j))
        return jnp.einsum("blhk,hkd->bld", y, p["wo"]), _put(ac, new, j)

    x, st, ac = _mixed_layers(params, cfg, x, cache, mamba, attention)
    return x, DecodeCache(ac, st, cache.pos + Lc, cache.pages)


def _mixed_forward(params: dict, cfg: ArchConfig, tokens):
    """Full-sequence logits of a mixed stack: the chunk continuation from
    an empty cache, over the whole sequence. The layer runs loop with
    traced trip counts, so there is no reverse-mode gradient: the mixed
    stack serves and has no training path."""
    B, L = tokens.shape
    x, _ = _mixed_chunk(params, cfg, init_cache(cfg, B, L),
                        _mixed_embed(params, cfg, tokens))
    return _mixed_logits(params, cfg, x), jnp.zeros((), jnp.float32)


def _mixed_decode_step(params: dict, cfg: ArchConfig, cache: DecodeCache,
                       tokens, active):
    """One decode tick of a mixed stack. The SSM state, conv tails and KV
    rings of every layer ride the layer loops' carry; each layer reads and
    writes its own entry of its kind's stack in place, so the pool's state
    is never scanned through, written back whole or copied. Drained slots
    keep their bytes (``active`` masks the writes) and add nothing."""
    x = _mixed_embed(params, cfg, tokens[:, 0])
    act = None if active is None else active.astype(bool)
    spec = cfg.attention_spec()
    pos = cache.pos

    def mamba(j, h, st):
        old = _at(st, j)
        y, new = ssm.ssd_decode_step(_at(params["ssd"], j), h, old,
                                     **_ssd_kw(cfg))
        if act is not None:
            new = _state_passthrough(new, old, act)
            y = jnp.where(act[:, None], y, 0).astype(y.dtype)
        return y, _put(st, new, j)

    def attention(j, h, ac):
        p = _at(params["attn"], j)
        q, k, v = _qkv(cfg, p, h[:, None], pos[:, None])
        y, new = attn.decode_step(spec, None, q[:, 0], k[:, 0], v[:, 0],
                                  ac._replace(pos=_at(ac.pos, j)),
                                  active=act, layer=j)
        return (jnp.einsum("bhk,hkd->bd", y, p["wo"]),
                new._replace(pos=_put(ac.pos, new.pos, j)))

    x, st, ac = _mixed_layers(params, cfg, x, cache, mamba, attention)
    step = 1 if act is None else act.astype(jnp.int32)
    return (_mixed_logits(params, cfg, x)[:, None, :],
            DecodeCache(ac, st, pos + step, cache.pages))
