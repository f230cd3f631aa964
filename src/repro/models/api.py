"""Family-dispatching model API: one call surface for all 10+ architectures.

    params    = api.init_params(cfg, key)
    axes      = api.param_axes(cfg)          # logical sharding axes pytree
    loss, mx  = api.loss_fn(params, cfg, batch)
    logits,c  = api.prefill(params, cfg, **batch)
    logits,c  = api.decode_step(params, cfg, cache, tokens)
"""
from __future__ import annotations

import dataclasses

import jax

from repro.configs.base import ArchConfig
from repro.models import transformer, whisper


def _mod(cfg: ArchConfig):
    return whisper if cfg.family == "encdec" else transformer


def init_params(cfg: ArchConfig, key: jax.Array) -> dict:
    return _mod(cfg).init_params(cfg, key)


def abstract_params(cfg: ArchConfig) -> dict:
    """ShapeDtypeStruct pytree of the parameters — no allocation."""
    return jax.eval_shape(
        lambda k: init_params(cfg, k), jax.ShapeDtypeStruct((2,), "uint32"))


def param_axes(cfg: ArchConfig) -> dict:
    return _mod(cfg).param_axes(cfg)


def loss_fn(params, cfg: ArchConfig, batch: dict, *, remat: bool = False):
    return _mod(cfg).loss_fn(params, cfg, batch, remat=remat)


def forward(params, cfg: ArchConfig, batch: dict):
    if cfg.family == "encdec":
        return whisper.forward(params, cfg, batch["tokens"],
                               batch["frame_embeds"])
    return transformer.forward(params, cfg, batch["tokens"],
                               patch_embeds=batch.get("patch_embeds"))


def init_cache(cfg: ArchConfig, batch: int, max_len: int, *,
               page_size: int = 0, num_pages: int = 0, shards: int = 1):
    """``page_size > 0`` requests a *paged* pool cache (KV rings become
    shared physical pages with a PageState table — DESIGN.md §11) for
    configs that :func:`supports_paging`; ignored otherwise (constant-
    state kinds have nothing to page)."""
    if page_size and cfg.family != "encdec":
        return transformer.init_cache(cfg, batch, max_len,
                                      page_size=page_size,
                                      num_pages=num_pages, shards=shards)
    return _mod(cfg).init_cache(cfg, batch, max_len)


def abstract_cache(cfg: ArchConfig, batch: int, max_len: int, *,
                   page_size: int = 0, num_pages: int = 0,
                   shards: int = 1):
    """Cache shapes without allocation."""
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len,
                                             page_size=page_size,
                                             num_pages=num_pages,
                                             shards=shards))


# -- Slot-pooled cache surface (continuous-batching serving) ---------------
#
# A pool cache is an ordinary init_cache(cfg, num_slots, max_len); slots are
# batch rows. Admission/eviction are single-slot overwrites — O(slot bytes),
# no paging — because every regime's per-sequence decode state lives in
# contiguous batch-indexed leaves (constant-state (S, z), KV rings, SSM
# carries) with per-slot positions. Under a slot-sharded pool (DESIGN.md
# §8) the slot dim is partitioned over the `data` mesh axis in contiguous
# static blocks; both ops below are dynamic-updates along that dim, so
# jitted with the pool's sharding as in- AND out-sharding (cache donated)
# they lower to shard-local writes — only the owning shard's block mutates.


def reset_slot(cfg: ArchConfig, cache, slot: int, pages=None):
    """Zero one slot (eviction). Slot-stable: other rows untouched — and
    under a sharded pool, shard-local: only ``slot``'s static owner shard
    writes; every other shard's bytes alias through the donated input.
    Paged pool: ``pages`` installs the host allocator's post-free
    ``PageState`` (the slot's pages return to the free list)."""
    if pages is not None:
        return transformer.reset_slot(cfg, cache, slot, pages)
    return _mod(cfg).reset_slot(cfg, cache, slot)


def write_slot(cfg: ArchConfig, cache, src, slot: int, pages=None):
    """Install a batch=1 request cache into a pool slot (admission).

    ``src`` (a freshly prefilled request cache) is replicated by the
    engine's jit signature, so the prefill output lands directly on the
    owning shard as part of the donated pool update — admission never
    moves another shard's slot bytes or reshards the pool. Paged pool:
    ``pages`` carries the post-allocation ``PageState``."""
    if pages is not None:
        return transformer.write_slot(cfg, cache, src, slot, pages)
    return _mod(cfg).write_slot(cfg, cache, src, slot)


def supports_paging(cfg: ArchConfig) -> bool:
    """Whether the pooled KV rings can be page-indexed (DESIGN.md §11).

    True only for non-windowed exact quadratic rings — the one decode
    state that scales with context. Constant-state kinds (linear SLAY,
    SSM/hybrid carries) bypass paging: their per-slot state is O(1), the
    paper's serving asymmetry."""
    return cfg.family != "encdec" and transformer.supports_paging(cfg)


def context_capacity(cfg: ArchConfig, max_len: int) -> int | None:
    """Rows of context (prefix + prompt + decode budget) one slot admits;
    ``None`` = unbounded (constant-state decode or an exactly-wrapping
    windowed ring)."""
    return _mod(cfg).context_capacity(cfg, max_len)


def slot_state_finite(cfg: ArchConfig, cache) -> jax.Array:
    """(B,) bool per-slot finiteness probe over the pooled decode state.

    The quarantine guard's detection surface (DESIGN.md §10): True where
    every float state leaf of that slot (KV rings, constant-state (S, z),
    SSM carries, cross-attention summaries) is finite. Reductions are
    per-slot only — shard-local under a slot-sharded pool, so the §8
    zero-collective decode contract is preserved when this runs inside
    the jitted macro-step."""
    return _mod(cfg).slot_state_finite(cfg, cache)


def corrupt_slot(cfg: ArchConfig, cache, slot: int):
    """Overwrite one slot's float state with NaN (fault injection).

    Chaos-harness primitive (``serving.faults``) — the write mirrors
    ``reset_slot``'s slot-stable shard-local shape, so injecting a fault
    never perturbs neighbouring slots' bytes or the pool sharding."""
    return _mod(cfg).corrupt_slot(cfg, cache, slot)


def supports_chunked_prefill(cfg: ArchConfig) -> bool:
    """Whether prefill can be fed chunk-by-chunk with state continuation.

    True for every decoder-only config — all attention kinds (linear,
    softmax, exact yat), the ssm/hybrid scan-carry families, and vision
    frontends (the patch prefix feeds through ``prefill_chunk(embeds=)``
    chunk-by-chunk — DESIGN.md §9/§11). False only for encdec."""
    return _mod(cfg).supports_chunked_prefill(cfg)


def prefill_chunk(cfg: ArchConfig, params, cache, tokens, embeds=None):
    """Absorb one prompt chunk into an existing cache; last-token logits.

    Exact continuation for any chunk schedule: linear (S, z) and SSM
    (scan + conv-tail) carries are fp32; quadratic kinds re-attend the
    ring prefix. ``embeds`` (B, Lc, d) feeds a pre-embedded chunk (vision
    patch prefix) instead of token ids."""
    if embeds is not None:
        return transformer.prefill_chunk(params, cfg, cache, tokens,
                                         embeds=embeds)
    return _mod(cfg).prefill_chunk(params, cfg, cache, tokens)


def supports_masked_prefill(cfg: ArchConfig) -> bool:
    """Whether prefill accepts ``true_len`` (right-padded prompts) — the
    enabler for length-bucketed compilation of the non-chunkable serving
    prefill fallback."""
    return _mod(cfg).supports_masked_prefill(cfg)


def prefill(params, cfg: ArchConfig, batch: dict, *,
            max_len: int | None = None, true_len=None):
    """Absorb a prompt batch. ``true_len`` (B,) int32 marks real lengths of
    right-padded prompts (see transformer.prefill)."""
    if cfg.family == "encdec":
        return whisper.prefill(params, cfg, batch["tokens"],
                               batch["frame_embeds"], max_len=max_len,
                               true_len=true_len)
    return transformer.prefill(params, cfg, batch["tokens"],
                               patch_embeds=batch.get("patch_embeds"),
                               max_len=max_len, true_len=true_len)


def decode_step(params, cfg: ArchConfig, cache, tokens, active=None):
    """One decode tick. ``active`` (B,) masks continuous-batching pool
    slots: drained rows are an exact state passthrough with zero attention
    output (their logits are meaningless — callers sample active rows
    only), so the pool dispatch stays one fixed-shape jitted call."""
    return _mod(cfg).decode_step(params, cfg, cache, tokens, active)


# -- Speculative decoding surface (DESIGN.md §13) ---------------------------


def supports_speculative(cfg: ArchConfig) -> bool:
    """Whether ``cfg`` can verify draft-verify speculative decoding:
    a non-windowed exact quadratic ring (rollback = ``pos`` rewind) whose
    params differ from the linear SLAY draft's only by the tiny ``slay``
    projection entry (one pytree serves both regimes)."""
    return cfg.family != "encdec" and transformer.supports_speculative(cfg)


def draft_config(cfg: ArchConfig) -> ArchConfig:
    """The linear-SLAY draft twin of a verifier config: same architecture,
    ``attn_kind="slay"`` — the paper's linearization of the verifier's own
    kernel, which is what makes its proposals land (high acceptance)."""
    return dataclasses.replace(cfg, attn_kind="slay")


def ensure_draft_params(draft_cfg: ArchConfig, params: dict) -> dict:
    """Add the draft's ``slay`` projection entry to a verifier params tree.

    The draft shares every transformer weight with the verifier; only the
    SLAY anchor/omega random projections are extra. They are derived from
    a fixed key so the draft — and therefore sampled spec streams — is
    deterministic per checkpoint, never per process. (Draft quality only
    affects acceptance rate, not output distribution.)"""
    if "slay" in params:
        return params
    from repro.core.slay import slay_init
    params = dict(params)
    params["slay"] = slay_init(jax.random.PRNGKey(0),
                               draft_cfg.slay_config())
    return params


def verify_chunk(cfg: ArchConfig, params, cache, tokens, active=None):
    """Score ``Lc`` candidate tokens per slot in one exact dispatch:
    tokens (B, Lc) -> (logits (B, Lc, V), advanced cache). Row j is the
    verifier's distribution after absorbing tokens[:, :j+1]; ``active``
    masks drained slots exactly like ``decode_step``."""
    return transformer.verify_chunk(params, cfg, cache, tokens, active)


def slot_positions(cfg: ArchConfig, cache) -> jax.Array:
    """(B,) int32 per-slot absorbed-context horizons."""
    return cache.pos


def rollback_slots(cfg: ArchConfig, cache, new_pos):
    """Rewind per-slot horizons to ``new_pos`` (B,) — the rejected-suffix
    rollback: a pure ``pos`` rewind, no ring bytes move, page table
    untouched (see transformer.rollback_slots)."""
    return transformer.rollback_slots(cfg, cache, new_pos)
