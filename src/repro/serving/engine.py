"""Serving engines: continuous batching over a slot-pooled decode cache.

Two engines share one model surface (``repro.models.api``):

* :class:`ServingEngine` — the lockstep reference: one prefill per batch,
  then decode steps in lockstep until every request finishes. Simple,
  exact, and the parity oracle for the continuous engine.
* :class:`ContinuousServingEngine` — the production shape: a
  :class:`Scheduler` owns a fixed pool of ``num_slots`` decode slots;
  requests queue, are admitted into free slots via *chunked prefill*
  (interleaved with decode ticks so long prompts never stall the pool),
  stream tokens per request, and on EOS/max-tokens are evicted by a single
  slot overwrite.

Why continuous batching is dramatically simpler for SLAY than for KV-cache
models: the constant-state path's per-slot decode state is O(m·dv) per
layer-head *regardless of context length*, so admitting a new request is a
single ``write_slot`` overwrite of a fixed-size block and evicting is a
``reset_slot`` zero — no paged KV allocator needed, no fragmentation, no
copy-out. The KV path rides the same surface with ring-buffer slot resets;
with ``ServingConfig.page_size`` set, its rings additionally draw physical
pages from a shared :class:`repro.serving.pages.PagePool` (DESIGN.md §11)
so short and long requests share HBM — constant-state kinds bypass paging
(their state is O(1), the paper's serving asymmetry). A
``prefix_cache_bytes`` budget enables the content-addressed prefix cache
(``repro.serving.prefix_cache``): admissions whose prompt shares a cached
prefix seed their slot from a stored state snapshot and chunk-prefill only
the suffix.

Cache shardings come from ``sharding.serving_cache_sharding`` and depend
only on pool shape — never on which slots are live — so admission/eviction
never reshard (slot-stable contract).

Fault model (DESIGN.md §10): every request terminates with exactly one
``finish_reason`` from ``sampling.FINISH_REASONS``. Admission failures are
typed (:class:`AdmissionError` and subclasses) and overload degrades per
``ServingConfig.overload_policy`` instead of throwing; requests carry
tick- and wall-clock deadlines and can be cancelled anywhere in their
lifecycle (queued, mid-prefill, slot-resident, even mid-macro-step); a
per-slot NaN/Inf lane inside the jitted macro-step detects numeric faults
and the host replay quarantines + retries them. ``serving.faults`` holds
the deterministic chaos injector that exercises all of it.

Tracing: the continuous engine writes host spans (``engine.*``, via
``jax.profiler.TraceAnnotation``) at step granularity — the step, the
admission, each prefill chunk, the first-token pull and the slot install,
the decode launch / device wait / host replay, the journal flush and the
checkpoint — never per token or per slot. With no profiler running a span
costs well under a microsecond; under ``jax.profiler.trace`` they land on
the device trace's clock. Every jitted engine program is a named function
(``engine_macro_decode``, ``engine_prefill_chunk``, ...), so the device
trace lists it as ``jit_<name>``.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig, ServingConfig
from repro.distributed import sharding as shd
from repro.models import api
from repro.serving import checkpoint as checkpoint_lib
from repro.serving import faults as faults_lib
from repro.serving import journal as journal_lib
from repro.serving import pages as pages_lib
from repro.serving import prefix_cache as prefix_lib
from repro.serving import sampling
from repro.serving import speculative


def jit_serve_fns(cfg: ArchConfig, mesh, max_len: int,
                  rules: shd.ShardingRules = shd.DEFAULT_RULES,
                  batch: int | None = None):
    """jit'd (prefill, decode_step) with rule-derived shardings — the
    lockstep engine's entry points.

    decode_step donates the cache (in-place ring-buffer update on device).
    When ``batch`` is given the cache sharding comes from
    ``sharding.serving_cache_sharding``, which shards the batch (slot) dim
    over the ``data`` mesh axis under the same slot-stable contract as the
    continuous engine's pool (DESIGN.md §8): shardings derive from shapes
    only, so in- and out-shardings agree and decode never reshards.
    """
    axes = api.param_axes(cfg)
    p_abs = api.abstract_params(cfg)
    p_sh = shd.logical_to_sharding(mesh, rules, p_abs, axes)
    b_sh = shd.batch_sharding(mesh, rules)

    def _prefill(params, batch_):
        with shd.activation_sharding(mesh, rules):
            return api.prefill(params, cfg, batch_, max_len=max_len)

    pf = jax.jit(_prefill, in_shardings=(p_sh, b_sh), out_shardings=None)
    if batch is not None:
        c_abs = api.abstract_cache(cfg, batch, max_len)
        c_sh = shd.serving_cache_sharding(mesh, rules, c_abs)
    else:
        c_sh = None
    dec = jax.jit(
        lambda params, cache, tok: api.decode_step(params, cfg, cache, tok),
        in_shardings=(p_sh, c_sh, b_sh) if c_sh is not None else None,
        out_shardings=(b_sh, c_sh) if c_sh is not None else None,
        donate_argnums=(1,))
    return pf, dec


class AdmissionError(RuntimeError):
    """Typed admission failure. ``queue_depth``/``max_queue`` let callers
    report or back off instead of parsing a message (DESIGN.md §10)."""

    def __init__(self, msg: str, *, queue_depth: int = 0,
                 max_queue: int = 0):
        super().__init__(msg)
        self.queue_depth = queue_depth
        self.max_queue = max_queue


class QueueFullError(AdmissionError):
    """Admission queue at ``max_queue`` under the ``reject_new`` overload
    policy. The request was NOT enqueued — the caller keeps it."""


class RequestTooLargeError(AdmissionError, ValueError):
    """prefix + prompt + max_new_tokens exceeds the slot's ``max_len``
    context ring. Also a ValueError (the pre-§10 type, kept for callers)."""


@dataclasses.dataclass
class Request:
    """One generation request.

    Deadlines (all optional, checked every tick — DESIGN.md §10): the
    ``*_ticks`` forms are measured from ``arrival_time`` on the engine's
    logical clock (backend-independent, what tests/benches use); the
    ``*_s`` forms are wall-clock from submission. ``ttft_*`` bounds time
    to the first emitted token only; ``deadline_*`` bounds the whole
    request. A deadline expiring on the same tick as a natural stop loses
    — the emission is processed first, so EOS wins. ``on_finish`` fires
    exactly once per request with its ``finish_reason``
    (``sampling.FINISH_REASONS``); on a fault retry ``on_token`` replays
    the stream from index 0 (deterministic sampling regenerates the same
    prefix when the fault was transient).
    """

    prompt: np.ndarray               # (Lp,) int32
    max_new_tokens: int = 32
    eos_id: int = -1                 # -1: never stop early
    arrival_time: float = 0.0        # engine ticks (continuous engine only)
    on_token: Callable[[int, int], None] | None = None  # (rid, token)
    ttft_deadline_ticks: float | None = None   # first token by arrival + T
    deadline_ticks: float | None = None        # finished by arrival + T
    ttft_deadline_s: float | None = None       # wall-clock equivalents,
    deadline_s: float | None = None            # measured from submit()
    on_finish: Callable[[int, str], None] | None = None  # (rid, reason)

    def __post_init__(self):
        # Fail at construction with an actionable message, not mid-decode
        # with a shape error or a silent never-terminating slot.
        if np.asarray(self.prompt).size == 0:
            raise ValueError("empty prompt: a request must carry at least "
                             "one prompt token")
        if self.max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{self.max_new_tokens}")
        if not np.isfinite(self.arrival_time) or self.arrival_time < 0:
            raise ValueError(f"arrival_time must be finite and >= 0, got "
                             f"{self.arrival_time!r}")
        for name in ("ttft_deadline_ticks", "deadline_ticks",
                     "ttft_deadline_s", "deadline_s"):
            v = getattr(self, name)
            if v is not None and (not np.isfinite(v) or v <= 0):
                raise ValueError(f"{name} must be finite and > 0 when "
                                 f"set, got {v!r}")


def _model_batch(cfg: ArchConfig, tokens: jnp.ndarray) -> dict:
    """Token batch plus zero frontend stand-ins (vision/audio stubs)."""
    batch = {"tokens": tokens}
    B = tokens.shape[0]
    if cfg.frontend == "vision":
        batch["patch_embeds"] = jnp.zeros(
            (B, cfg.num_patches, cfg.d_model), cfg.activation_dtype)
    if cfg.frontend == "audio":
        batch["frame_embeds"] = jnp.zeros(
            (B, cfg.enc_seq, cfg.d_model), cfg.activation_dtype)
    return batch


class ServingEngine:
    """Lockstep reference engine (parity oracle for the continuous path).

    NOTE: batched generate left-pads prompts to a common length, so with
    mixed prompt lengths the pad tokens are visible to the model (seed
    behavior, kept for the oracle). For exact per-request results, call
    with a single request — the continuous engine's parity tests do.
    """

    def __init__(self, cfg: ArchConfig, params, mesh, *, max_len: int = 4096,
                 rules: shd.ShardingRules = shd.DEFAULT_RULES):
        self.cfg, self.params, self.mesh = cfg, params, mesh
        self.max_len = max_len
        self.prefill_fn, self.decode_fn = jit_serve_fns(cfg, mesh, max_len,
                                                        rules)

    def generate(self, requests: list[Request], *,
                 temperature: float = 0.0, seed: int = 0) -> list[np.ndarray]:
        """Run a batch of requests to completion.

        Returns one int32 array per request, of the *actual* generated
        length: up to and including the EOS token when ``eos_id`` fires,
        ``max_new_tokens`` otherwise (no trailing zero padding).
        """
        cfg = self.cfg
        B = len(requests)
        lp = max(len(r.prompt) for r in requests)
        over = max(lp + r.max_new_tokens for r in requests)
        if over > self.max_len:
            # Non-windowed KV rings would silently truncate the context.
            raise ValueError(f"prompt+max_new ({over}) exceeds "
                             f"max_len {self.max_len}")
        # Left-pad prompts to a common length (pad id 0).
        prompts = np.zeros((B, lp), np.int32)
        for i, r in enumerate(requests):
            prompts[i, lp - len(r.prompt):] = r.prompt
        batch = _model_batch(cfg, jnp.asarray(prompts))
        with self.mesh:
            logits, cache = self.prefill_fn(self.params, batch)
            key = jax.random.PRNGKey(seed)
            max_new = max(r.max_new_tokens for r in requests)
            out = np.zeros((B, max_new), np.int32)
            lengths = np.zeros(B, np.int64)
            done = np.zeros(B, bool)
            tok = self._sample(logits, temperature, key)
            for t in range(max_new):
                tok_np = np.asarray(tok[:, 0])
                for i, r in enumerate(requests):
                    if done[i]:
                        continue
                    out[i, t] = tok_np[i]
                    lengths[i] += 1
                    if (t + 1 >= r.max_new_tokens
                            or int(tok_np[i]) == r.eos_id):
                        done[i] = True
                if done.all():
                    break
                key, sub = jax.random.split(key)
                logits, cache = self.decode_fn(self.params, cache, tok)
                tok = self._sample(logits, temperature, sub)
        return [out[i, :lengths[i]] for i in range(B)]

    @staticmethod
    def _sample(logits, temperature: float, key) -> jnp.ndarray:
        logits = logits[:, -1, :]
        if temperature <= 0.0:
            return jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        g = jax.random.categorical(key, logits / temperature)
        return g.astype(jnp.int32)[:, None]


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------


def _macro_decode(params, cache, last_tok, active, rids, gen, eos_ids,
                  max_new, *, cfg: ArchConfig, num_ticks: int,
                  temperature: float, seed: int, fault_guard: bool = True):
    """K decode ticks as one jitted ``lax.scan`` over the slot pool.

    The serving decode hot loop, fully device-resident: per tick the pool
    runs one masked ``api.decode_step`` (drained slots are an exact state
    passthrough), sampling happens on device keyed per (seed, rid,
    token-index), and a slot that hits EOS or its ``max_new`` budget
    mid-macro-step is masked for the remaining ticks. The host receives
    only the (K, S) int32 token buffer plus (K, S) emitted and fault
    flags — one sync per K ticks instead of an (S, vocab) logits pull
    per token.

    Fault lane (DESIGN.md §10, ``fault_guard``): after each tick's
    ``decode_step`` the per-slot finiteness of the freshly written decode
    state AND the logits row is checked on device. A non-finite slot does
    not emit (its sampled token is garbage), is masked from the remaining
    ticks exactly like an EOS hit, and is flagged in the (K, S) fault
    plane — which rides the token-buffer pull the host already does, so
    detection costs zero extra host syncs and ``host_syncs_per_token``
    stays <= 1/K. Both checks reduce per slot only (shard-local under a
    slot-sharded pool): no collectives enter the §8 decode contract.

    last_tok/active/rids/gen/eos_ids/max_new are (S,) vectors; ``gen``
    counts tokens already emitted per slot (the prefill-sampled first
    token included), which is exactly the sampling ``idx`` of the *next*
    token — so the stream is byte-identical for every K.
    """
    def tick(carry, _):
        cache, last_tok, active, gen = carry
        logits, cache = api.decode_step(params, cfg, cache,
                                        last_tok[:, None], active)
        row = logits[:, -1, :]
        tok = sampling.sample_tokens(row, rids, gen,
                                     temperature=temperature, seed=seed)
        if fault_guard:
            ok = api.slot_state_finite(cfg, cache) & jnp.all(
                jnp.isfinite(row.astype(jnp.float32)), axis=-1)
            faulted = active & jnp.logical_not(ok)
        else:
            ok = jnp.ones_like(active)
            faulted = jnp.zeros_like(active)
        emitted = active & ok
        tok = jnp.where(emitted, tok, last_tok)
        gen = gen + emitted.astype(jnp.int32)
        hit = emitted & sampling.stop_hit(tok, gen, eos_ids, max_new)
        active = emitted & jnp.logical_not(hit)
        return (cache, tok, active, gen), (tok, emitted, faulted)

    (cache, _, _, _), (toks, em, flt) = jax.lax.scan(
        tick, (cache, last_tok, active, gen), None, length=num_ticks)
    return cache, toks, em, flt


def pool_bytes(cache) -> dict:
    """Bytes of a pooled decode cache (arrays or shapes) by kind of state:
    ``ssm_state`` and ``conv_tails`` (Mamba carries), ``kv_rings``,
    ``linear_state`` (the linear kinds' (S, z)); kinds it lacks are left
    out."""
    a, m = getattr(cache, "attn", None), getattr(cache, "ssm", None)
    parts = {"ssm_state": m and m.h, "conv_tails": m and m.conv,
             "kv_rings": a and (a.k, a.v), "linear_state": a and (a.s, a.z)}
    out = {}
    for kind, tree in parts.items():
        n = sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
                for x in jax.tree.leaves(tree))
        if n:
            out[kind] = n
    return out


def _bucket_len(n: int, lo: int, cap: int) -> int:
    """Smallest pow-2 >= max(n, lo), capped at ``cap`` (>= n always)."""
    b = lo
    while b < n:
        b *= 2
    return min(b, cap) if cap >= n else n


@dataclasses.dataclass
class RequestStats:
    rid: int
    arrival: float                   # ticks
    prompt_len: int = 0
    slot: int | None = None          # pool slot served in (last, if retried)
    admitted: float | None = None    # prefill started (ticks)
    admitted_wall: float | None = None  # prefill started (engine clock)
    first_token: float | None = None
    finished: float | None = None
    first_token_wall: float | None = None
    arrival_wall: float | None = None
    finish_reason: str | None = None  # sampling.FINISH_REASONS; None = live
    retries: int = 0                 # fault-quarantine re-admissions so far
    prefix_cached: bool = False      # seeded from the prefix cache (§11)
    prefix_tokens: int = 0           # prompt tokens reused from a snapshot

    @property
    def ttft_ticks(self) -> float | None:
        """Ticks to first token — None until one is emitted (a request
        cancelled/shed/expired pre-emission has no TTFT, by design: it
        must drop out of the percentiles rather than read as 0)."""
        if self.first_token is None:
            return None
        return self.first_token - self.arrival

    @property
    def ttft_s(self) -> float | None:
        if self.first_token_wall is None or self.arrival_wall is None:
            return None
        return self.first_token_wall - self.arrival_wall


@dataclasses.dataclass
class ServingMetrics:
    """Counters the engine updates every tick; ``summary()`` aggregates.

    Units: *ticks* are the engine's logical clock (one scheduling decision
    = one tick; backend-independent, what CI trends on); *wall* is host
    ``time.perf_counter()`` seconds (meaningful on TPU only). Counters are
    per engine lifetime unless noted.
    """

    num_slots: int = 0          # pool size the engine was built with (slots)
    macro_ticks: int = 1        # K: decode ticks per jitted dispatch (ticks)
    slot_shards: int = 1        # data-axis pool shards in effect (count)
    ticks: int = 0              # engine clock: scheduling decisions (ticks)
    decode_ticks: int = 0       # ticks that ran a pool decode step (ticks)
    prefill_ticks: int = 0      # ticks that ran a prefill chunk (ticks)
    tokens_generated: int = 0   # decode tokens emitted to requests (tokens)
    prompt_tokens: int = 0      # prompt tokens absorbed by prefill (tokens)
    requests_completed: int = 0  # requests finished (EOS or budget) (count)
    queue_depth_sum: int = 0    # sum of ready-queue depth per tick (req*ticks)
    queue_depth_max: int = 0    # peak ready-queue depth (requests)
    occupancy_sum: int = 0      # sum of live slots per tick (slots*ticks)
    # Hot-loop sync cadence. decode_dispatches counts jitted macro-step
    # calls (one per K decode ticks, whole pool — never per slot or per
    # shard); host_syncs counts blocking device->host pulls in the decode
    # loop (the (K, S) token buffer, one per dispatch). Prefill first-token
    # pulls are tracked separately (prefill_token_syncs): they are one
    # int32 scalar per admitted request, off the per-token hot loop.
    decode_dispatches: int = 0  # jitted K-tick macro-step calls (count)
    # Macro-steps dispatched while a request stood ready and a slot was
    # free: the admissions the interleave policy (decode_ticks_per_prefill)
    # held back behind a decode dispatch.
    decode_dispatches_while_ready: int = 0  # (count)
    host_syncs: int = 0         # blocking device->host pulls, decode (count)
    prefill_token_syncs: int = 0  # first-token scalar pulls at admit (count)
    bucket_hits: int = 0        # fallback prefill reusing a bucket (count)
    bucket_misses: int = 0      # first compile of a bucket length (count)
    # Fault-tolerance counters (DESIGN.md §10). requests_terminated counts
    # EVERY terminal request (any finish_reason); requests_completed stays
    # the successful subset (eos | length). finish_reasons is the per-
    # reason breakdown; fault_events records each quarantine as
    # {"rid", "slot", "tick"} (the chaos harness joins these against its
    # injection log to measure detection latency).
    requests_terminated: int = 0   # requests reaching any terminal state
    finish_reasons: dict = dataclasses.field(  # reason -> count
        default_factory=dict)
    faults_detected: int = 0    # non-finite slots quarantined (count)
    fault_retries: int = 0      # re-admissions after a quarantine (count)
    fault_retries_succeeded: int = 0  # retried requests ending eos|length
    # Prefix-cache + paged-pool instrumentation (DESIGN.md §11). The page
    # gauges mirror the host allocator; 0 everywhere when unpaged.
    prefix_hits: int = 0        # admissions seeded from the prefix cache
    prefix_tokens_reused: int = 0  # prompt tokens skipped via snapshots
    num_pages: int = 0          # paged-pool size in pages (0 = unpaged)
    pages_in_use: int = 0       # gauge: pages currently allocated
    pages_peak: int = 0         # high-water mark of pages_in_use
    fault_events: list = dataclasses.field(  # per-quarantine records
        default_factory=list)
    # Durability counters (DESIGN.md §12). tokens_replayed counts post-
    # restore tokens that were regenerated on device but deduplicated
    # against the journal (verified byte-equal, not re-delivered);
    # checkpoints_written counts atomic engine checkpoints.
    tokens_replayed: int = 0    # journal-deduped regenerated tokens (count)
    checkpoints_written: int = 0  # atomic checkpoints written (count)
    # Speculative decoding counters (DESIGN.md §13). Proposed counts every
    # draft token offered to the verifier in a counted (non-faulted, slot-
    # active) round; accepted counts those that survived the accept test.
    # Emitted tokens exceed accepted ones — each round also emits a
    # corrected-or-bonus token — which is why tokens_per_dispatch can beat
    # macro_ticks even at acceptance < 1.
    speculative: bool = False   # engine is in draft-verify mode
    spec_gamma: int = 0         # draft tokens per round (0 = non-spec)
    draft_tokens_proposed: int = 0  # draft tokens offered to the verifier
    draft_tokens_accepted: int = 0  # draft tokens accepted
    # Device bytes of the slot pool by kind of decode state, from its
    # shapes at construction (pool_bytes): what the pool adds to the
    # weights in the device's memory.
    pool_bytes: dict = dataclasses.field(default_factory=dict)
    # Injectable time source (satellite of DESIGN.md §12): every wall-
    # clock read in the engine goes through this, so deadline tests use a
    # fake clock and journal timestamps are replayable.
    clock: Callable[[], float] = time.perf_counter
    wall_start: float | None = None  # engine construction time (wall)
    per_request: dict = dataclasses.field(  # rid -> RequestStats
        default_factory=dict)

    def __post_init__(self):
        if self.wall_start is None:
            self.wall_start = self.clock()

    def sample(self, queue_depth: int, occupancy: int):
        self.queue_depth_sum += queue_depth
        self.queue_depth_max = max(self.queue_depth_max, queue_depth)
        self.occupancy_sum += occupancy

    def summary(self) -> dict:
        wall = max(self.clock() - self.wall_start, 1e-9)
        ttfts = sorted(s.ttft_ticks for s in self.per_request.values()
                       if s.ttft_ticks is not None)
        ttfts_s = sorted(s.ttft_s for s in self.per_request.values()
                         if s.ttft_s is not None)
        # Split TTFT by prefix-cache seeding — the §11 win the prefix-cache
        # tests assert on (cached admissions skip prefill work).
        ttfts_c = sorted(s.ttft_ticks for s in self.per_request.values()
                         if s.ttft_ticks is not None and s.prefix_cached)
        ttfts_w = sorted(s.ttft_ticks for s in self.per_request.values()
                         if s.ttft_ticks is not None and not s.prefix_cached)

        def pct(xs, q):
            return xs[min(int(q * len(xs)), len(xs) - 1)] if xs else None

        t = max(self.ticks, 1)
        return {
            "ticks": self.ticks,
            "decode_ticks": self.decode_ticks,
            "prefill_ticks": self.prefill_ticks,
            "requests_completed": self.requests_completed,
            "tokens_generated": self.tokens_generated,
            "prompt_tokens": self.prompt_tokens,
            "macro_ticks": self.macro_ticks,
            "slot_shards": self.slot_shards,
            "decode_dispatches": self.decode_dispatches,
            "host_syncs": self.host_syncs,
            "prefill_token_syncs": self.prefill_token_syncs,
            "host_syncs_per_token":
                self.host_syncs / max(self.tokens_generated, 1),
            "tokens_per_dispatch":
                self.tokens_generated / max(self.decode_dispatches, 1),
            "dispatches_per_decode_tick":
                self.decode_dispatches / max(self.decode_ticks, 1),
            "bucket_hits": self.bucket_hits,
            "bucket_misses": self.bucket_misses,
            "requests_terminated": self.requests_terminated,
            "finish_reasons": dict(self.finish_reasons),
            # Degraded-mode rates are over terminated requests (0.0 when
            # nothing terminated yet — never a division by zero, even for
            # a run whose every request was cancelled before emitting).
            "shed_rate": self.finish_reasons.get("shed", 0)
            / max(self.requests_terminated, 1),
            "deadline_miss_rate": self.finish_reasons.get("deadline", 0)
            / max(self.requests_terminated, 1),
            "faults_detected": self.faults_detected,
            "fault_retries": self.fault_retries,
            "fault_retries_succeeded": self.fault_retries_succeeded,
            "tokens_replayed": self.tokens_replayed,
            "checkpoints_written": self.checkpoints_written,
            "pool_bytes": dict(self.pool_bytes),
            "wall_s": wall,
            "decode_tokens_per_s": self.tokens_generated / wall,
            "total_tokens_per_s":
                (self.tokens_generated + self.prompt_tokens) / wall,
            "mean_queue_depth": self.queue_depth_sum / t,
            "max_queue_depth": self.queue_depth_max,
            "mean_slot_occupancy":
                self.occupancy_sum / (t * max(self.num_slots, 1)),
            "ttft_ticks_p50": pct(ttfts, 0.50),
            "ttft_ticks_p95": pct(ttfts, 0.95),
            "ttft_s_p50": pct(ttfts_s, 0.50),
            "ttft_s_p95": pct(ttfts_s, 0.95),
            "prefix_hits": self.prefix_hits,
            "prefix_tokens_reused": self.prefix_tokens_reused,
            "ttft_cached_ticks_p50": pct(ttfts_c, 0.50),
            "ttft_cached_ticks_p95": pct(ttfts_c, 0.95),
            "ttft_cold_ticks_p50": pct(ttfts_w, 0.50),
            "ttft_cold_ticks_p95": pct(ttfts_w, 0.95),
            "num_pages": self.num_pages,
            "pages_in_use": self.pages_in_use,
            "pages_peak": self.pages_peak,
            "speculative": self.speculative,
            "spec_gamma": self.spec_gamma,
            "draft_tokens_proposed": self.draft_tokens_proposed,
            "draft_tokens_accepted": self.draft_tokens_accepted,
            "draft_acceptance_rate": self.draft_tokens_accepted
            / max(self.draft_tokens_proposed, 1),
        }


EngineMetrics = ServingMetrics   # pre-§8 name, kept for callers


@dataclasses.dataclass
class _Slot:
    """One live sequence in the decode pool."""

    rid: int
    req: Request
    last_tok: int
    tokens: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Prefill:
    """An admission in flight: prompt being absorbed chunk-by-chunk."""

    rid: int
    req: Request
    slot: int
    cache: object                    # per-request (batch=1) decode cache
    offset: int = 0                  # prompt tokens absorbed so far
    prefix_offset: int = 0           # pre-embedded frontend rows absorbed
    logits: object | None = None     # (1, 1, V) — full prefix-cache hit
    draft: object | None = None      # batch=1 draft cache (speculative mode)


class Scheduler:
    """Owns the slot pool and the admission queue.

    Policy: FIFO admission order; the *slot* a request lands in is chosen
    shard-aware — the free slot whose data shard currently serves the
    fewest live requests (ties break to the lowest slot id, which with a
    single shard reduces to the pre-§8 lowest-free-slot policy). At most
    one prefill is in flight (chunked, so a long prompt yields to decode
    ticks between chunks); decode and prefill strictly interleave per
    ``decode_ticks_per_prefill`` when both have work.

    Shard awareness: slot->shard ownership is *static* — with S slots over
    N shards, shard k owns the contiguous block [k*S/N, (k+1)*S/N), the
    same split GSPMD applies to the slot-sharded pool cache — so admission
    and eviction never migrate state across shards, only overwrite
    shard-local slot blocks. Balancing admissions across shards keeps
    every data shard's masked decode work even under partial load. Token
    streams never depend on the slot (or shard) chosen: sampling is keyed
    on (seed, rid, token-index) only.
    """

    def __init__(self, serving: ServingConfig, slot_shards: int = 1):
        self.serving = serving
        self.slot_shards = max(slot_shards, 1)
        self.slots_per_shard = serving.num_slots // self.slot_shards
        self.free: list[int] = list(range(serving.num_slots))
        self.active: dict[int, _Slot] = {}
        self.waiting: collections.deque = collections.deque()  # (rid, req)
        self.ready: collections.deque = collections.deque()
        self._decode_since_prefill = serving.decode_ticks_per_prefill

    def shard_of(self, slot: int) -> int:
        """Static owner shard of ``slot`` (GSPMD contiguous-block split)."""
        return slot // self.slots_per_shard

    def submit(self, rid: int, req: Request) -> list[tuple[int, "Request"]]:
        """Enqueue a request; returns the (rid, req) pairs shed to make
        room (``shed_oldest`` policy — the engine terminates them with
        ``finish_reason="shed"``).

        Overload behavior when the queue sits at ``max_queue``
        (DESIGN.md §10): ``reject_new`` raises :class:`QueueFullError`
        with the depth spelled out (nothing is mutated — the caller keeps
        the request); ``shed_oldest`` drops the longest-waiting queued
        request; ``queue_wait`` admits unconditionally and relies on the
        engine's queue-age sweep to shed stale requests instead."""
        shed: list[tuple[int, Request]] = []
        depth = len(self.waiting) + len(self.ready)
        if self.serving.max_queue and depth >= self.serving.max_queue:
            policy = self.serving.overload_policy
            if policy == "reject_new":
                raise QueueFullError(
                    f"admission queue full: {depth} queued >= max_queue "
                    f"{self.serving.max_queue} (overload_policy="
                    f"'reject_new'; retry later, or configure "
                    f"'shed_oldest' / 'queue_wait' to degrade instead)",
                    queue_depth=depth, max_queue=self.serving.max_queue)
            if policy == "shed_oldest":
                victim = self.pop_oldest()
                if victim is not None:
                    shed.append(victim)
            # queue_wait: admit; the age sweep sheds laggards by deadline.
        self.waiting.append((rid, req))
        # Keep ordered by (arrival, rid) so a late submission with an
        # earlier arrival_time cannot be head-of-line blocked.
        self.waiting = collections.deque(
            sorted(self.waiting, key=lambda t: (t[1].arrival_time, t[0])))
        return shed

    def pop_oldest(self) -> tuple[int, Request] | None:
        """Remove and return the longest-waiting queued request — ready
        queue first (already arrived, FIFO head is oldest), else the
        earliest-arriving waiting entry. None if nothing is queued."""
        if self.ready:
            return self.ready.popleft()
        if self.waiting:
            return self.waiting.popleft()
        return None

    def cancel(self, rid: int) -> Request | None:
        """Remove a still-queued request (ready or waiting); returns its
        Request, or None if ``rid`` is not queued here (it may be in a
        slot, mid-prefill, or already terminal — the engine checks)."""
        for q in (self.ready, self.waiting):
            for item in q:
                if item[0] == rid:
                    q.remove(item)
                    return item[1]
        return None

    def poll_arrivals(self, now: float):
        while self.waiting and self.waiting[0][1].arrival_time <= now:
            self.ready.append(self.waiting.popleft())

    def next_admission(self, slot_ok=None):
        """Pop the request to admit next, reserving a slot — or None.

        The slot comes from the least-loaded shard (see class docstring);
        request order itself stays strictly FIFO. ``slot_ok(slot, req)``
        further filters candidate slots (the paged pool gates on its
        shard's free pages — DESIGN.md §11); when no slot qualifies the
        head request stays queued (head-of-line waits for pages to free,
        preserving FIFO admission order)."""
        if not self.ready or not self.free:
            return None
        rid, req = self.ready[0]
        cands = (self.free if slot_ok is None
                 else [s for s in self.free if slot_ok(s, req)])
        if not cands:
            return None
        self.ready.popleft()
        load = [0] * self.slot_shards
        for slot in self.active:
            load[self.shard_of(slot)] += 1
        slot = min(cands, key=lambda s: (load[self.shard_of(s)], s))
        self.free.remove(slot)
        return rid, req, slot

    def evict(self, slot: int):
        del self.active[slot]
        self.free.append(slot)
        self.free.sort()

    @property
    def queue_depth(self) -> int:
        return len(self.ready)

    @property
    def occupancy(self) -> int:
        return len(self.active)

    def want_prefill(self, prefill_inflight: bool) -> bool:
        """Interleave policy: prefill only after enough decode ticks, unless
        there is no decode work at all."""
        has_work = prefill_inflight or (bool(self.ready) and bool(self.free))
        if not has_work:
            return False
        if not self.active:
            return True
        return (self._decode_since_prefill
                >= self.serving.decode_ticks_per_prefill)

    def note_decode(self):
        self._decode_since_prefill += 1

    def note_prefill(self):
        self._decode_since_prefill = 0


class ContinuousServingEngine:
    """Continuous-batching engine over a fixed decode-slot pool.

    Usage::

        eng = ContinuousServingEngine(cfg, params, mesh,
                                      serving=ServingConfig(num_slots=4))
        rids = [eng.submit(r) for r in requests]
        outs, metrics = eng.run()          # rid -> np.ndarray of tokens

    or drive it tick-by-tick with :meth:`step` for external event loops.
    Time is a logical tick counter; request ``arrival_time`` is in ticks,
    letting benchmarks replay arrival traces deterministically on any
    backend. With ``macro_ticks`` K > 1 a decode dispatch covers K ticks:
    the host replays the returned (K, S) token buffer tick-by-tick so
    streaming callbacks, TTFT-in-ticks, queue-depth samples, and eviction
    all happen at exact per-tick granularity — only admission waits for a
    macro-step boundary (the K tradeoff; token streams are K-invariant).

    Compile-cache note: the decode hot loop is exactly one jitted
    macro-step entry. The chunked prefill path — every decoder-only
    config: all attention kinds *and* the ssm/hybrid scan-carry families
    (DESIGN.md §9) — compiles once per distinct chunk length (bounded by
    ``prefill_chunk``); the non-chunkable fallback (modality frontends)
    compiles once per pow-2 length *bucket* (right-padded, masked exactly
    via ``true_len``), except encdec which has no masked form and stays
    per-length. :meth:`jit_cache_entries` exposes the live counts (CI
    budgets them).

    Sharding (DESIGN.md §8): the slot pool — cache, control vectors, and
    the (K, S) token buffers — shards over the mesh ``data`` axis per
    ``serving.slot_shards``; slot->shard ownership is static and the
    decode macro-step contains no cross-shard collectives
    (:meth:`decode_hlo` exposes the compiled HLO the contract test greps).
    Params replicate over the slot axes (``sharding.serving_param_rules``).
    Token streams for a fixed trace are byte-identical across mesh shapes:
    sampling is keyed on (seed, rid, token-index), never on placement.
    """

    def __init__(self, cfg: ArchConfig, params, mesh, *,
                 serving: ServingConfig = ServingConfig(),
                 rules: shd.ShardingRules = shd.DEFAULT_RULES,
                 fault_injector=None, prefix_cache=None,
                 journal: journal_lib.Journal | None = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.cfg, self.params, self.mesh = cfg, params, mesh
        self.serving = serving
        self.rules = rules
        # Injectable wall-clock source — every perf_counter read in the
        # engine and its metrics goes through this (fake clocks make the
        # wall-deadline tests deterministic; DESIGN.md §12 satellite).
        self._clock = clock
        # Chaos harness hook (serving.faults.FaultInjector) — test/bench
        # only; None in production. The engine consults it for slot
        # corruption, injected cancellations, and arrival delays.
        self._injector = fault_injector
        S, L = serving.num_slots, serving.max_len
        # Resolve the slot-pool sharding once (static for the engine's
        # lifetime): shard the pool over the `data` mesh axis per
        # serving.slot_shards, falling back to a replicated pool when
        # num_slots is not divisible (recorded like the rule-engine
        # divisibility fallback; surfaced in metrics/bench rows).
        self.slot_shard_fallbacks: list = []
        slot_axes, self.slot_shards = shd.pool_slot_axes(
            mesh, rules, S, serving.slot_shards,
            self.slot_shard_fallbacks)
        self.sched = Scheduler(serving, self.slot_shards)
        # Paged slot memory (DESIGN.md §11): only KV-ring kinds page —
        # constant-state (linear SLAY / SSM carry) decode state is O(1)
        # per slot, so a page_size request is a silent no-op for them.
        self._paged = bool(serving.page_size) and api.supports_paging(cfg)
        self.page_pool: pages_lib.PagePool | None = None
        if self._paged:
            lp = L // serving.page_size      # config validates divisibility
            num_pages = serving.num_pages or S * lp
            if num_pages % self.slot_shards:
                raise ValueError(
                    f"num_pages={num_pages} must divide evenly over "
                    f"{self.slot_shards} slot shards (the page dim shards "
                    f"in the same static blocks as the slot dim — §8)")
            self.page_pool = pages_lib.PagePool(
                S, num_pages, serving.page_size, lp,
                shards=self.slot_shards)
        # Content-addressed prefix cache (DESIGN.md §11): seeding relies
        # on chunked-prefill state continuation, so encdec (the one
        # non-chunkable family) never caches. A shared instance can be
        # passed in (warm-up engine populates, measured engine hits).
        self.prefix_cache = prefix_cache
        if self.prefix_cache is None and serving.prefix_cache_bytes:
            self.prefix_cache = prefix_lib.PrefixCache(
                serving.prefix_cache_bytes)
        if not api.supports_chunked_prefill(cfg):
            self.prefix_cache = None
        self._pfx_refs: dict[int, prefix_lib.PrefixEntry] = {}
        self.metrics = ServingMetrics(
            num_slots=serving.num_slots, macro_ticks=serving.macro_ticks,
            slot_shards=self.slot_shards,
            num_pages=self.page_pool.num_pages if self._paged else 0,
            clock=clock)
        self.tick = 0
        self._next_rid = 0
        self._outputs: dict[int, list] = {}
        self._prefill: _Prefill | None = None
        # Durability layer (DESIGN.md §12). With a journal attached, every
        # admission/token/termination is journaled (fsync once per engine
        # step — macro-step granularity, hot-loop cadence untouched) and
        # checkpoint_every_ticks > 0 adds periodic atomic checkpoints in
        # the journal's directory. ``_replay_until[rid]`` marks how many
        # tokens of a restored request are already journaled: regenerated
        # tokens below that index are verified byte-equal and deduped
        # instead of re-delivered.
        self.journal = journal
        self._ckpt_dir = (os.path.dirname(os.path.abspath(journal.path))
                          if journal is not None else None)
        self._last_ckpt_tick = 0
        self._replay_until: dict[int, int] = {}
        self.recovery: dict | None = None
        self._audit = serving.debug_audit or (
            os.environ.get("REPRO_DEBUG_AUDIT", "") not in ("", "0"))
        self._chunkable = api.supports_chunked_prefill(cfg)
        self._bucketable = (serving.prefill_buckets
                            and api.supports_masked_prefill(cfg))
        self._seen_buckets: set[int] = set()

        # Speculative decoding (DESIGN.md §13): the engine holds TWO slot
        # pools over one params pytree — the linear SLAY draft pool
        # (constant-state, never paged) and the exact verifier pool (the
        # ordinary `self.pool`, paged or not). Draft and verifier slots
        # move in lockstep: admission prefills and installs both, decode
        # runs spec rounds, eviction resets both.
        self._spec = bool(serving.speculative)
        self.draft_cfg: ArchConfig | None = None
        self.draft_pool = None
        if self._spec:
            if not api.supports_speculative(cfg):
                raise ValueError(
                    f"speculative decoding needs a verifier config with "
                    f"api.supports_speculative (a non-windowed exact "
                    f"quadratic attention kind); got attn_kind="
                    f"{cfg.attn_kind!r}, family={cfg.family!r}")
            self.draft_cfg = api.draft_config(cfg)
            params = api.ensure_draft_params(self.draft_cfg, params)
            self.params = params
            self.metrics.speculative = True
            self.metrics.spec_gamma = serving.spec_gamma
            # Mutually exclusive with the prefix cache (config validates
            # the byte-budget knob; a shared instance is dropped too): a
            # prefix snapshot seeds only the verifier ring — the draft
            # pool would have no matching state to seed from.
            self.prefix_cache = None

        # Param shapes/axes: in speculative mode the draft config's tree
        # is the superset (same transformer weights + the tiny `slay`
        # projection entry the verifier ignores), so it drives placement.
        axes_cfg = self.draft_cfg if self._spec else cfg
        axes = api.param_axes(axes_cfg)
        p_abs = api.abstract_params(axes_cfg)
        # Params replicate over the slot (data) axes at serving time —
        # FSDP-sharded weights would all-gather inside every decode tick
        # (DESIGN.md §8 zero-collective contract).
        p_sh = shd.logical_to_sharding(mesh, shd.serving_param_rules(rules),
                                       p_abs, axes)
        page_kw = dict(page_size=serving.page_size if self._paged else 0,
                       num_pages=(self.page_pool.num_pages
                                  if self._paged else 0),
                       shards=self.slot_shards)
        c_abs = api.abstract_cache(cfg, S, L, **page_kw)
        self.metrics.pool_bytes = pool_bytes(c_abs)
        c_sh = shd.serving_cache_sharding(
            mesh, rules, c_abs, num_slots=S,
            slot_shards=serving.slot_shards,
            num_pages=self.page_pool.num_pages if self._paged else None)
        # Per-slot control vectors and the (K, S) token/emitted buffers
        # carry the same slot sharding as the pool cache.
        v_sh = shd.serving_vector_sharding(mesh, rules, num_slots=S,
                                           slot_shards=serving.slot_shards)
        buf_sh = shd.serving_vector_sharding(
            mesh, rules, num_slots=S, slot_shards=serving.slot_shards,
            leading=1)
        rep_sh = jax.sharding.NamedSharding(mesh,
                                            jax.sharding.PartitionSpec())
        self._abstract = (p_abs, c_abs)
        self._cache_sharding = c_sh   # restore() re-places checkpointed pools
        with mesh:
            self.pool = jax.device_put(api.init_cache(cfg, S, L, **page_kw),
                                       c_sh)
            self.params = jax.device_put(params, p_sh)
        # Host mirrors of the per-slot decode vectors fed to the jitted
        # macro-step. The replay loop applies the *same* emit/EOS/budget
        # logic as the device scan, so mirrors and device state never
        # diverge and nothing needs to be read back besides the token
        # buffer itself.
        self._last_tok = np.zeros(S, np.int32)
        self._active = np.zeros(S, bool)
        self._rids = np.zeros(S, np.int32)
        self._gen = np.zeros(S, np.int32)
        self._eos = np.full(S, -1, np.int32)
        self._maxn = np.zeros(S, np.int32)
        # The decode hot loop: one jitted K-tick macro-step for the whole
        # pool (donated cache, fused sampling, masked drained slots). Every
        # input/output carries the slot sharding, so the scan partitions
        # into independent per-shard slot blocks — no collectives (§8).
        # in_slot_pool lets the decode kernel, which GSPMD cannot
        # partition, run per shard under shard_map.
        def engine_macro_decode(params, cache, *vectors):
            return _macro_decode(params, cache, *vectors, cfg=cfg,
                                 num_ticks=serving.macro_ticks,
                                 temperature=serving.temperature,
                                 seed=serving.seed,
                                 fault_guard=serving.fault_guard)

        self._macro_fn = jax.jit(
            shd.in_slot_pool(engine_macro_decode, mesh, slot_axes),
            in_shardings=(p_sh, c_sh) + (v_sh,) * 6,
            out_shardings=(c_sh, buf_sh, buf_sh, buf_sh),
            donate_argnums=(1,))
        # Speculative decode hot loop (§13): K draft-verify rounds per
        # dispatch over both pools, (K, gamma+1, S) token/emitted/fault
        # buffers plus a (K, S) accepted-count plane — still one host
        # pull per dispatch, same zero-collective slot partitioning.
        self._draft_sharding = None
        self._spec_fn = None
        if self._spec:
            d_abs = api.abstract_cache(self.draft_cfg, S, L)
            d_sh = shd.serving_cache_sharding(
                mesh, rules, d_abs, num_slots=S,
                slot_shards=serving.slot_shards)
            self._draft_sharding = d_sh
            self._draft_abstract = d_abs
            buf2_sh = shd.serving_vector_sharding(
                mesh, rules, num_slots=S, slot_shards=serving.slot_shards,
                leading=2)
            with mesh:
                self.draft_pool = jax.device_put(
                    api.init_cache(self.draft_cfg, S, L), d_sh)
            draft_cfg = self.draft_cfg

            def engine_spec_macro(params, draft_pool, pool, *vectors):
                return speculative.spec_macro(
                    params, draft_pool, pool, *vectors, draft_cfg=draft_cfg,
                    cfg=cfg, num_rounds=serving.macro_ticks,
                    gamma=serving.spec_gamma,
                    temperature=serving.temperature, seed=serving.seed,
                    fault_guard=serving.fault_guard)

            self._spec_fn = jax.jit(
                shd.in_slot_pool(engine_spec_macro, mesh, slot_axes),
                in_shardings=(p_sh, d_sh, c_sh) + (v_sh,) * 6,
                out_shardings=(d_sh, c_sh, buf2_sh, buf2_sh, buf2_sh,
                               buf_sh),
                donate_argnums=(1, 2))
        def engine_sample_first(logits, rids, idxs):
            return sampling.sample_tokens(logits, rids, idxs,
                                          temperature=serving.temperature,
                                          seed=serving.seed)

        self._sample_fn = jax.jit(engine_sample_first)
        # Slot ops: slot index is a traced scalar -> one compile each, and
        # out-shardings pinned to the pool's (slot-stable, never reshards).
        # The batch=1 source cache is pinned replicated, so a write_slot is
        # a shard-local donated dynamic-update: only the owning shard's
        # block changes, the others alias their input bytes.
        if self._paged:
            # Paged variants additionally take the host allocator's
            # PageState snapshot (write: post-alloc mapping to install;
            # reset: post-free mapping — the op zeroes the slot's pages
            # via the *old* device mapping first, so a freed page always
            # hands zeros to its next owner).
            pg_sh = c_sh.pages

            def engine_write_slot(pool, src, i, st):
                return api.write_slot(cfg, pool, src, i, st)

            def engine_reset_slot(pool, i, st):
                return api.reset_slot(cfg, pool, i, st)

            self._write_fn = jax.jit(
                engine_write_slot,
                in_shardings=(c_sh, rep_sh, None, pg_sh),
                out_shardings=c_sh, donate_argnums=(0,))
            self._reset_fn = jax.jit(
                engine_reset_slot,
                in_shardings=(c_sh, None, pg_sh), out_shardings=c_sh,
                donate_argnums=(0,))
        else:
            def engine_write_slot(pool, src, i):
                return api.write_slot(cfg, pool, src, i)

            def engine_reset_slot(pool, i):
                return api.reset_slot(cfg, pool, i)

            self._write_fn = jax.jit(
                engine_write_slot,
                in_shardings=(c_sh, rep_sh, None), out_shardings=c_sh,
                donate_argnums=(0,))
            self._reset_fn = jax.jit(
                engine_reset_slot,
                in_shardings=(c_sh, None), out_shardings=c_sh,
                donate_argnums=(0,))

        # Fault injection (chaos harness only): NaN one slot's float
        # state. Same slot-stable donated-update shape as reset_slot;
        # never compiled unless an injector actually fires.
        def engine_corrupt_slot(pool, i):
            return api.corrupt_slot(cfg, pool, i)

        self._corrupt_fn = jax.jit(
            engine_corrupt_slot, in_shardings=(c_sh, None),
            out_shardings=c_sh, donate_argnums=(0,))

        def engine_prefill_chunk(p, c, t):
            return api.prefill_chunk(cfg, p, c, t)

        # Pre-embedded prefill chunks (vision patch prefix): same donated
        # continuation, fed (1, Lc, d) rows instead of token ids — this is
        # what lets an oversized vision prompt absorb its patch prefix
        # chunk-by-chunk instead of being rejected at admission (§11).
        def engine_prefill_chunk_embeds(p, c, e):
            return api.prefill_chunk(cfg, p, c, None, embeds=e)

        def engine_prefill(p, b):
            return api.prefill(p, cfg, b, max_len=L)

        def engine_prefill_masked(p, b, n):
            return api.prefill(p, cfg, b, max_len=L, true_len=n)

        self._chunk_fn = jax.jit(engine_prefill_chunk, donate_argnums=(1,))
        self._chunk_embeds_fn = jax.jit(engine_prefill_chunk_embeds,
                                        donate_argnums=(1,))
        self._prefill_fn = jax.jit(engine_prefill)
        self._prefill_masked_fn = jax.jit(engine_prefill_masked)
        if self._spec:
            # Draft-pool twins of the slot/prefill ops. The draft pool is
            # never paged (constant-state — nothing to page), so these are
            # always the unpaged shapes.
            dcfg = self.draft_cfg
            d_sh = self._draft_sharding

            def engine_draft_write_slot(pool, src, i):
                return api.write_slot(dcfg, pool, src, i)

            def engine_draft_reset_slot(pool, i):
                return api.reset_slot(dcfg, pool, i)

            self._dwrite_fn = jax.jit(
                engine_draft_write_slot,
                in_shardings=(d_sh, rep_sh, None), out_shardings=d_sh,
                donate_argnums=(0,))
            self._dreset_fn = jax.jit(
                engine_draft_reset_slot,
                in_shardings=(d_sh, None), out_shardings=d_sh,
                donate_argnums=(0,))

            def engine_draft_prefill_chunk(p, c, t):
                return api.prefill_chunk(dcfg, p, c, t)

            def engine_draft_prefill(p, b):
                return api.prefill(p, dcfg, b, max_len=L)

            def engine_draft_prefill_masked(p, b, n):
                return api.prefill(p, dcfg, b, max_len=L, true_len=n)

            self._dchunk_fn = jax.jit(engine_draft_prefill_chunk,
                                      donate_argnums=(1,))
            self._dprefill_fn = jax.jit(engine_draft_prefill)
            self._dprefill_masked_fn = jax.jit(engine_draft_prefill_masked)
        if journal is not None and journal.nbytes == 0:
            # Fresh journal: stamp the sampling/geometry contract once.
            # restore() refuses a journal whose stream keying or sampling
            # params differ — regenerated tokens would not be byte-equal.
            journal.append({
                "t": "meta", "v": journal_lib.JOURNAL_VERSION,
                "stream_key_v": sampling.STREAM_KEY_VERSION,
                "seed": serving.seed, "temperature": serving.temperature,
                "num_slots": S, "max_len": L,
                # §13: sampled spec streams consume different substreams
                # than plain decode, so restore must not cross modes (and
                # gamma changes which indices take the bonus base draw).
                "speculative": self._spec,
                "spec_gamma": serving.spec_gamma if self._spec else 0})
            journal.flush()

    # -- submission ---------------------------------------------------------

    def submit(self, req: Request) -> int:
        """Queue a request; returns its request id.

        Raises typed :class:`AdmissionError` subclasses
        (DESIGN.md §10): :class:`RequestTooLargeError` when prefix +
        prompt + max_new overflows the slot ring (the KV ring would
        silently overwrite live context otherwise), and
        :class:`QueueFullError` when the queue is at ``max_queue`` under
        the ``reject_new`` overload policy. Under ``shed_oldest`` the
        longest-waiting queued request is terminated with
        ``finish_reason="shed"`` instead; under ``queue_wait`` admission
        always succeeds and staleness is bounded by the queue-age sweep.
        A rejected request is never enqueued and consumes no rid."""
        if self._injector is not None:
            delay = self._injector.arrival_delay_for()
            if delay:
                req = dataclasses.replace(
                    req, arrival_time=req.arrival_time + delay)
        prefix = (self.cfg.num_patches
                  if self.cfg.frontend == "vision" else 0)
        need = prefix + len(req.prompt) + req.max_new_tokens
        if self._spec:
            # Verify overshoot (§13): a round writes up to spec_gamma ring
            # rows past the accept horizon before rolling back, so the
            # slot needs that much extra headroom to never wrap onto live
            # context.
            need += self.serving.spec_gamma
        # Capacity is per config kind (api.context_capacity): None means
        # unbounded — constant-state decode (linear SLAY, SSM carries) or
        # an exactly-wrapping windowed ring — so an oversized prompt (e.g.
        # a linear-attention vision request whose patch prefix + prompt
        # exceeds max_len) is admitted and absorbed chunk-by-chunk (§11).
        # Unbounded admission still requires chunked prefill: the
        # non-chunkable fallback runs one full-length prefill that cannot
        # exceed the ring.
        cap = api.context_capacity(self.cfg, self.serving.max_len)
        if cap is None and not (self._chunkable
                                and self.serving.prefill_chunk):
            cap = self.serving.max_len
        if cap is not None and need > cap:
            raise RequestTooLargeError(
                f"request does not fit its decode slot: "
                + (f"{prefix} vision-prefix patches + " if prefix else "")
                + f"{len(req.prompt)} prompt + {req.max_new_tokens} "
                f"max_new "
                + (f"+ {self.serving.spec_gamma} spec verify headroom "
                   if self._spec else "")
                + f"= {need} > context capacity {cap} "
                f"(the cache ring would overwrite live context; shorten "
                f"the prompt/max_new_tokens or raise ServingConfig."
                f"max_len)",
                queue_depth=self.sched.queue_depth,
                max_queue=self.serving.max_queue)
        rid = self._next_rid
        shed = self.sched.submit(rid, req)   # may raise QueueFullError
        self._next_rid += 1
        st = RequestStats(rid=rid, arrival=req.arrival_time,
                          prompt_len=len(req.prompt))
        st.arrival_wall = self._clock()
        self.metrics.per_request[rid] = st
        self._outputs[rid] = []
        if self.journal is not None:
            prompt = np.asarray(req.prompt, np.int32).reshape(-1)
            self.journal.append({
                "t": "admit", "rid": rid,
                "prompt": [int(x) for x in prompt],
                "digest": prefix_lib.token_digest(prompt).hex(),
                "arrival": float(req.arrival_time),
                "max_new": int(req.max_new_tokens),
                "eos": int(req.eos_id),
                "ttft_deadline_ticks": req.ttft_deadline_ticks,
                "deadline_ticks": req.deadline_ticks,
                "ttft_deadline_s": req.ttft_deadline_s,
                "deadline_s": req.deadline_s,
                "ts": self._clock()})
        for srid, sreq in shed:
            self._terminate(srid, sreq, "shed")
        if self.journal is not None:
            # Admission durability: fsync before the caller learns the
            # rid. Off the decode hot loop, so the §7 cadence is intact.
            self.journal.flush()
        return rid

    # -- engine ticks -------------------------------------------------------

    def step(self) -> bool:
        """One scheduling decision: a prefill chunk (one tick) or a decode
        macro-step (K ticks, replayed per tick). Returns False when fully
        idle.

        Tick anatomy (DESIGN.md §10): arrivals poll, then the lifecycle
        sweep (deadline expiry + queue-age shedding), then chaos
        injections if an injector is attached, then the scheduling
        decision proper. The sweep also runs after every replayed decode
        tick, so deadlines are enforced at per-tick granularity even
        under K-tick macro-stepping."""
        sched = self.sched
        with TraceAnnotation("engine.step") as span:
            sched.poll_arrivals(self.tick)
            kind = "idle"
            with self.mesh:
                self._lifecycle_sweep()
                if self._injector is not None:
                    self._apply_injections()
                if sched.want_prefill(self._prefill is not None):
                    kind = "prefill"
                    self.metrics.sample(sched.queue_depth, sched.occupancy)
                    self._prefill_tick()
                    sched.note_prefill()
                    self.metrics.prefill_ticks += 1
                    self.tick += 1
                elif sched.active:
                    kind = "decode"
                    if sched.ready and sched.free:
                        self.metrics.decode_dispatches_while_ready += 1
                    if self._spec:
                        self._decode_spec()
                    else:
                        self._decode_macro()
                else:
                    self.metrics.sample(sched.queue_depth, sched.occupancy)
                    self.tick += 1
            span.set_metadata(kind=kind)
            self.metrics.ticks = self.tick
            if self.journal is not None:
                # One fsync per engine step = macro-step granularity: the
                # K-tick decode dispatch batch-journals its emissions here.
                self.journal.flush()
                every = self.serving.checkpoint_every_ticks
                if every and self.tick - self._last_ckpt_tick >= every:
                    self.checkpoint()
        return kind != "idle" or bool(sched.waiting)

    def run(self, requests: list[Request] | None = None, *,
            max_ticks: int | None = None):
        """Drive to completion. Returns (outputs, metrics summary) where
        outputs maps rid -> int32 array of that request's generated tokens
        (actual length: through EOS inclusive, or max_new_tokens)."""
        for r in requests or ():
            self.submit(r)
        limit = max_ticks if max_ticks is not None else 10_000_000
        while self.tick < limit:
            if not (self.sched.active or self.sched.ready
                    or self.sched.waiting or self._prefill):
                break
            self.step()
        if self.journal is not None:
            self.journal.flush()
        if self._audit:
            self._debug_audit()
        outs = {rid: np.asarray(toks, np.int32)
                for rid, toks in self._outputs.items()}
        summary = self.metrics.summary()
        summary["journal_bytes"] = (self.journal.nbytes
                                    if self.journal is not None else 0)
        # Leak contract (CI asserts these on every bench row): a drained
        # engine holds zero live slots and an empty queue — every
        # admission path, including quarantine retries, cancels, and
        # deadline evictions, returned its slot to the pool.
        summary["final_occupancy"] = self.sched.occupancy
        summary["final_queue_depth"] = self.sched.queue_depth
        # Paged pool: every exit path returned its pages to the free list
        # ("pages leaked = 0" — the CI bench contract asserts this).
        summary["final_pages_in_use"] = (
            self.page_pool.pages_in_use() if self.page_pool else 0)
        return outs, summary

    # -- durability: checkpoint / restore (DESIGN.md §12) -------------------

    def checkpoint(self) -> str:
        """Write an atomic engine checkpoint next to the journal.

        Journal first, checkpoint second: the flush guarantees every
        token the checkpointed mirrors count as emitted is on disk, so a
        restored resident slot's ``gen`` can never run ahead of its
        journaled stream. Called automatically every
        ``serving.checkpoint_every_ticks`` ticks (macro-step boundaries),
        or explicitly."""
        if self.journal is None:
            raise RuntimeError(
                "checkpointing requires the engine to have a journal "
                "(ContinuousServingEngine(..., journal=Journal(path)))")
        with TraceAnnotation("engine.checkpoint"):
            self.journal.flush()
            state = checkpoint_lib.snapshot_engine(self)
            path = checkpoint_lib.checkpoint_path(self._ckpt_dir, self.tick)
            checkpoint_lib.save(path, state)
        self._last_ckpt_tick = self.tick
        self.metrics.checkpoints_written += 1
        return path

    @classmethod
    def restore(cls, path: str, cfg: ArchConfig, params, mesh, *,
                serving: ServingConfig = ServingConfig(),
                rules: shd.ShardingRules = shd.DEFAULT_RULES,
                fault_injector=None, prefix_cache=None,
                clock: Callable[[], float] = time.perf_counter,
                on_token: Callable[[int, int], None] | None = None,
                on_finish: Callable[[int, str], None] | None = None,
                redeliver: bool = False) -> "ContinuousServingEngine":
        """Rebuild an engine from a durability directory (journal +
        checkpoints) after a crash, with byte-identical streams.

        Recovery sequence (DESIGN.md §12): tolerant journal replay (torn
        tail dropped and truncated), latest *valid* checkpoint load
        (corrupt files skipped), fresh engine construction, then
        ``_apply_restore``: device pool + mirrors + allocator + prefix
        cache come from the checkpoint when its geometry matches this
        config; every live rid is rebuilt from its journaled admission —
        checkpoint-resident ones resume mid-stream in their slots,
        everything else re-queues in arrival order and re-prefills from
        scratch. Because sampling is keyed on (seed, rid, token-index),
        both paths regenerate the pre-crash tokens bit-for-bit; the
        journal horizon dedupes them (verified in ``_emit``) so streaming
        callbacks see each token exactly once. A checkpoint with a
        *different* slot count (restore onto another machine shape) is
        rejected wholesale and recovery is journal-only — streams are
        still byte-identical, only more tokens replay.

        ``on_token``/``on_finish`` attach to every restored live request;
        ``redeliver=True`` additionally re-fires them for the journaled
        prefix (and journaled terminal requests) at restore time —
        exactly-once delivery for a consumer that lost its own state with
        the process."""
        t0 = clock()
        jpath = os.path.join(path, journal_lib.JOURNAL_NAME)
        jst = journal_lib.replay(jpath)
        meta = jst.meta
        if meta is not None:
            if meta.get("stream_key_v") != sampling.STREAM_KEY_VERSION:
                raise ValueError(
                    f"journal stream keying v{meta.get('stream_key_v')} != "
                    f"engine v{sampling.STREAM_KEY_VERSION}: regenerated "
                    f"tokens would not be byte-identical; cannot resume")
            if (int(meta.get("seed", serving.seed)) != serving.seed
                    or float(meta.get("temperature", serving.temperature))
                    != serving.temperature):
                raise ValueError(
                    "journal was written under a different sampling config "
                    f"(seed={meta.get('seed')}, temperature="
                    f"{meta.get('temperature')}); restore with the same "
                    "seed/temperature or streams diverge")
            if "speculative" in meta and (
                    bool(meta["speculative"]) != bool(serving.speculative)
                    or int(meta.get("spec_gamma", 0))
                    != (serving.spec_gamma if serving.speculative else 0)):
                # §13: sampled spec streams consume tagged substreams and
                # the bonus-index pattern depends on gamma, so crossing
                # modes (or gammas) would regenerate different tokens.
                raise ValueError(
                    "journal was written under a different speculative "
                    f"config (speculative={meta['speculative']}, "
                    f"spec_gamma={meta.get('spec_gamma')}); restore with "
                    "the same speculative/spec_gamma or streams diverge")
        ck = checkpoint_lib.latest_valid(path)
        jr = journal_lib.Journal(jpath, truncate_to=jst.valid_bytes)
        eng = cls(cfg, params, mesh, serving=serving, rules=rules,
                  fault_injector=fault_injector, prefix_cache=prefix_cache,
                  journal=jr, clock=clock)
        eng._apply_restore(jst, ck, on_token=on_token, on_finish=on_finish,
                           redeliver=redeliver)
        eng.recovery["wall_s"] = clock() - t0
        return eng

    def _apply_restore(self, jst: journal_lib.JournalState,
                       ck: dict | None, *, on_token, on_finish,
                       redeliver: bool):
        S = self.serving.num_slots
        usable = (
            ck is not None
            and int(ck.get("num_slots", -1)) == S
            and int(ck.get("max_len", -1)) == self.serving.max_len
            and int(ck.get("page_size", -1))
            == (self.serving.page_size if self._paged else 0)
            and bool(ck.get("speculative", False)) == self._spec
            and int(ck.get("spec_gamma", 0))
            == (self.serving.spec_gamma if self._spec else 0))
        if usable:
            cur = jax.tree.leaves(self.pool)
            saved = ck["pool"]
            usable = (len(cur) == len(saved) and all(
                tuple(c.shape) == tuple(s.shape)
                and np.dtype(c.dtype) == np.dtype(s.dtype)
                for c, s in zip(cur, saved)))
        if usable and self._spec:
            dcur = jax.tree.leaves(self.draft_pool)
            dsaved = ck.get("draft_pool") or []
            usable = (len(dcur) == len(dsaved) and all(
                tuple(c.shape) == tuple(s.shape)
                and np.dtype(c.dtype) == np.dtype(s.dtype)
                for c, s in zip(dcur, dsaved)))
        resident: dict[int, int] = {}       # rid -> slot
        if usable:
            treedef = jax.tree.structure(self.pool)
            with self.mesh:
                self.pool = jax.device_put(
                    jax.tree.unflatten(
                        treedef, [jnp.asarray(x) for x in ck["pool"]]),
                    self._cache_sharding)
            if self._spec:
                dtree = jax.tree.structure(self.draft_pool)
                with self.mesh:
                    self.draft_pool = jax.device_put(
                        jax.tree.unflatten(
                            dtree,
                            [jnp.asarray(x) for x in ck["draft_pool"]]),
                        self._draft_sharding)
            mir = ck["mirrors"]
            self._last_tok = np.asarray(mir["last_tok"], np.int32).copy()
            self._active = np.asarray(mir["active"], bool).copy()
            self._rids = np.asarray(mir["rids"], np.int32).copy()
            self._gen = np.asarray(mir["gen"], np.int32).copy()
            self._eos = np.asarray(mir["eos"], np.int32).copy()
            self._maxn = np.asarray(mir["maxn"], np.int32).copy()
            if self.page_pool is not None and ck.get("page_pool"):
                self.page_pool.load_snapshot(ck["page_pool"])
            self.tick = int(ck["tick"])
            self.metrics.ticks = self.tick
            self._last_ckpt_tick = self.tick
            resident = {int(r): int(s) for s, r in ck["slots"].items()}
            if self.prefix_cache is not None and ck.get("prefix"):
                # Rebuild the prefix-cache index. Entries are batch=1
                # unpaged snapshots; refcounts restart at zero (live pins
                # are re-acquired when restored requests re-admit).
                pstruct = jax.tree.structure(
                    api.init_cache(self.cfg, 1, self.serving.max_len))
                for ent in ck["prefix"]:
                    try:
                        cache = jax.tree.unflatten(
                            pstruct,
                            [jnp.asarray(x) for x in ent["cache"]])
                        lg = (jnp.asarray(ent["logits"])
                              if ent["logits"] is not None else None)
                        self.prefix_cache.insert(ent["tokens"], cache,
                                                 logits=lg, copy=False)
                    except Exception:
                        continue  # shape-incompatible entry: skip, a miss
        nr = int(ck["next_rid"]) if usable else 0
        if jst.admits:
            nr = max(nr, max(jst.admits) + 1)
        self._next_rid = nr
        # Validate checkpoint residency against the journal: a resident
        # slot needs a journaled admission, no terminal record, agreeing
        # mirrors, and a journaled stream at least as long as its ``gen``
        # (guaranteed by the flush-before-checkpoint order; anything else
        # falls back to re-admission from scratch).
        for rid, slot in list(resident.items()):
            toks = jst.tokens.get(rid, [])
            ok = (rid in jst.admits and rid not in jst.fins
                  and 0 <= slot < S and bool(self._active[slot])
                  and int(self._rids[slot]) == rid
                  and 0 < int(self._gen[slot]) <= len(toks))
            if not ok:
                resident.pop(rid)
        now_wall = self._clock()
        for rid in sorted(jst.admits):
            a = jst.admits[rid]
            toks = [int(t) for t in jst.tokens.get(rid, [])]
            st = RequestStats(rid=rid, arrival=float(a["arrival"]),
                              prompt_len=len(a["prompt"]))
            st.arrival_wall = now_wall   # wall deadlines re-anchor here
            st.retries = int(jst.retries.get(rid, 0))
            self.metrics.per_request[rid] = st
            self._outputs[rid] = list(toks)
            fin = jst.fins.get(rid)
            if fin is not None:
                # Terminal before the crash: the stream is fixed from the
                # journal; not re-admitted, not re-counted in lifetime
                # counters (they describe this engine's work).
                st.finish_reason = fin
                st.finished = self.tick
                continue
            req = Request(
                np.asarray(a["prompt"], np.int32),
                max_new_tokens=int(a["max_new"]),
                eos_id=int(a["eos"]),
                arrival_time=float(a["arrival"]),
                on_token=on_token, on_finish=on_finish,
                ttft_deadline_ticks=a.get("ttft_deadline_ticks"),
                deadline_ticks=a.get("deadline_ticks"),
                ttft_deadline_s=a.get("ttft_deadline_s"),
                deadline_s=a.get("deadline_s"))
            if toks:
                self._replay_until[rid] = len(toks)
            slot = resident.get(rid)
            if slot is not None:
                gen = int(self._gen[slot])
                rec = _Slot(rid, req, int(self._last_tok[slot]),
                            tokens=list(toks[:gen]))
                self.sched.active[slot] = rec
                self.sched.free.remove(slot)
                st.slot = slot
                st.admitted = self.tick
                st.first_token = self.tick
                st.first_token_wall = now_wall
            else:
                self.sched.waiting.append((rid, req))
        self.sched.waiting = collections.deque(
            sorted(self.sched.waiting,
                   key=lambda t: (t[1].arrival_time, t[0])))
        # Clear mirror/allocator state for slots the journal suffix shows
        # were evicted (or whose residency failed validation) after the
        # checkpoint. No device op needed: inactive slots are masked
        # passthrough in the decode scan, write_slot fully overwrites on
        # reuse, and unmapped pages gather as zeros.
        for slot in range(S):
            if self._active[slot] and slot not in self.sched.active:
                self._active[slot] = False
                if (self.page_pool is not None
                        and self.page_pool.slot_pages(slot)):
                    self.page_pool.free_slot(slot)
        if self.page_pool is not None:
            self._note_pages()
        if redeliver:
            for rid in sorted(self._outputs):
                if on_token is not None:
                    for tok in self._outputs[rid]:
                        on_token(rid, int(tok))
                fin = jst.fins.get(rid)
                if fin is not None and on_finish is not None:
                    on_finish(rid, fin)
        self.recovery = {
            "checkpoint_used": bool(usable),
            "checkpoint_tick": int(ck["tick"]) if usable else None,
            "journal_records": jst.records,
            "journal_dropped_tail": jst.dropped_tail,
            "resident_resumed": len(self.sched.active),
            "requeued": len(self.sched.waiting),
            "terminal_from_journal": len(jst.fins),
        }

    def _debug_audit(self):
        """Invariant audit (``ServingConfig.debug_audit`` or the
        ``REPRO_DEBUG_AUDIT`` env var), run at the end of every
        :meth:`run`: the page allocator's free/owned partition must be
        consistent and every prefix-cache refcount must correspond to a
        live engine pin — a leaked pin would block eviction forever."""
        if self.page_pool is not None:
            self.page_pool.check()
        if self.prefix_cache is not None:
            refs = self.prefix_cache.live_refs()
            pins = len(self._pfx_refs)
            assert refs == pins, (
                f"prefix-cache refcount leak: {refs} live refs vs {pins} "
                f"engine pins")

    # -- internals ----------------------------------------------------------

    def _need_rows(self, req: Request) -> int:
        """Context rows a request occupies: frontend prefix + prompt +
        decode budget (what the page allocator sizes a slot's pages by) —
        plus, in speculative mode, ``spec_gamma`` verify-overshoot rows
        (§13: a round's ring writes reach past the accept horizon before
        rolling back; the pages for those rows are allocated up front so
        rollback never touches the page table and nothing can leak)."""
        prefix = (self.cfg.num_patches
                  if self.cfg.frontend == "vision" else 0)
        need = prefix + len(req.prompt) + req.max_new_tokens
        if self._spec:
            need += self.serving.spec_gamma
        return need

    def _note_pages(self):
        self.metrics.pages_in_use = self.page_pool.pages_in_use()
        self.metrics.pages_peak = self.page_pool.pages_peak

    def _seed_from_prefix(self, pf: _Prefill, C: int):
        """Seed an admission from the longest cached prompt prefix (§11).

        A full-prompt hit skips prefill entirely (the stored last-token
        logits sample token 0 — sampling is keyed (seed, rid, idx), never
        on how the state was produced). A proper-prefix hit deep-copies
        the snapshot (the donating chunk jit would invalidate the cached
        buffers) and chunk-prefills only the suffix — hits land on chunk
        multiples only, so the suffix chunk schedule is identical to a
        cold prefill's and the stream stays byte-identical."""
        entry = self.prefix_cache.lookup(pf.req.prompt, chunk=C)
        if entry is None:
            return
        self.prefix_cache.acquire(entry)
        self._pfx_refs[pf.rid] = entry
        st = self.metrics.per_request[pf.rid]
        st.prefix_cached = True
        st.prefix_tokens = entry.length
        self.metrics.prefix_hits += 1
        self.metrics.prefix_tokens_reused += entry.length
        if entry.length == len(pf.req.prompt):
            pf.cache = entry.cache   # write_slot does not donate its src
            pf.logits = entry.logits
        else:
            pf.cache = prefix_lib.tree_copy(entry.cache)
        pf.offset = entry.length
        pf.prefix_offset = (self.cfg.num_patches
                            if self.cfg.frontend == "vision" else 0)

    def _admit(self, C: int) -> _Prefill | None:
        """Pop the next request into a reserved slot and start its prefill
        (cache, pages, prefix seeding), or None when none can be admitted."""
        slot_ok = None
        if self.page_pool is not None:
            slot_ok = (lambda s, r:
                       self.page_pool.can_alloc(s, self._need_rows(r)))
        admission = self.sched.next_admission(slot_ok)
        if admission is None:
            return None
        rid, req, slot = admission
        pf = _Prefill(rid, req, slot,
                      api.init_cache(self.cfg, 1, self.serving.max_len))
        if self._spec:
            # Dual-cache residency (§13): the draft twin absorbs the
            # same prompt so both regimes enter decode in agreement.
            pf.draft = api.init_cache(self.draft_cfg, 1,
                                      self.serving.max_len)
        if self.page_pool is not None:
            # Host-side reservation only: the device PageState learns
            # the mapping at install (write_slot) time, so an
            # admission cancelled mid-prefill frees host-side with no
            # device op — and freshly freed pages are zeros (reset
            # zeroes them via the old mapping), never stale bytes.
            self.page_pool.alloc(slot, self._need_rows(req))
            self._note_pages()
        if self.prefix_cache is not None and self._chunkable and C:
            self._seed_from_prefix(pf, C)
        self._prefill = pf
        st = self.metrics.per_request[rid]
        st.admitted = self.tick
        st.admitted_wall = self._clock()
        st.slot = slot
        return pf

    def _prefill_chunk(self, pf: _Prefill, prompt: np.ndarray, C: int):
        """Dispatch the next piece of a prompt's prefill: one chunk, or the
        whole prompt on the non-chunkable paths. Returns its logits (None
        after a vision-prefix chunk, which yields none)."""
        if self._chunkable and C:
            patches = (self.cfg.num_patches
                       if self.cfg.frontend == "vision" else 0)
            if pf.prefix_offset < patches:
                # Vision patch prefix, absorbed as pre-embedded rows chunk
                # by chunk — this is why an oversized vision prompt no
                # longer needs (and is no longer bounded by) a full-length
                # prefill (§11 bugfix).
                n = min(C, patches - pf.prefix_offset)
                emb = jnp.zeros((1, n, self.cfg.d_model),
                                self.cfg.activation_dtype)
                _, pf.cache = self._chunk_embeds_fn(self.params, pf.cache,
                                                    emb)
                pf.prefix_offset += n
                return None
            chunk = prompt[pf.offset:pf.offset + C]
            toks = jnp.asarray(chunk[None, :])
            logits, pf.cache = self._chunk_fn(self.params, pf.cache, toks)
            if self._spec:
                _, pf.draft = self._dchunk_fn(self.params, pf.draft, toks)
            pf.offset += len(chunk)
            if (self.prefix_cache is not None and pf.offset % C == 0
                    and pf.offset < len(prompt)):
                # Chunk-boundary snapshot: a future prompt sharing this
                # prefix seeds from it and prefills only its suffix.
                self.prefix_cache.insert(prompt[:pf.offset], pf.cache)
        elif self._bucketable:
            # Non-chunkable fallback, bucketed: right-pad to the pow-2
            # bucket and mask exactly via true_len — one compile per
            # bucket instead of one per distinct prompt length. The cap
            # leaves room for the vision patch prefix: prefix + bucket
            # must fit the KV ring or the ring write would drop real
            # prefix rows still inside the validity horizon (submit()
            # rejects any request whose prefix + prompt + max_new exceeds
            # max_len, so the cap can never undershoot the prompt here).
            prefix = (self.cfg.num_patches
                      if self.cfg.frontend == "vision" else 0)
            Lb = _bucket_len(len(prompt), self.serving.prefill_bucket_min,
                             self.serving.max_len - prefix)
            if Lb in self._seen_buckets:
                self.metrics.bucket_hits += 1
            else:
                self._seen_buckets.add(Lb)
                self.metrics.bucket_misses += 1
            padded = np.zeros(Lb, np.int32)
            padded[:len(prompt)] = prompt
            batch = _model_batch(self.cfg, jnp.asarray(padded[None, :]))
            tl = jnp.full((1,), prefix + len(prompt), jnp.int32)
            logits, pf.cache = self._prefill_masked_fn(self.params, batch,
                                                       tl)
            if self._spec:
                _, pf.draft = self._dprefill_masked_fn(self.params, batch,
                                                       tl)
            pf.offset = len(prompt)
        else:
            batch = _model_batch(self.cfg, jnp.asarray(prompt[None, :]))
            logits, pf.cache = self._prefill_fn(self.params, batch)
            if self._spec:
                _, pf.draft = self._dprefill_fn(self.params, batch)
            pf.offset = len(prompt)
        return logits

    def _prefill_tick(self):
        pf = self._prefill
        C = self.serving.prefill_chunk
        if pf is None:
            with TraceAnnotation("engine.admit") as span:
                pf = self._admit(C)
                if pf is None:
                    return
                span.set_metadata(rid=pf.rid, slot=pf.slot)
        prompt = np.asarray(pf.req.prompt, np.int32)
        logits = pf.logits
        if logits is None:           # not None: a full prefix-cache hit
            with TraceAnnotation("engine.prefill.chunk", rid=pf.rid,
                                 offset=pf.offset):
                logits = self._prefill_chunk(pf, prompt, C)
        if pf.offset < len(prompt):
            return                       # more chunks; decode may interleave
        # Prompt fully absorbed: sample the first token on device (same
        # fused sampler as the decode loop, idx 0) and install the request
        # into its pool slot. One int32 scalar crosses to host.
        with TraceAnnotation("engine.prefill.first_token", rid=pf.rid):
            tok0 = int(self._sample_fn(
                logits[:, -1, :], jnp.full((1,), pf.rid, jnp.int32),
                jnp.zeros((1,), jnp.int32))[0])
        self.metrics.prefill_token_syncs += 1
        with TraceAnnotation("engine.prefill.install", rid=pf.rid,
                             slot=pf.slot):
            self._install(pf, prompt, logits, tok0)

    def _install(self, pf: _Prefill, prompt: np.ndarray, logits, tok0: int):
        """Move a fully prefilled request into its pool slot: the slot
        write, the host mirrors, and its first token's emission."""
        C = self.serving.prefill_chunk
        req = pf.req
        if (self.prefix_cache is not None and self._chunkable and C
                and pf.logits is None):
            # Full-prompt entry with last-token logits: a repeat of this
            # exact prompt becomes a zero-prefill admission.
            self.prefix_cache.insert(prompt, pf.cache,
                                     logits=logits[:, -1:, :])
        if self.page_pool is not None:
            self.pool = self._write_fn(self.pool, pf.cache,
                                       jnp.int32(pf.slot),
                                       self.page_pool.device_vectors())
        else:
            self.pool = self._write_fn(self.pool, pf.cache,
                                       jnp.int32(pf.slot))
        if self._spec:
            self.draft_pool = self._dwrite_fn(self.draft_pool, pf.draft,
                                              jnp.int32(pf.slot))
        self._prefill = None
        self.metrics.prompt_tokens += (
            len(prompt) - self.metrics.per_request[pf.rid].prefix_tokens)
        slot_rec = _Slot(pf.rid, req, tok0)
        self.sched.active[pf.slot] = slot_rec
        self._last_tok[pf.slot] = tok0
        self._active[pf.slot] = True
        self._rids[pf.slot] = pf.rid
        self._gen[pf.slot] = 1
        self._eos[pf.slot] = req.eos_id
        self._maxn[pf.slot] = req.max_new_tokens
        self._emit(slot_rec, tok0, 0)
        if tok0 == req.eos_id or req.max_new_tokens <= 1:
            self._finish(pf.slot,
                         sampling.finish_reason_of(tok0, req.eos_id))

    def _decode_macro(self):
        """One decode dispatch = K device ticks for the whole pool; replay
        the token buffer on host at per-tick granularity so streaming
        callbacks, TTFT/queue-depth samples, and eviction stay exact."""
        with TraceAnnotation("engine.decode.launch"):
            self.pool, toks, em, flt = self._macro_fn(
                self.params, self.pool, jnp.asarray(self._last_tok),
                jnp.asarray(self._active), jnp.asarray(self._rids),
                jnp.asarray(self._gen), jnp.asarray(self._eos),
                jnp.asarray(self._maxn))
        self.metrics.decode_dispatches += 1
        with TraceAnnotation("engine.decode.wait"):
            toks, em, flt = (np.asarray(toks), np.asarray(em),
                             np.asarray(flt))  # ONE host sync per K ticks
        self.metrics.host_syncs += 1
        with TraceAnnotation("engine.decode.replay"):
            self._replay_macro(toks, em, flt)

    def _replay_macro(self, toks, em, flt):
        """Replay a macro-step's (K, S) buffers tick by tick."""
        for t in range(toks.shape[0]):
            if not (em[t].any() or flt[t].any()):
                break   # every slot drained mid-macro-step; suffix unused
            self.sched.poll_arrivals(self.tick)
            self.metrics.sample(self.sched.queue_depth,
                                self.sched.occupancy)
            # Quarantine before emission: a faulted slot never emitted at
            # this tick (its sampled token is garbage by definition).
            for slot in np.nonzero(flt[t])[0]:
                if int(slot) in self.sched.active:
                    self._quarantine(int(slot))
            for slot in list(self.sched.active):
                if not em[t, slot]:
                    continue
                rec = self.sched.active[slot]
                tk = int(toks[t, slot])
                rec.last_tok = tk
                self._last_tok[slot] = tk
                self._gen[slot] += 1
                self._emit(rec, tk, int(self._gen[slot]) - 1)
                if (tk == rec.req.eos_id
                        or len(rec.tokens) >= rec.req.max_new_tokens):
                    self._finish(slot, sampling.finish_reason_of(
                        tk, rec.req.eos_id))
            self.sched.note_decode()
            self.metrics.decode_ticks += 1
            self.tick += 1
            self.metrics.ticks = self.tick
            # Sweep *after* the tick's emissions: EOS beats a deadline
            # expiring on the same tick; an on_token cancel has already
            # removed its slot from residency by the time we get here.
            self._lifecycle_sweep()

    def _decode_spec(self):
        """One speculative dispatch = K draft-verify rounds (§13); replay
        the (K, gamma+1, S) token buffer on host one round per tick.

        A round is one engine tick (one scheduling quantum) emitting up to
        gamma+1 tokens per slot, so the per-tick contracts — streaming
        callbacks in emission order, quarantine before emission, the
        lifecycle sweep after — run exactly like the plain macro-step's
        replay; only the tokens-per-tick arithmetic changes. Still one
        host sync per dispatch."""
        with TraceAnnotation("engine.decode.launch"):
            self.draft_pool, self.pool, toks, em, flt, acc = self._spec_fn(
                self.params, self.draft_pool, self.pool,
                jnp.asarray(self._last_tok), jnp.asarray(self._active),
                jnp.asarray(self._rids), jnp.asarray(self._gen),
                jnp.asarray(self._eos), jnp.asarray(self._maxn))
        self.metrics.decode_dispatches += 1
        with TraceAnnotation("engine.decode.wait"):
            toks, em, flt, acc = (np.asarray(toks), np.asarray(em),
                                  np.asarray(flt), np.asarray(acc))
        self.metrics.host_syncs += 1      # ONE host sync per K rounds
        with TraceAnnotation("engine.decode.replay"):
            self._replay_spec(toks, em, flt, acc)

    def _replay_spec(self, toks, em, flt, acc):
        """Replay a speculative dispatch's buffers one round per tick."""
        G = self.serving.spec_gamma
        for r in range(toks.shape[0]):
            if not (em[r].any() or flt[r].any()):
                break   # every slot drained mid-dispatch; suffix unused
            self.sched.poll_arrivals(self.tick)
            self.metrics.sample(self.sched.queue_depth,
                                self.sched.occupancy)
            # Quarantine before emission — a faulted round emitted nothing
            # (device side: its verifier rewound to the round start, its
            # draft kept the snapshot; the flag rides row 0 only).
            for slot in np.nonzero(flt[r, 0])[0]:
                if int(slot) in self.sched.active:
                    self._quarantine(int(slot))
            # Acceptance accounting: acc[r, s] >= 0 is a counted round
            # (slot active, not faulted) that offered G drafts.
            for slot in range(acc.shape[1]):
                v = int(acc[r, slot])
                if v >= 0:
                    self.metrics.draft_tokens_proposed += G
                    self.metrics.draft_tokens_accepted += v
            for j in range(toks.shape[1]):
                if not em[r, j].any():
                    break   # per-slot emissions are a j-prefix: done
                for slot in list(self.sched.active):
                    if not em[r, j, slot]:
                        continue
                    rec = self.sched.active.get(slot)
                    if rec is None:   # cancelled by an earlier callback
                        continue
                    tk = int(toks[r, j, slot])
                    rec.last_tok = tk
                    self._last_tok[slot] = tk
                    self._gen[slot] += 1
                    self._emit(rec, tk, int(self._gen[slot]) - 1)
                    if (tk == rec.req.eos_id
                            or len(rec.tokens) >= rec.req.max_new_tokens):
                        self._finish(slot, sampling.finish_reason_of(
                            tk, rec.req.eos_id))
            self.sched.note_decode()
            self.metrics.decode_ticks += 1
            self.tick += 1
            self.metrics.ticks = self.tick
            self._lifecycle_sweep()

    def jit_cache_entries(self) -> dict:
        """Live jit-cache entry counts per engine entry point — the
        recompile budget the hot-loop tests assert on (the decode hot loop
        must stay at exactly one entry; prefill entries are bounded by the
        chunk/bucket counts, never by the number of distinct prompt
        lengths).

        Counting relies on jax's ``_cache_size`` introspection; entry
        points it cannot measure are omitted (callers treat a missing key
        as "unmeasurable", not as a budget violation)."""
        fns = {"macro_decode": self._macro_fn, "sample": self._sample_fn,
               "write": self._write_fn, "reset": self._reset_fn,
               "corrupt": self._corrupt_fn, "chunk": self._chunk_fn,
               "chunk_embeds": self._chunk_embeds_fn,
               "prefill": self._prefill_fn,
               "prefill_masked": self._prefill_masked_fn}
        if self._spec:
            fns.update({"spec_macro": self._spec_fn,
                        "draft_write": self._dwrite_fn,
                        "draft_reset": self._dreset_fn,
                        "draft_chunk": self._dchunk_fn})
        out = {}
        for name, fn in fns.items():
            try:
                out[name] = int(fn._cache_size())
            except Exception:         # pragma: no cover — jax internals
                continue
        return out

    def decode_hlo(self) -> str:
        """Compiled HLO of the decode macro-step at the engine's shapes and
        shardings — the §8 zero-collective contract surface: on a slot-
        sharded mesh the op table must contain no collective opcodes
        (``repro.analysis.hlo.parse_hlo`` + ``check_no_collectives`` is
        how the sharded-parity tests assert it — parsed opcodes, not
        substring greps). Compiles (cached) but never executes."""
        p_abs, c_abs = self._abstract
        S = self.serving.num_slots
        i32 = jax.ShapeDtypeStruct((S,), jnp.int32)
        b1 = jax.ShapeDtypeStruct((S,), jnp.bool_)
        with self.mesh:
            if self._spec:
                lowered = self._spec_fn.lower(
                    p_abs, self._draft_abstract, c_abs, i32, b1, i32, i32,
                    i32, i32)
            else:
                lowered = self._macro_fn.lower(p_abs, c_abs, i32, b1, i32,
                                               i32, i32, i32)
        return lowered.compile().as_text()

    def contract_lowerings(self) -> dict:
        """Compiled HLO text + expected donated-leaf count for every
        ``donate_argnums`` engine entry point — the DESIGN.md §14 contract
        surface the HLO analyzer checks (zero collectives, no host
        callbacks, and every donated leaf actually aliased in
        ``input_output_alias``; XLA drops unusable donations *silently*,
        which would double the pool's HBM footprint with no error).

        Returns ``{name: (compiled_hlo_text, expected_donated_leaves)}``.
        ``write_slot``/``reset_slot`` are only lowered for unpaged pools —
        the paged variants take a live host ``PageState`` snapshot that
        has no static abstract here. Compiles (cached) but never
        executes."""
        p_abs, c_abs = self._abstract
        S, L = self.serving.num_slots, self.serving.max_len
        i32 = jax.ShapeDtypeStruct((S,), jnp.int32)
        b1 = jax.ShapeDtypeStruct((S,), jnp.bool_)
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        cache_leaves = len(jax.tree.leaves(c_abs))
        out = {}
        with self.mesh:
            if self._spec:
                lowered = self._spec_fn.lower(
                    p_abs, self._draft_abstract, c_abs, i32, b1, i32, i32,
                    i32, i32)
                donated = cache_leaves + len(
                    jax.tree.leaves(self._draft_abstract))
            else:
                lowered = self._macro_fn.lower(p_abs, c_abs, i32, b1, i32,
                                               i32, i32, i32)
                donated = cache_leaves
            out["macro_decode"] = (lowered.compile().as_text(), donated)
            if not self._paged:
                src_abs = api.abstract_cache(self.cfg, 1, L)
                lowered = self._write_fn.lower(c_abs, src_abs, scalar)
                out["write_slot"] = (lowered.compile().as_text(),
                                     cache_leaves)
                lowered = self._reset_fn.lower(c_abs, scalar)
                out["reset_slot"] = (lowered.compile().as_text(),
                                     cache_leaves)
        return out

    def _emit(self, rec: _Slot, tok: int, idx: int):
        """Deliver one emitted token. ``idx`` is the request's token index
        (the sampling key index — 0 for the prefill-sampled first token).

        Post-restore dedup (DESIGN.md §12): tokens with ``idx`` below the
        request's journaled horizon were already delivered before the
        crash. Deterministic (seed, rid, idx) sampling regenerates them
        bit-for-bit — verified here, which *is* the byte-identity
        assertion — and they are counted as replayed, not re-journaled or
        re-delivered to callbacks."""
        st = self.metrics.per_request[rec.rid]
        out = self._outputs[rec.rid]
        if idx < self._replay_until.get(rec.rid, 0):
            if idx >= len(out) or tok != out[idx]:
                raise RuntimeError(
                    f"restore byte-identity violated: rid {rec.rid} token "
                    f"{idx} regenerated {tok} != journaled "
                    f"{out[idx] if idx < len(out) else '<missing>'}")
            rec.tokens.append(tok)
            self.metrics.tokens_generated += 1
            self.metrics.tokens_replayed += 1
            if st.first_token is None:
                st.first_token = self.tick
                st.first_token_wall = self._clock()
            return
        rec.tokens.append(tok)
        out.append(tok)
        self.metrics.tokens_generated += 1
        if st.first_token is None:
            st.first_token = self.tick
            st.first_token_wall = self._clock()
        if self.journal is not None:
            self.journal.append({"t": "tok", "rid": rec.rid,
                                 "tok": int(tok)})
        if rec.req.on_token is not None:
            rec.req.on_token(rec.rid, tok)

    def _evict_slot_state(self, slot: int):
        """Zero a slot's device state; paged pools first return its pages
        to the free list (the reset op zeroes them via the old device
        mapping, so the next owner always reads zeros — never a prior
        slot's bytes, in particular never an injected NaN)."""
        if self.page_pool is not None:
            self.page_pool.free_slot(slot)
            self._note_pages()
            self.pool = self._reset_fn(self.pool, jnp.int32(slot),
                                       self.page_pool.device_vectors())
        else:
            self.pool = self._reset_fn(self.pool, jnp.int32(slot))
        if self._spec:
            self.draft_pool = self._dreset_fn(self.draft_pool,
                                              jnp.int32(slot))

    def _finish(self, slot: int, reason: str):
        """Evict a slot-resident request into terminal state ``reason``."""
        rec = self.sched.active[slot]
        self._active[slot] = False
        # Eviction = one slot overwrite (constant-state asymmetry: O(m·dv)
        # zeros for SLAY vs an O(max_len) ring zero for KV backends).
        self._evict_slot_state(slot)
        self.sched.evict(slot)
        self._terminate(rec.rid, rec.req, reason)

    def _terminate(self, rid: int, req: Request, reason: str):
        """Stamp the single terminal state of a request — every exit path
        (natural stop, deadline, cancel, shed, fault) funnels here, so
        ``on_finish`` fires exactly once and the finish-reason breakdown
        always sums to ``requests_terminated``."""
        st = self.metrics.per_request[rid]
        st.finished = self.tick
        st.finish_reason = reason
        self._replay_until.pop(rid, None)
        if self.journal is not None:
            self.journal.append({"t": "fin", "rid": rid, "reason": reason,
                                 "tick": self.tick})
        entry = self._pfx_refs.pop(rid, None)
        if entry is not None:       # release the seeding snapshot's pin
            self.prefix_cache.release(entry)
        m = self.metrics
        m.requests_terminated += 1
        m.finish_reasons[reason] = m.finish_reasons.get(reason, 0) + 1
        if reason in ("eos", "length"):
            m.requests_completed += 1
            if st.retries:
                m.fault_retries_succeeded += 1
        if req.on_finish is not None:
            req.on_finish(rid, reason)

    def _quarantine(self, slot: int):
        """Non-finite decode state detected in ``slot`` (DESIGN.md §10):
        reset the slot and either re-admit the request *from scratch* at
        the head of the ready queue — deterministic (seed, rid, idx)
        sampling regenerates the identical stream prefix when the fault
        was transient, so a successful retry is indistinguishable from a
        fault-free run — or, with ``serving.fault_retries`` exhausted,
        terminate it with ``finish_reason="fault"``. The possibly-tainted
        emitted prefix is dropped either way."""
        rec = self.sched.active[slot]
        st = self.metrics.per_request[rec.rid]
        m = self.metrics
        m.faults_detected += 1
        m.fault_events.append({"rid": rec.rid, "slot": slot,
                               "tick": self.tick})
        self._active[slot] = False
        self._evict_slot_state(slot)
        self.sched.evict(slot)
        ent = self._pfx_refs.pop(rec.rid, None)
        if ent is not None:
            self.prefix_cache.release(ent)
        if st.retries < self.serving.fault_retries:
            st.retries += 1
            m.fault_retries += 1
            self._outputs[rec.rid] = []
            # A retry restarts the stream from index 0: void the journaled
            # prefix (replay folds a retry record into an empty token
            # list) and drop any restore-dedup horizon with it.
            self._replay_until.pop(rec.rid, None)
            if self.journal is not None:
                self.journal.append({"t": "retry", "rid": rec.rid})
            st.first_token = None
            st.first_token_wall = None
            st.prefix_cached = False
            st.prefix_tokens = 0
            # Head of the ready queue: the request already waited its
            # turn once; retry latency is one admission, not a requeue.
            self.sched.ready.appendleft((rec.rid, rec.req))
        else:
            self._terminate(rec.rid, rec.req, "fault")

    def _release_prefill_slot(self, slot: int):
        """Return a mid-prefill slot to the pool (cancel/deadline before
        install). Pages were only ever reserved host-side — the device
        PageState never learned the mapping — so freeing is host-only."""
        self.sched.free.append(slot)
        self.sched.free.sort()
        if self.page_pool is not None:
            self.page_pool.free_slot(slot)
            self._note_pages()

    # -- lifecycle: cancellation, deadlines, queue-age shedding -------------

    def cancel(self, rid: int) -> bool:
        """Cancel a request anywhere in its lifecycle: still queued,
        mid-chunked-prefill, or slot-resident (including mid-macro-step —
        the replay loop re-checks slot residency per buffered tick, so a
        cancelled slot's remaining device ticks are dropped on the floor).
        Returns True if the request was live and is now terminated with
        ``finish_reason="cancelled"``; False if ``rid`` is unknown or
        already terminal (idempotent — ``on_finish`` never fires twice)."""
        st = self.metrics.per_request.get(rid)
        if st is None or st.finish_reason is not None:
            return False
        req = self.sched.cancel(rid)
        if req is not None:                  # still queued
            self._terminate(rid, req, "cancelled")
            return True
        pf = self._prefill
        if pf is not None and pf.rid == rid:  # admission in flight
            self._prefill = None
            self._release_prefill_slot(pf.slot)
            self._terminate(rid, pf.req, "cancelled")
            return True
        for slot, rec in self.sched.active.items():
            if rec.rid == rid:               # slot-resident
                with self.mesh:
                    self._finish(slot, "cancelled")
                return True
        return False                         # pragma: no cover — unreachable

    def _lifecycle_sweep(self):
        """Deadline expiry plus ``queue_wait`` age shedding, applied to
        every live request (queued, mid-prefill, slot-resident).

        Runs at the top of each engine tick and again after every
        *replayed* tick of a decode macro-step, so deadlines hold at
        per-tick granularity even with K > 1. Expiry is strict
        (``now - arrival > deadline``) and the decode replay processes a
        tick's emissions before sweeping it, so a natural stop landing on
        the deadline tick finishes ``eos``/``length`` — EOS wins.
        TTFT deadlines only bind while no token has been emitted yet."""
        now = self.tick
        wall = self._clock()

        def expired(req: Request, st: RequestStats) -> bool:
            age = now - req.arrival_time
            wage = (wall - st.arrival_wall
                    if st.arrival_wall is not None else 0.0)
            if st.first_token is None:
                if (req.ttft_deadline_ticks is not None
                        and age > req.ttft_deadline_ticks):
                    return True
                if (req.ttft_deadline_s is not None
                        and wage > req.ttft_deadline_s):
                    return True
            if req.deadline_ticks is not None and age > req.deadline_ticks:
                return True
            if req.deadline_s is not None and wage > req.deadline_s:
                return True
            return False

        sched = self.sched
        per = self.metrics.per_request
        for q in (sched.ready, sched.waiting):
            for item in list(q):
                rid, req = item
                if expired(req, per[rid]):
                    q.remove(item)
                    self._terminate(rid, req, "deadline")
        if (self.serving.overload_policy == "queue_wait"
                and self.serving.queue_wait_ticks):
            # queue_wait admits unconditionally at submit; staleness is
            # bounded here instead — queued longer than the budget = shed.
            W = self.serving.queue_wait_ticks
            for q in (sched.ready, sched.waiting):
                for item in list(q):
                    rid, req = item
                    if now - req.arrival_time > W:
                        q.remove(item)
                        self._terminate(rid, req, "shed")
        pf = self._prefill
        if pf is not None and expired(pf.req, per[pf.rid]):
            self._prefill = None
            self._release_prefill_slot(pf.slot)
            self._terminate(pf.rid, pf.req, "deadline")
        for slot, rec in list(sched.active.items()):
            if expired(rec.req, per[rec.rid]):
                self._finish(slot, "deadline")

    def _apply_injections(self):
        """Consult the chaos injector (test/bench only): injected
        cancellations hit the public :meth:`cancel` path; slot corruption
        NaNs a live slot's float state on device — detection is then the
        macro-step fault lane's job, exactly as for an organic fault."""
        inj = self._injector
        if inj.crash_now(self.tick):
            # Simulated process death (DESIGN.md §12): propagate out of
            # step() with no flush and no cleanup — buffered journal
            # records are lost exactly as a real kill -9 would lose them.
            raise faults_lib.EngineCrash(self.tick)
        live_rids = ([rec.rid for rec in self.sched.active.values()]
                     + [rid for rid, _ in self.sched.ready])
        for rid in inj.cancel_rids(self.tick, live_rids):
            self.cancel(rid)
        for slot in inj.corrupt_slots(self.tick,
                                      list(self.sched.active)):
            if slot in self.sched.active:
                self.pool = self._corrupt_fn(self.pool, jnp.int32(slot))
