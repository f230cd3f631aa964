"""Serving benchmark: continuous batching under a Poisson arrival trace.

Replays a seeded Poisson request trace (exponential inter-arrival times, in
engine ticks) through :class:`repro.serving.ContinuousServingEngine` at
several load levels and emits ``BENCH_serving.json`` (repo root) — the
serving perf trajectory CI tracks per PR:

* ``decode_tokens_per_s`` / ``total_tokens_per_s`` — wall-clock throughput
  (noisy on CPU; structural on TPU),
* ``ttft_ticks_p50`` / ``p95`` — time-to-first-token in engine ticks, a
  backend-independent measure of scheduling latency (queueing + chunked
  prefill) that survives CPU timing noise,
* ``mean_slot_occupancy`` / ``mean_queue_depth`` — pool pressure,
* ``host_syncs_per_token`` / ``tokens_per_dispatch`` /
  ``dispatches_per_decode_tick`` — the decode hot-loop sync cadence under
  K-tick macro-stepping (backend-independent: the win the on-device loop
  buys regardless of accelerator), plus ``jit_cache_entries`` per row —
  the recompile budget CI gates on.

Both cache regimes run: the constant-state SLAY path (slot overwrite
eviction) and the KV-ring softmax baseline (same scheduler, O(max_len)
slot state), so the JSON shows the serving asymmetry directly. Two
scan-carry rows (``ssm_scan`` = mamba2, ``hybrid_scan`` = hymba) track
exact chunked-prefill continuation for the SSD families (DESIGN.md §9) —
their bucket counters must read zero (fallback retired; CI asserts it).
A ``constant_state_sharded`` row replays the last constant_state trace on
a mesh=(data=N,) slot-sharded pool (``benchmarks/serving_sharded_row.py``:
in-process on an accelerator, left out below N devices; a
forced-multi-device subprocess on CPU); every row carries a
``stream_digest`` (sha256 of the rid-ordered token streams) and the CI
contract step asserts the sharded digest equals the single-shard one —
the DESIGN.md §8 byte-identical-stream contract.

The speculative family (DESIGN.md §13) rides every run: an ``exact_yat``
greedy baseline (yat_spherical verifier config, plain decode) and a
``spec_constant_state`` draft-verify row on the same trace — the linear
SLAY regime drafts ``spec_gamma`` tokens per slot, the exact verifier
scores them in one chunked dispatch. The CI spec-decode contract asserts
``stream_digest`` equality between the two rows (greedy speculative ≡
greedy exact, byte-identical), ``tokens_per_dispatch > macro_ticks``,
and ``draft_acceptance_rate >= 0.5``. Both rows replay one pinned
contract trace (fixed geometry + seed, identical at every tier): the
byte-identity contract presumes a unique fp32 argmax at every emitted
position, and random smoke weights can manufacture exact top-2 logit
ties that the two differently-shaped scorer programs may legally break
either way (DESIGN.md §13), so the pinned seed is checked tie-free.

Three DESIGN.md §11 rows ride every run: ``kv_ring_paged`` replays the
kv_ring trace with the page-table layer on (its ``stream_digest`` must
equal the unpaged row's; ``pages_peak`` / ``final_pages_in_use`` expose
pool pressure and the no-leak contract), and a ``prefix_cold`` /
``prefix_cached`` pair replays a shared-system-prefix trace cold and then
against a cache warmed by a throwaway engine — the cached row must
full-hit every request (``prefix_hit_rate == 1.0``), stream
byte-identically, and beat the cold row's ``ttft_ticks_p50``. The tick
metrics of every non-chaos row are additionally gated against the
committed baseline by ``tools/check_bench.py`` (re-baseline deliberate
shifts with ``--update``).

``--chaos`` appends degraded-mode rows (DESIGN.md §10): a ``chaos_nan``
row replays the constant_state trace under a seeded
:class:`repro.serving.faults.FaultInjector` that NaNs one live slot every
``chaos_nan_every`` ticks — measuring fault-detection latency (bounded by
the K-tick fault plane), retry success, and byte-identical parity of
every successfully-finished stream against the fault-free baseline — and
a ``chaos_overload`` row drives an all-at-once burst through the
``shed_oldest`` overload policy with one impossible deadline, measuring
shed and deadline-miss rates. The CI ``chaos-serving`` step asserts the
leak contract (``final_occupancy == 0``) and ``fault_retries_succeeded
>= 1`` from these rows. ``--chaos`` also runs the crash-recovery drills
(DESIGN.md §12): per decode regime, a journaled + checkpointed engine is
killed mid-flight by the seeded crash injector, restored from disk, and
driven to completion — ``crash_recovery_*`` rows record recovery wall
time, tokens replayed through the journal-dedup horizon, journal bytes
per token, and the ``streams_byte_identical`` flag the CI crash contract
step asserts (alongside zero leaked slots/pages).

    PYTHONPATH=src python -m benchmarks.run --suite serving
    PYTHONPATH=src python -m benchmarks.run --suite serving --smoke
    PYTHONPATH=src python -m benchmarks.run --suite serving --smoke --chaos
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import subprocess
import sys
import tempfile

import jax
import numpy as np

from benchmarks.common import BenchResult
from repro import configs
from repro.configs.base import ServingConfig
from repro.launch.mesh import make_host_mesh
from repro.models import api
from repro.serving import journal as journal_lib
from repro.serving.engine import ContinuousServingEngine, Request
from repro.serving.faults import (EngineCrash, FaultInjector,
                                  detection_latencies)
from repro.serving.prefix_cache import PrefixCache

_JSON_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_serving.json")

# (requests, max_new, prompt range); load = arrival rate in requests/tick.
# max_new >= 2*macro_ticks so every trace amortizes the K-tick macro-step
# (the host_syncs_per_token <= 1/K contract CI asserts on).
_MACRO_TICKS = 8
# chaos_nan_every: chaos-row NaN-injection cadence (ticks). Full keeps the
# headline 1-corruption-per-64-ticks rate; smoke/quick shrink it so the
# shorter traces still see >= 1 fault (the CI chaos contract requires a
# successful retry, so every tier must actually fault).
_SMOKE = {"n": 4, "max_new": 16, "prompt": (3, 8), "loads": (0.25, 1.0),
          "num_slots": 2, "max_len": 32, "prefill_chunk": 4,
          "page_size": 8, "chaos_nan_every": 6}
_QUICK = {"n": 10, "max_new": 16, "prompt": (4, 16), "loads": (0.1, 0.5),
          "num_slots": 4, "max_len": 64, "prefill_chunk": 8,
          "page_size": 16, "chaos_nan_every": 12}
_FULL = {"n": 32, "max_new": 24, "prompt": (8, 48),
         "loads": (0.05, 0.2, 0.8), "num_slots": 8, "max_len": 128,
         "prefill_chunk": 16, "page_size": 16, "chaos_nan_every": 64}


def _poisson_trace(rng, n: int, rate: float, prompt_range, vocab: int,
                   max_new: int) -> list[Request]:
    """n requests with exp(rate) inter-arrival ticks and random prompts."""
    t = 0.0
    reqs = []
    lo, hi = prompt_range
    for _ in range(n):
        t += rng.exponential(1.0 / rate)
        plen = int(rng.integers(lo, hi + 1))
        prompt = rng.integers(3, vocab, size=plen).astype(np.int32)
        reqs.append(Request(prompt, max_new_tokens=max_new,
                            arrival_time=t))
    return reqs


def _prefix_trace(rng, n: int, rate: float, chunk: int, vocab: int,
                  max_new: int) -> list[Request]:
    """Repeated-system-prompt trace (DESIGN.md §11): every prompt is one
    shared 2-chunk system prefix plus a short unique suffix — the shape
    the content-addressed prefix cache is built for."""
    sysp = rng.integers(3, vocab, size=2 * chunk).astype(np.int32)
    t = 0.0
    reqs = []
    for _ in range(n):
        t += rng.exponential(1.0 / rate)
        s = int(rng.integers(1, chunk + 1))
        suffix = rng.integers(3, vocab, size=s).astype(np.int32)
        reqs.append(Request(np.concatenate([sysp, suffix]),
                            max_new_tokens=max_new, arrival_time=t))
    return reqs


def _stream_digest(outs: dict) -> str:
    """sha256 over the rid-ordered token streams — the byte-identity
    fingerprint the §8 sharded/unsharded contract compares."""
    h = hashlib.sha256()
    for rid in sorted(outs):
        h.update(np.int64(rid).tobytes())
        h.update(np.asarray(outs[rid], np.int32).tobytes())
    return h.hexdigest()


def _sharded_row(p: dict, load: float) -> dict | None:
    """Run the constant_state trace on a slot-sharded mesh=(data=N,) pool.

    On an accelerator the row runs in this process on the devices present
    (one process per chip: a child could not get them), and is left out
    with fewer than N devices. On CPU, jax pins its device count at first
    init, so the row runs in a subprocess with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` and comes back
    as JSON on stdout.
    """
    data = 4 if p["num_slots"] % 4 == 0 else 2
    lo, hi = p["prompt"]
    if jax.default_backend() != "cpu":
        if jax.device_count() < data:
            print(f"# constant_state_sharded: left out, needs {data} "
                  f"devices, have {jax.device_count()}", flush=True)
            return None
        from benchmarks.serving_sharded_row import sharded_row
        return sharded_row(load=load, n=p["n"], max_new=p["max_new"],
                           prompt=(lo, hi), num_slots=p["num_slots"],
                           max_len=p["max_len"],
                           prefill_chunk=p["prefill_chunk"],
                           macro_ticks=_MACRO_TICKS, data=data)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    # Append (not overwrite): the child must see the parent's XLA flags
    # plus the forced device count, or numerics-affecting flags would
    # make the byte-identity digest comparison spuriously fail.
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={data}"
                        ).strip()
    env["PYTHONPATH"] = os.path.join(repo, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.serving_sharded_row",
         "--load", str(load), "--n", str(p["n"]),
         "--max-new", str(p["max_new"]),
         "--prompt-lo", str(lo), "--prompt-hi", str(hi),
         "--num-slots", str(p["num_slots"]),
         "--max-len", str(p["max_len"]),
         "--prefill-chunk", str(p["prefill_chunk"]),
         "--macro-ticks", str(_MACRO_TICKS), "--data", str(data)],
        capture_output=True, text=True, timeout=1800, env=env, cwd=repo)
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded serving row failed:\n{proc.stdout}\n{proc.stderr}")
    return json.loads(proc.stdout.splitlines()[-1])


def _trace_row(cfg, params, mesh, p: dict, load: float, regime: str,
               results: list, rows: list, *, page_size: int = 0,
               prefix_cache=None, reqs=None, speculative: bool = False,
               spec_gamma: int = 2):
    """Run one (config, load) Poisson trace; append BenchResults + a JSON
    row, asserting the backend-independent hot-loop contract.

    ``page_size`` pages the slot pool (``*_paged``/``prefix_*`` rows);
    ``prefix_cache`` shares a pre-warmed PrefixCache (``prefix_cached``
    row); ``reqs`` overrides the default Poisson trace; ``speculative``
    turns on draft-verify decoding (``spec_*`` rows, DESIGN.md §13)."""
    if reqs is None:
        rng = np.random.default_rng(1234)
        reqs = _poisson_trace(rng, p["n"], load, p["prompt"],
                              cfg.vocab_size, p["max_new"])
    eng = ContinuousServingEngine(
        cfg, params, mesh,
        serving=ServingConfig(num_slots=p["num_slots"],
                              max_len=p["max_len"],
                              prefill_chunk=p["prefill_chunk"],
                              macro_ticks=_MACRO_TICKS,
                              page_size=page_size,
                              speculative=speculative,
                              spec_gamma=spec_gamma),
        prefix_cache=prefix_cache)
    outs, summary = eng.run(reqs)
    assert summary["requests_completed"] == p["n"]
    # Hot-loop contract (backend-independent): one pooled dispatch
    # covers >= 1 decode tick, and the decode loop syncs to host
    # at most once per K generated tokens.
    assert summary["dispatches_per_decode_tick"] <= 1.0 + 1e-9
    assert summary["host_syncs_per_token"] <= 1.0 / _MACRO_TICKS \
        + 1e-9, summary["host_syncs_per_token"]
    jit_entries = eng.jit_cache_entries()
    # Missing key = jax introspection unavailable, not a recompile.
    # In speculative mode the hot loop is the spec macro-step instead.
    hot = "spec_macro" if speculative else "macro_decode"
    assert jit_entries.get(hot, 1) == 1, jit_entries
    tag = f"serving/{regime}/load{load:g}"
    for key in ("decode_tokens_per_s", "ttft_ticks_p50",
                "ttft_ticks_p95", "mean_slot_occupancy",
                "mean_queue_depth", "host_syncs_per_token",
                "tokens_per_dispatch"):
        unit = ("tok/s" if "per_s" in key
                else "ticks" if "ttft" in key else "ratio")
        results.append(BenchResult(
            f"{tag}/{key}", float(summary[key]), unit,
            extra={"regime": regime, "load": load}))
    rows.append({"regime": regime, "load": load,
                 "num_slots": p["num_slots"],
                 "requests": p["n"],
                 "prefix_hit_rate": summary["prefix_hits"] / p["n"],
                 "stream_digest": _stream_digest(outs),
                 "jit_cache_entries": jit_entries, **summary})
    return outs


def _chaos_rows(cfg, params, mesh, p: dict, load: float, base_outs: dict,
                results: list, rows: list):
    """Degraded-mode rows (DESIGN.md §10), both deterministic given the
    trace + injector seeds, so their rates are trendable per PR.

    ``chaos_nan``: the exact constant_state Poisson trace, with the
    injector NaN-ing one live slot's device state every
    ``chaos_nan_every`` ticks. Asserted here (and re-asserted from the
    JSON by CI): every request terminates, no slot leaks, >= 1 fault is
    detected, every faulted request finishes ``eos``/``length`` after at
    most one retry or is terminated as ``fault`` — and every successful
    stream (retried ones included) is byte-identical to the fault-free
    baseline, because sampling keyed on (seed, rid, token-index) makes
    retry-from-scratch transparent.

    ``chaos_overload``: the same requests arriving all at once into a
    half-sized admission queue under ``shed_oldest``, the last request
    carrying an impossible 2-tick total deadline, and the injector
    cancelling a live request periodically — exercising shed, deadline,
    and cancelled exits in one row.
    """
    rng = np.random.default_rng(1234)
    reqs = _poisson_trace(rng, p["n"], load, p["prompt"],
                          cfg.vocab_size, p["max_new"])
    sv = ServingConfig(num_slots=p["num_slots"], max_len=p["max_len"],
                       prefill_chunk=p["prefill_chunk"],
                       macro_ticks=_MACRO_TICKS, fault_retries=1)
    inj = FaultInjector(seed=418, nan_every=p["chaos_nan_every"])
    eng = ContinuousServingEngine(cfg, params, mesh, serving=sv,
                                  fault_injector=inj)
    outs, summary = eng.run(reqs)
    assert summary["final_occupancy"] == 0, summary
    assert summary["requests_terminated"] == p["n"], summary
    assert summary["faults_detected"] >= 1, summary
    for rid, st in eng.metrics.per_request.items():
        assert st.finish_reason in ("eos", "length", "fault"), st
        assert st.retries <= sv.fault_retries, st
        if st.finish_reason in ("eos", "length"):
            np.testing.assert_array_equal(outs[rid], base_outs[rid])
    lat = detection_latencies(inj.log, eng.metrics.fault_events)
    assert lat, (inj.log, eng.metrics.fault_events)
    chaos_extra = {
        "chaos_nan_every": p["chaos_nan_every"],
        "faults_injected": sum(1 for e in inj.log if e["kind"] == "nan"),
        "fault_detect_latency_ticks_mean": float(np.mean(lat)),
        "fault_detect_latency_ticks_max": int(np.max(lat)),
    }
    rows.append({"regime": "chaos_nan", "load": load,
                 "num_slots": p["num_slots"], "requests": p["n"],
                 "stream_digest": _stream_digest(outs),
                 "jit_cache_entries": eng.jit_cache_entries(),
                 **chaos_extra, **summary})
    for key in ("faults_detected", "fault_retries_succeeded"):
        results.append(BenchResult(
            f"serving/chaos_nan/load{load:g}/{key}",
            float(summary[key]), "count",
            extra={"regime": "chaos_nan", "load": load}))
    results.append(BenchResult(
        f"serving/chaos_nan/load{load:g}/fault_detect_latency_ticks_max",
        float(chaos_extra["fault_detect_latency_ticks_max"]), "ticks",
        extra={"regime": "chaos_nan", "load": load}))

    burst = [dataclasses.replace(r, arrival_time=0.0) for r in reqs]
    burst[-1] = dataclasses.replace(burst[-1], deadline_ticks=2.0)
    svo = ServingConfig(num_slots=p["num_slots"], max_len=p["max_len"],
                        prefill_chunk=p["prefill_chunk"],
                        macro_ticks=_MACRO_TICKS,
                        max_queue=max(p["n"] // 2, 1),
                        overload_policy="shed_oldest")
    inj2 = FaultInjector(seed=419, cancel_every=3 * _MACRO_TICKS)
    eng2 = ContinuousServingEngine(cfg, params, mesh, serving=svo,
                                   fault_injector=inj2)
    for r in burst:
        eng2.submit(r)
    outs2, s2 = eng2.run()
    assert s2["final_occupancy"] == 0, s2
    assert s2["requests_terminated"] == p["n"], s2
    assert s2["finish_reasons"].get("shed", 0) >= 1, s2
    assert s2["finish_reasons"].get("deadline", 0) >= 1, s2
    rows.append({"regime": "chaos_overload", "load": load,
                 "num_slots": p["num_slots"], "requests": p["n"],
                 "max_queue": svo.max_queue,
                 "stream_digest": _stream_digest(outs2),
                 "jit_cache_entries": eng2.jit_cache_entries(), **s2})
    for key in ("shed_rate", "deadline_miss_rate"):
        results.append(BenchResult(
            f"serving/chaos_overload/load{load:g}/{key}",
            float(s2[key]), "ratio",
            extra={"regime": "chaos_overload", "load": load}))


def _crash_recovery_rows(mesh, p: dict, load: float, regimes, results,
                         rows):
    """Crash-recovery drills (DESIGN.md §12): kill-and-restore per regime.

    Each regime's exact Poisson trace replays against a journaled +
    periodically-checkpointed engine; the seeded crash injector kills the
    process state mid-flight (an exception with no flush and no cleanup —
    the host dies with dirty buffers, a fair stand-in for ``kill -9``);
    the engine then restores from disk and finishes. Asserted here and
    re-asserted from the JSON by the CI chaos contract step:

    * ``streams_byte_identical`` — the merged restored streams' digest
      equals the fault-free row's (the §12 byte-identity contract),
    * ``tokens_replayed > 0`` — the crash landed mid-stream, so recovery
      actually regenerated and deduped journaled tokens (a vacuous drill
      that crashed before any emission would pass identity for free),
    * zero leaked slots/pages/queue entries after the recovered drain.

    Recovery cost shows up as ``recovery_wall_s`` (journal replay +
    checkpoint load + re-prefill on the restore path) and
    ``journal_bytes_per_token`` (durability overhead per emitted token).
    """
    for name, cfg, params, page_size in regimes:
        base_row = next(r for r in rows if r["regime"] == name
                        and r["load"] == load)
        sv = ServingConfig(num_slots=p["num_slots"], max_len=p["max_len"],
                           prefill_chunk=p["prefill_chunk"],
                           macro_ticks=_MACRO_TICKS, page_size=page_size,
                           checkpoint_every_ticks=_MACRO_TICKS)
        reqs = _poisson_trace(np.random.default_rng(1234), p["n"], load,
                              p["prompt"], cfg.vocab_size, p["max_new"])
        with tempfile.TemporaryDirectory(prefix="slay-crash-") as d:
            jr = journal_lib.Journal(
                os.path.join(d, journal_lib.JOURNAL_NAME))
            inj = FaultInjector(seed=808, crash_window=(10, 16))
            eng = ContinuousServingEngine(cfg, params, mesh, serving=sv,
                                          fault_injector=inj, journal=jr)
            crash_tick = None
            try:
                eng.run(reqs)
            except EngineCrash as e:
                crash_tick = e.tick
            assert crash_tick is not None, \
                f"{name}: crash injector never fired (trace too short?)"
            eng2 = ContinuousServingEngine.restore(d, cfg, params, mesh,
                                                   serving=sv)
            rec = eng2.recovery
            outs, s2 = eng2.run()
        identical = _stream_digest(outs) == base_row["stream_digest"]
        assert identical, f"{name}: restored streams diverged"
        assert s2["tokens_replayed"] > 0, (name, s2["tokens_replayed"])
        assert s2["final_occupancy"] == 0 == s2["final_queue_depth"], s2
        assert s2["final_pages_in_use"] == 0, s2
        regime = f"crash_recovery_{name}"
        extra = {
            "crash_tick": int(crash_tick),
            "recovery_wall_s": float(rec["wall_s"]),
            "checkpoint_used": bool(rec["checkpoint_used"]),
            "checkpoint_tick": rec["checkpoint_tick"],
            "resident_resumed": rec["resident_resumed"],
            "requeued": rec["requeued"],
            "terminal_from_journal": rec["terminal_from_journal"],
            "journal_records": rec["journal_records"],
            "journal_bytes_per_token":
                s2["journal_bytes"] / max(s2["tokens_generated"], 1),
            "streams_byte_identical": identical,
        }
        rows.append({"regime": regime, "load": load,
                     "num_slots": p["num_slots"], "requests": p["n"],
                     "stream_digest": _stream_digest(outs),
                     **extra, **s2})
        for key, unit in (("recovery_wall_s", "s"),
                          ("journal_bytes_per_token", "bytes/tok")):
            results.append(BenchResult(
                f"serving/{regime}/load{load:g}/{key}",
                float(extra[key]), unit,
                extra={"regime": regime, "load": load}))
        results.append(BenchResult(
            f"serving/{regime}/load{load:g}/tokens_replayed",
            float(s2["tokens_replayed"]), "tokens",
            extra={"regime": regime, "load": load}))


def run(quick: bool = True, smoke: bool = False, chaos: bool = False):
    p = _SMOKE if smoke else (_QUICK if quick else _FULL)
    mesh = make_host_mesh()
    results = []
    rows = []
    cs_cfg = cs_params = cs_outs = None
    kv_cfg = kv_params = None
    for regime, attn_kind in (("constant_state", "slay"),
                              ("kv_ring", "softmax")):
        cfg = configs.get_smoke_config("slayformer-124m",
                                       attn_kind=attn_kind)
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        for load in p["loads"]:
            outs = _trace_row(cfg, params, mesh, p, load, regime,
                              results, rows)
            if regime == "constant_state":
                # Chaos parity baseline: the fault-free streams of the
                # last constant_state load.
                cs_cfg, cs_params, cs_outs = cfg, params, outs
            else:
                kv_cfg, kv_params = cfg, params

    # Paged-pool row (DESIGN.md §11): the exact kv_ring trace with the KV
    # rings drawn from a shared page pool. Streams must be byte-identical
    # to the unpaged row — paging is a memory-layout change, never a
    # numerics change — and a drained engine leaks zero pages.
    load = p["loads"][-1]
    _trace_row(kv_cfg, kv_params, mesh, p, load, "kv_ring_paged",
               results, rows, page_size=p["page_size"])
    paged_row = rows[-1]
    kv_row = next(r for r in rows if r["regime"] == "kv_ring"
                  and r["load"] == load)
    assert paged_row["stream_digest"] == kv_row["stream_digest"], \
        (paged_row["stream_digest"], kv_row["stream_digest"])
    assert paged_row["final_pages_in_use"] == 0, paged_row
    assert paged_row["pages_peak"] >= 1, paged_row

    # Prefix-cache rows (DESIGN.md §11): a repeated-system-prompt trace,
    # cold (no cache) vs cached (a warm-up engine populates a shared
    # PrefixCache; the measured engine then hits on every admission).
    # Streams must be byte-identical cold-vs-cached — seeding from a
    # snapshot preserves the suffix chunk schedule and sampling is keyed
    # (seed, rid, idx) — while cached TTFT drops (prefill work skipped).
    def prefix_reqs():
        return _prefix_trace(np.random.default_rng(99), p["n"], load,
                             p["prefill_chunk"], kv_cfg.vocab_size,
                             p["max_new"])

    _trace_row(kv_cfg, kv_params, mesh, p, load, "prefix_cold",
               results, rows, page_size=p["page_size"],
               reqs=prefix_reqs())
    cold_row = rows[-1]
    shared = PrefixCache(64 * 1024 * 1024)
    warm = ContinuousServingEngine(
        kv_cfg, kv_params, mesh,
        serving=ServingConfig(num_slots=p["num_slots"],
                              max_len=p["max_len"],
                              prefill_chunk=p["prefill_chunk"],
                              macro_ticks=_MACRO_TICKS,
                              page_size=p["page_size"]),
        prefix_cache=shared)
    warm.run(prefix_reqs())
    _trace_row(kv_cfg, kv_params, mesh, p, load, "prefix_cached",
               results, rows, page_size=p["page_size"],
               prefix_cache=shared, reqs=prefix_reqs())
    cached_row = rows[-1]
    assert cached_row["stream_digest"] == cold_row["stream_digest"], \
        (cached_row["stream_digest"], cold_row["stream_digest"])
    assert cached_row["prefix_hit_rate"] == 1.0, cached_row
    assert cold_row["prefix_hit_rate"] == 0.0, cold_row
    assert cached_row["ttft_ticks_p50"] < cold_row["ttft_ticks_p50"], \
        (cached_row["ttft_ticks_p50"], cold_row["ttft_ticks_p50"])
    for r in (cold_row, cached_row):
        assert r["final_pages_in_use"] == 0, r
    for key, row in (("prefix_hit_rate", cached_row),
                     ("prefix_tokens_reused", cached_row)):
        results.append(BenchResult(
            f"serving/prefix_cached/load{load:g}/{key}",
            float(row[key]), "ratio" if "rate" in key else "tokens",
            extra={"regime": "prefix_cached", "load": load}))

    # Scan-carry prefill rows (DESIGN.md §9): ssm/hybrid serve through
    # exact chunked-prefill continuation — the bucketed masked-prefill
    # fallback is retired for them, so the bucket counters must stay at
    # zero (the CI serving contract step re-asserts this from the JSON)
    # and prefill progresses chunk-by-chunk in the tick trajectory.
    for regime, arch in (("ssm_scan", "mamba2-780m"),
                         ("hybrid_scan", "hymba-1.5b")):
        cfg = configs.get_smoke_config(arch)
        assert api.supports_chunked_prefill(cfg), arch
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        load = p["loads"][-1]
        _trace_row(cfg, params, mesh, p, load, regime, results, rows)
        row = rows[-1]
        assert row["bucket_misses"] == 0 == row["bucket_hits"], row
        assert row["prefill_ticks"] > 0, row

    # Sharded-pool variant (DESIGN.md §8): same trace as the last
    # constant_state load, slot pool sharded over mesh=(data=N,). The
    # digest must match the single-shard row byte-for-byte — asserted
    # here and re-asserted from the JSON by the CI contract step.
    load = p["loads"][-1]
    sharded = _sharded_row(p, load)
    if sharded is not None:
        base = next(r for r in rows
                    if r["regime"] == "constant_state" and r["load"] == load)
        assert sharded["stream_digest"] == base["stream_digest"], \
            (sharded["stream_digest"], base["stream_digest"])
        rows.append(sharded)
        results.append(BenchResult(
            f"serving/constant_state_sharded/load{load:g}/slot_shards",
            float(sharded["slot_shards"]), "shards",
            extra={"regime": "constant_state_sharded", "load": load}))

    # Speculative-decoding family (DESIGN.md §13): an exact-yat greedy
    # baseline row plus a draft-verify row on the same config — linear
    # SLAY drafts spec_gamma tokens per slot, the exact yat verifier
    # scores them in one chunked dispatch. The contract asserted here and
    # re-asserted from the JSON by the CI spec-decode step: the accepted
    # streams are byte-identical to plain greedy exact decode (the
    # accept/resample correction emits exactly the verifier's argmax) and
    # the amortization is real — tokens/dispatch materially above
    # macro_ticks, draft acceptance >= 0.5. The small SLAY feature bank
    # keeps the draft steps cheap; it is the *verifier's* features that
    # set output quality, so the baseline uses the same trunk.
    #
    # The family runs on a pinned contract trace (geometry + seed below),
    # identical at every bench tier. Byte-identity presumes the verifier's
    # fp32 argmax is unique at every emitted position: decode_step and
    # verify_chunk are different XLA programs (shapes (S,1,V) vs
    # (S,gamma+1,V)), so an *exact* top-2 logit tie — measure-zero for
    # trained weights but easy to hit with random smoke weights — can
    # legally resolve either way; near-ties within the two programs'
    # rounding do too. The pinned seed was checked tie-free on
    # jax/jaxlib 0.9.0 (seed 2024, checked under 0.4.37, flips two late
    # token there); see DESIGN.md §13 for the contract's fine print.
    spec_load = 1.0
    sp = {**p, "n": 4, "max_new": 16, "prompt": (3, 8),
          "num_slots": 2, "max_len": 32, "prefill_chunk": 4}
    spec_cfg = configs.get_smoke_config("slayformer-124m",
                                        attn_kind="yat_spherical",
                                        slay_anchors=16, slay_prf=32)
    spec_params = api.init_params(spec_cfg, jax.random.PRNGKey(0))

    def spec_reqs():
        return _poisson_trace(np.random.default_rng(10), sp["n"], spec_load,
                              sp["prompt"], spec_cfg.vocab_size,
                              sp["max_new"])

    _trace_row(spec_cfg, spec_params, mesh, sp, spec_load, "exact_yat",
               results, rows, reqs=spec_reqs())
    exact_row = rows[-1]
    _trace_row(spec_cfg, spec_params, mesh, sp, spec_load, "spec_constant_state",
               results, rows, reqs=spec_reqs(),
               speculative=True, spec_gamma=2)
    spec_row = rows[-1]
    assert spec_row["stream_digest"] == exact_row["stream_digest"], \
        (spec_row["stream_digest"], exact_row["stream_digest"])
    assert spec_row["tokens_per_dispatch"] > _MACRO_TICKS, \
        spec_row["tokens_per_dispatch"]
    assert spec_row["draft_acceptance_rate"] >= 0.5, \
        spec_row["draft_acceptance_rate"]
    for key, unit in (("draft_acceptance_rate", "ratio"),
                      ("draft_tokens_proposed", "tokens"),
                      ("tokens_per_dispatch", "ratio")):
        results.append(BenchResult(
            f"serving/spec_constant_state/load{spec_load:g}/{key}",
            float(spec_row[key]), unit,
            extra={"regime": "spec_constant_state", "load": spec_load}))

    if chaos:
        _chaos_rows(cs_cfg, cs_params, mesh, p, load, cs_outs,
                    results, rows)
        # Crash-recovery drills (DESIGN.md §12): one kill-and-restore per
        # decode regime, byte-identity asserted against the fault-free
        # rows above (same trace, same load).
        _crash_recovery_rows(
            mesh, p, load,
            [("constant_state", cs_cfg, cs_params, 0),
             ("kv_ring", kv_cfg, kv_params, 0),
             ("kv_ring_paged", kv_cfg, kv_params, p["page_size"])],
            results, rows)

    payload = {
        "meta": {
            "backend": jax.default_backend(),
            "smoke": smoke, "quick": quick, "chaos": chaos,
            "params": {**p, "macro_ticks": _MACRO_TICKS},
            "note": ("ttft/occupancy are in engine ticks (backend-"
                     "independent scheduling trajectory); *_per_s are "
                     "wall-clock and only meaningful on TPU"),
        },
        "results": rows,
    }
    with open(_JSON_PATH, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    return results
