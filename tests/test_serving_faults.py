"""Fault-tolerant request lifecycle (DESIGN.md §10): construction-time
validation, typed admission errors + overload policies, cancellation at
every lifecycle stage (queued, mid-prefill, mid-macro-step), tick/wall
deadlines with EOS-wins ordering, NaN slot quarantine + retry, and the
deterministic chaos harness (parity of non-faulted streams, seeded
injector reproducibility)."""
import dataclasses

import jax
import numpy as np
import pytest

from repro import configs
from repro.configs.base import ServingConfig
from repro.launch.mesh import make_host_mesh
from repro.models import api
from repro.serving import faults
from repro.serving.engine import (AdmissionError, ContinuousServingEngine,
                                  QueueFullError, Request,
                                  RequestTooLargeError, ServingMetrics)
from sharded_driver import poisson_trace

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_smoke_config("slayformer-124m")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_host_mesh()
    return cfg, params, mesh


def _prompt(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(3, cfg.vocab_size, size=n).astype(np.int32)


class FakeClock:
    """Deterministic injectable wall clock: time moves only when the test
    says so, making wall-deadline expiry independent of host speed."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += float(dt)


def _engine(cfg, params, mesh, injector=None, clock=None, journal=None,
            **kw):
    sv = ServingConfig(**{"num_slots": 2, "max_len": 64,
                          "prefill_chunk": 4, "macro_ticks": 4, **kw})
    extra = {}
    if clock is not None:
        extra["clock"] = clock
    if journal is not None:
        extra["journal"] = journal
    return ContinuousServingEngine(cfg, params, mesh, serving=sv,
                                   fault_injector=injector, **extra)


# -- construction-time validation -------------------------------------------


def test_request_validation():
    with pytest.raises(ValueError, match="empty prompt"):
        Request(np.array([], np.int32))
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request(np.array([1], np.int32), max_new_tokens=0)
    with pytest.raises(ValueError, match="arrival_time"):
        Request(np.array([1], np.int32), arrival_time=float("nan"))
    for field in ("ttft_deadline_ticks", "deadline_ticks",
                  "ttft_deadline_s", "deadline_s"):
        with pytest.raises(ValueError, match=field):
            Request(np.array([1], np.int32), **{field: 0.0})
        with pytest.raises(ValueError, match=field):
            Request(np.array([1], np.int32), **{field: float("inf")})
    # Valid deadlines construct fine.
    Request(np.array([1], np.int32), deadline_ticks=5.0,
            ttft_deadline_s=0.5)


def test_serving_config_validation():
    with pytest.raises(ValueError, match="temperature"):
        ServingConfig(temperature=float("nan"))
    with pytest.raises(ValueError, match="temperature"):
        ServingConfig(temperature=-1.0)
    with pytest.raises(ValueError, match="overload_policy"):
        ServingConfig(overload_policy="panic")
    with pytest.raises(ValueError, match="queue_wait_ticks"):
        ServingConfig(queue_wait_ticks=-1)
    with pytest.raises(ValueError, match="fault_retries"):
        ServingConfig(fault_retries=-1)


# -- typed admission + overload policies ------------------------------------


def test_reject_new_raises_typed_queue_full(setup):
    cfg, params, mesh = setup
    eng = _engine(cfg, params, mesh, max_queue=2)
    reqs = [Request(_prompt(cfg, 4, i), max_new_tokens=2) for i in range(3)]
    eng.submit(reqs[0])
    eng.submit(reqs[1])
    with pytest.raises(QueueFullError) as ei:
        eng.submit(reqs[2])
    assert isinstance(ei.value, AdmissionError)
    assert isinstance(ei.value, RuntimeError)
    assert ei.value.queue_depth == 2 and ei.value.max_queue == 2
    # The rejected request consumed no rid and left no orphan state; the
    # engine drains clean.
    outs, s = eng.run()
    assert set(outs) == {0, 1}
    assert s["requests_terminated"] == 2 and s["final_occupancy"] == 0


def test_too_large_is_admission_and_value_error(setup):
    """Bounded (KV ring) configs reject oversized requests with the typed
    error; constant-state configs have unbounded capacity (DESIGN.md §11)
    and only reject when chunked prefill is off (the one-shot fallback
    prefill cannot exceed the ring)."""
    cfg, params, mesh = setup
    bcfg = configs.get_smoke_config("slayformer-124m", attn_kind="softmax")
    bparams = api.init_params(bcfg, jax.random.PRNGKey(0))
    eng = _engine(bcfg, bparams, mesh)
    bad = Request(_prompt(bcfg, 8), max_new_tokens=1000)
    with pytest.raises(RequestTooLargeError) as ei:
        eng.submit(bad)
    assert isinstance(ei.value, AdmissionError)
    assert isinstance(ei.value, ValueError)   # pre-§10 contract preserved
    # The linear (constant-state) setup config admits the same request —
    # its decode state is O(1) in context — unless chunked prefill is off.
    assert api.context_capacity(cfg, 64) is None
    eng2 = _engine(cfg, params, mesh, prefill_chunk=0)
    with pytest.raises(RequestTooLargeError):
        eng2.submit(bad)


def test_shed_oldest_at_queue_boundary(setup):
    """All-arrive-at-once burst at exactly max_queue sheds nothing; one
    past the boundary sheds exactly the longest-waiting request."""
    cfg, params, mesh = setup
    reasons = {}
    n, q = 4, 2
    eng = _engine(cfg, params, mesh, max_queue=q,
                  overload_policy="shed_oldest")
    reqs = [Request(_prompt(cfg, 4, i), max_new_tokens=2,
                    on_finish=lambda rid, why: reasons.update({rid: why}))
            for i in range(n)]
    rids = [eng.submit(r) for r in reqs]       # never raises
    assert rids == list(range(n))
    outs, s = eng.run()
    # n=4 into a queue of 2: submissions 3 and 4 each shed the then-oldest
    # queued request (rids 0 and 1).
    assert reasons[0] == "shed" and reasons[1] == "shed"
    assert s["finish_reasons"]["shed"] == n - q
    assert s["requests_terminated"] == n
    assert len(outs[0]) == 0                    # shed pre-emission
    assert s["final_occupancy"] == 0 and s["final_queue_depth"] == 0
    assert s["shed_rate"] == pytest.approx((n - q) / n)


def test_queue_wait_sheds_stale_requests(setup):
    cfg, params, mesh = setup
    eng = _engine(cfg, params, mesh, num_slots=1, max_queue=2,
                  overload_policy="queue_wait", queue_wait_ticks=2)
    reqs = [Request(_prompt(cfg, 4, i), max_new_tokens=6)
            for i in range(4)]
    for r in reqs:
        eng.submit(r)                          # queue_wait never raises
    outs, s = eng.run()
    # One slot: the first request serves; the rest age out at > 2 ticks.
    assert s["finish_reasons"]["length"] == 1
    assert s["finish_reasons"]["shed"] == 3
    assert len(outs[0]) == 6
    assert s["final_occupancy"] == 0


# -- cancellation at every lifecycle stage ----------------------------------


def test_cancel_queued_is_idempotent(setup):
    cfg, params, mesh = setup
    fired = []
    eng = _engine(cfg, params, mesh, num_slots=1)
    r0 = eng.submit(Request(_prompt(cfg, 4, 0), max_new_tokens=4))
    r1 = eng.submit(Request(
        _prompt(cfg, 4, 1), max_new_tokens=4,
        on_finish=lambda rid, why: fired.append((rid, why))))
    assert eng.cancel(r1) is True
    assert eng.cancel(r1) is False              # already terminal
    assert eng.cancel(999) is False             # unknown rid
    assert fired == [(r1, "cancelled")]         # on_finish exactly once
    outs, s = eng.run()
    assert len(outs[r0]) == 4 and len(outs[r1]) == 0
    assert s["finish_reasons"] == {"cancelled": 1, "length": 1}
    assert eng.metrics.per_request[r1].ttft_ticks is None


def test_cancel_mid_prefill_frees_slot(setup):
    cfg, params, mesh = setup
    eng = _engine(cfg, params, mesh, prefill_chunk=4)
    rid = eng.submit(Request(_prompt(cfg, 12), max_new_tokens=4))
    eng.step()                                  # first prefill chunk only
    assert eng._prefill is not None and eng._prefill.rid == rid
    assert eng.cancel(rid) is True
    assert eng._prefill is None
    assert sorted(eng.sched.free) == list(range(2))   # slot returned
    outs, s = eng.run()
    assert len(outs[rid]) == 0
    assert s["finish_reasons"] == {"cancelled": 1}
    assert s["final_occupancy"] == 0


def test_cancel_mid_macro_step_from_stream_callback(setup):
    """An on_token callback cancelling its own request mid-replay drops
    the remaining buffered device ticks; a co-resident request is
    unaffected."""
    cfg, params, mesh = setup
    got = []

    def cb(rid, tok):
        got.append(tok)
        if len(got) == 3:
            assert eng.cancel(rid) is True

    eng = _engine(cfg, params, mesh, macro_ticks=8)
    ra = eng.submit(Request(_prompt(cfg, 4, 0), max_new_tokens=12,
                            on_token=cb))
    rb = eng.submit(Request(_prompt(cfg, 4, 1), max_new_tokens=12))
    outs, s = eng.run()
    assert len(outs[ra]) == 3                   # buffered suffix dropped
    assert len(outs[rb]) == 12                  # co-resident unaffected
    assert s["finish_reasons"] == {"cancelled": 1, "length": 1}
    assert s["final_occupancy"] == 0
    assert eng.metrics.per_request[ra].finish_reason == "cancelled"


# -- deadlines ---------------------------------------------------------------


def test_ttft_deadline_expires_queued_request(setup):
    cfg, params, mesh = setup
    eng = _engine(cfg, params, mesh, num_slots=1)
    r0 = eng.submit(Request(_prompt(cfg, 4, 0), max_new_tokens=8))
    r1 = eng.submit(Request(_prompt(cfg, 4, 1), max_new_tokens=8,
                            ttft_deadline_ticks=2.0))
    outs, s = eng.run()
    assert len(outs[r0]) == 8
    assert len(outs[r1]) == 0
    assert eng.metrics.per_request[r1].finish_reason == "deadline"
    assert s["deadline_miss_rate"] == pytest.approx(0.5)


def test_total_deadline_cuts_stream_mid_decode(setup):
    cfg, params, mesh = setup
    eng = _engine(cfg, params, mesh, num_slots=1)
    rid = eng.submit(Request(_prompt(cfg, 4), max_new_tokens=16,
                             deadline_ticks=3.0))
    outs, s = eng.run()
    assert 1 <= len(outs[rid]) < 16             # emitted, then expired
    assert eng.metrics.per_request[rid].finish_reason == "deadline"
    assert eng.metrics.per_request[rid].ttft_ticks is not None
    assert s["final_occupancy"] == 0


def test_natural_stop_beats_deadline_on_same_tick(setup):
    """A deadline expiring on the very tick of the natural stop loses:
    emissions are processed before the sweep and expiry is strict."""
    cfg, params, mesh = setup
    req = Request(_prompt(cfg, 4), max_new_tokens=3)
    eng = _engine(cfg, params, mesh)
    rid = eng.submit(req)
    baseline, _ = eng.run()
    finish_age = (eng.metrics.per_request[rid].finished
                  - eng.metrics.per_request[rid].arrival)
    eng2 = _engine(cfg, params, mesh)
    rid2 = eng2.submit(Request(_prompt(cfg, 4), max_new_tokens=3,
                               deadline_ticks=float(finish_age)))
    outs2, _ = eng2.run()
    assert eng2.metrics.per_request[rid2].finish_reason == "length"
    np.testing.assert_array_equal(outs2[rid2], baseline[rid])


def test_wall_clock_deadline_expires(setup):
    """Wall deadlines read the engine's injectable clock, so a fake clock
    makes expiry exact: the stream survives while the clock is under
    budget and cuts the moment the test advances it past, regardless of
    how slow (or fast) the host actually is."""
    cfg, params, mesh = setup
    fc = FakeClock()
    eng = _engine(cfg, params, mesh, clock=fc)
    rid = eng.submit(Request(_prompt(cfg, 4), max_new_tokens=8,
                             deadline_s=0.5))
    eng.step()                                  # clock frozen: no expiry
    assert eng.metrics.per_request[rid].finish_reason is None
    fc.advance(1.0)                             # blow the 0.5 s budget
    outs, s = eng.run()
    assert eng.metrics.per_request[rid].finish_reason == "deadline"
    assert len(outs[rid]) < 8
    assert s["final_occupancy"] == 0


def test_wall_clock_deadline_survives_when_clock_frozen(setup):
    """Control for the fake-clock test above: with the clock never
    advanced the same sub-second budget never expires and the request
    runs to its natural stop — proving expiry is driven by the injected
    clock, not real elapsed time."""
    cfg, params, mesh = setup
    eng = _engine(cfg, params, mesh, clock=FakeClock())
    rid = eng.submit(Request(_prompt(cfg, 4), max_new_tokens=8,
                             deadline_s=0.5))
    outs, s = eng.run()
    assert eng.metrics.per_request[rid].finish_reason == "length"
    assert len(outs[rid]) == 8


def test_ttft_wall_deadline_with_fake_clock(setup):
    """A queued request whose TTFT wall budget elapses (on the fake
    clock) before a slot frees expires without ever emitting."""
    cfg, params, mesh = setup
    fc = FakeClock()
    eng = _engine(cfg, params, mesh, num_slots=1, clock=fc)
    r0 = eng.submit(Request(_prompt(cfg, 4, 0), max_new_tokens=8))
    r1 = eng.submit(Request(_prompt(cfg, 4, 1), max_new_tokens=8,
                            ttft_deadline_s=0.25))
    fc.advance(1.0)
    outs, s = eng.run()
    assert len(outs[r0]) == 8
    assert len(outs[r1]) == 0
    assert eng.metrics.per_request[r1].finish_reason == "deadline"
    assert eng.metrics.per_request[r1].ttft_s is None


# -- metrics edge cases ------------------------------------------------------


def test_summary_with_no_emissions_and_empty_engine(setup):
    cfg, params, mesh = setup
    # A metrics object with zero requests summarizes without dividing by
    # zero anywhere.
    empty = ServingMetrics(num_slots=2).summary()
    assert empty["ttft_ticks_p50"] is None and empty["shed_rate"] == 0.0
    # A request cancelled before any token: excluded from TTFT
    # percentiles (not counted as 0), still in the terminated counters.
    eng = _engine(cfg, params, mesh)
    rid = eng.submit(Request(_prompt(cfg, 4), max_new_tokens=4))
    eng.cancel(rid)
    outs, s = eng.run()
    st = eng.metrics.per_request[rid]
    assert st.ttft_ticks is None and st.ttft_s is None
    assert s["ttft_ticks_p50"] is None
    assert s["requests_terminated"] == 1 and s["requests_completed"] == 0


# -- NaN quarantine + chaos harness -----------------------------------------


def _trace(cfg, n=3, max_new=8):
    rng = np.random.default_rng(7)
    return [Request(rng.integers(3, cfg.vocab_size,
                                 size=5).astype(np.int32),
                    max_new_tokens=max_new, arrival_time=float(i))
            for i in range(n)]


def _poisson(cfg):
    """Four Poisson requests (prompts of 3-8 tokens, 16 new tokens each)."""
    return poisson_trace(np.random.default_rng(1234), 4, 1.0, (3, 8),
                         cfg.vocab_size, 16)


# Per trace: (trace, engine overrides, injector seeds and NaN cadence).
_NAN_RUNS = {
    "mixed": (_trace, {"temperature": 0.7}, {"seed": 7, "nan_every": 5}),
    "poisson": (_poisson, {"max_len": 32, "macro_ticks": 8},
                {"seed": 418, "nan_every": 6}),
}


@pytest.mark.chaos
@pytest.mark.parametrize("trace", sorted(_NAN_RUNS))
def test_nan_quarantine_retry_reproduces_stream(setup, trace):
    """Inject NaNs into live slots; the macro-step fault lane detects
    them within 4 macro-steps, the host quarantines + retries, every
    request terminates and the pool drains, and every successfully-
    finished stream is byte-identical to the fault-free run — retry-from-
    scratch is transparent under (seed, rid, idx)-keyed sampling. The
    hot loop keeps one dispatch per decode tick and one compiled
    macro-step through the faults."""
    cfg, params, mesh = setup
    make_trace, kw, inj_kw = _NAN_RUNS[trace]
    base, _ = _engine(cfg, params, mesh, **kw).run(make_trace(cfg))
    inj = faults.FaultInjector(**inj_kw)
    eng = _engine(cfg, params, mesh, injector=inj, **kw)
    outs, s = eng.run(make_trace(cfg))
    assert s["faults_detected"] >= 1
    assert s["fault_retries"] >= 1
    assert s["fault_retries_succeeded"] >= 1
    assert s["requests_terminated"] == len(base)
    assert s["final_occupancy"] == 0 and s["final_queue_depth"] == 0
    assert s["dispatches_per_decode_tick"] <= 1.0
    entries = eng.jit_cache_entries()
    assert entries["macro_decode"] == 1, entries
    assert sum(v for v in entries.values() if v > 0) <= 24, entries
    for rid, st in eng.metrics.per_request.items():
        assert st.finish_reason in ("eos", "length", "fault")
        assert st.retries <= 1
        if st.finish_reason in ("eos", "length"):
            np.testing.assert_array_equal(outs[rid], base[rid])
    lat = faults.detection_latencies(inj.log, eng.metrics.fault_events)
    assert lat and max(lat) <= 4 * eng.serving.macro_ticks


@pytest.mark.chaos
def test_overload_burst_sheds_and_misses_deadlines(setup):
    """An all-at-once burst into a half-sized queue under shed_oldest,
    the last request with an impossible 2-tick deadline and a live request
    cancelled every 3 macro-steps: some requests are shed, some miss their
    deadline, every request terminates, and the pool drains."""
    cfg, params, mesh = setup
    burst = [dataclasses.replace(r, arrival_time=0.0) for r in _poisson(cfg)]
    burst[-1] = dataclasses.replace(burst[-1], deadline_ticks=2.0)
    inj = faults.FaultInjector(seed=419, cancel_every=3 * 8)
    eng = _engine(cfg, params, mesh, injector=inj, max_len=32,
                  macro_ticks=8, max_queue=len(burst) // 2,
                  overload_policy="shed_oldest")
    for r in burst:
        eng.submit(r)
    _, s = eng.run()
    assert s["shed_rate"] > 0, s["finish_reasons"]
    assert s["deadline_miss_rate"] > 0, s["finish_reasons"]
    assert s["requests_terminated"] == len(burst)
    assert s["final_occupancy"] == 0 and s["final_queue_depth"] == 0
    assert s["dispatches_per_decode_tick"] <= 1.0
    entries = eng.jit_cache_entries()
    assert entries["macro_decode"] == 1, entries
    assert sum(v for v in entries.values() if v > 0) <= 24, entries


@pytest.mark.chaos
def test_fault_retries_exhausted_terminates_as_fault(setup):
    cfg, params, mesh = setup
    inj = faults.FaultInjector(seed=7, nan_every=1)
    eng = _engine(cfg, params, mesh, num_slots=1, fault_retries=0,
                  injector=inj)
    rid = eng.submit(Request(_prompt(cfg, 4), max_new_tokens=8))
    outs, s = eng.run()
    assert eng.metrics.per_request[rid].finish_reason == "fault"
    assert s["finish_reasons"] == {"fault": 1}
    assert s["fault_retries"] == 0
    assert s["final_occupancy"] == 0


@pytest.mark.chaos
def test_chaos_run_is_deterministic(setup):
    """Same trace + same injector seed => identical fault schedule,
    identical streams, identical degraded-mode counters."""
    cfg, params, mesh = setup

    def once():
        inj = faults.FaultInjector(seed=11, nan_every=4, cancel_every=9,
                                   delay_prob=0.5, max_delay_ticks=3)
        eng = _engine(cfg, params, mesh, injector=inj)
        outs, s = eng.run(_trace(cfg, n=4))
        return inj.log, outs, s["finish_reasons"]

    log_a, outs_a, fr_a = once()
    log_b, outs_b, fr_b = once()
    assert log_a == log_b
    assert fr_a == fr_b
    assert set(outs_a) == set(outs_b)
    for rid in outs_a:
        np.testing.assert_array_equal(outs_a[rid], outs_b[rid])
