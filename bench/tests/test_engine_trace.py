"""The engine's own spans and programs in a trace recorded on the chip
(``engine_trace.engine_view``), and the readers of the metrics built on
them.

The fixture (``fixtures/engine_trace``, made by
``record_engine_trace_fixture.py``) is the program's serving engine
serving three requests from submission to idle, each ``step()`` in a
``bench.step`` span and one ``bench.sleep`` between two steps.
"""
import json
import os
import types

import jax
import pytest

from bench import engine_trace, trace

HERE = os.path.dirname(__file__)
FIXTURE = os.path.join(HERE, "fixtures", "engine_trace")
OLD_FIXTURE = os.path.join(HERE, "fixtures", "trace")
ANONYMOUS = ("jit_wrapped", "jit__lambda", "jit__unknown")


@pytest.fixture(scope="module")
def expect():
    with open(os.path.join(FIXTURE, "expect.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(expect):
    return trace.reduce(FIXTURE, expect["window_s"])


@pytest.fixture(scope="module")
def view():
    return engine_trace.engine_view(FIXTURE)


@pytest.fixture(scope="module")
def raw():
    """The fixture's module events and engine spans, read here without
    the reduction: (name, start_s, end_s[, stats])."""
    path = os.path.join(FIXTURE, "plugins", "profile", "run",
                        "trace.xplane.pb")
    modules, spans = [], []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                t = (ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9)
                if (plane.name.startswith("/device:")
                        and line.name == "XLA Modules"):
                    modules.append((ev.name.split("(")[0],) + t)
                elif (plane.name.startswith("/host:")
                      and ev.name.startswith("engine.")):
                    spans.append((ev.name,) + t + (dict(ev.stats),))
    return types.SimpleNamespace(modules=sorted(modules, key=lambda e: e[1]),
                                 spans=sorted(spans, key=lambda e: e[1]))


def test_programs_by_their_names(view, expect):
    progs = view["programs"]
    assert progs["jit_engine_macro_decode"][1] == expect["decode_steps"]
    assert progs["jit_engine_prefill_chunk"][1] == expect["chunks"]
    assert progs["jit_engine_sample_first"][1] == expect["installs"]
    assert progs["jit_engine_write_slot"][1] == expect["installs"]
    assert all(sec > 0 for sec, _ in progs.values())
    assert not set(progs) & set(ANONYMOUS)


def test_spans_counted_with_their_idle(view, expect):
    spans = view["spans"]
    assert spans["engine.step"][1] == expect["steps"]
    for name in ("engine.decode.launch", "engine.decode.wait",
                 "engine.decode.replay"):
        assert spans[name][1] == expect["decode_steps"]
    assert spans["engine.prefill.chunk"][1] == expect["chunks"]
    assert spans["engine.admit"][1] == expect["installs"]
    assert spans["engine.prefill.install"][1] == expect["installs"]
    for sec, n, idle in spans.values():
        assert n > 0 and 0 <= idle <= sec + 1e-9
    # A step holds its children: it covers at least their idle time.
    inner = sum(spans[n][2] for n in ("engine.decode.launch",
                                      "engine.decode.wait",
                                      "engine.decode.replay"))
    assert spans["engine.step"][2] >= inner * (1 - 1e-9)


def test_macro_steps_run_between_launch_and_wait(raw, expect):
    """Each macro-step's device run starts after its launch span starts
    and ends before its wait span ends: the host spans and the device ops
    are on one clock."""
    runs = [m for m in raw.modules if m[0] == "jit_engine_macro_decode"]
    launch = [s for s in raw.spans if s[0] == "engine.decode.launch"]
    wait = [s for s in raw.spans if s[0] == "engine.decode.wait"]
    assert len(runs) == len(launch) == len(wait) == expect["decode_steps"]
    for (_, r0, r1), (_, l0, _, _), (_, _, w1, _) in zip(runs, launch,
                                                          wait):
        assert l0 <= r0 < r1 <= w1


def test_gaps_labelled_by_the_engine_first(view, reduced, expect):
    gaps = view["engine_gaps"]
    # The same ten stretches as ``gaps``, relabelled.
    assert [g[1] for g in gaps] == [g[1] for g in reduced["gaps"]]
    name, sec = gaps[0]
    assert name == "bench.sleep" and sec >= expect["sleep_s"]
    labels = {g[0] for g in gaps}
    assert labels - {"bench.sleep"}
    assert labels <= set(view["spans"]) | {"bench.sleep", "bench.step",
                                              "bench.host_other"}


def test_prefill_device_ms_by_hand(view, raw, expect):
    sec = sum(r1 - r0 for name, r0, r1 in raw.modules
              if name in ("jit_engine_prefill_chunk",
                          "jit_engine_sample_first",
                          "jit_engine_write_slot"))
    got = engine_trace.prefill_device_ms({"trace": view})
    assert got == pytest.approx(1e3 * sec / expect["installs"], rel=1e-6)


def test_decode_host_ms_by_hand(view, raw, expect):
    sec = sum(s1 - s0 for name, s0, s1, _ in raw.spans
              if name in ("engine.decode.launch", "engine.decode.replay"))
    steps = [types.SimpleNamespace(decode_ticks=expect["decode_ticks"])]
    got = engine_trace.decode_host_ms({"trace": view,
                                       "traced_steps": steps})
    assert got == pytest.approx(1e3 * sec / expect["decode_ticks"],
                                rel=1e-6)


def test_a_trace_without_the_engines_names_reads_nothing():
    """A program whose spans and programs are named otherwise (here, the
    kernel fixture's ``jit_prog`` and ``bench.*`` spans) gives no
    reading, not a zero."""
    tr = engine_trace.engine_view(OLD_FIXTURE)
    steps = [types.SimpleNamespace(decode_ticks=8)]
    assert engine_trace.prefill_device_ms({"trace": tr}) is None
    assert engine_trace.decode_host_ms({"trace": tr,
                                        "traced_steps": steps}) is None
    assert tr["spans"] == {} and set(tr["programs"]) == {"jit_prog"}


def _ctx(per_request, dues, window=(10.0, 20.0)):
    return {"rec": types.SimpleNamespace(due=dues), "window": window,
            "per_request": per_request}


def test_queue_wait_ms_engine_by_hand():
    st = types.SimpleNamespace
    per = {0: st(arrival_wall=9.0, admitted_wall=9.5),    # due before W0
           1: st(arrival_wall=11.0, admitted_wall=11.25),
           2: st(arrival_wall=12.0, admitted_wall=12.75),
           3: st(arrival_wall=13.0, admitted_wall=None)}  # not admitted
    dues = {0: 9.0, 1: 11.0, 2: 12.0, 3: 13.0}
    assert engine_trace.queue_wait_ms_engine(_ctx(per, dues)) == 500.0
    # An engine without the admission stamp gives no reading.
    old = {r: st(arrival_wall=v.arrival_wall) for r, v in per.items()}
    assert engine_trace.queue_wait_ms_engine(_ctx(old, dues)) is None


def test_admission_held_share_by_hand():
    ctx = {"dispatches": {"open": (10, 4), "close": (30, 14)}}
    assert engine_trace.admission_held_share(ctx) == 50.0
    ctx = {"dispatches": {"open": (10, None), "close": (30, None)}}
    assert engine_trace.admission_held_share(ctx) is None
    ctx = {"dispatches": {"open": (10, 4), "close": (10, 4)}}
    assert engine_trace.admission_held_share(ctx) is None
