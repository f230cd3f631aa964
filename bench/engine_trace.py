"""The program's own view of a traced window, and the arithmetic of the
per-layer metrics built on it.

``engine_view`` reads the same ``.xplane.pb`` as ``trace.reduce``, on the
same clock, and gives:

- ``programs``: per jitted program, its device seconds and call count
  from the device's ``XLA Modules`` line, by the module's name without
  its ``(id)`` suffix (``jit_engine_macro_decode``), averaged over
  devices;
- ``spans``: per ``engine.*`` host span name, [host seconds, count,
  device-idle seconds inside the spans] (idle of the first device);
- ``engine_gaps``: the ten longest idle stretches of the first device,
  each labelled with the innermost ``engine.*`` span covering its
  middle, else with its ``bench.*`` label as in ``trace.reduce``'s
  ``gaps``.

The readers take a context like ``readers``' own, whose ``trace`` also
holds these keys, plus ``per_request`` (the engine's ``RequestStats`` by
rid) and ``dispatches`` (``{"open": (n, held), "close": (n, held)}`` of
``decode_dispatches`` and ``decode_dispatches_while_ready``). A cell's
run does not pass them yet (PERF.md §7), so no cell reports these
metrics. The span and program names are kept here as literals and not
imported from the program: a renamed span or program reads as a missing
metric, not as a silently different one.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

import jax

from bench import timeline
from bench import trace as trace_lib

MODULES_LINE = "XLA Modules"
ENGINE_SPAN = "engine."
_MODULE_ID = re.compile(r"\(\d+\)$")

SPAN_INSTALL = "engine.prefill.install"
SPANS_DECODE_HOST = ("engine.decode.launch", "engine.decode.replay")
PREFILL_PROGRAMS = ("jit_engine_prefill_chunk", "jit_engine_sample_first",
                    "jit_engine_write_slot")


def engine_view(log_dir: str, label_of=None) -> dict:
    """``programs``, ``spans`` and ``engine_gaps`` of one trace (see the
    module's docstring); ``label_of`` renames ``bench.*`` spans as in
    ``trace.reduce``."""
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[0])
    programs: dict[str, list] = {}
    first, n, eng, bench = None, 0, [], []
    for plane in pd.planes:
        if trace_lib.DEVICE_PLANE.match(plane.name):
            n += 1
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for ev in line.events:
                        rec = programs.setdefault(
                            _MODULE_ID.sub("", ev.name), [0.0, 0])
                        rec[0] += ev.duration_ns * 1e-9
                        rec[1] += 1
                elif line.name == trace_lib.OPS_LINE and first is None:
                    first = [(ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9)
                             for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    span = (ev.name, ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9)
                    if ev.name.startswith(ENGINE_SPAN):
                        eng.append(span)
                    elif ev.name.startswith("bench."):
                        bench.append(span)
    for rec in programs.values():
        rec[0] /= max(n, 1)
        rec[1] //= max(n, 1)
    idle = (timeline.gaps(first, min(s for s, _ in first),
                          max(e for _, e in first)) if first else [])
    starts = [s for s, _ in idle]
    spans: dict[str, list] = {}
    for name, s, e in eng:
        rec = spans.setdefault(name, [0.0, 0, 0.0])
        rec[0] += e - s
        rec[1] += 1
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(idle) and idle[i][0] < e:
            rec[2] += max(0.0, min(e, idle[i][1]) - max(s, idle[i][0]))
            i += 1
    bench = trace_lib._labelled(bench, label_of)
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:10]
    gap_list = []
    for s, e in longest:
        mid = (s + e) / 2
        inner = [(name, s1, e1) for name, s1, e1 in eng if s1 <= mid < e1]
        gap_list.append([trace_lib._cover(inner or bench, mid), e - s])
    return {"programs": programs, "spans": spans, "engine_gaps": gap_list}


def queue_wait_ms_engine(ctx):
    """Mean time from ``submit()`` to admission, both on the engine's
    clock (``RequestStats.arrival_wall``, ``admitted_wall``), over the
    requests due in the window."""
    per = ctx.get("per_request") or {}
    W0, W1 = ctx["window"]
    w = []
    for r, d in ctx["rec"].due.items():
        st = per.get(r)
        a = getattr(st, "admitted_wall", None)
        if W0 <= d < W1 and a is not None and st.arrival_wall is not None:
            w.append(a - st.arrival_wall)
    return 1e3 * sum(w) / len(w) if w else None


def admission_held_share(ctx):
    """Share of the window's macro-steps dispatched while a request stood
    ready and a slot was free (``decode_dispatches_while_ready`` over
    ``decode_dispatches``, both counted across the window), in %."""
    d = ctx.get("dispatches")
    if not d:
        return None
    (n0, h0), (n1, h1) = d["open"], d["close"]
    if h0 is None or h1 is None or n1 <= n0:
        return None
    return 100.0 * (h1 - h0) / (n1 - n0)


def prefill_device_ms(ctx):
    """Device time of the prefill programs (chunks, first-token sampler,
    slot install) in the traced stretch, per request installed in it."""
    tr = ctx["trace"]
    if not tr or "programs" not in tr:
        return None
    installs = tr["spans"].get(SPAN_INSTALL, (0.0, 0))[1]
    sec = sum(tr["programs"].get(p, (0.0, 0))[0] for p in PREFILL_PROGRAMS)
    if not installs or sec <= 0:
        return None
    return 1e3 * sec / installs


def decode_host_ms(ctx):
    """Host time of the decode launch and the token replay in the traced
    stretch, per decode tick its steps advanced: the host's share of a
    tick, apart from the wait on the device."""
    tr = ctx["trace"]
    if not tr or "spans" not in tr:
        return None
    spans = [tr["spans"].get(n) for n in SPANS_DECODE_HOST]
    ticks = sum(s.decode_ticks for s in ctx["traced_steps"])
    if not ticks or any(sp is None for sp in spans):
        return None
    return 1e3 * sum(sp[0] for sp in spans) / ticks
