"""Profiler trace of the measured window, and its reduction to numbers.

``Capture`` wraps ``jax.profiler`` around the window (a run with
``--trace 1``) and notes the host-clock length of the traced window.
``reduce`` reads the ``.xplane.pb`` back with nothing but JAX and gives:

- ``busy_s``: union of the intervals in which an op ran on the device,
  averaged over the devices;
- ``per_op``: per op name, the summed device time and event count;
- ``ops``: the ten op names that took most device time;
- ``gaps``: the ten longest idle stretches, each labelled with the host
  span (``bench.*`` annotations the drivers write) that covers it.
"""
from __future__ import annotations

import functools
import glob
import os
import re
import shutil
import tempfile
import time

import jax

from bench import timeline

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


class Capture:
    """Trace one window into a scratch directory (removed by ``close``)."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if enabled else None
        self.window_s = None

    def start(self):
        if self.enabled:
            # Device ops and the host's own annotations (level 1) only: the
            # Python tracer, on by default, records every function call of
            # the host loop, slows it, and makes the trace too large to
            # read within a run's time.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._t0 = time.perf_counter()

    def stop(self):
        if self.enabled:
            self.window_s = time.perf_counter() - self._t0
            jax.profiler.stop_trace()
            self.stop_s = time.perf_counter() - self._t0 - self.window_s

    def annotate(self, name: str):
        """Host span in the trace (a no-op context when not tracing)."""
        if self.enabled:
            return jax.profiler.TraceAnnotation(name)
        return _NULL

    def close(self):
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


def load_events(log_dir: str):
    """(device ops per device, host annotations) from the trace file.

    Device ops: {plane name: [(op text, start_s, end_s)]}; host
    annotations: [(name, start_s, end_s)] for ``bench.*`` spans. Times are
    in seconds on the trace's common clock."""
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[0])
    dev, host = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append((ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9))
            dev[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append((ev.name, ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9))
    return dev, host


_CONTAINER = re.compile(r"\s(while|conditional|call)\(")
_LAYOUT = re.compile(r"\{[^}]*\}")


@functools.lru_cache(maxsize=None)
def op_name(text: str) -> tuple[str, str, bool]:
    """(short name, result type, is a container) of a device op event.

    The event's name is the op's HLO text, ``%copy.3 = f32[..] copy(..)``.
    A while loop, conditional or call spans the ops of its body, which
    have events of their own, so it is left out of per-op sums."""
    lhs, _, rhs = text.partition(" = ")
    rtype = _LAYOUT.sub("", rhs.split(" ", 1)[0]).rstrip(",")
    return (lhs.strip().lstrip("%"), rtype[:80], bool(_CONTAINER.search(rhs)))


def reduce(log_dir: str, window_s: float, label_of=None) -> dict:
    """Numbers of one traced window.

    ``per_op`` maps each device op's short name to [seconds, count, result
    type], averaged over devices, leaf ops only; metric readers pick their
    kernels from it. ``label_of(name, index)`` renames the index-th host
    span of that name (drivers use it to tell prefill steps from decode
    steps)."""
    dev, host = load_events(log_dir)
    n = max(len(dev), 1)
    busy, per_op = [], {}
    for ops in dev.values():
        busy.append(timeline.busy_union((s, e) for _, s, e in ops))
        for text, s, e in ops:
            name, rtype, container = op_name(text)
            if container:
                continue
            rec = per_op.setdefault(name, [0.0, 0, rtype])
            rec[0] += e - s
            rec[1] += 1
    for rec in per_op.values():
        rec[0] /= n
        rec[1] //= n
    first = next(iter(dev.values()), [])
    gap_list = []
    if first:
        w0 = min(s for _, s, _ in first)
        w1 = max(e for _, _, e in first)
        host_sorted = _labelled(host, label_of)
        idle = sorted(timeline.gaps(((s, e) for _, s, e in first), w0,
                                    w1), key=lambda g: g[0] - g[1])[:10]
        gap_list = [[_cover(host_sorted, (s + e) / 2), e - s]
                    for s, e in idle]
    top = sorted(per_op.items(), key=lambda kv: -kv[1][0])[:10]
    return {"busy_s": sum(busy) / n, "window_s": window_s, "devices": len(dev),
            "per_op": per_op,
            "ops": [[f"{k} {v[2]}", v[0]] for k, v in top],
            "gaps": gap_list}


def kernel_time(tr: dict, pattern: str) -> tuple[float, int]:
    """Summed device seconds and event count of the ops whose short name
    matches ``pattern``."""
    pat = re.compile(pattern)
    sec, cnt = 0.0, 0
    for name, (s, c, _) in tr["per_op"].items():
        if pat.search(name):
            sec += s
            cnt += c
    return sec, cnt


def _labelled(host, label_of):
    counts: dict[str, int] = {}
    out = []
    for name, s, e in sorted(host, key=lambda h: h[1]):
        i = counts.get(name, 0)
        counts[name] = i + 1
        out.append((label_of(name, i) if label_of else name, s, e))
    return out


def _cover(host, t: float) -> str:
    """Innermost host span covering time t (the harness, when none)."""
    best = None
    for name, s, e in host:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "bench.host_other"
