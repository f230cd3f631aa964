"""Arithmetic behind the per-layer metric readers (``bench/metrics/``).

Each reader takes the run's context: ``rec`` (the serving recorder),
``window`` (W0, W1 on the host clock), ``steps`` (engine steps in the
window), ``traced_steps`` (engine steps of the traced stretch), ``arch``,
``pool``, ``trace`` (``trace.reduce`` output or None) and ``peaks``.
A reader with nothing to read returns None; a share of a
peak or a roofline is never reported as 0 for want of data.
"""
from __future__ import annotations

import bisect

from bench import trace as trace_lib, work

# The decode kernel's device ops: the masked Pallas step the engine's
# macro-step runs once per layer per tick, named in the trace after its
# jitted entry point.
DECODE_KERNEL = r"^decode_linear_attention\b"


def _window_dues(ctx) -> dict:
    W0, W1 = ctx["window"]
    return {r: d for r, d in ctx["rec"].due.items() if W0 <= d < W1}


def queue_wait_ms(ctx):
    rec = ctx["rec"]
    w = [rec.admit[r] - d for r, d in _window_dues(ctx).items()
         if r in rec.admit]
    return 1e3 * sum(w) / len(w) if w else None


def prefill_phase_ms(ctx):
    rec = ctx["rec"]
    w = [rec.token_times[r][0] - rec.admit[r]
         for r in _window_dues(ctx) if r in rec.admit and rec.token_times[r]]
    return 1e3 * sum(w) / len(w) if w else None


def _decode_steps(ctx):
    return [s for s in ctx["steps"] if s.decode_ticks]


def decode_tick_ms(ctx):
    st = _decode_steps(ctx)
    ticks = sum(s.decode_ticks for s in st)
    return 1e3 * sum(s.t1 - s.t0 for s in st) / ticks if ticks else None


def mfu_decode(ctx):
    """Model FLOPs of the tokens the window's decode steps produced, over
    those steps' wall time times the chip's peak."""
    st = _decode_steps(ctx)
    if not st:
        return None
    starts = [s.t0 for s in st]
    rec, arch = ctx["rec"], ctx["arch"]
    flops = 0
    for rid, ts in rec.token_times.items():
        plen = len(rec.req[rid].prompt)
        for j, t in enumerate(ts[1:], start=1):
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and t <= st[i].t1:
                flops += work.decode_token_flops(arch, plen + j)
    wall = sum(s.t1 - s.t0 for s in st)
    return 100.0 * flops / (wall * ctx["peaks"]["bf16_flops_per_s"])


def idle_share(ctx):
    tr = ctx["trace"]
    if not tr or not tr["window_s"] or not tr["devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def decode_step_roofline(ctx):
    """Least time of the decode kernel's calls over their summed device
    time in the trace. The kernel skips the rows of idle slots, so the
    least work is that of the rows that decoded: every token a decode step
    of the traced stretch delivered is one slot's kv-head rows, once per
    layer."""
    tr = ctx["trace"]
    if not tr:
        return None
    sec, count = trace_lib.kernel_time(tr, DECODE_KERNEL)
    tokens = sum(s.tokens for s in ctx["traced_steps"] if s.decode_ticks)
    if not count or sec <= 0 or not tokens:
        return None
    a = ctx["arch"]
    w = work.decode_step_call(a["num_kv_heads"],
                              a["num_heads"] // a["num_kv_heads"],
                              work.feature_dim(a), a["head_dim"])
    n = tokens * a["num_layers"]
    t, _ = work.least_time(n * w["flops"], n * w["bytes"], ctx["peaks"])
    return 100.0 * t / sec


