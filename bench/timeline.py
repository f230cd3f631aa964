"""Window arithmetic on a serving timeline, all on the host clock.

Pure functions over plain numbers, so the tests can feed hand-made
timelines. A request is due at ``due``; its tokens are delivered at the
times in ``token_times`` (tokens of one macro-step share a time).
"""
from __future__ import annotations

import math
from typing import Iterable, Sequence


def percentile(values: Iterable[float], q: float) -> float | None:
    """Linear-interpolated q-quantile (0 <= q <= 1); None when empty."""
    xs = sorted(values)
    if not xs:
        return None
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def in_window(t: float, w0: float, w1: float) -> bool:
    return w0 <= t < w1


def ttfts(dues: dict, first_times: dict, w0: float, w1: float) -> dict:
    """rid -> time from due to first token, for requests due in the window
    that got one (the others are failures of the window)."""
    return {rid: first_times[rid] - d for rid, d in dues.items()
            if in_window(d, w0, w1) and rid in first_times}


def tpot(times: Sequence[float], w0: float, w1: float) -> float | None:
    """(last - first) / (n - 1) over a request's tokens delivered in the
    window; None unless they span at least two deliveries."""
    ts = [t for t in times if in_window(t, w0, w1)]
    if len(ts) < 2 or ts[-1] <= ts[0]:
        return None
    return (ts[-1] - ts[0]) / (len(ts) - 1)


def tokens_in_window(token_times: dict, w0: float, w1: float) -> int:
    return sum(1 for ts in token_times.values() for t in ts
               if in_window(t, w0, w1))


def rate(count: int, w0: float, w1: float) -> float:
    """Completed work per second over the whole window."""
    return count / (w1 - w0)


def busy_union(intervals: Iterable[tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Iterable[tuple[float, float]], w0: float,
         w1: float) -> list[tuple[float, float]]:
    """Idle [start, end) stretches of [w0, w1) between busy intervals."""
    out, t = [], w0
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, w1)))
        t = max(t, e)
        if t >= w1:
            break
    if t < w1:
        out.append((t, w1))
    return [(s, e) for s, e in out if e > s]
