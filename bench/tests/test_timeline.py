"""Window arithmetic on hand-made timelines."""
from types import SimpleNamespace

import pytest

from bench import spec, timeline


def open_loop_e2e(dues, times, w0, w1):
    rec = SimpleNamespace(due=dues, token_times=times)
    return spec.driver("open_loop").end_to_end(rec, w0, w1)


def test_percentile_by_hand():
    assert timeline.percentile([], 0.9) is None
    assert timeline.percentile([5.0], 0.9) == 5.0
    xs = list(range(1, 11))                 # 1..10
    assert timeline.percentile(xs, 0.5) == pytest.approx(5.5)
    assert timeline.percentile(xs, 0.9) == pytest.approx(9.1)
    assert timeline.percentile(reversed(xs), 0.9) == pytest.approx(9.1)


def test_tpot_counts_only_the_window():
    ts = [0.5, 1.0, 1.0, 2.0, 3.0, 9.0]     # two tokens share a delivery
    assert timeline.tpot(ts, 1.0, 3.5) == pytest.approx((3.0 - 1.0) / 3)
    assert timeline.tpot([1.0, 1.0], 0.0, 5.0) is None   # one delivery
    assert timeline.tpot([0.1, 9.0], 1.0, 5.0) is None


def test_ttft_from_due_not_from_submission():
    dues = {0: 0.5, 1: 1.0, 2: 2.0, 3: 4.9}
    first = {0: 0.9, 1: 1.2, 3: 5.5}
    got = timeline.ttfts(dues, first, 1.0, 5.0)
    assert got == pytest.approx({1: 0.2, 3: 0.6})     # 0 before, 2 unanswered


def _steady(stall: float = 0.0):
    """20 requests due every 0.5 s, each answered in 0.1 s then one token
    every 0.05 s; a stall of ``stall`` s at t = 5 delays every delivery
    that falls after it."""
    dues, times = {}, {}
    for r in range(20):
        due = 0.5 * r
        ts = [due + 0.1 + 0.05 * k for k in range(10)]
        times[r] = [t + stall if t >= 5.0 else t for t in ts]
        dues[r] = due
    return dues, times


def test_a_stall_moves_p90_and_the_rate():
    d0, t0 = _steady()
    d1, t1 = _steady(stall=1.5)
    base = open_loop_e2e(d0, t0, 1.0, 9.0)
    hit = open_loop_e2e(d1, t1, 1.0, 9.0)
    assert base["ttft_ms_p90"] == pytest.approx(100.0)
    assert base["tpot_ms_p90"] == pytest.approx(50.0)
    assert hit["ttft_ms_p90"] > base["ttft_ms_p90"] + 500
    assert hit["tpot_ms_p90"] > base["tpot_ms_p90"]
    assert hit["output_tok_s"] < base["output_tok_s"]
    # The rate is over all the work and all the time of the window.
    n = sum(1 for ts in t0.values() for t in ts if 1.0 <= t < 9.0)
    assert base["output_tok_s"] == pytest.approx(n / 8.0)


def test_busy_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 1.5), (2.0, 3.0), (2.5, 2.7), (4.0, 4.5)]
    assert timeline.busy_union(iv) == pytest.approx(3.0)
    assert timeline.gaps(iv, 0.0, 5.0) == [(1.5, 2.0), (3.0, 4.0),
                                           (4.5, 5.0)]
    assert timeline.busy_union([]) == 0.0
