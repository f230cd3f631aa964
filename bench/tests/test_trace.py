"""The trace reduction on a small trace recorded on the chip.

The fixture (``fixtures/trace``, made by ``record_trace_fixture.py``)
holds the serving decode kernel called four times in a jitted loop, the
loop run twice with a 50 ms ``bench.sleep`` host span between the runs.
"""
import json
import os

import pytest

from bench import readers, trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "trace")


@pytest.fixture(scope="module")
def expect():
    with open(os.path.join(FIXTURE, "expect.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(expect):
    return trace.reduce(FIXTURE, expect["window_s"])


def test_busy_union_and_idle_share(reduced, expect):
    assert reduced["devices"] == 1
    # The host slept 50 ms between the two calls: the device was idle for
    # at least that long, and busy for some of the window.
    assert 0 < reduced["busy_s"] <= reduced["window_s"] - expect["sleep_s"]
    share = readers.idle_share({"trace": reduced})
    assert 100 * expect["sleep_s"] / expect["window_s"] <= share < 100


def test_per_kernel_sum(reduced, expect):
    sec, count = trace.kernel_time(reduced, readers.DECODE_KERNEL)
    assert count == expect["kernel_calls"]
    assert 0 < sec < reduced["busy_s"]
    # Leaf ops only: the loop that holds the kernel is not summed, so the
    # per-op times add up to no more than the busy time.
    assert not any(n.startswith("while") for n in reduced["per_op"])
    assert sum(v[0] for v in reduced["per_op"].values()) <= (
        reduced["busy_s"] * 1.0001)


def test_longest_gap_is_the_host_sleep(reduced, expect):
    name, sec = reduced["gaps"][0]
    assert name == "bench.sleep"
    assert sec >= expect["sleep_s"]
    assert len(reduced["ops"]) <= 10 and len(reduced["gaps"]) <= 10


def test_op_name_by_hand():
    assert trace.op_name("%copy.3 = f32[2,3]{1,0} copy(f32[2,3]{1,0} %p)") == (
        "copy.3", "f32[2,3]", False)
    assert trace.op_name("%while.6 = (s32[], f32[4]) while((s32[], f32[4]) "
                         "%t), condition=%c, body=%b")[2]
    assert not trace.op_name("%fusion.1 = f32[4] fusion(f32[4] %a), "
                             "kind=kLoop, calls=%fused")[2]
