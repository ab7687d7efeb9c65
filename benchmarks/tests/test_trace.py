"""The trace reduction: busy union, idle share, totals per name, idle gaps by
host annotation, on hand-made rows and on a piece of a real trace."""

import json
import os

import pytest

from benchmarks.lib import trace

ROWS = [["a", 0, 10], ["b", 5, 10], ["a", 30, 10], ["nested", 32, 2]]


def test_busy_is_a_union_not_a_sum():
    assert trace.merged_intervals(ROWS) == [(0, 15), (30, 40)]
    assert trace.busy_ns(ROWS) == 25
    assert trace.busy_ns([]) == 0


def test_idle_share_over_a_window():
    assert trace.idle_share(ROWS, (0, 50)) == pytest.approx(0.5)
    assert trace.idle_share(ROWS, (5, 35)) == pytest.approx(1 - 15 / 30)
    with pytest.raises(ValueError):
        trace.idle_share(ROWS, (5, 5))


def test_clip_keeps_the_part_inside():
    assert trace.clip(ROWS, (8, 31)) == [["a", 8, 2], ["b", 8, 7], ["a", 30, 1]]
    assert trace.clip(ROWS, None) == ROWS


def test_totals_and_top():
    totals = trace.totals_by_name(ROWS)
    assert totals == {"a": 20, "b": 10, "nested": 2}
    assert trace.top(totals, 2) == [["a", 20 / 1e9], ["b", 10 / 1e9]]


def test_idle_gaps_go_to_the_innermost_host_annotation():
    host = [["bench/outer", 14, 20], ["bench/inner", 20, 6]]
    gaps = trace.idle_gaps(ROWS, host, (0, 50))
    assert gaps == {"bench/inner": 15, "(no annotation)": 10}
    assert sum(gaps.values()) == 50 - trace.busy_ns(ROWS)


def test_host_window_needs_exactly_one():
    assert trace.host_window([["bench/window", 3, 4]], "bench/window") == (3, 7)
    with pytest.raises(ValueError):
        trace.host_window([], "bench/window")


RECORDED = os.path.join(os.path.dirname(__file__), "data", "trace_ar16k_train.json")


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        return json.load(f)


def test_recorded_trace_reduces(recorded):
    """A piece of this PR's first traced chip run of ``ar16k-train-b32``
    (TPU v5 lite): the numbers below were read from it by hand."""
    (plane, ops), = recorded["devices"].items()
    assert plane.startswith(trace.DEVICE_PLANE_PREFIX)
    window = trace.host_window(recorded["host"], "bench/window")
    expect = recorded["expect"]
    assert len(ops) == expect["n_ops"]
    assert trace.busy_ns(trace.clip(ops, window)) == pytest.approx(expect["busy_ns"])
    assert trace.idle_share(ops, window) == pytest.approx(expect["idle_share"])
    totals = trace.totals_by_name(trace.clip(ops, window))
    name, seconds = trace.top(totals, 1)[0]
    assert name == expect["top_name"] and seconds == pytest.approx(expect["top_seconds"])
    assert any("flash" in n for n in totals), "the flash kernels are named in the trace"
    gaps = trace.idle_gaps(ops, [h for h in recorded["host"] if h[0] != "bench/window"], window)
    assert sum(gaps.values()) == pytest.approx((window[1] - window[0]) - expect["busy_ns"])
