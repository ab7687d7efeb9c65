"""The benchmark's stall-hardened slope measurement, under a fake clock.

robust_slope's contract: per-iteration time from interleaved short/long
chain timings, min-reduced per estimate, median across estimates, with
stall-corrupted (non-positive) estimates dropped — a host stall must not
surface as inflated throughput (the failure mode the median replaced min
for), and an all-stall measurement must fail loudly instead of returning a
garbage sentinel.
"""

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import bench  # noqa: E402


class FakeClock:
    """perf_counter substitute advanced by the fake run() below."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def make_run(clock, per_step: float, stall_schedule=None):
    """run(k) advances the clock by k * per_step, plus any scheduled stall:
    ``stall_schedule`` maps call index -> extra seconds."""
    calls = {"n": 0}
    stall_schedule = stall_schedule or {}

    def run(k):
        extra = stall_schedule.get(calls["n"], 0.0)
        calls["n"] += 1
        clock.now += k * per_step + extra

    return run


@pytest.fixture
def clock(monkeypatch):
    c = FakeClock()
    monkeypatch.setattr(bench.time, "perf_counter", c)
    return c


def test_clean_measurement_recovers_step_time(clock):
    run = make_run(clock, per_step=0.005)
    s = bench.robust_slope(run, 2, 22, estimates=3, reps=2)
    assert s == pytest.approx(0.005, rel=1e-9)


def test_stall_on_long_chain_does_not_inflate_estimate(clock):
    # calls: 2 compile, then per estimate: reps * (short, long).
    # Stall every long-chain rep of estimate 0 (call idxs 3 and 5): that
    # estimate's slope is inflated; the median of the three estimates must
    # still be the clean step time.
    run = make_run(clock, per_step=0.005, stall_schedule={3: 2.0, 5: 2.0})
    s = bench.robust_slope(run, 2, 22, estimates=3, reps=2)
    assert s == pytest.approx(0.005, rel=1e-9)


def test_stall_on_short_chain_does_not_deflate_result(clock):
    # Stall both short-chain reps of estimate 0 (call idxs 2 and 4): that
    # estimate's slope goes negative (t_short > t_long) and must be dropped,
    # not selected — min-of-estimates would have returned it.
    run = make_run(clock, per_step=0.005, stall_schedule={2: 2.0, 4: 2.0})
    s = bench.robust_slope(run, 2, 22, estimates=3, reps=2)
    assert s == pytest.approx(0.005, rel=1e-9)


def test_all_estimates_corrupted_raises(clock):
    # every short-chain rep stalls -> every estimate non-positive
    stalls = {i: 5.0 for i in range(2, 20, 2)}
    run = make_run(clock, per_step=0.005, stall_schedule=stalls)
    with pytest.raises(RuntimeError, match="non-positive"):
        bench.robust_slope(run, 2, 22, estimates=3, reps=2)


# --- interleaved_slopes (the multi-variant harness shared by tools/*_ab.py) ---


def test_interleaved_recovers_each_variant(clock):
    runs = {"a": make_run(clock, per_step=0.005), "b": make_run(clock, per_step=0.008)}
    meds = bench.interleaved_slopes(runs, 2, 22, estimates=3, reps=2)
    assert meds["a"] == pytest.approx(0.005, rel=1e-9)
    assert meds["b"] == pytest.approx(0.008, rel=1e-9)


def test_interleaved_stall_on_one_variant_leaves_other_clean(clock):
    # Call order per rep is a-short, a-long, b-short, b-long. Stall b's
    # short chains in estimate 0 (per-variant call idxs 0 and 2 of the
    # measurement phase): b's first estimate goes negative and is dropped;
    # a must be untouched and b's median comes from its clean estimates.
    runs = {
        "a": make_run(clock, per_step=0.005),
        "b": make_run(clock, per_step=0.008, stall_schedule={0: 2.0, 2: 2.0}),
    }
    meds = bench.interleaved_slopes(runs, 2, 22, estimates=3, reps=2)
    assert meds["a"] == pytest.approx(0.005, rel=1e-9)
    assert meds["b"] == pytest.approx(0.008, rel=1e-9)


def test_interleaved_all_stalled_variant_returns_none(clock):
    # every short chain of 'b' stalls -> all b estimates non-positive ->
    # None (the tools print a rerun message), while 'a' still measures
    stalls = {i: 5.0 for i in range(0, 12, 2)}
    runs = {
        "a": make_run(clock, per_step=0.005),
        "b": make_run(clock, per_step=0.008, stall_schedule=stalls),
    }
    meds = bench.interleaved_slopes(runs, 2, 22, estimates=3, reps=2)
    assert meds["a"] == pytest.approx(0.005, rel=1e-9)
    assert meds["b"] is None


def test_auto_microbatch_always_divides():
    """The derived chunk count must divide every batch size (an indivisible
    pair silently disables chunking in the train path) and prefer chunks of
    4 where possible."""
    for b in range(1, 65):
        mb = bench.auto_microbatch(b)
        assert b % mb == 0, (b, mb)
        chunk = b // mb
        assert chunk in (1, 2, 4), (b, mb)
        if b % 4 == 0:
            assert chunk == 4, (b, mb)
        elif b % 2 == 0:
            assert chunk == 2, (b, mb)
