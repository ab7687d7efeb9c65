"""Profiling utilities — the observability upgrade over the reference, which
has no profiler integration at all (SURVEY §5.1): a ``jax.profiler`` trace
context for xprof/TensorBoard and a step timer for throughput accounting.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional, Sequence


@contextlib.contextmanager
def trace(log_dir: str, create_perfetto_link: bool = False):
    """Capture a JAX profiler trace (XLA + host) under ``log_dir``; view with
    TensorBoard's profile plugin or xprof."""
    import jax

    jax.profiler.start_trace(log_dir, create_perfetto_link=create_perfetto_link)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class StepTimer:
    """Wall-clock step timing with warmup discard and percentile summary.

    Dispatch is asynchronous: wait for the step's result before ``tick()``
    (``jax.block_until_ready`` or a host fetch such as ``float(loss)``), or
    the timing measures only the enqueue.
    """

    def __init__(self, warmup: int = 1):
        self.warmup = warmup
        self._times: List[float] = []
        self._last: Optional[float] = None

    def start(self) -> None:
        self._last = time.perf_counter()

    def tick(self) -> None:
        now = time.perf_counter()
        if self._last is not None:
            self._times.append(now - self._last)
        self._last = now

    @property
    def steps(self) -> List[float]:
        return self._times[self.warmup :]

    def mean(self) -> float:
        steps = self.steps
        if not steps:
            raise ValueError("No timed steps (after warmup discard)")
        return sum(steps) / len(steps)

    def percentile(self, p: float) -> float:
        """The p-th percentile (0..100) of the retained step times, linearly
        interpolated between order statistics."""
        steps = self.steps
        if not steps:
            raise ValueError("No timed steps (after warmup discard)")
        return percentile(steps, p)

    def summary(self) -> Dict[str, float]:
        """The percentile summary the class docstring promises: p50/p90/p99
        plus mean and sample count. Below :data:`LOW_N` samples the
        percentiles are exact order statistics (nearest rank, no
        interpolation) and the row carries ``low_n`` — a 3-sample window has
        no p99 tail, and interpolating one would print a fake number
        consumers (obs_report, obs_diff) cannot distinguish from a real
        tail. (A caller whose samples need normalization before
        summarizing builds its percentiles from :func:`percentile`
        directly and applies the same rule.)"""
        return summarize_latencies(self.steps)

    def steps_per_sec(self) -> float:
        return 1.0 / self.mean()


def percentile(values: Sequence[float], p: float) -> float:
    """Linear-interpolation percentile of a non-empty sequence (numpy's
    default method, with a ValueError contract on bad inputs)."""
    if not values:
        raise ValueError("percentile of an empty sequence")
    if not 0.0 <= p <= 100.0:
        raise ValueError(f"percentile p must be in [0, 100], got {p}")
    import numpy as np

    return float(np.percentile(list(values), p))


# below this many samples, percentile summaries switch to exact order
# statistics and are marked low_n (interpolated tails over 3 points are
# extrapolation dressed up as measurement)
LOW_N = 5


def exact_percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile: the smallest order statistic covering at
    least p% of the sample — always an observed value, never interpolated."""
    if not values:
        raise ValueError("percentile of an empty sequence")
    if not 0.0 <= p <= 100.0:
        raise ValueError(f"percentile p must be in [0, 100], got {p}")
    s = sorted(float(v) for v in values)
    import math

    return s[max(int(math.ceil(p / 100.0 * len(s))) - 1, 0)]


def summarize_latencies(values: Sequence[float]) -> Dict[str, float]:
    """``{mean, p50, p90, p99, n[, low_n]}`` — the shared latency-summary
    shape (StepTimer.summary, span breakdowns, SLO aggregation). Below
    :data:`LOW_N` samples: exact order statistics plus ``low_n: True``."""
    vals = [float(v) for v in values]
    if not vals:
        raise ValueError("No timed steps (after warmup discard)")
    low_n = len(vals) < LOW_N
    pct = exact_percentile if low_n else percentile
    out = {
        "mean": sum(vals) / len(vals),
        "p50": pct(vals, 50),
        "p90": pct(vals, 90),
        "p99": pct(vals, 99),
        "n": float(len(vals)),
    }
    if low_n:
        out["low_n"] = True
    return out
