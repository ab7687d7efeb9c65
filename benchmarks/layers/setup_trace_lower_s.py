"""Seconds of set-up spent tracing and lowering: the self time of the
``startup/trace`` and ``startup/lower`` spans (JAX's own events) outside
``TrainState.create``, from the program's start-up record cut to set-up
(``lib/startup.py``). Prints trace and lower apart, by function. ``None`` where
the program holds no record."""

from benchmarks.lib import startup


def read(run):
    return startup.metric(run, "trace_lower_s")
