"""Seconds of set-up spent making programs ready, compiled or read back
from the persistent cache: the self time of the ``startup/compile`` spans
(JAX's ``backend_compile_duration``) outside ``TrainState.create``, from the
program's start-up record cut to set-up (``lib/startup.py``). Prints hits,
misses and the seconds of cache reads, by function. ``None`` where the program
holds no record."""

from benchmarks.lib import startup


def read(run):
    return startup.metric(run, "compile_s")
