"""Seconds of set-up that no span of the program's start-up record holds:
``setup_s`` less imports, ``TrainState.create``, tracing and lowering, and
compiling (``lib/startup.py``): the interpreter and jax coming up, the chip,
the benchmark's weight draw and feed on the host, the first calls' device
time. Prints the parts beside their sum, which is ``setup_s``. ``None`` where
the program holds no record."""

from benchmarks.lib import startup


def read(run):
    return startup.metric(run, "unattributed_s")
