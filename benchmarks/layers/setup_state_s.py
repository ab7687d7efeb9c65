"""Seconds of set-up inside ``TrainState.create``: the ``startup/state_create``
span with everything that nests under it (the small programs ``tx.init``
builds leaf by leaf), host time, from the program's start-up record cut to
set-up (``lib/startup.py``). ``None`` where the program holds no record."""

from benchmarks.lib import startup


def read(run):
    return startup.metric(run, "state_s")
