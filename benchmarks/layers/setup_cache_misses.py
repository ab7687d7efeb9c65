"""Programs of set-up that the persistent compilation cache did not serve:
the ``startup/compile`` spans whose ``cache`` is ``"miss"``, anywhere in
set-up, from the program's start-up record (``lib/startup.py``). 0 on a warm
cache. Prints their functions. ``None`` where the program holds no record."""

from benchmarks.lib import startup


def read(run):
    return startup.metric(run, "cache_misses")
