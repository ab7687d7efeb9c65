"""Seconds of set-up the program spent importing its own packages: the self
time of the ``startup/import`` spans (a package's own modules and third-party
imports, not its sub-packages'), from the program's start-up record cut to
set-up (``lib/startup.py``). Prints the packages and the three modules
``training/__init__.py`` brackets, largest first. ``None`` where the program
holds no record."""

from benchmarks.lib import startup


def read(run):
    return startup.metric(run, "import_s")
