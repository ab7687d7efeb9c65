"""Percent of the leaf device time whose instruction has no layer."""

from benchmarks.lib import scopes


def read(run):
    return scopes.read(run, "unscoped_device_share.decode", lambda name, row: row["layer"] == scopes.UNSCOPED, over="leaf")
