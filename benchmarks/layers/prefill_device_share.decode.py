"""Percent of the leaf device time under the phase ``prefill``: the prompt
pass, the first token, and what is made once a call for the decode loop."""

from benchmarks.lib import scopes


def read(run):
    return scopes.read(run, "prefill_device_share.decode", lambda name, row: row["phase"] == "prefill", over="leaf")
