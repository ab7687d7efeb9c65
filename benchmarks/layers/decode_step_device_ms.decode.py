"""Device ms a step of the decode loop: the leaf time of the phase
``decode`` over ``calls x (new_tokens - 1)`` steps."""

from benchmarks.lib import scopes


def read(run):
    return scopes.read(run, "decode_step_device_ms.decode", lambda name, row: row["phase"] == "decode", over="decode")
