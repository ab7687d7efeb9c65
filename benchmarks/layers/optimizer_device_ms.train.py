"""Device ms a step of the phase ``optimizer``: gradient clipping, the AdamW
update and ``apply_updates``."""

from benchmarks.lib import scopes


def read(run):
    return scopes.read(run, "optimizer_device_ms.train", lambda name, row: row["phase"] == "optimizer")
