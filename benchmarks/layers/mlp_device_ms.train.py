"""Device ms a step under the layer ``mlp`` (the feed-forward of every
attention block, its LayerNorm included), forward and backward."""

from benchmarks.lib import scopes


def read(run):
    return scopes.read(run, "mlp_device_ms.train", lambda name, row: row["layer"] == "mlp",
                       parts=lambda name, row: row["phase"])
