"""Device ms a step of the input side: ``embed`` + ``prefix_dropout`` +
``input_adapter``, forward and backward (the ``embed_pos_grad_*`` kernel included)."""

from benchmarks.lib import scopes


def read(run):
    return scopes.read(run, "embed_device_ms.train", lambda name, row: row["layer"] in scopes.EMBED_LAYERS,
                       parts=lambda name, row: f"{row['phase']}/{row['layer']}")
