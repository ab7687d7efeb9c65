"""Device ms a decode step spent in the differential attentions outside the
shared cache's reads: ``diff/step`` (the eight window layers' two batched
products over their rings, and the owning layer's own read of the shared cache
after its write) + ``diff/combine`` (the maps' difference and the subnorm, of
all sixteen attention layers) + ``diff/proj`` (their projections) in the phase
``decode``, from the run's table of device time by program scope
(``lib/scopes.py``). The seven cross layers' reads are
``phi4flash_cross_step_ms.decode``'s. Prints the parts. ``None`` where there is
no such table or the program opens no ``diff/step`` scope (a parent commit,
another family's cell)."""

from benchmarks.lib import scopes

NAME = "phi4flash_diff_step_ms.decode"
LAYERS = ("diff/step", "diff/combine", "diff/proj")


def read(run):
    if "cross_attention" not in (run["family"].cfg.get("layer_types") or ()):
        return None
    found = scopes.times(run, NAME)
    if found is None:
        return None
    parts = found.by(lambda _, row: row["layer"], lambda _, row: row["phase"] == "decode" and row["layer"] in LAYERS)
    if not parts.get("diff/step"):
        return None
    steps = scopes.per(run)[0]["decode"]
    print(f"{NAME}: ms a step: " + ", ".join(f"{k} {v / 1e6 / steps:.3f}" for k, v in sorted(parts.items(), key=lambda kv: -kv[1])),
          flush=True)
    return sum(parts.values()) / 1e6 / steps
