"""Device ms a decode step spent in the delta layers: ``kda/proj`` +
``kda/conv`` + ``kda/gate`` + ``kda/update`` + ``kda/out`` in the phase
``decode`` (6 layers' projections, the three convolutions over their windows
with the norms, the log-decays and steps, the state read, decayed, corrected
and written, the head norm, the output gate and the output projection), from
the run's table of device time by program scope (``lib/scopes.py``). Prints the
parts. ``None`` where there is no such table or the program opens no
``kda/update`` scope (a parent commit, another family's cell)."""

from benchmarks.lib import scopes

NAME = "ling_kda_step_ms.decode"
LAYERS = ("kda/proj", "kda/conv", "kda/gate", "kda/update", "kda/out")


def read(run):
    if "kda" not in (run["family"].cfg.get("layer_types") or ()):
        return None
    found = scopes.times(run, NAME)
    if found is None:
        return None
    parts = found.by(lambda name, row: row["layer"], lambda name, row: row["phase"] == "decode" and row["layer"] in LAYERS)
    if not parts.get("kda/update"):
        return None
    steps = scopes.per(run)[0]["decode"]
    print(f"{NAME}: ms a step: " + ", ".join(f"{k} {v / 1e6 / steps:.3f}" for k, v in sorted(parts.items(), key=lambda kv: -kv[1])),
          flush=True)
    return sum(parts.values()) / 1e6 / steps
