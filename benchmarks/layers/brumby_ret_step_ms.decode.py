"""Device ms a decode step spent in the retention layers: ``ret/proj`` +
``ret/gate`` + ``ret/update`` + ``ret/out`` in the phase ``decode`` (5 layers'
projections with their q/k norms and rotary, the gate, the state read, decayed,
updated, written and read for ``y``, the output projection), from the run's
table of device time by program scope (``lib/scopes.py``). Prints the parts.
``None`` where there is no such table or the program opens no ``ret/update``
scope (a parent commit, another family's cell)."""

from benchmarks.lib import scopes

NAME = "brumby_ret_step_ms.decode"
LAYERS = ("ret/proj", "ret/gate", "ret/update", "ret/out")


def read(run):
    if "power_retention" not in (run["family"].cfg.get("layer_types") or ()):
        return None
    found = scopes.times(run, NAME)
    if found is None:
        return None
    parts = found.by(lambda name, row: row["layer"], lambda name, row: row["phase"] == "decode" and row["layer"] in LAYERS)
    if not parts.get("ret/update"):
        return None
    steps = scopes.per(run)[0]["decode"]
    print(f"{NAME}: ms a step: " + ", ".join(f"{k} {v / 1e6 / steps:.3f}" for k, v in sorted(parts.items(), key=lambda kv: -kv[1])),
          flush=True)
    return sum(parts.values()) / 1e6 / steps
