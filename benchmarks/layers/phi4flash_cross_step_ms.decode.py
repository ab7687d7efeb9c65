"""Device ms a decode step spent on the shared cache: ``yoco/kv`` (the owning
layer's projections and its write of the step's row) + ``yoco/cross`` (the
cross layers' reads: two batched products each over the one cache) in the phase
``decode``, from the run's table of device time by program scope
(``lib/scopes.py``). Prints the parts. ``None`` where there is no such table or
the program opens no ``yoco/cross`` scope (a parent commit, another family's cell)."""

from benchmarks.lib import scopes

NAME = "phi4flash_cross_step_ms.decode"
LAYERS = ("yoco/kv", "yoco/cross")


def read(run):
    if "cross_attention" not in (run["family"].cfg.get("layer_types") or ()):
        return None
    found = scopes.times(run, NAME)
    if found is None:
        return None
    parts = found.by(lambda _, row: row["layer"], lambda _, row: row["phase"] == "decode" and row["layer"] in LAYERS)
    if not parts.get("yoco/cross"):
        return None
    steps = scopes.per(run)[0]["decode"]
    print(f"{NAME}: ms a step: " + ", ".join(f"{k} {v / 1e6 / steps:.3f}" for k, v in sorted(parts.items(), key=lambda kv: -kv[1])),
          flush=True)
    return sum(parts.values()) / 1e6 / steps
