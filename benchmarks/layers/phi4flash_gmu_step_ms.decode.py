"""Device ms a decode step spent in the gated memory units: the scope ``gmu`` in
the phase ``decode`` (seven layers, each the input projection, its silu, the
product with the memory and the output projection: two products of 2560 x 5120,
no state), from the run's table of device time by program scope
(``lib/scopes.py``). ``None`` where there is no such table or the program opens
no such scope (a parent commit, another family's cell)."""

from benchmarks.lib import scopes

NAME = "phi4flash_gmu_step_ms.decode"


def read(run):
    if "gmu" not in (run["family"].cfg.get("layer_types") or ()):
        return None
    found = scopes.times(run, NAME)
    if found is None:
        return None
    ns = sum(found.by(lambda _, row: row["layer"], lambda _, row: row["phase"] == "decode" and row["layer"] == "gmu").values())
    if not ns:
        return None
    steps = scopes.per(run)[0]["decode"]
    print(f"{NAME}: {ns / 1e6 / steps:.3f} ms a step under gmu", flush=True)
    return ns / 1e6 / steps
