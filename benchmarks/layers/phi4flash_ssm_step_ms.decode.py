"""Device ms a decode step spent in the nine Mamba mixers: ``ssm/proj_in`` +
``ssm/conv`` + ``ssm/select`` + ``ssm/update`` + ``ssm/out`` in the phase
``decode`` (the projections, the window's shift and convolution, the step size
with ``B`` and ``C``, the state read, updated and written, the memory layer's
``m`` with the skip, the gate and the output projection), from the run's table
of device time by program scope (``lib/scopes.py``): ``core/ssm.py``'s step as
``jamba_ssm_step_ms.decode`` reads it on that cell's 26 mixers. Prints the
parts. ``None`` where there is no such table, the configuration has no gated
memory unit or the program opens no ``ssm/update`` scope."""

from benchmarks.lib import scopes

NAME = "phi4flash_ssm_step_ms.decode"
LAYERS = ("ssm/proj_in", "ssm/conv", "ssm/select", "ssm/update", "ssm/out")


def read(run):
    if "gmu" not in (run["family"].cfg.get("layer_types") or ()):
        return None
    found = scopes.times(run, NAME)
    if found is None:
        return None
    parts = found.by(lambda _, row: row["layer"], lambda _, row: row["phase"] == "decode" and row["layer"] in LAYERS)
    if not parts.get("ssm/update"):
        return None
    steps = scopes.per(run)[0]["decode"]
    print(f"{NAME}: ms a step: " + ", ".join(f"{k} {v / 1e6 / steps:.3f}" for k, v in sorted(parts.items(), key=lambda kv: -kv[1])),
          flush=True)
    return sum(parts.values()) / 1e6 / steps
