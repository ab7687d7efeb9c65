"""Device ms a decode step spent in the shortcut branch: ``moe/route`` +
``moe/experts`` + ``moe/zero`` + ``moe/combine`` in the phase ``decode`` (the
router over 768 outputs, the 16 held experts on the step's tokens, the
identity experts' ``w0 * x``), from the run's table of device time by program
scope (``lib/scopes.py``). Prints the parts. ``None`` where there is no such
table or the program opens no ``moe/zero`` scope (a parent commit, another
family's cell)."""

from benchmarks.lib import scopes

NAME = "longcat_shortcut_step_ms.decode"
LAYERS = ("moe/route", "moe/experts", "moe/zero", "moe/combine")


def read(run):
    found = scopes.times(run, NAME)
    if found is None:
        return None
    parts = found.by(lambda name, row: row["layer"], lambda name, row: row["phase"] == "decode" and row["layer"] in LAYERS)
    if not parts.get("moe/zero"):
        return None
    steps = scopes.per(run)[0]["decode"]
    print(f"{NAME}: ms a step: " + ", ".join(f"{k} {v / 1e6 / steps:.3f}" for k, v in sorted(parts.items(), key=lambda kv: -kv[1])),
          flush=True)
    return sum(parts.values()) / 1e6 / steps
