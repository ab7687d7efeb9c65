"""Device ms a call of the prompt pass's selection: what runs under ``prefill``
/ ``dsa/select`` in both full layers (the exact top-``index_topk`` of each
query's index scores as a mask: the selection kernels ``dsa_select_...`` and
what XLA runs around them), from the run's table of device time by program
scope (``lib/scopes.py``). Prints the parts by instruction stem, and the
selections a call makes (``lib/dots3_cost.py::selections``). ``None`` where
there is no such table or the program opens no ``dsa/select`` scope."""

from benchmarks.lib import dots3_cost, scopes

NAME = "dots3_select_device_ms.decode"


def read(run):
    cfg = run["family"].cfg
    if not cfg.get("index_topk"):
        return None
    found = scopes.times(run, NAME)
    if found is None:
        return None
    keep = lambda name, row: row["phase"] == "prefill" and row["layer"] == "dsa/select"  # noqa: E731
    parts = found.by(lambda name, row: name.split(".")[0], keep)
    if not parts:
        return None
    p, calls = run["cell"]["params"], scopes.per(run)[0][""]
    made = dots3_cost.full_layers(cfg) * dots3_cost.selections(cfg, p["batch_size"], p["prompt_len"])
    print(f"{NAME}: ms a call: " + ", ".join(f"{k} {v / 1e6 / calls:.3f}" for k, v in sorted(parts.items(), key=lambda kv: -kv[1]))
          + f"; {made} selections of {cfg['index_topk']} a call", flush=True)
    return sum(parts.values()) / 1e6 / calls
