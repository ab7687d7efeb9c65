"""Device ms a decode step spent in the full layers' mechanism: ``dsa/index`` +
``dsa/step_score`` + ``dsa/step_select`` + ``dsa/step_gather`` +
``dsa/step_attend`` in the phase ``decode`` (both full layers: the indexer's
projections of the new token, its scores against every cached index key, the
top-``index_topk``, the gather of the chosen latent rows, the absorbed attention
over them), from the run's table of device time by program scope
(``lib/scopes.py``). Prints the parts, and the bytes a step must read of the
caches beside what attention over every key would read
(``lib/dots3_cost.py::dsa_step_bytes``). ``None`` where there is no such table
or the program opens no ``dsa/step_select`` scope."""

from benchmarks.lib import dots3_cost, scopes

NAME = "dots3_dsa_step_ms.decode"
LAYERS = ("dsa/index", "dsa/step_score", "dsa/step_select", "dsa/step_gather", "dsa/step_attend")


def read(run):
    cfg = run["family"].cfg
    if not cfg.get("index_topk"):
        return None
    found = scopes.times(run, NAME)
    if found is None:
        return None
    parts = found.by(lambda name, row: row["layer"], lambda name, row: row["phase"] == "decode" and row["layer"] in LAYERS)
    if not parts.get("dsa/step_select"):
        return None
    p = run["cell"]["params"]
    steps = scopes.per(run)[0]["decode"]
    context = p["prompt_len"] + p["new_tokens"] // 2
    must = dots3_cost.full_layers(cfg) * dots3_cost.dsa_step_bytes(cfg, p["batch_size"], context)
    every = dots3_cost.full_layers(cfg) * p["batch_size"] * context * dots3_cost.latent_row_bytes(cfg)
    print(f"{NAME}: ms a step: " + ", ".join(f"{k} {v / 1e6 / steps:.3f}" for k, v in sorted(parts.items(), key=lambda kv: -kv[1]))
          + f"; a step must read {must / 1e6:.1f} MB of index keys and selected rows ({1e3 * must / run['peaks']['hbm_bytes_per_s']:.3f} ms "
          f"at the HBM peak) where every key would be {every / 1e6:.1f} MB", flush=True)
    return sum(parts.values()) / 1e6 / steps
