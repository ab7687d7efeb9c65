"""The decode loop's share of the HBM peak over the traced calls, in percent:
the least time ``new_tokens - 1`` one-token steps of the stack could take,
moving each step's bytes once (``lib/dots3_cost.py::decode_scan_bytes``: of a
sparse layer's 32 held experts those that one of the step's tokens is routed to
under even routing, every other weight and the head once, a full layer's index
keys of the whole context and the latent rows of the keys it selects, a window
layer's ring) at the HBM peak, over the device time of the phase ``decode``
(the run's table of device time by program scope, ``lib/scopes.py``): the share
of the whole step. Prints a step's bytes by part, beside what a program that
reads every held expert moves. ``None`` where the configuration has no indexer
or the run has no such table."""

from benchmarks.lib import dots3_cost, scopes

NAME = "dots3_step_hbm_share.decode"


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or not cfg.get("index_topk"):
        return None
    found = scopes.times(run, NAME)
    if found is None:
        return None
    decode_ns = found.sum(lambda name, row: row["phase"] == "decode")
    if not decode_ns:
        return None
    p, calls = run["cell"]["params"], counters["calls"]
    steps = p["new_tokens"] - 1
    scan_bytes = dots3_cost.decode_scan_bytes(cfg, p["batch_size"], p["prompt_len"], p["new_tokens"])
    least_s = calls * scan_bytes / run["peaks"]["hbm_bytes_per_s"]
    middle = p["prompt_len"] + p["new_tokens"] // 2
    parts = dots3_cost.decode_step_parts(cfg, p["batch_size"], middle, dots3_cost.experts_hit(cfg, p["batch_size"]))
    held = dots3_cost.decode_step_parts(cfg, p["batch_size"], middle, cfg["n_held_experts"])
    print(f"{NAME}: {decode_ns / 1e6 / calls / steps:.3f} ms a decode step against {1e3 * least_s / calls / steps:.3f} ms to move "
          f"{scan_bytes / steps / 1e9:.2f} GB a step at the HBM peak ("
          + ", ".join(f"{k} {v / 1e9:.3f} GB" for k, v in parts.items())
          + f"; {sum(held.values()) / 1e9:.2f} GB with every held expert read)", flush=True)
    return 100.0 * least_s / (decode_ns / 1e9)
