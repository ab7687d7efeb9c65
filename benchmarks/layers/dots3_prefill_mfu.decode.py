"""The prompt pass's share of the bf16 peak over the traced calls, in percent:
its useful matrix-unit operations **by the mechanism's definition**
(``lib/dots3_cost.py::prefill_flops``: every token through the stack's dense
products, the held experts for the pairs routed to them; the indexer's scores
over the causal pairs, ``2 * 64 * 128`` a pair; a full layer's attention over
the ``min(t + 1, index_topk)`` keys a query selects, not over the dense
rectangle a program may run under a mask; a window layer's attention over its
window; the head once a row) over the device time of the phase ``prefill`` (the
run's table of device time by program scope, ``lib/scopes.py``). ``None`` where
the configuration has no indexer or the run has no such table."""

from benchmarks.lib import dots3_cost, scopes

NAME = "dots3_prefill_mfu.decode"


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or not cfg.get("index_topk"):
        return None
    found = scopes.times(run, NAME)
    if found is None:
        return None
    prefill_s = found.sum(lambda name, row: row["phase"] == "prefill") / 1e9
    if prefill_s <= 0:
        return None
    p, calls = run["cell"]["params"], counters["calls"]
    flops = calls * dots3_cost.prefill_flops(cfg, p["batch_size"], p["prompt_len"])
    print(f"{NAME}: {1e3 * prefill_s / calls:.2f} ms of the phase prefill a call for {flops / calls / 1e12:.1f} TFLOP of the prompt "
          f"pass's products by their definition", flush=True)
    return 100.0 * flops / prefill_s / run["peaks"]["bf16_flops_per_s"]
