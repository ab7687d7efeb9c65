"""The prompt pass's share of the bf16 peak over the traced calls, in percent:
its useful operations (``lib/longcat_cost.py::prefill_flops``: every token
through both attentions' projections, both dense feed-forwards and the router,
the held experts on the 0.25 pairs a token routed to them under even routing,
both attentions over the visible pairs, the head once a row) over the
device-busy time outside the decode ``while`` (the prompt pass with its cache
fill and first sample: a little more than the ``prefill`` scope alone, so the
share errs low). ``None`` where the configuration has no experts without
weights or the window holds no ``while``."""

from benchmarks.lib import dsv3_cost, longcat_cost


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or not cfg.get("zero_expert_num"):
        return None
    while_ns = dsv3_cost.decode_while_ns(dsv3_cost.first_plane(run))
    if not while_ns:
        return None
    outside_s = run["busy_s"] - while_ns / 1e9
    if outside_s <= 0:
        return None
    p = run["cell"]["params"]
    flops = counters["calls"] * longcat_cost.prefill_flops(cfg, p["batch_size"], p["prompt_len"])
    print(f"longcat_prefill_mfu.decode: {1e3 * outside_s / counters['calls']:.2f} ms busy outside the decode steps a call "
          f"for {flops / counters['calls'] / 1e12:.1f} TFLOP of prompt pass", flush=True)
    return 100.0 * flops / outside_s / run["peaks"]["bf16_flops_per_s"]
