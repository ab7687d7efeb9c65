"""The prompt pass's share of the bf16 peak over the traced calls, in
percent: its useful operations (``lib/dsv3_cost.py``: every token through the
products of the stack, the held experts on the pairs routed to them under
even routing and not on every token, attention over the visible pairs, the
head at the last position) over the device-busy time outside the decode
``while``."""

from benchmarks.lib import dsv3_cost


def read(run):
    counters = run["counters"]
    if run["trace"] is None or not counters.get("calls"):
        return None
    while_ns = dsv3_cost.decode_while_ns(dsv3_cost.first_plane(run))
    if not while_ns:
        return None
    outside_s = run["busy_s"] - while_ns / 1e9
    if outside_s <= 0:
        return None
    p = run["cell"]["params"]
    flops = counters["calls"] * dsv3_cost.prefill_flops(run["family"].cfg, p["batch_size"], p["prompt_len"])
    print(f"prefill_mfu.decode: {1e3 * outside_s / counters['calls']:.2f} ms busy outside the decode scan a call for "
          f"{flops / counters['calls'] / 1e12:.1f} TFLOP of prompt pass", flush=True)
    return 100.0 * flops / outside_s / run["peaks"]["bf16_flops_per_s"]
