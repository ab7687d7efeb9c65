"""The prompt pass's share of the bf16 peak over the traced calls, in
percent: its useful operations (``lib/mellum_cost.py::prefill_flops``: every
token through the products of the stack with the experts on its 8 routed
pairs, attention over the visible pairs, a window layer's band only, the head
at the last position) over the device-busy time outside the decode ``while``.
``None`` where the configuration has no ``layer_types`` or the window holds
no ``while``."""

from benchmarks.lib import dsv3_cost, mellum_cost


def read(run):
    counters = run["counters"]
    if run["trace"] is None or not counters.get("calls") or "layer_types" not in run["family"].cfg:
        return None
    while_ns = dsv3_cost.decode_while_ns(dsv3_cost.first_plane(run))
    if not while_ns:
        return None
    outside_s = run["busy_s"] - while_ns / 1e9
    if outside_s <= 0:
        return None
    p = run["cell"]["params"]
    flops = counters["calls"] * mellum_cost.prefill_flops(run["family"].cfg, p["batch_size"], p["prompt_len"])
    print(f"mellum_prefill_mfu.decode: {1e3 * outside_s / counters['calls']:.2f} ms busy outside the decode scan a call "
          f"for {flops / counters['calls'] / 1e12:.1f} TFLOP of prompt pass", flush=True)
    return 100.0 * flops / outside_s / run["peaks"]["bf16_flops_per_s"]
