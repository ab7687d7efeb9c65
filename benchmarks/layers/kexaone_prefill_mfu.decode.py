"""The prompt pass's share of the bf16 peak over the traced calls, in
percent: its useful operations, the multi-token-prediction module's pass over
the prompt included (``lib/kexaone_cost.py::prefill_flops``: every token
through the products of the stack and of the module with the held experts on
the pairs routed to them under even routing, attention over the visible pairs,
a window layer's band only, the head at the last position twice) over the
device-busy time outside the decode ``while``. ``None`` where the configuration
has no module or the window holds no ``while``."""

from benchmarks.lib import dsv3_cost, kexaone_cost


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or not cfg.get("num_nextn_predict_layers"):
        return None
    while_ns = dsv3_cost.decode_while_ns(dsv3_cost.first_plane(run))
    if not while_ns:
        return None
    outside_s = run["busy_s"] - while_ns / 1e9
    if outside_s <= 0:
        return None
    p = run["cell"]["params"]
    flops = counters["calls"] * kexaone_cost.prefill_flops(cfg, p["batch_size"], p["prompt_len"])
    print(f"kexaone_prefill_mfu.decode: {1e3 * outside_s / counters['calls']:.2f} ms busy outside the speculative steps a call "
          f"for {flops / counters['calls'] / 1e12:.1f} TFLOP of prompt pass", flush=True)
    return 100.0 * flops / outside_s / run["peaks"]["bf16_flops_per_s"]
