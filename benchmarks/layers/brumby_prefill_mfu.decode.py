"""The prompt pass's share of the bf16 peak over the traced calls, in percent:
its useful matrix-unit operations (``lib/brumby_cost.py::prefill_flops``: every
token through a layer's five dense products and the SwiGLU's three; every
layer's retention in the state form, a token's query and key sides whatever
chunk the program cuts a row into; the head once a row) over the device-busy time outside the decode ``while`` (the prompt
pass with its state hand-off and first sample: a little more than the
``prefill`` scope alone, so the share errs low). ``None`` where the
configuration has no retention layer or the window holds no ``while``."""

from benchmarks.lib import brumby_cost, dsv3_cost


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or "power_retention" not in (cfg.get("layer_types") or ()):
        return None
    events = dsv3_cost.first_plane(run)
    while_ns = dsv3_cost.decode_while_ns(events)
    if not while_ns:
        return None
    outside_s = run["busy_s"] - while_ns / 1e9
    if outside_s <= 0:
        return None
    p = run["cell"]["params"]
    flops = counters["calls"] * brumby_cost.prefill_flops(cfg, p["batch_size"], p["prompt_len"])
    print(f"brumby_prefill_mfu.decode: {1e3 * outside_s / counters['calls']:.2f} ms busy outside the decode steps a call "
          f"for {flops / counters['calls'] / 1e12:.1f} TFLOP of the prompt pass's products", flush=True)
    return 100.0 * flops / outside_s / run["peaks"]["bf16_flops_per_s"]
