"""The prompt pass's share of the bf16 peak over the traced calls, in percent:
its useful **product** operations (``lib/jamba_cost.py::prefill_flops``: every
token through the four products of a Mamba mixer or the four of an attention,
and the SwiGLU's three; the two attention layers over the visible pairs; the
head once a row) over the device-busy time outside the decode ``while`` (the
prompt pass with its cache fill and first sample: a little more than the
``prefill`` scope alone, so the share errs low). The selective scans'
elementwise work is no product and is **not counted**: the time they take
lowers this share, and ``jamba_ssm_scan_roofline.decode`` reads them. ``None``
where the configuration has no state-space layer or the window holds no
``while``."""

from benchmarks.lib import dsv3_cost, jamba_cost


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or "mamba" not in (cfg.get("layer_types") or ()):
        return None
    while_ns = dsv3_cost.decode_while_ns(dsv3_cost.first_plane(run))
    if not while_ns:
        return None
    outside_s = run["busy_s"] - while_ns / 1e9
    if outside_s <= 0:
        return None
    p = run["cell"]["params"]
    flops = counters["calls"] * jamba_cost.prefill_flops(cfg, p["batch_size"], p["prompt_len"])
    print(f"jamba_prefill_mfu.decode: {1e3 * outside_s / counters['calls']:.2f} ms busy outside the decode steps a call "
          f"for {flops / counters['calls'] / 1e12:.1f} TFLOP of the prompt pass's products", flush=True)
    return 100.0 * flops / outside_s / run["peaks"]["bf16_flops_per_s"]
