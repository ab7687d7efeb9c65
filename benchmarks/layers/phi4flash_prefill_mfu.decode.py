"""The prompt pass's share of the bf16 peak over the traced calls, in percent:
the useful **product** operations of the **cut** pass
(``lib/phi4flash_cost.py::prefill_flops``: every token through the products of
the 17 layers below the one that owns the shared cache and their window
attentions over the visible pairs, that layer's key and value projections over
the prompt, and at the last position of each row alone its query side, the 14
layers above it and the head) over the device-busy time outside the decode
``while`` (the prompt pass with its cache fill and first sample). A program that
ran all 32 layers over every position would do 1.9 times the work for the same
count and read about half. The selective scans' elementwise work is no product
and is **not counted**. ``None`` where the configuration has no cross-attention
layer or the window holds no ``while``."""

from benchmarks.lib import dsv3_cost, phi4flash_cost


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or "cross_attention" not in (cfg.get("layer_types") or ()):
        return None
    while_ns = dsv3_cost.decode_while_ns(dsv3_cost.first_plane(run))
    if not while_ns:
        return None
    outside_s = run["busy_s"] - while_ns / 1e9
    if outside_s <= 0:
        return None
    p = run["cell"]["params"]
    flops = counters["calls"] * phi4flash_cost.prefill_flops(cfg, p["batch_size"], p["prompt_len"])
    whole = phi4flash_cost.prefill_flops(cfg, p["batch_size"], p["prompt_len"], cut=False)
    print(f"phi4flash_prefill_mfu.decode: {1e3 * outside_s / counters['calls']:.2f} ms busy outside the decode steps a call "
          f"for {flops / counters['calls'] / 1e12:.1f} TFLOP of the cut prompt pass's products ({phi4flash_cost.layers_skipped(cfg)} "
          f"layers at one position a row; all layers over all positions would be {whole / 1e12:.1f} TFLOP)", flush=True)
    return 100.0 * flops / outside_s / run["peaks"]["bf16_flops_per_s"]
