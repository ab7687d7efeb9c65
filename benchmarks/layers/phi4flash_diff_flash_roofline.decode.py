"""The window layers' differential flash forward kernels' share of their
roofline over the traced calls, in percent: the least time the chip could take
for the visible band alone (``lib/phi4flash_cost.py::diff_flash_cost``: both
maps' scores and their products with the pair's value over the pairs a window
of ``sliding_window`` shows, query pairs and output moved once, each key-value
pair's keys and values once) over the device time of the kernels named
``flash_diff_fwd_q<n>_kv<n>_w<window>``. ``None`` where the trace holds no such kernel."""

import re

from benchmarks.lib import dsv3_cost, phi4flash_cost


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or "cross_attention" not in (cfg.get("layer_types") or ()):
        return None
    p, peaks = run["cell"]["params"], run["peaks"]
    # the device names an operation after its kernel with the instruction's number behind it (``..._w512.7``)
    windowed = re.compile(rf"flash_diff_fwd_q\d+_kv\d+_w{cfg.get('sliding_window')}(?!\d)")
    kernel_ns = sum(dur for name, _, dur in dsv3_cost.first_plane(run) if windowed.search(name))
    if not kernel_ns:
        return None
    cost = phi4flash_cost.diff_flash_cost(cfg, p["batch_size"], p["prompt_len"])
    layers = phi4flash_cost.n_layers(cfg, "sliding_attention")
    least = layers * dsv3_cost.roofline_seconds(cost["flops"], cost["bytes"], peaks)
    kernel_s = kernel_ns / 1e9
    bound = "bytes" if cost["bytes"] / peaks["hbm_bytes_per_s"] >= cost["flops"] / peaks["bf16_flops_per_s"] else "operations"
    print(f"phi4flash_diff_flash_roofline.decode: {1e3 * kernel_s / counters['calls']:.2f} ms of differential window flash kernels a call "
          f"against {1e3 * least:.2f} ms at the roofline (the {bound} bind)", flush=True)
    return 100.0 * least * counters["calls"] / kernel_s
