"""The window layers' flash forward kernels' share of their roofline over the
traced calls, in percent: the least time the chip could take for the visible
band alone (``lib/mellum_cost.py::window_flash_cost``: scores and values over
the pairs a window of ``sliding_window`` shows, queries and output moved once,
each key-value head's keys and values once) over the device time of the
kernels named ``flash_fwd_q<n>_kv<n>_w<window>``. ``None`` where the trace
holds no such kernel."""

import re

from benchmarks.lib import dsv3_cost, mellum_cost


def read(run):
    counters = run["counters"]
    if run["trace"] is None or not counters.get("calls"):
        return None
    cfg, p, peaks = run["family"].cfg, run["cell"]["params"], run["peaks"]
    # the device names an operation after its kernel with the instruction's number behind it (``..._w1024.7``)
    windowed = re.compile(rf"flash_fwd_q\d+_kv\d+_w{cfg.get('sliding_window')}(?!\d)")
    kernel_ns = sum(dur for name, _, dur in dsv3_cost.first_plane(run) if windowed.search(name))
    if not kernel_ns:
        return None
    cost = mellum_cost.window_flash_cost(cfg, p["batch_size"], p["prompt_len"])
    least = mellum_cost.window_layers(cfg) * dsv3_cost.roofline_seconds(cost["flops"], cost["bytes"], peaks)
    kernel_s = kernel_ns / 1e9
    print(f"mellum_flash_window_roofline.decode: {1e3 * kernel_s / counters['calls']:.2f} ms of window flash kernels a call "
          f"against {1e3 * least:.2f} ms at the roofline", flush=True)
    return 100.0 * least * counters["calls"] / kernel_s
