"""The window layers' flash forward kernels' share of their roofline over the
traced calls, in percent: the least time the chip could take for the visible
band alone (``lib/kexaone_cost.py::window_flash_cost``: scores and values over
the pairs a window of ``sliding_window`` 128 shows, queries and output moved
once, each key-value head's keys and values once) over the device time of the
kernels named ``flash_fwd_q<n>_kv<n>_w<window>``. ``None`` where the
configuration has no module or the trace holds no such kernel."""

import re

from benchmarks.lib import dsv3_cost, kexaone_cost


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or not cfg.get("num_nextn_predict_layers"):
        return None
    p, peaks = run["cell"]["params"], run["peaks"]
    # the device names an operation after its kernel with the instruction's number behind it (``..._w128.7``)
    windowed = re.compile(rf"flash_fwd_q\d+_kv\d+_w{cfg['sliding_window']}(?!\d)")
    kernel_ns = sum(dur for name, _, dur in dsv3_cost.first_plane(run) if windowed.search(name))
    if not kernel_ns:
        return None
    cost = kexaone_cost.window_flash_cost(cfg, p["batch_size"], p["prompt_len"])
    least = kexaone_cost.cache_layers(cfg)[0] * dsv3_cost.roofline_seconds(cost["flops"], cost["bytes"], peaks)
    kernel_s = kernel_ns / 1e9
    print(f"kexaone_flash_window_roofline.decode: {1e3 * kernel_s / counters['calls']:.2f} ms of window flash kernels a call "
          f"against {1e3 * least:.2f} ms at the roofline", flush=True)
    return 100.0 * least * counters["calls"] / kernel_s
