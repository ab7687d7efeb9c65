"""The window layers' flash forward kernels' share of their roofline over the
traced calls, in percent: the least time the chip could take for the visible
band alone (``lib/dots3_cost.py::window_attend_cost`` a window layer: scores and
values over the ``min(t + 1, sliding_window_size)`` keys a query sees, 256 + 128
channels a head; queries, keys, values and output moved once) over the device
time of the kernels named ``flash_mla_window_fwd_...``. ``None`` where the
configuration has no window latent attention or the trace holds no such kernel."""

from benchmarks.lib import dots3_cost, dsv3_cost

NAME = "dots3_window_flash_roofline.decode"
NAME_HOLDS = "flash_mla_window_fwd_"


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or not cfg.get("swa_kv_lora_rank"):
        return None
    kernel_ns = dsv3_cost.kernel_ns(dsv3_cost.first_plane(run), NAME_HOLDS)
    if not kernel_ns:
        return None
    p, peaks, calls = run["cell"]["params"], run["peaks"], counters["calls"]
    cost = dots3_cost.window_attend_cost(cfg, p["batch_size"], p["prompt_len"])
    layers = dots3_cost.window_layers(cfg)
    least = layers * dsv3_cost.roofline_seconds(cost["flops"], cost["bytes"], peaks)
    kernel_s = kernel_ns / 1e9
    print(f"{NAME}: {1e3 * kernel_s / calls:.2f} ms of window flash kernels a call against {1e3 * least:.2f} ms at the roofline "
          f"({layers * cost['flops'] / 1e12:.1f} TFLOP and {layers * cost['bytes'] / 1e9:.1f} GB a call)", flush=True)
    return 100.0 * least * calls / kernel_s
