"""The flash attention kernels' share of their roofline over the traced
steps, in percent: the least time the chip could take for the attention
calls of those steps (``lib/flops.py``: the larger of FLOPs over the bf16
peak and bytes over the HBM peak, forward and backward, recomputed scores
not counted) over the device time of the trace's flash custom-calls."""

from benchmarks.lib import flops, trace

# the program's flash wrappers all carry "flash" in their name, and XLA names
# a Mosaic call after the function that made it (jvp_flash_attention_..., ...)
KERNEL_NAME_HOLDS = "flash"


def read(run):
    if run["trace"] is None or "steps" not in run["counters"]:
        return None
    plane = sorted(run["trace"]["devices"])[0]
    events = trace.clip(run["trace"]["devices"][plane], run["trace_window"])
    kernel_ns = sum(dur for name, _, dur in events if KERNEL_NAME_HOLDS in name.lower())
    if kernel_ns <= 0:
        return None
    calls = run["family"].flash_calls(run["counters"]["batch_size"])
    ideal = flops.roofline_seconds(calls, run["peaks"], training=True)
    steps = run["counters"]["steps"]
    print(f"flash_roofline.train: {kernel_ns / 1e6 / steps:.3f} ms of flash kernels a step against "
          f"{ideal['seconds'] * 1e3:.3f} ms at the roofline, bound by {ideal['bound']}", flush=True)
    return 100.0 * ideal["seconds"] * steps / (kernel_ns / 1e9)
