"""The decode loop's share of the HBM peak over the traced calls, in percent:
the least time ``new_tokens - 1`` one-token steps of the hybrid stack could
take, moving each step's bytes once (``lib/jamba_cost.py::decode_scan_bytes``:
every weight once, the tied table once; every Mamba layer's convolution window
and float32 state read **and written** once; the two attention layers' caches
read at the length the step finds) at the HBM peak, over the device time of
the trace's decode ``while`` (of the window's ``while`` instructions the one
with the most time: a prompt pass's chunk loops are a layer's each). The count
names no path of the program: one that keeps a state on the chip between steps
moves less than this and reads over 100% of nothing, which is why the state's
bytes are the configuration's dtypes and not a program's. ``None`` where the
configuration has no state-space layer (another family's cell) or the window
holds no ``while``."""

from benchmarks.lib import dsv3_cost, jamba_cost


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or "mamba" not in (cfg.get("layer_types") or ()):
        return None
    while_ns = dsv3_cost.decode_while_ns(dsv3_cost.first_plane(run))
    if not while_ns:
        return None
    p = run["cell"]["params"]
    itemsize = 2 if p["cache_dtype"] == "bfloat16" else 4
    scan_bytes = jamba_cost.decode_scan_bytes(cfg, p["batch_size"], p["prompt_len"], p["new_tokens"], cache_itemsize=itemsize)
    least_s = counters["calls"] * scan_bytes / run["peaks"]["hbm_bytes_per_s"]
    steps = p["new_tokens"] - 1
    print(f"jamba_step_hbm_share.decode: {while_ns / 1e6 / counters['calls'] / steps:.3f} ms a decode step against "
          f"{1e3 * least_s / counters['calls'] / steps:.3f} ms to move {scan_bytes / steps / 1e9:.2f} GB a step at the HBM peak "
          f"(state and windows {2 * (jamba_cost.ssm_state_bytes(cfg, p['batch_size']) + jamba_cost.conv_window_bytes(cfg, p['batch_size'], itemsize)) / 1e9:.2f} GB of them)",
          flush=True)
    return 100.0 * least_s / (while_ns / 1e9)
