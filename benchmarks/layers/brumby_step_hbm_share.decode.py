"""The decode loop's share of the HBM peak over the traced calls, in percent:
the least time ``new_tokens - 1`` one-token steps of the retention stack could
take, moving each step's bytes once (``lib/brumby_cost.py::decode_scan_bytes``:
every layer's weights and the head once; every layer's float32 state, 8256
features by 129 a key-value head a row, read **and written** once) at the HBM
peak, over the device time of the trace's decode ``while`` (of the window's
``while`` instructions the one with the most time: a prompt pass's chunk loops
are a layer's each). The count names no path of the program: the state's bytes
are the configuration's dtypes and the feature map's distinct features, so a
program that pads the state reads a little under what it moves. ``None`` where
the configuration has no retention layer (another family's cell) or the window
holds no ``while``."""

from benchmarks.lib import brumby_cost, dsv3_cost


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or "power_retention" not in (cfg.get("layer_types") or ()):
        return None
    while_ns = dsv3_cost.decode_while_ns(dsv3_cost.first_plane(run))
    if not while_ns:
        return None
    p = run["cell"]["params"]
    scan_bytes = brumby_cost.decode_scan_bytes(cfg, p["batch_size"], p["new_tokens"])
    least_s = counters["calls"] * scan_bytes / run["peaks"]["hbm_bytes_per_s"]
    steps = p["new_tokens"] - 1
    print(f"brumby_step_hbm_share.decode: {while_ns / 1e6 / counters['calls'] / steps:.3f} ms a decode step against "
          f"{1e3 * least_s / counters['calls'] / steps:.3f} ms to move {scan_bytes / steps / 1e9:.2f} GB a step at the HBM peak "
          f"(state {2 * brumby_cost.state_bytes(cfg, p['batch_size']) / 1e9:.2f} GB of them)", flush=True)
    return 100.0 * least_s / (while_ns / 1e9)
