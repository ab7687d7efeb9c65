"""The decode scan's share of the HBM peak over the traced calls, in percent:
the least time the steps could take, reading each step's bytes once
(``lib/mellum_cost.py::decode_scan_bytes``: every weight a step's arithmetic
needs, of the experts those a batch hits under even routing, a full layer's
cache at the length that step finds, a window layer's at ``min(length,
sliding_window)``) at the HBM peak, over the device time of the trace's decode
``while``. The count names no path of the program: one that reads every expert
reads more than is counted. ``None`` where the configuration has no
``layer_types`` or the window holds no ``while``."""

from benchmarks.lib import dsv3_cost, mellum_cost


def read(run):
    counters = run["counters"]
    if run["trace"] is None or not counters.get("calls") or "layer_types" not in run["family"].cfg:
        return None
    while_ns = dsv3_cost.decode_while_ns(dsv3_cost.first_plane(run))
    if not while_ns:
        return None
    p = run["cell"]["params"]
    itemsize = 2 if p["cache_dtype"] == "bfloat16" else 4
    scan_bytes = mellum_cost.decode_scan_bytes(run["family"].cfg, p["batch_size"], p["prompt_len"], p["new_tokens"],
                                               cache_itemsize=itemsize)
    least_s = counters["calls"] * scan_bytes / run["peaks"]["hbm_bytes_per_s"]
    print(f"mellum_decode_scan_hbm_share.decode: {while_ns / 1e6 / counters['calls']:.2f} ms of decode scan a call against "
          f"{1e3 * least_s / counters['calls']:.2f} ms to read {scan_bytes / 1e9:.1f} GB at the HBM peak", flush=True)
    return 100.0 * least_s / (while_ns / 1e9)
