"""The decode loop's share of the HBM peak over the traced calls, in percent:
the least time ``new_tokens - 1`` one-token steps of the shortcut-connected
stack could take, reading each step's bytes once
(``lib/longcat_cost.py::decode_scan_bytes``: every weight once, of the 16 held
experts a layer those that 64 tokens hit under even routing over the 768
outputs, 63.5%; each of the eight caches at the length the step finds) at the
HBM peak, over the device time of the trace's decode ``while``. The count
names no path of the program: one that reads every held expert whatever the
step hits is held to the same bytes. ``None`` where the configuration has no
experts without weights (another family's cell) or the window holds no
``while``."""

from benchmarks.lib import dsv3_cost, longcat_cost


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or not cfg.get("zero_expert_num"):
        return None
    while_ns = dsv3_cost.decode_while_ns(dsv3_cost.first_plane(run))
    if not while_ns:
        return None
    p = run["cell"]["params"]
    itemsize = 2 if p["cache_dtype"] == "bfloat16" else 4
    scan_bytes = longcat_cost.decode_scan_bytes(cfg, p["batch_size"], p["prompt_len"], p["new_tokens"], cache_itemsize=itemsize)
    least_s = counters["calls"] * scan_bytes / run["peaks"]["hbm_bytes_per_s"]
    print(f"longcat_step_hbm_share.decode: {while_ns / 1e6 / counters['calls']:.2f} ms of decode steps a call against "
          f"{1e3 * least_s / counters['calls']:.2f} ms to read {scan_bytes / 1e9:.1f} GB at the HBM peak", flush=True)
    return 100.0 * least_s / (while_ns / 1e9)
