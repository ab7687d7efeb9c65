"""The speculative decode loop's share of the HBM peak over the traced calls,
in percent: the least time ``new_tokens - 1`` speculative steps could take,
reading each step's bytes once (``lib/kexaone_cost.py::spec_scan_bytes``: two
positions a row through the stack and the multi-token-prediction module, every
weight the arithmetic needs, of the held experts those the positions hit under
even routing, a growing cache at the length the step finds, a ring at
``min(length, sliding_window)``) at the HBM peak, over the device time of the
trace's decode ``while``. ``new_tokens - 1`` is the steps a call takes when no
draft is accepted, which is what seeded weights give; the count names no path
of the program. ``None`` where the configuration has no module or the window
holds no ``while``."""

from benchmarks.lib import dsv3_cost, kexaone_cost


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or not cfg.get("num_nextn_predict_layers"):
        return None
    while_ns = dsv3_cost.decode_while_ns(dsv3_cost.first_plane(run))
    if not while_ns:
        return None
    p = run["cell"]["params"]
    itemsize = 2 if p["cache_dtype"] == "bfloat16" else 4
    scan_bytes = kexaone_cost.spec_scan_bytes(cfg, p["batch_size"], p["prompt_len"], p["new_tokens"], cache_itemsize=itemsize)
    least_s = counters["calls"] * scan_bytes / run["peaks"]["hbm_bytes_per_s"]
    print(f"kexaone_spec_step_hbm_share.decode: {while_ns / 1e6 / counters['calls']:.2f} ms of speculative steps a call against "
          f"{1e3 * least_s / counters['calls']:.2f} ms to read {scan_bytes / 1e9:.1f} GB at the HBM peak", flush=True)
    return 100.0 * least_s / (while_ns / 1e9)
