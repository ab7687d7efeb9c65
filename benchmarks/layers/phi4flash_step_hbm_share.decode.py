"""The decode loop's share of the HBM peak over the traced calls, in percent:
the least time ``new_tokens - 1`` one-token steps of the decoder-hybrid-decoder
stack could take, moving each step's bytes once
(``lib/phi4flash_cost.py::decode_scan_bytes``: every weight once, the tied table
once; every Mamba layer's window and float32 state read **and written**; every
ring read; **the shared cache read once a reading layer**, eight times a step,
at the length the step finds: the layers run one after the other, so no read
serves two) at the HBM peak, over the device time of the trace's decode
``while`` (of the window's ``while`` instructions the one with the most time).
The count names no path of the program: one that kept the shared cache on the
chip across the layers of a step would move less and read over 100% of
nothing, which is why the bytes are the configuration's and not a program's.
``None`` where the configuration has no cross-attention layer (another family's
cell) or the window holds no ``while``."""

from benchmarks.lib import dsv3_cost, phi4flash_cost


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or "cross_attention" not in (cfg.get("layer_types") or ()):
        return None
    while_ns = dsv3_cost.decode_while_ns(dsv3_cost.first_plane(run))
    if not while_ns:
        return None
    p = run["cell"]["params"]
    itemsize = 2 if p["cache_dtype"] == "bfloat16" else 4
    scan_bytes = phi4flash_cost.decode_scan_bytes(cfg, p["batch_size"], p["prompt_len"], p["new_tokens"], cache_itemsize=itemsize)
    least_s = counters["calls"] * scan_bytes / run["peaks"]["hbm_bytes_per_s"]
    steps = p["new_tokens"] - 1
    readers = phi4flash_cost.shared_cache_readers(cfg)
    shared = readers * phi4flash_cost.shared_cache_bytes(cfg, p["batch_size"], p["prompt_len"] + p["new_tokens"] // 2, itemsize)
    print(f"phi4flash_step_hbm_share.decode: {while_ns / 1e6 / counters['calls'] / steps:.3f} ms a decode step against "
          f"{1e3 * least_s / counters['calls'] / steps:.3f} ms to move {scan_bytes / steps / 1e9:.2f} GB a step at the HBM peak "
          f"({readers} reads of the shared cache {shared / 1e9:.2f} GB of them at the middle step)", flush=True)
    return 100.0 * least_s / (while_ns / 1e9)
