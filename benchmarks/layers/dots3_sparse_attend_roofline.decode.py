"""The full layers' attention kernels' share of their roofline over the traced
calls, in percent: the least time the chip could take for the attention over
the selected keys **by its definition**
(``lib/dots3_cost.py::sparse_attend_cost`` a full layer: scores and values of
``min(t + 1, index_topk)`` keys a query a head; expanded queries, keys and
values read and the output written once) over the device time of the kernels
named ``flash_mla_masked_fwd_...``. The kernel that runs today multiplies the
dense causal rectangle under the selection's mask, eight times the definition's
operations at 32 768 tokens: its share says so, and a kernel that visits only
the selected keys is held to the same count. Prints the dense rectangle's
operations beside. ``None`` where the configuration has no indexer or the trace
holds no such kernel."""

from benchmarks.lib import dots3_cost, dsv3_cost

NAME = "dots3_sparse_attend_roofline.decode"
NAME_HOLDS = "flash_mla_masked_fwd_"


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or not cfg.get("index_topk"):
        return None
    kernel_ns = dsv3_cost.kernel_ns(dsv3_cost.first_plane(run), NAME_HOLDS)
    if not kernel_ns:
        return None
    p, peaks, calls = run["cell"]["params"], run["peaks"], counters["calls"]
    cost = dots3_cost.sparse_attend_cost(cfg, p["batch_size"], p["prompt_len"])
    layers = dots3_cost.full_layers(cfg)
    least = layers * dsv3_cost.roofline_seconds(cost["flops"], cost["bytes"], peaks)
    kernel_s = kernel_ns / 1e9
    dense = cost["flops"] * dots3_cost.causal_pairs(p["prompt_len"]) / dots3_cost.kept_pairs(p["prompt_len"], cfg["index_topk"])
    print(f"{NAME}: {1e3 * kernel_s / calls:.2f} ms of masked flash kernels a call against {1e3 * least:.2f} ms at the roofline of "
          f"the selected keys ({layers * cost['flops'] / 1e12:.1f} TFLOP a call; the dense causal rectangle is "
          f"{layers * dense / 1e12:.1f} TFLOP, {layers * dense * calls / kernel_s / 1e12:.1f} TFLOP/s of it achieved)", flush=True)
    return 100.0 * least * calls / kernel_s
