"""The grouped expert kernels' share of their roofline over the traced calls,
in percent: the least time the chip could take for the experts' three products
on the pair a prompt token sends to the 32 held experts of 5120 x 1536
(``lib/dots3_cost.py::expert_kernel_cost``: every held expert's weights once a
sparse layer, the rows in and out of each product), as
``mellum_moe_experts_roofline.decode`` counts them, over the device time of the
kernels named ``moe_experts_prefill_...`` in the phase ``prefill`` where the
run has its table of device time by scope, else of all of them. ``None`` where
the configuration has no indexer or the trace holds no such kernel."""

from benchmarks.lib import dots3_cost, dsv3_cost, scopes

NAME = "dots3_moe_experts_roofline.decode"
NAME_HOLDS = "moe_experts_prefill_"


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or not cfg.get("index_topk"):
        return None
    found = scopes.times(run, NAME)
    if found is not None:
        kernel_ns = found.sum(lambda name, row: row["phase"] == "prefill" and NAME_HOLDS in name)
    else:
        kernel_ns = dsv3_cost.kernel_ns(dsv3_cost.first_plane(run), NAME_HOLDS)
    if not kernel_ns:
        return None
    p, peaks, calls = run["cell"]["params"], run["peaks"], counters["calls"]
    cost = dots3_cost.expert_kernel_cost(cfg, p["batch_size"] * p["prompt_len"])
    least = dots3_cost.sparse_layers(cfg) * dsv3_cost.roofline_seconds(cost["flops"], cost["bytes"], peaks)
    kernel_s = kernel_ns / 1e9
    print(f"{NAME}: {1e3 * kernel_s / calls:.2f} ms of the prompt pass's expert kernels a call against {1e3 * least:.2f} ms at the "
          f"roofline ({dots3_cost.sparse_layers(cfg) * cost['flops'] / 1e12:.1f} TFLOP a call)", flush=True)
    return 100.0 * least * calls / kernel_s
