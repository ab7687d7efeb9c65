"""The indexer's score kernels' share of their roofline over the traced calls,
in percent: the least time the chip could take for the index scores of a
prompt pass **by their definition** (``lib/dots3_cost.py::index_score_cost`` a
full layer: ``2 * index_n_heads * index_head_dim`` operations a causal pair,
whatever blocks a program cuts the pairs into and whether it multiplies the
blocks after a query; the indexer's queries, keys and head weights read once)
over the device time of the kernels named ``dsa_index_scores_...``. ``None``
where the configuration has no indexer or the trace holds no such kernel."""

from benchmarks.lib import dots3_cost, dsv3_cost

NAME = "dots3_index_score_roofline.decode"
NAME_HOLDS = "dsa_index_scores_"


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or not cfg.get("index_topk"):
        return None
    kernel_ns = dsv3_cost.kernel_ns(dsv3_cost.first_plane(run), NAME_HOLDS)
    if not kernel_ns:
        return None
    p, peaks, calls = run["cell"]["params"], run["peaks"], counters["calls"]
    cost = dots3_cost.index_score_cost(cfg, p["batch_size"], p["prompt_len"])
    layers = dots3_cost.full_layers(cfg)
    least = layers * dsv3_cost.roofline_seconds(cost["flops"], cost["bytes"], peaks)
    kernel_s = kernel_ns / 1e9
    print(f"{NAME}: {1e3 * kernel_s / calls:.2f} ms of index score kernels a call against {1e3 * least:.2f} ms at the roofline "
          f"({layers * cost['flops'] / 1e12:.1f} TFLOP a call over the causal pairs; {layers * cost['flops'] * calls / kernel_s / 1e12:.1f} "
          f"TFLOP/s achieved)", flush=True)
    return 100.0 * least * calls / kernel_s
