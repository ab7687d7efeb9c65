"""The grouped expert kernels' share of their roofline over the traced calls,
in percent: the least time the chip could take for the held experts' three
products on the pairs a prompt's tokens send them under even routing over the
768 outputs (``lib/longcat_cost.py::expert_kernel_cost``: 0.25 pairs a token,
the 16 held experts' weights once a layer, the rows in and out of each
product; four layers a call) over the device time of the kernels named
``moe_experts_prefill_...`` (the prompt pass's: a decode step's 64 tokens run
the dense path, XLA's ``fusion``s inside the decode ``while``). The prompt pass
runs a layer in 16 chunks and reads the weights in each, which the count does
not: the share errs low. ``None`` where the configuration has no experts
without weights or the trace holds no such kernel."""

from benchmarks.lib import dsv3_cost, longcat_cost

NAME_HOLDS = "moe_experts_prefill_"


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or not cfg.get("zero_expert_num"):
        return None
    kernel_ns = dsv3_cost.kernel_ns(dsv3_cost.first_plane(run), NAME_HOLDS)
    if not kernel_ns:
        return None
    p, peaks = run["cell"]["params"], run["peaks"]
    cost = longcat_cost.expert_kernel_cost(cfg, p["batch_size"] * p["prompt_len"])
    least = cfg["num_hidden_layers"] * dsv3_cost.roofline_seconds(cost["flops"], cost["bytes"], peaks)
    kernel_s = kernel_ns / 1e9
    print(f"longcat_moe_experts_roofline.decode: {1e3 * kernel_s / counters['calls']:.2f} ms of expert kernels a call "
          f"against {1e3 * least:.2f} ms at the roofline", flush=True)
    return 100.0 * least * counters["calls"] / kernel_s
