"""The grouped expert kernels' share of their roofline over the traced calls,
in percent: the least time the chip could take for the experts' three
products on the 8 pairs a prompt token is routed to
(``lib/mellum_cost.py::expert_kernel_cost``: every expert's weights once a
layer, the rows in and out of each product) over the device time of the
kernels named ``moe_experts_prefill_...`` (the prompt pass's: a decode step
of 32 tokens runs the dense path, XLA's ``fusion``s inside the decode
``while``). ``None`` where the trace holds no such kernel."""

from benchmarks.lib import dsv3_cost, mellum_cost

NAME_HOLDS = "moe_experts_prefill_"


def read(run):
    counters = run["counters"]
    if run["trace"] is None or not counters.get("calls"):
        return None
    kernel_ns = dsv3_cost.kernel_ns(dsv3_cost.first_plane(run), NAME_HOLDS)
    if not kernel_ns:
        return None
    cfg, p, peaks = run["family"].cfg, run["cell"]["params"], run["peaks"]
    cost = mellum_cost.expert_kernel_cost(cfg, p["batch_size"] * p["prompt_len"])
    least = cfg["num_hidden_layers"] * dsv3_cost.roofline_seconds(cost["flops"], cost["bytes"], peaks)
    kernel_s = kernel_ns / 1e9
    print(f"mellum_moe_experts_roofline.decode: {1e3 * kernel_s / counters['calls']:.2f} ms of expert kernels a call "
          f"against {1e3 * least:.2f} ms at the roofline", flush=True)
    return 100.0 * least * counters["calls"] / kernel_s
