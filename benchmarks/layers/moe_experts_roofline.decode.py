"""The grouped expert kernels' share of their roofline over the traced calls,
in percent: the least time the chip could take for the held experts' three
products on the pairs routed to them (``lib/dsv3_cost.py``, even routing)
over the device time of the kernels named ``moe_experts_prefill_...`` (the
prompt pass's: a decode step of this cell runs no such kernel, its experts
are XLA's ``fusion``s inside the decode ``while`` of ``breakdown.device_ops``).
``None`` where the trace holds no such kernel."""

from benchmarks.lib import dsv3_cost

NAME_HOLDS = "moe_experts_prefill_"


def read(run):
    counters = run["counters"]
    if run["trace"] is None or not counters.get("calls"):
        return None
    kernel_ns = dsv3_cost.kernel_ns(dsv3_cost.first_plane(run), NAME_HOLDS)
    if not kernel_ns:
        return None
    cfg, p, peaks = run["family"].cfg, run["cell"]["params"], run["peaks"]
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    expert = dsv3_cost.expert_params(cfg)
    pairs = p["batch_size"] * p["prompt_len"] * dsv3_cost.local_pairs_per_token(cfg)
    # weights of every held expert once a layer, rows in and out of the three products
    moved = 2 * (cfg["n_held_experts"] * expert + pairs * (2 * cfg["hidden_size"] + 3 * cfg["moe_intermediate_size"]))
    least = layers * dsv3_cost.roofline_seconds(2.0 * pairs * expert, moved, peaks)
    kernel_s = kernel_ns / 1e9
    print(f"moe_experts_roofline.decode: {1e3 * kernel_s / counters['calls']:.2f} ms of expert kernels a call against "
          f"{1e3 * least:.2f} ms at the roofline", flush=True)
    return 100.0 * least * counters["calls"] / kernel_s
