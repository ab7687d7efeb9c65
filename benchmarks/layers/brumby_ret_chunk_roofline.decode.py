"""The chunked retention kernels' share of their roofline over the traced
calls, in percent: the least time the chip could take for a prompt pass's
retention (``lib/brumby_cost.py::chunk_cost`` a layer, the state form whatever
chunk the program cuts a row into: the larger of its matrix-unit operations,
``phi(Q) S`` and ``phi(K)^T V`` a token, over the bf16 peak and of one read of q,
k, v and the gates and one write of ``y`` and the final state over the HBM
peak) over the device time of the kernels named ``power_ret_chunk_...``. **The
kernels' own time**: what XLA runs around them under the program's ``ret/chunk``
scope (the keys' and values' turns, the gates' cumulative sums) is printed
beside it, with the share that scope's whole time would read, where the run
has its table of device time by scope (``lib/scopes.py``). The same is printed for the step's kernel (``power_ret_step_...``: a layer's
state read and written once a step at the HBM peak against the kernels' time);
the value is the prompt pass's. ``None`` where the configuration has no
retention layer or the trace holds no such kernel."""

from benchmarks.lib import brumby_cost, dsv3_cost, scopes

NAME = "brumby_ret_chunk_roofline.decode"
NAME_HOLDS = "power_ret_chunk_"
STEP_NAME_HOLDS = "power_ret_step_"


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or "power_retention" not in (cfg.get("layer_types") or ()):
        return None
    events = dsv3_cost.first_plane(run)
    kernel_ns = dsv3_cost.kernel_ns(events, NAME_HOLDS)
    if not kernel_ns:
        return None
    p, peaks, calls = run["cell"]["params"], run["peaks"], counters["calls"]
    cost = brumby_cost.chunk_cost(cfg, p["batch_size"], p["prompt_len"])
    layers = cfg["num_hidden_layers"]
    least = layers * dsv3_cost.roofline_seconds(cost["flops"], cost["bytes"], peaks)
    kernel_s = kernel_ns / 1e9
    bound = "bytes" if cost["bytes"] / peaks["hbm_bytes_per_s"] >= cost["flops"] / peaks["bf16_flops_per_s"] else "operations"
    print(f"{NAME}: {1e3 * kernel_s / calls:.2f} ms of chunk kernels a call against {1e3 * least:.2f} ms at the state form's "
          f"roofline (the {bound} bind); {layers * cost['flops'] * calls / kernel_s / 1e12:.1f} TFLOP/s achieved", flush=True)
    found = scopes.times(run, NAME)
    scope_ns = found.sum(lambda name, row: row["phase"] == "prefill" and row["layer"] == "ret/chunk") if found else 0.0
    if scope_ns:
        print(f"{NAME}: the ret/chunk scope whole {scope_ns / 1e6 / calls:.2f} ms a call, {(scope_ns - kernel_ns) / 1e6 / calls:.2f} of them "
              f"XLA's around the kernels: {100.0 * least * calls / (scope_ns / 1e9):.2f}% by the scope's time", flush=True)
    step_ns = dsv3_cost.kernel_ns(events, STEP_NAME_HOLDS)
    if step_ns:
        steps = p["new_tokens"] - 1
        step_least = 2 * brumby_cost.state_bytes(cfg, p["batch_size"]) / peaks["hbm_bytes_per_s"]
        print(f"{NAME}: the step's kernels {step_ns / 1e6 / calls / steps:.3f} ms a step against "
              f"{1e3 * step_least:.3f} ms to read and write the state at the HBM peak: "
              f"{100.0 * step_least * steps * calls / (step_ns / 1e9):.1f}% of their roofline", flush=True)
    return 100.0 * least * calls / kernel_s
