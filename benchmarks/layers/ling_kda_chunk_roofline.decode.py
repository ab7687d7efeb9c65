"""The chunked delta-rule kernels' share of their roofline over the traced
calls, in percent: the least time the chip could take for a prompt pass's
recurrences (``lib/ling_cost.py::chunk_cost`` a delta layer, whatever chunk the
program cuts a row into: the larger of the recurrence's matrix work, three
products of 128 x 128 a head a token, over the bf16 peak and of one read of q,
k, v, the log-decays and the steps and one write of ``y`` and the final state
over the HBM peak) over the device time of the kernels named
``kda_chunk_...``. **The kernels' own time**: what XLA runs around them under
the program's ``kda/chunk`` scope (the log-decays' running sums) is printed
beside it, with the share that scope's whole time would read, where the run has
its table of device time by scope (``lib/scopes.py``). The same is printed for
the step's kernel (``kda_step_...``: a layer's state read and written once a
step at the HBM peak against the kernels' time); the value is the prompt
pass's. ``None`` where the configuration has no delta layer or the trace holds
no such kernel."""

from benchmarks.lib import dsv3_cost, ling_cost, scopes

NAME = "ling_kda_chunk_roofline.decode"
NAME_HOLDS = "kda_chunk_"
STEP_NAME_HOLDS = "kda_step_"


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or "kda" not in (cfg.get("layer_types") or ()):
        return None
    events = dsv3_cost.first_plane(run)
    kernel_ns = dsv3_cost.kernel_ns(events, NAME_HOLDS)
    if not kernel_ns:
        return None
    p, peaks, calls = run["cell"]["params"], run["peaks"], counters["calls"]
    cost = ling_cost.chunk_cost(cfg, p["batch_size"], p["prompt_len"])
    layers = ling_cost.kda_layers(cfg)
    least = layers * dsv3_cost.roofline_seconds(cost["flops"], cost["bytes"], peaks)
    kernel_s = kernel_ns / 1e9
    bound = "bytes" if cost["bytes"] / peaks["hbm_bytes_per_s"] >= cost["flops"] / peaks["bf16_flops_per_s"] else "operations"
    print(f"{NAME}: {1e3 * kernel_s / calls:.2f} ms of chunk kernels a call against {1e3 * least:.2f} ms at the recurrence's roofline "
          f"({layers * cost['flops'] / 1e12:.2f} TFLOP and {layers * cost['bytes'] / 1e9:.2f} GB a call; the {bound} bind); "
          f"{layers * cost['flops'] * calls / kernel_s / 1e12:.2f} TFLOP/s achieved", flush=True)
    found = scopes.times(run, NAME)
    scope_ns = found.sum(lambda name, row: row["phase"] == "prefill" and row["layer"] == "kda/chunk") if found else 0.0
    if scope_ns:
        print(f"{NAME}: the kda/chunk scope whole {scope_ns / 1e6 / calls:.2f} ms a call, {(scope_ns - kernel_ns) / 1e6 / calls:.2f} of them "
              f"XLA's around the kernels: {100.0 * least * calls / (scope_ns / 1e9):.2f}% by the scope's time", flush=True)
    step_ns = dsv3_cost.kernel_ns(events, STEP_NAME_HOLDS)
    if step_ns:
        steps = p["new_tokens"] - 1
        step_least = ling_cost.step_state_bytes(cfg, p["batch_size"]) / peaks["hbm_bytes_per_s"]
        print(f"{NAME}: the step's kernels {step_ns / 1e6 / calls / steps:.3f} ms a step against {1e3 * step_least:.3f} ms to read and "
              f"write the state ({ling_cost.step_state_bytes(cfg, p['batch_size']) / 1e9:.2f} GB) once at the HBM peak: "
              f"{100.0 * step_least * steps * calls / (step_ns / 1e9):.1f}% of their roofline", flush=True)
    return 100.0 * least * calls / kernel_s
