"""The prompt pass's share of the bf16 peak over the traced calls, in percent:
its useful matrix-unit operations (``lib/ling_cost.py::prefill_flops``: every
token through the stack's dense products, the held experts for the pairs routed
to them; every delta layer's recurrence, three products of 128 x 128 a head a
token whatever chunk the program cuts a row into; the latent layer's attention
over the visible pairs; the head once a row) over the device time of the phase
``prefill`` (the run's table of device time by program scope,
``lib/scopes.py``: the prompt pass with its hand-off of states and cache and
the first sample). ``None`` where the configuration has no delta layer or the
run has no such table."""

from benchmarks.lib import ling_cost, scopes

NAME = "ling_prefill_mfu.decode"


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or "kda" not in (cfg.get("layer_types") or ()):
        return None
    found = scopes.times(run, NAME)
    if found is None:
        return None
    prefill_s = found.sum(lambda name, row: row["phase"] == "prefill") / 1e9
    if prefill_s <= 0:
        return None
    p, calls = run["cell"]["params"], counters["calls"]
    flops = calls * ling_cost.prefill_flops(cfg, p["batch_size"], p["prompt_len"])
    print(f"{NAME}: {1e3 * prefill_s / calls:.2f} ms of the phase prefill a call for {flops / calls / 1e12:.1f} TFLOP of the prompt "
          f"pass's products", flush=True)
    return 100.0 * flops / prefill_s / run["peaks"]["bf16_flops_per_s"]
