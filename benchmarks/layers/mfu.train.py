"""Model FLOP/s utilisation of the traced window, in percent: the operations
one optimizer step needs (``lib/flops.py``, forward and backward, recomputed
work not counted) times steps per second, over chips times the bf16 peak."""


def read(run):
    counters = run["counters"]
    if "steps" not in counters:
        return None
    flops_per_step = run["family"].train_flops(counters["batch_size"])
    achieved = flops_per_step * counters["steps"] / counters["elapsed_s"]
    return 100.0 * achieved / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
