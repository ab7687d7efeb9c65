"""The selective-scan kernels' share of their roofline over the traced calls,
in percent: the least time the chip could take for the nine scans of a prompt
pass (``lib/phi4flash_cost.py::scan_cost``, the recurrence alone: the larger of
the elementwise operations over the bf16 peak and of one read of the inputs and
one write of ``y`` and the final state over the HBM peak) over the device time
of the kernels named ``ssm_scan_...``: ``ops/selective_scan.py`` at Jamba2-3B's
own geometry (5120 channels, 16 states), so this is
``jamba_ssm_scan_roofline.decode``'s reading at rows of 8192 where that cell's
are 768. ``peaks.json`` has no vector-unit peak and the recurrence has no
matrix-unit form, so the bound that binds is the bytes', and the share reads
low for as long as the kernel is bound by the vector unit: the reader prints the
elementwise operations a second it achieved beside it. ``None`` where the
configuration has no gated memory unit or the trace holds no such kernel."""

from benchmarks.lib import dsv3_cost, phi4flash_cost

NAME = "phi4flash_ssm_scan_roofline.decode"
NAME_HOLDS = "ssm_scan_"


def read(run):
    counters, cfg = run["counters"], run["family"].cfg
    if run["trace"] is None or not counters.get("calls") or "gmu" not in (cfg.get("layer_types") or ()):
        return None
    kernel_ns = dsv3_cost.kernel_ns(dsv3_cost.first_plane(run), NAME_HOLDS)
    if not kernel_ns:
        return None
    p, peaks = run["cell"]["params"], run["peaks"]
    cost = phi4flash_cost.scan_cost(cfg, p["batch_size"], p["prompt_len"])
    layers = phi4flash_cost.n_layers(cfg, "mamba")
    least = layers * dsv3_cost.roofline_seconds(cost["flops"], cost["bytes"], peaks)
    kernel_s = kernel_ns / 1e9
    bound = "bytes" if cost["bytes"] / peaks["hbm_bytes_per_s"] >= cost["flops"] / peaks["bf16_flops_per_s"] else "operations"
    print(f"{NAME}: {1e3 * kernel_s / counters['calls']:.2f} ms of scan kernels a call against "
          f"{1e3 * least:.2f} ms at the roofline (the {bound} bind); "
          f"{layers * cost['flops'] * counters['calls'] / kernel_s / 1e12:.3f} T elementwise operations a second achieved", flush=True)
    return 100.0 * least * counters["calls"] / kernel_s
