"""MFU and goodput accounting.

MFU (model FLOPs utilization, the pjit-era scaling studies' primary health
metric) is analytic model FLOPs per second over the device's peak matmul
rate: ``mfu = model_flops_per_sec / (peak_flops * n_devices)``. The
numerator counts only the FLOPs the *model math* requires (the
``utils.flops.train_step_flops`` cost model —
rematerialization, padding and layout copies do not inflate it), so MFU is
comparable across implementations of the same model (the benchmark's
``mfu.train`` counts with its own ``benchmarks/lib/flops.py``).

Goodput is the productive fraction of wall time: step execution vs. the
compile / checkpoint / eval / other overheads a :class:`GoodputTracker`
buckets.
"""

from __future__ import annotations

import collections
import contextlib
import time
from typing import Dict, Optional, Tuple

# Per-device dense peak matmul FLOP/s at the training dtype (bf16 for the
# accelerators). Matched by substring against the lowercased
# ``Device.device_kind`` — first hit wins, so more specific patterns come
# first. A device off the table (the CPU among them) has no peak, so its
# MFU is null; ``TrainerConfig.peak_flops_per_device`` overrides per run.
PEAK_FLOPS = (
    ("v6 lite", 918e12),  # TPU v6e
    ("v6", 918e12),
    ("v5 lite", 197e12),  # TPU v5e (device_kind "TPU v5 lite")
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
    ("h100", 495e12),  # dense bf16 (989e12 is the 2:1-sparsity figure)
    ("a100", 312e12),
)


def device_peak_flops(device=None) -> Optional[float]:
    """Peak FLOP/s for ``device`` (default: the first addressable device),
    or None when the device kind is not in the table."""
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = (getattr(device, "device_kind", "") or "").lower()
    for pattern, peak in PEAK_FLOPS:
        if pattern in kind:
            return peak
    return None


def clm_train_telemetry(model_config) -> Optional[Tuple[int, float]]:
    """``(tokens_per_sample, flops_per_sample)`` for a Perceiver AR CLM
    config — what the trainer multiplies by the observed batch size to
    report ``tokens_per_sec`` / ``model_flops_per_sec`` / ``mfu``.

    Tokens are *latent* tokens (the positions that receive a loss); FLOPs
    are fwd+bwd per sample from ``utils.flops.train_step_flops``, the
    package's one analytic model, so every run's logged MFU counts the
    same operations for the same config. Prefix cross-attention
    is discounted by the configured prefix-dropout rate. Returns None for
    configs that are not CLM-shaped (no analytic cost model wired up).
    """
    required = ("vocab_size", "max_seq_len", "max_latents", "num_channels",
                "num_self_attention_layers", "self_attention_widening_factor",
                "cross_attention_widening_factor")
    if not all(hasattr(model_config, a) for a in required):
        return None
    from perceiver_io_tpu.utils.flops import train_step_flops

    keep = 1.0 - getattr(model_config, "cross_attention_dropout", 0.5)
    flops = train_step_flops(model_config, batch_size=1, prefix_dropout_keep=keep)
    return model_config.max_latents, float(flops)


class GoodputTracker:
    """Wall-time bucketing: everything measured into a named overhead bucket
    (``compile`` / ``checkpoint`` / ``eval`` / ...) counts against goodput;
    the remainder of elapsed time is productive step time.

    ``goodput = (elapsed - sum(overheads)) / elapsed``.
    """

    def __init__(self, clock=time.perf_counter):
        self._clock = clock
        self._t0 = clock()
        self._buckets: Dict[str, float] = collections.defaultdict(float)

    def add(self, name: str, seconds: float) -> None:
        self._buckets[name] += max(float(seconds), 0.0)

    @contextlib.contextmanager
    def measure(self, name: str):
        t0 = self._clock()
        try:
            yield
        finally:
            self.add(name, self._clock() - t0)

    def elapsed(self) -> float:
        return self._clock() - self._t0

    def overhead(self) -> float:
        """Total seconds booked into overhead buckets so far — snapshot it
        at window boundaries to compute per-window goodput deltas."""
        return sum(self._buckets.values())

    def summary(self) -> Dict[str, float]:
        total = max(self.elapsed(), 1e-9)
        overhead = self.overhead()
        productive = max(total - overhead, 0.0)
        out = {
            "total_s": round(total, 4),
            "productive_s": round(productive, 4),
            "goodput": round(productive / total, 4),
        }
        for name, secs in sorted(self._buckets.items()):
            out[f"{name}_s"] = round(secs, 4)
        return out
