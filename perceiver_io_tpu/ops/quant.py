"""Weight-only int8 quantization for the decode path.

The batched-decode KV cache already stores int8 (core/attention.py:KVCache);
this module covers the OTHER half of a decode step's HBM traffic: the
projection/MLP kernels, read in full once per generated token. Per-output-
channel symmetric int8 storage halves that read (the reference has no
quantized inference at all — torch decode moves full-precision weights,
reference: core/huggingface.py:158-185 — so this is beyond-parity,
exposed as an opt-in ``weight_dtype`` on the generation entry points).

Design notes, TPU-specific:

- Dequantization happens INSIDE the decode ``lax.scan`` body, per step.
  XLA's while-loop invariant code motion would normally hoist a
  loop-invariant ``convert(int8 -> bf16)`` out of the loop — which would
  materialize the full bf16 weights in HBM once and make the loop read
  bf16, silently deleting the entire bandwidth saving. It does not,
  because the pass refuses to hoist size-inflating ops (the convert
  doubles bytes); the multiply-by-scale then cannot hoist either (its
  operand is in-loop). The convert+scale fuse into each matmul's operand
  read, so HBM sees int8. Verified empirically: ``bench.py --mode decode
  --weight-dtype int8`` (since deleted) at batch 1 measured this speedup and
  its ``ceiling_fraction`` against the int8-bytes floor reads ~0.99 — a
  hoisted (bf16-materializing) convert would cap it near 0.78
  (``BENCH_extra_r4.json: decode_b1_int8w``; docs/performance.md).
- Scales are float32 and quantization rounds against the STORED scale
  (same contract as ``quantize_kv``): quantizing with a more precise
  scale than dequantization uses would leak rounding error.
- Only matmul kernels are quantized (leaf path ``.../kernel``, 2D).
  Embeddings stay full precision — the token/position tables are row-
  GATHERED in decode (not fully read, so no bandwidth win) and the tied
  logit head reads the token table (quality-sensitive). LayerNorm
  scales/biases and projection biases are vectors (no bandwidth).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
class QuantizedTensor:
    """int8 values + per-output-channel float32 scale; ``w ~= q * scale``.

    Registered as a pytree node so quantized trees pass through jit/scan
    boundaries; :func:`dequantize_weights` must run before the tree is fed
    to ``model.apply`` (modules expect plain arrays).
    """

    def __init__(self, q: jnp.ndarray, scale: jnp.ndarray):
        self.q = q
        self.scale = scale

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def dequantize(self, dtype=jnp.bfloat16) -> jnp.ndarray:
        return (self.q.astype(self.scale.dtype) * self.scale).astype(dtype)


def quantize_tensor(w: jnp.ndarray) -> QuantizedTensor:
    """Symmetric per-output-channel int8: scale over every axis but the
    last (for a flax ``Dense`` kernel ``(in, out)`` that is one scale per
    output column, group size = fan-in)."""
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=tuple(range(w.ndim - 1)), keepdims=True)
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return QuantizedTensor(q, scale)


def _is_kernel(path) -> bool:
    last = path[-1]
    key = getattr(last, "key", None)
    return key == "kernel"


def quantize_weights(params: Dict[str, Any], min_size: int = 0) -> Dict[str, Any]:
    """Replace every 2D+ matmul kernel of at least ``min_size`` elements in a
    flax param tree with a :class:`QuantizedTensor`; all other leaves pass
    through unchanged. Runs under jit (one device pass over the weights,
    amortized over a whole generation call)."""

    def visit(path, leaf):
        if _is_kernel(path) and leaf.ndim >= 2 and leaf.size >= min_size:
            return quantize_tensor(leaf)
        return leaf

    return jax.tree_util.tree_map_with_path(visit, params)


def dequantize_weights(qparams: Dict[str, Any], dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Inverse of :func:`quantize_weights`: expand quantized leaves to
    ``dtype`` arrays (call INSIDE the decode loop body — see module note on
    loop-invariant code motion)."""
    return jax.tree_util.tree_map(
        lambda x: x.dequantize(dtype) if isinstance(x, QuantizedTensor) else x,
        qparams,
        is_leaf=lambda x: isinstance(x, QuantizedTensor),
    )
