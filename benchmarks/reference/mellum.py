"""Mellum 2's language model (``JetBrains/Mellum2-12B-A2.5B-Instruct``,
``config.json``; the layer equations are those of Hugging Face's grouped-query
decoder layers with ``layer_types`` and of its softmax-routed sparse block),
plain: no kernels, no cache, every expert on every token weighted by the
routing, float32 at ``precision="float32"``. Imports nothing of the program.

Token embedding; blocks ``h = x + Attn_l(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``
(RMSNorm eps ``rms_norm_eps``, no biases); final RMSNorm; untied head.

- ``Attn_l``: ``q = x W_q`` (``num_attention_heads`` x ``head_dim``),
  ``k = x W_k``, ``v = x W_v`` (``num_key_value_heads`` x ``head_dim``); rotary
  on q and k, half-split pairing (channel i with channel i + head_dim / 2),
  theta ``rope_theta``: a ``sliding_attention`` layer with the plain
  frequencies, a ``full_attention`` layer with YaRN's (each frequency blended
  between ``f`` and ``f / factor`` by the linear ramp between the dimensions
  that ``beta_fast`` and ``beta_slow`` find over
  ``original_max_position_embeddings`` positions; cos and sin times
  ``attention_factor``); scores ``q k^T / sqrt(head_dim)``; query head i reads
  key-value head ``i // group``; position i sees ``j <= i`` and, on a window
  layer, ``j > i - sliding_window``; output ``W_o``.
- ``MoE``: ``p = softmax(h W_r)`` over all experts in float32 whatever the
  precision (the configuration states it), the ``num_experts_per_tok`` largest
  chosen, ``w = p_chosen / sum(p_chosen)`` (``norm_topk_prob``),
  ``y = sum_e w_e W2_e (silu(W1_e h) * W3_e h)``; no shared expert.

Departures from the published description: none in the equations. No q/k norm
is applied (the config has no key for one); no multi-token-prediction head is
built (the catalog row's ``described_as`` names one, the config has no key for
it); the depth is the configuration's (two layer periods of seven).

Computed in blocks so that it fits beside 7.6 GB of weights at the benchmark's
8447 positions: attention over ``Q_BLOCK`` queries at a time (a block's float32
scores for 32 heads are 0.55 GB), the experts one at a time in a ``lax.scan``
over the stacked weights, and the logits of the last ``last`` positions only (a
full-vocabulary float32 row is 393 KB).

Weights arrive as a flat ``{"params/.../w_q": array}`` dict under the
program's parameter names, in whatever dtype they are stored in; each is
widened to float32 where it is used, one expert at a time."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import common as c

Q_BLOCK = 512


def f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, scale, eps: float):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * f32(scale)


def rotary_tables(cfg: dict, layer_type: str):
    """``(inv_freq (head_dim / 2,), attention_factor)`` of a layer of this type
    (Hugging Face's ``_compute_yarn_parameters`` for the full layers)."""
    dim, base = cfg["head_dim"], cfg["rope_theta"]
    freqs = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    rs = cfg["rope_scaling"]
    if layer_type == "sliding_attention" or rs is None:
        return freqs.astype(np.float32), 1.0

    def correction_dim(rotations):
        return dim * math.log(rs["original_max_position_embeddings"] / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    keep = 1 - ramp  # 1: the frequency turns often enough over the original context and stays as it is
    return (freqs / rs["factor"] * (1 - keep) + freqs * keep).astype(np.float32), float(rs["attention_factor"])


def rotate(t, pos, inv_freq, factor: float):
    """``t`` (B, N, H, D): channel i of the first half and of the second are one complex number turned by ``pos * inv_freq[i]``."""
    angles = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]  # (N, D/2)
    cos, sin = (jnp.cos(angles) * factor)[None, :, None, :], (jnp.sin(angles) * factor)[None, :, None, :]
    half = t.shape[-1] // 2
    x1, x2 = t[..., :half], t[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, w: dict, prefix: str, cfg: dict, layer_type: str, precision: str):
    b, n, _ = x.shape
    heads, kv_heads, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    group = heads // kv_heads
    inv_freq, factor = rotary_tables(cfg, layer_type)
    pos = jnp.arange(n)
    q = rotate(c.mm(x, w[prefix + "/w_q"], precision).reshape(b, n, heads, d), pos, inv_freq, factor)
    k = rotate(c.mm(x, w[prefix + "/w_k"], precision).reshape(b, n, kv_heads, d), pos, inv_freq, factor)
    v = c.mm(x, w[prefix + "/w_v"], precision).reshape(b, n, kv_heads, d)
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)  # query head i reads key-value head i // group
    window = cfg["sliding_window"] if layer_type == "sliding_attention" else None

    def block(start):
        i = start + jnp.arange(Q_BLOCK)
        qb = lax.dynamic_slice_in_dim(q, start, Q_BLOCK, axis=1)
        scores = c.einsum("bihc,bjhc->bhij", qb, k, precision) / math.sqrt(d)
        visible = pos[None, :] <= i[:, None]
        if window is not None:
            visible &= pos[None, :] > i[:, None] - window
        probs = jax.nn.softmax(jnp.where(visible[None, None], scores, -jnp.inf), axis=-1)
        return c.einsum("bhij,bjhc->bihc", probs, v, precision)

    # whole blocks of queries, the last one moved back so that it ends at the last position
    starts = sorted({min(s, max(n - Q_BLOCK, 0)) for s in range(0, n, Q_BLOCK)})
    if n < Q_BLOCK:
        q = jnp.pad(q, ((0, 0), (0, Q_BLOCK - n), (0, 0), (0, 0)))
    outs = lax.map(block, jnp.asarray(starts))  # (blocks, B, Q_BLOCK, H, D)
    o = jnp.zeros((b, max(n, Q_BLOCK), heads, d), jnp.float32)
    for j, s in enumerate(starts):
        o = lax.dynamic_update_slice_in_dim(o, outs[j], s, axis=1)
    return c.mm(o[:, :n].reshape(b, n, heads * d), w[prefix + "/w_o"], precision)


def swiglu(x, w1, w3, w2, precision: str):
    return c.mm(jax.nn.silu(c.mm(x, w1, precision)) * c.mm(x, w3, precision), w2, precision)


def route(x, w: dict, prefix: str, cfg: dict):
    """Chosen experts (T, k) and their weights (T, k), float32 throughout."""
    p = jax.nn.softmax(jnp.dot(x, f32(w[prefix + "/gate"]), precision="highest"), axis=-1)
    chosen = jnp.argsort(-p, axis=-1)[:, : cfg["num_experts_per_tok"]]
    weight = jnp.take_along_axis(p, chosen, axis=1)
    return chosen, weight / weight.sum(-1, keepdims=True)


def experts(x, w: dict, prefix: str, cfg: dict, precision: str):
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    chosen, weight = route(x, w, prefix, cfg)

    def one(y, expert):
        i, w1, w3, w2 = expert
        gate = jnp.where(chosen == i, weight, 0.0).sum(-1)
        return y + gate[:, None] * swiglu(x, f32(w1), f32(w3), f32(w2), precision), None

    stacked = (jnp.arange(cfg["n_routed_experts"]), w[prefix + "/experts_w1"], w[prefix + "/experts_w3"],
               w[prefix + "/experts_w2"])
    y, _ = lax.scan(one, jnp.zeros_like(x), stacked)  # one expert at a time, on every token
    return y.reshape(shape)


def logits(w: dict, ids, cfg: dict, precision: str = "float32", last=None):
    """Logits (B, last, V) of the last ``last`` positions (default all) of a full causal forward."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = f32(w["params/embedding"][ids])
        for i, layer_type in enumerate(cfg["layer_types"]):
            layer = f"params/layer_{i}"
            x = x + attention(rms_norm(x, w[layer + "/attn_norm/scale"], eps), w, layer + "/attn", cfg, layer_type, precision)
            x = x + experts(rms_norm(x, w[layer + "/ffn_norm/scale"], eps), w, layer + "/ffn", cfg, precision)
        if last is not None:
            x = x[:, -last:]
        return c.mm(rms_norm(x, w["params/out_norm/scale"], eps), w["params/head"], precision)
