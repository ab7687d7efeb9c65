"""Manifest AI's Brumby-14B-Base (``manifestai/Brumby-14B-Base``, ``config.json``,
``model_type`` ``brumby``: the Qwen3-14B skeleton retrained with power retention,
arXiv:2507.04239, in every layer's attention's place), plain: the **attention
form** of power retention over whole rows, no state, no chunks, no kernels,
float32 at ``precision="float32"``. Imports nothing of the program.

Token embedding; pre-norm layers ``x = x + Ret_l(RMSNorm(x))``, ``x = x +
W_down(silu(W_gate u) * W_up u)`` with ``u = RMSNorm(x)``; final RMSNorm; an
untied head. For token ``t``, key-value head ``g`` and its query heads ``a``::

    q_t^a, k_t^g, v_t^g = h W_q, h W_k, h W_v        no bias; RMSNorm over head_dim on q and k; half-split rotary at t
    gamma_t^g = sigmoid(h W_g + b_g)_g               Lambda_t = sum_{l <= t} log gamma_l   (a cumulative sum)
    A_tj = (q_t . k_j)^2 exp(Lambda_t - Lambda_j)    for j <= t, else 0: a masked [T, T] matrix a head
    y_t = sum_j A_tj v_j / (sum_j A_tj + eps)        eps 1e-6
    out_t = concat_a(y_t^a) W_o

Departures from the published description, each written into the
configuration's ``assumed``: the degree (2), the gate's form (one sigmoid a
key-value head from a biased projection of the normed hidden state), the
normalisation (the gated sum of weights plus ``eps``) and that q/k norm and the
rotary stay are not keys of the config; the published package switches to the
state form only past ``switch_over_seq_len`` and runs a softmax-free key-value
cache below it, which is the same function and is not modelled; no scale on
``q . k`` (any scale cancels between numerator and denominator up to ``eps``).
The exponent ``Lambda_t - Lambda_j`` is taken from one cumulative sum over the
row, float32; at most 0 wherever it is used.

Computed in blocks of ``Q_BLOCK`` queries (a block's float32 weights for 40
heads over 4351 keys are 0.36 GB) and with the logits of the last ``last``
positions only. ``precision`` reaches the matrix products (``common.mm`` /
``common.einsum``); the norms, the gate's sigmoid and cumulative sum, the
square, the decay and the division are float32 whatever it says.

Weights arrive as a flat ``{"params/.../w_q": array}`` dict under the
program's parameter names, in whatever dtype they are stored in; each is
widened to float32 where it is used."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import common as c

Q_BLOCK = 512
EPS = 1e-6


def f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, scale, eps: float):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * f32(scale)


def rotate(t, pos, cfg: dict):
    """``t`` (B, N, H, D): channel i of the first half and of the second are one complex number turned by ``pos * inv_freq[i]``."""
    dim = cfg["head_dim"]
    inv_freq = (1.0 / (cfg["rope_theta"] ** (np.arange(0, dim, 2, dtype=np.float64) / dim))).astype(np.float32)
    angles = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]  # (N, D/2)
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    half = dim // 2
    x1, x2 = t[..., :half], t[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def retention(x, w: dict, prefix: str, cfg: dict, precision: str):
    """The layer over whole rows ``x`` (B, N, h), in the attention form."""
    b, n, _ = x.shape
    heads, kv_heads, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    group, eps = heads // kv_heads, cfg["rms_norm_eps"]
    pos = jnp.arange(n)
    q = rms_norm(c.mm(x, f32(w[prefix + "/w_q"]), precision).reshape(b, n, heads, d), w[prefix + "/q_norm/scale"], eps)
    k = rms_norm(c.mm(x, f32(w[prefix + "/w_k"]), precision).reshape(b, n, kv_heads, d), w[prefix + "/k_norm/scale"], eps)
    q, k = rotate(q, pos, cfg), rotate(k, pos, cfg)
    v = c.mm(x, f32(w[prefix + "/w_v"]), precision).reshape(b, n, kv_heads, d)
    gate = jax.nn.log_sigmoid(c.mm(x, f32(w[prefix + "/w_g"]), precision) + f32(w[prefix + "/b_g"]))  # (B, N, Hkv)
    lam = jnp.cumsum(gate, axis=1)
    k, v, lam = (jnp.repeat(t, group, axis=2) for t in (k, v, lam))  # query head i reads key-value head i // group
    lam = lam.transpose(0, 2, 1)  # (B, H, N)

    def block(start):
        i = start + jnp.arange(Q_BLOCK)
        qb = lax.dynamic_slice_in_dim(q, start, Q_BLOCK, axis=1)
        lam_i = lax.dynamic_slice_in_dim(lam, start, Q_BLOCK, axis=2)
        scores = c.einsum("bihc,bjhc->bhij", qb, k, precision)
        visible = pos[None, :] <= i[:, None]
        decay = jnp.exp(jnp.where(visible[None, None], lam_i[:, :, :, None] - lam[:, :, None, :], -jnp.inf))
        a = scores * scores * decay
        num = c.einsum("bhij,bjhc->bihc", a, v, precision)
        return num / (a.sum(-1).transpose(0, 2, 1)[..., None] + EPS)

    # whole blocks of queries, the last one moved back so that it ends at the last position
    starts = sorted({min(s, max(n - Q_BLOCK, 0)) for s in range(0, n, Q_BLOCK)})
    if n < Q_BLOCK:
        q = jnp.pad(q, ((0, 0), (0, Q_BLOCK - n), (0, 0), (0, 0)))
        lam = jnp.pad(lam, ((0, 0), (0, 0), (0, Q_BLOCK - n)))
        k, v = (jnp.pad(t, ((0, 0), (0, Q_BLOCK - n), (0, 0), (0, 0))) for t in (k, v))
        pos = jnp.arange(Q_BLOCK)
    outs = lax.map(block, jnp.asarray(starts))  # (blocks, B, Q_BLOCK, H, D)
    o = jnp.zeros((b, max(n, Q_BLOCK), heads, d), jnp.float32)
    for j, s in enumerate(starts):
        o = lax.dynamic_update_slice_in_dim(o, outs[j], s, axis=1)
    return c.mm(o[:, :n].reshape(b, n, heads * d), f32(w[prefix + "/w_o"]), precision)


def swiglu(x, w: dict, prefix: str, precision: str):
    gate = jax.nn.silu(c.mm(x, f32(w[prefix + "/w1"]), precision)) * c.mm(x, f32(w[prefix + "/w3"]), precision)
    return c.mm(gate, f32(w[prefix + "/w2"]), precision)


def logits(w: dict, ids, cfg: dict, precision: str = "float32", last=None):
    """Logits (B, last, V) of the last ``last`` positions (default all) of a full causal forward."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = f32(w["params/embedding"][ids])
        for i in range(cfg["num_hidden_layers"]):
            layer = f"params/layer_{i}"
            x = x + retention(rms_norm(x, w[layer + "/attn_norm/scale"], eps), w, layer + "/attn", cfg, precision)
            x = x + swiglu(rms_norm(x, w[layer + "/ffn_norm/scale"], eps), w, layer + "/ffn", precision)
        if last is not None:
            x = x[:, -last:]
        return c.mm(rms_norm(x, w["params/out_norm/scale"], eps), f32(w["params/head"]), precision)
