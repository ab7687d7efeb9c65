"""DeepSeek-V3's language model (arXiv:2412.19437 section 2; the model's own
``inference/model.py``), plain: the expanded attention only, no cache, experts
one at a time on every token, float32 at ``precision="float32"``.

Token embedding; blocks ``x + MLA(RMSNorm(x))``, ``x + FFN(RMSNorm(x))``
(RMSNorm eps ``rms_norm_eps``, no biases); the first ``first_k_dense_replace``
blocks have a dense SwiGLU, the others sigmoid-routed experts and a shared
expert; final RMSNorm; untied head.

- MLA: ``c_q = RMSNorm(x W_dq)``, ``q = c_q W_uq`` split a head into
  ``[q_nope, q_rope]``; ``[c_kv, k_rope] = x W_dkv``, ``c_kv = RMSNorm(c_kv)``;
  rotary (YaRN frequencies, adjacent channels paired) on ``q_rope`` and on the
  one ``k_rope`` all heads share; ``[k_nope, v] = c_kv W_ukv`` a head;
  ``score = (q_nope . k_nope + q_rope . k_rope) * d_qk^-0.5 * mscale^2``;
  causal softmax; ``o = concat_h(P v) W_o``.
- Experts: ``s = sigmoid(x W_g)`` in float32 whatever the precision (the
  configuration states it); chosen on ``s + b`` (a group's score is the sum
  of its two largest, the ``topk_group`` best groups stay, then the
  ``num_experts_per_tok`` largest in them); ``w = s[chosen] / sum * scale``;
  ``y = sum_i w_i E_i(x) + E_shared(x)``, ``E(x) = W_2 (silu(W_1 x) * W_3 x)``.

Departures from the published description: the multi-token-prediction module
is left out (report section 2.2: serving may discard it). **The layer is one
chip's share**: only the experts ``held_experts_start`` to
``+ n_held_experts`` exist here (the weights hold no others); a pair routed to
an expert held elsewhere adds nothing, and that partial result goes on to the
next layer, as in the program. With all experts held this is the whole model.
The vocabulary is the slice the weights hold.

Weights arrive as a flat ``{"params/.../w_dq": array}`` dict under the
program's parameter names, in whatever dtype they are stored in (bfloat16 at
the benchmark's size); each is widened to float32 where it is used, one
expert at a time, so no float32 copy of a whole layer is asked for."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common as c


def f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, scale, eps: float):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * f32(scale)


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """``precompute_freqs_cis`` of ``inference/model.py`` without the positions."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    rs = cfg["rope_scaling"]
    freqs = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))

    def correction_dim(rotations):
        return dim * math.log(rs["original_max_position_embeddings"] / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    smooth = 1 - ramp
    return (freqs / rs["factor"] * (1 - smooth) + freqs * smooth).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    mscale = 0.1 * cfg["rope_scaling"]["mscale_all_dim"] * math.log(cfg["rope_scaling"]["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * mscale * mscale


def rotate(t, pos, inv_freq):
    """``t`` (..., N, [H,] R) as complex pairs of adjacent channels times ``exp(i pos freq)``."""
    angles = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]  # (N, R/2)
    if t.ndim == 4:
        angles = angles[:, None, :]
    pairs = t.reshape(*t.shape[:-1], -1, 2)
    re, im = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([re * jnp.cos(angles) - im * jnp.sin(angles), re * jnp.sin(angles) + im * jnp.cos(angles)], axis=-1)
    return out.reshape(t.shape)


def mla(x, w: dict, prefix: str, cfg: dict, precision: str):
    b, n, _ = x.shape
    heads, nope, rope, dv = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    pos, inv_freq = jnp.arange(n), yarn_inv_freq(cfg)
    c_q = rms_norm(c.mm(x, w[prefix + "/w_dq"], precision), w[prefix + "/q_norm/scale"], eps)
    q = c.mm(c_q, w[prefix + "/w_uq"], precision).reshape(b, n, heads, nope + rope)
    q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], pos, inv_freq)
    kv = c.mm(x, w[prefix + "/w_dkv"], precision)
    c_kv = rms_norm(kv[..., :rank], w[prefix + "/kv_norm/scale"], eps)
    k_rope = rotate(kv[..., rank:], pos, inv_freq)
    up = c.mm(c_kv, w[prefix + "/w_ukv"], precision).reshape(b, n, heads, nope + dv)
    k_nope, v = up[..., :nope], up[..., nope:]
    scores = c.einsum("bihc,bjhc->bhij", q_nope, k_nope, precision) + c.einsum("bihc,bjc->bhij", q_rope, k_rope, precision)
    visible = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    probs = jax.nn.softmax(jnp.where(visible[None, None], scores * softmax_scale(cfg), -jnp.inf), axis=-1)
    o = c.einsum("bhij,bjhc->bihc", probs, v, precision).reshape(b, n, heads * dv)
    return c.mm(o, w[prefix + "/w_o"], precision)


def swiglu(x, w1, w3, w2, precision: str):
    return c.mm(jax.nn.silu(c.mm(x, w1, precision)) * c.mm(x, w3, precision), w2, precision)


def route(x, w: dict, prefix: str, cfg: dict):
    """Chosen experts (T, k) and their weights (T, k), float32 throughout."""
    e, groups = cfg["n_routed_experts"], cfg["n_group"]
    s = jax.nn.sigmoid(jnp.dot(x, f32(w[prefix + "/gate"]), precision="highest"))
    biased = (s + f32(w[prefix + "/gate_bias"])).reshape(-1, groups, e // groups)
    group_score = jnp.sort(biased, axis=-1)[..., -2:].sum(-1)
    kept = jnp.argsort(-group_score, axis=-1)[:, : cfg["topk_group"]]
    stays = (kept[:, :, None] == jnp.arange(groups)[None, None, :]).any(axis=1)
    masked = jnp.where(stays[:, :, None], biased, -jnp.inf).reshape(-1, e)
    chosen = jnp.argsort(-masked, axis=-1)[:, : cfg["num_experts_per_tok"]]
    weight = jnp.take_along_axis(s, chosen, axis=1)
    return chosen, weight / weight.sum(-1, keepdims=True) * cfg["routed_scaling_factor"]


def experts(x, w: dict, prefix: str, cfg: dict, precision: str):
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    chosen, weight = route(x, w, prefix, cfg)
    y = swiglu(x, w[prefix + "/shared/w1"], w[prefix + "/shared/w3"], w[prefix + "/shared/w2"], precision)
    start = cfg["held_experts_start"]
    for i in range(cfg["n_held_experts"]):  # one at a time; experts held elsewhere add nothing
        gate = jnp.where(chosen == start + i, weight, 0.0).sum(-1)
        y = y + gate[:, None] * swiglu(x, w[prefix + "/experts_w1"][i], w[prefix + "/experts_w3"][i],
                                       w[prefix + "/experts_w2"][i], precision)
    return y.reshape(shape)


def logits(w: dict, ids, cfg: dict, precision: str = "float32", last=None):
    """Logits (B, last, V) of the last ``last`` positions (default all) of a full causal forward."""
    eps = cfg["rms_norm_eps"]
    x = f32(w["params/embedding"][ids])
    for i in range(cfg["num_hidden_layers"]):
        layer = f"params/layer_{i}"
        x = x + mla(rms_norm(x, w[layer + "/attn_norm/scale"], eps), w, layer + "/attn", cfg, precision)
        h = rms_norm(x, w[layer + "/ffn_norm/scale"], eps)
        if i < cfg["first_k_dense_replace"]:
            x = x + swiglu(h, w[layer + "/ffn/w1"], w[layer + "/ffn/w3"], w[layer + "/ffn/w2"], precision)
        else:
            x = x + experts(h, w, layer + "/ffn", cfg, precision)
    if last is not None:
        x = x[:, -last:]
    return c.mm(rms_norm(x, w["params/out_norm/scale"], eps), w["params/head"], precision)
