"""LongCat-Flash's language model (``meituan-longcat/LongCat-Flash-Chat``,
``config.json``; technical report arXiv:2509.01322 section 2; the shortcut
topology is arXiv:2404.05019's), plain: no kernels, no cache, no batching,
experts one at a time on every token, float32 at ``precision="float32"``.
Imports nothing of the program; RMSNorm, the adjacent-pair rotary and the
SwiGLU are ``deepseek_v3.py``'s.

Token embedding; ``num_hidden_layers`` shortcut-connected layers; final
RMSNorm (eps ``rms_norm_eps``); untied head. A layer takes ``h`` and gives::

    a0 = h  + MLA_0(RMS(h))
    u  = RMS(a0)
    s  = MoE(u)                      # the shortcut branch: reads u, joins at the end
    b0 = a0 + FFN_0(u)               # dense SwiGLU
    a1 = b0 + MLA_1(RMS(b0))
    h' = a1 + FFN_1(RMS(a1)) + s

- ``MLA_j``: DeepSeek-V3's, with ``c_q = RMS(x W_dq) * sqrt(hidden / q_lora_rank)``
  (``mla_scale_q_lora``) and ``c_kv = RMS((x W_dkv)[:rank]) * sqrt(hidden / kv_lora_rank)``
  (``mla_scale_kv_lora``), the one shared ``k_rope`` rotated and not scaled;
  rotary on adjacent channel pairs with plain frequencies
  ``theta^(-2i/rope)`` (no YaRN, no ``mscale``); scores over
  ``sqrt(nope + rope)``; causal softmax; ``W_o``.
- ``MoE(u)``: ``p = softmax(u W_r)`` in float32 whatever the precision, over
  ``n_routed_experts + zero_expert_num`` outputs (the first are SwiGLUs, the
  last identity experts); the ``num_experts_per_tok`` largest of ``p + b``
  chosen; ``w_e = routed_scaling_factor * p_e``, not renormalised; no groups,
  no shared expert;
  ``MoE(u) = sum_{chosen e with weights} w_e E_e(u) + (sum_{chosen identity e} w_e) u``.

Departures from the published description, each in the configuration file's
``assumed``: the two factors' form (the config holds the switches only; the
form is the report's variance alignment); no renormalisation of the chosen
weights (the config has no ``norm_topk_prob``). **The layer is one chip's
share**: only the experts ``held_experts_start`` to ``+ n_held_experts`` of the
``n_routed_experts`` that have weights exist here; a pair routed to an expert
held elsewhere adds nothing, the identity experts are whole on every chip, and
that partial result goes on to the next layer, as in the program. The
vocabulary is the slice the weights hold.

Weights arrive as a flat ``{"params/.../w_dq": array}`` dict under the
program's parameter names, in whatever dtype they are stored in; each is
widened to float32 where it is used, one product or one expert at a time."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common as c
from .deepseek_v3 import f32, rms_norm, rotate, swiglu


def inv_freq(cfg: dict) -> np.ndarray:
    dim = cfg["qk_rope_head_dim"]
    return (1.0 / (cfg["rope_theta"] ** (np.arange(0, dim, 2, dtype=np.float64) / dim))).astype(np.float32)


def mla(x, w: dict, prefix: str, cfg: dict, precision: str):
    b, n, hidden = x.shape
    heads, nope, rope, dv = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q_rank, rank, eps = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    pos, freq = jnp.arange(n), inv_freq(cfg)
    c_q = rms_norm(c.mm(x, w[prefix + "/w_dq"], precision), w[prefix + "/q_norm/scale"], eps)
    if cfg["mla_scale_q_lora"]:
        c_q = c_q * (hidden / q_rank) ** 0.5
    q = c.mm(c_q, w[prefix + "/w_uq"], precision).reshape(b, n, heads, nope + rope)
    q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], pos, freq)
    kv = c.mm(x, w[prefix + "/w_dkv"], precision)
    c_kv = rms_norm(kv[..., :rank], w[prefix + "/kv_norm/scale"], eps)
    if cfg["mla_scale_kv_lora"]:
        c_kv = c_kv * (hidden / rank) ** 0.5
    k_rope = rotate(kv[..., rank:], pos, freq)
    up = c.mm(c_kv, w[prefix + "/w_ukv"], precision).reshape(b, n, heads, nope + dv)
    k_nope, v = up[..., :nope], up[..., nope:]
    scores = c.einsum("bihc,bjhc->bhij", q_nope, k_nope, precision) + c.einsum("bihc,bjc->bhij", q_rope, k_rope, precision)
    visible = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    probs = jax.nn.softmax(jnp.where(visible[None, None], scores * (nope + rope) ** -0.5, -jnp.inf), axis=-1)
    o = c.einsum("bhij,bjhc->bihc", probs, v, precision).reshape(b, n, heads * dv)
    return c.mm(o, w[prefix + "/w_o"], precision)


def route(x, w: dict, prefix: str, cfg: dict):
    """Chosen router outputs (T, k) and their weights (T, k), float32 throughout."""
    p = jax.nn.softmax(jnp.dot(x, f32(w[prefix + "/gate"]), precision="highest"), axis=-1)
    chosen = jnp.argsort(-(p + f32(w[prefix + "/gate_bias"])), axis=-1)[:, : cfg["num_experts_per_tok"]]
    return chosen, jnp.take_along_axis(p, chosen, axis=1) * cfg["routed_scaling_factor"]


def experts(x, w: dict, prefix: str, cfg: dict, precision: str):
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    chosen, weight = route(x, w, prefix, cfg)
    # the identity experts, whole on every chip: a token's pairs among them add their weights' sum times the token
    y = jnp.where(chosen >= cfg["n_routed_experts"], weight, 0.0).sum(-1)[:, None] * x
    start = cfg["held_experts_start"]
    for i in range(cfg["n_held_experts"]):  # one at a time; experts held elsewhere add nothing
        gate = jnp.where(chosen == start + i, weight, 0.0).sum(-1)
        y = y + gate[:, None] * swiglu(x, w[prefix + "/experts_w1"][i], w[prefix + "/experts_w3"][i],
                                       w[prefix + "/experts_w2"][i], precision)
    return y.reshape(shape)


def layer(h, w: dict, prefix: str, cfg: dict, precision: str):
    """One shortcut-connected layer (the module docstring's equations)."""
    eps = cfg["rms_norm_eps"]
    dense = lambda x, name: swiglu(x, w[f"{prefix}/{name}/w1"], w[f"{prefix}/{name}/w3"], w[f"{prefix}/{name}/w2"], precision)  # noqa: E731
    a0 = h + mla(rms_norm(h, w[prefix + "/attn0_norm/scale"], eps), w, prefix + "/attn0", cfg, precision)
    u = rms_norm(a0, w[prefix + "/ffn0_norm/scale"], eps)
    s = experts(u, w, prefix + "/moe", cfg, precision)
    b0 = a0 + dense(u, "ffn0")
    a1 = b0 + mla(rms_norm(b0, w[prefix + "/attn1_norm/scale"], eps), w, prefix + "/attn1", cfg, precision)
    return a1 + dense(rms_norm(a1, w[prefix + "/ffn1_norm/scale"], eps), "ffn1") + s


def logits(w: dict, ids, cfg: dict, precision: str = "float32", last=None, layer_fn=layer):
    """Logits (B, last, V) of the last ``last`` positions (default all) of a
    full causal forward. ``layer_fn`` is the layer; a test hands in a wrong one."""
    x = f32(w["params/embedding"][ids])
    for i in range(cfg["num_hidden_layers"]):
        x = layer_fn(x, w, f"params/layer_{i}", cfg, precision)
    if last is not None:
        x = x[:, -last:]
    return c.mm(rms_norm(x, w["params/out_norm/scale"], cfg["rms_norm_eps"]), w["params/head"], precision)
