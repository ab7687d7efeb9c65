"""Ling-3.0-flash-VL's language model (``inclusionAI/Ling-3.0-flash-VL``,
``config.json``: Kimi delta attention, arXiv:2510.26692, in five layers of six
and multi-head latent attention, arXiv:2412.19437, in the sixth; sigmoid-routed
experts with a shared expert after the leading dense layers), plain: **the
recurrence token by token** under ``lax.scan``, the latent attention expanded,
the experts one at a time on every token, no state handed on, no cache, no
chunk, no kernel, float32 at ``precision="float32"`` under
``jax.default_matmul_precision("highest")``. Imports nothing of the program.

Token embedding; pre-norm layers ``x = x + Mixer_l(RMSNorm(x))``, ``x = x +
FFN_l(RMSNorm(x))``; final RMSNorm; an untied head. Layer ``l``'s mixer is what
``layer_types[l]`` says.

**A ``"kda"`` layer** (``H`` heads of ``D = head_dim`` on q, k and v; no
rotary, no position)::

    q = l2norm(silu(conv(x W_q))) * D^-0.5     k = l2norm(silu(conv(x W_k)))     v = silu(conv(x W_v))
    g = kda_lower_bound * sigmoid(exp(A_log[h]) * (x W_f + dt_bias))      a log-decay a channel, in (kda_lower_bound, 0)
    b = sigmoid(x W_b)                                                     one a head
    S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T       S: D x D a head
    o_t = S_t^T q_t
    y   = (RMSNorm_head(o_t) * sigmoid(x W_g)) W_o

``conv`` is causal and depthwise, ``short_conv_kernel_size`` taps (stored
``(taps, channels)``, the oldest input's tap first), zeros before a row's first
token, no bias; ``l2norm`` is ``t / sqrt(sum t^2 + 1e-6)`` over a head's
channels; the RMSNorm before the output gate is over a head's channels with one
learned scale of ``D``.

**A ``"latent_attention"`` layer**: ``q = x W_uq`` (no query latent) split a
head into ``[q_nope, q_rope]``; ``[c_kv, k_rope] = x W_dkv``, ``c_kv =
RMSNorm(c_kv)``; plain rotary at ``rope_theta`` (adjacent channels paired) on
``q_rope`` and on the one ``k_rope`` all heads share; ``[k_nope, v] = c_kv
W_ukv`` a head; ``score = (q_nope . k_nope + q_rope . k_rope) * (nope +
rope)^-0.5``; causal softmax; ``o_h = (P v)_h * sigmoid(x w_gate)_h`` (one gate
a head); ``out = concat_h(o_h) W_o``.

**Feed-forward**: the first ``first_k_dense_replace`` layers a dense SwiGLU,
the others ``reference/deepseek_v3.py``'s expert layer as it stands (sigmoid
scores in float32, chosen on ``s + b`` within the ``topk_group`` best of
``n_group`` groups, weights the unbiased scores renormalised times
``routed_scaling_factor``, a shared expert), **one chip's share**: only the
experts ``held_experts_start`` to ``+ n_held_experts`` exist here; a pair
routed to an expert held elsewhere adds nothing, and that partial result goes
on to the next layer, as in the program. The vocabulary is the slice the
weights hold.

What the published ``config.json`` has no key for, or a key that can be read
two ways (the gate's bounded form, the heads of the delta layers, the norm
before the output gate, which norms ``use_qk_norm`` answers, the rotary's
pairing), is the configuration file's ``assumed``; program and reference share
every one. ``precision`` reaches the matrix products (``common.mm`` /
``common.einsum``); the norms, the gates' sigmoids, the decays and the
recurrence itself are float32 whatever it says.

Weights arrive as a flat ``{"params/.../w_q": array}`` dict under the
program's parameter names, in whatever dtype they are stored in; each is
widened to float32 where it is used."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import common as c
from .deepseek_v3 import experts, f32, rms_norm, rotate, swiglu

L2_EPS = 1e-6


def causal_conv(t, taps):
    """``t`` (B, N, C) through a causal depthwise convolution with ``taps`` (K, C), then silu: position ``i`` sees ``i - K + 1 .. i``."""
    k, n = taps.shape[0], t.shape[1]
    padded = jnp.pad(t, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(f32(taps[j]) * padded[:, j:j + n] for j in range(k)))


def delta_rule(q, k, v, g, beta):
    """The recurrence, a token a step: ``q``, ``k``, ``v``, ``g`` (B, N, H, D), ``beta`` (B, N, H) -> ``o`` (B, N, H, D)."""
    b, _, heads, d = q.shape

    def token(s, at):  # s (B, H, D_k, D_v)
        q_t, k_t, v_t, g_t, b_t = at
        s = jnp.exp(g_t)[..., None] * s
        predicted = jnp.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + b_t[..., None, None] * k_t[..., None] * (v_t - predicted)[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    _, o = lax.scan(token, jnp.zeros((b, heads, d, d), jnp.float32), tuple(jnp.swapaxes(t, 0, 1) for t in (q, k, v, g, beta)))
    return jnp.swapaxes(o, 0, 1)


def kda(x, w: dict, prefix: str, cfg: dict, precision: str):
    """The layer over whole rows ``x`` (B, N, h)."""
    b, n, _ = x.shape
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    split = lambda t: t.reshape(b, n, heads, d)  # noqa: E731
    l2norm = lambda t: t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS)  # noqa: E731
    project = lambda name: c.mm(x, f32(w[f"{prefix}/{name}"]), precision)  # noqa: E731
    q = l2norm(split(causal_conv(project("w_q"), w[prefix + "/conv_q"]))) * d ** -0.5
    k = l2norm(split(causal_conv(project("w_k"), w[prefix + "/conv_k"])))
    v = split(causal_conv(project("w_v"), w[prefix + "/conv_v"]))
    rate = jnp.exp(f32(w[prefix + "/a_log"]))[:, None]  # (H, 1): one rate a head
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(rate * split(project("w_f") + f32(w[prefix + "/dt_bias"])))
    beta = jax.nn.sigmoid(project("w_b"))
    o = rms_norm(delta_rule(q, k, v, g, beta), w[prefix + "/o_norm/scale"], cfg["rms_norm_eps"])
    gated = o * split(jax.nn.sigmoid(project("w_g")))
    return c.mm(gated.reshape(b, n, heads * d), f32(w[prefix + "/w_o"]), precision)


def latent_attention(x, w: dict, prefix: str, cfg: dict, precision: str):
    b, n, _ = x.shape
    heads, nope, rope, dv = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    pos = jnp.arange(n)
    inv_freq = (1.0 / (cfg["rope_theta"] ** (np.arange(0, rope, 2, dtype=np.float64) / rope))).astype(np.float32)
    q = c.mm(x, f32(w[prefix + "/w_uq"]), precision).reshape(b, n, heads, nope + rope)
    q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], pos, inv_freq)
    kv = c.mm(x, f32(w[prefix + "/w_dkv"]), precision)
    c_kv = rms_norm(kv[..., :rank], w[prefix + "/kv_norm/scale"], eps)
    k_rope = rotate(kv[..., rank:], pos, inv_freq)
    up = c.mm(c_kv, f32(w[prefix + "/w_ukv"]), precision).reshape(b, n, heads, nope + dv)
    k_nope, v = up[..., :nope], up[..., nope:]
    scores = c.einsum("bihc,bjhc->bhij", q_nope, k_nope, precision) + c.einsum("bihc,bjc->bhij", q_rope, k_rope, precision)
    visible = pos[None, :] <= pos[:, None]
    probs = jax.nn.softmax(jnp.where(visible[None, None], scores * (nope + rope) ** -0.5, -jnp.inf), axis=-1)
    o = c.einsum("bhij,bjhc->bihc", probs, v, precision)
    o = o * jax.nn.sigmoid(c.mm(x, f32(w[prefix + "/w_gate"]), precision))[..., None]
    return c.mm(o.reshape(b, n, heads * dv), f32(w[prefix + "/w_o"]), precision)


def logits(w: dict, ids, cfg: dict, precision: str = "float32", last=None):
    """Logits (B, last, V) of the last ``last`` positions (default all) of a
    full causal forward."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = f32(w["params/embedding"][ids])
        for i, kind in enumerate(cfg["layer_types"]):
            layer = f"params/layer_{i}"
            h = rms_norm(x, w[layer + "/attn_norm/scale"], eps)
            if kind == "kda":
                x = x + kda(h, w, layer + "/mixer", cfg, precision)
            else:
                x = x + latent_attention(h, w, layer + "/attn", cfg, precision)
            h = rms_norm(x, w[layer + "/ffn_norm/scale"], eps)
            if i < cfg["first_k_dense_replace"]:
                x = x + swiglu(h, w[layer + "/ffn/w1"], w[layer + "/ffn/w3"], w[layer + "/ffn/w2"], precision)
            else:
                x = x + experts(h, w, layer + "/ffn", cfg, precision)
        if last is not None:
            x = x[:, -last:]
        return c.mm(rms_norm(x, w["params/out_norm/scale"], eps), f32(w["params/head"]), precision)
