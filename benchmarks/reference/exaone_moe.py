"""K-EXAONE's language model (``LGAI-EXAONE/K-EXAONE-236B-A23B``,
``config.json``, ``model_type`` ``exaone_moe``) with its multi-token-prediction
module, plain: no kernels, no cache, no batching tricks, float32 at
``precision="float32"``. Imports nothing of the program; the expert layer (the
sigmoid rule over a share of the experts, with the shared expert) is
``deepseek_v3.py``'s and the small pieces (RMSNorm, half-split rotary) are
``mellum.py``'s, the two references this configuration's layers are made of.

Token embedding; blocks ``h = x + Attn_l(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``
(eps ``rms_norm_eps``, no biases); final RMSNorm; untied head.

- ``Attn_l``: ``q = x W_q`` (``num_attention_heads`` x ``head_dim``), ``k = x W_k``,
  ``v = x W_v`` (``num_key_value_heads`` x ``head_dim``); an RMSNorm over the
  ``head_dim`` channels of every head of q and of k (one learned scale each a
  layer); on a ``sliding_attention`` layer half-split rotary on q and k, theta
  ``rope_theta``, plain frequencies, and on a ``full_attention`` layer no
  rotary at all; scores ``q k^T / sqrt(head_dim)``; query head i reads
  key-value head ``i // group``; position i sees ``j <= i`` and, on a window
  layer, ``j > i - sliding_window``; output ``W_o``.
- ``FFN_l``: layers before ``first_k_dense_replace`` a SwiGLU of the dense
  width; from there on ``s = sigmoid(h W_g)`` in float32 whatever the
  precision, the ``num_experts_per_tok`` largest of ``s + bias`` chosen (one
  group: no group limit), ``w = s_chosen / sum(s_chosen) * routed_scaling_factor``,
  the held experts' part ``sum_e w_e W2_e (silu(W1_e h) * W3_e h)`` plus one
  shared SwiGLU.
- the module (:func:`mtp_logits`), DeepSeek-V3's form (arXiv:2412.19437 section
  2.2, whose key names the config copies): ``u_i = W_eh [RMSNorm_e(Emb(t_{i+1}));
  RMSNorm_h(h_i)]`` with ``h_i`` the last block's output at position i (before
  the final norm), one block of ``mtp_layer_types[0]`` with a sparse
  feed-forward over ``u``, the module's own final RMSNorm, the model's
  embedding and head: logits for ``t_{i+2}``.

Departures from the published description, each an assumption the
configuration file lists under ``assumed`` (the config has no key for them):
the norms sit in front of the branches (pre-norm, the program's block), where
EXAONE 4.0 puts them on the branch outputs (``Exaone4DecoderLayer`` of
transformers 4.57.6; the ``exaone_moe`` release's own modeling file was not at
hand, so this one is unread, and :func:`block` is where it would move); the
q/k norm and "no rotary on full layers" as ``Exaone4Attention.forward`` of that
file has them; the module's form and its sparse feed-forward; ``h_i`` taken
before the final norm. What the absent
experts of the other seven chips would add is left out, as in the program; the
vocabulary is the held slice.

Weights arrive as a flat ``{"params/.../w_q": array}`` dict under the
program's parameter names, in whatever dtype they are stored in."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common as c
from .deepseek_v3 import experts, swiglu
from .mellum import f32, rms_norm, rotate


def attention(x, w: dict, prefix: str, cfg: dict, layer_type: str, precision: str):
    b, n, _ = x.shape
    heads, kv_heads, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    q = rms_norm(c.mm(x, w[prefix + "/w_q"], precision).reshape(b, n, heads, d), w[prefix + "/q_norm/scale"], eps)
    k = rms_norm(c.mm(x, w[prefix + "/w_k"], precision).reshape(b, n, kv_heads, d), w[prefix + "/k_norm/scale"], eps)
    v = c.mm(x, w[prefix + "/w_v"], precision).reshape(b, n, kv_heads, d)
    pos = jnp.arange(n)
    visible = pos[None, :] <= pos[:, None]
    if layer_type == "sliding_attention":  # only these layers carry position
        inv_freq = (1.0 / (cfg["rope_theta"] ** (np.arange(0, d, 2, dtype=np.float64) / d))).astype(np.float32)
        q, k = rotate(q, pos, inv_freq, 1.0), rotate(k, pos, inv_freq, 1.0)
        visible &= pos[None, :] > pos[:, None] - cfg["sliding_window"]
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)  # query head i reads key-value head i // group
    scores = c.einsum("bihc,bjhc->bhij", q, k, precision) / math.sqrt(d)
    probs = jax.nn.softmax(jnp.where(visible[None, None], scores, -jnp.inf), axis=-1)
    o = c.einsum("bhij,bjhc->bihc", probs, v, precision).reshape(b, n, heads * d)
    return c.mm(o, w[prefix + "/w_o"], precision)


def block(x, w: dict, prefix: str, cfg: dict, layer_type: str, sparse: bool, precision: str):
    eps = cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, w[prefix + "/attn_norm/scale"], eps), w, prefix + "/attn", cfg, layer_type, precision)
    h = rms_norm(x, w[prefix + "/ffn_norm/scale"], eps)
    if sparse:
        return x + experts(h, w, prefix + "/ffn", cfg, precision)
    return x + swiglu(h, w[prefix + "/ffn/w1"], w[prefix + "/ffn/w3"], w[prefix + "/ffn/w2"], precision)


def hidden(w: dict, ids, cfg: dict, precision: str):
    """The last block's output (B, N, h), before the final norm."""
    x = f32(w["params/embedding"][ids])
    for i, layer_type in enumerate(cfg["layer_types"]):
        x = block(x, w, f"params/layer_{i}", cfg, layer_type, i >= cfg["first_k_dense_replace"], precision)
    return x


def _head(x, w: dict, norm: str, cfg: dict, precision: str, last):
    if last is not None:
        x = x[:, -last:]
    return c.mm(rms_norm(x, w[norm], cfg["rms_norm_eps"]), w["params/head"], precision)


def logits(w: dict, ids, cfg: dict, precision: str = "float32", last=None):
    """Logits (B, last, V) of the last ``last`` positions (default all) of a full causal forward of the main model."""
    with jax.default_matmul_precision("highest"):
        return _head(hidden(w, ids, cfg, precision), w, "params/out_norm/scale", cfg, precision, last)


def mtp_logits(w: dict, ids, cfg: dict, precision: str = "float32", last=None):
    """The module's logits (B, N - 1, V), or their last ``last`` positions:
    at position i, from ``h_i`` and token ``i + 1``, its prediction of token
    ``i + 2``. The last position of ``ids`` has no token after it and gets none."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        h = hidden(w, ids, cfg, precision)[:, :-1]
        e = f32(w["params/embedding"][ids[:, 1:]])
        both = jnp.concatenate([rms_norm(e, w["params/mtp/embed_norm/scale"], eps),
                                rms_norm(h, w["params/mtp/hidden_norm/scale"], eps)], axis=-1)
        u = block(c.mm(both, w["params/mtp/w_eh"], precision), w, "params/mtp/block", cfg, cfg["mtp_layer_types"][0], True, precision)
        return _head(u, w, "params/mtp/out_norm/scale", cfg, precision, last)
