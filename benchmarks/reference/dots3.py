"""dots3-note-prev's language model (``dots-studio/dots3-note-prev``,
``config.json``; the catalog row's ``config`` is the description), plain: the
index scores formed whole for a block of queries, ``lax.top_k`` of them, the
softmax masked to the chosen keys; the window as a mask; the experts one at a
time on every token; no cache, no kernel, no absorbed form, float32 at
``precision="float32"`` under ``jax.default_matmul_precision("highest")``.
Imports nothing of the program.

Token embedding; pre-norm layers ``x = x + Attn_l(RMSNorm(x))``, ``x = x +
FFN_l(RMSNorm(x))``; final RMSNorm; an untied head. Layer ``l``'s attention is
what ``layer_types[l]`` says.

**A ``"full_attention"`` layer**: DeepSeek-V3's latent attention (``q_lora_rank``,
``kv_lora_rank``, ``num_attention_heads`` heads of ``qk_nope_head_dim`` +
``qk_rope_head_dim`` query-key and ``v_head_dim`` value channels, plain rotary at
``rope_theta``, adjacent channels paired) under DeepSeek sparse attention
(DeepSeek-V3.2's ``index_n_heads``, ``index_head_dim``, ``index_topk``). With
``x_t`` the normed hidden state, ``r = sqrt(hidden / rank)`` where
``mla_scale_*_lora`` and 1 elsewhere::

    c^Q_t = RMSNorm(x_t W_dq) r_q          [c^KV_t; k^R_t] = x_t W_dkv,  c^KV_t = RMSNorm(c^KV_t) r_kv
    q_{t,i} = [c^Q_t W_uq]_i = [q^N; rope(q^R)]     k_{s,i} = [(c^KV_s W_ukv)^N_i; rope(k^R_s)]     v_{s,i} = (c^KV_s W_ukv)^V_i
    q^I_{t,j} = rope_I(c^Q_t W^I_q)_j            j = 1..index_n_heads, index_head_dim channels
    k^I_t     = rope_I(LayerNorm(x_t W^I_k))     eps 1e-6, scale and bias
    w_t       = (x_t W^I_w) * index_n_heads^-0.5 * index_head_dim^-0.5
    I_{t,s}   = sum_j w_{t,j} * relu(q^I_{t,j} . k^I_s)        s <= t
    S_t       = the min(t + 1, index_topk) keys s <= t with the largest I_{t,s}
    o_{t,i}   = sum_{s in S_t} softmax_{s in S_t}(q_{t,i} . k_{s,i} * (nope + rope)^-0.5) v_{s,i}
    u_t       = W_o [ sigmoid(x_t W_g)_i * o_{t,i} ]_i

``rope_I`` turns the first ``qk_rope_head_dim`` channels of an indexer's query or
key in the half-split pairing (channel ``i`` with ``i + rope / 2``) at the
layer's frequencies. ``lax.top_k`` gives a tie at the threshold to the lower
position.

**A ``"sliding_attention"`` layer**: the same latent attention at the ``swa_*``
sizes and ``swa_rope_theta``, with no indexer, its causal mask keeping the last
``sliding_window_size`` positions: ``t - sliding_window_size < s <= t``.

**Feed-forward**: the first ``first_k_dense_replace`` layers a dense SwiGLU, the
others ``reference/deepseek_v3.py``'s expert layer as it stands (sigmoid scores
in float32, chosen on ``s + b``, here in one group, weights the unbiased scores
renormalised times ``routed_scaling_factor``, a shared expert), **one chip's
share**: only the experts ``held_experts_start`` to ``+ n_held_experts`` exist
here; a pair routed to an expert held elsewhere adds nothing, and that partial
result goes on to the next layer, as in the program. The vocabulary is the slice
the weights hold.

Departures from the equations above: none in the arithmetic. The attention of
a layer runs over **blocks of queries** (``INDEX_QUERY_BLOCK`` at a time for the
selection, which is kept as a row's (N, N) mask; ``QUERY_BLOCK`` queries of
``HEAD_BLOCK`` heads at a time for the attention, their gated values carried
through their rows of ``W_o`` and summed over the head blocks; each against
every key, under ``lax.map``; the last block is padded with queries that are
thrown away), so that 33 023 positions fit a chip beside 8 GB of weights: a
block's scores are formed whole, nothing is carried from block to block. The
feed-forwards run over blocks of ``TOKEN_BLOCK`` tokens, an expert at a time on
every token of the block. ``wrong=`` plants one fault of
the mechanism, for the controls (``WRONG``). ``precision`` reaches the matrix
products (``common.mm`` / ``common.einsum``; the indexer's two products too);
the norms, the sigmoids, the relu and head sum, the selection and the softmax
are float32 whatever it says. What the published ``config.json`` has no key
for, or a key that reads two ways (the rescale, the gate's input, the indexer's
rotary, the window's convention), is the configuration file's ``assumed``;
program and reference share every one.

Weights arrive as a flat ``{"params/.../w_dq": array}`` dict under the program's
parameter names, in whatever dtype they are stored in; each is widened to
float32 where it is used."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import common as c
from .deepseek_v3 import experts, f32, rms_norm, swiglu

QUERY_BLOCK = 256  # queries whose attention scores are whole at once, HEAD_BLOCK heads of them
HEAD_BLOCK = 8
INDEX_QUERY_BLOCK = 64  # queries whose index scores are whole at once, every head of them
TOKEN_BLOCK = 8192  # tokens a feed-forward takes at once
INDEX_NORM_EPS = 1e-6
# the wrong models of the controls: each is the model above with one thing changed
WRONG = ("recent_keys", "every_key", "index_keys_unrotated", "window_one_short", "no_gate")


def inv_freq(rope: int, theta: float) -> np.ndarray:
    return (1.0 / (theta ** (np.arange(0, rope, 2, dtype=np.float64) / rope))).astype(np.float32)


def _angles(t, pos, freq):
    angles = pos.astype(jnp.float32)[:, None] * jnp.asarray(freq)[None, :]  # (N, R/2)
    return angles[:, None, :] if t.ndim == 3 else angles


def rotate_pairs(t, pos, freq):
    """``t`` (N, [H,] R): adjacent channels ``(2i, 2i + 1)`` are one complex number times ``exp(i pos freq_i)``."""
    a = _angles(t, pos, freq)
    re, im = t[..., 0::2], t[..., 1::2]
    return jnp.stack([re * jnp.cos(a) - im * jnp.sin(a), re * jnp.sin(a) + im * jnp.cos(a)], axis=-1).reshape(t.shape)


def rotate_half_split(t, pos, freq):
    """``t`` (N, [J,] R): channel ``i`` of the first half and channel ``i`` of the second are one complex number times ``exp(i pos freq_i)``."""
    a = _angles(t, pos, freq)
    half = t.shape[-1] // 2
    re, im = t[..., :half], t[..., half:]
    return jnp.concatenate([re * jnp.cos(a) - im * jnp.sin(a), re * jnp.sin(a) + im * jnp.cos(a)], axis=-1)


def sizes(cfg: dict, kind: str) -> dict:
    """The latent attention's sizes of a layer of ``kind``."""
    pre = "swa_" if kind == "sliding_attention" else ""
    keys = ("q_lora_rank", "kv_lora_rank", "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta")
    return {key: cfg[pre + key] for key in keys}


def in_blocks(fn, n: int, block: int, *per_query, whole: int = 0):
    """``fn(positions (Q,), *blocks)`` over blocks of ``block`` queries of ``per_query`` arrays (N, ...), joined again
    to (N, ...): the last block is padded with copies of position 0, which are thrown away. ``whole`` keeps the padded
    rows, as many as make whole multiples of it (a result of a gigabyte is then not copied once more to lose them; its
    rows are read a block at a time)."""
    blocks = -(-n // block) if not whole else -(-n // whole) * (whole // block)
    padded = blocks * block
    pos = jnp.where(jnp.arange(padded) < n, jnp.arange(padded), 0).reshape(blocks, block)
    cut = [jnp.pad(a, ((0, padded - n),) + ((0, 0),) * (a.ndim - 1)).reshape(blocks, block, *a.shape[1:]) for a in per_query]
    out = lax.map(lambda args: fn(*args), (pos, *cut))
    out = out.reshape(padded, *out.shape[2:])
    return out if whole else out[:n]


def selection(x, c_q, w: dict, prefix: str, cfg: dict, precision: str, wrong):
    """One row's selection (N up to whole blocks of ``QUERY_BLOCK``, N) bool, ``keep[t, s]`` where query ``t`` attends
    to key ``s`` (rows past ``N`` are padding): ``I`` whole for a block of queries, ``lax.top_k`` of it, the chosen keys as a mask."""
    n = x.shape[0]
    heads, d, topk, rope = cfg["index_n_heads"], cfg["index_head_dim"], min(cfg["index_topk"], n), cfg["qk_rope_head_dim"]
    pos, freq = jnp.arange(n), inv_freq(rope, cfg["rope_theta"])

    def index_rope(t):
        return jnp.concatenate([rotate_half_split(t[..., :rope], pos, freq), t[..., rope:]], axis=-1)

    q = index_rope(c.mm(c_q, f32(w[prefix + "/w_iq"]), precision).reshape(n, heads, d))
    k = c.mm(x, f32(w[prefix + "/w_ik"]), precision)
    mean = k.mean(-1, keepdims=True)
    k = (k - mean) / jnp.sqrt(((k - mean) ** 2).mean(-1, keepdims=True) + INDEX_NORM_EPS)
    k = k * f32(w[prefix + "/index_k_norm/scale"]) + f32(w[prefix + "/index_k_norm/bias"])
    if wrong != "index_keys_unrotated":
        k = index_rope(k)
    weights = c.mm(x, f32(w[prefix + "/w_iw"]), precision) * (heads ** -0.5 * d ** -0.5)

    def keep(at, q_b, w_b):
        causal = pos[None, :] <= at[:, None]
        if wrong == "every_key":
            return causal
        if wrong == "recent_keys":
            return causal & (pos[None, :] > at[:, None] - cfg["index_topk"])
        scores = jnp.sum(w_b[:, :, None] * jax.nn.relu(c.einsum("qjd,sd->qjs", q_b, k, precision)), axis=1)
        _, chosen = lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
        picked = jnp.zeros((at.shape[0], n), jnp.bool_).at[jnp.arange(at.shape[0])[:, None], chosen].set(True)
        return picked & causal  # a query with fewer than ``topk`` keys before it keeps them all

    return in_blocks(keep, n, INDEX_QUERY_BLOCK, q, weights, whole=QUERY_BLOCK)


def latent_attention(x, w: dict, prefix: str, cfg: dict, kind: str, precision: str, wrong=None):
    """The layer over one row ``x`` (N, h)."""
    n = x.shape[0]
    s = sizes(cfg, kind)
    heads, nope, rope, dv = s["num_attention_heads"], s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    rank, q_rank, eps, hidden = s["kv_lora_rank"], s["q_lora_rank"], cfg["rms_norm_eps"], cfg["hidden_size"]
    pos, freq = jnp.arange(n), inv_freq(rope, s["rope_theta"])
    c_q = rms_norm(c.mm(x, f32(w[prefix + "/w_dq"]), precision), w[prefix + "/q_norm/scale"], eps)
    if cfg["mla_scale_q_lora"]:
        c_q = c_q * (hidden / q_rank) ** 0.5
    kv = c.mm(x, f32(w[prefix + "/w_dkv"]), precision)
    c_kv = rms_norm(kv[..., :rank], w[prefix + "/kv_norm/scale"], eps)
    if cfg["mla_scale_kv_lora"]:
        c_kv = c_kv * (hidden / rank) ** 0.5
    k_rope = rotate_pairs(kv[..., rank:], pos, freq)

    if kind == "sliding_attention":  # the window as a mask, formed for the block's queries
        window = cfg["sliding_window_size"] - (1 if wrong == "window_one_short" else 0)
        keep = lambda at: (pos[None, :] <= at[:, None]) & (pos[None, :] > at[:, None] - window)  # noqa: E731
    elif cfg.get("index_topk"):  # the selection, formed once a row and read a block of queries at a time
        chosen = selection(x, c_q, w, prefix, cfg, precision, wrong)
        keep = lambda at: lax.dynamic_slice_in_dim(chosen, at[0], at.shape[0], axis=0)  # noqa: E731  (a block's rows lie together)
    else:
        keep = lambda at: pos[None, :] <= at[:, None]  # noqa: E731

    # a block of heads at a time, one block after the other (``lax.scan``: a row's queries, keys and values of every head
    # are gigabytes, and blocks the compiler were free to overlap would be held side by side)
    g = max(d for d in range(1, min(heads, HEAD_BLOCK) + 1) if heads % d == 0)
    blocks = heads // g
    w_uq = jnp.moveaxis(f32(w[prefix + "/w_uq"]).reshape(q_rank, blocks, g * (nope + rope)), 1, 0)
    w_ukv = jnp.moveaxis(f32(w[prefix + "/w_ukv"]).reshape(rank, blocks, g * (nope + dv)), 1, 0)
    w_o = f32(w[prefix + "/w_o"]).reshape(blocks, g * dv, hidden)
    gated = cfg["mla_head_gate"] and wrong != "no_gate"
    gate = jax.nn.sigmoid(c.mm(x, f32(w[prefix + "/w_gate"]), precision)) if gated else jnp.ones((n, heads), jnp.float32)
    gate = jnp.moveaxis(gate.reshape(n, blocks, g), 1, 0)

    def head_block(out, of):
        w_uq_b, w_ukv_b, w_o_b, gate_b = of
        q = c.mm(c_q, w_uq_b, precision).reshape(n, g, nope + rope)
        up = c.mm(c_kv, w_ukv_b, precision).reshape(n, g, nope + dv)
        k_nope, v = up[..., :nope], up[..., nope:]

        def attend(at, qn, qr):
            scores = c.einsum("ihc,jhc->hij", qn, k_nope, precision) + c.einsum("ihc,jc->hij", qr, k_rope, precision)
            probs = jax.nn.softmax(jnp.where(keep(at)[None], scores * (nope + rope) ** -0.5, -jnp.inf), axis=-1)
            return c.einsum("hij,jhc->ihc", probs, v, precision)

        o = in_blocks(attend, n, QUERY_BLOCK, q[..., :nope], rotate_pairs(q[..., nope:], pos, freq)) * gate_b[..., None]
        return out + c.mm(o.reshape(n, g * dv), w_o_b, precision), None  # these heads' rows of W_o

    return lax.scan(head_block, jnp.zeros((n, hidden), jnp.float32), (w_uq, w_ukv, w_o, gate))[0]


def in_token_blocks(fn, x):
    """``fn`` over blocks of ``TOKEN_BLOCK`` tokens of ``x`` (T, h), one block after the other, joined again: a
    feed-forward is a function of one token, and 33 023 tokens' float32 intermediates of every expert at once do not fit."""
    t = x.shape[0]
    blocks = -(-t // TOKEN_BLOCK)
    padded = jnp.pad(x, ((0, blocks * TOKEN_BLOCK - t), (0, 0))).reshape(blocks, TOKEN_BLOCK, x.shape[1])
    return lax.map(fn, padded).reshape(blocks * TOKEN_BLOCK, x.shape[1])[:t]


def logits(w: dict, ids, cfg: dict, precision: str = "float32", last=None, wrong=None):
    """Logits (B, last, V) of the last ``last`` positions (default all) of a full causal forward. ``wrong`` (one of
    ``WRONG``) plants a fault: the selection replaced by the most recent ``index_topk`` keys, or by every key; the
    indexer's keys left unrotated; the window one position short; the head-wise gate left out."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"wrong={wrong!r}: one of {WRONG}")
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = f32(w["params/embedding"][ids])
        for i, kind in enumerate(cfg["layer_types"]):
            layer = f"params/layer_{i}"
            h = rms_norm(x, w[layer + "/attn_norm/scale"], eps)
            a = [latent_attention(h[row], w, layer + "/attn", cfg, kind, precision, wrong) for row in range(h.shape[0])]
            x = x + jnp.stack(a)
            h = rms_norm(x, w[layer + "/ffn_norm/scale"], eps).reshape(-1, x.shape[-1])
            if i < cfg["first_k_dense_replace"]:
                ffn = lambda t, layer=layer: swiglu(t, f32(w[layer + "/ffn/w1"]), f32(w[layer + "/ffn/w3"]), f32(w[layer + "/ffn/w2"]), precision)  # noqa: E731
            else:
                ffn = lambda t, layer=layer: experts(t, w, layer + "/ffn", cfg, precision)  # noqa: E731
            x = x + in_token_blocks(ffn, h).reshape(x.shape)
        if last is not None:
            x = x[:, -last:]
        return c.mm(rms_norm(x, w["params/out_norm/scale"], eps), f32(w["params/head"]), precision)
