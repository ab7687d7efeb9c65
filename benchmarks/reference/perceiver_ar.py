"""Perceiver AR causal language model (arXiv:2202.07765), plain float32.

Token plus learned absolute position embedding; one causal cross-attention
of the last ``max_latents`` positions over ``[kept prefix; latents]`` with
rotary features on half of each head's channels; a causal self-attention
stack over the latents (rotary on the first ``num_self_attention_rotary_layers``
layers); logits tied to the token embedding plus a bias. Pre-LayerNorm,
exact GELU, no biases on q/k/v or in the MLPs; the cross-attention's output
projection has one, the self-attention's has none.

Departures from the paper: the prefix-dropout keep set is an input (the
rows the caller kept, sorted), so the reference and the program drop the
same positions. Weights arrive as a flat ``{"params/.../kernel": array}``
dict under the program's parameter names."""

from __future__ import annotations

import jax.numpy as jnp

from . import common as c

ROOT = "params/perceiver_ar"


def hidden(w: dict, ids, keep_idx, cfg: dict, precision: str = "float32", latents=None):
    """Final latent states (B, L, C) for ``ids`` (B, N): the last ``latents``
    positions (default ``min(N, max_latents)``) are latents, the rest the
    prefix, of which ``keep_idx`` (B, K; ``None`` keeps all) survives."""
    b, n = ids.shape
    lat = min(n, cfg["max_latents"]) if latents is None else latents
    prefix_len = n - lat
    heads = cfg["num_heads"]
    rotated = cfg["num_channels"] // heads // 2
    x = w["params/input_adapter/txt_embedding/embedding"][ids]
    x = x + w["params/input_adapter/pos_embedding/embedding"][:n][None]
    pos = jnp.broadcast_to(jnp.arange(n)[None], (b, n))
    x_lat, pos_lat = x[:, prefix_len:], pos[:, prefix_len:]
    x_pre, pos_pre = x[:, :prefix_len], pos[:, :prefix_len]
    if keep_idx is not None:
        x_pre = jnp.take_along_axis(x_pre, keep_idx[..., None], axis=1)
        pos_pre = keep_idx
    ang_lat = c.rotary_features(pos_lat, rotated)
    ang_kv = jnp.concatenate([c.rotary_features(pos_pre, rotated), ang_lat], axis=1)

    ca = ROOT + "/cross_attention"
    q_in = c.layer_norm(x_lat, w, ca + "/cross_attn/q_norm")
    kv_in = jnp.concatenate([c.layer_norm(x_pre, w, ca + "/cross_attn/kv_norm"), q_in], axis=1)
    h = x_lat + c.attention(q_in, kv_in, w, ca + "/cross_attn/attention", heads, precision,
                            causal=True, angles_q=ang_lat, angles_k=ang_kv)
    h = h + c.mlp(h, w, ca + "/mlp", precision)
    for i in range(cfg["num_self_attention_layers"]):
        layer = f"{ROOT}/self_attention/layer_{i}"
        ang = ang_lat if i < cfg["num_self_attention_rotary_layers"] else None
        x_n = c.layer_norm(h, w, layer + "/self_attn/norm")
        h = h + c.attention(x_n, x_n, w, layer + "/self_attn/attention", heads, precision,
                            causal=True, angles_q=ang, angles_k=ang)
        h = h + c.mlp(h, w, layer + "/mlp", precision)
    return h


def logits(w: dict, ids, keep_idx, cfg: dict, precision: str = "float32", latents=None):
    h = hidden(w, ids, keep_idx, cfg, precision, latents)
    table = w["params/input_adapter/txt_embedding/embedding"]
    return c.mm(h, table.T, precision) + w["params/output_adapter/bias"]


def loss(w: dict, batch: dict, cfg: dict, precision: str = "float32"):
    """Mean next-token cross-entropy over the latent positions.
    ``batch``: ``input_ids`` (B, N), ``labels`` (B, N) already shifted,
    ``prefix_keep_idx`` (B, K) or absent."""
    out = logits(w, batch["input_ids"], batch.get("prefix_keep_idx"), cfg, precision)
    return c.cross_entropy(out, batch["labels"][:, -out.shape[1]:])
