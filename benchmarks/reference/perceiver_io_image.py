"""Perceiver IO image classifier with 2D Fourier features
(arXiv:2107.14795, App. A; deepmind/vision-perceiver-fourier), plain float32.

Pixels are flattened and concatenated with Fourier position features
(raw positions, then sines, then cosines, ``num_frequency_bands`` per
axis, frequencies linear from 1 to half the axis length); a learned latent
array cross-attends to them with one head whose q/k/v width is the input
width; ``num_self_attention_blocks`` passes through ONE block of
``num_self_attention_layers_per_block`` self-attention layers (weights
shared across passes); one learned output query cross-attends to the
latents and a linear layer gives the class logits. Pre-LayerNorm, exact
GELU, biases everywhere, widening factor as configured. Weights arrive as a
flat dict under the program's parameter names."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import common as c


def fourier_features(shape, bands: int):
    """(prod(shape), len(shape) * (2 * bands + 1)) float32, computed in the
    graph: as a constant it would sit in the compiled program 52 MB large."""
    coords = [jnp.linspace(-1.0, 1.0, num=s, dtype=jnp.float32) for s in shape]
    pos = jnp.stack(jnp.meshgrid(*coords, indexing="ij"), axis=-1)
    grids = [pos[..., i:i + 1] * jnp.linspace(1.0, s / 2.0, num=bands, dtype=jnp.float32)
             for i, s in enumerate(shape)]
    enc = [pos] + [jnp.sin(math.pi * g) for g in grids] + [jnp.cos(math.pi * g) for g in grids]
    return jnp.concatenate(enc, axis=-1).reshape(-1, len(shape) * (2 * bands + 1))


def _cross_layer(x_q, x_kv, w, prefix, heads, precision):
    q_in = c.layer_norm(x_q, w, prefix + "/cross_attn/q_norm")
    kv_in = c.layer_norm(x_kv, w, prefix + "/cross_attn/kv_norm")
    h = x_q + c.attention(q_in, kv_in, w, prefix + "/cross_attn/attention", heads, precision)
    return h + c.mlp(h, w, prefix + "/mlp", precision)


def logits(w: dict, image, cfg: dict, precision: str = "float32"):
    b = image.shape[0]
    shape = tuple(cfg["image_shape"])
    enc = fourier_features(shape[:-1], cfg["num_frequency_bands"])
    x_in = jnp.concatenate([image.reshape(b, -1, shape[-1]),
                            jnp.broadcast_to(enc[None], (b,) + enc.shape)], axis=-1)
    lat = jnp.broadcast_to(w["params/encoder/latent_provider/query"][None],
                           (b, cfg["num_latents"], cfg["num_latent_channels"]))
    h = _cross_layer(lat, x_in, w, "params/encoder/cross_attn_1", cfg["num_cross_attention_heads"], precision)

    def block(h, _):
        for i in range(cfg["num_self_attention_layers_per_block"]):
            layer = f"params/encoder/self_attn_1/layer_{i}"
            x_n = c.layer_norm(h, w, layer + "/self_attn/norm")
            h = h + c.attention(x_n, x_n, w, layer + "/self_attn/attention",
                                cfg["num_self_attention_heads"], precision)
            h = h + c.mlp(h, w, layer + "/mlp", precision)
        return h, None

    # the passes share one block's weights: a scan keeps the program one pass long
    h, _ = jax.lax.scan(block, h, None, length=cfg["num_self_attention_blocks"])
    query = jnp.broadcast_to(w["params/decoder/output_query_provider/query"][None],
                             (b, 1, cfg["num_output_query_channels"]))
    out = _cross_layer(query, h, w, "params/decoder/cross_attn", cfg["decoder_num_cross_attention_heads"], precision)
    return c.dense(out, w, "params/decoder/output_adapter/linear", precision)[:, 0]


def loss(w: dict, batch: dict, cfg: dict, precision: str = "float32"):
    """Mean cross-entropy. ``batch``: ``image`` (B, H, W, C) float32,
    ``label`` (B,)."""
    return c.cross_entropy(logits(w, batch["image"], cfg, precision), batch["label"])
