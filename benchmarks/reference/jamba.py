"""AI21's Jamba2-3B (``ai21labs/AI21-Jamba2-3B``, ``config.json``, ``model_type``
``jamba``: the hybrid stack of arXiv:2403.19887 with the Mamba-1 layer of
arXiv:2312.00752), plain: no kernels, no cache, no state handed on, float32 at
``precision="float32"``. Imports nothing of the program.

Token embedding ``E``; 28 pre-norm layers ``x = x + mixer_l(RMSNorm(x))``,
``x = x + W_down(silu(W_gate u) * W_up u)`` with ``u = RMSNorm(x)`` (every
feed-forward dense: ``num_experts`` 1); final RMSNorm; logits ``h E^T`` (tied).
No positional encoding of any kind.

- Layer ``i`` is an attention layer where ``i % attn_layer_period ==
  attn_layer_offset``, else a Mamba layer.
- Attention: ``q = x W_q`` (``num_attention_heads`` x ``head_dim``), ``k = x W_k``,
  ``v = x W_v`` (``num_key_value_heads`` x ``head_dim``), no bias, no rotary;
  scores ``q k^T / sqrt(head_dim)``, query head ``i`` reads key-value head
  ``i // group``, position ``i`` sees ``j <= i`` (a masked softmax); output ``W_o``.
- Mamba mixer (``d = mamba_expand * hidden_size``, ``N = mamba_d_state``,
  ``R = mamba_dt_rank``, ``K = mamba_d_conv``), per row::

      [x_t ; z_t] = W_in u_t
      x_t = silu(sum_{j<K} w_conv[j] * x_{t-K+1+j} + b_conv)    K shifted sums, zeros before the row
      [dt_t ; B_t ; C_t] = W_x x_t,  each through its RMSNorm
      D_t = softplus(W_dt dt_t + b_dt)
      h_t = exp(D_t * A) * h_{t-1} + (D_t * x_t) * B_t          a plain lax.scan over tokens, h_0 = 0
      y_t = sum_n h_t[n] * C_t[n] + d_skip * x_t
      out_t = W_out (y_t * silu(z_t))

Departures from the published code, each one of storage and none of the
mathematics: ``a_log`` (N, d) and ``conv_w`` (K, d) are the transposes of the
published ``A_log`` (d, N) and ``conv1d.weight`` (d, 1, K), as the program
stores them; the published fast path fuses ``exp(D A)`` and the scan in one
CUDA kernel, here they are separate float32 operations; logits are computed
for the last ``last`` positions only. ``precision`` reaches the matrix products
(``common.mm`` / ``common.einsum``): the convolution, the norms, the step
size's softplus and the recurrence are float32 elementwise whatever it says.

Weights arrive as a flat ``{"params/.../w_in": array}`` dict under the
program's parameter names, in whatever dtype they are stored in; each is
widened to float32 where it is used."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from . import common as c


def f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, scale, eps: float):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * f32(scale)


def layer_kinds(cfg: dict) -> tuple:
    """``"attention"`` or ``"mamba"`` for each layer, from the period and the offset."""
    return tuple("attention" if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"] else "mamba"
                 for i in range(cfg["num_hidden_layers"]))


def attention(x, w: dict, prefix: str, cfg: dict, precision: str):
    b, n, _ = x.shape
    heads, kv_heads, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = c.mm(x, f32(w[prefix + "/w_q"]), precision).reshape(b, n, heads, d)
    k = c.mm(x, f32(w[prefix + "/w_k"]), precision).reshape(b, n, kv_heads, d)
    v = c.mm(x, f32(w[prefix + "/w_v"]), precision).reshape(b, n, kv_heads, d)
    k, v = jnp.repeat(k, heads // kv_heads, axis=2), jnp.repeat(v, heads // kv_heads, axis=2)
    scores = c.einsum("bihc,bjhc->bhij", q, k, precision) / math.sqrt(d)
    visible = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    probs = jax.nn.softmax(jnp.where(visible[None, None], scores, -jnp.inf), axis=-1)
    o = c.einsum("bhij,bjhc->bihc", probs, v, precision).reshape(b, n, heads * d)
    return c.mm(o, f32(w[prefix + "/w_o"]), precision)


def mamba(x, w: dict, prefix: str, cfg: dict, precision: str, break_carry_at=None):
    """The mixer over whole rows ``x`` (B, T, h). ``break_carry_at`` (a token
    index) zeroes the state before that token: the wrong layer of the
    "a dropped carry shows" test, never the reference."""
    b, t, _ = x.shape
    d, n, r, k = cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    eps = cfg["rms_norm_eps"]
    xz = c.mm(x, f32(w[prefix + "/w_in"]), precision)
    x_in, z = xz[..., :d], xz[..., d:]
    padded = jnp.pad(x_in, ((0, 0), (k - 1, 0), (0, 0)))
    conv_w = f32(w[prefix + "/conv_w"])
    conv = sum(conv_w[j] * padded[:, j:j + t] for j in range(k)) + f32(w[prefix + "/conv_b"])
    xc = jax.nn.silu(conv)
    sel = c.mm(xc, f32(w[prefix + "/w_x"]), precision)
    dt = rms_norm(sel[..., :r], w[prefix + "/dt_norm/scale"], eps)
    bb = rms_norm(sel[..., r:r + n], w[prefix + "/b_norm/scale"], eps)
    cc = rms_norm(sel[..., r + n:], w[prefix + "/c_norm/scale"], eps)
    delta = jax.nn.softplus(c.mm(dt, f32(w[prefix + "/w_dt"]), precision) + f32(w[prefix + "/dt_bias"]))
    a = -jnp.exp(f32(w[prefix + "/a_log"]))  # (N, d)

    def token(h, at):
        i, x_t, d_t, b_t, c_t = at
        if break_carry_at is not None:
            h = jnp.where(i == break_carry_at, 0.0, h)
        h = jnp.exp(d_t[:, None, :] * a[None]) * h + (d_t * x_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    over_time = tuple(jnp.swapaxes(v, 0, 1) for v in (xc, delta, bb, cc))
    _, y = lax.scan(token, jnp.zeros((b, n, d), jnp.float32), (jnp.arange(t),) + over_time)
    y = jnp.swapaxes(y, 0, 1) + f32(w[prefix + "/d_skip"]) * xc
    return c.mm(y * jax.nn.silu(z), f32(w[prefix + "/w_out"]), precision)


def swiglu(x, w: dict, prefix: str, precision: str):
    gate = jax.nn.silu(c.mm(x, f32(w[prefix + "/w1"]), precision)) * c.mm(x, f32(w[prefix + "/w3"]), precision)
    return c.mm(gate, f32(w[prefix + "/w2"]), precision)


def logits(w: dict, ids, cfg: dict, precision: str = "float32", last=None, mamba_fn=mamba):
    """Logits (B, last, V) of the last ``last`` positions (default all) of a
    full causal forward. ``mamba_fn`` takes a wrong mixer for the tests."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        table = w["params/embedding"]
        x = f32(table[ids])
        for i, kind in enumerate(layer_kinds(cfg)):
            layer = f"params/layer_{i}"
            u = rms_norm(x, w[layer + "/attn_norm/scale"], eps)
            if kind == "attention":
                x = x + attention(u, w, layer + "/attn", cfg, precision)
            else:
                x = x + mamba_fn(u, w, layer + "/mixer", cfg, precision)
            x = x + swiglu(rms_norm(x, w[layer + "/ffn_norm/scale"], eps), w, layer + "/ffn", precision)
        if last is not None:
            x = x[:, -last:]
        return c.mm(rms_norm(x, w["params/out_norm/scale"], eps), f32(table).T, precision)
