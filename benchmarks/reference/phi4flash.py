"""Microsoft's Phi-4-mini-flash-reasoning (``microsoft/Phi-4-mini-flash-reasoning``,
``config.json``, ``model_type`` ``phi4flash``: SambaY, arXiv:2507.06607), plain:
no kernels, no cache, no state or memory handed on between calls, every layer
over every position, float32 at ``precision="float32"``. Imports nothing of the
program.

Token embedding ``E``; 32 pre-norm layers ``a = x + Mix_i(LN(x))``, ``x' = a +
W_down(silu(W_gate u) * W_up u)`` with ``u = LN'(a)``, LayerNorms with scale and
bias (``layer_norm_eps``), no MLP bias; a last LayerNorm; logits ``LN(x) E^T``
(tied). No positional encoding of any kind. ``Mix_i`` by the published rule
(:func:`layer_kinds`; ``mb_per_layer`` 2, the cross-decoder from
``num_hidden_layers // 2 + 2``):

- even ``i`` below the cross-decoder: a **Mamba-1** mixer (arXiv:2312.00752; ``d =
  mamba_expand * hidden_size``, ``N``, ``R``, ``K`` = ``mamba_d_state``,
  ``mamba_dt_rank``, ``mamba_d_conv``), per row::

      [x_t ; z_t] = W_in u_t
      x_t = silu(sum_{j<K} w_conv[j] * x_{t-K+1+j} + b_conv)    K shifted sums, zeros before the row
      [dt_t ; B_t ; C_t] = W_x x_t                              no norm on any of the three
      D_t = softplus(W_dt dt_t + b_dt)
      h_t = exp(D_t * A) * h_{t-1} + (D_t * x_t) * B_t          a plain lax.scan over tokens, h_0 = 0
      m_t = sum_n h_t[n] * C_t[n] + d_skip * x_t
      out_t = W_out (m_t * silu(z_t))

  The last of them (layer ``num_hidden_layers // 2``) hands ``m`` on, as it
  stands **before** the gate.
- even ``i`` in the cross-decoder: a **gated memory unit**, ``out_t = W_out(silu(W_in
  u_t) * m_t)`` with that ``m`` at the same position. No state, no scan.
- odd ``i``: **differential attention** (arXiv:2410.05258). ``[q ; k ; v] = W_qkv u +
  b_qkv`` (``H`` query heads, ``Hkv`` key and value heads of ``d = hidden_size / H``).
  Query head ``p`` pairs with ``p + H/2``, key head ``g`` with ``g + Hkv/2``, the
  values likewise, ``V_g = [v_g ; v_{g + Hkv/2}]``; pair ``p`` reads pair ``g = p //
  (H / Hkv)``::

      A1 = softmax(q_p k_g^T / sqrt(d) + M)     A2 = softmax(q_{p+H/2} k_{g+Hkv/2}^T / sqrt(d) + M)
      o_p = (1 - lam0_i) * RMSNorm_2d((A1 - lam_i A2) V_g)      scale (2d,), eps 1e-5
      lam_i = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0_i          lam0_i = 0.8 - 0.6 exp(-0.3 i)

  and the output is ``W_o [o_0 .. o_{H/2-1}] + b_o``. ``M`` shows position ``t`` the
  ``j`` with ``t - sliding_window < j <= t`` below layer ``num_hidden_layers // 2 + 1``
  and ``j <= t`` on that layer. An odd layer of the cross-decoder has ``q = W_q u +
  b_q`` alone and attends, in the same form under its own ``lam`` and subnorm,
  over the keys and values **that layer** projected (YOCO, arXiv:2405.05254).

Departures from the published code, each one of storage and none of the
mathematics: ``a_log`` (N, d) and ``conv_w`` (K, d) are the transposes of the
published tensors; ``w1`` / ``w3`` are the two halves of the published fused
gate-and-up projection; attention runs a block of queries at a time so that a
row of 8448 positions fits; logits are computed for the last ``last`` positions
only. ``precision`` reaches the matrix products (``common.mm`` /
``common.einsum``): the convolution, the norms, the step size's softplus, the
recurrence, the gates, the maps' difference and the subnorm are float32
whatever it says.

``wrong`` names a **wrong model** for the tests and the chip's controls, never
the reference: ``"stale_cache"`` (a cross layer at position ``t`` sees ``j < t``:
the owning layer's write of the step left out), ``"lam0"`` (``lam_i = 0``: one
softmax map), ``"memory_after_gate"`` (the memory taken after ``silu(z)``).

Weights arrive as a flat ``{"params/.../w_in": array}`` dict under the program's
parameter names, in whatever dtype they are stored in; each is widened to
float32 where it is used."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from . import common as c

Q_BLOCK = 512
SUBNORM_EPS = 1e-5
WRONG = (None, "stale_cache", "lam0", "memory_after_gate")


def f32(a):
    return a.astype(jnp.float32)


def layer_norm(x, w: dict, prefix: str, eps: float):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * f32(w[prefix + "/scale"]) + f32(w[prefix + "/bias"])


def layer_kinds(cfg: dict) -> tuple:
    """``"mamba"``, ``"window"``, ``"full"``, ``"gmu"`` or ``"cross"`` for each layer, by the ``phi4flash`` rule."""
    n, per = cfg["num_hidden_layers"], cfg["mb_per_layer"]
    owner = n // 2 + 1  # the layer whose keys and values the cross-decoder reads
    kinds = []
    for i in range(n):
        if i % per == 0:
            kinds.append("mamba" if i < owner else "gmu")
        else:
            kinds.append("window" if i < owner else "full" if i == owner else "cross")
    return tuple(kinds)


def lambda_init(i: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def mamba(x, w: dict, prefix: str, cfg: dict, precision: str, wrong=None):
    """The mixer over whole rows ``x`` (B, T, h): its output and the memory ``m`` (B, T, d) before the gate."""
    b, t, _ = x.shape
    d, n, r, k = cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    xz = c.mm(x, f32(w[prefix + "/w_in"]), precision)
    x_in, z = xz[..., :d], xz[..., d:]
    padded = jnp.pad(x_in, ((0, 0), (k - 1, 0), (0, 0)))
    conv_w = f32(w[prefix + "/conv_w"])
    xc = jax.nn.silu(sum(conv_w[j] * padded[:, j:j + t] for j in range(k)) + f32(w[prefix + "/conv_b"]))
    sel = c.mm(xc, f32(w[prefix + "/w_x"]), precision)
    dt, bb, cc = sel[..., :r], sel[..., r:r + n], sel[..., r + n:]
    delta = jax.nn.softplus(c.mm(dt, f32(w[prefix + "/w_dt"]), precision) + f32(w[prefix + "/dt_bias"]))
    a = -jnp.exp(f32(w[prefix + "/a_log"]))  # (N, d)

    def token(h, at):
        x_t, d_t, b_t, c_t = at
        h = jnp.exp(d_t[:, None, :] * a[None]) * h + (d_t * x_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    over_time = tuple(jnp.swapaxes(v, 0, 1) for v in (xc, delta, bb, cc))
    _, y = lax.scan(token, jnp.zeros((b, n, d), jnp.float32), over_time)
    m = jnp.swapaxes(y, 0, 1) + f32(w[prefix + "/d_skip"]) * xc
    gated = m * jax.nn.silu(z)
    return c.mm(gated, f32(w[prefix + "/w_out"]), precision), gated if wrong == "memory_after_gate" else m


def gmu(x, m, w: dict, prefix: str, precision: str):
    return c.mm(jax.nn.silu(c.mm(x, f32(w[prefix + "/w_in"]), precision)) * m, f32(w[prefix + "/w_out"]), precision)


def differential(q, k, v, lam, scale, i: int, window, precision: str, strict: bool = False):
    """``q`` (B, N, H, d), ``k``, ``v`` (B, N, Hkv, d) -> (B, N, H * d): the
    pairs' normed differences, a block of queries at a time. ``strict`` hides a
    position's own key (position 0 keeps it: a softmax needs one)."""
    b, n, heads, d = q.shape
    kv_heads = k.shape[2]
    group = heads // kv_heads
    pos = jnp.arange(n)
    # pair p's two queries and, repeated over the pairs that share them, its two keys and its value of 2d
    k1, k2 = jnp.repeat(k[:, :, :kv_heads // 2], group, axis=2), jnp.repeat(k[:, :, kv_heads // 2:], group, axis=2)
    vv = jnp.repeat(jnp.concatenate([v[:, :, :kv_heads // 2], v[:, :, kv_heads // 2:]], axis=-1), group, axis=2)

    def block(start):
        i_ = start + jnp.arange(Q_BLOCK)
        qb = lax.dynamic_slice_in_dim(q, start, Q_BLOCK, axis=1)
        visible = (pos[None, :] < i_[:, None]) | ((pos[None, :] == 0) & (i_[:, None] == 0)) if strict else pos[None, :] <= i_[:, None]
        if window is not None:
            visible &= pos[None, :] > i_[:, None] - window

        def attend(qh, kh):
            scores = c.einsum("bihc,bjhc->bhij", qh, kh, precision) / math.sqrt(d)
            probs = jax.nn.softmax(jnp.where(visible[None, None], scores, -jnp.inf), axis=-1)
            return c.einsum("bhij,bjhc->bihc", probs, vv, precision)

        o = attend(qb[:, :, :heads // 2], k1) - lam * attend(qb[:, :, heads // 2:], k2)  # (B, Q_BLOCK, H/2, 2d)
        return (1.0 - lambda_init(i)) * o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + SUBNORM_EPS) * scale

    # whole blocks of queries, the last one moved back so that it ends at the last position
    starts = sorted({min(s, max(n - Q_BLOCK, 0)) for s in range(0, n, Q_BLOCK)})
    if n < Q_BLOCK:
        q = jnp.pad(q, ((0, 0), (0, Q_BLOCK - n), (0, 0), (0, 0)))
    outs = lax.map(block, jnp.asarray(starts))  # (blocks, B, Q_BLOCK, H/2, 2d)
    o = jnp.zeros((b, max(n, Q_BLOCK), heads // 2, 2 * d), jnp.float32)
    for j, s in enumerate(starts):
        o = lax.dynamic_update_slice_in_dim(o, outs[j], s, axis=1)
    return o[:, :n].reshape(b, n, heads * d)


def attention(x, w: dict, prefix: str, cfg: dict, i: int, kind: str, precision: str, shared=None, wrong=None):
    """A differential attention layer over whole rows ``x`` (B, N, h): its
    output and the keys and values it attended over (a cross layer: ``shared``, the owning layer's)."""
    b, n, _ = x.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    if kind == "cross":
        q = (c.mm(x, f32(w[prefix + "/w_q"]), precision) + f32(w[prefix + "/b_q"])).reshape(b, n, heads, d)
        k, v = shared
    else:
        qkv = c.mm(x, f32(w[prefix + "/w_qkv"]), precision) + f32(w[prefix + "/b_qkv"])
        q = qkv[..., :heads * d].reshape(b, n, heads, d)
        k = qkv[..., heads * d:(heads + kv_heads) * d].reshape(b, n, kv_heads, d)
        v = qkv[..., (heads + kv_heads) * d:].reshape(b, n, kv_heads, d)
    lam = (jnp.exp(jnp.sum(f32(w[prefix + "/lambda_q1"]) * f32(w[prefix + "/lambda_k1"])))
           - jnp.exp(jnp.sum(f32(w[prefix + "/lambda_q2"]) * f32(w[prefix + "/lambda_k2"]))) + lambda_init(i))
    if wrong == "lam0":
        lam = 0.0
    o = differential(q, k, v, lam, f32(w[prefix + "/subln/scale"]), i, cfg["sliding_window"] if kind == "window" else None,
                     precision, strict=kind == "cross" and wrong == "stale_cache")
    return c.mm(o, f32(w[prefix + "/w_o"]), precision) + f32(w[prefix + "/b_o"]), (k, v)


def swiglu(x, w: dict, prefix: str, precision: str):
    gate = jax.nn.silu(c.mm(x, f32(w[prefix + "/w1"]), precision)) * c.mm(x, f32(w[prefix + "/w3"]), precision)
    return c.mm(gate, f32(w[prefix + "/w2"]), precision)


def logits(w: dict, ids, cfg: dict, precision: str = "float32", last=None, wrong=None):
    """Logits (B, last, V) of the last ``last`` positions (default all) of a
    full causal forward, all layers over all positions. ``wrong`` is one of :data:`WRONG`."""
    if wrong not in WRONG:
        raise ValueError(f"wrong {wrong!r}: one of {WRONG}")
    eps = cfg["layer_norm_eps"]
    with jax.default_matmul_precision("highest"):
        table = w["params/embedding"]
        x = f32(table[ids])
        memory = shared = None
        for i, kind in enumerate(layer_kinds(cfg)):
            layer = f"params/layer_{i}"
            u = layer_norm(x, w, layer + "/attn_norm", eps)
            if kind == "mamba":
                out, memory = mamba(u, w, layer + "/mixer", cfg, precision, wrong)  # the last Mamba layer's is what is read
            elif kind == "gmu":
                out = gmu(u, memory, w, layer + "/mixer", precision)
            else:
                out, kv = attention(u, w, layer + "/attn", cfg, i, kind, precision, shared, wrong)
                if kind == "full":
                    shared = kv
            x = x + out
            x = x + swiglu(layer_norm(x, w, layer + "/ffn_norm", eps), w, layer + "/ffn", precision)
        if last is not None:
            x = x[:, -last:]
        return c.mm(layer_norm(x, w, "params/out_norm", eps), f32(table).T, precision)
