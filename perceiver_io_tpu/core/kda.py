"""Kimi delta attention (arXiv:2510.26692, the Kimi Linear layer), in the form
the Ling 3.0 family's configuration keys describe it: a linear-attention layer
whose state has one size whatever the context, updated by the delta rule under
a decay a key channel.

With ``x`` the block's normed input, for token ``t`` and head ``h`` (``H =
num_attention_heads`` heads of ``D = head_dim`` channels on q, k and v)::

    q = l2norm(silu(conv(x W_q))) * D^-0.5      k = l2norm(silu(conv(x W_k)))      v = silu(conv(x W_v))
    g = kda_lower_bound * sigmoid(exp(A_log[h]) * (x W_f + dt_bias))     log-decay a channel, in (kda_lower_bound, 0)
    b = sigmoid(x W_b)                                                    one a head
    S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T      S: D x D a head, float32
    o_t = S_t^T q_t
    y   = (RMSNorm_head(o_t) * sigmoid(x W_g)) W_o

``conv`` is the causal depthwise convolution of ``short_conv_kernel_size`` taps
with its silu, no bias, zeros before a row's first token (``core/ssm.py``'s
`causal_conv` and its window helpers: one
convolution in the code base), a convolution each for q, k and v; ``l2norm``
and the RMSNorm are over a head's ``D`` channels. No rotary and no positional
encoding: the layer reads no position. ``W_f`` and ``W_g`` are full rank
(``no_kda_lora``). The bounded gate is the reading of ``kda_safe_gate`` and
``kda_lower_bound``: a token forgets at most ``exp(kda_lower_bound)`` a channel,
which is what lets the chunked form factor its decays over sub-chunks of 16
rows in float32 (``ops/kda.py``).

One set of weights, two ways through them, as in ``core/ssm.py``:

``expand`` (the prompt pass)
    whole rows from an empty state: projections and gates in XLA. Where the
    kernels may run (``flash_enabled()`` and a head of 128) ``ops/kda.py``'s
    chunk kernel takes the three raw projections and the tap tables and
    **shapes q, k and v itself**, on the tiles it holds, in front of the
    recurrence (convolution, silu, l2 norm, q's scale, one rounding: no XLA
    pass over them, PR 52); elsewhere ``_shape`` shapes them in XLA and the
    recurrence is a ``lax.scan`` of a token a step. Also returns what a step
    needs of the rows' past: the final ``S`` and the three windows (the raw
    projections' last ``K - 1`` rows, XLA's on both paths).

``step`` (one new token a row against the state)
    the windows shift by one row and ``_shape`` shapes the token's q, k and v
    in XLA (on every path); ``S`` is read, decayed, corrected and written
    once: where the kernels run one Pallas call over the state where it lies,
    updated in place (``ops/kda.py::kda_step``), elsewhere the same arithmetic
    in XLA (``kda_update``).

``S`` is stored transposed (``core/cache.py::DeltaState``). ``S``, ``g`` (its
projection's output, bias and sigmoid), ``b``, the norms and the output gate's
sigmoid are float32; products take ``dtype`` operands and accumulate in float32;
q, k and v are rounded to ``dtype`` once, after their norms.
"""

from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from perceiver_io_tpu.core.cache import DeltaState
from perceiver_io_tpu.core.ssm import causal_conv, rows_window, step_window, window_tail
from perceiver_io_tpu.obs import probes
from perceiver_io_tpu.ops.flash_attention import flash_enabled
from perceiver_io_tpu.ops.kda import L2_EPS as _L2_EPS
from perceiver_io_tpu.ops.kda import kda_chunked, kda_reference, kda_step, kda_supported, kda_update, sub_chunk_safe
from perceiver_io_tpu.ops.layernorm import RMSNorm


class KimiDeltaAttention(nn.Module):
    """``config`` needs ``hidden_size``, ``num_attention_heads``, ``head_dim``,
    ``short_conv_kernel_size``, ``kda_lower_bound``, ``rms_norm_eps`` and
    ``init_scale``."""

    config: object
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        c = self.config
        if not sub_chunk_safe(c.kda_lower_bound):
            raise ValueError(f"kda_lower_bound {c.kda_lower_bound}: the chunked form's sub-chunks overflow float32 under it")
        init = nn.initializers.normal(c.init_scale)
        h, heads, width, taps = c.hidden_size, c.num_attention_heads, self.width, c.short_conv_kernel_size
        self.w_q = self.param("w_q", init, (h, width), self.param_dtype)
        self.w_k = self.param("w_k", init, (h, width), self.param_dtype)
        self.w_v = self.param("w_v", init, (h, width), self.param_dtype)
        self.conv_q = self.param("conv_q", init, (taps, width), self.param_dtype)
        self.conv_k = self.param("conv_k", init, (taps, width), self.param_dtype)
        self.conv_v = self.param("conv_v", init, (taps, width), self.param_dtype)
        self.w_f = self.param("w_f", init, (h, width), self.param_dtype)
        self.dt_bias = self.param("dt_bias", init, (width,), self.param_dtype)
        self.a_log = self.param("a_log", init, (heads,), self.param_dtype)
        self.w_b = self.param("w_b", init, (h, heads), self.param_dtype)
        self.w_g = self.param("w_g", init, (h, width), self.param_dtype)
        self.o_norm = RMSNorm(epsilon=c.rms_norm_eps, dtype=jnp.float32, param_dtype=self.param_dtype)
        self.w_o = self.param("w_o", init, (width, h), self.param_dtype)

    @property
    def width(self) -> int:
        return self.config.num_attention_heads * self.config.head_dim

    def _mm(self, x, w):
        return jnp.dot(x.astype(self.dtype), w.astype(self.dtype))

    # ------------------------------------------------------------ shared

    def _heads(self, t):
        return t.reshape(*t.shape[:-1], self.config.num_attention_heads, self.config.head_dim)

    def _l2norm(self, t):
        """``t`` (..., H * D) float32, a head's channels normed to length 1."""
        t = self._heads(t)
        return (t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + _L2_EPS)).reshape(*t.shape[:-2], self.width)

    def _shape(self, windows):
        """The three convolutions over their windows (B, T + K - 1, H * D):
        ``q`` (scaled), ``k`` and ``v`` (B, T, H * D) in ``dtype``."""
        q = self._l2norm(causal_conv(windows[0], self.conv_q)) * self.config.head_dim ** -0.5
        k = self._l2norm(causal_conv(windows[1], self.conv_k))
        return q.astype(self.dtype), k.astype(self.dtype), causal_conv(windows[2], self.conv_v).astype(self.dtype)

    def _gates(self, x):
        """``x`` (B, T, h) -> the log-decays ``g`` (B, T, H * D) and the steps ``b`` (B, T, H), float32."""
        c = self.config
        pre = jnp.dot(x.astype(self.dtype), self.w_f.astype(self.dtype), preferred_element_type=jnp.float32)
        rate = jnp.repeat(jnp.exp(self.a_log.astype(jnp.float32)), c.head_dim)
        g = c.kda_lower_bound * jax.nn.sigmoid(rate * (pre + self.dt_bias.astype(jnp.float32)))
        b = jax.nn.sigmoid(jnp.dot(x.astype(self.dtype), self.w_b.astype(self.dtype), preferred_element_type=jnp.float32))
        return g, b

    def _out(self, o, x):
        """The head-wise RMSNorm, the output gate and ``W_o``: ``o`` (B, T, H * D), ``x`` the layer's input."""
        gate = jax.nn.sigmoid(jnp.dot(x.astype(self.dtype), self.w_g.astype(self.dtype), preferred_element_type=jnp.float32))
        normed = self.o_norm(self._heads(o.astype(jnp.float32))).reshape(o.shape)
        return self._mm(normed * gate, self.w_o)

    @staticmethod
    def _tap(s, g, b):
        if probes.active():
            probes.tap("kda.state", {"kda_state_abs_max": jnp.max(jnp.abs(s)),
                                     "kda_state_nonfinite": jnp.sum(~jnp.isfinite(s)).astype(jnp.int32),
                                     # a site's means, summed over the sites with their count (a collector adds what is no ``*_max``)
                                     "kda_decay_sum": jnp.mean(jnp.exp(g)), "kda_beta_sum": jnp.mean(b),
                                     "kda_sites": jnp.ones((), jnp.int32)})

    # ------------------------------------------------------ the prompt pass

    def expand(self, x) -> Tuple[jnp.ndarray, DeltaState]:
        """Whole rows ``x`` (B, T, h) from an empty state: the output (B, T, h)
        and the rows' state after their last token (the windows in ``dtype``)."""
        c = self.config
        taps = c.short_conv_kernel_size
        kernels = flash_enabled() and kda_supported(c.head_dim)
        with jax.named_scope("kda/proj"):
            inputs = [self._mm(x, w) for w in (self.w_q, self.w_k, self.w_v)]
        with jax.named_scope("kda/conv"):
            windows = [rows_window(t, taps) for t in inputs]
            kept = [window_tail(w, taps) for w in windows]
            if not kernels:
                q, k, v = self._shape(windows)
        with jax.named_scope("kda/gate"):
            g, b = self._gates(x)
        with jax.named_scope("kda/chunk"):
            if kernels:  # the chunk kernel shapes the raw projections on the tiles it holds: nothing of ``_shape`` runs in XLA
                o, s = kda_chunked(*inputs, g, b, c.num_attention_heads, taps=(self.conv_q, self.conv_k, self.conv_v))
            else:
                o, s = kda_reference(self._heads(q), self._heads(k), self._heads(v), self._heads(g), b)
                o = o.reshape(q.shape)
            self._tap(s, g, b)
        with jax.named_scope("kda/out"):
            return self._out(o, x), DeltaState(s=s, conv_q=kept[0], conv_k=kept[1], conv_v=kept[2])

    # ------------------------------------------------------------- one step

    def step(self, x, state: DeltaState) -> Tuple[jnp.ndarray, DeltaState]:
        """One new token a row, ``x`` (B, 1, h), against ``state``: the output (B, 1, h) and the advanced state."""
        c = self.config
        with jax.named_scope("kda/proj"):
            inputs = [self._mm(x, w) for w in (self.w_q, self.w_k, self.w_v)]
        with jax.named_scope("kda/conv"):
            windows = [step_window(old, t) for old, t in zip((state.conv_q, state.conv_k, state.conv_v), inputs)]
            q, k, v = (self._heads(t[:, 0]) for t in self._shape(windows))
            kept = [window_tail(w, c.short_conv_kernel_size) for w in windows]
        with jax.named_scope("kda/gate"):
            g, b = self._gates(x)
        with jax.named_scope("kda/update"):
            update = kda_step if flash_enabled() and kda_supported(c.head_dim) else kda_update
            o, s = update(q, k, v, self._heads(g[:, 0]), b[:, 0], state.s)
            self._tap(s, g, b)
        with jax.named_scope("kda/out"):
            out = self._out(o.reshape(x.shape[0], 1, self.width), x)
            return out, DeltaState(s=s, conv_q=kept[0], conv_k=kept[1], conv_v=kept[2])
