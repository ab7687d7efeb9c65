"""Core Perceiver building blocks: attention layers, Perceiver IO encoder/
decoder, Perceiver AR and the causal sequence model.

Behavioral parity with the reference core
(reference: perceiver/model/core/modules.py:173-930), redesigned for XLA:

- All shapes are static. The prefix cross-attention dropout of Perceiver AR
  (reference: modules.py:809-830) keeps its *compute reduction* via a
  static-count ``lax.top_k`` gather (the keep count is a Python int), instead
  of the reference's data-dependent boolean select.
- KV caches are fixed-capacity buffers (see ``core.attention``); the
  init-call vs decode-call distinction (reference: modules.py:795-800, where
  it is "is the cache list empty?") is the static ``decode`` flag.
- Rotary alignment for cached decoding is computed from position *values*
  (dynamic values, static shapes) so a single compiled decode step serves
  every cache fill level; this replaces the reference's right-aligned slicing
  of freshly-sized encodings (modules.py:850-866).
- Activation checkpointing is ``nn.remat`` on the attention layers
  (reference: fairscale checkpoint_wrapper, modules.py:933-956). CPU
  activation offload has no TPU analog; remat policies take its place.
- Weight sharing for repeated encoder cross-attention/self-attention blocks
  (reference: modules.py:579-602) is module-instance reuse.
"""

from __future__ import annotations

import collections
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from flax import struct
from jax import lax

from perceiver_io_tpu.core.attention import AttentionOutput, KVCache, MultiHeadAttention, init_kv_cache
from perceiver_io_tpu.obs.probes import probe
from perceiver_io_tpu.ops.layernorm import LayerNorm
from perceiver_io_tpu.core.config import CausalSequenceModelConfig
from perceiver_io_tpu.core.position import positions

LAYER_NORM_EPSILON = 1e-5  # match torch nn.LayerNorm default

# channel-pad rounding shared by the fused split-kv input route: the gate in
# PerceiverEncoder.__call__ must predict exactly the padded head dims
# split_kv_projection emits and call_with_split_kv hands to flash_attention
SPLIT_KV_PAD = 8


def split_padded(n: int) -> int:
    """Channel width after the fused split-kv route's zero-padding."""
    return n + (-n) % SPLIT_KV_PAD


def _remat(layer_cls, static_argnums, checkpoint: bool, offload: bool):
    """Activation-checkpointing wrapper for an attention layer class; returns
    the class unchanged when neither flag is set.

    ``checkpoint``: plain ``nn.remat`` — recompute in the backward pass
    (reference: fairscale checkpoint_wrapper, modules.py:933-956).
    ``offload``: the TPU analog of the reference's ``activation_offloading``
    (CPU offload of saved activations, config.py:60-61,75-76) — dot outputs
    are kept in **pinned host memory** instead of HBM and fetched back during
    backward (``offload_dot_with_no_batch_dims``); everything else is
    rematerialized.
    """
    if not (checkpoint or offload):
        return layer_cls
    policy = None
    if offload:
        policy = jax.checkpoint_policies.offload_dot_with_no_batch_dims(
            "device", "pinned_host"
        )
    return nn.remat(layer_cls, static_argnums=static_argnums, prevent_cse=False, policy=policy)


@struct.dataclass
class BlockOutput:
    last_hidden_state: jnp.ndarray
    kv_cache: Optional[Tuple[KVCache, ...]] = None


@struct.dataclass
class CausalModelOutput:
    last_hidden_state: jnp.ndarray
    logits: jnp.ndarray
    kv_cache: Optional[Tuple[KVCache, ...]] = None


class CrossAttention(nn.Module):
    """Pre-layer-norm cross-attention (reference: modules.py:173-230).

    If ``x_kv_prefix`` is given instead of ``x_kv``, the key/value input is
    ``concat(norm(x_kv_prefix), norm(x_q))`` so the query attends to itself at
    the end of the sequence (Perceiver AR)."""

    num_heads: int
    num_q_input_channels: int
    num_kv_input_channels: int
    num_qk_channels: Optional[int] = None
    num_v_channels: Optional[int] = None
    max_heads_parallel: Optional[int] = None
    causal_attention: bool = False
    dropout: float = 0.0
    qkv_bias: bool = True
    out_bias: bool = True
    init_scale: float = 0.02
    dtype: jnp.dtype = jnp.float32
    use_flash: Optional[bool] = None

    def setup(self):
        self.q_norm = LayerNorm(epsilon=LAYER_NORM_EPSILON, dtype=self.dtype)
        self.kv_norm = LayerNorm(epsilon=LAYER_NORM_EPSILON, dtype=self.dtype)
        self.attention = MultiHeadAttention(
            num_heads=self.num_heads,
            num_q_input_channels=self.num_q_input_channels,
            num_kv_input_channels=self.num_kv_input_channels,
            num_qk_channels=self.num_qk_channels,
            num_v_channels=self.num_v_channels,
            max_heads_parallel=self.max_heads_parallel,
            causal_attention=self.causal_attention,
            dropout=self.dropout,
            qkv_bias=self.qkv_bias,
            out_bias=self.out_bias,
            init_scale=self.init_scale,
            dtype=self.dtype,
            use_flash=self.use_flash,
        )

    def __call__(
        self,
        x_q,
        x_kv=None,
        x_kv_prefix=None,
        pad_mask=None,
        rope_q=None,
        rope_k=None,
        kv_cache=None,
        deterministic: bool = True,
    ) -> AttentionOutput:
        x_q = self.q_norm(x_q)
        if x_kv is None:
            with jax.named_scope("kv_concat"):
                # the materialized [prefix; latents] kv tensor — labeled so
                # graphlint's hot-concat rule attributes it precisely
                # (analysis/flagship.py DEFAULT_ALLOW allowlists exactly
                # this scope)
                x_kv_prefix = self.kv_norm(x_kv_prefix)
                x_kv = jnp.concatenate([x_kv_prefix, x_q], axis=1)
        else:
            x_kv = self.kv_norm(x_kv)
        return self.attention(
            x_q,
            x_kv,
            pad_mask=pad_mask,
            rope_q=rope_q,
            rope_k=rope_k,
            kv_cache=kv_cache,
            deterministic=deterministic,
        )

    def split_kv_projection(self, x_pix, enc):
        """K/V of ``kv_norm(concat([x_pix, enc], -1))`` WITHOUT materializing
        the concatenated input or its LayerNorm output.

        ``x_pix`` (B, M, P) is the per-example part (pixels); ``enc`` (M, F)
        is a per-position CONSTANT (the image Fourier features). The vision
        encoder's profile (b=16, v5e) spends ~14 ms/step building two
        (B, 50176, 261) concat+cast copies, LayerNorm-ing them, and padding
        the projections — all of it linear-algebraically redundant:

        with z = gamma * (x - mu) * r + beta (the LN row) and a projection
        W/b, ``z @ W + b = r*(x @ Wg) - (mu*r)*colsum(Wg) + (beta @ W + b)``
        where ``Wg = diag(gamma) @ W``; and since x = [pix | enc],
        ``x @ Wg = pix @ Wg[:P] + enc @ Wg[P:]`` with the second term shared
        across the batch. The per-position LN stats (mu, r) come from pixel
        sums plus precomputed constants of ``enc``. Everything the kernels
        consume is emitted directly, channel-padded to a multiple of
        ``SPLIT_KV_PAD`` with EXACT zeros via weight-side padding (no (B, M, C)
        pad op). Numerics: stats in f32 like the LN; the GEMMs run in the
        module dtype on raw (un-normalized) inputs — same accumulation
        magnitudes, equivalence pinned by tests/test_fused_image_input.py.

        Returns ``(k, v, k_pad, v_pad)`` with k/v (B, M, ch+pad).
        """
        mha = self.attention
        if self.is_initializing():
            # the standard path's parameter shapes, created eagerly so both
            # paths share one checkpoint layout
            z = jnp.zeros((1, 1, self.num_kv_input_channels), self.dtype)
            self.kv_norm(z)
            mha.k_proj(z)
            mha.v_proj(z)
        n_pix = x_pix.shape[-1]
        c = self.num_kv_input_channels
        ln = self.kv_norm.variables["params"]
        gamma = ln["scale"].astype(jnp.float32)
        beta = ln["bias"].astype(jnp.float32)

        enc = lax.stop_gradient(enc)
        enc32 = enc.astype(jnp.float32)
        s1_enc = enc32.sum(-1)
        s2_enc = (enc32 * enc32).sum(-1)
        pix32 = x_pix.astype(jnp.float32)
        s1 = pix32.sum(-1) + s1_enc[None]  # (B, M)
        s2 = (pix32 * pix32).sum(-1) + s2_enc[None]
        mean = s1 / c
        var = jnp.maximum(s2 / c - mean * mean, 0.0)
        r = lax.rsqrt(var + LAYER_NORM_EPSILON)
        dt = self.dtype
        r_dt = r.astype(dt)[..., None]
        mr_dt = (mean * r).astype(dt)[..., None]

        def project(dense, out_ch):
            p = dense.variables["params"]
            w = p["kernel"].astype(jnp.float32)  # (C, out_ch)
            b = p["bias"].astype(jnp.float32) if "bias" in p else jnp.zeros((out_ch,), jnp.float32)
            pad = split_padded(out_ch) - out_ch
            wg = w * gamma[:, None]
            if pad:
                wg = jnp.pad(wg, ((0, 0), (0, pad)))
                w_p = jnp.pad(w, ((0, 0), (0, pad)))
                b_p = jnp.pad(b, (0, pad))
            else:
                w_p, b_p = w, b
            colsum = wg.sum(0).astype(dt)  # (out+pad,)
            const = (beta @ w_p + b_p).astype(dt)
            enc_term = enc.astype(dt) @ wg[n_pix:].astype(dt)  # (M, out+pad)
            pix_term = x_pix.astype(dt) @ wg[:n_pix].astype(dt)  # (B, M, out+pad)
            xw = pix_term + enc_term[None]
            return xw * r_dt - mr_dt * colsum + const, pad

        k, k_pad = project(mha.k_proj, mha.qk_channels)
        v, v_pad = project(mha.v_proj, mha.v_channels)
        return k, v, k_pad, v_pad


class SelfAttention(nn.Module):
    """Pre-layer-norm self-attention (reference: modules.py:233-278)."""

    num_heads: int
    num_channels: int
    num_qk_channels: Optional[int] = None
    num_v_channels: Optional[int] = None
    max_heads_parallel: Optional[int] = None
    causal_attention: bool = False
    dropout: float = 0.0
    qkv_bias: bool = True
    out_bias: bool = True
    init_scale: float = 0.02
    dtype: jnp.dtype = jnp.float32
    use_flash: Optional[bool] = None

    def setup(self):
        self.norm = LayerNorm(epsilon=LAYER_NORM_EPSILON, dtype=self.dtype)
        self.attention = MultiHeadAttention(
            num_heads=self.num_heads,
            num_q_input_channels=self.num_channels,
            num_kv_input_channels=self.num_channels,
            num_qk_channels=self.num_qk_channels,
            num_v_channels=self.num_v_channels,
            max_heads_parallel=self.max_heads_parallel,
            causal_attention=self.causal_attention,
            dropout=self.dropout,
            qkv_bias=self.qkv_bias,
            out_bias=self.out_bias,
            init_scale=self.init_scale,
            dtype=self.dtype,
            use_flash=self.use_flash,
        )

    def __call__(
        self,
        x,
        pad_mask=None,
        rope_q=None,
        rope_k=None,
        kv_cache=None,
        deterministic: bool = True,
    ) -> AttentionOutput:
        x = self.norm(x)
        return self.attention(
            x,
            x,
            pad_mask=pad_mask,
            rope_q=rope_q,
            rope_k=rope_k,
            kv_cache=kv_cache,
            deterministic=deterministic,
        )


# The exact GELU between ``dense_1`` and ``dense_2``, with its own
# differentiation rule. Left to autodiff, XLA keeps only ``h`` (the bf16
# ``dense_1`` output) and two bit-packed predicate masks, and expands the
# whole ``erfc`` (both branches, two polynomials, an exponential and two
# divides an element, in float32 on the VPU) again inside the input of every
# GEMM that consumes ``gelu(h)``: the forward ``dense_2``, its ``dW2`` and,
# with the density, its ``dy W2^T``. It counts the bytes it saves and not the
# VPU work, and a GEMM of [32768, 2048] x [2048, 512] that takes 0.39 to 0.44
# ms plain took 1.14 to 1.34 ms behind the expansion (v5e, PERF.md 6, PR 35).
#
# The rule splits ``nn.gelu``'s expression where it rounds: ``e = erfc(-h /
# sqrt 2)`` in the compute dtype, then ``0.5 h e``. The forward evaluates
# ``e`` once, in ``dense_1``'s epilogue, and an ``optimization_barrier`` on
# ``(h, e)`` makes XLA keep both; ``dense_2`` and ``dW2`` read them and
# multiply, and the backward differentiates the product at the kept ``e`` and
# takes only ``erfc``'s derivative (one exponential) at the kept ``h``. Values
# and gradients are autodiff's to the bit (tests/test_mlp_gelu.py). Why ``e``
# and not ``gelu(h)`` or ``gelu'(h)``: an XLA fusion has one root, and its
# other results are expensive values on the way to it, as ``h`` is to ``e``;
# two results that share the ``erfc`` leave ``dense_1`` as a float32 array of
# the hidden shape and a second fusion (tools/mlp_gelu_ab.py, PERF.md 6).
#
# The primal is ``nn.gelu`` itself: a program that does not differentiate
# (the generator, the serving engine) lowers to what it lowered to before.


def _erfc_term(x):
    return lax.erfc(-x * np.sqrt(0.5).astype(x.dtype))  # as nn.gelu writes it


def _gelu_from(x, e):
    return jnp.array(0.5 * x * e, dtype=x.dtype)


@jax.custom_vjp
def gelu_exact(x):
    """``nn.gelu(x, approximate=False)``, with ``erfc`` evaluated once a site
    under differentiation (see above)."""
    return nn.gelu(x, approximate=False)


def _gelu_exact_fwd(x):
    _MLP_GELU_SITES[(math.prod(x.shape[:-1]), x.shape[-1] if x.ndim else 1, jnp.dtype(x.dtype).name)] += 1
    x, e = lax.optimization_barrier((x, _erfc_term(x)))
    return _gelu_from(x, e), (x, e)


def _gelu_exact_bwd(residuals, da):
    x, e = residuals
    dx, de = jax.vjp(_gelu_from, x, e)[1](da)
    # erfc's own value is dead code here: its derivative is an exponential of x alone
    return (dx + jax.vjp(_erfc_term, x)[1](de)[0],)


gelu_exact.defvjp(_gelu_exact_fwd, _gelu_exact_bwd)

# forward-rule traces by (rows, width, dtype): a trace-time fact like
# ``ops.flash_attention._TILE_PLANS``, read by obs.recompile for the
# ``compile`` event row
_MLP_GELU_SITES: collections.Counter = collections.Counter()


def mlp_gelu_sites() -> collections.Counter:
    """A snapshot of the forward-rule traces so far, for :func:`mlp_gelu_plans`'s ``since``."""
    return collections.Counter(_MLP_GELU_SITES)


def mlp_gelu_plans(since: Optional[collections.Counter] = None) -> list:
    """One row per distinct ``(rows, width)`` at which :func:`gelu_exact` was
    differentiated (the ``mlp_gelu`` rows of the ``compile`` event,
    docs/observability.md): ``sites`` (traces of the forward rule, since the
    snapshot ``since`` where given: the trace of one step reads its MLPs of
    that shape), what each keeps for its backward and how often it evaluates
    ``erfc``. Counts from shapes."""
    traced = _MLP_GELU_SITES - since if since is not None else _MLP_GELU_SITES
    return [
        {"rows": rows, "width": width, "dtype": dtype, "sites": sites, "residuals": "h+erfc",
         "residual_bytes": 2 * rows * width * jnp.dtype(dtype).itemsize, "erfc_evals_per_site": 1}
        for (rows, width, dtype), sites in sorted(traced.items())
    ]


class MLP(nn.Module):
    """LayerNorm -> Dense(widening * C) -> GELU(exact) -> Dense(C)
    (reference: modules.py:444-454)."""

    num_channels: int
    widening_factor: int
    bias: bool = True
    init_scale: float = 0.02
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        dense = lambda feat, name: nn.Dense(  # noqa: E731
            feat,
            use_bias=self.bias,
            kernel_init=nn.initializers.normal(stddev=self.init_scale),
            dtype=self.dtype,
            name=name,
        )
        with jax.named_scope("mlp"):
            # name pinned: auto-naming would differ from nn.LayerNorm's
            x = LayerNorm(epsilon=LAYER_NORM_EPSILON, dtype=self.dtype, name="LayerNorm_0")(x)
            x = dense(self.widening_factor * self.num_channels, "dense_1")(x)
            x = gelu_exact(x)
            x = dense(self.num_channels, "dense_2")(x)
        return x


class CrossAttentionLayer(nn.Module):
    """Cross-attention + MLP with residuals (reference: modules.py:293-330)."""

    num_heads: int
    num_q_input_channels: int
    num_kv_input_channels: int
    num_qk_channels: Optional[int] = None
    num_v_channels: Optional[int] = None
    max_heads_parallel: Optional[int] = None
    causal_attention: bool = False
    widening_factor: int = 1
    dropout: float = 0.0
    residual_dropout: float = 0.0
    attention_residual: bool = True
    qkv_bias: bool = True
    out_bias: bool = True
    mlp_bias: bool = True
    init_scale: float = 0.02
    dtype: jnp.dtype = jnp.float32
    use_flash: Optional[bool] = None

    def setup(self):
        self.cross_attn = CrossAttention(
            num_heads=self.num_heads,
            num_q_input_channels=self.num_q_input_channels,
            num_kv_input_channels=self.num_kv_input_channels,
            num_qk_channels=self.num_qk_channels,
            num_v_channels=self.num_v_channels,
            max_heads_parallel=self.max_heads_parallel,
            causal_attention=self.causal_attention,
            dropout=self.dropout,
            qkv_bias=self.qkv_bias,
            out_bias=self.out_bias,
            init_scale=self.init_scale,
            dtype=self.dtype,
            use_flash=self.use_flash,
        )
        self.mlp = MLP(
            num_channels=self.num_q_input_channels,
            widening_factor=self.widening_factor,
            bias=self.mlp_bias,
            init_scale=self.init_scale,
            dtype=self.dtype,
        )
        self.res_dropout = nn.Dropout(self.residual_dropout)

    def __call__(
        self,
        x_q,
        x_kv=None,
        x_kv_prefix=None,
        pad_mask=None,
        rope_q=None,
        rope_k=None,
        kv_cache=None,
        deterministic: bool = True,
    ) -> AttentionOutput:
        attn = self.cross_attn(
            x_q,
            x_kv=x_kv,
            x_kv_prefix=x_kv_prefix,
            pad_mask=pad_mask,
            rope_q=rope_q,
            rope_k=rope_k,
            kv_cache=kv_cache,
            deterministic=deterministic,
        )
        if self.attention_residual:
            h = x_q + self.res_dropout(attn.last_hidden_state, deterministic=deterministic)
        else:
            h = attn.last_hidden_state
        h = h + self.res_dropout(self.mlp(h), deterministic=deterministic)
        return AttentionOutput(last_hidden_state=h, kv_cache=attn.kv_cache)

    def call_with_split_kv(self, x_q, x_pix, enc, deterministic: bool = True) -> AttentionOutput:
        """The full layer (attention + residual + MLP) with k/v built by
        :meth:`CrossAttention.split_kv_projection` — the vision encoder's
        fused-input route (pad_mask-free, single-head, no attention-prob
        dropout; `PerceiverEncoder` gates these). Numerically the standard
        ``__call__`` on ``concat([x_pix, broadcast(enc)], -1)``."""
        from perceiver_io_tpu.ops.flash_attention import flash_attention

        ca = self.cross_attn
        mha = ca.attention
        q_in = ca.q_norm(x_q)
        k, v, k_pad, v_pad = ca.split_kv_projection(x_pix, enc)
        q = mha.project_q(q_in)  # (B, 1, N, dk) scaled; single head
        if k_pad:
            q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, k_pad)))
        o = flash_attention(q, k[:, None], v[:, None], causal=False)
        if v_pad:
            o = o[..., : mha.v_channels]
        h_attn = mha.merge_output(o.astype(x_q.dtype))
        if self.attention_residual:
            h = x_q + self.res_dropout(h_attn, deterministic=deterministic)
        else:
            h = h_attn
        h = h + self.res_dropout(self.mlp(h), deterministic=deterministic)
        return AttentionOutput(last_hidden_state=h, kv_cache=None)


class SelfAttentionLayer(nn.Module):
    """Self-attention + MLP with residuals (reference: modules.py:333-367)."""

    num_heads: int
    num_channels: int
    num_qk_channels: Optional[int] = None
    num_v_channels: Optional[int] = None
    max_heads_parallel: Optional[int] = None
    causal_attention: bool = False
    widening_factor: int = 1
    dropout: float = 0.0
    residual_dropout: float = 0.0
    qkv_bias: bool = True
    out_bias: bool = True
    mlp_bias: bool = True
    init_scale: float = 0.02
    dtype: jnp.dtype = jnp.float32
    use_flash: Optional[bool] = None

    def setup(self):
        self.self_attn = SelfAttention(
            num_heads=self.num_heads,
            num_channels=self.num_channels,
            num_qk_channels=self.num_qk_channels,
            num_v_channels=self.num_v_channels,
            max_heads_parallel=self.max_heads_parallel,
            causal_attention=self.causal_attention,
            dropout=self.dropout,
            qkv_bias=self.qkv_bias,
            out_bias=self.out_bias,
            init_scale=self.init_scale,
            dtype=self.dtype,
            use_flash=self.use_flash,
        )
        self.mlp = MLP(
            num_channels=self.num_channels,
            widening_factor=self.widening_factor,
            bias=self.mlp_bias,
            init_scale=self.init_scale,
            dtype=self.dtype,
        )
        self.res_dropout = nn.Dropout(self.residual_dropout)

    def __call__(
        self,
        x,
        pad_mask=None,
        rope_q=None,
        rope_k=None,
        kv_cache=None,
        deterministic: bool = True,
    ) -> AttentionOutput:
        attn = self.self_attn(
            x,
            pad_mask=pad_mask,
            rope_q=rope_q,
            rope_k=rope_k,
            kv_cache=kv_cache,
            deterministic=deterministic,
        )
        h = x + self.res_dropout(attn.last_hidden_state, deterministic=deterministic)
        h = h + self.res_dropout(self.mlp(h), deterministic=deterministic)
        return AttentionOutput(last_hidden_state=h, kv_cache=attn.kv_cache)


class SelfAttentionBlock(nn.Module):
    """Stack of self-attention layers with per-layer KV caches and rotary
    gating: layer i gets RoPE iff ``i < num_rotary_layers`` (-1 = all layers)
    (reference: modules.py:370-441)."""

    num_layers: int
    num_heads: int
    num_channels: int
    num_qk_channels: Optional[int] = None
    num_v_channels: Optional[int] = None
    num_rotary_layers: int = 1
    max_heads_parallel: Optional[int] = None
    causal_attention: bool = False
    widening_factor: int = 1
    dropout: float = 0.0
    residual_dropout: float = 0.0
    activation_checkpointing: bool = False
    activation_offloading: bool = False
    qkv_bias: bool = True
    out_bias: bool = True
    mlp_bias: bool = True
    init_scale: float = 0.02
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        # static_argnums counts `self` at 0; 6 == `deterministic`.
        layer_cls = _remat(
            SelfAttentionLayer, (6,), self.activation_checkpointing, self.activation_offloading
        )
        self.layers = [
            layer_cls(
                num_heads=self.num_heads,
                num_channels=self.num_channels,
                num_qk_channels=self.num_qk_channels,
                num_v_channels=self.num_v_channels,
                max_heads_parallel=self.max_heads_parallel,
                causal_attention=self.causal_attention,
                widening_factor=self.widening_factor,
                dropout=self.dropout,
                residual_dropout=self.residual_dropout,
                qkv_bias=self.qkv_bias,
                out_bias=self.out_bias,
                mlp_bias=self.mlp_bias,
                init_scale=self.init_scale,
                dtype=self.dtype,
                name=f"layer_{i}",
            )
            for i in range(self.num_layers)
        ]

    def __call__(
        self,
        x,
        pad_mask=None,
        rope_q=None,
        rope_k=None,
        kv_cache: Optional[Tuple[KVCache, ...]] = None,
        deterministic: bool = True,
    ) -> BlockOutput:
        kv_cache_updated = [] if kv_cache is not None else None
        for i, layer in enumerate(self.layers):
            use_rope = i < self.num_rotary_layers or self.num_rotary_layers == -1
            cache_i = None if kv_cache is None else kv_cache[i]
            out = layer(
                x,
                pad_mask,
                rope_q if use_rope else None,
                rope_k if use_rope else None,
                cache_i,
                deterministic,
            )
            # Probeline tap (obs/probes.py): traces zero ops unless a probe
            # collector is open — per-layer activation stats ride out as aux
            # outputs of the same compiled program
            x = probe(f"{self.name or 'self_attn'}.layer_{i}", out.last_hidden_state)
            if kv_cache_updated is not None:
                kv_cache_updated.append(out.kv_cache)
        return BlockOutput(
            last_hidden_state=x,
            kv_cache=None if kv_cache_updated is None else tuple(kv_cache_updated),
        )


class PerceiverEncoder(nn.Module):
    """Perceiver IO encoder: a learned latent array cross-attends to the
    adapted input, followed by self-attention blocks; supports repeated
    cross-attention with configurable weight sharing
    (reference: modules.py:457-607)."""

    input_adapter: nn.Module
    num_latents: int
    num_latent_channels: int
    num_cross_attention_heads: int = 4
    num_cross_attention_qk_channels: Optional[int] = None
    num_cross_attention_v_channels: Optional[int] = None
    num_cross_attention_layers: int = 1
    first_cross_attention_layer_shared: bool = False
    cross_attention_widening_factor: int = 1
    num_self_attention_heads: int = 4
    num_self_attention_qk_channels: Optional[int] = None
    num_self_attention_v_channels: Optional[int] = None
    num_self_attention_layers_per_block: int = 6
    num_self_attention_blocks: int = 1
    first_self_attention_block_shared: bool = True
    self_attention_widening_factor: int = 1
    dropout: float = 0.0
    residual_dropout: float = 0.0
    init_scale: float = 0.02
    activation_checkpointing: bool = False
    activation_offloading: bool = False
    dtype: jnp.dtype = jnp.float32

    @property
    def extra_cross_attention_layer(self) -> bool:
        return self.num_cross_attention_layers > 1 and not self.first_cross_attention_layer_shared

    @property
    def extra_self_attention_block(self) -> bool:
        return self.num_self_attention_blocks > 1 and not self.first_self_attention_block_shared

    def setup(self):
        from perceiver_io_tpu.core.adapter import TrainableQueryProvider

        if self.num_cross_attention_layers <= 0:
            raise ValueError("num_cross_attention_layers must be > 0")
        if self.num_self_attention_blocks <= 0:
            raise ValueError("num_self_attention_blocks must be > 0")
        if self.num_cross_attention_layers > self.num_self_attention_blocks:
            raise ValueError("num_cross_attention_layers must be <= num_self_attention_blocks")

        self.latent_provider = TrainableQueryProvider(
            num_queries=self.num_latents,
            num_query_channels=self.num_latent_channels,
            init_scale=self.init_scale,
            dtype=self.dtype,
        )

        cross_attn_cls = _remat(
            CrossAttentionLayer, (8,), self.activation_checkpointing, self.activation_offloading
        )

        def cross_attn(name):
            return cross_attn_cls(
                num_heads=self.num_cross_attention_heads,
                num_q_input_channels=self.num_latent_channels,
                num_kv_input_channels=self.input_adapter.num_input_channels,
                num_qk_channels=self.num_cross_attention_qk_channels,
                num_v_channels=self.num_cross_attention_v_channels,
                widening_factor=self.cross_attention_widening_factor,
                dropout=self.dropout,
                residual_dropout=self.residual_dropout,
                init_scale=self.init_scale,
                dtype=self.dtype,
                name=name,
            )

        def self_attn(name):
            return SelfAttentionBlock(
                num_layers=self.num_self_attention_layers_per_block,
                num_heads=self.num_self_attention_heads,
                num_channels=self.num_latent_channels,
                num_qk_channels=self.num_self_attention_qk_channels,
                num_v_channels=self.num_self_attention_v_channels,
                num_rotary_layers=0,
                widening_factor=self.self_attention_widening_factor,
                dropout=self.dropout,
                residual_dropout=self.residual_dropout,
                activation_checkpointing=self.activation_checkpointing,
                activation_offloading=self.activation_offloading,
                init_scale=self.init_scale,
                dtype=self.dtype,
                name=name,
            )

        self.cross_attn_1 = cross_attn("cross_attn_1")
        self.self_attn_1 = self_attn("self_attn_1")
        if self.extra_cross_attention_layer:
            self.cross_attn_n = cross_attn("cross_attn_n")
        if self.extra_self_attention_block:
            self.self_attn_n = self_attn("self_attn_n")

    def _use_split_input(self, pad_mask, deterministic) -> bool:
        """Route the cross-attentions through the fused split-kv path (the
        adapter's constant positional features folded into the projections —
        CrossAttention.split_kv_projection) when the configuration allows:
        no pad mask, single-head CA (the channel pad trick is per-head), no
        active attention-prob dropout, remat AND offload off (the nn.remat
        class transform wraps ``__call__`` only). Shape support for the flash
        kernels is checked at the call site where the input is known."""
        if not getattr(self.input_adapter, "supports_split", False):
            return False
        if pad_mask is not None or self.num_cross_attention_heads != 1:
            return False
        if self.dropout > 0.0 and not deterministic:
            return False
        return not (self.activation_checkpointing or self.activation_offloading)

    def __call__(self, x, pad_mask=None, return_adapted_input: bool = False, deterministic: bool = True):
        from perceiver_io_tpu.ops.flash_attention import flash_enabled, flash_supported

        b = x.shape[0]

        x_latent = self.latent_provider()
        x_latent = jnp.broadcast_to(x_latent, (b,) + x_latent.shape[1:])

        # return_adapted_input forfeits the route's saving (the concat would be
        # materialized anyway for the return value) — take the standard path
        use_split = not return_adapted_input and self._use_split_input(pad_mask, deterministic)
        if use_split:
            x_pix, enc = self.input_adapter.split(x)
            qk = self.cross_attn_1.cross_attn.attention.qk_channels
            v = self.cross_attn_1.cross_attn.attention.v_channels
            use_split = flash_enabled() and flash_supported(
                self.num_latents, x_pix.shape[1], split_padded(qk), split_padded(v), False
            )

        if use_split:
            x_adapted = None

            def call_ca(layer, x_latent):
                with jax.named_scope("cross_attend"):
                    return layer.call_with_split_kv(
                        x_latent, x_pix, enc, deterministic
                    ).last_hidden_state

        else:
            with jax.named_scope("input_adapter"):
                x_adapted = self.input_adapter(x)

            def call_ca(layer, x_latent):
                with jax.named_scope("cross_attend"):
                    return layer(
                        x_latent, x_adapted, None, pad_mask, None, None, None, deterministic
                    ).last_hidden_state

        def call_sa(block, x_latent):
            with jax.named_scope("self_attend"):
                return block(x_latent, deterministic=deterministic).last_hidden_state

        x_latent = call_ca(self.cross_attn_1, x_latent)
        x_latent = call_sa(self.self_attn_1, x_latent)

        cross_attn_n = self.cross_attn_n if self.extra_cross_attention_layer else self.cross_attn_1
        self_attn_n = self.self_attn_n if self.extra_self_attention_block else self.self_attn_1

        for i in range(1, self.num_self_attention_blocks):
            if i < self.num_cross_attention_layers:
                x_latent = call_ca(cross_attn_n, x_latent)
            x_latent = call_sa(self_attn_n, x_latent)

        if return_adapted_input:
            return x_latent, x_adapted
        return x_latent


class PerceiverDecoder(nn.Module):
    """Perceiver IO decoder: output queries cross-attend to the latents, the
    output adapter maps to task output (reference: modules.py:610-675).

    ``output_query_provider`` must expose ``num_query_channels`` and be
    callable with the (optional) adapted input."""

    output_adapter: Any
    output_query_provider: Any
    num_latent_channels: int
    num_cross_attention_heads: int = 4
    num_cross_attention_qk_channels: Optional[int] = None
    num_cross_attention_v_channels: Optional[int] = None
    cross_attention_widening_factor: int = 1
    cross_attention_residual: bool = True
    dropout: float = 0.0
    init_scale: float = 0.02
    activation_checkpointing: bool = False
    activation_offloading: bool = False
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        cross_attn_cls = _remat(
            CrossAttentionLayer, (8,), self.activation_checkpointing, self.activation_offloading
        )
        self.cross_attn = cross_attn_cls(
            num_heads=self.num_cross_attention_heads,
            num_q_input_channels=self.output_query_provider.num_query_channels,
            num_kv_input_channels=self.num_latent_channels,
            num_qk_channels=self.num_cross_attention_qk_channels,
            num_v_channels=self.num_cross_attention_v_channels,
            widening_factor=self.cross_attention_widening_factor,
            attention_residual=self.cross_attention_residual,
            dropout=self.dropout,
            init_scale=self.init_scale,
            dtype=self.dtype,
            name="cross_attn",
        )

    def __call__(self, x_latent, x_adapted=None, deterministic: bool = True, **adapter_kwargs):
        output_query = self.output_query_provider(x_adapted)
        if output_query.shape[0] != x_latent.shape[0]:
            output_query = jnp.broadcast_to(
                output_query, (x_latent.shape[0],) + output_query.shape[1:]
            )
        with jax.named_scope("cross_attend"):
            output = self.cross_attn(
                output_query, x_latent, None, None, None, None, None, deterministic
            ).last_hidden_state
        with jax.named_scope("output_adapter"):
            return self.output_adapter(output, **adapter_kwargs)


class PerceiverIO(nn.Module):
    """Encoder + decoder composition (reference: modules.py:678-688)."""

    encoder: PerceiverEncoder
    decoder: PerceiverDecoder

    def __call__(self, x, pad_mask=None, deterministic: bool = True, **adapter_kwargs):
        x_latent = self.encoder(x, pad_mask=pad_mask, deterministic=deterministic)
        return self.decoder(x_latent, deterministic=deterministic, **adapter_kwargs)


class PerceiverAR(nn.Module):
    """Perceiver AR (arXiv:2202.07765): one causal cross-attention of the
    latent suffix over [prefix; latents], then a causal self-attention stack
    over the latents, with right-aligned RoPE
    (reference: modules.py:691-871).

    The ``input_adapter`` must return ``(embedded, frq_pos_enc)`` (the
    RotarySupport contract, reference: adapter.py:22-32).

    Call modes:
      - ``kv_cache=None``: plain forward (training / eval).
      - ``kv_cache=..., decode=False``: init call — full forward that also
        populates the caches (prefix split applies).
      - ``kv_cache=..., decode=True``: incremental decode — the whole input is
        latent, positions continue from the cache length.
    """

    input_adapter: nn.Module
    num_heads: int = 8
    max_heads_parallel: Optional[int] = None
    num_self_attention_layers: int = 6
    num_self_attention_rotary_layers: int = 1
    self_attention_widening_factor: int = 4
    cross_attention_widening_factor: int = 4
    cross_attention_dropout: float = 0.5
    # "gather" (default): drop prefix positions by a static-count selection —
    # also shrinks the CA kernel's kv length by the dropped count. On the
    # statically un-padded path with a token adapter the selection is applied
    # to token ids / position-table rows BEFORE embedding ("compact" route,
    # round 5); otherwise to embedded rows. "gather_embed": force the
    # embedded-row gather everywhere (the round-4 implementation, kept as the
    # reproducible A/B lever — docs/performance.md). "mask": keep the
    # full-length prefix and mask dropped positions out of the CA softmax
    # (SURVEY §7.3) — numerically identical, measured slower at the 16k
    # flagship (docs/performance.md round-4 A/B).
    prefix_dropout_mode: str = "gather"
    post_attention_dropout: float = 0.0
    residual_dropout: float = 0.0
    activation_checkpointing: bool = False
    activation_offloading: bool = False
    init_scale: float = 0.02
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        if self.prefix_dropout_mode not in ("gather", "gather_embed", "mask"):
            raise ValueError(f"unknown prefix_dropout_mode: {self.prefix_dropout_mode!r}")
        num_channels = self.input_adapter.num_input_channels
        cross_attn_cls = _remat(
            CrossAttentionLayer, (8,), self.activation_checkpointing, self.activation_offloading
        )
        self.cross_attention = cross_attn_cls(
            num_heads=self.num_heads,
            num_q_input_channels=num_channels,
            num_kv_input_channels=num_channels,
            max_heads_parallel=self.max_heads_parallel,
            causal_attention=True,
            widening_factor=self.cross_attention_widening_factor,
            dropout=self.post_attention_dropout,
            residual_dropout=self.residual_dropout,
            qkv_bias=False,
            out_bias=True,
            mlp_bias=False,
            init_scale=self.init_scale,
            dtype=self.dtype,
            name="cross_attention",
        )
        self.self_attention = SelfAttentionBlock(
            num_layers=self.num_self_attention_layers,
            num_heads=self.num_heads,
            num_channels=num_channels,
            causal_attention=True,
            widening_factor=self.self_attention_widening_factor,
            dropout=self.post_attention_dropout,
            residual_dropout=self.residual_dropout,
            num_rotary_layers=self.num_self_attention_rotary_layers,
            activation_checkpointing=self.activation_checkpointing,
            activation_offloading=self.activation_offloading,
            qkv_bias=False,
            out_bias=False,
            mlp_bias=False,
            init_scale=self.init_scale,
            dtype=self.dtype,
            name="self_attention",
        )

    @property
    def rotated_channels(self) -> int:
        return self.input_adapter.rotated_channels_per_head

    def __call__(
        self,
        x,
        prefix_len: int,
        pad_mask=None,
        kv_cache: Optional[Tuple[KVCache, ...]] = None,
        decode: bool = False,
        deterministic: bool = True,
        sa_pad_mask=None,
        pos_shift=None,
        prefix_keep_idx=None,
        pos_offset=None,
    ) -> BlockOutput:
        """``sa_pad_mask``/``pos_shift`` apply to decode steps only:
        slot masks for the self-attention caches (expired sliding-window
        slots) and an explicit left-pad position shift (B, 1) — needed when
        ``pad_mask`` also marks expired slots and can no longer double as the
        left-pad count (see generation.py's roll-free sliding window).

        ``prefix_keep_idx``: optional host-sampled prefix-dropout keep set,
        (B, keep) int32, **sorted unique per row**, where
        ``keep = prefix_len - int(prefix_len * cross_attention_dropout)``.
        When given, the in-graph subset draw (``top_k`` + ``sort`` over the
        prefix — a full on-device sort, ~0.9 ms/step at the 16k flagship) is
        skipped; the draw runs on the host where it overlaps device compute
        through the input pipeline (training.prefix_dropout). The
        distribution is identical: a uniformly random size-``keep`` subset,
        exactly the reference's ``torch.topk``-of-uniforms draw
        (reference: modules.py:814-819).

        **Failure mode (host-supplied indices are trusted input):** the
        gathers' scatter-free VJPs (`ops/gathers.py`) assume each row of
        ``prefix_keep_idx`` is unique (and sorted, on the compact route). A
        duplicated index does NOT error — the forward gathers the row twice
        but the inverted-map backward credits only one copy, silently
        corrupting d_embedding/d_position-table. Verify suspect pipelines
        with ``ops.gathers.debug_unique_indices()``.

        ``pos_offset``: optional absolute start position for the whole input
        (scalar, possibly traced) — the Shareline shared-prefill seam: when a
        prompt's leading ``pos_offset`` tokens are already resident in the
        cross-attention cache (gathered from shared pool pages), the forward
        runs over the SUFFIX alone, whose token ``i`` sits at absolute
        position ``pos_offset + i``. Rotate-at-write keys and the
        right-aligned causal mask make the result bit-exact equal to the
        full-prompt forward on the einsum attend route (pinned by
        tests/test_pages.py decode_shared)."""
        if decode and kv_cache is None:
            raise ValueError("decode=True requires kv_cache")
        if pos_offset is not None and decode:
            raise ValueError("pos_offset applies to the forward route; decode "
                             "steps derive positions from the cache fill level")
        if kv_cache is not None and not deterministic and self.cross_attention_dropout > 0.0:
            # reference: modules.py:810-812
            raise ValueError("cross-attention dropout not supported with caching")

        if decode:
            if prefix_keep_idx is not None:
                raise ValueError("prefix_keep_idx applies to training forwards, not decode steps")
            return self._decode_step(
                x,
                pad_mask=pad_mask,
                kv_cache=kv_cache,
                deterministic=deterministic,
                sa_pad_mask=sa_pad_mask,
                pos_shift=pos_shift,
            )
        return self._forward(
            x,
            prefix_len=prefix_len,
            pad_mask=pad_mask,
            kv_cache=kv_cache,
            deterministic=deterministic,
            prefix_keep_idx=prefix_keep_idx,
            pos_offset=pos_offset,
        )

    def _forward(self, x, prefix_len, pad_mask, kv_cache, deterministic,
                 prefix_keep_idx=None, pos_offset=None):
        b, n = x.shape[0], x.shape[1]
        if not 0 <= prefix_len < n:
            raise ValueError(f"prefix_len ({prefix_len}) out of valid range [0..{n})")

        dropout_active = (
            not deterministic and prefix_len > 0 and self.cross_attention_dropout > 0.0
        )
        if pos_offset is not None and dropout_active:
            # the compact embed route below draws its keep set over positions
            # 0..prefix_len and would silently ignore the offset
            raise ValueError("pos_offset is a serving-forward seam; "
                             "cross-attention dropout is not supported with it")
        # static keep count (training/prefix_dropout.prefix_keep_count)
        keep = prefix_len - int(prefix_len * self.cross_attention_dropout)
        if dropout_active and prefix_keep_idx is not None:
            if prefix_keep_idx.shape[-1] != keep:
                raise ValueError(
                    f"prefix_keep_idx carries {prefix_keep_idx.shape[-1]} indices; "
                    f"this config keeps {keep} of {prefix_len} prefix positions"
                )

        # Compact route (default "gather" mode, statically un-padded input,
        # token adapter): apply the dropout selection to token ids and
        # position-table rows BEFORE embedding, so the full-length (B, N, C)
        # embedding and its row-gather (forward + inverse-gather backward,
        # ~1.2 ms/step at the 16k flagship at batch 4) never exist. Numerically the
        # embedded-row gather below: embedding is a per-position lookup, so
        # gather-then-embed == embed-then-gather row for row.
        if (
            dropout_active
            and self.prefix_dropout_mode == "gather"
            and pad_mask is None
            and hasattr(self.input_adapter, "embed_compact")
        ):
            with jax.named_scope("prefix_dropout"):
                if prefix_keep_idx is not None:
                    keep_idx = prefix_keep_idx
                else:
                    rand = jax.random.uniform(self.make_rng("dropout"), (b, prefix_len))
                    _, keep_idx = lax.top_k(rand, keep)
                    keep_idx = jnp.sort(keep_idx, axis=-1)
            with jax.named_scope("embed"):
                x_emb, frq = self.input_adapter.embed_compact(x, keep_idx, prefix_len)
            x_emb = probe("perceiver_ar.embed", x_emb)
            with jax.named_scope("embed"):  # the split, and in the backward the sum of the two halves' gradients
                x_prefix, x_latent = x_emb[:, :keep], x_emb[:, keep:]
                frq_prefix, frq_latent = frq[:, :keep], frq[:, keep:]
            return self._attend(
                x_latent, x_prefix, frq_latent, frq_prefix,
                pad_latent=None, pad_prefix=None,
                kv_cache=kv_cache, deterministic=deterministic,
            )

        # pad_mask None statically means positions are arange(n) — the adapter
        # then embeds positions via a table slice (scatter-free backward)
        with jax.named_scope("embed"):
            if pad_mask is None:
                pos = None if pos_offset is None else positions(b, n, offset=pos_offset)
                x_emb, frq = self.input_adapter(x, pos)
                pad_latent = pad_prefix = None
            else:
                shift = pad_mask.sum(axis=1, keepdims=True).astype(jnp.int32)
                x_emb, frq = self.input_adapter(x, positions(b, n, shift=shift, offset=pos_offset))
                pad_latent, pad_prefix = pad_mask[:, prefix_len:], pad_mask[:, :prefix_len]

        x_emb = probe("perceiver_ar.embed", x_emb)
        with jax.named_scope("embed"):
            x_latent, x_prefix = x_emb[:, prefix_len:], x_emb[:, :prefix_len]
            frq_latent, frq_prefix = frq[:, prefix_len:], frq[:, :prefix_len]

        if dropout_active:
            with jax.named_scope("prefix_dropout"):
                # Static-count prefix dropout: keep `keep` positions, chosen
                # uniformly, order preserved (reference: modules.py:809-830).
                if prefix_keep_idx is not None:
                    keep_idx, rand = prefix_keep_idx, None
                else:
                    rand = jax.random.uniform(self.make_rng("dropout"), (b, prefix_len))
                    keep_idx = None
                    if self.prefix_dropout_mode != "mask":
                        _, keep_idx = lax.top_k(rand, keep)
                        keep_idx = jnp.sort(keep_idx, axis=-1)

                if self.prefix_dropout_mode == "mask":
                    # Keep-mask form (SURVEY §7.3): the prefix stays full length
                    # and dropped positions are masked out of the CA softmax —
                    # numerically the gathered softmax. Measured SLOWER than the
                    # gather at the 16k flagship: the gather also nearly halves
                    # the flash CA kernel work (kv 8704 vs 16384), which outweighs
                    # the gather machinery it removes (docs/performance.md,
                    # round-4 A/B table). Kept as an option and for the
                    # seq-parallel path, where masking is structurally required.
                    if rand is None:
                        keep_mask = jnp.zeros((b, prefix_len), bool)
                        keep_mask = keep_mask.at[jnp.arange(b)[:, None], keep_idx].set(True)
                    else:
                        # threshold at the keep-th largest uniform: the same keep
                        # set top_k would select, without materializing indices
                        thr, _ = lax.top_k(rand, keep)
                        keep_mask = rand >= thr[:, -1:]
                    drop = ~keep_mask
                    pad_prefix = drop if pad_prefix is None else (pad_prefix | drop)
                    if pad_latent is None:
                        pad_latent = jnp.zeros((b, n - prefix_len), bool)
                else:
                    # gather-backward gather (ops/gathers.py): the scatter-add VJP
                    # of this row gather costs ~0.8 ms/step at the 16k flagship
                    from perceiver_io_tpu.ops.gathers import gather_rows

                    x_prefix = gather_rows(x_prefix, keep_idx)
                    frq_prefix = jnp.take_along_axis(frq_prefix, keep_idx[..., None], axis=1)
                    if pad_prefix is not None:
                        pad_prefix = jnp.take_along_axis(pad_prefix, keep_idx, axis=1)

        return self._attend(
            x_latent, x_prefix, frq_latent, frq_prefix,
            pad_latent=pad_latent, pad_prefix=pad_prefix,
            kv_cache=kv_cache, deterministic=deterministic,
        )

    def _attend(
        self, x_latent, x_prefix, frq_latent, frq_prefix,
        *, pad_latent, pad_prefix, kv_cache, deterministic,
    ) -> BlockOutput:
        """Cross-attention over [prefix; latents] + the latent self-attention
        stack — the shared tail of both `_forward` embedding routes."""
        rope_q = frq_latent
        rope_k_ca = jnp.concatenate([frq_prefix, frq_latent], axis=1)
        pad_ca = None if pad_prefix is None else jnp.concatenate([pad_prefix, pad_latent], axis=1)

        if kv_cache is None:
            ca_cache, sa_cache = None, None
        else:
            ca_cache, sa_cache = kv_cache[0], tuple(kv_cache[1:])
            # the pad mask reads against cache slots — align it to capacity
            # (rope_k_ca needs no alignment: keys rotate at write, so it
            # covers exactly the appended tokens)
            if pad_ca is not None:
                ca_capacity = ca_cache.capacity
                pad_ca = jnp.pad(pad_ca, ((0, 0), (0, ca_capacity - pad_ca.shape[1])))

        with jax.named_scope("cross_attend"):
            ca_out = self.cross_attention(
                x_latent,
                None,
                x_prefix,
                pad_ca,
                rope_q,
                rope_k_ca,
                ca_cache,
                deterministic,
            )
        with jax.named_scope("self_attend"):
            sa_out = self.self_attention(
                probe("perceiver_ar.cross_attend", ca_out.last_hidden_state),
                None,
                frq_latent,
                frq_latent,
                sa_cache,
                deterministic,
            )

        if kv_cache is None:
            new_cache = None
        else:
            new_cache = (ca_out.kv_cache,) + tuple(sa_out.kv_cache)
        return BlockOutput(last_hidden_state=sa_out.last_hidden_state, kv_cache=new_cache)

    def seq_parallel_forward(
        self,
        x_latent,
        frq_latent,
        x_prefix_local,
        frq_prefix_local,
        *,
        axis_name: str,
        prefix_pad_local=None,
        deterministic: bool = True,
    ):
        """Sequence-parallel forward with the **prefix sharded** over the mesh
        axis ``axis_name`` — call inside ``jax.shard_map``.

        This is the explicit-overlap wiring of the ring/blockwise kernels into
        the model (SURVEY §5.7: shard the prefix KV axis — beyond reference
        parity; the reference handles long context single-device,
        perceiver/model/core/modules.py:850-866). The decomposition follows
        the Perceiver AR structure: latents (queries) are replicated, the
        long prefix is sharded, so the causal cross-attention over
        [prefix; latents] splits exactly into

        - a per-device partial over the local prefix block (no causal mask —
          every prefix position precedes every latent), LSE-combined across
          the axis with one ``pmax`` + two ``psum`` (communication O(latents),
          independent of context length), and
        - a local causal partial over the latent block (replicated),

        merged with an online-softmax combine — numerically identical to the
        dense forward. The latent self-attention stack is small (O(latents²))
        and runs replicated; no communication.

        Inputs are pre-embedded (see ``CausalSequenceModel.seq_parallel_forward``
        for the token-level entry): ``x_latent``/``frq_latent`` (B, L, C)/(B, L, R)
        replicated, ``x_prefix_local``/``frq_prefix_local`` the per-device
        prefix block, ``prefix_pad_local`` (B, P_local) True at padding.

        Training (``deterministic=False``) supports the reference's prefix
        cross-attention dropout (default 0.5, reference: modules.py:809-830)
        as a **keep-mask**: every device draws the dense path's exact keep
        set from the replicated ``'dropout'`` rng (same ``make_rng`` fold,
        same ``top_k`` draw over the global prefix) and masks its local
        block's dropped positions — masked softmax over the kept set is
        numerically the dense path's gathered softmax (SURVEY §7.3:
        masking, not gather). Post-attention/residual dropout stay
        unsupported here (the hand-wired cross-attention block applies
        none, so enabling them only in the SA stack would silently diverge
        from the dense path).
        """
        from perceiver_io_tpu.ops.online_softmax import (
            block_attention,
            finalize,
            online_combine,
        )

        if not deterministic and (
            self.post_attention_dropout > 0.0 or self.residual_dropout > 0.0
        ):
            raise ValueError(
                "post-attention/residual dropout is not supported on the "
                "sequence-parallel path; set post_attention_dropout/"
                "residual_dropout to 0 or pass deterministic=True"
            )

        ca_layer = self.cross_attention
        ca = ca_layer.cross_attn
        mha = ca.attention

        # Reference KV construction for the prefix mode (modules.py:222-224):
        # x_kv = concat(kv_norm(prefix), q_norm(latents)).
        q_in = ca.q_norm(x_latent)
        kv_prefix = ca.kv_norm(x_prefix_local)

        q = mha.project_q(q_in, rope_q=frq_latent)
        k_p, v_p = mha.project_kv(kv_prefix, rope_k=frq_prefix_local)
        k_l, v_l = mha.project_kv(q_in, rope_k=frq_latent)

        # per-device prefix partial; all prefix positions precede all latents,
        # so only the pad mask (and the training keep-mask) applies
        b = x_latent.shape[0]
        p_local = x_prefix_local.shape[1]
        mask_p = jnp.zeros((b, p_local), bool)
        if prefix_pad_local is not None:
            mask_p = mask_p | prefix_pad_local
        if not deterministic and self.cross_attention_dropout > 0.0 and p_local > 0:
            # the dense path's static-count keep set (see _forward), drawn
            # identically on every device from the replicated rng, then
            # sliced to this device's block
            p_total = p_local * lax.axis_size(axis_name)
            keep = p_total - int(p_total * self.cross_attention_dropout)
            rand = jax.random.uniform(self.make_rng("dropout"), (b, p_total))
            _, keep_idx = lax.top_k(rand, keep)
            keep_mask = jnp.zeros((b, p_total), bool)
            keep_mask = keep_mask.at[jnp.arange(b)[:, None], keep_idx].set(True)
            start = lax.axis_index(axis_name) * p_local
            keep_local = lax.dynamic_slice_in_dim(keep_mask, start, p_local, axis=1)
            mask_p = mask_p | ~keep_local

        # the prefix partial + its O(L) LSE-combine across the axis is the
        # ring/sequence-parallel CA primitive (parallel/ring_attention.py —
        # the path --trainer.strategy=ring reaches)
        from perceiver_io_tpu.parallel.ring_attention import seq_sharded_cross_attention

        o_p, m_glob, l_p = seq_sharded_cross_attention(
            q, k_p, v_p, mask_p, axis_name=axis_name, causal=False, finalize=False
        )

        # replicated causal latent partial
        n_lat = x_latent.shape[1]
        lat_idx = jnp.arange(n_lat, dtype=jnp.int32)
        masked_l = (lat_idx[None, None, None, :] > lat_idx[None, None, :, None])
        o_l, m_l, l_l = block_attention(q, k_l, v_l, masked_l)

        o, _, l = online_combine((o_p, m_glob, l_p), (o_l, m_l, l_l))
        h_attn = mha.merge_output(finalize(o, l).astype(x_latent.dtype))

        # cross-attention layer residuals + MLP (dropout inactive: deterministic)
        h = x_latent + h_attn
        h = h + ca_layer.mlp(h)

        sa_out = self.self_attention(
            h, None, frq_latent, frq_latent, None, deterministic
        )
        return sa_out.last_hidden_state

    def _decode_step(self, x, pad_mask, kv_cache, deterministic, sa_pad_mask=None, pos_shift=None):
        """One incremental step: the whole input is latent; absolute positions
        continue from the cache fill level (dynamic values, static shapes).
        Cached keys carry their rotation from write time, so only the new
        tokens' encodings are computed — O(1) rotary work per step instead of
        O(window)."""
        b, n_x = x.shape[0], x.shape[1]
        ca_cache, sa_cache = kv_cache[0], tuple(kv_cache[1:])

        if pos_shift is not None:
            shift = pos_shift
        else:
            shift = None if pad_mask is None else pad_mask.sum(axis=1, keepdims=True).astype(jnp.int32)
        n_total = ca_cache.length + n_x  # dynamic
        offset = n_total - n_x
        if getattr(offset, "ndim", 0) == 1:
            # paged cache: per-slot lengths (B,) — each decode slot continues
            # from its own fill level (ragged batching); the contiguous
            # cache's scalar length takes the branch above unchanged
            offset = offset[:, None]
        q_pos = positions(b, n_x, shift=shift, offset=offset)

        with jax.named_scope("embed"):
            x_emb, frq_q = self.input_adapter(x, q_pos)

        x_prefix = jnp.zeros((b, 0, x_emb.shape[-1]), dtype=x_emb.dtype)

        with jax.named_scope("cross_attend"):
            ca_out = self.cross_attention(
                x_emb, None, x_prefix, pad_mask, frq_q, frq_q, ca_cache, deterministic
            )
        with jax.named_scope("self_attend"):
            sa_out = self.self_attention(
                probe("perceiver_ar.cross_attend", ca_out.last_hidden_state),
                sa_pad_mask, frq_q, frq_q, sa_cache, deterministic,
            )
        new_cache = (ca_out.kv_cache,) + tuple(sa_out.kv_cache)
        return BlockOutput(last_hidden_state=sa_out.last_hidden_state, kv_cache=new_cache)


class CausalSequenceModel(nn.Module):
    """Perceiver AR + token input adapter + optional final LayerNorm +
    tied-embedding logits (reference: modules.py:874-930)."""

    config: CausalSequenceModelConfig
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        from perceiver_io_tpu.core.adapter import TiedTokenOutputAdapter, TokenInputAdapterWithRotarySupport

        cfg = self.config
        num_rotated_channels = cfg.num_channels // cfg.num_heads
        if cfg.abs_pos_emb:
            # rotary embedding only for the first 50% of head channels
            num_rotated_channels //= 2

        self.input_adapter = TokenInputAdapterWithRotarySupport(
            vocab_size=cfg.vocab_size,
            max_seq_len=cfg.max_seq_len,
            num_input_channels=cfg.num_channels,
            abs_pos_emb=cfg.abs_pos_emb,
            rotated_channels_per_head=num_rotated_channels,
            init_scale=cfg.init_scale,
            dtype=self.dtype,
            name="input_adapter",
        )
        ar_kwargs = cfg.base_kwargs()
        self.perceiver_ar = PerceiverAR(
            input_adapter=self.input_adapter,
            init_scale=cfg.init_scale,
            dtype=self.dtype,
            name="perceiver_ar",
            **ar_kwargs,
        )
        if cfg.output_norm:
            self.out_norm = LayerNorm(epsilon=LAYER_NORM_EPSILON, dtype=self.dtype)
        self.output_adapter = TiedTokenOutputAdapter(
            vocab_size=cfg.vocab_size, emb_bias=cfg.output_bias, dtype=self.dtype
        )

    @property
    def max_seq_len(self) -> int:
        return self.config.max_seq_len

    @property
    def max_latents(self) -> int:
        return self.config.max_latents

    @property
    def max_prefix_len(self) -> int:
        return self.config.max_seq_len - self.config.max_latents

    @staticmethod
    def init_cache(
        config: CausalSequenceModelConfig,
        batch_size: int,
        ca_capacity: Optional[int] = None,
        sa_capacity: Optional[int] = None,
        dtype=jnp.float32,
    ) -> Tuple[KVCache, ...]:
        """Empty fixed-capacity caches: one cross-attention cache over the full
        window and one cache per self-attention layer over the latents."""
        ca_capacity = ca_capacity or config.max_seq_len
        sa_capacity = sa_capacity or config.max_latents
        ca = init_kv_cache(batch_size, ca_capacity, config.num_channels, config.num_channels, dtype)
        sas = tuple(
            init_kv_cache(batch_size, sa_capacity, config.num_channels, config.num_channels, dtype)
            for _ in range(config.num_self_attention_layers)
        )
        return (ca,) + sas

    @staticmethod
    def init_paged_cache(
        config: CausalSequenceModelConfig,
        slots: int,
        page_size: int,
        ca_num_pages: int,
        ca_pages_per_slot: int,
        sa_num_pages: int,
        sa_pages_per_slot: int,
        dtype=jnp.float32,
    ):
        """Empty paged caches for the batched decode engine: one page pool
        for the cross-attention window and one per self-attention layer.
        Every SA layer shares one page-id space (layers append in lockstep,
        so one allocation covers them all — the engine writes identical
        page tables into each layer's cache pytree)."""
        from perceiver_io_tpu.core.cache import init_paged_kv_cache

        c = config.num_channels
        ca = init_paged_kv_cache(
            slots, ca_num_pages, page_size, ca_pages_per_slot, c, c, dtype
        )
        sas = tuple(
            init_paged_kv_cache(
                slots, sa_num_pages, page_size, sa_pages_per_slot, c, c, dtype
            )
            for _ in range(config.num_self_attention_layers)
        )
        return (ca,) + sas

    def seq_parallel_forward(
        self,
        latent_ids,
        prefix_ids_local,
        *,
        axis_name: str,
        prefix_pad_local=None,
        deterministic: bool = True,
    ):
        """Token-level sequence-parallel forward — call inside ``shard_map``
        with ``latent_ids`` (B, L) replicated and ``prefix_ids_local``
        (B, P/n_dev) this device's prefix block (see
        ``parallel.long_context.make_seq_parallel_clm_forward`` for the
        whole-array wrapper). Returns replicated latent logits (B, L, V).

        Absolute positions are global: device ``i`` embeds prefix positions
        ``[i*P_local, (i+1)*P_local)``; latents sit at ``[P, P+L)``. Left
        padding shifts positions by the global pad count (``psum`` over the
        axis), matching the dense path's ``positions()`` shift
        (reference: perceiver/model/core/modules.py:775-779).
        """
        b, n_lat = latent_ids.shape
        p_local = prefix_ids_local.shape[1]
        n_dev = lax.axis_size(axis_name)
        idx = lax.axis_index(axis_name)
        p_total = p_local * n_dev

        # the dense __call__ validation (window bounds), on static shapes
        if p_total > self.max_prefix_len:
            raise ValueError(
                f"prefix_len ({p_total}) exceeds max_prefix_len ({self.max_prefix_len})"
            )
        if not 0 < n_lat <= self.max_latents:
            raise ValueError(
                f"number of latent positions ({n_lat}) out of valid range "
                f"[1..{self.max_latents}]"
            )

        shift = None
        if prefix_pad_local is not None:
            local_pad = prefix_pad_local.sum(axis=1, keepdims=True).astype(jnp.int32)
            shift = lax.psum(local_pad, axis_name)

        pos_prefix = positions(b, p_local, shift=shift, offset=idx * p_local)
        pos_latent = positions(b, n_lat, shift=shift, offset=p_total)

        emb_prefix, frq_prefix = self.input_adapter(prefix_ids_local, pos_prefix)
        emb_latent, frq_latent = self.input_adapter(latent_ids, pos_latent)

        h = self.perceiver_ar.seq_parallel_forward(
            emb_latent,
            frq_latent,
            emb_prefix,
            frq_prefix,
            axis_name=axis_name,
            prefix_pad_local=prefix_pad_local,
            deterministic=deterministic,
        )
        if self.config.output_norm:
            h = self.out_norm(h)
        return self.output_adapter(h, attend=self.input_adapter.attend)

    def __call__(
        self,
        x,
        prefix_len: int,
        pad_mask=None,
        kv_cache: Optional[Tuple[KVCache, ...]] = None,
        decode: bool = False,
        deterministic: bool = True,
        sa_pad_mask=None,
        pos_shift=None,
        prefix_keep_idx=None,
        pos_offset=None,
    ) -> CausalModelOutput:
        if prefix_len > self.max_prefix_len:
            raise ValueError(
                f"prefix_len ({prefix_len}) exceeds max_prefix_len ({self.max_prefix_len})"
            )
        out = self.perceiver_ar(
            x,
            prefix_len=prefix_len,
            pad_mask=pad_mask,
            kv_cache=kv_cache,
            decode=decode,
            deterministic=deterministic,
            sa_pad_mask=sa_pad_mask,
            pos_shift=pos_shift,
            prefix_keep_idx=prefix_keep_idx,
            pos_offset=pos_offset,
        )
        h = out.last_hidden_state
        with jax.named_scope("logits"):
            if self.config.output_norm:
                h = self.out_norm(h)
            logits = probe("logits", self.output_adapter(h, attend=self.input_adapter.attend))
        return CausalModelOutput(last_hidden_state=h, logits=logits, kv_cache=out.kv_cache)
