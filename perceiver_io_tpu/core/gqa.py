"""Grouped-query attention with rotary by layer type (the Mellum family:
``layer_types`` mixes ``sliding_attention`` and ``full_attention`` layers).

``q = x W_q`` has ``num_attention_heads`` heads, ``k = x W_k`` and
``v = x W_v`` have ``num_key_value_heads``; query head i reads key-value head
``i // group``. No biases. With ``qk_norm`` (the EXAONE family) each head of q
and of k passes an RMSNorm over its ``head_dim`` channels before the rotary.
Rotary on q and k in the half-split
pairing (``core/position.py::apply_rotary_half``): a window layer rotates with
the plain frequencies, a full layer with YaRN's (``rope_scaling``:
frequencies blended between ``f`` and ``f / factor``, cos and sin times
``attention_factor``), or not at all where ``full_attention_rotary`` is off
(the EXAONE family's full layers carry no position). Position i sees
``j <= i`` and, on a window layer,
``j > i - sliding_window``. The softmax scale is ``head_dim ** -0.5``.

One set of weights, two ways through them, as in ``core/mla.py``:

``expand`` (the prompt pass)
    causal self-attention over whole rows through the flash forward of
    ``ops/flash_attention.py::flash_attention_gqa`` (the window kernel walks
    only the kv blocks a q block sees; keys and values are not written out a
    query head). Also returns the rows' rotated keys and their values,
    heads-major, for the caches.

``step`` (one new token against the cache)
    the token's key and value are written first, then XLA's two batched
    products over the cache, the 8 query heads of a group against their one
    key-value head. A full layer's cache is a :class:`KVCache` that grows
    with the context; a window layer's a :class:`WindowKVCache`, a ring of
    ``sliding_window`` slots (keys are stored rotated, so the order of the
    slots does not matter to the softmax). Both keep ``(B * Hkv, slots, D)``:
    a key-value head is a batch row of the products, which then read the
    cache once, in place (a Pallas kernel in their place measured slower:
    ``tools/moe_ab.py --geom mellum``, PERF.md 6, PR 32).

``verify`` (a speculative step: a row's last token and the draft after it)
    ``n`` positions a row, each row at its own length, against the caches
    with a length a row (``core/cache.py::RaggedKVCache``,
    ``RaggedWindowKVCache``): the positions are written, not yet kept, and
    each query sees what ``cache.visible`` says of its position. Where the
    flash kernels run and ``ops/gqa_verify.py::gqa_verify_supported`` holds
    (a head of whole lanes, a capacity of whole sublane tiles, the step's
    positions within a tile and a ring's slack, a row's blocks within the
    VMEM limit: shapes and dtypes alone) the write, the scores of the ``n *
    group`` queries a key-value head, the mask, the softmax and the values
    product are one Pallas call a layer over the caches the decode loop
    carries row-major, each read once and updated in place by the tiles that
    hold the new rows (``gqa_verify_<ring|full>_r.._q.._s.._d..``; PERF.md 6,
    PR 44). Elsewhere ``cache.write`` (XLA's per-row scatters) and the same two
    batched products as ``step``, ``n`` times the group's queries against one
    read of the cache: what the tests hold the kernel to.

Scores and the softmax are float32; products take ``dtype`` operands and
accumulate in float32.
"""

from __future__ import annotations

from typing import Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from perceiver_io_tpu.core.cache import KVCache, RaggedKVCache, RaggedWindowKVCache, WindowKVCache
from perceiver_io_tpu.core.position import apply_rotary_half, yarn_inv_freq
from perceiver_io_tpu.ops.flash_attention import flash_attention_gqa, flash_enabled, gqa_flash_supported
from perceiver_io_tpu.ops.gqa_verify import gqa_verify, gqa_verify_supported
from perceiver_io_tpu.ops.layernorm import RMSNorm

Cache = Union[KVCache, WindowKVCache]
RaggedCache = Union[RaggedKVCache, RaggedWindowKVCache]


class GroupedQueryAttention(nn.Module):
    """``config`` needs ``hidden_size``, ``num_attention_heads``,
    ``num_key_value_heads``, ``head_dim``, ``rope_theta``, ``rope_scaling``
    (``None`` or YaRN's ``factor``, ``beta_fast``, ``beta_slow``,
    ``attention_factor``, ``original_max_position_embeddings``; full layers
    only), ``sliding_window``, ``init_scale``, ``qk_norm`` (with ``rms_norm_eps``)
    and ``full_attention_rotary``. ``window`` says
    which kind of layer this is."""

    config: object
    window: bool
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        c = self.config
        init = nn.initializers.normal(c.init_scale)
        q_width, kv_width = c.num_attention_heads * c.head_dim, c.num_key_value_heads * c.head_dim
        self.w_q = self.param("w_q", init, (c.hidden_size, q_width), self.param_dtype)
        self.w_k = self.param("w_k", init, (c.hidden_size, kv_width), self.param_dtype)
        self.w_v = self.param("w_v", init, (c.hidden_size, kv_width), self.param_dtype)
        self.w_o = self.param("w_o", init, (q_width, c.hidden_size), self.param_dtype)
        if c.qk_norm:
            kw = dict(epsilon=c.rms_norm_eps, dtype=self.dtype, param_dtype=self.param_dtype)
            self.q_norm, self.k_norm = RMSNorm(**kw), RMSNorm(**kw)

    @property
    def span(self) -> str:
        return "attn/window" if self.window else "attn/full"

    def _rotary(self):
        """``(inv_freq, attention_factor)`` of this kind of layer."""
        c, s = self.config, self.config.rope_scaling
        if self.window or s is None:
            return yarn_inv_freq(c.head_dim, c.rope_theta, 1.0, 1.0, 1.0, 1), 1.0
        inv_freq = yarn_inv_freq(c.head_dim, c.rope_theta, s.factor, s.beta_fast, s.beta_slow,
                                 s.original_max_position_embeddings)
        return inv_freq, s.attention_factor

    def _mm(self, x, w):
        return jnp.dot(x.astype(self.dtype), w.astype(self.dtype))

    def _project(self, x, pos):
        """``x`` (B, N, h), ``pos`` (B, N) -> rotated ``q`` (B, N, H, D), rotated ``k`` and ``v`` (B, N, Hkv, D)."""
        c = self.config
        b, n, _ = x.shape
        inv_freq, factor = self._rotary()
        q = self._mm(x, self.w_q).reshape(b, n, c.num_attention_heads, c.head_dim)
        k = self._mm(x, self.w_k).reshape(b, n, c.num_key_value_heads, c.head_dim)
        v = self._mm(x, self.w_v).reshape(b, n, c.num_key_value_heads, c.head_dim)
        if c.qk_norm:
            q, k = self.q_norm(q), self.k_norm(k)
        if not (self.window or c.full_attention_rotary):
            return q, k, v
        return (apply_rotary_half(q, pos[:, :, None], inv_freq, factor),
                apply_rotary_half(k, pos[:, :, None], inv_freq, factor), v)

    # ------------------------------------------------------ the prompt pass

    def expand(self, x, pos) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
        """Causal (windowed) self-attention of ``x`` (B, N, h) over itself.
        Returns the output (B, N, h) and the cache rows of these tokens:
        rotated keys and values, heads-major (B, Hkv, N, D)."""
        c = self.config
        b, n, _ = x.shape
        heads, kv_heads, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        window = c.sliding_window if self.window else None
        with jax.named_scope(self.span):
            q, k, v = self._project(x, pos)
            k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
            if flash_enabled() and gqa_flash_supported(n, d):
                o = flash_attention_gqa(q.reshape(b, n, heads * d), k, v, heads, window=window, sm_scale=d ** -0.5)
            else:
                qg = q.reshape(b, n, kv_heads, heads // kv_heads, d)
                s = jnp.einsum("bigqd,bgjd->bgqij", qg, k, preferred_element_type=jnp.float32) * d ** -0.5
                i, j = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
                visible = (j <= i) if window is None else (j <= i) & (j > i - window)
                p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
                o = jnp.einsum("bgqij,bgjd->bigqd", p.astype(v.dtype), v).reshape(b, n, heads * d)
            return self._mm(o, self.w_o), (k, v)

    # ------------------------------------------------------------- one step

    def step(self, x, cache: Cache, pos) -> Tuple[jnp.ndarray, Cache]:
        """One new token a row, ``x`` (B, 1, h) at positions ``pos`` (B, 1),
        against ``cache``: its key and value are written first."""
        c = self.config
        b = x.shape[0]
        heads, kv_heads, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        with jax.named_scope(self.span):
            q, k, v = self._project(x, pos)
            with jax.named_scope("kv_cache_append"):
                cache = cache.append(k.reshape(b * kv_heads, 1, d), v.reshape(b * kv_heads, 1, d))
            o = cached_decode_attention(q.reshape(b * kv_heads, heads // kv_heads, d), cache, d ** -0.5)
            return self._mm(o.astype(self.dtype).reshape(b, 1, heads * d), self.w_o), cache


    # ---------------------------------------------------- a speculative step

    def verify(self, x, cache: RaggedCache, pos) -> Tuple[jnp.ndarray, RaggedCache]:
        """``n`` positions a row, ``x`` (B, n, h) at ``pos`` (B, n) =
        ``cache.length + 0 .. n - 1``: their keys and values are written (the
        caller keeps those that stay), and query i sees the cache as of its
        own position."""
        c = self.config
        b, n, _ = x.shape
        heads, kv_heads, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        group = heads // kv_heads
        with jax.named_scope(self.span):
            q, k, v = self._project(x, pos)
            window = cache.window if isinstance(cache, RaggedWindowKVCache) else None
            fused = verify_fused(cache.k.shape, cache.k.dtype, kv_heads, n, group, window)
            k, v = (r.transpose(0, 2, 1, 3).reshape(b * kv_heads, n, d) for r in (k, v))
            if not fused:  # XLA's per-row scatters, where they always stood in the step
                with jax.named_scope("kv_cache_write"):
                    cache = cache.write(k, v)
            qg = q.reshape(b, n, kv_heads, group, d).transpose(0, 2, 1, 3, 4).reshape(b * kv_heads, n * group, d)
            if fused:  # write and attend in one kernel over the caches, which it updates in place
                k, v, o = gqa_verify(qg, k, v, cache.k, cache.v, cache.length, heads=kv_heads, window=window, sm_scale=d ** -0.5)
                cache = cache.replace(k=k, v=v)
            else:
                o = cached_verify_attention(qg, cache, cache.visible(n, group), d ** -0.5)
            o = o.astype(self.dtype).reshape(b, kv_heads, n, group, d).transpose(0, 2, 1, 3, 4).reshape(b, n, heads * d)
            return self._mm(o, self.w_o), cache


def verify_fused(cache_shape, dtype, kv_heads: int, n: int, group: int, window=None) -> bool:
    """Whether :meth:`GroupedQueryAttention.verify` hands a cache of
    ``cache_shape`` (B * Hkv, slots, D) and ``dtype`` to the kernel
    (``ops/gqa_verify.py``): where the flash kernels run and the kernel's own
    rule takes the shapes. The one rule: the decoder's ``compile`` row asks it too."""
    return flash_enabled() and gqa_verify_supported(cache_shape, dtype, kv_heads, n, group, window)


def cached_verify_attention(q: jnp.ndarray, cache: RaggedCache, visible: jnp.ndarray, sm_scale: float) -> jnp.ndarray:
    """``q`` (B * Hkv, queries, D) against ``cache.k`` / ``cache.v``
    (B * Hkv, slots, D), query by query what ``visible`` (B * Hkv, queries,
    slots) shows. Returns ``softmax(q . k) @ v`` (B * Hkv, queries, D) in
    float32: :func:`cached_decode_attention`'s two products with a mask a query."""
    s = jnp.einsum("bqd,bsd->bqs", q.astype(cache.k.dtype), cache.k, preferred_element_type=jnp.float32) * sm_scale
    p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
    return jnp.einsum("bqs,bsd->bqd", p.astype(cache.v.dtype), cache.v, preferred_element_type=jnp.float32)


def cached_decode_attention(q: jnp.ndarray, cache: Cache, sm_scale: float) -> jnp.ndarray:
    """One query a row and query head: ``q`` (B * Hkv, group, D) against
    ``cache.k`` / ``cache.v`` (B * Hkv, slots, D), the slots that hold no live
    token masked: those at or past ``length`` of a growing cache, those at or
    past ``min(length, slots)`` of a ring (once it has wrapped every slot is
    inside the window). Returns ``softmax(q . k) @ v`` (B * Hkv, group, D) in
    float32. Two batched products in XLA; the softmax is float32."""
    s = jnp.einsum("bqd,bsd->bqs", q.astype(cache.k.dtype), cache.k, preferred_element_type=jnp.float32) * sm_scale
    valid = jnp.arange(cache.capacity, dtype=jnp.int32) < cache.length
    p = jax.nn.softmax(jnp.where(valid[None, None, :], s, -jnp.inf), axis=-1)
    return jnp.einsum("bqs,bsd->bqd", p.astype(cache.v.dtype), cache.v, preferred_element_type=jnp.float32)
