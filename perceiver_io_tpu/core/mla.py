"""Multi-head latent attention (DeepSeek-V2/V3, arXiv:2412.19437 section 2.1.1).

Keys and values of all heads are functions of one low-rank latent per token,
``c_kv`` (``kv_lora_rank`` channels), plus one rotary key ``k_rope`` that every
head shares. The cache therefore holds one joint row a token
(:class:`~perceiver_io_tpu.core.cache.LatentCache`), not a key and a value per
head. One set of weights, two ways to compute the same function:

``expand`` (the prompt pass)
    per-head keys ``[k_nope; k_rope]`` and values are built from the latent
    and go through ordinary causal attention (the flash kernels on a TPU,
    ``d_qk`` = nope + rope, ``d_v`` = v). Right for many queries at once:
    the up-projection is paid once a token. At the published head widths
    (128 + 64 rotary, 128 value channels; an even number of heads, rows in
    whole blocks) the kernel is ``flash_attention_mla``, which reads the
    up-projections' outputs token-major as they are written: a score is
    ``q_nope . k_nope + q_rope . k_rope`` with the one ``k_rope`` a token
    read by every head, the queries' rotary halves are turned by the
    lane-rolling kernel of ``ops/rotary.py``, and nothing is concatenated,
    written out a head at a time or turned heads-major (PERF.md 6, PR 42).
    Other shapes, and the CPU, take the heads-major kernel or XLA on
    concatenated operands: the same function to rounding.

``absorb`` (one new token against the cache)
    ``q_nope . (c_kv W_uk) = (q_nope W_uk^T) . c_kv``: the query is carried
    into the latent space, scores and values are read from the cached rows
    that all heads share, and ``W_uv`` is applied to the attended latent.
    The cache is read once a row for all heads, and nothing per head is
    ever built for the cached tokens. Where the flash kernels run (a TPU)
    the cache side of the step, the append of the new row and the attention
    over the rows, is one kernel over the row-major cache, which it updates
    in place (``ops/mla_absorb.py``); elsewhere ``LatentCache.append`` and
    :func:`latent_decode_attention`, the same arithmetic in XLA.

Rotary: YaRN frequencies on the ``qk_rope_head_dim`` channels of the queries
and on ``k_rope``, adjacent channels paired (``core/position.py``); the
softmax scale is ``(nope + rope)^-0.5 * mscale^2``. RMSNorm on both latents.
No biases. Scores and the softmax are float32; products take ``dtype``
operands and accumulate in float32.

Two switches of the LongCat-Flash family scale the normed latents, so that the
up-projections see an input of the hidden state's variance whatever the rank:
``mla_scale_q_lora`` gives ``c_q = RMSNorm(x W_dq) * sqrt(hidden / q_lora_rank)``
and ``mla_scale_kv_lora`` ``c_kv = RMSNorm(.) * sqrt(hidden / kv_lora_rank)``
(``k_rope`` is not scaled). The cache's rows hold the scaled ``c_kv``: both
paths read it from :meth:`_latent_rows`, so the absorbed step agrees with the
expanded pass by construction. ``rope_scaling`` ``None`` is plain rotary.

Two switches of the Ling 3.0 family: ``q_lora_rank`` ``None`` takes the queries
straight from the hidden state (``q = x W_uq`` with ``W_uq`` ``hidden`` rows
tall: no ``w_dq``, no ``q_norm``), and ``mla_head_gate`` multiplies every
head's attended values by one gate, ``o_h <- o_h * sigmoid(x w_gate)_h``
(``w_gate`` ``hidden`` x heads, the sigmoid float32), before ``W_o``, on both
paths.
"""

from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from perceiver_io_tpu.core.cache import LatentCache
from perceiver_io_tpu.core.position import apply_rotary_interleaved, yarn_inv_freq, yarn_mscale
from perceiver_io_tpu.ops.flash_attention import (
    flash_attention, flash_attention_mla, flash_enabled, mla_flash_supported,
)
from perceiver_io_tpu.ops.layernorm import RMSNorm
from perceiver_io_tpu.ops.mla_absorb import mla_absorb, mla_absorb_supported
from perceiver_io_tpu.ops.rotary import rotary_angles, rotate_packed


class MultiHeadLatentAttention(nn.Module):
    """``config`` needs: ``hidden_size``, ``num_attention_heads``,
    ``q_lora_rank`` (``None``: no query latent), ``kv_lora_rank``, ``qk_nope_head_dim``,
    ``qk_rope_head_dim``, ``v_head_dim``, ``rms_norm_eps``, ``rope_theta``,
    ``rope_scaling`` (``None`` or an object with YaRN's ``factor``,
    ``beta_fast``, ``beta_slow``, ``mscale``, ``mscale_all_dim``,
    ``original_max_position_embeddings``), ``mla_scale_q_lora``,
    ``mla_scale_kv_lora``, ``mla_head_gate`` and ``init_scale``."""

    config: object
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        c = self.config
        heads = c.num_attention_heads
        init = nn.initializers.normal(c.init_scale)
        norm = dict(epsilon=c.rms_norm_eps, dtype=self.dtype, param_dtype=self.param_dtype)
        if c.q_lora_rank is not None:
            self.w_dq = self.param("w_dq", init, (c.hidden_size, c.q_lora_rank), self.param_dtype)
            self.q_norm = RMSNorm(**norm)
        self.w_uq = self.param(
            "w_uq", init, (query_rank(c), heads * (c.qk_nope_head_dim + c.qk_rope_head_dim)), self.param_dtype
        )
        self.w_dkv = self.param("w_dkv", init, (c.hidden_size, c.kv_lora_rank + c.qk_rope_head_dim), self.param_dtype)
        self.kv_norm = RMSNorm(**norm)
        self.w_ukv = self.param(
            "w_ukv", init, (c.kv_lora_rank, heads * (c.qk_nope_head_dim + c.v_head_dim)), self.param_dtype
        )
        if c.mla_head_gate:
            self.w_gate = self.param("w_gate", init, (c.hidden_size, heads), self.param_dtype)
        self.w_o = self.param("w_o", init, (heads * c.v_head_dim, c.hidden_size), self.param_dtype)

    # ------------------------------------------------------------ shared

    @property
    def sm_scale(self) -> float:
        c = self.config
        scale = (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5
        if c.rope_scaling is not None:
            m = yarn_mscale(c.rope_scaling.factor, c.rope_scaling.mscale_all_dim)
            scale *= m * m
        return scale

    def _inv_freq(self):
        c, s = self.config, self.config.rope_scaling
        if s is None:
            return yarn_inv_freq(c.qk_rope_head_dim, c.rope_theta, 1.0, 1.0, 1.0, 1)  # factor 1: plain rotary
        return yarn_inv_freq(c.qk_rope_head_dim, c.rope_theta, s.factor, s.beta_fast, s.beta_slow,
                             s.original_max_position_embeddings)

    def _mm(self, x, w):
        return jnp.dot(x.astype(self.dtype), w.astype(self.dtype))

    def _scaled(self, latent, rank: int, on: bool):
        """A normed latent times ``sqrt(hidden / rank)`` where the configuration switches that on."""
        return latent * (self.config.hidden_size / rank) ** 0.5 if on else latent

    def _c_q(self, x):
        """What ``w_uq`` multiplies: the normed query latent, or the hidden state itself where there is none."""
        c = self.config
        if c.q_lora_rank is None:
            return x.astype(self.dtype)
        return self._scaled(self.q_norm(self._mm(x, self.w_dq)), c.q_lora_rank, c.mla_scale_q_lora)

    def _project_out(self, o, x):
        """``o`` (B, N, H * v) through the head-wise gate, where the configuration has one, and ``W_o``."""
        c = self.config
        if c.mla_head_gate:
            gate = jax.nn.sigmoid(jnp.dot(x.astype(self.dtype), self.w_gate.astype(self.dtype), preferred_element_type=jnp.float32))
            o = (o.reshape(*o.shape[:-1], c.num_attention_heads, c.v_head_dim) * gate[..., None]).reshape(o.shape).astype(self.dtype)
        return self._mm(o, self.w_o)

    def _queries(self, x, pos) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """``x`` (B, N, h), ``pos`` (B, N) -> ``q_nope`` (B, N, H, nope) and
        the rotated ``q_rope`` (B, N, H, rope)."""
        c = self.config
        b, n, _ = x.shape
        q = self._mm(self._c_q(x), self.w_uq)
        q = q.reshape(b, n, c.num_attention_heads, c.qk_nope_head_dim + c.qk_rope_head_dim)
        q_nope, q_rope = q[..., : c.qk_nope_head_dim], q[..., c.qk_nope_head_dim:]
        return q_nope, apply_rotary_interleaved(q_rope, pos[:, :, None], self._inv_freq())

    def _w_uq_packed(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """``w_uq``'s columns, ``[nope | rope]`` a head, as the two sets ``(rank, H * nope)`` and ``(rank, H * rope)``:
        from the ``views`` collection where the caller took them once in front of a loop (:func:`expand_views`)."""
        if self.has_variable(VIEWS, "w_uq_nope"):
            return self.get_variable(VIEWS, "w_uq_nope"), self.get_variable(VIEWS, "w_uq_rope")
        return split_w_uq(self.w_uq.astype(self.dtype), self.config)

    def _queries_packed(self, x, pos) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """:meth:`_queries` token-major, ``q_nope`` (B, N, H * nope) and the
        rotated ``q_rope`` (B, N, H * rope): a product a column set of
        ``w_uq``, and the rotation by the lane-rolling kernel
        (``ops/rotary.py``; the values are ``apply_rotary_interleaved``'s)."""
        c_q = self._c_q(x)
        w_nope, w_rope = self._w_uq_packed()
        q_rope = rotate_interleaved_packed(jnp.dot(c_q, w_rope), pos, self._inv_freq(), self.config.num_attention_heads)
        return jnp.dot(c_q, w_nope), q_rope

    def _latent_rows(self, x, pos) -> jnp.ndarray:
        """The cache's rows for ``x``: ``[RMSNorm(c_kv), scaled where switched on; rotated k_rope]`` (B, N, rank + rope)."""
        c = self.config
        kv = self._mm(x, self.w_dkv)
        c_kv = self._scaled(self.kv_norm(kv[..., : c.kv_lora_rank]), c.kv_lora_rank, c.mla_scale_kv_lora)
        k_rope = apply_rotary_interleaved(kv[..., c.kv_lora_rank:], pos, self._inv_freq())
        return jnp.concatenate([c_kv, k_rope], axis=-1)

    def _w_ukv(self):
        c = self.config
        return self.w_ukv.astype(self.dtype).reshape(
            c.kv_lora_rank, c.num_attention_heads, c.qk_nope_head_dim + c.v_head_dim
        )

    # ---------------------------------------------------------- expanded

    def expand(self, x, pos) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Causal self-attention of ``x`` (B, N, h) over itself, per-head keys
        and values built from the latent. Returns the output (B, N, h) and
        the cache rows (B, N, rank + rope) of these tokens."""
        c = self.config
        b, n, _ = x.shape
        heads = c.num_attention_heads
        with jax.named_scope("mla/expand"):
            if flash_enabled() and mla_flash_supported(n, heads, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim):
                # the kernel reads what the up-projections write: nothing heads-major, nothing a head wide in between
                q_nope, q_rope = self._queries_packed(x, pos)
                rows = self._latent_rows(x, pos)
                kv = self._mm(rows[..., : c.kv_lora_rank], self.w_ukv)
                o = flash_attention_mla(q_nope, q_rope, kv, rows[..., c.kv_lora_rank:], heads, sm_scale=self.sm_scale)
                return self._project_out(o, x), rows
            q_nope, q_rope = self._queries(x, pos)
            rows = self._latent_rows(x, pos)
            kv = jnp.einsum("bnc,chd->bnhd", rows[..., : c.kv_lora_rank], self._w_ukv())
            k_nope, v = kv[..., : c.qk_nope_head_dim], kv[..., c.qk_nope_head_dim:]
            k_rope = jnp.broadcast_to(rows[:, :, None, c.kv_lora_rank:], (b, n, heads, c.qk_rope_head_dim))
            # heads-major (B, H, N, D) for the attention
            q = jnp.concatenate([q_nope, q_rope], axis=-1).transpose(0, 2, 1, 3)
            k = jnp.concatenate([k_nope, k_rope.astype(k_nope.dtype)], axis=-1).transpose(0, 2, 1, 3)
            v = v.transpose(0, 2, 1, 3)
            if flash_enabled():
                o = flash_attention(q, k, v, causal=True, sm_scale=self.sm_scale)
            else:
                s = jnp.einsum("bhic,bhjc->bhij", q, k, preferred_element_type=jnp.float32) * self.sm_scale
                visible = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
                p = jax.nn.softmax(jnp.where(visible[None, None], s, -jnp.inf), axis=-1)
                o = jnp.einsum("bhij,bhjc->bhic", p.astype(v.dtype), v)
            o = o.transpose(0, 2, 1, 3).reshape(b, n, heads * c.v_head_dim)
            return self._project_out(o, x), rows

    # ---------------------------------------------------------- absorbed

    def absorb(self, x, cache: LatentCache, pos) -> Tuple[jnp.ndarray, LatentCache]:
        """One new token a row, ``x`` (B, 1, h) at positions ``pos`` (B, 1),
        against ``cache``: its row is appended first, then the query, carried
        into the latent space, reads scores and values from the cached rows."""
        c = self.config
        b = x.shape[0]
        heads, rank = c.num_attention_heads, c.kv_lora_rank
        with jax.named_scope("mla/absorb"):
            q_nope, q_rope = self._queries(x, pos)
            row = self._latent_rows(x, pos)
            fused = flash_enabled() and mla_absorb_supported(cache.rows.shape, cache.rows.dtype, rank)
            if not fused:  # XLA's append, where it always stood in the step (``tests/test_decoder_lm.py`` pins the order)
                with jax.named_scope("latent_cache_append"):
                    cache = cache.append(row)
            w_ukv = self._w_ukv()
            w_uk, w_uv = w_ukv[..., : c.qk_nope_head_dim], w_ukv[..., c.qk_nope_head_dim:]
            q_abs = jnp.einsum("bhd,chd->bhc", q_nope[:, 0], w_uk)
            q_cat = jnp.concatenate([q_abs, q_rope[:, 0].astype(q_abs.dtype)], axis=-1)
            if fused:  # append and attend in one kernel over the cache, which it updates in place
                rows, o_lat = mla_absorb(
                    q_cat, row, cache.rows, cache.length, sm_scale=self.sm_scale, keep=rank, out_dtype=self.dtype
                )
                cache = LatentCache(rows=rows, length=cache.length + 1)
            else:
                o_lat = latent_decode_attention(q_cat, cache, self.sm_scale)[..., :rank]
            o = jnp.einsum("bhc,chd->bhd", o_lat.astype(self.dtype), w_uv)
            return self._project_out(o.reshape(b, 1, heads * c.v_head_dim), x), cache


VIEWS = "views"  # the collection of :func:`expand_views`


def query_rank(config) -> int:
    """The rows of ``w_uq``: the query latent's rank, or the hidden size where the configuration has no query latent."""
    return config.hidden_size if config.q_lora_rank is None else config.q_lora_rank


def split_w_uq(w_uq: jnp.ndarray, config) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``w_uq`` (rank, H * (nope + rope)), ``[nope | rope]`` a head -> (rank, H * nope) and (rank, H * rope)."""
    c = config
    rank, nope = query_rank(c), c.qk_nope_head_dim
    w = w_uq.reshape(rank, c.num_attention_heads, nope + c.qk_rope_head_dim)
    return w[..., :nope].reshape(rank, -1), w[..., nope:].reshape(rank, -1)


@jax.named_scope("rotary")
def rotate_interleaved_packed(t: jnp.ndarray, pos: jnp.ndarray, inv_freq, heads: int) -> jnp.ndarray:
    """``apply_rotary_interleaved`` on every head of token-major ``t`` (B, N,
    H * R) at ``pos`` (B, N), by the lane-rolling kernel: a pair's partner is
    one lane away, and no lane is shuffled in XLA. The values are
    ``apply_rotary_interleaved``'s to the bit (float32 arithmetic, rounded
    once to ``t``'s dtype)."""
    angles = pos.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq, jnp.float32)
    # a pair's two lanes turn by one angle
    return rotate_packed(t, rotary_angles(jnp.repeat(angles, 2, axis=-1)), heads)


def expand_views(params, config, dtype) -> dict:
    """The ``views`` collection for a tree of ``params`` that holds latent
    attentions: beside every ``w_uq`` its two column sets in ``dtype``, as
    :meth:`MultiHeadLatentAttention.expand` multiplies by them where the
    kernel runs. A prompt pass that loops over chunks takes them here, once a
    call: inside the loop's body the compiler would cut them out of the
    weight again every chunk (a slice is not hoisted for its own sake)."""
    views = {}
    for key, sub in params.items():
        if hasattr(sub, "items") and (found := expand_views(sub, config, dtype)):
            views[key] = found
    if "w_uq" in params:
        with jax.named_scope("mla/expand"):
            views["w_uq_nope"], views["w_uq_rope"] = split_w_uq(params["w_uq"].astype(dtype), config)
    return views


def latent_decode_attention(q_cat: jnp.ndarray, cache: LatentCache, sm_scale: float) -> jnp.ndarray:
    """Absorbed attention of one query a row: ``q_cat`` (B, H, width) against
    the joint rows ``cache.rows`` (B, capacity, width), slots at or past
    ``cache.length`` masked. Returns ``softmax(q . row) @ row`` (B, H, width)
    in float32: all ``width`` channels, of which the caller keeps the first
    ``kv_lora_rank`` (attending over the whole row avoids a copy of the cache
    without its rope channels; the rope channels of the result are unused).

    Two batched products over the cache in XLA; the softmax is float32."""
    rows = cache.rows
    s = jnp.einsum("bhc,bsc->bhs", q_cat.astype(rows.dtype), rows, preferred_element_type=jnp.float32) * sm_scale
    valid = jnp.arange(cache.capacity, dtype=jnp.int32) < cache.length
    p = jax.nn.softmax(jnp.where(valid[None, None, :], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhs,bsc->bhc", p.astype(rows.dtype), rows, preferred_element_type=jnp.float32)
