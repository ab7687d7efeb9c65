"""Latent attention that chooses its keys, and latent attention behind a window.

Two layer kinds of one published family (dots3-note; the selection is
DeepSeek sparse attention, DeepSeek-V3.2's, over DeepSeek-V3's latent
attention of ``core/mla.py``), both subclasses of
:class:`~perceiver_io_tpu.core.mla.MultiHeadLatentAttention`: same weights,
same two ways to compute the function (``expand`` for whole rows, ``absorb``
for one new token against the cache), same cache rows.

**A full layer** (:class:`SparseLatentAttention`) adds a *lightning indexer*:
with ``x_t`` the normed hidden state and ``c^Q_t`` the query latent as the
attention itself takes it (normed, rescaled where the configuration says so)::

    q^I_{t,j} = rope(c^Q_t W^I_q)_j            j = 1..index_n_heads, index_head_dim channels
    k^I_t     = rope(LayerNorm(x_t W^I_k))     one key a token, cached beside the latent row
    w_t       = (x_t W^I_w) * index_n_heads^-0.5 * index_head_dim^-0.5
    I_{t,s}   = sum_j w_{t,j} * relu(q^I_{t,j} . k^I_s)        s <= t
    S_t       = the min(t + 1, index_topk) keys s <= t with the largest I_{t,s}

and the attention's softmax runs over ``S_t`` and not over every ``s <= t``.
The rotary turns the first ``qk_rope_head_dim`` channels of an indexer query or
key in the half-split pairing (channel ``i`` with ``i + rope / 2``) at the
layer's own frequencies. Scores and their head sum are float32 from ``dtype``
operands. **The selection is exact**: ``S_t`` is the set the definition gives
for the scores the program computed, found without a sort as the threshold
that ``index_topk`` scores reach (a bisection over the bits of a float32,
:func:`topk_mask`), ties at the threshold to the lower positions; no
approximate top-k, no block granularity. Where a row has at most
``index_topk`` positions everything is selected and the pass is plain latent
attention's, by the parent's own code.

``expand`` (the prompt pass) forms the selection as a mask a row of queries,
a chunk of queries at a time (the float32 score matrix of one 32 768-token row
is 4.3 GB: it is never whole in memory), and runs the *expanded* attention
under it: on a TPU three kernels of ``ops/dsa.py`` (the indexer's scores, the
selection, the flash forward under a mask), elsewhere the same arithmetic in
XLA. ``absorb`` (a step) scores the cached index keys, takes ``lax.top_k``,
gathers the chosen latent rows and runs the *absorbed* attention over those:
the arithmetic of :func:`~perceiver_io_tpu.core.mla.latent_decode_attention`
over gathered rows.

**A window layer** (:class:`WindowLatentAttention`) is a second latent
attention with sizes of its own (the configuration's ``swa_*`` keys, handed in
as :class:`LatentSizes`) whose causal mask keeps the last ``window`` positions,
``t - window < s <= t``, and no indexer. Its cache is a ring of latent rows
(:class:`~perceiver_io_tpu.core.cache.LatentRingCache`).

Scopes (``obs/xplane.py``): the projections the two kinds share with plain
latent attention stay under ``mla/expand`` and ``mla/absorb``; the mechanism
opens ``dsa/index`` (the indexer's three projections, norm and rotary),
``dsa/score``, ``dsa/select`` and ``dsa/attend`` in the pass, ``dsa/step_score``,
``dsa/step_select``, ``dsa/step_gather`` and ``dsa/step_attend`` in a step; a
window layer is ``mla/window`` in the pass and ``mla/window_step`` in a step.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from perceiver_io_tpu.core.cache import IndexedLatentCache, LatentRingCache
from perceiver_io_tpu.core.mla import MultiHeadLatentAttention, rotate_interleaved_packed
from perceiver_io_tpu.core.position import apply_rotary_half, apply_rotary_interleaved
from perceiver_io_tpu.obs import probes
from perceiver_io_tpu.ops import dsa as kernels
from perceiver_io_tpu.ops.flash_attention import flash_enabled
from perceiver_io_tpu.ops.layernorm import LayerNorm

INDEX_NORM_EPS = 1e-6  # the indexer's key LayerNorm (DeepSeek-V3.2's own default)

# How a full layer's prompt pass is cut, not what it computes: the queries whose index scores are whole in memory at
# once (2048 x 32 768 float32 scores are 268 MB; their indexer queries, 64 heads of 128, are made a chunk at a time too)
# and the heads whose expanded queries, keys and values are (8 heads of a 32 768-token row are 0.3 GB; all 128 would be
# 4.7 GB beside 9.5 GB of weights and hidden state). Compiled for a described v5e the cell's generator is 14.56 GB at 8
# heads a pass, 14.85 at 16 and 15.5 at 32, against the 14.9 the other cells are held to (tests/test_tpu_compile.py)
_SCORE_QUERIES = 2048
_HEADS_A_PASS = 8


@dataclass(frozen=True)
class LatentSizes:
    """The sizes :class:`MultiHeadLatentAttention` reads off a configuration, for an attention whose sizes are not the
    configuration's own (a window layer's ``swa_*``)."""

    hidden_size: int
    num_attention_heads: int
    q_lora_rank: Optional[int]
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rms_norm_eps: float
    rope_theta: float
    init_scale: float
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    mla_head_gate: bool = False
    rope_scaling: None = None


def window_sizes(config) -> LatentSizes:
    """A window layer's latent attention, from the configuration's ``swa_*`` keys (the switches are the model's)."""
    c = config
    return LatentSizes(
        hidden_size=c.hidden_size, num_attention_heads=c.swa_num_attention_heads, q_lora_rank=c.swa_q_lora_rank,
        kv_lora_rank=c.swa_kv_lora_rank, qk_nope_head_dim=c.swa_qk_nope_head_dim, qk_rope_head_dim=c.swa_qk_rope_head_dim,
        v_head_dim=c.swa_v_head_dim, rms_norm_eps=c.rms_norm_eps, rope_theta=c.swa_rope_theta, init_scale=c.init_scale,
        mla_scale_q_lora=c.mla_scale_q_lora, mla_scale_kv_lora=c.mla_scale_kv_lora, mla_head_gate=c.mla_head_gate,
    )


# ------------------------------------------------------------------ the selection


def index_scores(q: jnp.ndarray, k: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """``I = sum_j w_j relu(q_j . k)``: ``q`` (B, Q, J, D), ``k`` (B, S, D), ``w`` (B, Q, J) float32 -> (B, Q, S)
    float32, products from the operands as they are, accumulated, weighed and summed over the heads in float32."""
    s = jnp.einsum("bqjd,bsd->bqjs", q, k.astype(q.dtype), preferred_element_type=jnp.float32)
    return jnp.sum(w[..., None] * jax.nn.relu(s), axis=2)


def topk_mask(scores: jnp.ndarray, k: int) -> jnp.ndarray:
    """The ``k`` largest of each row of ``scores`` (..., S) float32 as a bool mask (..., S), exactly: the bisection of
    ``ops.dsa.largest`` over the scores' order-preserving integer image (32 counts a row, no sort; ties at the
    threshold to the lowest positions). Hidden slots are ``-inf`` and are never chosen: a row with fewer than ``k``
    finite scores has them all. The set is ``lax.top_k``'s."""
    return kernels.largest(kernels.sortable(lax.bitcast_convert_type(scores, jnp.int32)), k) & (scores > -jnp.inf)


def causal_scores(scores: jnp.ndarray, first: jnp.ndarray) -> jnp.ndarray:
    """``scores`` (B, Q, S) of the queries at positions ``first .. first + Q - 1`` with every key after its query at ``-inf``."""
    q_pos = first + lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    return jnp.where(lax.broadcasted_iota(jnp.int32, scores.shape, 2) <= q_pos, scores, -jnp.inf)


# ------------------------------------------------------------------ a full layer


class SparseLatentAttention(MultiHeadLatentAttention):
    """Latent attention over the keys a lightning indexer selects (the module docstring). ``config`` needs, beside the
    parent's, ``index_n_heads``, ``index_head_dim``, ``index_topk`` and a ``q_lora_rank`` (the indexer's queries are
    read off the query latent)."""

    def setup(self):
        super().setup()
        c = self.config
        init = nn.initializers.normal(c.init_scale)
        self.w_iq = self.param("w_iq", init, (c.q_lora_rank, c.index_n_heads * c.index_head_dim), self.param_dtype)
        self.w_ik = self.param("w_ik", init, (c.hidden_size, c.index_head_dim), self.param_dtype)
        self.index_k_norm = LayerNorm(epsilon=INDEX_NORM_EPS, dtype=self.dtype, param_dtype=self.param_dtype)
        self.w_iw = self.param("w_iw", init, (c.hidden_size, c.index_n_heads), self.param_dtype)

    # ------------------------------------------------------------ the indexer

    def _index_rotary(self, t, pos):
        """The first ``qk_rope_head_dim`` channels of ``t`` (..., N, [J,] D) turned in the half-split pairing."""
        rope = self.config.qk_rope_head_dim
        turned = apply_rotary_half(t[..., :rope], pos, self._inv_freq())
        return jnp.concatenate([turned, t[..., rope:]], axis=-1)

    def _index_keys(self, x, pos) -> jnp.ndarray:
        """``k^I`` (B, N, D) of ``x`` (B, N, h), rotated: what the index cache holds."""
        return self._index_rotary(self.index_k_norm(self._mm(x, self.w_ik)), pos)

    def _index_queries(self, c_q, x, pos) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """``q^I`` (B, N, J, D), rotated, and the head weights ``w`` (B, N, J) float32, from the query latent ``c_q``
        (B, N, rank) as the attention takes it and the normed hidden state ``x``. Products of weights alone, so that
        a loop over chunks of queries may call it (no submodule is met)."""
        c = self.config
        b, n, _ = x.shape
        q = self._mm(c_q, self.w_iq).reshape(b, n, c.index_n_heads, c.index_head_dim)
        w = jnp.dot(x.astype(self.dtype), self.w_iw.astype(self.dtype), preferred_element_type=jnp.float32)
        return self._index_rotary(q, pos[:, :, None]), w * (c.index_n_heads ** -0.5 * c.index_head_dim ** -0.5)

    def selection(self, x, pos) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """The prompt pass's selection over whole rows ``x`` (B, N, h), ``N > index_topk``: the mask (B, N, N) int8,
        1 where query ``t`` keeps key ``s``, and the index keys (B, N, D). A chunk of queries at a time."""
        c = self.config
        b, n, _ = x.shape
        with jax.named_scope("dsa/index"):
            keys = self._index_keys(x, pos)
            c_q = self._c_q(x)
        fused = flash_enabled() and kernels.selection_supported(n, c.index_head_dim)
        chunk = next(d for d in range(min(n, _SCORE_QUERIES), 0, -1) if n % d == 0 and (not fused or d % kernels.LANES == 0))

        def queries(first):  # the chunk's indexer queries and head weights: a whole row's would be half a gigabyte
            with jax.named_scope("dsa/index"):
                return self._index_queries(*(lax.dynamic_slice_in_dim(t, first, chunk, 1) for t in (c_q, x, pos)))

        if fused:  # the head sum on the tile the product leaves, the bisection on rows that stay in VMEM, a chunk's
            # selection written straight into the row's mask
            def with_chunk(i, mask):
                first = i * chunk
                q, w = queries(first)
                with jax.named_scope("dsa/score"):
                    scores = kernels.index_scores(q.reshape(b, chunk, -1), keys, w, c.index_n_heads, first)
                with jax.named_scope("dsa/select"):
                    return kernels.select_mask_into(mask, scores, c.index_topk, first)

            return lax.fori_loop(0, n // chunk, with_chunk, jnp.zeros((b, n, n), jnp.int8)), keys

        def of_chunk(i):
            first = i * chunk
            q, w = queries(first)
            with jax.named_scope("dsa/score"):
                scores = causal_scores(index_scores(q, keys, w), first)
            with jax.named_scope("dsa/select"):
                return topk_mask(scores, c.index_topk).astype(jnp.int8)

        mask = lax.map(of_chunk, jnp.arange(n // chunk, dtype=jnp.int32))  # (chunks, B, chunk, N)
        with jax.named_scope("dsa/select"):
            return jnp.moveaxis(mask, 0, 1).reshape(b, n, n), keys

    def _tap(self, kept, distance):
        """``dsa.select``: the keys a query keeps (``kept`` (B, Q, S), not 0 where kept) and the share of them within the
        window layers' window of it (``distance`` (B, Q, S): how far before its query a key lies)."""
        if not probes.active():
            return
        kept = (kept != 0).astype(jnp.float32)
        window = getattr(self.config, "sliding_window_size", None)
        recent = kept if window is None else kept * (distance < window)
        probes.tap("dsa.select", {"dsa_selected_sum": jnp.mean(jnp.sum(kept, axis=-1)),
                                  "dsa_selected_max": jnp.max(jnp.sum(kept, axis=-1)).astype(jnp.int32),
                                  "dsa_recent_share_sum": jnp.sum(recent) / jnp.maximum(jnp.sum(kept), 1.0),
                                  "dsa_sites": jnp.ones((), jnp.int32)})

    # ---------------------------------------------------------- expanded

    def expand(self, x, pos) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
        """Causal self-attention of ``x`` (B, N, h) over the selected keys. Returns the output (B, N, h) and the two
        caches' rows of these tokens: the latent rows (B, N, rank + rope) and the index keys (B, N, D)."""
        c = self.config
        b, n, _ = x.shape
        if n <= c.index_topk:  # every key is selected: plain latent attention, by the parent's own code
            out, rows = super().expand(x, pos)
            self._tap(pos[:, None, :] <= pos[:, :, None], pos[:, :, None] - pos[:, None, :])
            with jax.named_scope("dsa/index"):
                return out, (rows, self._index_keys(x, pos))
        mask, keys = self.selection(x, pos)
        self._tap(mask, pos[:, :, None] - pos[:, None, :])
        heads = c.num_attention_heads
        with jax.named_scope("mla/expand"):
            rows = self._latent_rows(x, pos)
        if flash_enabled() and kernels.masked_flash_supported(n, heads, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim):
            return self._attend_packed(x, pos, rows, mask), (rows, keys)
        o = self._attend_heads_major(x, pos, rows, mask)
        with jax.named_scope("mla/expand"):
            return self._project_out(o, x), (rows, keys)

    def _attend_packed(self, x, pos, rows, mask):
        """The masked flash kernel on token-major operands, ``_HEADS_A_PASS`` heads at a time, each group's output gated
        and carried through its rows of ``W_o`` at once: the layer's output (B, N, h), summed over the groups in float32
        (a row's attended values of every head side by side would be another gigabyte)."""
        c = self.config
        heads, rank, nope, rope, v = c.num_attention_heads, c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        group = next(g for g in range(min(heads, _HEADS_A_PASS), 0, -1) if heads % g == 0 and g % 2 == 0)
        with jax.named_scope("mla/expand"):
            c_q = self._c_q(x)
            w_nope, w_rope = self._w_uq_packed()
            w_ukv = self.w_ukv.astype(self.dtype)
        out = None
        for h0 in range(0, heads, group):
            with jax.named_scope("mla/expand"):
                q_nope = jnp.dot(c_q, w_nope[:, h0 * nope:(h0 + group) * nope])
                q_rope = rotate_interleaved_packed(jnp.dot(c_q, w_rope[:, h0 * rope:(h0 + group) * rope]), pos, self._inv_freq(), group)
                kv = jnp.dot(rows[..., :rank].astype(self.dtype), w_ukv[:, h0 * (nope + v):(h0 + group) * (nope + v)])
            with jax.named_scope("dsa/attend"):
                o = kernels.flash_attention_mla_masked(q_nope, q_rope, kv, rows[..., rank:], mask, group, sm_scale=self.sm_scale)
            with jax.named_scope("mla/expand"):
                part = project_heads(self, o, x, h0, group)
                out = part if out is None else out + part
        return out.astype(self.dtype)

    def _attend_heads_major(self, x, pos, rows, mask):
        """The same attention in XLA on heads-major operands (the CPU, and shapes the kernel does not take)."""
        c = self.config
        with jax.named_scope("mla/expand"):
            q_nope, q_rope = self._queries(x, pos)
            kv = jnp.einsum("bnc,chd->bnhd", rows[..., : c.kv_lora_rank], self._w_ukv())
            k_nope, v = kv[..., : c.qk_nope_head_dim], kv[..., c.qk_nope_head_dim:]
        with jax.named_scope("dsa/attend"):
            return masked_attention(q_nope, q_rope, k_nope, rows[..., c.kv_lora_rank:], v, mask, self.sm_scale)

    # ---------------------------------------------------------- absorbed

    @staticmethod
    def _candidates(cache: IndexedLatentCache) -> jnp.ndarray:
        """(capacity,) bool: the slots a step's selection may choose, every key written so far, the step's own included."""
        return jnp.arange(cache.capacity, dtype=jnp.int32) < cache.length

    def absorb(self, x, cache: IndexedLatentCache, pos) -> Tuple[jnp.ndarray, IndexedLatentCache]:
        """One new token a row, ``x`` (B, 1, h) at ``pos`` (B, 1), against ``cache``: its latent row and its index key
        are appended first; the indexer scores every cached key, ``index_topk`` of them are chosen, their latent rows
        gathered, and the query, carried into the latent space, attends over those."""
        c = self.config
        rank, topk = c.kv_lora_rank, min(c.index_topk, cache.capacity)
        with jax.named_scope("mla/absorb"):
            q_nope, q_rope = self._queries(x, pos)
            row = self._latent_rows(x, pos)
        with jax.named_scope("dsa/index"):
            key = self._index_keys(x, pos)
            q_i, w_i = self._index_queries(self._c_q(x), x, pos)
        with jax.named_scope("latent_cache_append"):
            cache = cache.append(row, key)
        with jax.named_scope("dsa/step_score"):
            scores = index_scores(q_i, cache.index.rows, w_i)[:, 0]  # (B, capacity)
            scores = jnp.where(self._candidates(cache)[None, :], scores, -jnp.inf)
        with jax.named_scope("dsa/step_select"):
            best, chosen = lax.top_k(scores, topk)  # exact; a tie goes to the lower position
            kept = best > -jnp.inf  # a context shorter than ``index_topk`` fills the rest with slots no key lives in
        self._tap(kept[:, None], (pos - chosen)[:, None])
        with jax.named_scope("dsa/step_gather"):
            gathered = jnp.take_along_axis(cache.latent.rows, chosen[:, :, None], axis=1)  # (B, topk, rank + rope)
        with jax.named_scope("mla/absorb"):
            q_cat, w_uv = absorbed_query(self, q_nope, q_rope)
        with jax.named_scope("dsa/step_attend"):
            o_lat = attend_rows(q_cat, gathered, kept, self.sm_scale)[..., :rank]
        with jax.named_scope("mla/absorb"):
            return absorbed_output(self, o_lat, w_uv, x), cache


def project_heads(attn: MultiHeadLatentAttention, o: jnp.ndarray, x: jnp.ndarray, first: int, count: int) -> jnp.ndarray:
    """The heads ``first .. first + count - 1`` of a layer's attended values, ``o`` (B, N, count * v), through the
    head-wise gate (where the configuration has one) and their rows of ``W_o``: their part of the output (B, N, h), float32."""
    c = attn.config
    v = c.v_head_dim
    if c.mla_head_gate:
        gate = jax.nn.sigmoid(jnp.dot(x.astype(attn.dtype), attn.w_gate[:, first:first + count].astype(attn.dtype), preferred_element_type=jnp.float32))
        o = (o.reshape(*o.shape[:-1], count, v) * gate[..., None]).reshape(o.shape).astype(attn.dtype)
        # the gated values as an array of their own: left to itself the compiler folds the product into the projection and
        # writes the gate, broadcast to every channel in float32, out twice (0.5 GB a pass of 16 heads of a 32 768-token row)
        o = lax.optimization_barrier(o)
    return jnp.dot(o.astype(attn.dtype), attn.w_o[first * v:(first + count) * v].astype(attn.dtype), preferred_element_type=jnp.float32)


def absorbed_query(attn: MultiHeadLatentAttention, q_nope: jnp.ndarray, q_rope: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One query a row carried into the latent space, as ``MultiHeadLatentAttention.absorb`` carries it: ``q_nope``
    (B, 1, H, nope) and the rotated ``q_rope`` (B, 1, H, rope) -> ``q_cat`` (B, H, rank + rope), and ``W_uv`` (rank, H, v)."""
    nope = attn.config.qk_nope_head_dim
    w_ukv = attn._w_ukv()
    q_abs = jnp.einsum("bhd,chd->bhc", q_nope[:, 0], w_ukv[..., :nope])
    return jnp.concatenate([q_abs, q_rope[:, 0].astype(q_abs.dtype)], axis=-1), w_ukv[..., nope:]


def absorbed_output(attn: MultiHeadLatentAttention, o_lat: jnp.ndarray, w_uv: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """The attended latent ``o_lat`` (B, H, rank) through ``W_uv``, the gate and ``W_o``: the step's output (B, 1, h)."""
    o = jnp.einsum("bhc,chd->bhd", o_lat.astype(attn.dtype), w_uv)
    return attn._project_out(o.reshape(o.shape[0], 1, -1), x)


def masked_attention(q_nope, q_rope, k_nope, k_rope, v, keep, sm_scale: float) -> jnp.ndarray:
    """Expanded latent attention under a mask in XLA, heads-major (the CPU, and shapes the kernels do not take):
    ``q_nope`` / ``k_nope`` (B, N, H, nope), ``q_rope`` (B, N, H, rope), ``k_rope`` (B, N, rope), ``v`` (B, N, H, v),
    ``keep`` (B or 1, N, N) not 0 where query ``i`` sees key ``j`` -> (B, N, H * v). Scores and softmax float32."""
    s = jnp.einsum("bihc,bjhc->bhij", q_nope, k_nope, preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bihc,bjc->bhij", q_rope, k_rope.astype(q_rope.dtype), preferred_element_type=jnp.float32)
    p = jax.nn.softmax(jnp.where(keep[:, None] != 0, s * sm_scale, -jnp.inf), axis=-1)
    o = jnp.einsum("bhij,bjhc->bihc", p.astype(v.dtype), v)
    return o.reshape(*o.shape[:2], -1)


def attend_rows(q_cat: jnp.ndarray, rows: jnp.ndarray, visible: jnp.ndarray, sm_scale: float) -> jnp.ndarray:
    """Absorbed attention of one query a row over joint rows: ``q_cat`` (B, H, width) against ``rows`` (B, S, width),
    of which ``visible`` ((B, S) or (S,) bool) are seen. Returns ``softmax(q . row) @ row`` (B, H, width) float32, the
    arithmetic of ``core.mla.latent_decode_attention`` under a mask that is not a length."""
    s = jnp.einsum("bhc,bsc->bhs", q_cat.astype(rows.dtype), rows, preferred_element_type=jnp.float32) * sm_scale
    seen = visible[:, None, :] if visible.ndim == 2 else visible[None, None, :]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhs,bsc->bhc", p.astype(rows.dtype), rows, preferred_element_type=jnp.float32)


# ------------------------------------------------------------------ a window layer


class WindowLatentAttention(MultiHeadLatentAttention):
    """Latent attention whose causal mask keeps the last ``window`` positions (the module docstring); ``config`` is a
    :class:`LatentSizes`."""

    window: int = 0

    def _visible(self, n: int):
        i, j = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
        return (j <= i) & (j > i - self.window)

    def expand(self, x, pos) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Causal self-attention of ``x`` (B, N, h) over the window. Returns the output (B, N, h) and the cache rows
        (B, N, rank + rope) of these tokens."""
        c = self.config
        b, n, _ = x.shape
        heads, rank, nope, rope, v_dim = c.num_attention_heads, c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        with jax.named_scope("mla/window"):
            rows = self._latent_rows(x, pos)
            if flash_enabled() and kernels.window_flash_supported(n, nope, rope, v_dim):
                return self._attend_packed(x, pos, rows), rows
            q_nope, q_rope = self._queries(x, pos)
            kv = jnp.einsum("bnc,chd->bnhd", rows[..., :rank], self._w_ukv())
            o = masked_attention(q_nope, q_rope, kv[..., :nope], rows[..., rank:], kv[..., nope:], self._visible(n)[None], self.sm_scale)
            return self._project_out(o, x), rows

    def _attend_packed(self, x, pos, rows):
        """The window flash kernel on token-major operands, a head's 256 query-key channels as two lane blocks
        (``[nope 0..127]`` and ``[nope 128..191 | rope]``), ``_HEADS_A_PASS`` heads at a time, each group's output gated and
        carried through its rows of ``W_o`` at once: the layer's output (B, N, h)."""
        c = self.config
        b, n, _ = x.shape
        heads, rank, nope, rope, v_dim = c.num_attention_heads, c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        group = next(g for g in range(min(heads, _HEADS_A_PASS), 0, -1) if heads % g == 0)
        c_q = self._c_q(x)
        w_uq = self.w_uq.astype(self.dtype)
        w_ukv = self._w_ukv()  # (rank, H, nope + v)
        latent, k_rope = rows[..., :rank].astype(self.dtype), rows[..., rank:].astype(self.dtype)
        inv_freq = self._inv_freq()
        out = None
        for h0 in range(0, heads, group):
            q = jnp.dot(c_q, w_uq[:, h0 * (nope + rope):(h0 + group) * (nope + rope)]).reshape(b, n, group, nope + rope)
            q = jnp.concatenate([q[..., :nope], apply_rotary_interleaved(q[..., nope:], pos[:, :, None], inv_freq)], axis=-1)
            k_nope = jnp.einsum("bnc,chd->bnhd", latent, w_ukv[:, h0:h0 + group, :nope])
            lanes = kernels.LANES
            k_low = k_nope[..., :lanes].reshape(b, n, group * lanes)
            k_high = jnp.concatenate([k_nope[..., lanes:], jnp.broadcast_to(k_rope[:, :, None, :], (b, n, group, rope))], axis=-1)
            v = jnp.einsum("bnc,chd->bnhd", latent, w_ukv[:, h0:h0 + group, nope:]).reshape(b, n, group * v_dim)
            o = kernels.flash_attention_mla_window(q.reshape(b, n, group * (nope + rope)), k_low, k_high.reshape(b, n, group * lanes),
                                                   v, group, self.window, sm_scale=self.sm_scale)
            part = project_heads(self, o, x, h0, group)
            out = part if out is None else out + part
        return out.astype(self.dtype)

    def absorb(self, x, cache: LatentRingCache, pos) -> Tuple[jnp.ndarray, LatentRingCache]:
        """One new token a row against the ring: its row is written over the slot of a position that left the window,
        then the query, carried into the latent space, attends over the slots its window holds."""
        c = self.config
        with jax.named_scope("mla/window_step"):
            q_nope, q_rope = self._queries(x, pos)
            row = self._latent_rows(x, pos)
            with jax.named_scope("latent_cache_append"):
                cache = cache.append(row)
            q_cat, w_uv = absorbed_query(self, q_nope, q_rope)
            o_lat = attend_rows(q_cat, cache.rows, cache.visible(), self.sm_scale)[..., :c.kv_lora_rank]
            return absorbed_output(self, o_lat, w_uv, x), cache


__all__ = ["LatentSizes", "SparseLatentAttention", "WindowLatentAttention", "attend_rows", "causal_scores", "index_scores",
           "topk_mask", "window_sizes"]
