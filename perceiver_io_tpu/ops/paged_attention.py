"""Paged decode attention — the TPU kernel that walks page tables.

The batched serving engine stores KV in fixed-size pages
(``core.cache.PagedKVCache``); a decode step attends each slot's single
query over that slot's pages. Two implementations, one contract:

- **gather fallback** (``core.attention.MultiHeadAttention.
  _paged_decode_attend``): ``jnp.take`` rebuilds the contiguous (S,
  capacity, C) view and runs the block-diagonal decode GEMM — this is what
  CPU tier-1 certifies token-exact against the contiguous cache, and it is
  the default everywhere (the ``decode_paged`` graphcheck contract budgets
  its gathers and pins that no kv-axis concatenate appears);
- **page-walk kernel** (this module): the kv BlockSpec *index maps* read
  the scalar-prefetched page table, so block ``(s, j)`` DMAs page
  ``page_table[s, j]`` straight from the pool (*Ragged Paged Attention*,
  arXiv:2604.15464). The contiguous view is never materialized and the
  per-step HBM traffic is O(valid tokens), not O(slots x capacity).

The kernel is forward-only (decode has no backward), gated behind the
``paged`` kernel feature (``ops.flash_attention.fast_kernels``) —
default-off until a real-TPU A/B graduates it through the ledger; the gather fallback is the shipping semantics either way.
Equivalence kernel-vs-fallback is pinned in interpret mode by
``tests/test_paged_engine.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from perceiver_io_tpu.ops.flash_attention import (
    LANES,
    MASK_VALUE,
    _compiler_params,
    _dot,
    _interpret_default,
)

# minimum page rows for a loadable f32 tile (sublane dimension)
_MIN_PAGE_SIZE = 8


def paged_kernel_supported(cache, num_heads: int, d_qk: int, d_v: int) -> bool:
    """Whether the page-walk kernel can serve this cache geometry: float
    pools (the int8 scale-folding variant stays on the fallback until it is
    A/B'd on hardware), lane-aligned packed head widths, loadable pages."""
    if cache.quantized:
        return False
    if cache.page_size < _MIN_PAGE_SIZE:
        return False
    return (num_heads * d_qk) % LANES == 0 and (num_heads * d_v) % LANES == 0


def _paged_kernel(
    table_ref,  # scalar prefetch: (S, pages_per_slot) int32
    q_ref,  # (1, 1, h*d_qk)
    k_ref,  # (1, page, h*d_qk) — the page the index map selected
    v_ref,  # (1, page, h*d_v)
    bias_ref,  # (1, 1, 1, page) f32 — 0 where visible, MASK_VALUE where masked
    o_ref,  # (1, 1, h*d_v)
    m_scr,  # (h, 1, LANES) f32
    l_scr,  # (h, 1, LANES) f32
    acc_scr,  # (h, 1, d_v) f32
    *,
    num_heads: int,
    d_qk: int,
    d_v: int,
    num_kv_blocks: int,
):
    j = pl.program_id(1)
    h = num_heads

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    bias = bias_ref[0, 0]  # (1, page)
    for hh in range(h):
        qh = q_ref[0, :, hh * d_qk : (hh + 1) * d_qk]  # (1, d_qk)
        kh = k_ref[0, :, hh * d_qk : (hh + 1) * d_qk]  # (page, d_qk)
        vh = v_ref[0, :, hh * d_v : (hh + 1) * d_v]  # (page, d_v)
        s = _dot(qh, kh, ((1,), (1,))) + bias  # (1, page) f32
        m_prev = m_scr[hh, :, :1]
        l_prev = l_scr[hh, :, :1]
        m_curr = jnp.max(s, axis=1)[:, None]
        m_next = jnp.maximum(m_prev, m_curr)
        p = jnp.exp(s - m_next)
        alpha = jnp.exp(m_prev - m_next)
        l_scr[hh, :, :1] = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
        m_scr[hh, :, :1] = m_next
        o_curr = _dot(p.astype(vh.dtype), vh, ((1,), (0,)))  # (1, d_v)
        acc_scr[hh] = acc_scr[hh] * alpha + o_curr

    @pl.when(j == num_kv_blocks - 1)
    def _store():
        for hh in range(h):
            l = l_scr[hh, :, :1]
            l_inv = jnp.where(l == 0.0, 1.0, 1.0 / l)
            o_ref[0, :, hh * d_v : (hh + 1) * d_v] = (acc_scr[hh] * l_inv).astype(o_ref.dtype)


def paged_decode_attention(qh: jnp.ndarray, cache, mask=None) -> jnp.ndarray:
    """Single-query attention over paged KV: ``qh`` (S, H, Dk) scaled and
    rotated, ``cache`` a float ``PagedKVCache``; ``mask`` (S, capacity)
    True-=-masked (defaults to the per-slot validity mask ``j >=
    length[s]``). Returns (S, H, Dv) — the caller merges heads.

    One grid step per (slot, page): the kv BlockSpec index maps read the
    scalar-prefetched page table, so each step's DMA source IS the page —
    the pool is never gathered into a contiguous view. Pages a slot does
    not own point at the scratch page and arrive fully masked."""
    s_slots, h, d_qk = qh.shape
    page = cache.page_size
    npb = cache.pages_per_slot
    d_v = cache.v.shape[2] // h
    cap = cache.capacity

    if mask is None:
        kv_idx = jnp.arange(cap, dtype=jnp.int32)
        mask = kv_idx[None, :] >= cache.length[:, None]
    # Mosaic wants a block's last two dims to tile (8, 128) or span the
    # array's: q/out/bias blocks are one row, so each gets a unit axis in
    # front of its lanes and the slot (and page) index moves to leading dims
    bias = jnp.where(mask, MASK_VALUE, 0.0).astype(jnp.float32).reshape(s_slots, npb, 1, page)

    q_packed = qh.reshape(s_slots, 1, h * d_qk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s_slots, npb),
        in_specs=[
            pl.BlockSpec((1, 1, h * d_qk), lambda s, j, table: (s, 0, 0)),
            # the page walk: block (s, j) loads pool page table[s, j]
            pl.BlockSpec((1, page, h * d_qk), lambda s, j, table: (table[s, j], 0, 0)),
            pl.BlockSpec((1, page, h * d_v), lambda s, j, table: (table[s, j], 0, 0)),
            pl.BlockSpec((1, 1, 1, page), lambda s, j, table: (s, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, h * d_v), lambda s, j, table: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1, LANES), jnp.float32),
            pltpu.VMEM((h, 1, LANES), jnp.float32),
            pltpu.VMEM((h, 1, d_v), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, num_heads=h, d_qk=d_qk, d_v=d_v, num_kv_blocks=npb
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_slots, 1, h * d_v), qh.dtype),
        compiler_params=_compiler_params("arbitrary", "arbitrary"),
        interpret=_interpret_default(),
    )(cache.page_table, q_packed, cache.k, cache.v, bias)
    return out.reshape(s_slots, h, d_v)


def paged_attention_reference(qh: jnp.ndarray, cache, mask=None) -> jnp.ndarray:
    """The gather-view reference the kernel is pinned against (same math as
    the fallback in ``core.attention``, head-major output): softmax in f32,
    value matmul in the storage dtype."""
    k_slots, v_slots, _, _ = cache.gather_view()
    cap = k_slots.shape[1]
    if mask is None:
        kv_idx = jnp.arange(cap, dtype=jnp.int32)
        mask = kv_idx[None, :] >= cache.length[:, None]
    h, d_v = qh.shape[1], cache.v.shape[2] // qh.shape[1]
    d_qk = qh.shape[2]
    k_h = k_slots.reshape(k_slots.shape[0], cap, h, d_qk)
    v_h = v_slots.reshape(v_slots.shape[0], cap, h, d_v)
    scores = jnp.einsum("bhc,bjhc->bhj", qh, k_h, preferred_element_type=jnp.float32)
    scores = jnp.where(mask[:, None, :], MASK_VALUE, scores)
    attn = jax.nn.softmax(scores)
    return jnp.einsum("bhj,bjhc->bhc", attn.astype(v_h.dtype), v_h)


__all__ = [
    "paged_decode_attention",
    "paged_attention_reference",
    "paged_kernel_supported",
]
