"""KV-cache disciplines behind one interface: init / append / view as pytree
ops with static layouts (ROADMAP item 2, the clean way into the paged engine).

Two disciplines dispatch through the same seam today:

- :class:`KVCache` — the fixed-capacity **contiguous** cache the sliding-
  window decode has always used (one ``(B, capacity, C)`` buffer + a scalar
  valid length, written with ``lax.dynamic_update_slice``). This module is
  its new home; ``core.attention`` re-exports it unchanged, and the append
  it performs is op-for-op the code that used to live inline in
  ``MultiHeadAttention.__call__`` — the committed ``decode``/``prefill``
  graphcheck contracts pin that the extraction changed no compiled graph.
- :class:`PagedKVCache` — fixed-size **pages** from a shared pool with a
  per-request page table (arXiv:2604.15464, *Ragged Paged Attention*): every
  decode slot owns whole pages, lengths are per-slot (ragged batching), and
  a retired request's pages return to the host-side free list
  (``serving.pages.PageAllocator``) without moving a byte of KV. Appends are
  per-slot scatters under the ``paged_kv_append`` scope (the cross-program
  rule's declared-paged-companion label); reads gather pages back through
  the page table — ``gather_view`` is the ``jax.lax`` fallback CPU tier-1
  certifies token-exact against the contiguous path, and
  ``ops.paged_attention`` holds the TPU kernel that walks the table in
  BlockSpec index maps instead of materializing the view.

Both disciplines keep the int8 storage path: per-token symmetric scales ride
in ``k_scale``/``v_scale`` planes shaped like the slots (contiguous) or the
pages (paged), and :func:`quantize_kv` is shared so the rounding contract
cannot fork.

Layout invariants the seam pins (and the ``decode_paged`` contract checks):

- slots-major storage ``(…, slot, C)`` — the channels-minor layout the
  decode GEMMs read without a head transpose (see core/attention.py);
- keys stored **rotated** (rotate-at-write): a token's rotation rides it
  into whichever discipline stores it, so positions never need re-rotation;
- appends never concatenate: ``dynamic_update_slice`` (contiguous) or a
  page-indexed scatter (paged) — no discipline's graph holds a kv-axis
  concatenate.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import struct
from jax import lax


@struct.dataclass
class KVCache:
    """Fixed-capacity cache: ``k``/``v`` are (B, capacity, C) with valid data
    in slots [0, length); ``length`` is a traced int32 scalar.

    ``int8`` storage (``init_kv_cache(dtype=jnp.int8)``) keeps per-token
    symmetric quantization scales in ``k_scale``/``v_scale`` (B, capacity).
    Decode is HBM-bandwidth-bound (docs/performance.md: batch-8 runs at the
    chip's physical ceiling), so halving cache bytes buys real throughput —
    the scales fold into elementwise ops OUTSIDE the two cache GEMMs, and
    XLA reads the int8 operands at int8 bytes (measured by a probe since
    deleted: 1.69x on the decode attention core)."""

    k: jnp.ndarray
    v: jnp.ndarray
    length: jnp.ndarray
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None

    @property
    def capacity(self) -> int:
        return self.k.shape[1]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    def map_slots(self, fn, length=None) -> "KVCache":
        """Apply ``fn`` to every per-slot array (k, v, and the scales when
        present) — the one way generation code may rebuild a cache, so
        slot reorders/rolls/tiles can never drop the scale planes."""
        return KVCache(
            k=fn(self.k),
            v=fn(self.v),
            length=self.length if length is None else length,
            k_scale=None if self.k_scale is None else fn(self.k_scale),
            v_scale=None if self.v_scale is None else fn(self.v_scale),
        )

    def append(self, k: jnp.ndarray, v: jnp.ndarray) -> "KVCache":
        """Write ``k``/``v`` (B, N, C) — keys already rotated — at
        ``length``; returns the advanced cache. Exactly the in-place
        ``dynamic_update_slice`` writes the attention module has always
        traced (callers own the ``kv_cache_append`` named scope), so the
        extraction is invisible to the compiled graph."""
        start = self.length
        if self.quantized:
            # rotate-then-quantize: rotation preserves per-token norms
            # only approximately, so the scale is computed from the
            # rotated keys that actually get stored
            k_q, k_sc_new = quantize_kv(k)
            v_q, v_sc_new = quantize_kv(v)
            return KVCache(
                k=lax.dynamic_update_slice(self.k, k_q, (0, start, 0)),
                v=lax.dynamic_update_slice(self.v, v_q, (0, start, 0)),
                length=start + k.shape[1],
                k_scale=lax.dynamic_update_slice(self.k_scale, k_sc_new, (0, start)),
                v_scale=lax.dynamic_update_slice(self.v_scale, v_sc_new, (0, start)),
            )
        return KVCache(
            k=lax.dynamic_update_slice(self.k, k.astype(self.k.dtype), (0, start, 0)),
            v=lax.dynamic_update_slice(self.v, v.astype(self.v.dtype), (0, start, 0)),
            length=start + k.shape[1],
            k_scale=None,
            v_scale=None,
        )


def quantize_kv(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-token symmetric int8 quantization: (..., N, C) -> int8 values and
    a (..., N) bf16 scale with ``x ~= q * scale``. int8->bf16 is exact (|q|
    <= 127), so dequantization error is the rounding step alone."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    # round against the scale AS STORED (bf16): quantizing with a more
    # precise scale than dequantization uses would leak the bf16 rounding
    # into the error bound (up to ~0.25 extra steps at |q|=127). bf16
    # rounds to nearest, so the stored scale can be a hair below amax/127;
    # nudge up one ulp-ish factor to keep |q| <= 127 exactly.
    scale = jnp.maximum(amax / 127.0, 1e-8).astype(jnp.bfloat16)
    scale = jnp.where(scale.astype(jnp.float32) * 127.0 < amax, scale * jnp.bfloat16(1.0079), scale)
    q = jnp.round(x32 / scale.astype(jnp.float32)[..., None])
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return q, scale


def init_kv_cache(
    batch_size: int,
    capacity: int,
    num_qk_channels: int,
    num_v_channels: int,
    dtype=jnp.float32,
) -> KVCache:
    """Empty cache (length 0) — the analog of the reference's
    ``empty_kv_cache`` (modules.py:282-285) with pre-allocated capacity.
    ``dtype=jnp.int8`` selects quantized storage (see :class:`KVCache`)."""
    scales = None
    if dtype == jnp.int8:
        scales = jnp.zeros((batch_size, capacity), jnp.bfloat16)
    return KVCache(
        k=jnp.zeros((batch_size, capacity, num_qk_channels), dtype),
        v=jnp.zeros((batch_size, capacity, num_v_channels), dtype),
        length=jnp.zeros((), jnp.int32),
        k_scale=scales,
        v_scale=scales,
    )


# ---------------------------------------------------------------------------
# paged discipline
# ---------------------------------------------------------------------------


@struct.dataclass
class PagedKVCache:
    """Paged KV cache: ``k``/``v`` are (num_pages, page_size, C) pools; each
    decode slot ``s`` owns the pages ``page_table[s]`` names and has
    ``length[s]`` valid tokens — token ``t`` of slot ``s`` lives at
    ``(page_table[s, t // page_size], t % page_size)``.

    Page 0 is the SCRATCH page by convention (``serving.pages.PageAllocator``
    never hands it out): unallocated page-table entries point at it, and an
    inactive slot's appends land there harmlessly — the compiled engine step
    is total over all slots, active or not, so no per-slot control flow.

    ``length`` is per-slot (B,) int32 — the ragged-batching axis the
    contiguous cache's scalar length cannot express. Appends are one token
    per slot (the engine decode step); prompt KV arrives via
    ``commit_prefill`` from a contiguous prefill cache (prefill/decode
    disaggregation — the prompt pass itself stays the committed ``prefill``
    program, untouched).

    int8 storage mirrors :class:`KVCache`: per-token bf16 scales in
    ``k_scale``/``v_scale`` pools shaped (num_pages, page_size)."""

    k: jnp.ndarray
    v: jnp.ndarray
    page_table: jnp.ndarray  # (B, pages_per_slot) int32
    length: jnp.ndarray  # (B,) int32 valid tokens per slot
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None

    @property
    def page_size(self) -> int:
        return self.k.shape[1]

    @property
    def num_pages(self) -> int:
        return self.k.shape[0]

    @property
    def pages_per_slot(self) -> int:
        return self.page_table.shape[1]

    @property
    def capacity(self) -> int:
        """Per-slot token capacity (the contiguous view's slot axis)."""
        return self.pages_per_slot * self.page_size

    @property
    def slots(self) -> int:
        return self.page_table.shape[0]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    def append(self, k: jnp.ndarray, v: jnp.ndarray) -> "PagedKVCache":
        """Append ONE token per slot: ``k``/``v`` are (B, 1, C), keys already
        rotated. The write position is page-table-indexed — a gather for the
        page id, then a scatter into the pool (callers own the
        ``paged_kv_append`` named scope the cross-program rule keys on).
        Overflowing slots clamp to their last page (inactive slots point at
        scratch and never overflow live data)."""
        if k.shape[1] != 1:
            raise ValueError(f"paged append is one token per slot, got {k.shape[1]}")
        b = self.page_table.shape[0]
        pos = self.length
        page_idx = jnp.minimum(pos // self.page_size, self.pages_per_slot - 1)
        page_id = jnp.take_along_axis(self.page_table, page_idx[:, None], axis=1)[:, 0]
        offset = pos % self.page_size
        if self.quantized:
            rows = jnp.arange(b)
            k_q, k_sc = quantize_kv(k)
            v_q, v_sc = quantize_kv(v)
            return PagedKVCache(
                k=self.k.at[page_id, offset].set(k_q[:, 0].astype(self.k.dtype)),
                v=self.v.at[page_id, offset].set(v_q[:, 0].astype(self.v.dtype)),
                page_table=self.page_table,
                length=pos + 1,
                k_scale=self.k_scale.at[page_id, offset].set(k_sc[rows, 0]),
                v_scale=self.v_scale.at[page_id, offset].set(v_sc[rows, 0]),
            )
        return PagedKVCache(
            k=self.k.at[page_id, offset].set(k[:, 0].astype(self.k.dtype)),
            v=self.v.at[page_id, offset].set(v[:, 0].astype(self.v.dtype)),
            page_table=self.page_table,
            length=pos + 1,
            k_scale=None,
            v_scale=None,
        )

    def append_span(self, k: jnp.ndarray, v: jnp.ndarray) -> "PagedKVCache":
        """Append N tokens per slot at each slot's own fill level — the
        SPECULATIVE VERIFY geometry (``generation.make_speculative_paged_
        step_fn``): token ``i`` of slot ``b`` lands at position
        ``length[b] + i``, via a page-table gather for the page ids and one
        scatter per pool (k, v, and the scale planes when quantized) —
        still no kv-axis concatenate, the same discipline :meth:`append`
        pins one token at a time. Rollback of a rejected span suffix is the
        CALLER adjusting ``length`` back down (a per-slot counter move; the
        written slots beyond the new length are dead until the next span
        overwrites them). Out-of-range positions clamp into the slot's last
        page — callers provision ``pages_per_slot`` with span slack."""
        n = k.shape[1]
        pos = self.length[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]  # (B, n)
        page_idx = jnp.minimum(pos // self.page_size, self.pages_per_slot - 1)
        page_id = jnp.take_along_axis(self.page_table, page_idx, axis=1)  # (B, n)
        offset = pos % self.page_size
        if self.quantized:
            k_q, k_sc = quantize_kv(k)
            v_q, v_sc = quantize_kv(v)
            return PagedKVCache(
                k=self.k.at[page_id, offset].set(k_q.astype(self.k.dtype)),
                v=self.v.at[page_id, offset].set(v_q.astype(self.v.dtype)),
                page_table=self.page_table,
                length=self.length + n,
                k_scale=self.k_scale.at[page_id, offset].set(k_sc),
                v_scale=self.v_scale.at[page_id, offset].set(v_sc),
            )
        return PagedKVCache(
            k=self.k.at[page_id, offset].set(k.astype(self.k.dtype)),
            v=self.v.at[page_id, offset].set(v.astype(self.v.dtype)),
            page_table=self.page_table,
            length=self.length + n,
            k_scale=None,
            v_scale=None,
        )

    def gather_view(self) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray], Optional[jnp.ndarray]]:
        """The contiguous (B, capacity, C) view of every slot's pages — the
        ``jax.lax`` gather fallback the CPU tier-1 suite certifies
        token-exact against :class:`KVCache`. One gather per pool (k, v, and
        the scale planes when quantized) — the ``decode_paged`` contract
        budgets exactly these; the TPU kernel (ops/paged_attention.py) walks
        the table in its BlockSpecs instead and never materializes this."""
        b = self.slots
        cap = self.capacity

        def view(pool):
            g = jnp.take(pool, self.page_table.reshape(-1), axis=0)
            return g.reshape((b, cap) + pool.shape[2:])

        k = view(self.k)
        v = view(self.v)
        if not self.quantized:
            return k, v, None, None
        return k, v, view(self.k_scale), view(self.v_scale)


def init_paged_kv_cache(
    slots: int,
    num_pages: int,
    page_size: int,
    pages_per_slot: int,
    num_qk_channels: int,
    num_v_channels: int,
    dtype=jnp.float32,
) -> PagedKVCache:
    """Empty paged cache: all page-table entries point at the scratch page
    (page 0), all lengths 0. The pool is shared by every slot; the host-side
    allocator (serving.pages) owns which pages each live request holds."""
    if num_pages < 2:
        raise ValueError("need at least 2 pages (page 0 is reserved scratch)")
    scales = None
    if dtype == jnp.int8:
        scales = jnp.zeros((num_pages, page_size), jnp.bfloat16)
    return PagedKVCache(
        k=jnp.zeros((num_pages, page_size, num_qk_channels), dtype),
        v=jnp.zeros((num_pages, page_size, num_v_channels), dtype),
        page_table=jnp.zeros((slots, pages_per_slot), jnp.int32),
        length=jnp.zeros((slots,), jnp.int32),
        k_scale=scales,
        v_scale=scales,
    )


def commit_prefill(
    paged: PagedKVCache,
    slot: int,
    page_ids: jnp.ndarray,
    prefill_cache: KVCache,
    n_tokens: jnp.ndarray,
) -> PagedKVCache:
    """Move one request's prompt KV from a contiguous prefill cache into its
    freshly allocated pages — the prefill/decode disaggregation seam: the
    prompt pass runs the committed contiguous ``prefill`` program, then this
    (jit-friendly, donation-safe) copy lands its rows in the pool.

    ``page_ids`` is (n,) int32 naming the pages slot ``slot`` now owns (the
    allocator's grant, scratch-padded to the static table width is the
    CALLER's job — this writes ``len(page_ids)`` pages' worth of rows);
    ``n_tokens`` is the request's true token count (page-tail rows beyond it
    carry junk from the prefill buffer's slack — harmless: reads mask
    ``>= length``). ``slot`` is a static int (one compiled copy per slot id
    would retrace; callers jit with ``static_argnums`` on it or pass a
    traced scalar via the (slot,) update below)."""
    n = page_ids.shape[0]
    page_size = paged.page_size

    def rows_of(buf):
        # (1, cap, ...) -> the first n*page_size slots as (n, page_size, ...);
        # a prefill buffer shorter than the page span (its capacity is
        # prompt + budget, not page-rounded) zero-pads the tail — those rows
        # sit beyond `length` and reads mask them
        want = n * page_size
        rows = buf[0]
        if rows.shape[0] < want:
            widths = [(0, want - rows.shape[0])] + [(0, 0)] * (rows.ndim - 1)
            rows = jnp.pad(rows, widths)
        elif rows.shape[0] > want:
            rows = lax.slice_in_dim(rows, 0, want, axis=0)
        return rows.reshape((n, page_size) + buf.shape[2:])

    table_row = jnp.zeros((paged.pages_per_slot,), jnp.int32).at[:n].set(page_ids)
    k_scale = paged.k_scale
    v_scale = paged.v_scale
    if paged.quantized:
        if not prefill_cache.quantized:
            raise ValueError("paged cache is int8 but the prefill cache is not")
        k_scale = k_scale.at[page_ids].set(rows_of(prefill_cache.k_scale))
        v_scale = v_scale.at[page_ids].set(rows_of(prefill_cache.v_scale))
    elif prefill_cache.quantized:
        raise ValueError("prefill cache is int8 but the paged cache is not")
    return PagedKVCache(
        k=paged.k.at[page_ids].set(rows_of(prefill_cache.k)),
        v=paged.v.at[page_ids].set(rows_of(prefill_cache.v)),
        page_table=paged.page_table.at[slot].set(table_row),
        length=paged.length.at[slot].set(n_tokens.astype(jnp.int32)),
        k_scale=k_scale,
        v_scale=v_scale,
    )


def release_slot(paged: PagedKVCache, slot: int) -> PagedKVCache:
    """Point a retired slot's table row back at scratch and zero its length
    (the device half of a retire; the host half returns the pages to the
    allocator's free list). No pool bytes move."""
    return PagedKVCache(
        k=paged.k,
        v=paged.v,
        page_table=paged.page_table.at[slot].set(jnp.zeros((paged.pages_per_slot,), jnp.int32)),
        length=paged.length.at[slot].set(0),
        k_scale=paged.k_scale,
        v_scale=paged.v_scale,
    )


# ---------------------------------------------------------------------------
# latent discipline
# ---------------------------------------------------------------------------


@struct.dataclass
class LatentCache:
    """Fixed-capacity cache of multi-head latent attention: one joint row a
    token, ``[c_kv (kv_lora_rank); k_rope (rope dim, rotated at write)]``,
    shared by every head. ``rows`` is (B, capacity, width) with valid data in
    slots [0, length); ``length`` is a traced int32 scalar, one for the
    batch (no row is padded). There is no ``k`` and no ``v``: the absorbed
    attention reads scores and values from the same row."""

    rows: jnp.ndarray
    length: jnp.ndarray

    @property
    def capacity(self) -> int:
        return self.rows.shape[1]

    @property
    def row_bytes(self) -> int:
        return self.rows.shape[2] * self.rows.dtype.itemsize

    def append(self, rows: jnp.ndarray) -> "LatentCache":
        """Write ``rows`` (B, N, width) at ``length``; returns the advanced cache."""
        new = lax.dynamic_update_slice(self.rows, rows.astype(self.rows.dtype), (0, self.length, 0))
        return LatentCache(rows=new, length=self.length + rows.shape[1])


def init_latent_cache(batch_size: int, capacity: int, width: int, dtype=jnp.float32) -> LatentCache:
    return LatentCache(rows=jnp.zeros((batch_size, capacity, width), dtype), length=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# recurrent state (no discipline of slots: it does not grow)
# ---------------------------------------------------------------------------


@struct.dataclass
class RecurrentState:
    """What a state-space layer (``core/ssm.py``) keeps of a row's past, of
    one size whatever the context: ``conv`` (B, d_conv - 1, d_inner), the last
    inputs of the causal convolution, oldest first, and ``ssm`` (B, d_state,
    d_inner) float32, the recurrence's state with the channels on the minor
    axis. Unlike the caches around it in a generator's state it has no length
    and no slots: a step reads and writes it whole."""

    conv: jnp.ndarray
    ssm: jnp.ndarray


def init_recurrent_state(batch_size: int, d_conv: int, d_state: int, d_inner: int, dtype=jnp.float32) -> RecurrentState:
    """The state before a row's first token: a window of zeros (the convolution
    pads with zeros) and ``h_0 = 0``. ``dtype`` is the window's; the state is float32."""
    return RecurrentState(conv=jnp.zeros((batch_size, d_conv - 1, d_inner), dtype),
                          ssm=jnp.zeros((batch_size, d_state, d_inner), jnp.float32))


@struct.dataclass
class RetentionState:
    """What a power retention layer (``core/retention.py``) keeps of a row's
    past, of one size whatever the context: ``s`` (B, Hkv, R, D) float32, a
    key-value head's decayed sum of ``phi(k) v^T``, and ``z`` (B, Hkv, R / D, D)
    float32, its decayed sum of ``phi(k)``, over the ``R`` rows of the feature
    map ``phi`` (``ops/power_retention.py``: ``D / 2 + 1`` tiles of ``D``, the
    values' channel on a tile's rows and the feature on the lanes). No slots: a
    step reads and writes both whole. **It has a length** (the tokens the state
    holds, a scalar as a :class:`KVCache`'s): a stack made of such states alone
    has no growing cache to read a step's rotary position off, so the state
    carries it."""

    s: jnp.ndarray
    z: jnp.ndarray
    length: jnp.ndarray


def init_retention_state(batch_size: int, kv_heads: int, feature_rows: int, head_dim: int) -> RetentionState:
    """The state before a row's first token: nothing summed, no token held. Float32 whatever the caches' dtype."""
    return RetentionState(s=jnp.zeros((batch_size, kv_heads, feature_rows, head_dim), jnp.float32),
                          z=jnp.zeros((batch_size, kv_heads, feature_rows // head_dim, head_dim), jnp.float32),
                          length=jnp.zeros((), jnp.int32))


@struct.dataclass
class DeltaState:
    """What a Kimi delta attention layer (``core/kda.py``) keeps of a row's
    past, of one size whatever the context: ``s`` (B, H, D_v, D_k) float32, a
    head's delta-rule state **stored transposed** (the value's channel on the
    rows, the key's on the lanes: ``ops/kda.py``), and the last ``K - 1`` inputs
    of the three causal convolutions, ``conv_q``, ``conv_k``, ``conv_v`` (B, K -
    1, H * D) each, oldest first. No slots and **no length**: the layer reads no
    position, and a stack that mixes it with attention reads a step's position
    off the attention's cache. A step reads and writes ``s`` whole, in place."""

    s: jnp.ndarray
    conv_q: jnp.ndarray
    conv_k: jnp.ndarray
    conv_v: jnp.ndarray


def init_delta_state(batch_size: int, heads: int, head_dim: int, d_conv: int, dtype=jnp.float32) -> DeltaState:
    """The state before a row's first token: nothing written, windows of zeros
    (the convolutions pad with zeros). ``dtype`` is the windows'; the state is float32."""
    window = jnp.zeros((batch_size, d_conv - 1, heads * head_dim), dtype)
    return DeltaState(s=jnp.zeros((batch_size, heads, head_dim, head_dim), jnp.float32),
                      conv_q=window, conv_k=window, conv_v=window)


# ---------------------------------------------------------------------------
# window discipline
# ---------------------------------------------------------------------------


@struct.dataclass
class WindowKVCache:
    """The cache of a sliding-window attention layer: a ring of ``window``
    slots. ``k``/``v`` are (B, window, C); the token at position ``p`` lives
    in slot ``p % window``, so a write at ``length % window`` overwrites the
    one position that has just left the window. Keys are stored rotated, so
    the order of the slots does not matter to the softmax and nothing is ever
    moved. ``length`` is a traced int32 scalar, the tokens seen so far (one
    for the batch); slots ``[0, min(length, window))`` hold live tokens.

    Beside it, in the same generator state, a full-attention layer keeps a
    :class:`KVCache` that grows with the context."""

    k: jnp.ndarray
    v: jnp.ndarray
    length: jnp.ndarray

    @property
    def capacity(self) -> int:
        return self.k.shape[1]

    def fill(self, k: jnp.ndarray, v: jnp.ndarray, n: int) -> "WindowKVCache":
        """The cache after a prompt pass of ``n`` positions over an empty
        ring: ``k``/``v`` (B, min(n, window), C) are the rows of the prompt's
        last positions, each put in its slot."""
        w = self.capacity
        if k.shape[1] != min(n, w):
            raise ValueError(f"a prompt of {n} positions fills a ring of {w} with its last {min(n, w)}, got {k.shape[1]}")

        def place(buf, rows):
            rows = rows.astype(buf.dtype)
            if n <= w:
                return lax.dynamic_update_slice(buf, rows, (0, 0, 0))
            # position p sits at slot p % w: the rows from n - w on, turned by (n - w) % w
            return jnp.roll(rows, (n - w) % w, axis=1)

        return WindowKVCache(k=place(self.k, k), v=place(self.v, v), length=jnp.asarray(n, jnp.int32))

    def append(self, k: jnp.ndarray, v: jnp.ndarray) -> "WindowKVCache":
        """Write one token a row, ``k``/``v`` (B, 1, C), keys already rotated, at ``length % window``."""
        if k.shape[1] != 1:
            raise ValueError(f"a window cache takes one token a step, got {k.shape[1]}")
        slot = self.length % self.capacity
        return WindowKVCache(
            k=lax.dynamic_update_slice(self.k, k.astype(self.k.dtype), (0, slot, 0)),
            v=lax.dynamic_update_slice(self.v, v.astype(self.v.dtype), (0, slot, 0)),
            length=self.length + 1,
        )


def init_window_kv_cache(batch_size: int, window: int, num_qk_channels: int, num_v_channels: int,
                         dtype=jnp.float32) -> WindowKVCache:
    return WindowKVCache(
        k=jnp.zeros((batch_size, window, num_qk_channels), dtype),
        v=jnp.zeros((batch_size, window, num_v_channels), dtype),
        length=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# a length a row: the caches of a step that yields one or two tokens a row
# ---------------------------------------------------------------------------
#
# A speculative step (``generation._generate_speculative``) runs the model on
# a row's last emitted token and on the draft of the next one, and each row
# keeps the draft's position only where the model agreed with it: rows advance
# by different counts, so the length is a row's own. ``k``/``v`` are
# (B * H, slots, C) as in :class:`KVCache` under grouped-query attention (a
# key-value head is a row of the products); ``length`` is (B,) int32, shared by
# a row's H heads. ``write`` puts ``n`` positions a row at ``length`` and on
# and leaves ``length`` alone; ``keep`` advances it by the positions that
# stay. What was written past the kept positions is dead: no query sees it
# (``visible``) and the next step's write starts on top of it. Any capacity
# and any slack of a ring from ``n - 1`` on is sound: a slot no position of the
# row maps to yet, or one past what a query sees, is masked by where it lies.
# The speculative generator builds both kinds in whole sublane tiles of the
# cache's dtype (``decoder_lm._Decoder.ring_slack``, ``full_capacity``), which
# is what ``ops/gqa_verify.py``, the step's kernel on the chip, writes back;
# ``write`` and ``visible`` here are the path of every other cache and what the
# kernel is held to. These stand beside the classes above, whose programs are
# the accepted cells'.


def _query_positions(length: jnp.ndarray, heads: int, n: int, group: int) -> jnp.ndarray:
    """(B * H, n * group) int32: the position of each query of a step of ``n`` positions, ``group`` queries a position."""
    return jnp.repeat(length, heads)[:, None] + (jnp.arange(n * group, dtype=jnp.int32) // group)[None, :]


def _row_scatter(buf: jnp.ndarray, slots: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    """``buf[r, slots[r, i]] = rows[r, i]``: ``buf`` (R, S, C), ``slots`` (R, n)
    distinct within a row, ``rows`` (R, n, C). A slot past ``S`` is dropped."""
    r = jnp.arange(buf.shape[0], dtype=jnp.int32)[:, None]
    return buf.at[r, slots].set(rows.astype(buf.dtype), mode="drop", unique_indices=True)


@struct.dataclass
class RaggedKVCache:
    """A growing cache with a length a row: position ``p`` of row ``b`` lives
    in slot ``p`` of rows ``b * H .. b * H + H - 1``."""

    k: jnp.ndarray
    v: jnp.ndarray
    length: jnp.ndarray  # (B,) int32

    @property
    def capacity(self) -> int:
        return self.k.shape[1]

    @property
    def heads(self) -> int:
        return self.k.shape[0] // self.length.shape[0]

    def fill(self, k: jnp.ndarray, v: jnp.ndarray) -> "RaggedKVCache":
        """The cache after a prompt pass over an empty one: ``k``/``v`` (B * H, n, C), every row ``n`` long."""
        n = k.shape[1]
        return RaggedKVCache(
            k=lax.dynamic_update_slice(self.k, k.astype(self.k.dtype), (0, 0, 0)),
            v=lax.dynamic_update_slice(self.v, v.astype(self.v.dtype), (0, 0, 0)),
            length=jnp.full_like(self.length, n),
        )

    def write(self, k: jnp.ndarray, v: jnp.ndarray) -> "RaggedKVCache":
        """``k``/``v`` (B * H, n, C), keys already rotated, at each row's ``length .. length + n - 1``."""
        slots = jnp.repeat(self.length, self.heads)[:, None] + jnp.arange(k.shape[1], dtype=jnp.int32)[None, :]
        return self.replace(k=_row_scatter(self.k, slots, k), v=_row_scatter(self.v, slots, v))

    def keep(self, m: jnp.ndarray) -> "RaggedKVCache":
        """Advance each row by ``m`` (B,) of the positions last written."""
        return self.replace(length=self.length + m.astype(jnp.int32))

    def visible(self, n: int, group: int = 1) -> jnp.ndarray:
        """(B * H, n * group, slots) bool, in the layout of the attention
        products (``group`` queries a position, position-major): what the
        query at ``length + i`` sees once ``n`` positions are written, slots
        up to its own."""
        q_pos = _query_positions(self.length, self.heads, n, group)
        return jnp.arange(self.capacity, dtype=jnp.int32)[None, None, :] <= q_pos[:, :, None]


def init_ragged_kv_cache(batch_size: int, heads: int, capacity: int, num_qk_channels: int, num_v_channels: int,
                         dtype=jnp.float32) -> RaggedKVCache:
    return RaggedKVCache(
        k=jnp.zeros((batch_size * heads, capacity, num_qk_channels), dtype),
        v=jnp.zeros((batch_size * heads, capacity, num_v_channels), dtype),
        length=jnp.zeros((batch_size,), jnp.int32),
    )


@struct.dataclass
class RaggedWindowKVCache:
    """A window layer's ring with a length a row and ``slack`` slots more
    than the window: position ``p`` lives in slot ``p % (window + slack)``.
    A step that writes ``n <= slack + 1`` positions at ``length`` and on
    overwrites positions ``length + i - window - slack``, which no query of the
    step or after it sees, so a position that is written and not kept has
    destroyed nothing: a ring of ``window`` slots alone would lose position
    ``length - window + 1`` to the draft at ``length + 1`` while the query at
    ``length`` still sees it. ``visible`` reads each slot's position back from
    the row's length, so a dead slot (a rejected draft, or a slot of the
    slack) is masked by where it lies, not by what it holds."""

    k: jnp.ndarray
    v: jnp.ndarray
    length: jnp.ndarray  # (B,) int32: the positions kept so far
    window: int = struct.field(pytree_node=False)

    @property
    def capacity(self) -> int:
        return self.k.shape[1]

    @property
    def slack(self) -> int:
        return self.capacity - self.window

    @property
    def heads(self) -> int:
        return self.k.shape[0] // self.length.shape[0]

    def fill(self, k: jnp.ndarray, v: jnp.ndarray, n: int) -> "RaggedWindowKVCache":
        """The cache after a prompt pass of ``n`` positions over an empty
        ring: ``k``/``v`` (B * H, min(n, window), C) are the rows of the
        prompt's last positions, each put in its slot."""
        w, ring = self.window, self.capacity
        if k.shape[1] != min(n, w):
            raise ValueError(f"a prompt of {n} positions fills a ring of window {w} with its last {min(n, w)}, got {k.shape[1]}")
        slots = (max(n - w, 0) + jnp.arange(min(n, w), dtype=jnp.int32)) % ring  # fixed at trace time

        def place(buf, rows):
            return buf.at[:, slots].set(rows.astype(buf.dtype), unique_indices=True)

        return self.replace(k=place(self.k, k), v=place(self.v, v), length=jnp.full_like(self.length, n))

    def write(self, k: jnp.ndarray, v: jnp.ndarray) -> "RaggedWindowKVCache":
        """``k``/``v`` (B * H, n, C), keys already rotated, at each row's positions ``length .. length + n - 1``."""
        n = k.shape[1]
        if n > self.slack + 1:
            raise ValueError(f"a ring with {self.slack} slots of slack takes at most {self.slack + 1} positions a step, got {n}")
        slots = (jnp.repeat(self.length, self.heads)[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]) % self.capacity
        return self.replace(k=_row_scatter(self.k, slots, k), v=_row_scatter(self.v, slots, v))

    def keep(self, m: jnp.ndarray) -> "RaggedWindowKVCache":
        return self.replace(length=self.length + m.astype(jnp.int32))

    def visible(self, n: int, group: int = 1) -> jnp.ndarray:
        """(B * H, n * group, slots) bool, in the layout of the attention
        products, once ``n`` positions are written at ``length`` and on: slot
        ``s`` holds the largest position ``p <= length + n - 1`` with
        ``p % slots == s``, and the query at ``q = length + i`` sees it iff
        ``q - window < p <= q`` (and ``p >= 0``: the ring may not be full)."""
        ring = self.capacity
        last = jnp.repeat(self.length, self.heads)[:, None] + (n - 1)  # (B * H, 1): the largest position written
        held = last - (last - jnp.arange(ring, dtype=jnp.int32)[None, :]) % ring  # (B * H, slots)
        p, q = held[:, None, :], _query_positions(self.length, self.heads, n, group)[:, :, None]
        return (p <= q) & (p > q - self.window) & (p >= 0)


def init_ragged_window_kv_cache(batch_size: int, heads: int, window: int, slack: int, num_qk_channels: int,
                                num_v_channels: int, dtype=jnp.float32) -> RaggedWindowKVCache:
    return RaggedWindowKVCache(
        k=jnp.zeros((batch_size * heads, window + slack, num_qk_channels), dtype),
        v=jnp.zeros((batch_size * heads, window + slack, num_v_channels), dtype),
        length=jnp.zeros((batch_size,), jnp.int32),
        window=window,
    )


# ---------------------------------------------------------------------------
# latent attention that chooses its keys, and latent attention behind a window
# ---------------------------------------------------------------------------
#
# A stack whose full layers run learned sparse attention (``core/dsa.py``) over
# latent attention and whose window layers run a second latent attention keeps
# three cache kinds in one generator state: a full layer's growing
# :class:`LatentCache` of joint rows **and**, beside it, the growing cache of its
# indexer's keys (one key of ``index_head_dim`` channels a token; the class that
# holds it is :class:`LatentCache` itself, its "joint row" the one key: no
# second class for an array and a length), both in one
# :class:`IndexedLatentCache`; and a window layer's :class:`LatentRingCache`.


@struct.dataclass
class IndexedLatentCache:
    """A full layer's two growing caches: ``latent`` the joint rows the
    attention reads (B, capacity, kv_lora_rank + rope), ``index`` the indexer's
    rotated keys (B, capacity, index_head_dim) the selection scores. Both are
    written at the same position, so one length serves."""

    latent: LatentCache
    index: LatentCache

    @property
    def length(self) -> jnp.ndarray:
        return self.latent.length

    @property
    def capacity(self) -> int:
        return self.latent.capacity

    def append(self, rows: jnp.ndarray, keys: jnp.ndarray) -> "IndexedLatentCache":
        return IndexedLatentCache(latent=self.latent.append(rows), index=self.index.append(keys))


def init_indexed_latent_cache(batch_size: int, capacity: int, width: int, index_width: int, dtype=jnp.float32) -> IndexedLatentCache:
    return IndexedLatentCache(latent=init_latent_cache(batch_size, capacity, width, dtype),
                              index=init_latent_cache(batch_size, capacity, index_width, dtype))


@struct.dataclass
class LatentRingCache:
    """The cache of a latent attention behind a sliding window: a ring of
    joint rows. ``rows`` is (B, slots, width) with ``slots >= window``; the
    token at position ``p`` lives in slot ``p % slots``, so a write at
    ``length % slots`` overwrites a position that left the window ``slots -
    window`` steps ago or more. ``slots`` may be more than ``window`` (whole
    sublane tiles of the cache's dtype, so that the loop carries the ring
    row-major): what a slot holds is known from where it lies, and
    :meth:`visible` hides a slot whose position is out of the window or was
    never written. ``length`` is a traced int32 scalar, the tokens seen so
    far (one for the batch)."""

    rows: jnp.ndarray
    length: jnp.ndarray
    window: int = struct.field(pytree_node=False)

    @property
    def capacity(self) -> int:
        return self.rows.shape[1]

    def fill(self, rows: jnp.ndarray, n: int) -> "LatentRingCache":
        """The ring after a prompt pass of ``n`` positions over an empty one:
        ``rows`` (B, min(n, slots), width) are the prompt's last positions, each put in its slot."""
        slots = self.capacity
        if rows.shape[1] != min(n, slots):
            raise ValueError(f"a prompt of {n} positions fills a ring of {slots} with its last {min(n, slots)}, got {rows.shape[1]}")
        rows = rows.astype(self.rows.dtype)
        if n <= slots:
            placed = lax.dynamic_update_slice(self.rows, rows, (0, 0, 0))
        else:  # position p sits at slot p % slots: the rows from n - slots on, turned by (n - slots) % slots
            placed = jnp.roll(rows, (n - slots) % slots, axis=1)
        return LatentRingCache(rows=placed, length=jnp.asarray(n, jnp.int32), window=self.window)

    def append(self, row: jnp.ndarray) -> "LatentRingCache":
        """Write one token a row, ``row`` (B, 1, width), at ``length % slots``."""
        if row.shape[1] != 1:
            raise ValueError(f"a ring takes one token a step, got {row.shape[1]}")
        placed = lax.dynamic_update_slice(self.rows, row.astype(self.rows.dtype), (0, self.length % self.capacity, 0))
        return LatentRingCache(rows=placed, length=self.length + 1, window=self.window)

    def visible(self) -> jnp.ndarray:
        """(slots,) bool: the slots the query at position ``length - 1`` (the
        token last written) sees: slot ``j`` holds position ``t - (t - j) % slots``
        with ``t = length - 1``, seen where it is not negative and within ``window`` of ``t``."""
        t = self.length - 1
        age = (t - jnp.arange(self.capacity, dtype=jnp.int32)) % self.capacity
        return (age < self.window) & (age <= t)


def init_latent_ring_cache(batch_size: int, window: int, slots: int, width: int, dtype=jnp.float32) -> LatentRingCache:
    if slots < window:
        raise ValueError(f"a ring of {slots} slots cannot hold a window of {window}")
    return LatentRingCache(rows=jnp.zeros((batch_size, slots, width), dtype), length=jnp.zeros((), jnp.int32), window=window)
