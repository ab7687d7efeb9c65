"""Sequence/context parallelism: ring attention and sequence-sharded
cross-attention over the ``seq`` mesh axis.

The reference has **no** sequence/context parallelism (SURVEY §2.7 P8); long
context is handled architecturally by Perceiver AR's asymmetric attention
(reference: perceiver/model/core/modules.py:850-866). This module is the
beyond-parity TPU scale-out path for that same architecture: when the context
no longer fits one chip's HBM, the KV sequence axis is sharded over the mesh
and attention is computed blockwise with online-softmax combination, with XLA
collectives (``ppermute`` / ``psum`` / ``pmax``) riding ICI.

Two primitives, both exact (no approximation — they reproduce dense softmax
attention up to float error):

- :func:`seq_sharded_cross_attention` — queries replicated (or small, e.g.
  Perceiver AR latents), KV sharded along ``seq``. Each device attends its
  local KV block, then partial outputs are combined with a log-sum-exp
  reduction (one ``pmax`` + two ``psum``). This is the cheap form when
  ``num_latents`` is small: communication is O(latents), independent of
  context length.
- :func:`ring_self_attention` — queries *and* KV sharded along ``seq``
  (blockwise self-attention over a very long sequence). KV blocks rotate
  around the ring with ``ppermute`` while each device accumulates its query
  block's online softmax — the Ring Attention pattern (Liu et al.,
  arXiv:2310.01889), expressed with XLA collectives instead of NCCL.

Both are plain functions over per-device shards, designed to be called inside
``jax.shard_map`` with a named ``seq`` axis; :func:`make_ring_cross_attention`
/ :func:`make_ring_self_attention` build jitted whole-array wrappers.

Masking follows the core attention contract (core/attention.py): ``pad_mask``
is True at *masked* key positions; causal masking is right-aligned when the
query length differs from the total KV length (reference semantics,
modules.py:135-140).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from perceiver_io_tpu.ops.online_softmax import (
    NEG_INF as _NEG_INF,
    block_attention as _block_attention,
    finalize as _finalize,
    online_combine as _online_combine,
)
from perceiver_io_tpu.parallel.mesh import AXIS_SEQ


def seq_sharded_cross_attention(
    q: jnp.ndarray,
    k_local: jnp.ndarray,
    v_local: jnp.ndarray,
    pad_mask_local: Optional[jnp.ndarray] = None,
    *,
    axis_name: str = AXIS_SEQ,
    causal: bool = False,
    kv_len_total: Optional[int] = None,
    finalize: bool = True,
):
    """Cross-attention with replicated queries and KV sharded along
    ``axis_name``. Call inside ``shard_map``.

    q: (B, H, N, Dk) replicated per device (pre-scaled, pre-RoPE'd).
    k_local/v_local: (B, H, M_local, Dk|Dv) — this device's KV block.
    pad_mask_local: (B, M_local) True = masked, or None.
    causal: right-aligned causal mask over *global* KV positions (Perceiver
        AR latents: query i sits at global position kv_len_total - N + i).
    finalize: normalize and return (B, H, N, Dv) f32 output (default); with
        ``finalize=False`` return the un-normalized online-softmax partial
        ``(o, m, l)`` so callers can fold further blocks in with
        ``online_combine`` — the composition hook PerceiverAR's
        sequence-parallel forward uses to merge the sharded-prefix partial
        with its replicated causal latent block.
    Returns the normalized output (B, H, N, Dv) in float32, identical on all
    devices of the axis (or the ``(o, m, l)`` partial, see ``finalize``).
    """
    idx = lax.axis_index(axis_name)
    m_local = k_local.shape[2]
    if kv_len_total is None:
        kv_len_total = m_local * lax.axis_size(axis_name)

    kv_global = idx * m_local + jnp.arange(m_local, dtype=jnp.int32)
    masked = jnp.zeros((1, 1, 1, m_local), dtype=bool)
    if pad_mask_local is not None:
        masked = masked | pad_mask_local[:, None, None, :]
    if causal:
        n_q = q.shape[2]
        q_abs = kv_len_total - n_q + jnp.arange(n_q, dtype=jnp.int32)
        masked = masked | (kv_global[None, None, None, :] > q_abs[None, None, :, None])

    o, m, l = _block_attention(q, k_local, v_local, masked)

    # log-sum-exp combine across the axis: O(N) communication, not O(M)
    m_glob = lax.pmax(m, axis_name)
    scale = jnp.exp(m - jnp.maximum(m_glob, _NEG_INF / 2))
    o = lax.psum(o * scale[..., None], axis_name)
    l = lax.psum(l * scale, axis_name)
    if not finalize:
        return o, m_glob, l
    return _finalize(o, l)


def ring_self_attention(
    q_local: jnp.ndarray,
    k_local: jnp.ndarray,
    v_local: jnp.ndarray,
    pad_mask_local: Optional[jnp.ndarray] = None,
    *,
    axis_name: str = AXIS_SEQ,
    causal: bool = False,
) -> jnp.ndarray:
    """Ring attention: queries and KV both sharded along ``axis_name``.
    Call inside ``shard_map``.

    q_local: (B, H, N_local, Dk) — this device's query block (pre-scaled).
    k_local/v_local: (B, H, M_local, ·) — this device's KV block.
    pad_mask_local: (B, M_local) True = masked, or None.

    KV blocks (and their pad masks) travel around the ring with ``ppermute``;
    each device folds every visiting block into its query block's online
    softmax. With ``causal=True``, blocks entirely in the future contribute
    nothing (they are masked, not skipped — control flow stays static; XLA
    still overlaps the permute with the block matmul).
    """
    n_dev = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    n_q, m_local = q_local.shape[2], k_local.shape[2]

    # Right-aligned query positions (core attention contract): when the
    # global query length differs from the global KV length, query i sits at
    # global slot kv_total - q_total + i.
    right_shift = (m_local - n_q) * n_dev
    q_global = right_shift + idx * n_q + jnp.arange(n_q, dtype=jnp.int32)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    o = jnp.zeros(q_local.shape[:3] + (v_local.shape[3],), jnp.float32)
    m = jnp.full(q_local.shape[:3], _NEG_INF, jnp.float32)
    l = jnp.zeros(q_local.shape[:3], jnp.float32)
    k_blk, v_blk, pm_blk = k_local, v_local, pad_mask_local

    for step in range(n_dev):
        src = (idx - step) % n_dev  # whose block we currently hold
        kv_global = src * m_local + jnp.arange(m_local, dtype=jnp.int32)
        masked = jnp.zeros((1, 1, 1, m_local), dtype=bool)
        if pm_blk is not None:
            masked = masked | pm_blk[:, None, None, :]
        if causal:
            masked = masked | (kv_global[None, None, None, :] > q_global[None, None, :, None])
        blk = _block_attention(q_local, k_blk, v_blk, masked)
        o, m, l = _online_combine((o, m, l), blk)
        if step + 1 < n_dev:
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)
            if pm_blk is not None:
                pm_blk = lax.ppermute(pm_blk.astype(jnp.uint8), axis_name, perm).astype(bool)

    return _finalize(o, l)


def _make_wrapper(fn, mesh: Mesh, q_spec: P, out_spec: P):
    """Build an attend(q, k, v, pad_mask=None) dispatcher over jitted
    shard_maps (one with and one without the optional mask argument)."""
    kv_spec = P(None, None, AXIS_SEQ, None)
    with_mask = jax.jit(
        jax.shard_map(
            fn, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec, P(None, AXIS_SEQ)),
            out_specs=out_spec,
        )
    )
    no_mask = jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec), out_specs=out_spec)
    )

    def attend(q, k, v, pad_mask=None):
        return with_mask(q, k, v, pad_mask) if pad_mask is not None else no_mask(q, k, v)

    return attend


def make_ring_cross_attention(mesh: Mesh, *, causal: bool = False, kv_len_total: Optional[int] = None):
    """Jitted whole-array wrapper: q replicated, k/v (and pad_mask, if any)
    sharded along ``seq`` on their length axis. Arrays are (B, H, N|M, D);
    pad_mask (B, M) or omitted."""
    fn = partial(
        seq_sharded_cross_attention, axis_name=AXIS_SEQ, causal=causal, kv_len_total=kv_len_total
    )
    return _make_wrapper(fn, mesh, q_spec=P(), out_spec=P())


def make_ring_self_attention(mesh: Mesh, *, causal: bool = False):
    """Jitted whole-array wrapper: q, k, v (and pad_mask, if any) all
    sharded along ``seq`` on their length axis."""
    fn = partial(ring_self_attention, axis_name=AXIS_SEQ, causal=causal)
    spec = P(None, None, AXIS_SEQ, None)
    return _make_wrapper(fn, mesh, q_spec=spec, out_spec=spec)
