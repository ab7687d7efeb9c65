"""Fused blockwise (flash) attention Pallas kernels for TPU.

This is the HBM-bandwidth fix for the 16k-context Perceiver AR north star
(SURVEY §5.7): the reference materializes the full (latents x sequence)
score matrix per layer (reference: perceiver/model/core/modules.py:151-163,
bounded only by the `max_heads_parallel` chunk loop); here scores never leave
VMEM. One mask form covers every attention in the framework:

``right-aligned causal``
    query *i* may attend kv slot *j* iff ``j <= i + offset`` with
    ``offset = kv_len - q_len``.  For square self-attention this is the
    standard causal mask; for Perceiver AR's cross-attention over
    ``[prefix; latents]`` it is exactly the reference's right-aligned mask
    (reference: modules.py:135-140) because every (possibly
    dropout-subsampled) prefix position precedes every latent query.
    ``causal=False`` disables the mask (Perceiver IO encoder/decoder).

Key padding is an additive f32 bias row per batch (0 or ``MASK_VALUE``),
streamed in kv blocks — O(B·Nkv) traffic, not O(Nq·Nkv). A packed call with
no pad mask and no padded keys has no such operand (``TilePlan.bias``).

Training support is a ``jax.custom_vjp`` using the standard flash
recomputation scheme: forward saves the row logsumexp; backward recomputes
probabilities blockwise from (q, k, lse). With several query blocks that is
two kernels (dKV accumulating over query blocks, dQ over kv blocks), each
rebuilding the scores; where the queries are one block (every call of the
Perceiver train steps) it is one kernel that rebuilds each score tile once
for dq, dk and dv (``_backward``). The packed forward likewise: the online
softmax over several kv blocks, a plain one where the keys are one block
(``_forward``).

All shapes are static; inputs are padded to block multiples by the wrapper
(padded kv slots are masked via the bias row, padded q rows are sliced off).
On CPU the kernels run in Pallas interpret mode (used by the test suite);
the numerics contract vs the einsum path is ``tests/test_flash_attention.py``.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import operator
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
LANES = 128

# One forward kernel per family (heads-major, packed) and the backward
# ``_backward`` picks by shape. Four VPU trims of these kernels (a base-2
# softmax, a skipped all-zero bias stream, a mask-free branch for fully
# visible causal tiles, narrower running-stat scratch) were measured on the
# v5e and lost, each alone and all together (+0.5% to +3.9% step time); they
# were deleted in PR 30 (table: docs/performance.md, "Round-3 ... REJECTED").
# Those readings were of the batch-4 step and the kernels of before PR 27 and
# PR 29. On today's kernels at batch 32 the bias row costs the packed calls 1
# to 2%, so a call without one has no such operand, chosen from the call and
# not by a flag (PR 48: the packed path's head comment).
#
# The one trace-time choice left is "paged": it routes the engine's paged
# decode attention through the page-walk kernel (ops/paged_attention.py)
# instead of the gather view. The chip has not made that choice yet: no
# serve cell exists, and the two have single readings on the v5e, not an A/B
# (ROADMAP R2 settles it). Read at TRACE time, like set_default_flash.
ALL_FEATURES = frozenset({"paged"})
# scoped per-context (contextvar, not a module global): a probe thread
# toggling features cannot leak them into another thread's traces
_FAST_FEATURES = contextvars.ContextVar("flash_fast_features", default=frozenset())


def _parse_features(mode) -> frozenset:
    if mode is True:
        return ALL_FEATURES
    if mode is False:
        return frozenset()
    unknown = frozenset(mode) - ALL_FEATURES
    if unknown:
        raise ValueError(f"unknown kernel features: {sorted(unknown)}")
    return frozenset(mode)


def fast_features() -> frozenset:
    """The active feature set (read at trace time where a feature routes)."""
    return _FAST_FEATURES.get()


def set_fast_kernels(mode) -> None:
    """Select trace-time kernel features (for A/B probes): True = all of
    ``ALL_FEATURES``, False = none (the default), or an iterable of feature
    names. Affects the CURRENT context only; prefer :func:`fast_kernels` for
    scoped use."""
    _FAST_FEATURES.set(_parse_features(mode))


@contextlib.contextmanager
def fast_kernels(mode):
    """Scoped feature selection: traces inside the with-block see ``mode``."""
    token = _FAST_FEATURES.set(_parse_features(mode))
    try:
        yield
    finally:
        _FAST_FEATURES.reset(token)


# GSPMD cannot partition a Mosaic kernel: inside a multi-device jit every
# pallas_call must sit in a shard_map ("Mosaic kernels cannot be
# automatically partitioned"). The attention kernels are independent per
# batch row, so under ``kernel_mesh`` the wrappers below run them on each
# device's batch shard. Trace-time and context-scoped like the toggles above.
_KERNEL_MESH = contextvars.ContextVar("flash_kernel_mesh", default=None)


@contextlib.contextmanager
def kernel_mesh(mesh, batch_axes):
    """Traces inside the block run the flash kernels per batch shard of
    ``mesh``: the batch dim split over ``batch_axes`` (mesh axis names),
    everything replicated over the other axes. ``mesh=None`` is a no-op."""
    token = _KERNEL_MESH.set(None if mesh is None else (mesh, tuple(batch_axes)))
    try:
        yield
    finally:
        _KERNEL_MESH.reset(token)


def _on_batch_shards(kernel, *operands):
    """``kernel(*operands)``, under :func:`kernel_mesh` as a shard_map over
    the batch (leading) dim of every operand."""
    scope = _KERNEL_MESH.get()
    if scope is None or scope[0].size == 1:
        return kernel(*operands)
    from jax.sharding import PartitionSpec as P

    mesh, batch_axes = scope
    axes = tuple(a for a in batch_axes if mesh.shape[a] > 1)
    spec = P(axes) if axes else P()
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=(spec,) * len(operands), out_specs=spec, check_vma=False
    )(*operands)


# Residual lane width for the packed kernels' lse/delta side-channels: only
# one lane per head carries information, but a few lanes keep the tiles
# loadable; 8 instead of 128 cuts ~250 MB/step of backward residual traffic
# at the 16k flagship (batch 4).
RES_LANES = 8

# Mosaic scoped-VMEM budget. The default 16MB rejects the block sizes that
# actually run fastest on v5e (measured: block_kv=2048 is ~3x faster than
# 512 at 16k context); 100MB keeps double-buffered 256x2048 f32 tiles legal.
_VMEM_LIMIT = 100 * 1024 * 1024


def _compiler_params(*dims: str):
    """Grid dimension semantics + raised VMEM ceiling (no-op in interpret)."""
    return pltpu.CompilerParams(dimension_semantics=dims, vmem_limit_bytes=_VMEM_LIMIT)


def _dot(a, b, dims):
    """MXU matmul accumulating in f32; f32 inputs use full-precision passes
    (Mosaic rejects fp32 contract precision on bf16 operands, where a single
    MXU pass is exact anyway)."""
    precision = lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=jnp.float32, precision=precision)


def _right_aligned_mask(bq: int, bkv: int, iq, ikv, block_q: int, block_kv: int, offset: int):
    """Boolean keep-mask for a (bq, bkv) score tile at block coords (iq, ikv)."""
    rows = lax.broadcasted_iota(jnp.int32, (bq, bkv), 0) + iq * block_q
    cols = lax.broadcasted_iota(jnp.int32, (bq, bkv), 1) + ikv * block_kv
    return cols <= rows + offset


def _block_visible(iq, ikv, block_q: int, block_kv: int, offset: int):
    """True iff any entry of score tile (iq, ikv) is unmasked."""
    return ikv * block_kv <= (iq + 1) * block_q - 1 + offset


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _keep_mask(n_rows: int, n_cols: int, shift):
    """Keep-mask of a score band in its own coordinates: column c is visible
    to row r iff ``c <= r + shift``."""
    rows = lax.broadcasted_iota(jnp.int32, (n_rows, n_cols), 0)
    cols = lax.broadcasted_iota(jnp.int32, (n_rows, n_cols), 1)
    return cols <= rows + shift


def _tile_body(band, iq, ikv, block_q: int, block_kv: int, offset: int):
    """A packed kernel's ``body(apply_mask, bands=None)`` from its
    ``band(r0, r1, width, keep)``: the whole tile under the right-aligned
    mask (or none), or the given bands of it (:func:`_row_bands`)."""

    def body(apply_mask: bool, bands=None):
        if bands is None:
            keep = _right_aligned_mask(block_q, block_kv, iq, ikv, block_q, block_kv, offset) if apply_mask else None
            band(0, block_q, block_kv, keep)
            return
        for r0, r1, width, shift in bands:
            band(r0, r1, width, None if shift is None else _keep_mask(r1 - r0, width, shift))

    return body


def _causal_dispatch(body, causal: bool, iq, ikv, block_q, block_kv, offset, diagonals=(), whole=True):
    """Run ``body(apply_mask)`` once per visible tile.

    ``diagonals`` (packed kernels, :func:`_diagonals`): for positions the
    mask's diagonal takes inside a tile, the bands of that tile that hold its
    visible scores. Such a tile runs ``body(True, bands)`` and computes
    nothing outside them; every other visible tile runs whole, as without
    (``whole`` False: the grid has no such tile, and none is emitted)."""
    when = pl.when
    if causal and diagonals:
        delta = iq * block_q + offset - ikv * block_kv
        for d, bands in diagonals:
            pl.when(delta == d)(functools.partial(body, True, bands))
        if not whole:
            return
        uncut = functools.reduce(jnp.logical_and, [delta != d for d, _ in diagonals])

        def when(cond):
            return pl.when(jnp.logical_and(cond, uncut))

    if causal:
        when(_block_visible(iq, ikv, block_q, block_kv, offset))(lambda: body(True))
    else:
        body(False)


def _fwd_kernel(
    *refs,  # bias, q, k, v, o, lse, m_scr, l_scr, acc_scr
    causal: bool,
    offset: int,
    sm_scale: float,
    num_kv_blocks: int,
):
    # refs: bias (1, 1, block_kv) f32; q (1, block_q, d_qk);
    # k (1, block_kv, d_qk); v (1, block_kv, d_v); outs o (1, block_q, d_v),
    # lse (1, block_q, LANES) f32; scratch m/l (block_q, LANES) f32,
    # acc (block_q, d_v) f32
    bias_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    iq, ikv = pl.program_id(1), pl.program_id(2)
    block_q, d_v = acc_scr.shape
    block_kv = k_ref.shape[1]

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _body(apply_mask: bool):
        q = q_ref[0]
        k = k_ref[0]
        s = _dot(q, k, ((1,), (1,)))  # (block_q, block_kv)
        s = s * sm_scale
        s = s + bias_ref[0]
        if apply_mask:
            keep = _right_aligned_mask(block_q, block_kv, iq, ikv, block_q, block_kv, offset)
            s = jnp.where(keep, s, MASK_VALUE)

        m_prev = m_scr[...]  # (block_q, LANES), lanes identical
        l_prev = l_scr[...]
        m_curr = jnp.max(s, axis=1)[:, None]  # (block_q, 1)
        m_next = jnp.maximum(m_prev, m_curr)  # (block_q, LANES)
        p = jnp.exp(s - m_next[:, :1])  # lane-broadcast subtract
        alpha = jnp.exp(m_prev - m_next)
        # flash-v2 style: keep the accumulator unnormalized; only rescale by
        # alpha when the running max moves. Normalization happens at store.
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
        m_scr[...] = m_next

        v = v_ref[0]
        o_curr = _dot(p.astype(v.dtype), v, ((1,), (0,)))
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + o_curr

    _causal_dispatch(_body, causal, iq, ikv, block_q, block_kv, offset)

    @pl.when(ikv == num_kv_blocks - 1)
    def _store():
        l = l_scr[...]
        l_inv = jnp.where(l == 0.0, 1.0, 1.0 / l)
        o_ref[0] = (acc_scr[...] * l_inv[:, :1]).astype(o_ref.dtype)
        # lse = m + log(l).
        # Rows with l == 0 only occur when every kv block was causally
        # invisible for the whole q block; the backward pass skips exactly
        # those blocks, so their lse is never read.
        lse_ref[0] = m_scr[...] + jnp.log(jnp.where(l == 0.0, 1.0, l))


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _scores(q, k, bias_row, keep, sm_scale):
    """The masked score tile ``q k^T * sm_scale + bias_row`` under a
    caller-built keep mask (None = no mask). ``bias_row`` None (a packed call
    without a pad mask or padded keys) emits no add: Mosaic makes the bias
    the product's accumulator, and an accumulator of zeros costs nothing
    where the bias row costs the forward 1% and the backward 2% (PERF.md 6,
    PR 48). The multiply is emitted whatever ``sm_scale`` is: Mosaic's
    canonicalizer folds a multiply by one (read in its dump of the
    1024 x 1024 forward)."""
    s = _dot(q, k, ((1,), (1,)))
    s = s * sm_scale
    if bias_row is not None:
        s = s + bias_row
    if keep is not None:
        s = jnp.where(keep, s, MASK_VALUE)
    return s


def _recompute_p_keep(q, k, bias_row, lse_col, keep, sm_scale):
    """Recompute the probability tile p = exp(s_masked - lse) from a
    caller-built keep mask (None = no mask)."""
    return jnp.exp(_scores(q, k, bias_row, keep, sm_scale) - lse_col)


def _recompute_p(q, k, bias_row, lse_col, iq, ikv, block_q, block_kv, offset, sm_scale, apply_mask):
    """`_recompute_p_keep` with the standard right-aligned causal keep mask."""
    keep = None
    if apply_mask:
        keep = _right_aligned_mask(q.shape[0], k.shape[0], iq, ikv, block_q, block_kv, offset)
    return _recompute_p_keep(q, k, bias_row, lse_col, keep, sm_scale)


def _dkv_kernel(
    *refs,  # bias, q, k, v, do, lse, delta, dk, dv, dk_scr, dv_scr
    causal: bool,
    offset: int,
    sm_scale: float,
    num_q_blocks: int,
):
    # refs: bias (1, 1, block_kv); q (1, block_q, d_qk);
    # k (1, block_kv, d_qk); v (1, block_kv, d_v); do (1, block_q, d_v);
    # lse/delta (1, block_q, LANES); outs dk (1, block_kv, d_qk),
    # dv (1, block_kv, d_v); scratch dk/dv f32
    bias_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr = refs
    ikv, iq = pl.program_id(1), pl.program_id(2)
    block_kv, _ = dk_scr.shape
    block_q = q_ref.shape[1]

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _body(apply_mask: bool):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]  # (block_q, 1)
        delta = delta_ref[0][:, :1]

        bias = bias_ref[0]
        p = _recompute_p(q, k, bias, lse, iq, ikv, block_q, block_kv, offset, sm_scale, apply_mask)
        # dv += p^T do
        dv_scr[...] += _dot(p.astype(do.dtype), do, ((0,), (0,)))
        # dp = do v^T ; ds = p * (dp - delta) * sm_scale (the base-2 factors
        # cancel: d/ds of 2^(s*c*log2e - lse2) is p*c, same as the exp form)
        dp = _dot(do, v, ((1,), (1,)))
        ds = p * (dp - delta) * sm_scale
        # dk += ds^T q
        dk_scr[...] += _dot(ds.astype(q.dtype), q, ((0,), (0,)))

    _causal_dispatch(_body, causal, iq, ikv, block_q, block_kv, offset)

    @pl.when(iq == num_q_blocks - 1)
    def _store():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(
    *refs,  # bias, q, k, v, do, lse, delta, dq, dq_scr
    causal: bool,
    offset: int,
    sm_scale: float,
    num_kv_blocks: int,
):
    # refs: bias (1, 1, block_kv); q (1, block_q, d_qk);
    # k (1, block_kv, d_qk); v (1, block_kv, d_v); do (1, block_q, d_v);
    # lse/delta (1, block_q, LANES); out dq (1, block_q, d_qk); scratch f32
    bias_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr = refs
    iq, ikv = pl.program_id(1), pl.program_id(2)
    block_q, _ = dq_scr.shape
    block_kv = k_ref.shape[1]

    @pl.when(ikv == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _body(apply_mask: bool):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]

        bias = bias_ref[0]
        p = _recompute_p(q, k, bias, lse, iq, ikv, block_q, block_kv, offset, sm_scale, apply_mask)
        dp = _dot(do, v, ((1,), (1,)))
        ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
        dq_scr[...] += _dot(ds, k, ((1,), (0,)))

    _causal_dispatch(_body, causal, iq, ikv, block_q, block_kv, offset)

    @pl.when(ikv == num_kv_blocks - 1)
    def _store():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_kernel(
    *refs,  # bias, q, k, v, do, lse, delta, dq, dk, dv, dq_scr
    causal: bool,
    offset: int,
    sm_scale: float,
    num_kv_blocks: int,
):
    # The call's queries are ONE block (grid (bh, kv blocks)): q, do, lse and
    # delta stay resident over a row's kv blocks, and each score tile is
    # rebuilt once for all three gradients. refs as in the two kernels above;
    # dk/dv of a kv block are whole after its one tile (one q block sees into
    # every kv block: padding is less than a block), so only dq needs a
    # scratch (f32, over the kv blocks).
    bias_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dq_scr = refs
    ikv = pl.program_id(1)
    block_q = q_ref.shape[1]
    block_kv = k_ref.shape[1]

    @pl.when(ikv == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _body(apply_mask: bool):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]

        bias = bias_ref[0]
        p = _recompute_p(q, k, bias, lse, 0, ikv, block_q, block_kv, offset, sm_scale, apply_mask)
        dv_ref[0] = _dot(p.astype(do.dtype), do, ((0,), (0,))).astype(dv_ref.dtype)
        dp = _dot(do, v, ((1,), (1,)))
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk_ref[0] = _dot(ds, q, ((0,), (0,))).astype(dk_ref.dtype)
        dq_scr[...] += _dot(ds, k, ((1,), (0,)))

    _causal_dispatch(_body, causal, 0, ikv, block_q, block_kv, offset)

    @pl.when(ikv == num_kv_blocks - 1)
    def _store():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# host-side wrappers
# ---------------------------------------------------------------------------


def _pad_to(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _geometry(n_q: int, n_kv: int) -> str:
    """The lengths an attention call was made with (before any padding to
    block multiples), as they appear in its kernels' names."""
    return f"q{n_q}_kv{n_kv}"


def _kernel_name(pass_: str, geom: str) -> str:
    """``flash_<pass>_q<n_q>_kv<n_kv>``: what a device trace prints for the
    Mosaic call (XLA names the custom call after the innermost name scope,
    and ``pallas_call(name=...)`` opens one), so a profile tells forward, dq
    and dkv apart and a 16k cross-attention from a latent self-attention.
    ``benchmarks/lib/flash_groups.py`` selects kernels by these names."""
    return f"flash_{pass_}_{geom}"


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, bias, causal, offset, sm_scale, block_q, block_kv, num_heads, geom):
    out, _ = _flash_fwd_impl(q, k, v, bias, causal, offset, sm_scale, block_q, block_kv, num_heads, geom)
    return out


def _flash_fwd_impl(q, k, v, bias, causal, offset, sm_scale, block_q, block_kv, num_heads, geom):
    bh, nq, d_qk = q.shape
    nkv = k.shape[1]
    d_v = v.shape[2]
    h = num_heads
    grid = (bh, nq // block_q, nkv // block_kv)

    in_specs = [
        pl.BlockSpec((1, 1, block_kv), lambda b, i, j: (b // h, 0, j)),
        pl.BlockSpec((1, block_q, d_qk), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_kv, d_qk), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_kv, d_v), lambda b, i, j: (b, j, 0)),
    ]

    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel,
            causal=causal,
            offset=offset,
            sm_scale=sm_scale,
            num_kv_blocks=grid[2],
        ),
        name=_kernel_name("fwd", geom),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d_v), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, nq, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, nq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, d_v), jnp.float32),
        ],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=_interpret_default(),
    )(bias, q, k, v)
    return out, lse


def _flash_fwd(q, k, v, bias, causal, offset, sm_scale, block_q, block_kv, num_heads, geom):
    out, lse = _flash_fwd_impl(q, k, v, bias, causal, offset, sm_scale, block_q, block_kv, num_heads, geom)
    # the kernel emits lse broadcast across all 128 lanes (tiled loads);
    # keep ONE lane as the residual — at 48 attention calls per step the
    # full-lane buffers alone were ~3GB at batch 32 (measured, image
    # classifier); the backward re-broadcasts transiently
    return out, (q, k, v, bias, out, lse[..., :1])


# Backward block sizes (None = same as forward). The bwd kernels have a
# different VMEM/compute profile than the forward (three matmuls + the
# recompute per tile); values must be power-of-two divisors of the forward
# blocks so they divide the padded array sizes.
BWD_BLOCK_Q: Optional[int] = None
BWD_BLOCK_KV: Optional[int] = None


def _bwd_blocks(block_q: int, block_kv: int) -> tuple:
    """The backward kernels' blocks for a call with these forward blocks."""
    if BWD_BLOCK_Q is not None:
        block_q = min(block_q, BWD_BLOCK_Q)
    if BWD_BLOCK_KV is not None:
        block_kv = min(block_kv, BWD_BLOCK_KV)
    return block_q, block_kv


def _backward(num_q_blocks: int) -> str:
    """Which backward a call runs, from its shapes alone. A flash backward
    is two kernels because dk/dv sum over query blocks and dq over kv blocks,
    and each rebuilds the score tiles for itself. Where the queries are one
    block (the Perceiver shape: few latents, a long input) both walk the same
    (row, kv block) pairs with the same q, do, lse and delta resident, so
    ``"one"`` kernel rebuilds each tile once for dq, dk and dv (PERF.md 6,
    PR 29); several query blocks keep the ``"split"`` pair."""
    return "one" if num_q_blocks == 1 else "split"


def _forward(num_kv_blocks: int) -> str:
    """Which forward a packed call runs, from its shapes alone. The online
    softmax carries a running maximum, sum and accumulator from kv block to
    kv block, and its cost goes by rows: statistics scratch loaded, rescaled
    and stored by every band of every head, zero-filled before and read back
    after. Where the keys are one block a row meets every key in one band and
    none of that computes anything (``exp(-inf - m) = 0`` times zeros), so the
    ``"plain"`` forward takes the softmax of the band and writes its rows of
    the output and the logsumexp straight from it (PERF.md 6, PR 48); several
    kv blocks keep the ``"online"`` one."""
    return "plain" if num_kv_blocks == 1 else "online"


def _flash_bwd(causal, offset, sm_scale, block_q, block_kv, num_heads, geom, residuals, g):
    block_q, block_kv = _bwd_blocks(block_q, block_kv)
    one = _backward(residuals[0].shape[1] // block_q) == "one"
    return (_flash_bwd_one if one else _flash_bwd_split)(
        causal, offset, sm_scale, block_q, block_kv, num_heads, geom, residuals, g
    )


def _bwd_operands(residuals, g):
    """The operands of the heads-major backward kernels: bias, q, k, v, do,
    lse, delta, the last two broadcast over lanes for tiled loads."""
    q, k, v, bias, out, lse_col = residuals
    lse = jnp.broadcast_to(lse_col, lse_col.shape[:2] + (LANES,))
    # delta_i = sum_c dO_ic * O_ic
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (LANES,))
    return [bias, q, k, v, g, lse, delta]


def _flash_bwd_one(causal, offset, sm_scale, block_q, block_kv, num_heads, geom, residuals, g):
    """The backward of a call whose queries are one block: one kernel."""
    q, k, v = residuals[:3]
    bh, nq, d_qk = q.shape
    nkv = k.shape[1]
    d_v = v.shape[2]
    h = num_heads
    assert nq == block_q, (nq, block_q)
    nkvb = nkv // block_kv
    inputs = _bwd_operands(residuals, g)

    row = lambda b, j: (b, 0, 0)  # the one q block of a batch row
    kv = lambda b, j: (b, j, 0)
    in_specs = [
        pl.BlockSpec((1, 1, block_kv), lambda b, j: (b // h, 0, j)),
        pl.BlockSpec((1, block_q, d_qk), row),
        pl.BlockSpec((1, block_kv, d_qk), kv),
        pl.BlockSpec((1, block_kv, d_v), kv),
        pl.BlockSpec((1, block_q, d_v), row),
        pl.BlockSpec((1, block_q, LANES), row),
        pl.BlockSpec((1, block_q, LANES), row),
    ]
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel,
            causal=causal,
            offset=offset,
            sm_scale=sm_scale,
            num_kv_blocks=nkvb,
        ),
        name=_kernel_name("bwd", geom),
        grid=(bh, nkvb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d_qk), row),
            pl.BlockSpec((1, block_kv, d_qk), kv),
            pl.BlockSpec((1, block_kv, d_v), kv),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, nq, d_qk), q.dtype),
            jax.ShapeDtypeStruct((bh, nkv, d_qk), k.dtype),
            jax.ShapeDtypeStruct((bh, nkv, d_v), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, d_qk), jnp.float32)],
        compiler_params=_compiler_params("parallel", "arbitrary"),
        interpret=_interpret_default(),
    )(*inputs)
    return dq, dk, dv, jnp.zeros_like(residuals[3])


def _flash_bwd_split(causal, offset, sm_scale, block_q, block_kv, num_heads, geom, residuals, g):
    """The backward of a call with several query blocks: dkv, then dq."""
    q, k, v, bias = residuals[:4]
    bh, nq, d_qk = q.shape
    nkv = k.shape[1]
    d_v = v.shape[2]
    h = num_heads
    nqb, nkvb = nq // block_q, nkv // block_kv
    inputs = _bwd_operands(residuals, g)

    dkv_in_specs = [
        pl.BlockSpec((1, 1, block_kv), lambda b, j, i: (b // h, 0, j)),
        pl.BlockSpec((1, block_q, d_qk), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_kv, d_qk), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_kv, d_v), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_q, d_v), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_q, LANES), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_q, LANES), lambda b, j, i: (b, i, 0)),
    ]

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel,
            causal=causal,
            offset=offset,
            sm_scale=sm_scale,
            num_q_blocks=nqb,
        ),
        name=_kernel_name("dkv", geom),
        grid=(bh, nkvb, nqb),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_kv, d_qk), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_kv, d_v), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, nkv, d_qk), k.dtype),
            jax.ShapeDtypeStruct((bh, nkv, d_v), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, d_qk), jnp.float32),
            pltpu.VMEM((block_kv, d_v), jnp.float32),
        ],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=_interpret_default(),
    )(*inputs)

    dq_in_specs = [
        pl.BlockSpec((1, 1, block_kv), lambda b, i, j: (b // h, 0, j)),
        pl.BlockSpec((1, block_q, d_qk), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_kv, d_qk), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_kv, d_v), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_q, d_v), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),
    ]

    (dq,) = pl.pallas_call(
        functools.partial(
            _dq_kernel,
            causal=causal,
            offset=offset,
            sm_scale=sm_scale,
            num_kv_blocks=nkvb,
        ),
        name=_kernel_name("dq", geom),
        grid=(bh, nqb, nkvb),
        in_specs=dq_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d_qk), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, nq, d_qk), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d_qk), jnp.float32)],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=_interpret_default(),
    )(*inputs)

    return dq, dk, dv, jnp.zeros_like(bias)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# tile plan (packed path)
# ---------------------------------------------------------------------------
#
# The grid can skip only whole tiles, and a square causal call whose block is
# its whole length has one tile: it scored all n_q x n_kv pairs for the half
# the mask keeps (PERF.md 6, PR 27). So a tile that the mask's diagonal
# crosses is cut into bands of rows, each scored only as far as its last
# visible kv slot; tiles below the diagonal run whole, as before. What is cut
# follows from the call's lengths alone: ``tile_plan`` says it, the wrapper
# uses it, and the ``compile`` event row records it.


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def _row_bands(delta: int, block_q: int, block_kv: int) -> tuple:
    """The visible part of a tile whose row r sees its kv slots c <= r + delta,
    as ``(r0, r1, width, shift)``: bands of ``_BAND_ROWS`` rows, each over the
    tile's kv slots up to the band's last visible one (rounded up to LANES).
    ``shift`` is the band's :func:`_keep_mask` argument, None where all of it
    is visible."""
    bands = []
    for r0 in range(0, block_q, _BAND_ROWS):
        r1 = min(r0 + _BAND_ROWS, block_q)
        width = min(block_kv, _round_up(r1 + delta, LANES))
        if width > 0:
            bands.append((r0, r1, width, r0 + delta if width - 1 > r0 + delta else None))
    return tuple(bands)


# Measured on the v5e (tools/tile_plan_ab.py; PERF.md 6, PR 27): bands of 256
# rows beat 128 and 512 in all three passes, bands of rows beat bands of kv
# slots (also in dkv), and smaller grid blocks with the hidden tiles skipped
# lost to the one-tile grid they replaced.
_BAND_ROWS = 256
# A tile is cut only if that leaves at most this share of it to score: bands
# are shorter matmuls and cost more per score than a whole tile (the 16k
# cross-attention's last kv block, 82% visible, ran 8% slower cut).
_BAND_MAX_SHARE = 0.75


def _band_area(bands) -> int:
    return sum((r1 - r0) * width for r0, r1, width, _ in bands)


def _tile_deltas(offset: int, block_q: int, block_kv: int, nqb: int, nkvb: int) -> list:
    """Where the mask's diagonal lies in each grid tile: ``delta`` such that
    the tile's row r sees its kv slots c <= r + delta."""
    return [iq * block_q + offset - ikv * block_kv for iq in range(nqb) for ikv in range(nkvb)]


def _diagonals(causal: bool, offset: int, block_q: int, block_kv: int, nqb: int, nkvb: int) -> dict:
    """``diagonals`` and ``whole`` of :func:`_causal_dispatch` (and of the
    packed kernels): the tiles on the diagonal that are cut into bands, as
    ``(delta, bands)`` with one static entry per position of the diagonal,
    and whether the grid has a visible tile that is not among them."""
    if not causal or offset < 0:
        return {"diagonals": (), "whole": True}
    visible = {d for d in _tile_deltas(offset, block_q, block_kv, nqb, nkvb) if d > -block_q}
    cut = []
    for d in sorted(d for d in visible if d < block_kv - 1):
        bands = _row_bands(d, block_q, block_kv)
        if _band_area(bands) <= _BAND_MAX_SHARE * block_q * block_kv:
            cut.append((d, bands))
    return {"diagonals": tuple(cut), "whole": len(cut) < len(visible)}


class TilePlan(NamedTuple):
    """How one packed attention call cuts its score matrix. The counts are in
    score tiles of LANES x LANES over the padded lengths."""

    block_q: int
    block_kv: int
    band_rows: int  # rows per band of a grid tile cut on the diagonal; 0 = every tile runs whole
    tiles_run: int
    tiles_masked: int  # of those run: in a band, or a whole grid tile, that the diagonal crosses
    tiles_skipped: int
    backward: str  # "one" kernel for dq, dk and dv, the "split" pair (``_backward``), or "none" (forward only)
    forward: str = "online"  # "plain" softmax where the keys are one block, else the "online" one (``_forward``)
    bias: bool = False  # the kernels take a bias row: the call has a pad mask, or its keys are padded to the block

    @property
    def run_share(self) -> float:
        return self.tiles_run / (self.tiles_run + self.tiles_skipped)


def _make_plan(n_q: int, n_kv: int, causal: bool, block_q: int, block_kv: int, pad_mask: bool = False) -> TilePlan:
    """The plan of a call at these grid blocks (what the kernels will do)."""
    nqb, nkvb = _round_up(n_q, block_q) // block_q, _round_up(n_kv, block_kv) // block_kv
    kernels = (_backward(nqb * block_q // _bwd_blocks(block_q, block_kv)[0]), _forward(nkvb), pad_mask or n_kv % block_kv != 0)
    unit = LANES * LANES
    total = nqb * nkvb * block_q * block_kv // unit
    if not causal:
        return TilePlan(block_q, block_kv, 0, total, 0, 0, *kernels)
    offset = n_kv - n_q
    cut = dict(_diagonals(causal, offset, block_q, block_kv, nqb, nkvb)["diagonals"])
    run = masked = 0
    for delta in _tile_deltas(offset, block_q, block_kv, nqb, nkvb):
        if delta <= -block_q:
            continue
        bands = cut.get(delta, ((0, block_q, block_kv, 0 if delta < block_kv - 1 else None),))
        run += _band_area(bands) // unit
        masked += _band_area([band for band in bands if band[3] is not None]) // unit
    return TilePlan(block_q, block_kv, _BAND_ROWS if cut else 0, run, masked, total - run, *kernels)


def tile_plan(
    n_q: int, n_kv: int, causal: bool, block_q: Optional[int] = None, block_kv: Optional[int] = None,
    window: Optional[int] = None, pad_mask: bool = False,
) -> TilePlan:
    """The tile plan of ``flash_attention_packed`` for a call of these
    lengths: a pure function of its arguments. ``block_q``/``block_kv`` are
    the wrapper's (None = the tuned hint, a value = an upper bound);
    ``pad_mask`` says whether the call has one (its ``bias``).

    With a ``window`` (query i sees ``i - window < j <= i``) it is the plan
    of :func:`flash_attention_gqa`, whose grid walks only the kv blocks a q
    block can see: square blocks, self-attention, forward only."""
    if window is not None:
        if not causal or n_q != n_kv:
            raise ValueError("a window needs causal self-attention (n_q == n_kv)")
        block = _choose_block(n_q, 1024 if block_q is None else block_q, exact=block_q is not None)
        return _make_window_plan(n_q, block, window)
    # The grid blocks are the ones of before PR 27 for every call. Unpadded
    # blocks for the generator's 768 x 768 prompt pass (768 -> 2 x 512 pads a
    # quarter) halved those kernels and cost the decode scan 7%: without the
    # K/V padded to the cache's length XLA laid the caches out differently
    # (PERF.md 6, PR 27). A change of blocks is a change of the program around
    # the kernel; the bands are not.
    bq = _choose_block(n_q, 1024 if block_q is None else block_q, exact=block_q is not None)
    bkv = _choose_block(n_kv, 2048 if block_kv is None else block_kv, exact=block_kv is not None)
    return _make_plan(n_q, n_kv, causal, bq, bkv, pad_mask)


# plans of the calls traced in this process, by (geometry, causal, bias): a
# trace-time fact like the feature set, read by obs.recompile for the
# ``compile`` event row (docs/observability.md)
_TILE_PLANS: dict = {}


def tile_plans() -> list:
    """One row per distinct packed attention call traced so far: its
    geometry (as in the kernel names), blocks and tile counts."""
    return [
        {"geometry": geom, "causal": causal, "window": _window_of(geom), **plan._asdict(),
         "run_share": round(plan.run_share, 4)}
        for (geom, causal, _), plan in sorted(_TILE_PLANS.items())
    ]


def _window_of(geom: str) -> Optional[int]:
    """The window a call's geometry names (``q<n_q>_kv<n_kv>_w<window>``), None where it names none."""
    return int(geom.rsplit("_w", 1)[1]) if "_w" in geom else None


# ---------------------------------------------------------------------------
# packed (slots-major) path
# ---------------------------------------------------------------------------
#
# The heads-major kernels above receive (B*H, N, D) operands, which forces a
# materialized (B, N, H, D) -> (B, H, N, D) transpose of every input and
# output around each kernel (profiled ~3 ms/step of layout copies at the 16k
# flagship, batch 4). The packed kernels instead take tensors in their
# NATURAL projection layout (B, N, H*D) — block rows are contiguous, so the
# DMA needs no transpose at all — and iterate heads inside the kernel over
# cheap VMEM minor-dim slices. Head dims must be multiples of 8 (no per-head
# zero padding is possible in a packed minor dim); other shapes use the
# heads-major path.
#
# A kernel's band body is specialised at trace time by what its call
# statically is, from shapes and operands alone (no flag; ``TilePlan`` and the
# ``compile`` event row say what was chosen): the backward is one kernel where
# the queries are one block (``_backward``); the forward is a plain softmax
# with no statistics scratch where the keys are one block (``_forward``); and
# the bias row is an operand of the forward and backward kernels only where
# the call has a pad mask or padded keys (``has_bias``): Mosaic makes
# ``s + bias`` the score product's accumulator, and on the v5e an accumulator
# that holds a bias row costs a vector add a score where one of zeros costs
# none (the 1024 x 8704 call: forward 9.63 -> 9.53 ms, backward 17.50 ->
# 17.12; PERF.md 6, PR 48). A multiply by ``sm_scale`` = 1 (the call sites
# scale the queries) needs no such care: Mosaic's canonicalizer folds it.


def _split_bias(refs, has_bias: bool):
    """``(bias_ref, the other refs)`` of a packed kernel: the bias row comes first, where the call has one."""
    return (refs[0], refs[1:]) if has_bias else (None, refs)


def _bias_row(bias_ref, width: int):
    """The first ``width`` slots of a kv block's bias row, None for a call without one."""
    return None if bias_ref is None else bias_ref[0, :, :width]


def _fwd_packed_kernel(
    *refs,  # [bias], q, k, v, o, lse, m_scr, l_scr, acc_scr
    causal: bool,
    offset: int,
    sm_scale: float,
    num_kv_blocks: int,
    num_heads: int,
    d_qk: int,
    d_v: int,
    has_bias: bool,
    diagonals: tuple = (),
    whole: bool = True,
):
    # The online forward (``_forward``: several kv blocks). refs: bias
    # (1, 1, block_kv) f32 where the call has one; q (1, block_q, h*d_qk);
    # k (1, block_kv, h*d_qk); v (1, block_kv, h*d_v); outs
    # o (1, block_q, h*d_v), lse (1, block_q, h*RES_LANES) f32; scratch
    # m/l (h, block_q, LANES) f32, acc (h, block_q, d_v)
    bias_ref, refs = _split_bias(refs, has_bias)
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    iq, ikv = pl.program_id(1), pl.program_id(2)
    h = num_heads
    block_q = q_ref.shape[1]
    block_kv = k_ref.shape[1]

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _band(r0, r1, width, keep):
        # rows [r0, r1) of the q block against the kv block's first ``width``
        # slots. Per-head minor-dim slices: Mosaic supports static lane
        # slices but not the (block, h*d) -> (block, h, d) vector reshape
        bias = _bias_row(bias_ref, width)
        for hh in range(h):
            qh = q_ref[0, r0:r1, hh * d_qk : (hh + 1) * d_qk]
            kh = k_ref[0, :width, hh * d_qk : (hh + 1) * d_qk]
            vh = v_ref[0, :width, hh * d_v : (hh + 1) * d_v]
            s = _scores(qh, kh, bias, keep, sm_scale)
            m_prev = m_scr[hh, r0:r1]
            l_prev = l_scr[hh, r0:r1]
            m_curr = jnp.max(s, axis=1)[:, None]
            m_next = jnp.maximum(m_prev, m_curr)
            p = jnp.exp(s - m_next[:, :1])
            alpha = jnp.exp(m_prev - m_next)
            l_scr[hh, r0:r1] = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
            m_scr[hh, r0:r1] = m_next
            o_curr = _dot(p.astype(vh.dtype), vh, ((1,), (0,)))
            acc_scr[hh, r0:r1] = acc_scr[hh, r0:r1] * alpha[:, :1] + o_curr

    _body = _tile_body(_band, iq, ikv, block_q, block_kv, offset)
    _causal_dispatch(_body, causal, iq, ikv, block_q, block_kv, offset, diagonals, whole)

    @pl.when(ikv == num_kv_blocks - 1)
    def _store():
        for hh in range(h):
            l = l_scr[hh]
            l_inv = jnp.where(l == 0.0, 1.0, 1.0 / l)
            o_ref[0, :, hh * d_v : (hh + 1) * d_v] = (
                acc_scr[hh] * l_inv[:, :1]
            ).astype(o_ref.dtype)
            lse = m_scr[hh] + jnp.log(jnp.where(l == 0.0, 1.0, l))
            lse_ref[0, :, hh * RES_LANES : (hh + 1) * RES_LANES] = lse[:, :RES_LANES]


def _fwd_plain_packed_kernel(
    *refs,  # [bias], q, k, v, o, lse
    causal: bool,
    offset: int,
    sm_scale: float,
    num_heads: int,
    d_qk: int,
    d_v: int,
    has_bias: bool,
    diagonals: tuple = (),
    whole: bool = True,
):
    # The plain forward (``_forward``: the call's keys are ONE block, grid
    # (b, q blocks, 1)). refs as the online kernel's, without scratch: a row
    # meets each of its keys in one band, so the band's own maximum and sum
    # are the row's, and its rows of o and lse are written from it. The
    # expressions are the online kernel's ``_store``, in its order, so both
    # give the same o and lse (a running maximum of -inf and sums of zero drop
    # out exactly; the sign of an exact zero of o is all that may differ).
    # ``_store`` guards ``l == 0``, the sum of a q block no tile touched; a
    # band's own sum holds exp(0) = 1 for the row's maximum, so it is at
    # least 1 and the two selects would pick ``1 / l`` and ``l`` every time.
    # The statistics are one column wide: Mosaic keeps a (rows, 1) value one
    # row a sublane with the lanes replicated, so it costs the vregs a
    # (rows, LANES) one does (a compare or select of it is 32 operations a
    # band a head), and what is saved is the scratch traffic, the rescale and
    # the second exponential, not lanes.
    bias_ref, refs = _split_bias(refs, has_bias)
    q_ref, k_ref, v_ref, o_ref, lse_ref = refs
    iq = pl.program_id(1)
    h = num_heads
    block_q = q_ref.shape[1]
    block_kv = k_ref.shape[1]

    def _band(r0, r1, width, keep):
        # rows [r0, r1) of the q block against the kv block's first ``width`` slots
        bias = _bias_row(bias_ref, width)
        for hh in range(h):
            qh = q_ref[0, r0:r1, hh * d_qk : (hh + 1) * d_qk]
            kh = k_ref[0, :width, hh * d_qk : (hh + 1) * d_qk]
            vh = v_ref[0, :width, hh * d_v : (hh + 1) * d_v]
            s = _scores(qh, kh, bias, keep, sm_scale)
            m = jnp.max(s, axis=1)[:, None]
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=1)[:, None]
            o = _dot(p.astype(vh.dtype), vh, ((1,), (0,)))
            o_ref[0, r0:r1, hh * d_v : (hh + 1) * d_v] = (o * (1.0 / l)).astype(o_ref.dtype)
            lse = m + jnp.log(l)
            lse_ref[0, r0:r1, hh * RES_LANES : (hh + 1) * RES_LANES] = jnp.broadcast_to(lse, (r1 - r0, RES_LANES))

    _body = _tile_body(_band, iq, 0, block_q, block_kv, offset)
    _causal_dispatch(_body, causal, iq, 0, block_q, block_kv, offset, diagonals, whole)

    if causal and block_q - 1 + offset < 0:
        # more queries than keys: a q block that sees no key at all is what the
        # online kernel's untouched scratch stores, zeros and a logsumexp of -inf
        @pl.when(jnp.logical_not(_block_visible(iq, 0, block_q, block_kv, offset)))
        def _hidden():
            o_ref[...] = jnp.zeros_like(o_ref)
            lse_ref[...] = jnp.full_like(lse_ref, -jnp.inf)


def _dkv_packed_kernel(
    *refs,  # [bias], q, k, v, do, lse, delta, dk, dv, dk_scr, dv_scr
    causal: bool,
    offset: int,
    sm_scale: float,
    num_q_blocks: int,
    num_heads: int,
    d_qk: int,
    d_v: int,
    has_bias: bool,
    diagonals: tuple = (),
    whole: bool = True,
):
    # refs: bias (1, 1, block_kv) where the call has one; q (1, block_q, h*d_qk);
    # k (1, block_kv, h*d_qk); v (1, block_kv, h*d_v); do (1, block_q, h*d_v);
    # lse/delta (1, block_q, h*RES_LANES); outs dk (1, block_kv, h*d_qk),
    # dv (1, block_kv, h*d_v); scratch dk/dv (h, block, d) f32
    bias_ref, refs = _split_bias(refs, has_bias)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr = refs
    ikv, iq = pl.program_id(1), pl.program_id(2)
    h = num_heads
    block_kv = k_ref.shape[1]
    block_q = q_ref.shape[1]

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _band(r0, r1, width, keep):
        # rows [r0, r1) of the q block against the kv block's first ``width`` slots
        bias = _bias_row(bias_ref, width)
        for hh in range(h):
            qh = q_ref[0, r0:r1, hh * d_qk : (hh + 1) * d_qk]
            kh = k_ref[0, :width, hh * d_qk : (hh + 1) * d_qk]
            vh = v_ref[0, :width, hh * d_v : (hh + 1) * d_v]
            doh = do_ref[0, r0:r1, hh * d_v : (hh + 1) * d_v]
            lse = lse_ref[0, r0:r1, hh * RES_LANES : hh * RES_LANES + 1]
            delta = delta_ref[0, r0:r1, hh * RES_LANES : hh * RES_LANES + 1]
            p = _recompute_p_keep(qh, kh, bias, lse, keep, sm_scale)
            dv_scr[hh, :width] += _dot(p.astype(doh.dtype), doh, ((0,), (0,)))
            dp = _dot(doh, vh, ((1,), (1,)))
            ds = p * (dp - delta) * sm_scale
            dk_scr[hh, :width] += _dot(ds.astype(qh.dtype), qh, ((0,), (0,)))

    _body = _tile_body(_band, iq, ikv, block_q, block_kv, offset)
    _causal_dispatch(_body, causal, iq, ikv, block_q, block_kv, offset, diagonals, whole)

    @pl.when(iq == num_q_blocks - 1)
    def _store():
        for hh in range(h):
            dk_ref[0, :, hh * d_qk : (hh + 1) * d_qk] = dk_scr[hh].astype(dk_ref.dtype)
            dv_ref[0, :, hh * d_v : (hh + 1) * d_v] = dv_scr[hh].astype(dv_ref.dtype)


def _dq_packed_kernel(
    *refs,  # [bias], q, k, v, do, lse, delta, dq, dq_scr
    causal: bool,
    offset: int,
    sm_scale: float,
    num_kv_blocks: int,
    num_heads: int,
    d_qk: int,
    d_v: int,
    has_bias: bool,
    diagonals: tuple = (),
    whole: bool = True,
):
    # refs: bias (1, 1, block_kv) where the call has one; q (1, block_q, h*d_qk);
    # k (1, block_kv, h*d_qk); v (1, block_kv, h*d_v); do (1, block_q, h*d_v);
    # lse/delta (1, block_q, h*RES_LANES); out dq (1, block_q, h*d_qk);
    # scratch dq (h, block_q, d_qk) f32
    bias_ref, refs = _split_bias(refs, has_bias)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr = refs
    iq, ikv = pl.program_id(1), pl.program_id(2)
    h = num_heads
    block_q = q_ref.shape[1]
    block_kv = k_ref.shape[1]

    @pl.when(ikv == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _band(r0, r1, width, keep):
        # rows [r0, r1) of the q block against the kv block's first ``width`` slots
        bias = _bias_row(bias_ref, width)
        for hh in range(h):
            qh = q_ref[0, r0:r1, hh * d_qk : (hh + 1) * d_qk]
            kh = k_ref[0, :width, hh * d_qk : (hh + 1) * d_qk]
            vh = v_ref[0, :width, hh * d_v : (hh + 1) * d_v]
            doh = do_ref[0, r0:r1, hh * d_v : (hh + 1) * d_v]
            lse = lse_ref[0, r0:r1, hh * RES_LANES : hh * RES_LANES + 1]
            delta = delta_ref[0, r0:r1, hh * RES_LANES : hh * RES_LANES + 1]
            p = _recompute_p_keep(qh, kh, bias, lse, keep, sm_scale)
            dp = _dot(doh, vh, ((1,), (1,)))
            ds = (p * (dp - delta) * sm_scale).astype(kh.dtype)
            dq_scr[hh, r0:r1] += _dot(ds, kh, ((1,), (0,)))

    _body = _tile_body(_band, iq, ikv, block_q, block_kv, offset)
    _causal_dispatch(_body, causal, iq, ikv, block_q, block_kv, offset, diagonals, whole)

    @pl.when(ikv == num_kv_blocks - 1)
    def _store():
        for hh in range(h):
            dq_ref[0, :, hh * d_qk : (hh + 1) * d_qk] = dq_scr[hh].astype(dq_ref.dtype)


def _bwd_packed_kernel(
    *refs,  # [bias], q, k, v, do, lse, delta, dq, dk, dv, dq_scr, [dk_scr, dv_scr]
    causal: bool,
    offset: int,
    sm_scale: float,
    num_kv_blocks: int,
    num_heads: int,
    d_qk: int,
    d_v: int,
    has_bias: bool,
    diagonals: tuple = (),
    whole: bool = True,
):
    # The call's queries are ONE block (grid (b, kv blocks)): q, do, lse and
    # delta stay resident over a row's kv blocks, and each score band is
    # rebuilt once for all three gradients. refs and the sums, in their
    # order, are those of the two kernels above: dq adds up over the kv
    # blocks in dq_scr (h, block_q, d_qk) f32; dk/dv of a kv block add up over
    # the bands of a cut tile in dk_scr/dv_scr (h, block_kv, d) f32, and where
    # the call cuts no tile there is no such scratch and they are written
    # straight from the tile's one band (the 1024 x 8704 call ran 6.8% faster
    # without it: PERF.md 6, PR 29).
    bias_ref, refs = _split_bias(refs, has_bias)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dq_scr, *kv_scr = refs
    ikv = pl.program_id(1)
    h = num_heads
    block_q = q_ref.shape[1]
    block_kv = k_ref.shape[1]

    @pl.when(ikv == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    if kv_scr:
        dk_scr, dv_scr = kv_scr
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _band(r0, r1, width, keep):
        # rows [r0, r1) of the q block against the kv block's first ``width`` slots
        bias = _bias_row(bias_ref, width)
        for hh in range(h):
            qh = q_ref[0, r0:r1, hh * d_qk : (hh + 1) * d_qk]
            kh = k_ref[0, :width, hh * d_qk : (hh + 1) * d_qk]
            vh = v_ref[0, :width, hh * d_v : (hh + 1) * d_v]
            doh = do_ref[0, r0:r1, hh * d_v : (hh + 1) * d_v]
            lse = lse_ref[0, r0:r1, hh * RES_LANES : hh * RES_LANES + 1]
            delta = delta_ref[0, r0:r1, hh * RES_LANES : hh * RES_LANES + 1]
            p = _recompute_p_keep(qh, kh, bias, lse, keep, sm_scale)
            dv = _dot(p.astype(doh.dtype), doh, ((0,), (0,)))
            dp = _dot(doh, vh, ((1,), (1,)))
            ds = (p * (dp - delta) * sm_scale).astype(qh.dtype)
            dk = _dot(ds, qh, ((0,), (0,)))
            dq_scr[hh, r0:r1] += _dot(ds, kh, ((1,), (0,)))
            if kv_scr:
                dv_scr[hh, :width] += dv
                dk_scr[hh, :width] += dk
            else:
                dv_ref[0, :, hh * d_v : (hh + 1) * d_v] = dv.astype(dv_ref.dtype)
                dk_ref[0, :, hh * d_qk : (hh + 1) * d_qk] = dk.astype(dk_ref.dtype)

    _body = _tile_body(_band, 0, ikv, block_q, block_kv, offset)
    _causal_dispatch(_body, causal, 0, ikv, block_q, block_kv, offset, diagonals, whole)

    if kv_scr:
        for hh in range(h):
            dk_ref[0, :, hh * d_qk : (hh + 1) * d_qk] = dk_scr[hh].astype(dk_ref.dtype)
            dv_ref[0, :, hh * d_v : (hh + 1) * d_v] = dv_scr[hh].astype(dv_ref.dtype)

    @pl.when(ikv == num_kv_blocks - 1)
    def _store():
        for hh in range(h):
            dq_ref[0, :, hh * d_qk : (hh + 1) * d_qk] = dq_scr[hh].astype(dq_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12))
def _flash_packed(q, k, v, bias, causal, offset, sm_scale, block_q, block_kv, h, d_qk, d_v, geom):
    out, _ = _flash_packed_fwd_impl(
        q, k, v, bias, causal, offset, sm_scale, block_q, block_kv, h, d_qk, d_v, geom
    )
    return out


def _flash_packed_fwd_impl(q, k, v, bias, causal, offset, sm_scale, block_q, block_kv, h, d_qk, d_v, geom):
    plain = _forward(k.shape[1] // block_kv) == "plain"
    return (_flash_packed_fwd_plain if plain else _flash_packed_fwd_online)(
        q, k, v, bias, causal, offset, sm_scale, block_q, block_kv, h, d_qk, d_v, geom
    )


def _bias_spec(bias, block_kv: int, index_map) -> list:
    """The ``in_specs`` entry of a packed kernel's bias row: none for a call without one."""
    return [] if bias is None else [pl.BlockSpec((1, 1, block_kv), index_map)]


def _with_bias(bias, operands: list) -> list:
    """A packed kernel's operands: the bias row first, where the call has one."""
    return operands if bias is None else [bias] + operands


def _packed_fwd_call(kernel, scratch, q, k, v, bias, causal, offset, sm_scale, block_q, block_kv, h, d_qk, d_v, geom):
    """Either packed forward: ``kernel`` over the grid (b, q blocks, kv blocks) with ``scratch``."""
    b, nq, _ = q.shape
    nkv = k.shape[1]
    grid = (b, nq // block_q, nkv // block_kv)

    in_specs = _bias_spec(bias, block_kv, lambda b_, i, j: (b_, 0, j)) + [
        pl.BlockSpec((1, block_q, h * d_qk), lambda b_, i, j: (b_, i, 0)),
        pl.BlockSpec((1, block_kv, h * d_qk), lambda b_, i, j: (b_, j, 0)),
        pl.BlockSpec((1, block_kv, h * d_v), lambda b_, i, j: (b_, j, 0)),
    ]

    out, lse = pl.pallas_call(
        functools.partial(
            kernel,
            causal=causal,
            offset=offset,
            sm_scale=sm_scale,
            num_heads=h,
            d_qk=d_qk,
            d_v=d_v,
            has_bias=bias is not None,
            **_diagonals(causal, offset, block_q, block_kv, grid[1], grid[2]),
        ),
        name=_kernel_name("fwd", geom),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, h * d_v), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, block_q, h * RES_LANES), lambda b_, i, j: (b_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nq, h * d_v), q.dtype),
            jax.ShapeDtypeStruct((b, nq, h * RES_LANES), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=_interpret_default(),
    )(*_with_bias(bias, [q, k, v]))
    return out, lse


def _flash_packed_fwd_online(q, k, v, bias, causal, offset, sm_scale, block_q, block_kv, h, d_qk, d_v, geom):
    """The forward of a call with several kv blocks: the online softmax over statistics scratch."""
    scratch = [
        pltpu.VMEM((h, block_q, LANES), jnp.float32),
        pltpu.VMEM((h, block_q, LANES), jnp.float32),
        pltpu.VMEM((h, block_q, d_v), jnp.float32),
    ]
    kernel = functools.partial(_fwd_packed_kernel, num_kv_blocks=k.shape[1] // block_kv)
    return _packed_fwd_call(kernel, scratch, q, k, v, bias, causal, offset, sm_scale, block_q, block_kv, h, d_qk, d_v, geom)


def _flash_packed_fwd_plain(q, k, v, bias, causal, offset, sm_scale, block_q, block_kv, h, d_qk, d_v, geom):
    """The forward of a call whose keys are one block: a plain softmax, no scratch."""
    assert k.shape[1] == block_kv, (k.shape, block_kv)
    return _packed_fwd_call(
        _fwd_plain_packed_kernel, [], q, k, v, bias, causal, offset, sm_scale, block_q, block_kv, h, d_qk, d_v, geom
    )


def _slim_lse(lse, h: int):
    """The residual form of a packed forward's lse: one lane per head (see the heads-major path note)."""
    return lse.reshape(lse.shape[0], lse.shape[1], h, RES_LANES)[..., :1]


def _flash_packed_fwd(q, k, v, bias, causal, offset, sm_scale, block_q, block_kv, h, d_qk, d_v, geom):
    out, lse = _flash_packed_fwd_impl(
        q, k, v, bias, causal, offset, sm_scale, block_q, block_kv, h, d_qk, d_v, geom
    )
    return out, (q, k, v, bias, out, _slim_lse(lse, h))


def _flash_packed_bwd(causal, offset, sm_scale, block_q, block_kv, h, d_qk, d_v, geom, residuals, g):
    block_q, block_kv = _bwd_blocks(block_q, block_kv)
    one = _backward(residuals[0].shape[1] // block_q) == "one"
    return (_flash_packed_bwd_one if one else _flash_packed_bwd_split)(
        causal, offset, sm_scale, block_q, block_kv, h, d_qk, d_v, geom, residuals, g
    )


def _packed_bwd_operands(residuals, g, h, d_v):
    """The operands of the packed backward kernels: bias (where the call has
    one), q, k, v, do, lse, delta, the last two as RES_LANES lanes per head."""
    q, k, v, bias, out, lse_slim = residuals
    b, nq, _ = q.shape
    lse = jnp.broadcast_to(lse_slim, (b, nq, h, RES_LANES)).reshape(b, nq, h * RES_LANES)
    # delta_i = sum_c dO_ic O_ic per head; minor-dim reshapes are bitcasts
    g4 = g.astype(jnp.float32).reshape(b, nq, h, d_v)
    out4 = out.astype(jnp.float32).reshape(b, nq, h, d_v)
    delta = jnp.sum(g4 * out4, axis=-1)  # (b, nq, h)
    delta = jnp.broadcast_to(delta[..., None], (b, nq, h, RES_LANES)).reshape(b, nq, h * RES_LANES)
    return _with_bias(bias, [q, k, v, g, lse, delta])


def _flash_packed_bwd_one(causal, offset, sm_scale, block_q, block_kv, h, d_qk, d_v, geom, residuals, g):
    """The backward of a call whose queries are one block: one kernel."""
    q, k, v, bias = residuals[:4]
    b, nq, _ = q.shape
    nkv = k.shape[1]
    assert nq == block_q, (nq, block_q)
    nkvb = nkv // block_kv
    inputs = _packed_bwd_operands(residuals, g, h, d_v)

    cut = _diagonals(causal, offset, block_q, block_kv, 1, nkvb)
    # dk/dv of a kv block add up in f32 scratch over the bands of a cut tile;
    # a call without one writes them straight from its one band a kv block
    # (one q block sees into every kv block: padding is less than a block)
    scratch = [pltpu.VMEM((h, block_q, d_qk), jnp.float32)]
    if cut["diagonals"]:
        scratch += [pltpu.VMEM((h, block_kv, d_qk), jnp.float32), pltpu.VMEM((h, block_kv, d_v), jnp.float32)]
    row = lambda b_, j: (b_, 0, 0)  # the one q block of a batch row
    kv = lambda b_, j: (b_, j, 0)
    in_specs = _bias_spec(bias, block_kv, lambda b_, j: (b_, 0, j)) + [
        pl.BlockSpec((1, block_q, h * d_qk), row),
        pl.BlockSpec((1, block_kv, h * d_qk), kv),
        pl.BlockSpec((1, block_kv, h * d_v), kv),
        pl.BlockSpec((1, block_q, h * d_v), row),
        pl.BlockSpec((1, block_q, h * RES_LANES), row),
        pl.BlockSpec((1, block_q, h * RES_LANES), row),
    ]
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_packed_kernel,
            causal=causal,
            offset=offset,
            sm_scale=sm_scale,
            num_kv_blocks=nkvb,
            num_heads=h,
            d_qk=d_qk,
            d_v=d_v,
            has_bias=bias is not None,
            **cut,
        ),
        name=_kernel_name("bwd", geom),
        grid=(b, nkvb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, h * d_qk), row),
            pl.BlockSpec((1, block_kv, h * d_qk), kv),
            pl.BlockSpec((1, block_kv, h * d_v), kv),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nq, h * d_qk), q.dtype),
            jax.ShapeDtypeStruct((b, nkv, h * d_qk), k.dtype),
            jax.ShapeDtypeStruct((b, nkv, h * d_v), v.dtype),
        ],
        scratch_shapes=scratch,
        compiler_params=_compiler_params("parallel", "arbitrary"),
        interpret=_interpret_default(),
    )(*inputs)
    return dq, dk, dv, None if bias is None else jnp.zeros_like(bias)


def _flash_packed_bwd_split(causal, offset, sm_scale, block_q, block_kv, h, d_qk, d_v, geom, residuals, g):
    """The backward of a call with several query blocks: dkv, then dq."""
    q, k, v, bias = residuals[:4]
    b, nq, _ = q.shape
    nkv = k.shape[1]
    nqb, nkvb = nq // block_q, nkv // block_kv
    inputs = _packed_bwd_operands(residuals, g, h, d_v)

    dkv_in_specs = _bias_spec(bias, block_kv, lambda b_, j, i: (b_, 0, j)) + [
        pl.BlockSpec((1, block_q, h * d_qk), lambda b_, j, i: (b_, i, 0)),
        pl.BlockSpec((1, block_kv, h * d_qk), lambda b_, j, i: (b_, j, 0)),
        pl.BlockSpec((1, block_kv, h * d_v), lambda b_, j, i: (b_, j, 0)),
        pl.BlockSpec((1, block_q, h * d_v), lambda b_, j, i: (b_, i, 0)),
        pl.BlockSpec((1, block_q, h * RES_LANES), lambda b_, j, i: (b_, i, 0)),
        pl.BlockSpec((1, block_q, h * RES_LANES), lambda b_, j, i: (b_, i, 0)),
    ]
    dq_in_specs = _bias_spec(bias, block_kv, lambda b_, i, j: (b_, 0, j)) + [
        pl.BlockSpec((1, block_q, h * d_qk), lambda b_, i, j: (b_, i, 0)),
        pl.BlockSpec((1, block_kv, h * d_qk), lambda b_, i, j: (b_, j, 0)),
        pl.BlockSpec((1, block_kv, h * d_v), lambda b_, i, j: (b_, j, 0)),
        pl.BlockSpec((1, block_q, h * d_v), lambda b_, i, j: (b_, i, 0)),
        pl.BlockSpec((1, block_q, h * RES_LANES), lambda b_, i, j: (b_, i, 0)),
        pl.BlockSpec((1, block_q, h * RES_LANES), lambda b_, i, j: (b_, i, 0)),
    ]

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_packed_kernel,
            causal=causal,
            offset=offset,
            sm_scale=sm_scale,
            num_q_blocks=nqb,
            num_heads=h,
            d_qk=d_qk,
            d_v=d_v,
            has_bias=bias is not None,
            **_diagonals(causal, offset, block_q, block_kv, nqb, nkvb),
        ),
        name=_kernel_name("dkv", geom),
        grid=(b, nkvb, nqb),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_kv, h * d_qk), lambda b_, j, i: (b_, j, 0)),
            pl.BlockSpec((1, block_kv, h * d_v), lambda b_, j, i: (b_, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nkv, h * d_qk), k.dtype),
            jax.ShapeDtypeStruct((b, nkv, h * d_v), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((h, block_kv, d_qk), jnp.float32),
            pltpu.VMEM((h, block_kv, d_v), jnp.float32),
        ],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=_interpret_default(),
    )(*inputs)

    (dq,) = pl.pallas_call(
        functools.partial(
            _dq_packed_kernel,
            causal=causal,
            offset=offset,
            sm_scale=sm_scale,
            num_kv_blocks=nkvb,
            num_heads=h,
            d_qk=d_qk,
            d_v=d_v,
            has_bias=bias is not None,
            **_diagonals(causal, offset, block_q, block_kv, nqb, nkvb),
        ),
        name=_kernel_name("dq", geom),
        grid=(b, nqb, nkvb),
        in_specs=dq_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, h * d_qk), lambda b_, i, j: (b_, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, nq, h * d_qk), q.dtype)],
        scratch_shapes=[pltpu.VMEM((h, block_q, d_qk), jnp.float32)],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=_interpret_default(),
    )(*inputs)

    return dq, dk, dv, None if bias is None else jnp.zeros_like(bias)


_flash_packed.defvjp(_flash_packed_fwd, _flash_packed_bwd)


# One trace for all the calls of one shape: a step's latent layers make the
# same call 8 to 48 times, and without this every one traces its three
# kernels anew (the banded bodies of a cut tile are four times the operations
# to trace). XLA inlines the call; the program is the same.
_flash_packed_cached = jax.jit(_flash_packed, static_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12))


def packed_supported(num_heads: int, d_qk: int, d_v: int) -> bool:
    """Head dims must tile cleanly in a packed minor dim (no per-head zero
    padding is possible there), and the TOTAL packed width is VMEM-bounded:
    blocks and scratches scale with h*d, so wide many-head configs that are
    fine per-head on the heads-major path would blow the Mosaic budget
    packed. (Per-head size caps live in :func:`flash_supported`.)"""
    return (
        d_qk % 8 == 0
        and d_v % 8 == 0
        and num_heads * d_qk <= 1024
        and num_heads * d_v <= 1024
    )


@jax.named_scope("flash_attention_packed")
def flash_attention_packed(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    num_heads: int,
    pad_mask: Optional[jnp.ndarray] = None,
    causal: bool = False,
    sm_scale: float = 1.0,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
) -> jnp.ndarray:
    """Blockwise fused attention over packed slots-major tensors.

    ``block_q``/``block_kv``: None = tuned default hint (a no-pad divisor up
    to 25% larger may be picked); an explicit value is an upper bound.

    :param q: queries (B, Nq, H*Dqk), already scaled/rotated.
    :param k: keys (B, Nkv, H*Dqk), already rotated.
    :param v: values (B, Nkv, H*Dv).
    :returns: (B, Nq, H*Dv) in q's dtype — the natural o_proj input layout.

    Semantics identical to :func:`flash_attention`; operands and results stay
    in the projection layout, so no transpose copies materialize around the
    kernels.
    """
    b, nq, cq = q.shape
    nkv = k.shape[1]
    h = num_heads
    d_qk = cq // h
    d_v = v.shape[2] // h
    offset = nkv - nq

    geom = _geometry(nq, nkv)
    plan = tile_plan(nq, nkv, causal, block_q, block_kv, pad_mask=pad_mask is not None)
    _TILE_PLANS[(geom, causal, plan.bias)] = plan
    block_q, block_kv = plan.block_q, plan.block_kv

    qf = _pad_to(q, 1, block_q)
    kf = _pad_to(k, 1, block_kv)
    vf = _pad_to(v, 1, block_kv)

    # additive kv bias per batch row: padded slots + user pad mask; a call
    # with neither has no bias operand (``plan.bias``)
    biases = ()
    if plan.bias:
        nkv_p = kf.shape[1]
        bias = jnp.zeros((b, nkv_p), jnp.float32)
        if pad_mask is not None:
            bias = bias.at[:, :nkv].set(jnp.where(pad_mask, MASK_VALUE, 0.0))
        if nkv_p != nkv:
            bias = bias.at[:, nkv:].set(MASK_VALUE)
        biases = (bias[:, None, :],)

    out = _on_batch_shards(
        lambda q_, k_, v_, *bias_: _flash_packed_cached(
            q_, k_, v_, bias_[0] if bias_ else None, causal, offset, sm_scale, block_q, block_kv, h, d_qk, d_v, geom
        ),
        qf, kf, vf, *biases,
    )
    return out[:, :nq, :]


@jax.named_scope("flash_attention")
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    pad_mask: Optional[jnp.ndarray] = None,
    causal: bool = False,
    sm_scale: float = 1.0,
    # None = tuned defaults, from a same-process sweep of the whole step at
    # batch 4 on v5e that changed the blocks of every call at once: block_q
    # 1024 beats 512 by ~1.6% and 256 by ~8%; block_kv 2048-class is flat vs
    # 4352. It could not tell the cross-attention from the self-attention
    # kernels; per geometry, at batch 32, PERF.md 6 (PR 27) has the readings.
    # Explicit values are upper bounds (exact _choose_block).
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
) -> jnp.ndarray:
    """Blockwise fused attention.

    :param q: queries (B, H, Nq, Dqk); assumed already scaled/rotated.
    :param k: keys (B, H, Nkv, Dqk).
    :param v: values (B, H, Nkv, Dv).
    :param pad_mask: optional (B, Nkv) boolean mask, True = padding slot.
    :param causal: apply the right-aligned causal mask
        ``kv_j <= q_i + (Nkv - Nq)`` (reference: modules.py:135-140).
    :param sm_scale: score scale applied inside the kernel.
    :returns: attention output (B, H, Nq, Dv) in q's dtype.
    """
    b, h, nq, d_qk = q.shape
    nkv = k.shape[2]
    d_v = v.shape[3]
    offset = nkv - nq  # from the *unpadded* lengths

    block_q = _choose_block(nq, 1024 if block_q is None else block_q, exact=block_q is not None)
    block_kv = _choose_block(nkv, 2048 if block_kv is None else block_kv, exact=block_kv is not None)

    qf = _pad_to(q.reshape(b * h, nq, d_qk), 1, block_q)
    kf = _pad_to(k.reshape(b * h, nkv, d_qk), 1, block_kv)
    vf = _pad_to(v.reshape(b * h, nkv, d_v), 1, block_kv)

    # zero-pad odd head dims to a tile-compatible multiple of 8: zero qk
    # channels contribute nothing to the scores, zero v channels produce
    # extra output channels sliced off below (e.g. the vision classifier's
    # qk width 261 — pixel channels + Fourier bands, reference parity —
    # would otherwise fall back to the dense O(Nq x Nkv) path)
    qf = _pad_to(qf, 2, 8)
    kf = _pad_to(kf, 2, 8)
    vf = _pad_to(vf, 2, 8)

    # additive kv bias per (batch*head) row: padded slots + user pad mask
    nkv_p = kf.shape[1]
    bias = jnp.zeros((b, nkv_p), jnp.float32)
    if pad_mask is not None:
        bias = bias.at[:, :nkv].set(jnp.where(pad_mask, MASK_VALUE, 0.0))
    if nkv_p != nkv:
        bias = bias.at[:, nkv:].set(MASK_VALUE)
    # kernels index the (B, 1, Nkv_p) bias with (bh // num_heads, 0, j)
    bias = bias[:, None, :]

    # (batch*heads) rows are batch-major, so a split of the leading dim over
    # the batch axes keeps each device's rows aligned with its bias rows
    out = _on_batch_shards(
        lambda q_, k_, v_, bias_: _flash(
            q_, k_, v_, bias_, causal, offset, sm_scale, block_q, block_kv, h, _geometry(nq, nkv)
        ),
        qf, kf, vf, bias,
    )
    return out[:, :nq, :d_v].reshape(b, h, nq, d_v)


def _choose_block(n: int, requested: int, exact: bool = False) -> int:
    """Pick a block size for an axis of length ``n``: prefer an exact divisor
    (multiple of 128) so the wrapper need not pad at all — e.g. the
    dropout-discounted 16k cross-attention kv of 8704 takes block 2176
    instead of padding to 10240 (pad + slice copies and ~18% wasted
    backward-kernel iterations, profiled ~0.6 ms/step at batch 4).
    Fall back to the requested size capped to a power of two (the original
    pad-to-multiple path).

    ``exact=False`` (the wrappers' *default* hint): a divisor up to 25%
    LARGER than the hint may be chosen. ``exact=True`` (caller passed an
    explicit block size — tests, VMEM-tuned configs, A/B sweeps): divisors
    never exceed the requested size, so the choice is an upper bound."""
    slack = 0 if exact else requested // 4
    best = 0
    for b in range(LANES, n + 1, LANES):
        if n % b == 0 and b <= requested + slack:
            best = b
    # only take the divisor when it is actually near the requested size —
    # a 128-wide divisor for an awkward length (e.g. 128*prime) would trade
    # a little padding for a much larger grid of tiny blocks
    if best >= requested // 2:
        return best
    return min(requested, _round_pow2_cap(n))


def _round_pow2_cap(n: int) -> int:
    """Largest power of two <= n (min 128) — keeps blocks tile-aligned for
    short sequences."""
    p = 128
    while p * 2 <= n:
        p *= 2
    return p


def flash_supported(
    nq: int, nkv: int, d_qk: int, d_v: int, has_dropout: bool
) -> bool:
    """Whether the fused path applies: no attention-prob dropout (the einsum
    path keeps that reference feature), head dims within the tile budget
    (odd widths are zero-padded to a multiple of 8 by the wrapper), and
    sequences long enough to be worth a kernel launch."""
    if has_dropout:
        return False
    if d_qk > 512 or d_v > 512:
        return False
    return nq >= 128 and nkv >= 128


# None = auto (TPU backend only); contextvar so a test/probe override stays
# scoped to its context instead of leaking across threads
_FLASH_DEFAULT = contextvars.ContextVar("flash_default", default=None)


def set_default_flash(mode: Optional[bool]) -> None:
    """Override the auto policy: True forces the fused path everywhere it is
    supported (interpret mode off-TPU — slow, for tests), False disables it,
    None restores auto (fused on TPU only).

    The flag is read at **trace time**: functions already jit-compiled keep
    whatever path they were traced with. Set it before building/jitting the
    model (or clear jit caches) for the toggle to take effect. Affects the
    current context only; prefer :func:`default_flash` for scoped use."""
    _FLASH_DEFAULT.set(mode)


@contextlib.contextmanager
def default_flash(mode: Optional[bool]):
    """Scoped :func:`set_default_flash`: traces inside the block see ``mode``."""
    token = _FLASH_DEFAULT.set(mode)
    try:
        yield
    finally:
        _FLASH_DEFAULT.reset(token)


def flash_enabled(explicit: Optional[bool] = None) -> bool:
    if explicit is not None:
        return explicit
    default = _FLASH_DEFAULT.get()
    if default is not None:
        return default
    return jax.default_backend() == "tpu"


# NOTE: a size-based auto policy ("einsum below nkv=4096, flash above") was
# prototyped and REJECTED on measurement: cross-process A/B suggested the
# latent self-attention (1024x1024) was ~35% faster on einsum, but the chip's
# burst-vs-sustained clocking (1.5-1.8x) had inflated the comparison — the
# same-process interleaved A/B (a tool since deleted) shows all-flash fastest at
# batch 4 (25.5 vs 29.0 ms/step) and within 4% at batch 1. Keep flash
# everywhere it is supported; re-measure in a cell (a traced benchmark run)
# before revisiting.


# ---------------------------------------------------------------------------
# grouped-query causal self-attention with a sliding window (forward only)
# ---------------------------------------------------------------------------
#
# A decoder-only model's prompt pass (models/text/decoder_lm.py, core/gqa.py):
# position i sees j <= i and, on a window layer, j > i - window; the keys and
# values have fewer heads than the queries. Blocks are square, and grid step
# ``s`` of q block ``iq`` takes kv block ``iq - s``: the diagonal tile first,
# then the blocks before it, as many as the window reaches (all of them
# without one). The index maps carry the offset, so a hidden kv block is
# never fetched; what a tile shows is the same for every q block and depends
# on ``s`` alone, so each step's visible bands (``_BAND_ROWS`` rows over the
# kv slots they see, as the packed kernels cut their diagonal tile) are
# static. Queries and the output stay in the projection layout (B, N, H*D)
# and a grid step takes one head's D columns; keys and values come heads-major
# with their own head count (the layout the decode caches keep), and query
# head h reads key-value head ``h // group``: nothing is written out 8 times.
# No cell differentiates it: there is no backward, and asking for one raises.


def _gqa_steps(n_blocks: int, block: int, window: int) -> int:
    """kv blocks a q block can see, its own included."""
    return min(n_blocks, -(-(window + block - 1) // block))


def _gqa_bands(step: int, block: int, window: int) -> tuple:
    """What the tile ``step`` blocks before the diagonal shows, as bands
    ``(r0, r1, c0, c1, masked)``: rows [r0, r1) of the q block against the kv
    slots [c0, c1) of the block (multiples of LANES), ``masked`` where some
    score of the band is hidden. Row r sees slot c iff ``c <= r + shift`` and
    ``c > r + shift - window`` with ``shift = step * block``. A tile that
    would keep more than ``_BAND_MAX_SHARE`` of its scores runs whole."""
    shift = step * block

    def band(r0, r1):
        c0 = max(0, (r0 + shift - window + 1) // LANES * LANES)
        c1 = min(block, _round_up(r1 + shift, LANES))
        masked = c1 - 1 > r0 + shift or c0 <= r1 - 1 + shift - window
        return (r0, r1, c0, c1, masked)

    bands = tuple(b for b in (band(r0, min(r0 + _BAND_ROWS, block)) for r0 in range(0, block, _BAND_ROWS)) if b[3] > b[2])
    if sum((r1 - r0) * (c1 - c0) for r0, r1, c0, c1, _ in bands) > _BAND_MAX_SHARE * block * block:
        return (band(0, block),)
    return bands


def _gqa_tiles(n_blocks: int, block: int, window: int) -> tuple:
    """``((first step, last step, bands), ...)``: consecutive steps that show the same bands, merged."""
    tiles = []
    for step in range(_gqa_steps(n_blocks, block, window)):
        bands = _gqa_bands(step, block, window)
        if tiles and tiles[-1][2] == bands:
            tiles[-1] = (tiles[-1][0], step, bands)
        else:
            tiles.append((step, step, bands))
    return tuple(tiles)


def _make_window_plan(n: int, block: int, window: int) -> TilePlan:
    n_blocks = _round_up(n, block) // block
    unit = LANES * LANES
    tiles = _gqa_tiles(n_blocks, block, window)
    run = masked = 0
    for lo, hi, bands in tiles:
        # q blocks that have a kv block ``step`` before them, over the steps that show these bands
        q_blocks = sum(n_blocks - step for step in range(lo, hi + 1))
        run += q_blocks * sum((r1 - r0) * (c1 - c0) for r0, r1, c0, c1, _ in bands) // unit
        masked += q_blocks * sum((r1 - r0) * (c1 - c0) for r0, r1, c0, c1, m in bands if m) // unit
    cut = any(bands[0][:4] != (0, block, 0, block) for _, _, bands in tiles)
    return TilePlan(block, block, _BAND_ROWS if cut else 0, run, masked, (n_blocks * block) ** 2 // unit - run, "none")


def _fwd_gqa_kernel(*refs, sm_scale: float, window: int, tiles: tuple, pairs: int = 1):
    # ``pairs`` q then as many k (1, block, d_i), a score the sum of their products; v, o (1, block, d);
    # scratch m/l (block, LANES) f32, acc (block, d) f32
    q_refs, k_refs = refs[:pairs], refs[pairs: 2 * pairs]
    v_ref, o_ref, m_scr, l_scr, acc_scr = refs[2 * pairs:]
    iq, s = pl.program_id(2), pl.program_id(3)
    block = o_ref.shape[1]

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _band(r0, r1, c0, c1, masked):
        scores = functools.reduce(
            operator.add, (_dot(q[0, r0:r1, :], k[0, c0:c1, :], ((1,), (1,))) for q, k in zip(q_refs, k_refs))
        ) * sm_scale
        if masked:
            rows = lax.broadcasted_iota(jnp.int32, scores.shape, 0) + (r0 + s * block)
            cols = lax.broadcasted_iota(jnp.int32, scores.shape, 1) + c0
            # the diagonal tile runs first and shows every row its own slot, so a row that a
            # later tile hides whole already has a finite running maximum and adds exp(-huge) = 0
            scores = jnp.where((cols <= rows) & (cols > rows - window), scores, MASK_VALUE)
        m_prev, l_prev = m_scr[r0:r1], l_scr[r0:r1]
        m_next = jnp.maximum(m_prev, jnp.max(scores, axis=1)[:, None])
        p = jnp.exp(scores - m_next[:, :1])
        alpha = jnp.exp(m_prev - m_next)
        l_scr[r0:r1] = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
        m_scr[r0:r1] = m_next
        v = v_ref[0, c0:c1, :]
        acc_scr[r0:r1] = acc_scr[r0:r1] * alpha[:, :1] + _dot(p.astype(v.dtype), v, ((1,), (0,)))

    for lo, hi, bands in tiles:
        @pl.when((s >= lo) & (s <= hi) & (iq >= s))
        def _tile(bands=bands):
            for b in bands:
                _band(*b)

    @pl.when(s == pl.num_programs(3) - 1)
    def _store():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_gqa(q, k, v, num_heads, sm_scale, block, window, geom):
    b, n, _ = q.shape
    d = k.shape[2]
    group = num_heads // (k.shape[0] // b)
    kv_heads = num_heads // group
    n_blocks = n // block
    steps = _gqa_steps(n_blocks, block, window)

    def kv_map(b_, h, i, s):
        return (b_ * kv_heads + h // group, jnp.maximum(i - s, 0), 0)

    return pl.pallas_call(
        functools.partial(_fwd_gqa_kernel, sm_scale=sm_scale, window=window, tiles=_gqa_tiles(n_blocks, block, window)),
        name=_kernel_name("fwd", geom),
        grid=(b, num_heads, n_blocks, steps),
        in_specs=[
            pl.BlockSpec((1, block, d), lambda b_, h, i, s: (b_, i, h)),
            pl.BlockSpec((1, block, d), kv_map),
            pl.BlockSpec((1, block, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block, d), lambda b_, h, i, s: (b_, i, h)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block, LANES), jnp.float32),
            pltpu.VMEM((block, LANES), jnp.float32),
            pltpu.VMEM((block, d), jnp.float32),
        ],
        compiler_params=_compiler_params("parallel", "parallel", "parallel", "arbitrary"),
        interpret=_interpret_default(),
    )(q, k, v)


def _flash_gqa_no_backward(*_):
    raise NotImplementedError(
        "flash_attention_gqa is forward only (the prompt pass of a served decoder): no backward kernel is written"
    )


_flash_gqa.defvjp(_flash_gqa_no_backward, _flash_gqa_no_backward)


def gqa_flash_supported(n: int, head_dim: int) -> bool:
    """A head's columns are one block of the projection layout: whole lanes
    on the chip (any width in interpret mode), and rows worth a kernel."""
    return n >= LANES and (head_dim % LANES == 0 or _interpret_default())


@jax.named_scope("flash_attention_gqa")
def flash_attention_gqa(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    num_heads: int,
    window: Optional[int] = None,
    sm_scale: float = 1.0,
    block: Optional[int] = None,
) -> jnp.ndarray:
    """Causal self-attention, grouped queries, an optional sliding window.

    :param q: queries (B, N, H*D) in the projection layout, already rotated.
    :param k: keys (B, Hkv, N, D), already rotated; ``Hkv`` divides ``H``
        and query head h reads key-value head ``h // (H // Hkv)``.
    :param v: values (B, Hkv, N, D).
    :param window: position i sees ``i - window < j <= i`` (None: ``j <= i``).
    :param block: None = the tuned hint, a value = an upper bound.
    :returns: (B, N, H*D) in q's dtype. Forward only.
    """
    b, n, _ = q.shape
    kv_heads, d = k.shape[1], k.shape[3]
    if num_heads % kv_heads or q.shape[2] != num_heads * d or k.shape[2] != n:
        raise ValueError(f"flash_attention_gqa: q {q.shape}, k {k.shape}, {num_heads} heads do not fit")
    block = _choose_block(n, 1024 if block is None else block, exact=block is not None)
    geom = _geometry(n, n) + ("" if window is None else f"_w{window}")
    n_pad = _round_up(n, block)
    reach = n_pad if window is None else window  # without a window every earlier block is seen
    _TILE_PLANS[(geom, True, False)] = _make_window_plan(n, block, reach)
    # padded kv slots lie after every real query: the causal mask hides them
    qf = _pad_to(q, 1, block)
    kf = _pad_to(k.reshape(b * kv_heads, n, d), 1, block)
    vf = _pad_to(v.reshape(b * kv_heads, n, d), 1, block)
    return _flash_gqa(qf, kf, vf, num_heads, sm_scale, block, reach, geom)[:, :n]


# ---------------------------------------------------------------------------
# expanded latent attention, causal self-attention (forward only)
# ---------------------------------------------------------------------------
#
# The prompt pass of multi-head latent attention (core/mla.py::expand), on the
# operands as its up-projections write them, token-major: a head's query is a
# 128-lane block of ``q_nope`` and half a block of ``q_rope``, its key and its
# value the lane blocks ``2h`` and ``2h + 1`` of the ``[k_nope | v]`` product,
# and the one rotary key a token is read by every head. A score is the window
# kernel's with two products in place of one, ``q_nope . k_nope + q_rope .
# k_rope`` into one float32 tile; nothing is sliced, concatenated, written out
# a head at a time or turned heads-major between the products and the kernel.
# The rotary halves are 64 lanes: a grid step takes the 128-lane block that
# holds its head's ``q_rope`` beside a neighbour's, against the token's
# ``k_rope`` with zeros on the neighbour's lanes (``[k | 0]`` for an even
# head, ``[0 | k]`` for an odd one: exact, and a contraction of 64 costs the
# MXU what one of 128 does).

MLA_ROPE = LANES // 2


def mla_flash_supported(n: int, num_heads: int, nope: int, rope: int, v_dim: int) -> bool:
    """The widths are the kernel's lane blocks, heads come in pairs (the
    rotary halves of two share a block) and the rows are whole blocks."""
    widths = nope == LANES and v_dim == LANES and rope == MLA_ROPE
    return widths and num_heads % 2 == 0 and n >= LANES and n % _choose_block(n, 1024) == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_mla(q_nope, q_rope, kv, k_rope, num_heads, sm_scale, block, geom):
    b, n, _ = q_nope.shape
    n_blocks = n // block

    def q_block(of):
        return pl.BlockSpec((1, block, LANES), lambda b_, h, i, s: (b_, i, of(h)))

    def kv_block(of):
        return pl.BlockSpec((1, block, LANES), lambda b_, h, i, s: (b_, jnp.maximum(i - s, 0), of(h)))

    return pl.pallas_call(
        functools.partial(_fwd_gqa_kernel, sm_scale=sm_scale, window=n, tiles=_gqa_tiles(n_blocks, block, n), pairs=2),
        name=_kernel_name("mla_fwd", geom),
        grid=(b, num_heads, n_blocks, n_blocks),
        in_specs=[
            q_block(lambda h: h), q_block(lambda h: h // 2),
            kv_block(lambda h: 2 * h), kv_block(lambda h: h % 2), kv_block(lambda h: 2 * h + 1),
        ],
        out_specs=q_block(lambda h: h),
        out_shape=jax.ShapeDtypeStruct(q_nope.shape, q_nope.dtype),
        scratch_shapes=[
            pltpu.VMEM((block, LANES), jnp.float32),
            pltpu.VMEM((block, LANES), jnp.float32),
            pltpu.VMEM((block, LANES), jnp.float32),
        ],
        compiler_params=_compiler_params("parallel", "parallel", "parallel", "arbitrary"),
        interpret=_interpret_default(),
    )(q_nope, q_rope, kv, k_rope, kv)


def _flash_mla_no_backward(*_):
    raise NotImplementedError(
        "flash_attention_mla is forward only (the prompt pass of a served decoder): no backward kernel is written"
    )


_flash_mla.defvjp(_flash_mla_no_backward, _flash_mla_no_backward)


def _mla_geometry(n: int, num_heads: int) -> str:
    return _geometry(n, n) + f"_h{num_heads}"


def mla_kernel_name(n: int, num_heads: int) -> str:
    """``flash_mla_fwd_q<N>_kv<N>_h<H>``: what a device trace prints for the call."""
    return _kernel_name("mla_fwd", _mla_geometry(n, num_heads))


@jax.named_scope("flash_attention_mla")
def flash_attention_mla(
    q_nope: jnp.ndarray,
    q_rope: jnp.ndarray,
    kv: jnp.ndarray,
    k_rope: jnp.ndarray,
    num_heads: int,
    sm_scale: float = 1.0,
    block: Optional[int] = None,
) -> jnp.ndarray:
    """Causal self-attention of expanded latent attention, token-major.

    :param q_nope: (B, N, H*128), head h in lanes ``[128 h, 128 h + 128)``.
    :param q_rope: (B, N, H*64), already rotated.
    :param kv: (B, N, H*256), a head's ``k_nope`` then its ``v``: the up-projection's output as it is.
    :param k_rope: (B, N, 64), the one rotated key a token that every head reads.
    :param block: None = the tuned hint, a value = an upper bound.
    :returns: (B, N, H*128) in ``q_nope``'s dtype. Forward only.
    """
    b, n, _ = q_nope.shape
    shapes = (b, n, num_heads * LANES), (b, n, num_heads * MLA_ROPE), (b, n, 2 * num_heads * LANES), (b, n, MLA_ROPE)
    if (q_nope.shape, q_rope.shape, kv.shape, k_rope.shape) != shapes or num_heads % 2:
        raise ValueError(
            f"flash_attention_mla: q_nope {q_nope.shape}, q_rope {q_rope.shape}, kv {kv.shape}, k_rope {k_rope.shape}, "
            f"{num_heads} heads do not fit"
        )
    block = _choose_block(n, 1024 if block is None else block, exact=block is not None)
    if n % block:
        raise ValueError(f"flash_attention_mla: {n} rows are not whole blocks of {block}")
    geom = _mla_geometry(n, num_heads)
    _TILE_PLANS[(geom, True, False)] = _make_window_plan(n, block, n)
    zeros = jnp.zeros_like(k_rope)
    # lane block 0 for the even heads, block 1 for the odd ones
    k_rope = jnp.concatenate([k_rope, zeros, zeros, k_rope], axis=-1).astype(kv.dtype)
    return _flash_mla(q_nope, q_rope.astype(q_nope.dtype), kv, k_rope, num_heads, sm_scale, block, geom)
