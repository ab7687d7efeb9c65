"""Gather/scatter-free building blocks for the training hot path.

XLA lowers the backward of an embedding lookup / row gather to a
``scatter-add``, which serializes on TPU. Profile of the 16k-context
Perceiver AR train step (batch 4, v5e, tools/xplane.py over a
``jax.profiler.trace`` capture):

- token-embedding gradient (65536 rows -> 262-row table): 1.03 ms/step
- prefix-dropout gather backward (30720 rows -> 61440 slots): 0.81 ms/step

Both rewrites below keep the forward untouched and replace only the VJP:

- ``small_vocab_embed``: d_table as a one-hot matmul (the MXU eats it;
  contraction size = number of looked-up rows). Only profitable for small
  vocabularies — flops scale with vocab — so callers gate on table height.
- ``gather_unique_rows``: for *unique* row indices (the dropout keep-set),
  the scatter-add backward is really a permutation: invert the index map
  once (an int scatter: tiny at batch 4, 1.1 to 1.3 ms each at the
  benchmark's batch 32, where XLA also sorts the 245 760 keys first) and the
  gradient becomes a row *gather* plus a zero mask.
- ``gather_sorted_table_rows``: a table shared by the batch, *sorted* unique
  indices: the table gradient as tile-local one-hot products in one Pallas
  kernel (below; batch-32 readings there).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import dtypes, lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# shard_map's static varying-mesh-axes inference cannot see through
# custom_vjp and rejects otherwise-correct out_specs; explicitly sharded
# paths (parallel/long_context.py) trace with the plain ops instead so the
# static check stays on.
_PLAIN_MODE = contextvars.ContextVar("gathers_plain_mode", default=False)


@contextlib.contextmanager
def plain_gathers():
    """Trace-time escape hatch: fall back to the plain XLA ops (scatter-add
    backwards) inside the with-block."""
    token = _PLAIN_MODE.set(True)
    try:
        yield
    finally:
        _PLAIN_MODE.reset(token)


def _int_zero(x):
    return np.zeros(x.shape, dtypes.float0)


# --------------------------------------------------- debug uniqueness check
#
# The scatter-free VJPs below are only correct for UNIQUE row indices per
# batch row: a duplicated index makes the forward gather emit the row twice,
# but the inverted-map backward credits the gradient to ONE copy and silently
# drops the other (no error, no NaN — just a wrong d_x/d_table). In-graph
# draws (lax.top_k of uniforms) are unique by construction; HOST-supplied
# index sets (`prefix_keep_idx`, training/prefix_dropout.py) are trusted
# input. `debug_unique_indices()` turns on verification for traces/calls
# inside the block — concrete operands are checked immediately, traced
# operands via a host callback that raises at run time.

_DEBUG_UNIQUE = contextvars.ContextVar("gathers_debug_unique", default=False)


@contextlib.contextmanager
def debug_unique_indices():
    """Opt-in (trace-time, like `plain_gathers`): verify that index operands
    of the scatter-free gather VJPs are unique per row (and sorted, for the
    sorted-table variant). Off by default — the check is a host round-trip
    per call, for debugging corrupted-gradient suspicions, not production."""
    token = _DEBUG_UNIQUE.set(True)
    try:
        yield
    finally:
        _DEBUG_UNIQUE.reset(token)


def _host_check_unique(idx, op_name: str, require_sorted: bool):
    a = np.asarray(idx).reshape(-1, np.asarray(idx).shape[-1])
    for r, row in enumerate(a):
        if np.unique(row).size != row.size:
            raise ValueError(
                f"{op_name}: index row {r} contains duplicates — the "
                "scatter-free VJP silently drops the gradient of all but one "
                "copy of a duplicated row (see ops/gathers.py)"
            )
        if require_sorted and row.size > 1 and not (np.diff(row) > 0).all():
            raise ValueError(
                f"{op_name}: index row {r} is not sorted ascending — the "
                "compact embedding route requires sorted keep sets"
            )


def _maybe_check_unique(idx, op_name: str, require_sorted: bool = False):
    if not _DEBUG_UNIQUE.get():
        return
    from perceiver_io_tpu.utils.arrays import concrete_or_none

    concrete = concrete_or_none(idx)
    if concrete is None:
        # traced: verify at run time on the host (the callback raising is
        # how the error surfaces from a jitted program)
        jax.debug.callback(
            lambda a: _host_check_unique(a, op_name, require_sorted), idx
        )
    else:
        _host_check_unique(concrete, op_name, require_sorted)


# ---------------------------------------------------------------- embedding


@jax.custom_vjp
def small_vocab_embed(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """``table[ids]`` whose gradient is ``one_hot(ids)^T @ g`` (a matmul)
    instead of a scatter-add. ``table`` (V, C), ``ids`` any int shape."""
    return jnp.take(table, ids, axis=0)


def _sve_fwd(table, ids):
    # dtype carried as a zero-size array: plain dtype objects are not JAX
    # types and cannot ride in custom_vjp residuals
    proto = jnp.zeros((0,), table.dtype)
    return jnp.take(table, ids, axis=0), (ids, table.shape[0], proto)


def _sve_bwd(res, g):
    ids, vocab, proto = res
    flat = ids.reshape(-1)
    gf = g.reshape(-1, g.shape[-1])
    onehot = jax.nn.one_hot(flat, vocab, dtype=gf.dtype)
    d_table = jnp.einsum(
        "nv,nc->vc", onehot, gf, preferred_element_type=jnp.float32
    ).astype(proto.dtype)
    return d_table, _int_zero(ids)


small_vocab_embed.defvjp(_sve_fwd, _sve_bwd)

# small enough that the one-hot contraction beats the scatter (flops ~ N*V*C)
SMALL_VOCAB_MAX = 2048


# named scopes on the dispatchers: graphlint (analysis/) attributes any
# plain-gather fallback here to these labels instead of a bare primitive —
# the hot-concat rule's gather check is scoped, so a route silently falling
# back to the scatter-add backward becomes visible by name
@jax.named_scope("embed_lookup")
def embed_lookup(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """Embedding lookup choosing the matmul-backward path for small tables."""
    if table.shape[0] <= SMALL_VOCAB_MAX and not _PLAIN_MODE.get():
        return small_vocab_embed(table, ids)
    return jnp.take(table, ids, axis=0)


# ------------------------------------------------------------- row gathers


@jax.custom_vjp
def gather_unique_rows(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``take_along_axis(x, idx[..., None], axis=1)`` for (B, N, C) ``x`` and
    (B, K) **unique-per-row** indices, with a gather-based backward."""
    return jnp.take_along_axis(x, idx[..., None], axis=1)


def _gur_fwd(x, idx):
    return jnp.take_along_axis(x, idx[..., None], axis=1), (idx, x.shape)


def _invert_idx(idx: jnp.ndarray, n: int):
    """Invert a (B, K) unique-per-row index map over rows [0, n): ``inv[b, j]``
    = position of row j in ``idx[b]`` (two tiny int32 scatters), ``kept[b, j]``
    = whether row j was selected."""
    b, k = idx.shape
    inv = jnp.zeros((b, n), jnp.int32)
    inv = jax.vmap(lambda i, v: i.at[v].set(jnp.arange(k, dtype=jnp.int32)))(inv, idx)
    kept = jnp.zeros((b, n), bool)
    kept = jax.vmap(lambda m, v: m.at[v].set(True))(kept, idx)
    return inv, kept


def _gur_bwd(res, g):
    idx, x_shape = res
    b, n, _ = x_shape
    inv, kept = _invert_idx(idx, n)
    d_x = jnp.take_along_axis(g, inv[..., None], axis=1)
    d_x = jnp.where(kept[..., None], d_x, 0)
    return d_x, _int_zero(idx)


gather_unique_rows.defvjp(_gur_fwd, _gur_bwd)


@jax.named_scope("gather_rows")
def gather_rows(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """`gather_unique_rows` unless tracing inside :func:`plain_gathers`."""
    if _PLAIN_MODE.get():
        return jnp.take_along_axis(x, idx[..., None], axis=1)
    _maybe_check_unique(idx, "gather_unique_rows")
    return gather_unique_rows(x, idx)


# ------------------------------------------------- shared-table row gathers
#
# d_table[p] = sum_b sum_k [idx[b,k] == p] g[b,k] for a table shared by the
# batch. Every row of ``idx`` is sorted and unique, so the rows of ``g[b]``
# that fall in a tile of T = EMBED_TILE consecutive positions are contiguous
# in ``g[b]``: they start at ``s[b,t] = #{k: idx[b,k] < t*T}`` and are at most
# T, so the two aligned T-row blocks ``s // T`` and ``s // T + 1`` of ``g[b]``
# always hold them. Per (tile, batch row) the gradient is a one-hot (T x T)
# built from each block's own index values (``idx == t*T + p``: an index
# outside the tile matches no p, so the window needs no mask of its own)
# times the block, on the MXU, summed over the batch in float32 and rounded
# once at the end. No (B, N, C) array, no inverted index map, no scatter, no
# sort. A one-hot times a value is exact, so against the scatter-add only the
# order of the float32 batch sum differs.
#
# The tile, read on the v5e at the 16k flagship's shape (batch 32, 7 680 kept
# of 15 360 positions, 512 channels, bf16; tools/embed_grad_ab.py, device ms a
# call; PERF.md 6, PR 31): 128 positions 2.327, 256 1.937, 384 2.038, 512
# 2.284 (fewer grid steps against more one-hot FLOPs; each reads g twice),
# against 11.453 for the inverse-gather VJP this replaced and 6.774 for XLA's
# scatter-add. Multiplying the second block always, or adding both products to
# the accumulator at once, moved 256 by under 3% either way.

EMBED_TILE = 256


def embed_tile_plan(positions: int, kept: int, batch: int, channels: int, plain: bool = False) -> dict:
    """What the table gradient of :func:`gather_table_rows` does for a call
    of these shapes: a pure function of its arguments (the ``embed_tiles``
    row of the ``compile`` event, docs/observability.md). ``route`` is
    ``tiles`` (the kernel ``embed_pos_grad_n<positions>_k<kept>``) or
    ``plain`` (XLA's scatter-add: inside :func:`plain_gathers`, or a channel
    count that is no multiple of the 128 lanes)."""
    tiled = not plain and channels % 128 == 0
    tiles = -(-positions // EMBED_TILE) if tiled else 0
    return {
        "positions": positions, "kept": kept, "batch": batch,
        "tile": EMBED_TILE if tiled else 0, "tiles": tiles, "grid_steps": tiles * batch,
        # two blocks a grid step, each a (T x T) one-hot times (T x channels)
        "onehot_flops": tiles * batch * 2 * 2 * EMBED_TILE * EMBED_TILE * channels,
        "route": "tiles" if tiled else "plain",
    }


# plans of the calls traced in this process, by (positions, kept, batch,
# route): a trace-time fact like ``ops.flash_attention._TILE_PLANS``, read by
# obs.recompile for the ``compile`` event row
_EMBED_PLANS: dict = {}


def embed_tile_plans() -> list:
    """One row per distinct :func:`gather_table_rows` call traced so far."""
    return [plan for _, plan in sorted(_EMBED_PLANS.items())]


def embed_grad_kernel_name(positions: int, kept: int) -> str:
    """What a device trace prints for the table-gradient kernel of a call
    that kept ``kept`` of ``positions`` table rows per batch row."""
    return f"embed_pos_grad_n{positions}_k{kept}"


def _embed_grad_kernel(first_ref, second_ref, idx0_ref, idx1_ref, g0_ref, g1_ref, out_ref, acc_ref, *, tile):
    del first_ref  # read by the index maps
    t, b = pl.program_id(0), pl.program_id(1)

    @pl.when(b == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = t * tile + lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    # float32 cotangents take full-precision passes; one bf16 pass is exact
    precision = lax.Precision.HIGHEST if g0_ref.dtype == jnp.float32 else None

    def add(idx_ref, g_ref):
        onehot = (rows == idx_ref[...]).astype(g_ref.dtype)  # (tile, tile) against (1, tile)
        acc_ref[...] += jnp.dot(onehot, g_ref[...], precision=precision, preferred_element_type=jnp.float32)

    add(idx0_ref, g0_ref)

    @pl.when(second_ref[t * pl.num_programs(1) + b] != 0)
    def _():
        add(idx1_ref, g1_ref)

    @pl.when(b == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "out_dtype", "tile"))
def _embed_table_grad(idx, g, n: int, out_dtype=None, tile: int = EMBED_TILE):
    """``zeros((n, C)).at[idx].add(g)`` summed over the batch, for (B, K)
    sorted unique-per-row ``idx`` and (B, K, C) ``g``, by the tile-local
    one-hot products above; in ``out_dtype`` (``g``'s by default)."""
    from perceiver_io_tpu.ops.flash_attention import _interpret_default

    b, k = idx.shape
    c = g.shape[-1]
    tiles, blocks = -(-n // tile), -(-k // tile)
    idx = idx.astype(jnp.int32)
    if k % tile:  # whole blocks; the pad matches no table row
        idx = jnp.pad(idx, ((0, 0), (0, blocks * tile - k)), constant_values=-1)
        g = jnp.pad(g, ((0, 0), (0, blocks * tile - k), (0, 0)))
    # rows of g[b] before each tile's first position, and before the end
    bounds = jnp.arange(tiles + 1, dtype=jnp.int32) * tile
    starts = (idx[:, :k, None] < bounds).sum(axis=1, dtype=jnp.int32)  # (B, tiles + 1)
    # the block of g[b] that holds a tile's first row (a tile past the last
    # kept index starts at K: the last block, in which nothing matches), and
    # whether its rows reach into the next block. Tile-major, like the grid.
    first = jnp.minimum(starts[:, :-1] // tile, blocks - 1)
    second = starts[:, 1:] > (first + 1) * tile
    first, second = first.T.reshape(-1), second.T.reshape(-1).astype(jnp.int32)

    def block(t, r, first, offset):  # the second block of a last first block is never used
        return jnp.minimum(first[t * b + r] + offset, blocks - 1)

    def idx_block(offset):
        return pl.BlockSpec((None, 1, tile), lambda t, r, first, second: (r, 0, block(t, r, first, offset)))

    def row_block(offset):
        return pl.BlockSpec((None, tile, c), lambda t, r, first, second: (r, block(t, r, first, offset), 0))

    out = pl.pallas_call(
        functools.partial(_embed_grad_kernel, tile=tile),
        name=embed_grad_kernel_name(n, k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles, b),
            in_specs=[idx_block(0), idx_block(1), row_block(0), row_block(1)],
            out_specs=pl.BlockSpec((tile, c), lambda t, r, first, second: (t, 0)),
            scratch_shapes=[pltpu.VMEM((tile, c), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((tiles * tile, c), out_dtype or g.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret_default(),
    )(first, second, idx[:, None, :], idx[:, None, :], g, g)
    return out[:n]


def _embed_table_grad_on_batch_shards(idx, g, n: int):
    """:func:`_embed_table_grad`; under ``ops.flash_attention.kernel_mesh``
    (GSPMD cannot partition a Mosaic call) per batch shard, the float32
    partial tables summed over the batch axes inside the shard_map."""
    from jax.sharding import PartitionSpec as P

    from perceiver_io_tpu.ops.flash_attention import _KERNEL_MESH

    scope = _KERNEL_MESH.get()
    axes = () if scope is None else tuple(a for a in scope[1] if scope[0].shape[a] > 1)
    if not axes:
        return _embed_table_grad(idx, g, n)

    def partial_sum(idx_, g_):
        return lax.psum(_embed_table_grad(idx_, g_, n, out_dtype=jnp.float32), axes)

    total = jax.shard_map(
        partial_sum, mesh=scope[0], in_specs=(P(axes), P(axes)), out_specs=P(), check_vma=False
    )(idx, g)
    return total.astype(g.dtype)


@jax.custom_vjp
def gather_sorted_table_rows(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``table[idx]`` for a (N, C) table shared across the batch and (B, K)
    **sorted unique-per-row** indices, whose table gradient is the tile-local
    one-hot products above (kernel ``embed_pos_grad_n<N>_k<K>``) in place of
    XLA's scatter-add. Used by the compact prefix-dropout embedding
    (core/adapter.py ``embed_compact``), where ``idx`` is the dropout keep
    set over position-table rows.

    Measured and rejected for this gradient on the v5e: inverting the index
    map and gathering ``g`` into (B, N, C) rows (0.1 ms a step at batch 4,
    11.5 at the benchmark's batch 32: PERF.md 6, PR 31), and a
    ``searchsorted`` membership test (4.2 ms a step at batch 4: a 13-trip
    loop of element gathers)."""
    return jnp.take(table, idx, axis=0)


def _gstr_fwd(table, idx):
    return jnp.take(table, idx, axis=0), (idx, table.shape[0])


def _gstr_bwd(res, g):
    idx, n = res
    return _embed_table_grad_on_batch_shards(idx, g, int(n)), _int_zero(idx)


gather_sorted_table_rows.defvjp(_gstr_fwd, _gstr_bwd)


@jax.named_scope("gather_table_rows")
def gather_table_rows(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """`gather_sorted_table_rows`; the plain ``take`` (scatter-add backward)
    when tracing inside :func:`plain_gathers` (which keeps shard_map's
    varying-axes check happy) or when the channels are no multiple of the 128
    lanes. Which it was is the ``route`` of :func:`embed_tile_plans`."""
    plain = _PLAIN_MODE.get()
    plan = embed_tile_plan(table.shape[0], idx.shape[1], idx.shape[0], table.shape[1], plain)
    _EMBED_PLANS[(plan["positions"], plan["kept"], plan["batch"], plan["route"])] = plan
    if not plain:
        _maybe_check_unique(idx, "gather_sorted_table_rows", require_sorted=True)
    if plan["route"] == "plain":
        return jnp.take(table, idx, axis=0)
    return gather_sorted_table_rows(table, idx)
