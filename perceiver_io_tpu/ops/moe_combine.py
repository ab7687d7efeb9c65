"""The way back to the tokens for an expert layer that holds a share of the
experts: ``y[t] += sum of weights[r] * rows[r] over the rows r of token t``,
the rows **in token order**, each product and each sum in float32.

``rows`` is (R, h) in the grouped kernel's dtype: a pass's rows as
``core/moe.py::experts_grouped`` brought them into the order of their tokens;
``tokens`` (R,) int32 says whose each is, ascending, and is ``T`` or more on
the rows past the pass's last pair, which are never read (they may hold
anything); ``weights`` (R,) float32. ``y`` is (T, h) float32 and is updated in
place: a token without a row keeps what it had.

Rows sorted by token mean a tile of tokens owns a contiguous run of rows. The
grid walks *visits*, pairs of (token tile, row tile) that overlap, in the order
of ``ops/grouped_matmul.py::visit_plan`` with a token tile in the place of an
expert: consecutive visits of one token tile keep its block of ``y`` resident,
a token tile without a row is not visited at all (its part of the aliased
buffer stays), and a row tile is weighed (``row.astype(float32) * weight``)
once however many token tiles share it. A visit then adds its rows one by one
on the vector unit, each into its token's row of the block: the arithmetic of
XLA's scatter-add, which this replaces (PERF.md 6, PR 50), so a token with one
or two rows gets that operation's bits whatever the order.

Kernel names, as a device trace shows them: ``moe_combine_t<T>_r<R>_h<h>``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from perceiver_io_tpu.ops.grouped_matmul import visit_plan

_VMEM_LIMIT = 100 * 1024 * 1024
_TOKEN_TILE = 256


def kernel_name(t: int, r: int, h: int) -> str:
    return f"moe_combine_t{t}_r{r}_h{h}"


def token_tile(t: int) -> int:
    """Tokens a tile: the largest multiple of 8 that divides ``t`` and is at most 256; all of them if none."""
    return max((d for d in range(8, min(t, _TOKEN_TILE) + 1, 8) if t % d == 0), default=t)


def _combine_kernel(offsets_ref, tile_ids_ref, row_tile_ids_ref, tokens_ref, rows_ref, weights_ref, y_ref, out_ref,
                    weighed_ref, *, tt, tr):
    v = pl.program_id(0)
    tile, row_tile = tile_ids_ref[v], row_tile_ids_ref[v]
    before = jnp.maximum(v - 1, 0)

    @pl.when((v == 0) | (row_tile_ids_ref[before] != row_tile))
    def _weigh():
        weighed_ref[...] = rows_ref[...].astype(jnp.float32) * weights_ref[...]

    @pl.when((v == 0) | (tile_ids_ref[before] != tile))
    def _start():  # the aliased output block holds nothing until it is written: read what was there through the input
        out_ref[...] = y_ref[...]

    first_row = row_tile * tr
    lo = jnp.maximum(offsets_ref[tile], first_row)
    hi = jnp.minimum(offsets_ref[tile + 1], first_row + tr)

    def add(r, carry):
        mine = pl.ds(tokens_ref[r] - tile * tt, 1)
        out_ref[mine, :] = out_ref[mine, :] + weighed_ref[pl.ds(r - first_row, 1), :]
        return carry

    lax.fori_loop(lo, hi, add, 0)


def visits(tokens, t: int, row_tile: int):
    """``visit_plan`` of rows sorted by token, a token tile in the place of a group: ``(offsets, tile_ids,
    row_tile_ids, num_visits)``. ``tokens`` (R,) ascending; the dead rows' tokens are past the last tile."""
    tt = token_tile(t)
    bounds = jnp.arange(t // tt + 1, dtype=jnp.int32) * tt
    before = (tokens[None, :] < bounds[:, None]).sum(axis=1, dtype=jnp.int32)  # rows before each token tile
    return visit_plan(before[1:] - before[:-1], tokens.shape[0], row_tile)


@functools.partial(jax.jit, static_argnames=("row_tile",))
def moe_combine(y, rows, weights, tokens, *, row_tile: int):
    """See the module docstring. ``y`` (T, h) float32, ``rows`` (R, h) with
    ``R % row_tile == 0``, ``weights`` (R,) float32, ``tokens`` (R,) int32
    ascending. Returns ``y`` with every row added to its token."""
    from perceiver_io_tpu.ops.grouped_matmul import _interpret_default  # at call time: tests steer the grouped kernels' rule

    t, h = y.shape
    r = rows.shape[0]
    if r % row_tile:
        raise ValueError(f"moe_combine: {r} rows are not a multiple of the row tile {row_tile}")
    tt = token_tile(t)
    offsets, tile_ids, row_tile_ids, num_visits = visits(tokens, t, row_tile)
    return pl.pallas_call(
        functools.partial(_combine_kernel, tt=tt, tr=row_tile),
        name=kernel_name(t, r, h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(num_visits,),
            in_specs=[
                pl.BlockSpec((row_tile, h), lambda v, off, tid, rid, tok: (rid[v], 0)),
                pl.BlockSpec((row_tile, 1), lambda v, off, tid, rid, tok: (rid[v], 0)),
                pl.BlockSpec((tt, h), lambda v, off, tid, rid, tok: (tid[v], 0)),
            ],
            out_specs=pl.BlockSpec((tt, h), lambda v, off, tid, rid, tok: (tid[v], 0)),
            scratch_shapes=[pltpu.VMEM((row_tile, h), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((t, h), jnp.float32),
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret_default(),
    )(offsets, tile_ids, row_tile_ids, tokens, rows, weights[:, None], y)
