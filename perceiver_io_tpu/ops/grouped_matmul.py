"""Grouped matrix product for the experts a chip holds: ``out[rows of group g]
= lhs[rows of group g] @ rhs[g]``, rows sorted by group, no row dropped.

``lhs`` is (M, K) with the rows of group 0 first, then group 1, and so on;
``group_sizes`` (G,) says how many rows each group has (traced values: the
routing decides them); ``rhs`` is (G, K, N). Rows at or past
``sum(group_sizes)`` belong to no group: their output is **not written**
(whatever the buffer held), so a caller selects them away (``jnp.where``, not
a multiply: the garbage may be a NaN).

The Pallas kernel follows the megablox scheme (``jax.experimental.pallas.ops.
tpu.megablox``): the grid walks *visits*, pairs of (row tile, group) that
overlap. A row tile that straddles a group boundary is visited once per group,
each visit storing only its own rows; consecutive visits of one row tile keep
the output block resident, so nothing is written twice to HBM. The number of
live visits is a traced grid bound: a tile no row falls in costs nothing, and
only the weights of groups that have rows are read. The contraction is held
whole wherever its blocks fit VMEM (:func:`block_plan`: every geometry a cell
runs), so a group's weight block has the same index on consecutive visits of
the group and is fetched once a group, not once a visit.

Kernel names, as a device trace shows them: ``moe_experts_prefill_m<M>_k<K>_n<N>``
(``benchmarks/layers/moe_experts_roofline.decode.py`` reads them): the pass
that runs the kernel is the prompt pass (and a decode step of 384 rows and
more, which no cell runs; a second pass name waits for a path that needs one).
"""

from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_LIMIT = 100 * 1024 * 1024
# the column tile where the contraction has to be cut (at most this: the blocks then stay near square)
_TILE_N = 1024


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def kernel_name(m: int, k: int, n: int) -> str:
    return f"moe_experts_prefill_m{m}_k{k}_n{n}"


def visit_plan(group_sizes: jnp.ndarray, m: int, tm: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``(group_offsets (G+1,), group_ids (V,), m_tile_ids (V,), num_visits ())``
    for ``V = m // tm + G - 1`` visit slots, of which the first ``num_visits``
    are live. Visit ``v`` covers the rows of row tile ``m_tile_ids[v]`` that
    belong to group ``group_ids[v]``; visits are ordered by row tile, then
    group. The slots past ``num_visits`` repeat the last live visit."""
    g = group_sizes.shape[0]
    tiles_m = m // tm
    slots = tiles_m + g - 1
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    starts = offsets[:-1]
    # tiles each group touches: from the tile its first row is in to the tile its last row is in
    first_tile = starts // tm
    last_tile = (ends + tm - 1) // tm  # exclusive
    group_tiles = jnp.where(sizes > 0, last_tile - first_tile, 0)
    group_ids = jnp.repeat(jnp.arange(g, dtype=jnp.int32), group_tiles, total_repeat_length=slots)
    # a row tile is visited once, plus once more for every group that starts inside it (not on its edge)
    starts_inside = (starts % tm != 0) & (sizes > 0)
    extra = jnp.zeros((tiles_m,), jnp.int32).at[jnp.where(starts_inside, first_tile, tiles_m)].add(1, mode="drop")
    # tiles past the last row are not visited at all
    used = (jnp.arange(tiles_m, dtype=jnp.int32) * tm < ends[-1]).astype(jnp.int32)
    m_tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32), (extra + 1) * used, total_repeat_length=slots)
    return offsets, group_ids, m_tile_ids, group_tiles.sum()


def _gmm_kernel(offsets_ref, group_ids_ref, m_tile_ids_ref, lhs_ref, rhs_ref, out_ref, *acc, tm, tn, tiles_k):
    v, k = pl.program_id(1), pl.program_id(2)
    lhs, rhs = lhs_ref[...], rhs_ref[0]
    precision = jax.lax.Precision.HIGHEST if lhs.dtype == jnp.float32 else None
    product = jnp.dot(lhs, rhs, preferred_element_type=jnp.float32, precision=precision)

    def store(total):
        group = group_ids_ref[v]
        rows = m_tile_ids_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
        mine = (rows >= offsets_ref[group]) & (rows < offsets_ref[group + 1])
        out_ref[...] = jnp.where(mine, total, out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)

    if tiles_k == 1:  # the contraction whole: no partial sums to carry
        return store(product)
    (acc_ref,) = acc

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += product
    pl.when(k == tiles_k - 1)(lambda: store(acc_ref[...]))


def _divisors(n: int) -> List[int]:
    """``n`` and the multiples of 128 under it that divide it, largest first."""
    return [n] + [t for t in range((n - 1) // 128 * 128, 0, -128) if n % t == 0]


def _vmem_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """What a grid step holds: the ``lhs``, ``rhs`` and ``out`` blocks twice each (the pipeline's two buffers) and the float32 product."""
    return 2 * (tm * tk + tk * tn + tm * tn) * itemsize + tm * tn * 4


def _blocks(k: int, n: int, tm: int, itemsize: int) -> Tuple[int, int]:
    """``(tk, tn)``: the contraction whole with the widest column that fits the
    kernel's VMEM (every geometry a cell runs: an expert's weights are fetched
    once, and with the column whole each ``lhs`` tile is too); where no column
    holds the contraction whole, the largest cut of ``k`` that fits beside a
    column of at most ``_TILE_N``."""
    fits = lambda tk, tn: _vmem_bytes(tm, tk, tn, itemsize) <= _VMEM_LIMIT  # noqa: E731
    for tn in _divisors(n):
        if fits(k, tn):
            return k, tn
    tn = next((t for t in _divisors(n) if t <= _TILE_N), n)
    return next((tk for tk in _divisors(k)[1:] if fits(tk, tn)), _divisors(k)[-1]), tn


def block_plan(m: int, k: int, n: int, tm: int, itemsize: int) -> dict:
    """How a product of these shapes is cut, from the shapes alone (a ``moe_tiles`` row of the ``compile`` event)."""
    tk, tn = _blocks(k, n, tm, itemsize)
    return {
        "m": m, "k": k, "n": n, "tm": tm, "tk": tk, "tn": tn, "tiles_k": k // tk, "tiles_n": n // tn,
        "rhs_block_bytes": tk * tn * itemsize, "vmem_bytes": _vmem_bytes(tm, tk, tn, itemsize),
        "weights_resident": tk == k,  # a group's block keeps its index from one visit of the group to the next
    }


_PLANS: dict = {}  # the products traced in this process, read by obs.recompile for the ``compile`` event row


def moe_tile_plans() -> List[dict]:
    """One row per distinct grouped product traced so far."""
    return [plan for _, plan in sorted(_PLANS.items())]


@functools.partial(jax.jit, static_argnames=("tm",))
def grouped_matmul(lhs, rhs, group_sizes, *, tm: int):
    """See the module docstring. ``lhs`` (M, K) with ``M % tm == 0``, ``rhs``
    (G, K, N), ``group_sizes`` (G,) integers; the result has ``lhs``'s dtype."""
    m, k = lhs.shape
    g, _, n = rhs.shape
    if m % tm:
        raise ValueError(f"grouped_matmul: {m} rows are not a multiple of the row tile {tm}")
    plan = _PLANS[(m, k, n, tm, lhs.dtype.itemsize)] = block_plan(m, k, n, tm, lhs.dtype.itemsize)
    tk, tn, tiles_k, tiles_n = plan["tk"], plan["tn"], plan["tiles_k"], plan["tiles_n"]
    offsets, group_ids, m_tile_ids, num_visits = visit_plan(group_sizes, m, tm)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tn=tn, tiles_k=tiles_k),
        name=kernel_name(m, k, n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles_n, num_visits, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda j, v, kk, off, gid, mid: (mid[v], kk)),
                pl.BlockSpec((1, tk, tn), lambda j, v, kk, off, gid, mid: (gid[v], kk, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, kk, off, gid, mid: (mid[v], j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * (tiles_k > 1),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
        ),
        interpret=_interpret_default(),
    )(offsets, group_ids, m_tile_ids, lhs, rhs)
