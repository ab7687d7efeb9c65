"""The cache side of a speculative step's grouped-query attention as one Pallas kernel.

``core.gqa.GroupedQueryAttention.verify`` takes ``n`` positions a row, each
row at its own length: their keys and values go into the layer's
:class:`~perceiver_io_tpu.core.cache.RaggedKVCache` (slots ``length + i``) or
:class:`~perceiver_io_tpu.core.cache.RaggedWindowKVCache` (slots ``(length +
i) % ring``), and the ``n * group`` queries of a key-value head read
``softmax(q . k^T * sm_scale) @ v`` over the slots their own position sees.
Left to XLA inside the decode ``while`` the loop carries the caches
*slot-major* (``{2,0,1}``: the (row, head) axis on the sublanes, so the keys
of one row, which a batched product contracts, lie a ``rows x D`` slab apart),
each write is a scatter of half-word rows into packed tiles behind a relayout
copy, and a layer is three passes: scores, softmax, values (PERF.md 6, PR 44).

Here a grid step takes one row of the batch with its key-value heads: the
heads' whole (slots, D) blocks of ``k`` and ``v`` in VMEM, each read once; the
new rows are put into their slots of the blocks there; scores, the mask (from
the row's scalar-prefetched length and iotas: the classes' ``visible``, no
boolean array), the float32 softmax and the values product never leave VMEM
(cache-dtype operands, float32 accumulation, the probabilities cast to the
cache's dtype before the values product: ``core.gqa.cached_verify_attention``'s
arithmetic). The caches are aliased operands in their declared row-major
layout, and of each the kernel writes back only the sublane tiles that hold
the new rows: the output stays in HBM (``memory_space=pl.ANY``) and one or two
windows of :func:`~perceiver_io_tpu.ops.mla_absorb.row_tile` slots go back by
DMA. Two, where the step's positions straddle a tile (``length % tile == tile
- 1``) or a ring wraps inside the step; an output block whose index follows
the length, ``ops/mla_absorb.py``'s way, covers one. A half-word row cannot
be written alone (that module's docstring says why).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from perceiver_io_tpu.ops.flash_attention import _VMEM_LIMIT, LANES
from perceiver_io_tpu.ops.mla_absorb import row_tile


def gqa_verify_kernel_name(ring: bool, rows: int, queries: int, slots: int, d: int) -> str:
    """``gqa_verify_<ring|full>_r<B*Hkv>_q<n*group>_s<slots>_d<D>``: what a device trace prints for the call."""
    return f"gqa_verify_{'ring' if ring else 'full'}_r{rows}_q{queries}_s{slots}_d{d}"


def _vmem_bytes(heads: int, slots: int, d: int, queries: int, itemsize: int) -> int:
    """A grid step's blocks: ``k`` and ``v`` double-buffered, and the float32 scores and probabilities of the heads."""
    return 2 * 2 * heads * slots * d * itemsize + 3 * heads * queries * slots * 4


def gqa_verify_supported(cache_shape, dtype, heads: int, n: int, group: int, window: Optional[int] = None) -> bool:
    """Whether :func:`gqa_verify` lowers for a cache ``cache_shape`` (B * Hkv,
    slots, D) of ``dtype`` with ``heads`` key-value heads a row, under a step
    of ``n`` positions and ``group`` queries a position: ``D`` in whole lanes,
    the slots in whole sublane tiles (the write-back is one or two, so ``n``
    within a tile), a step's blocks inside the kernels' VMEM limit, and of a
    ring of ``window`` the slack the step needs (what it refuses,
    ``RaggedWindowKVCache.write`` raises for)."""
    rows, slots, d = cache_shape
    tile = row_tile(dtype)
    fits = _vmem_bytes(heads, slots, d, n * group, jnp.dtype(dtype).itemsize) <= _VMEM_LIMIT * 3 // 4
    slack = window is None or n <= slots - window + 1
    return d % LANES == 0 and slots % tile == 0 and n <= tile and rows % heads == 0 and fits and slack


class VerifyPlan(NamedTuple):
    """One traced kernel geometry (a row of :func:`gqa_verify_plans`)."""

    kind: str  # "ring" or "full"
    rows: int  # B * Hkv
    queries: int  # n * group
    slots: int
    head_dim: int
    heads_a_step: int
    grid_steps: int
    vmem_bytes: int
    kernel: str


def verify_plan(cache_shape, dtype, heads: int, queries: int, window: Optional[int] = None) -> VerifyPlan:
    """How :func:`gqa_verify` cuts a cache ``cache_shape`` (B * Hkv, slots, D) of ``dtype``: one grid step a batch row of ``heads``."""
    rows, slots, d = cache_shape
    return VerifyPlan("full" if window is None else "ring", rows, queries, slots, d, heads, rows // heads,
                      _vmem_bytes(heads, slots, d, queries, jnp.dtype(dtype).itemsize),
                      gqa_verify_kernel_name(window is not None, rows, queries, slots, d))


_PLANS: dict = {}


def gqa_verify_plans() -> list:
    """One row per distinct kernel geometry traced so far (a speculative
    generator's ``compile`` row rides its prompt pass, before any step is
    traced, and derives its rows from the shapes: :func:`verify_plan`)."""
    return [plan._asdict() for _, plan in sorted(_PLANS.items())]


def _verify_kernel(length_ref, q_ref, k_new_ref, v_new_ref, k_ref, v_ref, k_hbm, v_hbm, o_ref, sem, *,
                   sm_scale: float, n: int, group: int, window: Optional[int], tile: int):
    b = pl.program_id(0)
    heads, slots, _ = k_ref.shape
    length = length_ref[b]

    # the slots of the step's positions and the tiles around them; a slot past the cache matches no row of its tile
    slot = [length + i if window is None else lax.rem(length + i, slots) for i in range(n)]
    base = [pl.multiple_of(jnp.minimum(s // tile * tile, slots - tile), tile) for s in slot]
    for ref, new_ref in ((k_ref, k_new_ref), (v_ref, v_new_ref)):
        for i in range(n):
            rows = ref[:, pl.ds(base[i], tile), :]
            at = lax.broadcasted_iota(jnp.int32, rows.shape, 1)
            ref[:, pl.ds(base[i], tile), :] = jnp.where(at == slot[i] - base[i], new_ref[:, i:i + 1, :], rows)

    # back to HBM: the tile of the first position and, where the last one lies in another, that one
    def tiles(at):
        return [pltpu.make_async_copy(ref.at[:, pl.ds(at, tile), :], hbm.at[pl.ds(b * heads, heads), pl.ds(at, tile), :], sem.at[j])
                for j, (ref, hbm) in enumerate(((k_ref, k_hbm), (v_ref, v_hbm)))]

    first, last = base[0], base[n - 1]
    for copy in tiles(first):
        copy.start()

    q, k, v = q_ref[...], k_ref[...], v_ref[...]
    s = jnp.einsum("hqd,hsd->hqs", q, k, preferred_element_type=jnp.float32) * sm_scale
    at = lax.broadcasted_iota(jnp.int32, s.shape, 2)
    query = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    q_pos = length + sum((query >= i * group).astype(jnp.int32) for i in range(1, n))  # length + query // group
    if window is None:  # ``RaggedKVCache.visible``
        visible = at <= q_pos
    else:  # ``RaggedWindowKVCache.visible``: the position a slot holds once the step's are written
        newest = length + (n - 1)
        lap = newest - lax.rem(newest, slots)
        held = jnp.where(at <= newest - lap, lap + at, lap - slots + at)
        visible = (held <= q_pos) & (held > q_pos - window) & (held >= 0)
    s = jnp.where(visible, s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    o_ref[...] = jnp.einsum("hqs,hsd->hqd", p.astype(v.dtype), v, preferred_element_type=jnp.float32).astype(o_ref.dtype)

    for copy in tiles(first):
        copy.wait()

    @pl.when(last != first)
    def _():
        for copy in tiles(last):
            copy.start()
        for copy in tiles(last):
            copy.wait()


@functools.partial(jax.jit, static_argnames=("heads", "window", "sm_scale"))
def gqa_verify(q, k_new, v_new, k, v, length, *, heads: int, window: Optional[int], sm_scale: float):
    """Write and attend in one call. ``k_new``/``v_new`` (B * Hkv, n, D) go to
    each row's positions ``length .. length + n - 1`` of ``k``/``v`` (B * Hkv,
    slots, D), ``length`` (B,) shared by a row's ``heads`` key-value heads, and
    ``q`` (B * Hkv, n * group, D), position-major, attends over what each
    query's position sees: slots up to its own of a growing cache (``window``
    None), the last ``window`` positions of a ring of ``slots``. Returns the two
    caches (the same buffers where the caller lets go of them) and ``softmax(q .
    k) @ v`` (B * Hkv, n * group, D) float32. See :func:`gqa_verify_supported`.
    Jitted per shape like the flash calls, so that a second lowering of a
    program meets the same serialized kernel."""
    from perceiver_io_tpu.ops.flash_attention import _interpret_default  # at call time: tests steer it

    rows, queries, d = q.shape
    n = k_new.shape[1]
    plan = verify_plan(k.shape, k.dtype, heads, queries, window)
    _PLANS[plan.kernel] = plan
    a_row = lambda width: pl.BlockSpec((heads, width, d), lambda i, lengths: (i, 0, 0))  # noqa: E731
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_verify_kernel, sm_scale=sm_scale, n=n, group=queries // n, window=window, tile=row_tile(k.dtype)),
        name=plan.kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(plan.grid_steps,),
            in_specs=[a_row(queries), a_row(n), a_row(n), a_row(plan.slots), a_row(plan.slots)],
            out_specs=[in_hbm, in_hbm, a_row(queries)],
            scratch_shapes=[pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype), jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(q.shape, jnp.float32)],
        input_output_aliases={4: 0, 5: 1},  # operands count the prefetched lengths: ``k`` and ``v`` are the caches that come back
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret_default(),
    )(length.astype(jnp.int32), q.astype(k.dtype), k_new.astype(k.dtype), v_new.astype(v.dtype), k, v)
