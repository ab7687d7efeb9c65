"""The cache side of an absorbed latent-attention step as one Pallas kernel.

``core.mla.MultiHeadLatentAttention.absorb`` takes one new token a row: its
joint row ``[c_kv; k_rope]`` goes into slot ``length`` of the layer's
:class:`~perceiver_io_tpu.core.cache.LatentCache`, and the query, carried
into the latent space, reads ``softmax(q_cat . rows^T * sm_scale) @ rows``
over slots ``[0, length]``. Left to XLA inside a decode ``while`` the loop
carries the cache with the *capacity* axis on the 128 lanes (``{1,2,0}``: the
layout the scores product likes), so the append of one row is 36 864 two-byte
writes each in a vector of its own (167 us for 73 KB at LongCat-Flash's
shapes, as much as the two products together), and the float32 scores make a
round trip through HBM between the product, the softmax and the values
product (PERF.md 6, PR 40).

Here a grid step takes one row of the batch: that row's whole (capacity,
width) block of the cache in VMEM, read once for scores and values both; the
new row is put into its slot of the block there; scores, mask, the float32
softmax and the values product never leave VMEM (``dtype`` operands, float32
accumulation, the probabilities cast to the cache's dtype before the values
product: ``core.mla.latent_decode_attention``'s arithmetic). The cache is an
aliased operand, in its declared row-major layout, and of it the kernel
writes back one sublane tile of rows a batch row: the :func:`row_tile` rows
around slot ``length`` (16 of bfloat16, 18 KB where the block read is 1.8 MB),
the others as they were read. One row alone cannot be written: a bfloat16
row shares its 32-bit words with its neighbour, and Mosaic refuses a DMA or a
store that is not whole tiles, as it refuses any slice of a reference whose
width (576) is not whole lanes; an output block whose index follows the
scalar-prefetched ``length`` asks for neither.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def mla_absorb_kernel_name(heads: int, capacity: int, width: int) -> str:
    """``mla_absorb_h<H>_s<capacity>_w<width>``: what a device trace prints for the call."""
    return f"mla_absorb_h{heads}_s{capacity}_w{width}"


def row_tile(dtype) -> int:
    """Rows of one sublane tile of ``dtype``: 8 words of 32 bits deep, so 8 of float32 and 16 of bfloat16."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def mla_absorb_supported(rows_shape, dtype, keep: int) -> bool:
    """Whether :func:`mla_absorb` lowers for a cache ``rows_shape`` (B,
    capacity, width) of ``dtype`` of which ``keep`` channels are values: the
    capacity in whole sublane tiles (the write-back is one), the kept channels
    in whole lanes."""
    return rows_shape[1] % row_tile(dtype) == 0 and keep % 128 == 0


def _absorb_kernel(length_ref, q_ref, new_ref, rows_ref, cache_ref, out_ref, *, sm_scale: float, keep: int, tile: int):
    n = length_ref[0]
    base = pl.multiple_of(n // tile * tile, tile)
    window = rows_ref[0, pl.ds(base, tile), :]
    slot = lax.broadcasted_iota(jnp.int32, window.shape, 0)
    window = jnp.where(slot == n - base, new_ref[0], window)
    rows_ref[0, pl.ds(base, tile), :] = window  # the block in VMEM: what the two products below read
    cache_ref[0] = window  # the tile of the cache that goes back to HBM
    q, rows = q_ref[0], rows_ref[0]  # (H, W), (S, W)
    s = lax.dot_general(q, rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * sm_scale
    slot = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(slot <= n, s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    values = rows_ref[0, :, :keep]
    out_ref[0] = jnp.dot(p.astype(values.dtype), values, preferred_element_type=jnp.float32).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "keep", "out_dtype"))
def mla_absorb(q_cat, new_row, rows, length, *, sm_scale: float, keep: int, out_dtype=jnp.float32):
    """Append and attend in one call: ``new_row`` (B, 1, width) is written to
    slot ``length`` (a scalar) of ``rows`` (B, capacity, width), and ``q_cat``
    (B, H, width) attends over slots ``[0, length]``. Returns the cache's rows
    (the same buffer where the caller lets go of ``rows``) and the first
    ``keep`` channels of ``softmax(q . row) @ row``, (B, H, keep) in
    ``out_dtype``, accumulated in float32. ``capacity`` is a multiple of
    :func:`row_tile` (:func:`mla_absorb_supported`). Jitted per shape like the
    flash calls, so that a second lowering of a program meets the same
    serialized kernel."""
    from perceiver_io_tpu.ops.flash_attention import _VMEM_LIMIT, _interpret_default  # at call time: tests steer the second

    b, h, w = q_cat.shape
    s = rows.shape[1]
    tile = row_tile(rows.dtype)
    return pl.pallas_call(
        functools.partial(_absorb_kernel, sm_scale=sm_scale, keep=keep, tile=tile),
        name=mla_absorb_kernel_name(h, s, w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, h, w), lambda i, n: (i, 0, 0)),
                pl.BlockSpec((1, 1, w), lambda i, n: (i, 0, 0)),
                pl.BlockSpec((1, s, w), lambda i, n: (i, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, tile, w), lambda i, n: (i, n[0] // tile, 0)),
                pl.BlockSpec((1, h, keep), lambda i, n: (i, 0, 0)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(rows.shape, rows.dtype), jax.ShapeDtypeStruct((b, h, keep), out_dtype)],
        input_output_aliases={3: 0},  # operands count the prefetched scalar: ``rows`` is the cache that comes back
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret_default(),
    )(jnp.reshape(length, (1,)).astype(jnp.int32), q_cat.astype(rows.dtype), new_row.astype(rows.dtype), rows)
