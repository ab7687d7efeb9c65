"""The rotary embedding of a packed slots-major array as one Pallas kernel.

``core.position.apply_rotary_pos_emb`` pairs adjacent channels
(``rotate_half``: ``[x0, x1, x2, x3] -> [-x1, x0, -x3, x2]``). On the chip
the channel axis is the 128-lane axis, and XLA answers the slice / stride /
stack / reshape / concatenate chain of that function by moving another axis
onto the lanes: at the 16k train step (keys ``bf16[32, 8704, 512]``) the
rotation cost 21 ms a step in float32 intermediates and full-size layout
copies, for 1.5 GB of traffic that the HBM moves in 2 ms (PERF.md 6, PR 37).

The kernel below keeps a (B, N, H*d) array in that layout: a lane rotation
by one is an XLU instruction (``pltpu.roll``), an even lane takes its odd
neighbour and an odd lane its even one (a **select**, so a pair never reaches
across a head and the values are ``apply_rotary_pos_emb``'s to the bit), and
``cos`` / ``sin`` come as one float32 table of a single head's channels,
(B, N, 2d), tiled over the heads in VMEM: never broadcast over heads in HBM.
Channels beyond the rotated ``R`` of a head pass through by a select as well.

XLA evaluates ``cos`` and ``sin`` where it likes them, the rows on the lanes
(:func:`rotary_angles`: (B, 2R, N), no lane is padding), and a product with a
constant 0/1 matrix at full precision lays them out for the kernel
(:func:`rotary_table`; exact: every entry is one value times 1). Built as
``concatenate`` and ``pad`` the same table cost twice the time, through two
relayout copies of 32-channel arrays padded to 128 lanes.

The backward is the transposed rotation on the same table: ``rotate_half``
is antisymmetric, so ``dx = g cos - rotate_half(g sin)``, which for the
pair-shared ``sin`` of ``frequency_position_encoding`` is the forward with
``sin`` negated. The residual is the (B, 2R, N) array, half the table's
bytes, laid out again for the backward; nothing of ``t``'s size is kept.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# elements of ``t`` a grid step takes (512 rows of 512 channels), rotated at
# once: worked on 16 or 32 rows at a time the same block took 1.6 and 1.1 times
# as long on the chip, and blocks of 256 or 1024 rows no less (PERF.md 6, PR 37)
ROTARY_BLOCK = 512 * 512
# a tile of rows. Under it (a decode step rotates one row) XLA fuses the rotation
# into the projection's epilogue; a kernel call would cost more than it saves
ROTARY_MIN_ROWS = 16


def rotary_kernel_name(pass_: str, n: int, channels: int) -> str:
    """``rotary_<fwd|bwd>_n<N>_c<H*d>``: what a device trace prints for the
    call. No ``flash`` in it: the benchmark's readers select the flash
    kernels by that word, and count this one under its scope, ``rotary``."""
    return f"rotary_{pass_}_n{n}_c{channels}"


def rotary_supported(t_shape, pos_enc_shape) -> bool:
    """Whether ``core.attention.rotate_slots_major`` runs the kernel on a
    (B, N, H, d) view with (B, N, R) angles: the packed width fills whole
    128-lane tiles, pairs lie inside the rotated part of a head, and there is
    a tile of rows."""
    b, n, h, d = t_shape
    r = pos_enc_shape[-1]
    return (
        tuple(pos_enc_shape) == (b, n, r)
        and (h * d) % 128 == 0
        and r % 2 == 0
        and 0 < r <= d
        and n >= ROTARY_MIN_ROWS
    )


def rotary_angles(pos_enc: jnp.ndarray) -> jnp.ndarray:
    """``cos | sin`` of (B, N, R) angles as (B, 2R, N) float32: XLA's ``cos``
    and ``sin`` of the float32 angles, as ``apply_rotary_pos_emb`` evaluates
    them. Positions are integers: no gradient flows to the angles."""
    pe = lax.stop_gradient(pos_enc).astype(jnp.float32)
    return jnp.swapaxes(jnp.concatenate([jnp.cos(pe), jnp.sin(pe)], axis=-1), 1, 2)


def rotary_table(cs: jnp.ndarray, head_dim: int) -> jnp.ndarray:
    """The kernel's table, (B, N, 2 * head_dim) float32, from
    :func:`rotary_angles`' (B, 2R, N): ``cos`` of a head's first R channels in
    lanes ``[0, R)``, ``sin`` in ``[head_dim, head_dim + R)``, zeros (which
    the kernel never reads) past ``R``."""
    r = cs.shape[1] // 2
    rows, lanes = np.arange(2 * r)[:, None], np.arange(2 * head_dim)[None, :]
    place = lanes == np.where(rows < r, rows, head_dim + rows - r)
    return jnp.einsum("bkn,kj->bnj", cs, jnp.asarray(place, jnp.float32), precision=lax.Precision.HIGHEST)


def _rotary_kernel(t_ref, cs_ref, out_ref, *, heads, head_dim, rotate_dim, transpose):
    c = heads * head_dim
    lane = lax.broadcasted_iota(jnp.int32, t_ref.shape, 1)
    x = t_ref[...].astype(jnp.float32)
    cs = cs_ref[...]
    cos = jnp.concatenate([cs[:, :head_dim]] * heads, axis=1)
    sin = jnp.concatenate([cs[:, head_dim:]] * heads, axis=1)
    even = lane % 2 == 0
    # roll(x, c - 1)[j] is x[j + 1], roll(x, 1)[j] is x[j - 1]; what wraps around is never selected
    if transpose:
        xs = x * sin
        y = x * cos + jnp.where(even, pltpu.roll(xs, c - 1, 1), -pltpu.roll(xs, 1, 1))
    else:
        y = x * cos + jnp.where(even, -pltpu.roll(x, c - 1, 1), pltpu.roll(x, 1, 1)) * sin
    if rotate_dim < head_dim:
        y = jnp.where(lane % head_dim < rotate_dim, y, x)
    out_ref[...] = y.astype(out_ref.dtype)


def _block_rows(n: int, channels: int) -> int:
    """Rows a grid step takes: ``ROTARY_BLOCK`` elements or the whole array, in whole tiles."""
    tile = ROTARY_MIN_ROWS
    return min(max(tile, ROTARY_BLOCK // channels // tile * tile), -(-n // tile) * tile)


@functools.partial(jax.jit, static_argnames=("heads", "rotate_dim", "transpose"))
def _rotary_call(t, cs, *, heads: int, rotate_dim: int, transpose: bool):
    """The rotation (``transpose``: its transpose) of (B, N, H*d) ``t`` by the
    (B, N, 2d) table of :func:`rotary_table`, in ``t``'s dtype. Jitted per
    shape like the packed flash call: the kernel is traced once a process,
    so a second lowering of the program (the benchmark's scope table) meets
    the same serialized body and the same cache key."""
    from perceiver_io_tpu.ops.flash_attention import _interpret_default

    b, n, c = t.shape
    head_dim = c // heads
    bn = _block_rows(n, c)

    def block(width):
        return pl.BlockSpec((None, bn, width), lambda i, j: (i, j, 0))

    return pl.pallas_call(
        functools.partial(_rotary_kernel, heads=heads, head_dim=head_dim, rotate_dim=rotate_dim, transpose=transpose),
        name=rotary_kernel_name("bwd" if transpose else "fwd", n, c),
        grid=(b, pl.cdiv(n, bn)),
        in_specs=[block(c), block(2 * head_dim)],
        out_specs=block(c),
        out_shape=jax.ShapeDtypeStruct(t.shape, t.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=_interpret_default(),
    )(t, cs)


def _on_shards(t, cs, heads, transpose):
    from perceiver_io_tpu.ops.flash_attention import _on_batch_shards

    table = rotary_table(cs, t.shape[-1] // heads)
    return _on_batch_shards(
        lambda t_, table_: _rotary_call(t_, table_, heads=heads, rotate_dim=cs.shape[1] // 2, transpose=transpose),
        t, table,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rotate_packed(t, cs, heads: int):
    """Rotate the first R channels of each of the ``heads`` heads of packed
    (B, N, H*d) ``t`` by :func:`rotary_angles`' (B, 2R, N) ``cs``. Under
    ``ops.flash_attention.kernel_mesh`` per batch shard, as the flash kernels
    run."""
    return _on_shards(t, cs, heads, False)


def _rotate_packed_fwd(t, cs, heads):
    return _on_shards(t, cs, heads, False), cs


def _rotate_packed_bwd(heads, cs, g):
    # behind a barrier, or XLA finds the forward's table again and keeps it alive through the step
    cs, g = lax.optimization_barrier((cs, g))
    return _on_shards(g, cs, heads, True), jnp.zeros_like(cs)


rotate_packed.defvjp(_rotate_packed_fwd, _rotate_packed_bwd)
