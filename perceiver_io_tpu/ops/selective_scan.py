"""The selective scan of a Mamba-1 layer's prompt pass as one Pallas kernel.

For a row's tokens ``t = 1..T``, channel ``d`` and state ``n`` (arXiv:2312.00752,
algorithm 2, after the discretisation)::

    h_t  = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) * B_t    [N, D], h_0 = 0, float32
    y_t  = sum_n h_t[n] * C_t[n]                           [D]

Everything is elementwise: ``A`` is a channel's *and* a state's, ``dt`` a
channel's, so there is no matrix-unit form, and the recurrence runs in token
order. ``jax.lax.associative_scan`` would hold ``[T, D, N]`` float32 in HBM (21
GB a layer at 65 536 tokens of 5120 channels) and a ``lax.scan`` of a token a
step launches a program a token. Here the grid is (row, tile of channels, chunk
of time) with time innermost and sequential, and the state of a tile lives in
VMEM scratch across a row's time chunks and is written out once, as the row's
final state: no ``[T, D, N]`` array exists anywhere.

**Layout.** In the recurrence channels lie on the lanes *and* the sublanes: a
tile is 8 x 128 = 1024 channels of one token, one float32 vector register a
state row. The ``N`` (16) state rows of a tile are then 16 registers, each
updated by whole-register operations, and ``B_t[n]`` and ``C_t[n]`` are
*scalars* to such a register: they come in through SMEM and splat, where a
layout with ``N`` on the sublanes would need a lane broadcast of a column per
token for ``B`` and a cross-sublane sum per token for ``C``. The sum over ``n``
is 16 register adds in a fixed order. A ``[.., D, N]`` layout would fill 16
lanes of 128.

The arrays do not lie that way, and the kernel takes them as they lie: ``x``
(in the mixer's dtype), the step size and ``y`` are ``(rows, T, D)`` with blocks
``(1, chunk, 1024)``, which the chip tiles 8 *tokens* x 128 channels, and ``A``
and the final state ``(N, D)`` / ``(rows, N, D)``, 8 *states* x 128 channels. A
``[.., D / 128, 128]`` view of any of them is a physical copy in HBM (until PR
47 the wrapper made three a call, 0.35 s of a 10.6 s call of
``jamba2-3b-decode-b256``). So the token loop walks slabs of 8 tokens: a slab of
a stream is 8 registers of (8 tokens x 128 channels), one a lane tile; turned
among themselves (:func:`_turn`: register ``g``'s sublane ``t`` becomes register
``t``'s sublane ``g``) they are 8 registers of (8 lane tiles x 128 channels), one
a token; the 8 tokens run in order, and their 8 ``y`` registers are turned back
and stored as one slab. ``x`` is widened to float32 on the loaded registers.
``A`` is turned once a tile into scratch and the state once a row, at the end.
The turn rides the load, store and shuffle slots under the vector ALUs and the
``exp`` that bind the kernel, and eight tokens a loop trip schedule better than
one: with the turn inside, a chunk of 16 rows of 256 tokens takes 0.699 ms where
the kernel over the views took 0.787, bit for bit the same ``y`` and state
(``tools/ssm_scan_ab.py`` on a v5e, PR 47: ``program`` against ``view4d``).

**Nothing is fused beside the recurrence.** The kernel is bound by the vector
and transcendental units (an ``exp`` and six operations a state element a
token), so whatever else runs in it adds to the unit that binds, while XLA's
elementwise fusions around it run at the HBM's rate: with the step size's bias
and softplus and the skip inside, the kernel took 1.153 ms for a chunk of 16
rows of 256 tokens where the recurrence alone takes 0.787, and 1.378 with the
gate ``y * silu(z)`` as well (``tools/ssm_scan_ab.py`` on a v5e, PR 41: 1.849,
1.528 and 2.090 ms with XLA's share). So the step size arrives computed (the
softplus is the epilogue of its projection), and the skip and the gate are one
XLA fusion over ``y``.

Forward only (the prompt pass of a served decoder): differentiation raises, as
``flash_attention_gqa``'s does. Interpret mode off the TPU, for the tests.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
CHANNEL_TILE = SUBLANES * LANES  # channels of a tile: one float32 register a state row
TIME_CHUNK = 128  # tokens a grid step, a multiple of 8: two float32 streams of 512 KB a buffer and x's, the scalars 16 KB of SMEM (64 and 256 read within 3%)


def ssm_scan_kernel_name(length: int, d_inner: int, d_state: int) -> str:
    """``ssm_scan_l<length>_d<d_inner>_n<d_state>``: what a device trace prints for the call."""
    return f"ssm_scan_l{length}_d{d_inner}_n{d_state}"


class ScanPlan(NamedTuple):
    """How one traced scan call is cut (a row of :func:`ssm_scan_plans`)."""

    length: int
    d_inner: int
    d_state: int
    channel_tile: int
    time_chunk: int
    grid_steps: int  # a row: tiles of channels x chunks of time
    vmem_bytes: int  # the streams' double buffers, the state and the constants of a tile


_SCAN_PLANS: dict = {}


def ssm_scan_plans() -> list:
    """One row per distinct scan geometry traced so far, for a ``compile`` event row."""
    return [plan._asdict() for _, plan in sorted(_SCAN_PLANS.items())]


def _tile_shape(d_inner: int):
    """``(groups, groups a tile, lanes)``: a row's channels cut into lane tiles (``groups`` of ``lanes``), and how many
    of them a grid step takes: 8, the sublanes of a register (every one where that does not divide: interpret mode's)."""
    lanes = LANES if d_inner % LANES == 0 else d_inner
    groups = d_inner // lanes
    return groups, (SUBLANES if groups % SUBLANES == 0 else groups), lanes


def scan_plan(length: int, d_inner: int, d_state: int, x_itemsize: int) -> ScanPlan:
    groups, sub, lanes = _tile_shape(d_inner)
    chunk = min(TIME_CHUNK, -(-length // SUBLANES) * SUBLANES)
    tile = sub * lanes
    streams = 2 * chunk * tile * (x_itemsize + 4 + 4)  # x as it arrives, dt and y float32, double-buffered
    consts = 3 * d_state * tile * 4  # A's block, double-buffered, and its turned copy
    state = 3 * d_state * tile * 4  # the scratch and the final state's block, double-buffered
    return ScanPlan(length, d_inner, d_state, tile, chunk, (groups // sub) * -(-length // chunk),
                    streams + consts + state)


def ssm_scan_supported(d_inner: int) -> bool:
    """Whether the kernel lowers for the chip: channels in whole tiles of 8 x 128 (any width in interpret mode)."""
    from perceiver_io_tpu.ops.flash_attention import _interpret_default

    return d_inner % CHANNEL_TILE == 0 or _interpret_default()


def _turn(slab, groups: int, lanes: int):
    """``(rows, groups * lanes)`` to ``(rows, groups, lanes)``: a row's lane tiles, which lie side by side, a register
    each, become the sublanes of one register a row. On the chip (8 rows, 8 tiles of 128 lanes) register ``g``'s
    sublane ``r`` becomes register ``r``'s sublane ``g``: Mosaic's transpose of a major axis with the sublanes."""
    return jnp.swapaxes(jnp.stack([slab[:, g * lanes:(g + 1) * lanes] for g in range(groups)]), 0, 1)


def _turn_back(regs):
    """``(rows, groups, lanes)`` to ``(rows, groups * lanes)``: :func:`_turn` undone."""
    tiles = jnp.swapaxes(regs, 0, 1)
    return jnp.concatenate([tiles[g] for g in range(tiles.shape[0])], axis=-1)


def _scan_kernel(bc_ref, x_ref, dt_ref, a_ref, y_ref, state_ref, h_scr, a_scr, *, d_state: int, chunk: int, length: int,
                 sub: int, lanes: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _start():
        h_scr[...] = jnp.zeros_like(h_scr)
        for n0 in range(0, d_state, SUBLANES):  # A, 8 states x 128 channels a register, to a register a state: once a tile
            n1 = min(n0 + SUBLANES, d_state)
            a_scr[n0:n1] = _turn(a_ref[n0:n1], sub, lanes)

    def slab(s, h, valid=None):
        """Tokens ``8 s .. 8 s + 7`` of the chunk in order; ``valid`` (the tail's) is how many of them are the row's."""
        rows = pl.ds(pl.multiple_of(s * SUBLANES, SUBLANES), SUBLANES)
        dt8 = dt_ref[0, rows]
        dts = _turn(dt8, sub, lanes)
        dtxs = _turn(dt8 * x_ref[0, rows].astype(jnp.float32), sub, lanes)  # x widened on the loaded registers
        ys = []
        for k in range(SUBLANES):
            dt, dtx = dts[k], dtxs[k]
            base = (s * SUBLANES + k) * (2 * d_state)
            y = None
            new = []
            for n in range(d_state):  # a state row a register; the sum over n in this order
                h_n = jnp.exp(dt * a_scr[n]) * h[n] + dtx * bc_ref[base + n]
                y_n = h_n * bc_ref[base + d_state + n]
                y = y_n if y is None else y + y_n
                new.append(h_n)
            ys.append(y)
            h = tuple(new) if valid is None else tuple(jnp.where(k < valid, h_n, old) for h_n, old in zip(new, h))
        y_ref[0, rows] = _turn_back(jnp.stack(ys))
        return h

    def carried():
        return tuple(h_scr[n] for n in range(d_state))

    def keep(h):
        for n in range(d_state):
            h_scr[n] = h[n]

    # the last chunk of a length that is no multiple of the chunk stops at the row's end: what lies
    # past it in the blocks is not the row's
    steps = chunk if length % chunk == 0 else jnp.minimum(chunk, length - j * chunk)
    keep(lax.fori_loop(0, steps // SUBLANES, slab, carried()))

    if length % SUBLANES:  # the row's last, partial slab: its tokens in order, the state kept from there on

        @pl.when(steps % SUBLANES > 0)
        def _tail():
            keep(slab(steps // SUBLANES, carried(), steps % SUBLANES))

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        for n0 in range(0, d_state, SUBLANES):  # the state, a register a state, to 8 states x 128 channels a register: once a row
            n1 = min(n0 + SUBLANES, d_state)
            state_ref[0, n0:n1] = _turn_back(h_scr[n0:n1])


@jax.jit
def _scan(x, dt, b, c, a):
    from perceiver_io_tpu.ops.flash_attention import _VMEM_LIMIT, _interpret_default  # at call time: tests steer the second

    rows, length, d_inner = x.shape
    d_state = b.shape[-1]
    groups, sub, lanes = _tile_shape(d_inner)
    plan = _SCAN_PLANS[(length, d_inner, d_state)] = scan_plan(length, d_inner, d_state, x.dtype.itemsize)
    chunk, tile = plan.time_chunk, plan.channel_tile
    n_chunks = -(-length // chunk)

    f32 = jnp.float32
    # a token's 2N scalars side by side, the rows' chunks end to end: a chunk's block of SMEM is one run of the array
    bc = jnp.concatenate([b.astype(f32), c.astype(f32)], axis=-1)
    bc = jnp.pad(bc, ((0, 0), (0, n_chunks * chunk - length), (0, 0))).reshape(-1)
    stream = pl.BlockSpec((1, chunk, tile), lambda r, i, j: (r, j, i))
    return pl.pallas_call(
        functools.partial(_scan_kernel, d_state=d_state, chunk=chunk, length=length, sub=sub, lanes=lanes),
        name=ssm_scan_kernel_name(length, d_inner, d_state),
        grid=(rows, groups // sub, n_chunks),
        in_specs=[
            pl.BlockSpec((chunk * 2 * d_state,), lambda r, i, j: (r * n_chunks + j,), memory_space=pltpu.SMEM),
            stream,
            stream,
            pl.BlockSpec((d_state, tile), lambda r, i, j: (0, i)),
        ],
        out_specs=[stream, pl.BlockSpec((1, d_state, tile), lambda r, i, j: (r, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((rows, length, d_inner), f32), jax.ShapeDtypeStruct((rows, d_state, d_inner), f32)],
        scratch_shapes=[pltpu.VMEM((d_state, sub, lanes), f32), pltpu.VMEM((d_state, sub, lanes), f32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret_default(),
    )(bc, x, dt.astype(f32), a.astype(f32))


@jax.custom_vjp
def selective_scan(x, dt, b, c, a):
    """The module docstring's recurrence over whole rows from a zero state.

    ``x`` and ``dt`` (rows, T, D): the convolved inputs and the step sizes
    (after the softplus); ``b`` and ``c`` (rows, T, N); ``a`` (N, D), negative:
    ``-exp(A_log)`` with the channels on the minor axis. Returns ``y`` (rows, T,
    D), without the skip, and the rows' final state (rows, N, D), both float32;
    every operand is widened to float32 and the sums of one channel's
    recurrence run in token order. ``D`` is a multiple of 1024 on the chip
    (:func:`ssm_scan_supported`)."""
    return _scan(x, dt, b, c, a)


def _no_backward(*_):
    raise NotImplementedError(
        "selective_scan is forward only (the prompt pass of a served decoder): no backward kernel is written"
    )


selective_scan.defvjp(_no_backward, _no_backward)


def selective_scan_reference(x, dt, b, c, a, state=None):
    """The same recurrence as a ``lax.scan`` of a token a step in plain XLA: what
    the mixer runs where the kernel may not (the CPU with the kernels off) and
    what the tests hold the kernel to. ``state`` (rows, N, D) is ``h_0`` (zero
    where left out)."""
    f32 = jnp.float32
    x, dt, b, c, a = (t.astype(f32) for t in (x, dt, b, c, a))
    h0 = jnp.zeros((x.shape[0], b.shape[-1], x.shape[-1]), f32) if state is None else state.astype(f32)

    def token(h, at):
        x_t, dt_t, b_t, c_t = at
        h = jnp.exp(dt_t[:, None, :] * a[None]) * h + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    h, y = lax.scan(token, h0, tuple(jnp.swapaxes(t, 0, 1) for t in (x, dt, b, c)))
    return jnp.swapaxes(y, 0, 1), h
