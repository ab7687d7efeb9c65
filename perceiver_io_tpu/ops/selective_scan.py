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

**Layout.** Channels lie on the lanes *and* the sublanes: the wrapper views
``[.., D]`` as ``[.., D / 128, 128]`` and a tile is 8 x 128 = 1024 channels, one
float32 vector register a state row. The ``N`` (16) state rows of a tile are
then 16 registers, each updated by whole-register operations, and ``B_t[n]`` and
``C_t[n]`` are *scalars* to such a register: they come in through SMEM and
splat, where a layout with ``N`` on the sublanes would need a lane broadcast of
a column per token for ``B`` and a cross-sublane sum per token for ``C``. The
sum over ``n`` is 16 register adds in a fixed order. A ``[.., D, N]`` layout would
fill 16 lanes of 128.

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
TIME_CHUNK = 128  # tokens a grid step: three streams of 512 KB a buffer, the scalars 16 KB of SMEM (64 and 256 read within 2%)


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
    """``(groups, sublanes a tile, lanes)`` of the channel view ``[D / lanes, lanes]``."""
    lanes = LANES if d_inner % LANES == 0 else d_inner
    groups = d_inner // lanes
    return groups, (SUBLANES if groups % SUBLANES == 0 else groups), lanes


def scan_plan(length: int, d_inner: int, d_state: int) -> ScanPlan:
    groups, sub, lanes = _tile_shape(d_inner)
    chunk = min(TIME_CHUNK, -(-length // SUBLANES) * SUBLANES)
    tile = sub * lanes
    streams = 3 * 2 * chunk * tile * 4  # x, dt and y, double-buffered, float32
    consts = 2 * d_state * tile * 4  # A
    state = 2 * d_state * tile * 4  # the scratch and the final state's block
    return ScanPlan(length, d_inner, d_state, tile, chunk, (groups // sub) * -(-length // chunk),
                    streams + consts + state)


def ssm_scan_supported(d_inner: int) -> bool:
    """Whether the kernel lowers for the chip: channels in whole tiles of 8 x 128 (any width in interpret mode)."""
    from perceiver_io_tpu.ops.flash_attention import _interpret_default

    return d_inner % CHANNEL_TILE == 0 or _interpret_default()


def _scan_kernel(bc_ref, x_ref, dt_ref, a_ref, y_ref, state_ref, h_scr, *, d_state: int, chunk: int, length: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _start():
        h_scr[...] = jnp.zeros_like(h_scr)

    def token(t, h):
        dt = dt_ref[0, t]
        dtx = dt * x_ref[0, t]
        y = None
        base = t * (2 * d_state)
        new = []
        for n in range(d_state):  # a state row a register; the sum over n in this order
            h_n = jnp.exp(dt * a_ref[n]) * h[n] + dtx * bc_ref[base + n]
            y_n = h_n * bc_ref[base + d_state + n]
            y = y_n if y is None else y + y_n
            new.append(h_n)
        y_ref[0, t] = y
        return tuple(new)

    # the last chunk of a length that is no multiple of the chunk stops at the row's end: what lies
    # past it in the blocks is not the row's
    steps = chunk if length % chunk == 0 else jnp.minimum(chunk, length - j * chunk)
    h = lax.fori_loop(0, steps, token, tuple(h_scr[n] for n in range(d_state)))
    for n in range(d_state):
        h_scr[n] = h[n]

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        state_ref[0] = h_scr[...]


@jax.jit
def _scan(x, dt, b, c, a):
    from perceiver_io_tpu.ops.flash_attention import _VMEM_LIMIT, _interpret_default  # at call time: tests steer the second

    rows, length, d_inner = x.shape
    d_state = b.shape[-1]
    groups, sub, lanes = _tile_shape(d_inner)
    plan = _SCAN_PLANS[(length, d_inner, d_state)] = scan_plan(length, d_inner, d_state)
    chunk = plan.time_chunk
    n_chunks = -(-length // chunk)

    f32 = jnp.float32
    # a token's 2N scalars side by side, the rows' chunks end to end: a chunk's block of SMEM is one run of the array
    bc = jnp.concatenate([b.astype(f32), c.astype(f32)], axis=-1)
    bc = jnp.pad(bc, ((0, 0), (0, n_chunks * chunk - length), (0, 0))).reshape(-1)
    view = lambda t: t.astype(f32).reshape(*t.shape[:-1], groups, lanes)  # noqa: E731
    stream = pl.BlockSpec((1, chunk, sub, lanes), lambda r, i, j: (r, j, i, 0))
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, d_state=d_state, chunk=chunk, length=length),
        name=ssm_scan_kernel_name(length, d_inner, d_state),
        grid=(rows, groups // sub, n_chunks),
        in_specs=[
            pl.BlockSpec((chunk * 2 * d_state,), lambda r, i, j: (r * n_chunks + j,), memory_space=pltpu.SMEM),
            stream,
            stream,
            pl.BlockSpec((d_state, sub, lanes), lambda r, i, j: (0, i, 0)),
        ],
        out_specs=[stream, pl.BlockSpec((1, d_state, sub, lanes), lambda r, i, j: (r, 0, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, length, groups, lanes), f32),
                   jax.ShapeDtypeStruct((rows, d_state, groups, lanes), f32)],
        scratch_shapes=[pltpu.VMEM((d_state, sub, lanes), f32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret_default(),
    )(bc, view(x), view(dt), view(a))
    return y.reshape(rows, length, d_inner), state.reshape(rows, d_state, d_inner)


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
