"""Power retention (arXiv:2507.04239, degree 2) of a prompt pass as one Pallas
kernel, and the feature map and one-token update that every form shares.

For a key-value head with its ``group`` query heads, token ``t``, gate
``gamma_t`` in (0, 1) and ``Lambda_t = sum_{l <= t} log gamma_l``::

    attention form    A_tj = (q_t . k_j)^2 exp(Lambda_t - Lambda_j)  for j <= t
                      y_t  = sum_j A_tj v_j / (sum_j A_tj + eps)
    recurrent form    S_t = gamma_t S_{t-1} + phi(k_t) v_t^T,  z_t = gamma_t z_{t-1} + phi(k_t)
                      y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

with ``phi`` the symmetric square, ``phi(x) . phi(y) = (x . y)^2``. No softmax,
no running maximum: every weight is a square times a decay, non-negative, and
every exponent is at most 0.

**The feature map, by cyclic distance.** The distinct products ``x_a x_b`` of a
``D``-vector are read off ``D / 2 + 1`` lane rotations: tile ``d`` holds
``c_d x_a x_{(a + d) mod D}`` on lane ``a``, with ``c_0 = 1`` (the squares),
``c_d = sqrt(2)`` for ``0 < d < D / 2`` (each unordered pair at that distance
once) and ``c_{D/2} = 1`` (the ``D / 2`` antipodal pairs lie on that tile twice,
``a`` and ``a + D / 2``, each at weight 1 where one copy would take
``sqrt(2)``). So ``phi`` is ``(D / 2 + 1) D`` wide, 8320 at ``D`` = 128 for the
8256 distinct features (0.8% more state), every tile is 128 lanes whole, and a
tile of ``phi`` is one lane rotation and one multiply of what is already in
registers: ``phi`` of a chunk never exists outside VMEM.

**The state's layout.** ``S`` is ``(B, Hkv, R, D)`` float32 with ``R = (D / 2 +
1) D``: row ``d D + e``, lane ``a`` holds ``sum_j w_j v_j[e] phi_d(k_j)[a]``, the
value's channel on the rows of a tile and the feature on its lanes. Both sides
of the kernel are then plain products of things with the feature on the lanes:
``phi_d(Q) S_d^T`` (contracting the lanes of both, as ``q k^T`` does) and
``S_d += (w V)^T phi_d(K)``. ``z`` is ``(B, Hkv, R / D, D)``, a tile a row.

**The chunked form** (the kernel). Grid (row, key-value head, chunk of time),
time innermost and sequential; the head's ``S`` and ``z`` are the output blocks
themselves, resident in VMEM across a row's chunks and written to HBM once, as
the row's final state. For a chunk after ``t0`` with local ``lambda_i = Lambda_i
- Lambda_t0``: the in-chunk scores ``(q_i . k_j)^2 exp(lambda_i - lambda_j)``
masked to ``j <= i``, their product with ``V`` and their row sums; the carried
part ``exp(lambda_i) phi(q_i)^T S_t0`` by tiles of ``phi`` over the group's
queries stacked (``group x chunk`` rows a product), and for the denominator
``exp(lambda_i) q_i^T Z q_i`` with ``Z = sum_j w_j k_j k_j^T`` kept beside ``z``
in scratch (``phi(q) . z = q^T Z q``: one product and one lane sum in the place
of a reduction a tile); then the state's update by tiles, ``z``'s row riding
the same product as eight more rows of its left operand.

**The step** (the second kernel). Grid (row, key-value head): the head's ``S``
and ``z`` come in and go out through the same HBM arrays
(``input_output_aliases``), each tile decayed, updated, written and read for
``y`` while it is in registers. XLA's form of the same update
(:func:`retention_update`) holds the state twice and reads it three times.

Products take the inputs' dtype as operands (bfloat16 on the chip: ``phi`` and
the state's tile are rounded to it a product) and accumulate in float32; the
state, the gates and the normalisation are float32. Forward only (the prompt
pass of a served decoder): differentiation raises, as ``flash_attention_gqa``'s
and the selective scan's do. Interpret mode off the TPU, for the tests.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from perceiver_io_tpu.ops.flash_attention import _dot  # a matrix-unit product accumulating in float32; float32 operands at full precision

LANES = 128
CHUNK = 256  # tokens a grid step: 3.17 ms a row of 4096 against 3.36 at 512 and 3.69 at 1024 (``tools/power_retention_ab.py`` on a v5e, PERF.md 6, PR 46)
EPS = 1e-6
_Z_ROWS = 8  # the rows of the update's left operand that carry ``z``: a whole sublane tile


def feature_tiles(head_dim: int) -> int:
    return head_dim // 2 + 1


def feature_rows(head_dim: int) -> int:
    """``R``: the rows of a head's state, ``(D / 2 + 1) D``."""
    return feature_tiles(head_dim) * head_dim


def _coefficients(head_dim: int) -> Tuple[float, ...]:
    return (1.0,) + (math.sqrt(2.0),) * (head_dim // 2 - 1) + (1.0,)


def phi(x):
    """``x`` (..., D) -> the feature map (..., D / 2 + 1, D), float32: tile
    ``d``, lane ``a`` is ``c_d x_a x_{(a + d) mod D}`` (the module docstring)."""
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"the feature map takes an even width, not {d}")
    x = x.astype(jnp.float32)
    twice = jnp.concatenate([x, x], axis=-1)
    turned = jnp.stack([twice[..., t:t + d] for t in range(feature_tiles(d))], axis=-2)
    return jnp.asarray(_coefficients(d), jnp.float32)[:, None] * x[..., None, :] * turned


def retention_update(q, k, v, gamma, s, z, eps: float = EPS):
    """The recurrent form's one token: ``q`` (B, H, D), ``k`` and ``v`` (B, Hkv,
    D), ``gamma`` (B, Hkv) float32, the state ``s`` (B, Hkv, R, D) and ``z`` (B,
    Hkv, R / D, D) float32. Returns ``y`` (B, H, D) float32 and the state after the
    token. The numerator is a product a tile with ``q``'s dtype as operands
    (``phi(q)`` and the state's tile rounded to it) summed over the tiles in
    float32; the update and the denominator are float32 elementwise."""
    b, heads, d = q.shape
    kv_heads, tiles = k.shape[1], feature_tiles(d)
    f32 = jnp.float32
    fk = phi(k)
    s = gamma[..., None, None, None] * s.reshape(b, kv_heads, tiles, d, d) \
        + v.astype(f32)[:, :, None, :, None] * fk[:, :, :, None, :]
    z = gamma[..., None, None] * z + fk
    fq = phi(q).reshape(b, kv_heads, heads // kv_heads, tiles, d)
    num = jnp.einsum("bgqta,bgtea->bgtqe", fq.astype(q.dtype), s.astype(q.dtype), preferred_element_type=f32).sum(axis=2)
    den = jnp.sum(fq * z[:, :, None], axis=(-2, -1))
    y = num / (den[..., None] + eps)
    return y.reshape(b, heads, d), s.reshape(b, kv_heads, tiles * d, d), z


def power_retention_reference(q, k, v, log_gamma, state=None, eps: float = EPS):
    """The recurrent form as a ``lax.scan`` of a token a step in plain XLA: what
    the mixer runs where the kernel may not (the CPU with the kernels off) and
    what the tests hold the kernel to. ``q`` (B, T, H, D), ``k`` and ``v`` (B,
    T, Hkv, D), ``log_gamma`` (B, T, Hkv) float32, ``state`` ``(s, z)`` or
    ``None`` for an empty one. Returns ``y`` (B, T, H, D) float32 and ``(s, z)``."""
    b, _, _, d = q.shape
    kv_heads = k.shape[2]
    if state is None:
        state = (jnp.zeros((b, kv_heads, feature_rows(d), d), jnp.float32), jnp.zeros((b, kv_heads, feature_tiles(d), d), jnp.float32))

    def token(carry, at):
        q_t, k_t, v_t, lg_t = at
        y, s, z = retention_update(q_t, k_t, v_t, jnp.exp(lg_t), *carry, eps=eps)
        return (s, z), y

    state, y = lax.scan(token, state, tuple(jnp.swapaxes(t, 0, 1) for t in (q, k, v, log_gamma.astype(jnp.float32))))
    return jnp.swapaxes(y, 0, 1), state


# ------------------------------------------------------ what both kernels share

_NN = ((1,), (0,))
_NT = ((1,), (1,))


def _feature(x, t: int, coef):
    """Tile ``t`` of ``phi`` of ``x`` (rows, D) float32, in registers: ``c_t x_a x_{(a + t) mod D}`` on lane ``a``, one lane rotation and a multiply."""
    turned = x if t == 0 else pltpu.roll(x, x.shape[1] - t, 1)
    return (x if coef[t] == 1.0 else x * coef[t]) * turned


# ------------------------------------------------------------ the step's kernel

def power_ret_step_kernel_name(batch: int, heads: int, head_dim: int) -> str:
    """``power_ret_step_b<batch>_h<heads>_d<head_dim>``: what a device trace prints for the call."""
    return f"power_ret_step_b{batch}_h{heads}_d{head_dim}"


def _step_kernel(gamma_ref, q_ref, k_ref, v_ref, s_in, z_in, y_ref, s_ref, z_ref, *, head_dim: int, kv_heads: int, eps: float):
    # ``s_in`` / ``z_in`` and ``s_ref`` / ``z_ref`` are the same arrays in HBM (aliased): the state is read through the input
    # blocks and written through the output blocks. An output block is never read: on the chip it holds no data until
    # it is written (interpret mode fills it from the aliased input and would hide that)
    d, f32 = head_dim, jnp.float32
    coef = _coefficients(d)
    gamma = gamma_ref[pl.program_id(0) * kv_heads + pl.program_id(1)]
    dt = q_ref.dtype
    q, k = q_ref[0, 0].astype(f32), k_ref[0, 0].astype(f32)  # (the group's heads in whole sublane tiles, D), (1, D)
    # the value's channel down the sublanes, by the identity's mask and a lane sum (no transpose for Mosaic to lower)
    diagonal = lax.broadcasted_iota(jnp.int32, (d, d), 0) == lax.broadcasted_iota(jnp.int32, (d, d), 1)
    v_down = jnp.sum(jnp.where(diagonal, v_ref[0, 0].astype(f32), 0.0), axis=1, keepdims=True)  # (D, 1)

    num = jnp.zeros(q.shape, f32)
    den = jnp.zeros(q.shape, f32)
    for t in range(len(coef)):  # a tile: decayed, updated, written, and read for y while it is in registers
        q_t, k_t = _feature(q, t, coef), _feature(k, t, coef)
        tile = slice(t * d, (t + 1) * d)
        s_t = gamma * s_in[0, 0, tile, :] + v_down * k_t
        s_ref[0, 0, tile, :] = s_t
        z_t = gamma * z_in[0, 0, t:t + 1, :] + k_t
        z_ref[0, 0, t:t + 1, :] = z_t
        num = num + _dot(q_t.astype(dt), s_t.astype(dt), _NT)
        den = den + q_t * z_t
    y_ref[0, 0] = num / (jnp.sum(den, axis=1, keepdims=True) + eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _step(q, k, v, gamma, s, z, eps: float):
    from perceiver_io_tpu.ops.flash_attention import _VMEM_LIMIT, _interpret_default

    b, heads, d = q.shape
    kv_heads = k.shape[1]
    group, tiles = heads // kv_heads, feature_tiles(d)
    rows = -(-group // 8) * 8  # a group's queries in whole sublane tiles: rows of zeros read 0 / eps
    q = jnp.pad(q.reshape(b, kv_heads, group, d), ((0, 0), (0, 0), (0, rows - group), (0, 0)))
    head_block = lambda n: pl.BlockSpec((1, 1, n, d), lambda r, g: (r, g, 0, 0))  # noqa: E731
    y, s, z = pl.pallas_call(
        functools.partial(_step_kernel, head_dim=d, kv_heads=kv_heads, eps=eps),
        name=power_ret_step_kernel_name(b, heads, d),
        grid=(b, kv_heads),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), head_block(rows), head_block(1), head_block(1),
                  head_block(tiles * d), head_block(tiles)],
        out_specs=[head_block(rows), head_block(tiles * d), head_block(tiles)],
        out_shape=[jax.ShapeDtypeStruct((b, kv_heads, rows, d), jnp.float32),
                   jax.ShapeDtypeStruct(s.shape, jnp.float32), jax.ShapeDtypeStruct(z.shape, jnp.float32)],
        input_output_aliases={4: 1, 5: 2},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret_default(),
    )(gamma.astype(jnp.float32).reshape(-1), q, k[:, :, None], v[:, :, None], s, z)
    return y[:, :, :group].reshape(b, heads, d), s, z


def power_retention_step(q, k, v, gamma, s, z, eps: float = EPS):
    """:func:`retention_update` as one Pallas call over the state where it lies:
    grid (row, key-value head), the head's ``S`` and ``z`` read once, decayed,
    updated and written back **in place** (``input_output_aliases``), ``y`` read
    off each tile of ``S`` while it is in registers: the decay and the update
    float32 on the vector unit, the numerator a product a tile with ``q``'s dtype
    as operands as :func:`retention_update` takes it (the group's 5 queries
    against a tile of 128 x 128: the matrix unit is all but idle, and so is out
    of the way of the state's bytes, which are the floor). Same arguments and
    results as :func:`retention_update`."""
    return _step(q, k, v, gamma, s, z, eps=eps)


# ---------------------------------------------------- the prompt pass's kernel


def power_ret_kernel_name(length: int, chunk: int, heads: int, head_dim: int) -> str:
    """``power_ret_chunk_l<length>_c<chunk>_h<heads>_d<head_dim>``: what a device trace prints for the call."""
    return f"power_ret_chunk_l{length}_c{chunk}_h{heads}_d{head_dim}"


class RetentionPlan(NamedTuple):
    """How one traced prompt-pass call is cut (a row of :func:`power_retention_plans`)."""

    length: int
    chunk: int
    heads: int
    kv_heads: int
    head_dim: int
    feature_rows: int
    grid_steps: int  # a row: key-value heads x chunks of time
    vmem_bytes: int  # the state's and the streams' double buffers and the widest intermediates of a chunk


_PLANS: dict = {}


def power_retention_plans() -> list:
    """One row per distinct prompt-pass geometry traced so far, for a ``compile`` event row."""
    return [plan._asdict() for _, plan in sorted(_PLANS.items())]


def chunk_of(length: int, chunk: int = CHUNK) -> int:
    """The tokens a grid step takes of a row of ``length``: ``chunk``, or the
    row whole in sublane tiles of the narrowest input where it is shorter (a
    length that is no multiple is padded with tokens that add nothing)."""
    return min(chunk, -(-length // 16) * 16)


def retention_plan(length: int, heads: int, kv_heads: int, head_dim: int, chunk: int = CHUNK, itemsize: int = 2) -> RetentionPlan:
    c, group, rows = chunk_of(length, chunk), heads // kv_heads, feature_rows(head_dim)
    state = 2 * (rows * head_dim + rows) * 4 + head_dim * head_dim * 4
    streams = 2 * c * (2 * group + 4) * head_dim * itemsize + 2 * c * (kv_heads + 1) * 4
    work = 3 * c * c * 4 + 4 * group * c * head_dim * 4
    return RetentionPlan(length, c, heads, kv_heads, head_dim, rows, kv_heads * -(-length // c), state + streams + work)


def power_retention_supported(head_dim: int) -> bool:
    """Whether the kernel lowers for the chip: a head of whole lanes (any even width in interpret mode)."""
    from perceiver_io_tpu.ops.flash_attention import _interpret_default

    return head_dim == LANES or (_interpret_default() and head_dim % 2 == 0)


def _chunk_kernel(lam_row_ref, lam_col_ref, q_ref, k_ref, v_ref, kt_ref, vt_ref, y_ref, s_ref, z_ref, zm_scr, *,
                  group: int, head_dim: int, chunk: int, eps: float):
    g, j = pl.program_id(1), pl.program_id(2)
    d, f32 = head_dim, jnp.float32
    coef = _coefficients(d)

    @pl.when(j == 0)
    def _start():
        s_ref[...] = jnp.zeros_like(s_ref)
        z_ref[...] = jnp.zeros_like(z_ref)
        zm_scr[...] = jnp.zeros_like(zm_scr)

    lam_row = lam_row_ref[0]  # (1, chunk): the local cumulative log-gates, along the lanes
    lam_all = lam_col_ref[0]  # (chunk, Hkv): every head's, along the sublanes; this head's lane is picked by a masked sum
    lam_col = jnp.sum(jnp.where(lax.broadcasted_iota(jnp.int32, lam_all.shape, 1) == g, lam_all, 0.0), axis=1, keepdims=True)
    i_pos = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j_pos = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(j_pos <= i_pos, jnp.exp(jnp.minimum(lam_col - lam_row, 0.0)), 0.0)
    carried = jnp.exp(lam_col)  # (chunk, 1): what the state before the chunk is worth at token i
    total = lam_row[:, chunk - 1:chunk]  # (1, 1)
    to_end = jnp.exp(total - lam_row)  # (1, chunk): what token j is worth at the chunk's end
    whole = jnp.exp(total)  # (1, 1): what the state before the chunk is worth at its end

    k, v = k_ref[0], v_ref[0]
    dt = k.dtype
    zm = zm_scr[...]

    # ---- the group's queries: in-chunk scores a head, the carried part over the heads stacked
    nums, dens, stacked = [], [], []
    for h in range(group):
        q_h = q_ref[0, :, h * d:(h + 1) * d]
        scores = _dot(q_h, k, _NT)
        p = scores * scores * decay
        nums.append(_dot(p.astype(dt), v, _NN))
        dens.append(jnp.sum(p, axis=1, keepdims=True))
        stacked.append(q_h)
    q_all = jnp.concatenate(stacked, axis=0)  # (group * chunk, D)
    q_f = q_all.astype(f32)
    den_carried = jnp.sum(_dot(q_all, zm.astype(dt), _NN) * q_f, axis=1, keepdims=True)
    num_carried = jnp.zeros((group * chunk, d), f32)
    for t in range(len(coef)):
        num_carried = num_carried + _dot(_feature(q_f, t, coef).astype(dt), s_ref[0, 0, t * d:(t + 1) * d, :].astype(dt), _NT)
    for h in range(group):
        rows = slice(h * chunk, (h + 1) * chunk)
        num = nums[h] + carried * num_carried[rows]
        den = dens[h] + carried * den_carried[rows]
        y_ref[0, :, h * d:(h + 1) * d] = (num / (den + eps)).astype(y_ref.dtype)

    # ---- the state after the chunk: S_d and z_d in one product a tile, Z beside them
    vt_w = vt_ref[0, 0].astype(f32) * to_end  # (D, chunk)
    left = jnp.concatenate([vt_w, jnp.broadcast_to(to_end, (_Z_ROWS, chunk))], axis=0).astype(dt)
    kt_w = (kt_ref[0, 0].astype(f32) * to_end).astype(dt)
    zm_scr[...] = whole * zm + _dot(kt_w, k, _NN)
    k_f = k.astype(f32)
    for t in range(len(coef)):
        update = _dot(left, _feature(k_f, t, coef).astype(dt), _NN)  # (D + 8, D)
        tile = slice(t * d, (t + 1) * d)
        s_ref[0, 0, tile, :] = whole * s_ref[0, 0, tile, :] + update[:d]
        z_ref[0, 0, t:t + 1, :] = whole * z_ref[0, 0, t:t + 1, :] + update[d:d + 1]


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "eps"))
def _chunked(q, k, v, log_gamma, heads: int, chunk: int, eps: float):
    from perceiver_io_tpu.ops.flash_attention import _VMEM_LIMIT, _interpret_default  # at call time: tests steer the second

    b, length, width = q.shape
    d = width // heads
    kv_heads = k.shape[-1] // d
    group, tiles = heads // kv_heads, feature_tiles(d)
    plan = _PLANS[(length, chunk, heads, kv_heads, d)] = retention_plan(length, heads, kv_heads, d, chunk, q.dtype.itemsize)
    c = plan.chunk
    n_chunks = -(-length // c)
    pad = n_chunks * c - length

    def padded(t):  # tokens past the row's end: no key, no value, a gate of 1, so they add nothing and forget nothing
        return jnp.pad(t, ((0, 0), (0, pad), (0, 0))) if pad else t

    q, k, v, log_gamma = padded(q), padded(k), padded(v), padded(log_gamma.astype(jnp.float32))
    lam_col = jnp.cumsum(log_gamma.reshape(b, n_chunks, c, kv_heads), axis=2).reshape(b, n_chunks * c, kv_heads)
    lam_row = jnp.swapaxes(lam_col, 1, 2).reshape(b * kv_heads, 1, n_chunks * c)
    heads_major = lambda t: jnp.swapaxes(t.reshape(b, n_chunks * c, kv_heads, d), 1, 2)  # noqa: E731
    kt, vt = (jnp.swapaxes(heads_major(t), 2, 3) for t in (k, v))  # (B, Hkv, D, T): a chunk's keys and values, turned once in XLA

    token_block = lambda w: pl.BlockSpec((1, c, w), lambda r, g, j: (r, j, g))  # noqa: E731
    turned_block = pl.BlockSpec((1, 1, d, c), lambda r, g, j: (r, g, 0, j))
    y, s, z = pl.pallas_call(
        functools.partial(_chunk_kernel, group=group, head_dim=d, chunk=c, eps=eps),
        name=power_ret_kernel_name(length, c, heads, d),
        grid=(b, kv_heads, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, c), lambda r, g, j: (r * kv_heads + g, 0, j)),
            pl.BlockSpec((1, c, kv_heads), lambda r, g, j: (r, j, 0)),
            token_block(group * d), token_block(d), token_block(d), turned_block, turned_block,
        ],
        out_specs=[
            token_block(group * d),
            pl.BlockSpec((1, 1, tiles * d, d), lambda r, g, j: (r, g, 0, 0)),
            pl.BlockSpec((1, 1, tiles, d), lambda r, g, j: (r, g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n_chunks * c, width), q.dtype),
            jax.ShapeDtypeStruct((b, kv_heads, tiles * d, d), jnp.float32),
            jax.ShapeDtypeStruct((b, kv_heads, tiles, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret_default(),
    )(lam_row, lam_col, q, k, v, kt, vt)
    return y[:, :length], s, z


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def power_retention(q, k, v, log_gamma, heads: int, chunk: int = CHUNK, eps: float = EPS):
    """The chunked form over whole rows from an empty state.

    ``q`` (B, T, H * D) and ``k``, ``v`` (B, T, Hkv * D), heads side by side as
    their projections write them (query head ``i`` reads key-value head ``i //
    group``); ``log_gamma`` (B, T, Hkv), the log of each token's gate. Returns
    ``y`` (B, T, H * D) in ``q``'s dtype, normalised, and the rows' final state
    ``s`` (B, Hkv, R, D) and ``z`` (B, Hkv, R / D, D), float32. ``D`` is 128 on the
    chip (:func:`power_retention_supported`); ``chunk`` is cut to a shorter row."""
    return _chunked(q, k, v, log_gamma, heads=heads, chunk=chunk, eps=eps)


def _no_backward(*_):
    raise NotImplementedError(
        "power_retention is forward only (the prompt pass of a served decoder): no backward kernel is written"
    )


power_retention.defvjp(_no_backward, _no_backward)
