"""The prompt pass of differential attention (arXiv:2410.05258; ``core/diff_attention.py``)
as one flash forward: two softmax maps a query pair over one value tile, ending
in their difference and the pair's RMSNorm. Forward only.

A query pair ``p`` is two heads of ``d`` channels, ``[q_a | q_b]`` on ``2d``
lanes; its key pair ``g = p // group`` lies the same way, ``[k_a | k_b]``, and
its values are one head of ``2d``. The two score maps are ``q_a . k_a`` and
``q_b . k_b``: the kernel takes the pair's one lane block of queries and one of
keys and zeroes the other half of the query for each map (``[q_a | 0] . [k_a |
k_b] = q_a . k_a``: exact, and a contraction of ``2d`` = 128 costs the MXU what
one of 64 does), so nothing is sliced to half a lane block, and keys, values and
the output stay in the layouts the caches and the projections keep. Each map has
its own running maximum, sum and accumulator over the one value tile, which is
read once for both. The last grid step ends in::

    o = acc_a / l_a - lam * acc_b / l_b            float32
    out = o * rsqrt(mean(o^2) + eps) * gain        gain = (1 - lam0) * the subnorm's scale

The grid, the index maps and the static bands a tile shows (a sliding window,
or every earlier block without one) are ``ops/flash_attention.py``'s grouped-query
forward's, as are its tile plans (``flash_diff_fwd_q<n>_kv<n>[_w<window>]``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from perceiver_io_tpu.ops.flash_attention import (
    LANES, MASK_VALUE, _TILE_PLANS, _choose_block, _compiler_params, _dot, _geometry, _gqa_steps, _gqa_tiles,
    _kernel_name, _make_window_plan, _pad_to, _round_up,
)

_ROWS = 8  # the sublanes of a float32 tile: how a scalar and a vector of the pair's width reach the kernel


def _fwd_diff_kernel(q_ref, k_ref, v_ref, lam_ref, gain_ref, o_ref, *scratch, sm_scale: float, window: int, tiles: tuple,
                     eps: float):
    # q, k, v, o (1, block, 2d); lam, gain (8, 2d) f32, every row the same; scratch, a map: m/l (block, LANES), acc (block, 2d) f32
    maps = (scratch[:3], scratch[3:])
    iq, s = pl.program_id(2), pl.program_id(3)
    block, width = o_ref.shape[1], o_ref.shape[2]

    @pl.when(s == 0)
    def _init():
        for m_scr, l_scr, acc_scr in maps:
            m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

    def _band(r0, r1, c0, c1, masked):
        q, k, v = q_ref[0, r0:r1, :], k_ref[0, c0:c1, :], v_ref[0, c0:c1, :]
        first = lax.broadcasted_iota(jnp.int32, q.shape, 1) < width // 2
        if masked:
            rows = lax.broadcasted_iota(jnp.int32, (r1 - r0, c1 - c0), 0) + (r0 + s * block)
            cols = lax.broadcasted_iota(jnp.int32, (r1 - r0, c1 - c0), 1) + c0
            visible = (cols <= rows) & (cols > rows - window)
        for half, (m_scr, l_scr, acc_scr) in zip((first, ~first), maps):
            scores = _dot(jnp.where(half, q, jnp.zeros_like(q)), k, ((1,), (1,))) * sm_scale
            if masked:  # the diagonal tile runs first: a row hidden whole by a later tile already has a finite maximum
                scores = jnp.where(visible, scores, MASK_VALUE)
            m_prev, l_prev = m_scr[r0:r1], l_scr[r0:r1]
            m_next = jnp.maximum(m_prev, jnp.max(scores, axis=1)[:, None])
            p = jnp.exp(scores - m_next[:, :1])
            alpha = jnp.exp(m_prev - m_next)
            l_scr[r0:r1] = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
            m_scr[r0:r1] = m_next
            acc_scr[r0:r1] = acc_scr[r0:r1] * alpha[:, :1] + _dot(p.astype(v.dtype), v, ((1,), (0,)))

    for lo, hi, bands in tiles:
        @pl.when((s >= lo) & (s <= hi) & (iq >= s))
        def _tile(bands=bands):
            for b in bands:
                _band(*b)

    @pl.when(s == pl.num_programs(3) - 1)
    def _store():
        (_, l_a, acc_a), (_, l_b, acc_b) = maps
        o = acc_a[...] / l_a[...][:, :1] - lam_ref[0:1, :] * (acc_b[...] / l_b[...][:, :1])
        o = o * lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + eps)
        o_ref[0] = (o * gain_ref[0:1, :]).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_diff(q, k, v, lam, gain, num_pairs, sm_scale, eps, block, window, geom):
    from perceiver_io_tpu.ops.flash_attention import _interpret_default  # at call time: tests steer it

    b, n, _ = q.shape
    width = k.shape[2]
    kv_pairs = k.shape[0] // b
    group = num_pairs // kv_pairs
    n_blocks = n // block

    def q_map(b_, p, i, s):
        return (b_, i, p)

    def kv_map(b_, p, i, s):
        return (b_ * kv_pairs + p // group, jnp.maximum(i - s, 0), 0)

    row = pl.BlockSpec((_ROWS, width), lambda b_, p, i, s: (0, 0))
    a_map = [pltpu.VMEM((block, LANES), jnp.float32), pltpu.VMEM((block, LANES), jnp.float32),
             pltpu.VMEM((block, width), jnp.float32)]
    return pl.pallas_call(
        functools.partial(_fwd_diff_kernel, sm_scale=sm_scale, window=window, tiles=_gqa_tiles(n_blocks, block, window), eps=eps),
        name=_kernel_name("diff_fwd", geom),
        grid=(b, num_pairs, n_blocks, _gqa_steps(n_blocks, block, window)),
        in_specs=[pl.BlockSpec((1, block, width), q_map), pl.BlockSpec((1, block, width), kv_map),
                  pl.BlockSpec((1, block, width), kv_map), row, row],
        out_specs=pl.BlockSpec((1, block, width), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=a_map + a_map,
        compiler_params=_compiler_params("parallel", "parallel", "parallel", "arbitrary"),
        interpret=_interpret_default(),
    )(q, k, v, lam, gain)


def _flash_diff_no_backward(*_):
    raise NotImplementedError(
        "flash_attention_diff is forward only (the prompt pass of a served decoder): no backward kernel is written"
    )


_flash_diff.defvjp(_flash_diff_no_backward, _flash_diff_no_backward)


def diff_flash_supported(n: int, pair_width: int) -> bool:
    """A pair's columns are one block of the projection layout: whole lanes on
    the chip (any even width in interpret mode), and rows worth a kernel."""
    from perceiver_io_tpu.ops.flash_attention import _interpret_default

    return n >= LANES and (pair_width % LANES == 0 or _interpret_default())


@jax.named_scope("flash_attention_diff")
def flash_attention_diff(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    lam: jnp.ndarray,
    gain: jnp.ndarray,
    num_pairs: int,
    window: Optional[int] = None,
    sm_scale: float = 1.0,
    eps: float = 1e-5,
    block: Optional[int] = None,
) -> jnp.ndarray:
    """Causal differential self-attention, grouped query pairs, an optional sliding window.

    :param q: query pairs (B, N, P * 2d), pair ``p``'s two heads side by side.
    :param k: key pairs (B, G, N, 2d), laid the same way; ``G`` divides ``P``
        and query pair p reads key-value pair ``p // (P // G)``.
    :param v: values (B, G, N, 2d), one head of ``2d`` a pair.
    :param lam: the layer's scalar on the second map, float32.
    :param gain: (2d,) float32, what multiplies the normed difference.
    :param window: position i sees ``i - window < j <= i`` (None: ``j <= i``).
    :returns: (B, N, P * 2d) in q's dtype: ``RMSNorm(A_a V - lam A_b V) * gain`` a pair.
    """
    b, n, _ = q.shape
    kv_pairs, width = k.shape[1], k.shape[3]
    if num_pairs % kv_pairs or q.shape[2] != num_pairs * width or k.shape[2] != n or width % 2:
        raise ValueError(f"flash_attention_diff: q {q.shape}, k {k.shape}, {num_pairs} pairs do not fit")
    block = _choose_block(n, 1024 if block is None else block, exact=block is not None)
    geom = _geometry(n, n) + ("" if window is None else f"_w{window}")
    reach = _round_up(n, block) if window is None else window  # without a window every earlier block is seen
    _TILE_PLANS[("diff_" + geom, True, False)] = _make_window_plan(n, block, reach)
    # padded kv slots lie after every real query: the causal mask hides them
    qf = _pad_to(q, 1, block)
    kf = _pad_to(k.reshape(b * kv_pairs, n, width), 1, block)
    vf = _pad_to(v.reshape(b * kv_pairs, n, width), 1, block)
    rows = lambda x: jnp.broadcast_to(x.astype(jnp.float32), (_ROWS, width))  # noqa: E731
    return _flash_diff(qf, kf, vf, rows(lam), rows(gain), num_pairs, float(sm_scale), float(eps), block, reach, geom)[:, :n]
