"""The prompt pass of latent attention that chooses its keys (``core/dsa.py``), as kernels.

Four calls, all forward only (the prompt pass of a served decoder), all in
interpret mode where the flash kernels are (``fa.default_flash(True)`` on the
CPU):

``index_scores`` (``dsa_index_scores_q<Q>_kv<N>_h<J>``)
    ``I = sum_j w_j relu(q_j . k)`` for a chunk of queries against a row's
    keys: a head's product on the matrix unit, the relu, the weight and the
    head sum on the tile it leaves, float32, so that the ``J`` score planes are
    never written out (in XLA they are: 64 x 4 bytes a pair, 0.55 TB a
    32 768-token row). Keys after a query are ``-inf``; a key block wholly after
    the chunk's last query is neither fetched nor multiplied (the chunk's first
    position is a prefetched scalar).

``select_mask`` (``dsa_select_q<Q>_kv<N>_k<topk>``)
    the exact top-k of each query's scores as an int8 mask: the bisection of
    ``core.dsa.topk_mask`` (32 counts over the bits of the scores' integer
    image, then the tie's position: :func:`largest`, the one function both run)
    on rows that stay in VMEM, where XLA reads the scores from HBM once a count.

``flash_attention_mla_masked`` (``flash_mla_masked_fwd_q<N>_kv<N>_h<H>``)
    ``flash_attention_mla``'s forward (token-major operands as the
    up-projections write them, two score products a head) with the selection's
    mask tile in the causal mask's place: a (block, block) int8 tile a grid step.

``flash_attention_mla_window`` (``flash_mla_window_fwd_q<N>_kv<N>_h<H>_w<window>``)
    the window kernel's body (``flash_attention_gqa``'s, two score products)
    for a latent attention of 192 + 64 query-key channels: a head's 256 channels
    are two lane blocks, ``[nope 0..127]`` and ``[nope 128..191 | rope]``.
"""

from __future__ import annotations

import functools
import importlib
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the module, not the function of its name that the package exports
fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")

LANES = fa.LANES
MASK_VALUE = fa.MASK_VALUE

SCORE_BLOCK_Q = 512
SCORE_BLOCK_KV = 1024
SELECT_ROWS = 32  # a sublane tile of int8
_BAND = 256  # rows of a score tile worked at once, as the flash kernels cut theirs


def _block(n: int, want: int) -> int:
    """The largest divisor of ``n`` that is a multiple of ``LANES`` and at most ``want`` (0 where there is none)."""
    return max((d for d in range(LANES, min(n, want) + 1, LANES) if n % d == 0), default=0)


# ------------------------------------------------------------------ the indexer's scores


def index_scores_kernel_name(q: int, n: int, heads: int) -> str:
    return f"dsa_index_scores_q{q}_kv{n}_h{heads}"


def select_kernel_name(q: int, n: int, k: int) -> str:
    return f"dsa_select_q{q}_kv{n}_k{k}"


def selection_supported(n: int, head_dim: int) -> bool:
    """Rows in whole blocks of lanes and an index head of whole lanes (any width in interpret mode)."""
    return n % LANES == 0 and n >= LANES and (head_dim % LANES == 0 or fa._interpret_default())


def _index_scores_kernel(first_ref, q_ref, k_ref, w_ref, o_ref, *, heads: int, head_dim: int):
    i, j = pl.program_id(1), pl.program_id(2)
    bq, bk = o_ref.shape[1], o_ref.shape[2]
    q_first = first_ref[0] + i * bq  # the position of the block's first query
    k_first = j * bk

    @pl.when(k_first <= q_first + bq - 1)
    def _visible():
        k = k_ref[0]
        w = w_ref[0]
        acc = jnp.zeros((bq, bk), jnp.float32)
        for h in range(heads):
            s = fa._dot(q_ref[0, :, h * head_dim:(h + 1) * head_dim], k, ((1,), (1,)))
            acc = acc + w[:, h:h + 1] * jnp.maximum(s, 0.0)
        rows = lax.broadcasted_iota(jnp.int32, acc.shape, 0) + q_first
        cols = lax.broadcasted_iota(jnp.int32, acc.shape, 1) + k_first
        o_ref[0] = jnp.where(cols <= rows, acc, -jnp.inf)

    @pl.when(k_first > q_first + bq - 1)
    def _hidden():
        o_ref[0] = jnp.full((bq, bk), -jnp.inf, jnp.float32)


@functools.partial(jax.jit, static_argnames=("heads",))
def index_scores(q, k, w, heads: int, first):
    """``q`` (B, Q, J * D) the indexer's rotated queries of the positions ``first .. first + Q - 1``, ``k`` (B, N, D)
    the row's rotated index keys, ``w`` (B, Q, J) float32 -> ``I`` (B, Q, N) float32, ``-inf`` at every key after its query."""
    b, n_q, width = q.shape
    n, d = k.shape[1], k.shape[2]
    bq, bk = _block(n_q, SCORE_BLOCK_Q), _block(n, SCORE_BLOCK_KV)

    def k_map(b_, i, j, first_):  # a block wholly after the chunk's queries is not fetched: the last visible one stays
        return (b_, jnp.minimum(j, (first_[0] + (i + 1) * bq - 1) // bk), 0)

    return pl.pallas_call(
        functools.partial(_index_scores_kernel, heads=heads, head_dim=d),
        name=index_scores_kernel_name(n_q, n, heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_q // bq, n // bk),
            in_specs=[
                pl.BlockSpec((1, bq, width), lambda b_, i, j, f: (b_, i, 0)),
                pl.BlockSpec((1, bk, d), k_map),
                pl.BlockSpec((1, bq, heads), lambda b_, i, j, f: (b_, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, bk), lambda b_, i, j, f: (b_, i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_q, n), jnp.float32),
        compiler_params=fa._compiler_params("parallel", "parallel", "arbitrary"),
        interpret=fa._interpret_default(),
    )(jnp.reshape(first, (1,)).astype(jnp.int32), q, k.astype(q.dtype), w.astype(jnp.float32))


# ------------------------------------------------------------------ the selection


def sortable(bits):
    """The int32 bit patterns of float32 scores -> int32 of the scores' total order (``-inf`` below every finite score;
    ``-0.0`` below ``0.0``, as a sort has them)."""
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def largest(key, k: int):
    """The ``k`` largest of each row of ``key`` (..., S) int32 as a bool mask, exactly and without a sort: the threshold
    is the largest value that ``k`` keys reach, found by bisection over the 32 bits (32 counts a row), and keys equal to
    it are taken from the lowest position up until the row has ``k``, that position found by a bisection too. Plain
    ``jnp``: XLA runs it on scores in HBM (``core.dsa.topk_mask``), the selection kernel on rows in VMEM."""
    def reached(cand):  # how many keys of a row are at or above ``cand`` (..., 1)
        return jnp.sum((key >= cand).astype(jnp.int32), axis=-1, keepdims=True)

    floor = jnp.full(key.shape[:-1] + (1,), jnp.iinfo(jnp.int32).min, jnp.int32)
    threshold = jnp.where(reached(jnp.zeros_like(floor)) >= k, 0, floor)

    def value_bit(i, t):
        cand = t | (jnp.int32(1) << (30 - i))
        return jnp.where(reached(cand) >= k, cand, t)

    threshold = lax.fori_loop(0, 31, value_bit, threshold)
    above = key > threshold
    tied = key == threshold
    need = k - jnp.sum(above.astype(jnp.int32), axis=-1, keepdims=True)
    at = lax.broadcasted_iota(jnp.int32, key.shape, key.ndim - 1)
    bits = max(key.shape[-1] - 1, 1).bit_length()

    def index_bit(i, last):  # the largest ``last`` with fewer than ``need`` tied keys before it
        cand = last | (jnp.int32(1) << (bits - 1 - i))
        before = jnp.sum((tied & (at < cand)).astype(jnp.int32), axis=-1, keepdims=True)
        return jnp.where(before < need, cand, last)

    last = lax.fori_loop(0, bits, index_bit, jnp.zeros_like(floor))
    return above | (tied & (at <= last))


def _select_kernel(first_ref, s_ref, mask_ref, o_ref, *, k: int):
    del first_ref, mask_ref  # the scalar is the index maps'; the mask's other rows stay as they are
    scores = s_ref[0]  # (rows, N)
    chosen = largest(sortable(pltpu.bitcast(scores, jnp.int32)), k) & (scores > -jnp.inf)
    o_ref[0] = chosen.astype(jnp.int32).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("k",))
def select_mask(scores, k: int):
    """``scores`` (B, Q, N) float32, hidden slots ``-inf`` -> int8 (B, Q, N), 1 at each row's ``k`` largest (every finite
    one where there are fewer), a tie at the threshold to the lower positions: ``core.dsa.topk_mask``'s set."""
    b, n_q, n = scores.shape
    return select_mask_into(jnp.zeros((b, n_q, n), jnp.int8), scores, k, 0)


@functools.partial(jax.jit, static_argnames=("k",), donate_argnums=(0,))
def select_mask_into(mask, scores, k: int, first):
    """:func:`select_mask` of ``scores`` (B, Q, N) written over the rows ``first .. first + Q - 1`` of ``mask`` (B, M, N)
    int8, which comes back (the same buffer where the caller lets go of it): a chunk of queries' selection goes
    straight into the row's mask, with no copy of the chunk in between. ``first`` is a multiple of the row block."""
    b, n_q, n = scores.shape
    rows = SELECT_ROWS if n_q % SELECT_ROWS == 0 and mask.shape[1] % SELECT_ROWS == 0 else n_q
    return pl.pallas_call(
        functools.partial(_select_kernel, k=k),
        name=select_kernel_name(n_q, n, k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_q // rows),
            in_specs=[pl.BlockSpec((1, rows, n), lambda b_, i, f: (b_, i, 0)), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, rows, n), lambda b_, i, f: (b_, f[0] // rows + i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(mask.shape, jnp.int8),
        input_output_aliases={2: 0},  # operands count the prefetched scalar: ``mask`` is the buffer that comes back
        compiler_params=fa._compiler_params("parallel", "parallel"),
        interpret=fa._interpret_default(),
    )(jnp.reshape(first, (1,)).astype(jnp.int32), scores, mask)


# ------------------------------------------------------------------ the attention under the mask


def masked_flash_kernel_name(n: int, heads: int) -> str:
    return f"flash_mla_masked_fwd_q{n}_kv{n}_h{heads}"


def masked_flash_supported(n: int, num_heads: int, nope: int, rope: int, v_dim: int) -> bool:
    """``flash_attention_mla``'s shapes: the published head widths, heads in pairs, rows in whole blocks."""
    return fa.mla_flash_supported(n, num_heads, nope, rope, v_dim)


def _fwd_masked_kernel(q_nope_ref, q_rope_ref, k_nope_ref, k_rope_ref, v_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr, *, sm_scale: float):
    # q / k (1, block, LANES) twice, a score the sum of the two products; v, o (1, block, LANES); mask (1, block, block)
    # int8; scratch m / l (block, LANES) f32, acc (block, LANES) f32. Grid step ``s`` of q block ``iq`` takes kv block ``iq - s``.
    iq, s = pl.program_id(2), pl.program_id(3)
    block = o_ref.shape[1]

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(iq >= s)
    def _tile():
        for r0 in range(0, block, _BAND):
            r1 = min(r0 + _BAND, block)
            scores = (fa._dot(q_nope_ref[0, r0:r1, :], k_nope_ref[0], ((1,), (1,)))
                      + fa._dot(q_rope_ref[0, r0:r1, :], k_rope_ref[0], ((1,), (1,)))) * sm_scale
            # a row whose tile keeps nothing holds MASK_VALUE, finite: what it adds is wiped by the first tile that
            # keeps a key (alpha = exp(MASK_VALUE - m) = 0), and every query keeps one
            scores = jnp.where(mask_ref[0, r0:r1, :].astype(jnp.int32) != 0, scores, MASK_VALUE)
            m_prev, l_prev = m_scr[r0:r1], l_scr[r0:r1]
            m_next = jnp.maximum(m_prev, jnp.max(scores, axis=1)[:, None])
            p = jnp.exp(scores - m_next[:, :1])
            alpha = jnp.exp(m_prev - m_next)
            l_scr[r0:r1] = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
            m_scr[r0:r1] = m_next
            v = v_ref[0]
            acc_scr[r0:r1] = acc_scr[r0:r1] * alpha[:, :1] + fa._dot(p.astype(v.dtype), v, ((1,), (0,)))

    @pl.when(s == pl.num_programs(3) - 1)
    def _store():
        o_ref[0] = (acc_scr[...] / l_scr[...][:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_heads", "sm_scale", "block"))
def _flash_mla_masked(q_nope, q_rope, kv, k_rope, mask, num_heads: int, sm_scale: float, block: int):
    b, n, _ = q_nope.shape
    n_blocks = n // block

    def q_block(of):
        return pl.BlockSpec((1, block, LANES), lambda b_, h, i, s: (b_, i, of(h)))

    def kv_block(of):
        return pl.BlockSpec((1, block, LANES), lambda b_, h, i, s: (b_, jnp.maximum(i - s, 0), of(h)))

    return pl.pallas_call(
        functools.partial(_fwd_masked_kernel, sm_scale=sm_scale),
        name=masked_flash_kernel_name(n, num_heads),
        grid=(b, num_heads, n_blocks, n_blocks),
        in_specs=[
            q_block(lambda h: h), q_block(lambda h: h // 2),
            kv_block(lambda h: 2 * h), kv_block(lambda h: h % 2), kv_block(lambda h: 2 * h + 1),
            pl.BlockSpec((1, block, block), lambda b_, h, i, s: (b_, i, jnp.maximum(i - s, 0))),
        ],
        out_specs=q_block(lambda h: h),
        out_shape=jax.ShapeDtypeStruct(q_nope.shape, q_nope.dtype),
        scratch_shapes=[
            pltpu.VMEM((block, LANES), jnp.float32),
            pltpu.VMEM((block, LANES), jnp.float32),
            pltpu.VMEM((block, LANES), jnp.float32),
        ],
        compiler_params=fa._compiler_params("parallel", "parallel", "parallel", "arbitrary"),
        interpret=fa._interpret_default(),
    )(q_nope, q_rope, kv, k_rope, kv, mask)


@jax.named_scope("flash_attention_mla_masked")
def flash_attention_mla_masked(q_nope, q_rope, kv, k_rope, mask, num_heads: int, sm_scale: float = 1.0, block: Optional[int] = None):
    """Self-attention of expanded latent attention under a mask, token-major (``flash_attention_mla``'s operands).

    :param q_nope: (B, N, H*128). :param q_rope: (B, N, H*64), already rotated.
    :param kv: (B, N, H*256), a head's ``k_nope`` then its ``v``. :param k_rope: (B, N, 64), rotated.
    :param mask: (B, N, N) int8, not 0 where query ``i`` sees key ``j``; nothing after the query is seen, and every
        query sees a key.
    :returns: (B, N, H*128) in ``q_nope``'s dtype. Forward only.
    """
    b, n, _ = q_nope.shape
    if mask.shape != (b, n, n) or kv.shape != (b, n, 2 * num_heads * LANES) or num_heads % 2:
        raise ValueError(f"flash_attention_mla_masked: q_nope {q_nope.shape}, kv {kv.shape}, mask {mask.shape}, {num_heads} heads do not fit")
    block = fa._choose_block(n, 1024 if block is None else block, exact=block is not None)
    zeros = jnp.zeros_like(k_rope)
    # lane block 0 for the even heads, block 1 for the odd ones
    k_rope = jnp.concatenate([k_rope, zeros, zeros, k_rope], axis=-1).astype(kv.dtype)
    return _flash_mla_masked(q_nope, q_rope.astype(q_nope.dtype), kv, k_rope, mask, num_heads, sm_scale, block)


# ------------------------------------------------------------------ the attention behind a window


def window_flash_kernel_name(n: int, heads: int, window: int) -> str:
    return f"flash_mla_window_fwd_q{n}_kv{n}_h{heads}_w{window}"


def window_flash_supported(n: int, nope: int, rope: int, v_dim: int) -> bool:
    """A head's query-key channels are two lane blocks and its values one; rows in whole blocks."""
    return nope + rope == 2 * LANES and rope <= LANES and v_dim == LANES and n >= LANES and n % fa._choose_block(n, 1024) == 0


@functools.partial(jax.jit, static_argnames=("num_heads", "window", "sm_scale", "block"))
def _flash_mla_window(q, k_low, k_high, v, num_heads: int, window: int, sm_scale: float, block: int):
    b, n, _ = q.shape
    n_blocks = n // block
    steps = fa._gqa_steps(n_blocks, block, window)

    def kv_block():
        return pl.BlockSpec((1, block, LANES), lambda b_, h, i, s: (b_, jnp.maximum(i - s, 0), h))

    return pl.pallas_call(
        functools.partial(fa._fwd_gqa_kernel, sm_scale=sm_scale, window=window, tiles=fa._gqa_tiles(n_blocks, block, window), pairs=2),
        name=window_flash_kernel_name(n, num_heads, window),
        grid=(b, num_heads, n_blocks, steps),
        in_specs=[
            pl.BlockSpec((1, block, LANES), lambda b_, h, i, s: (b_, i, 2 * h)),
            pl.BlockSpec((1, block, LANES), lambda b_, h, i, s: (b_, i, 2 * h + 1)),
            kv_block(), kv_block(), kv_block(),
        ],
        out_specs=pl.BlockSpec((1, block, LANES), lambda b_, h, i, s: (b_, i, h)),
        out_shape=jax.ShapeDtypeStruct(v.shape, v.dtype),
        scratch_shapes=[
            pltpu.VMEM((block, LANES), jnp.float32),
            pltpu.VMEM((block, LANES), jnp.float32),
            pltpu.VMEM((block, LANES), jnp.float32),
        ],
        compiler_params=fa._compiler_params("parallel", "parallel", "parallel", "arbitrary"),
        interpret=fa._interpret_default(),
    )(q, q, k_low, k_high, v)


@jax.named_scope("flash_attention_mla_window")
def flash_attention_mla_window(q, k_low, k_high, v, num_heads: int, window: int, sm_scale: float = 1.0, block: Optional[int] = None):
    """Causal self-attention behind a window, position ``i`` sees ``i - window < j <= i``, of an expanded latent
    attention whose head has 256 query-key channels, token-major.

    :param q: (B, N, H*256), a head's ``[nope | rope]``, the rotary part already rotated.
    :param k_low: (B, N, H*128), a head's first 128 ``k_nope`` channels.
    :param k_high: (B, N, H*128), a head's remaining ``k_nope`` channels, then the token's rotated ``k_rope``.
    :param v: (B, N, H*128).
    :returns: (B, N, H*128) in ``v``'s dtype. Forward only.
    """
    b, n, _ = v.shape
    shapes = (b, n, 2 * num_heads * LANES), (b, n, num_heads * LANES)
    if (q.shape, k_low.shape, k_high.shape, v.shape) != (shapes[0], shapes[1], shapes[1], shapes[1]):
        raise ValueError(f"flash_attention_mla_window: q {q.shape}, k {k_low.shape} {k_high.shape}, v {v.shape}, {num_heads} heads do not fit")
    block = fa._choose_block(n, 1024 if block is None else block, exact=block is not None)
    return _flash_mla_window(q, k_low.astype(q.dtype), k_high.astype(q.dtype), v.astype(q.dtype), num_heads, window, sm_scale, block)
