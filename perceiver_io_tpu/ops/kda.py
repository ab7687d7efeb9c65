"""Kimi delta attention (arXiv:2510.26692, the Kimi Linear layer) of a prompt
pass as one Pallas kernel, the one-token update as a second, and the plain
forms every path shares.

For a head with keys and values of ``D`` channels, token ``t``, a log-decay
**a key channel** ``g_t`` (at most 0) and a step ``b_t`` in (0, 1)::

    S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T         S: D_k x D_v, float32
    o_t = S_t^T q_t

the delta rule: the state first forgets (a decay a key channel), then the value
it would predict for ``k_t`` is taken out and ``v_t`` put in, at rate ``b_t``.
Written with the pseudo-value ``u_t = b_t (v_t - (Diag(exp(g_t)) S_{t-1})^T
k_t)`` the update is ``S_t = Diag(exp(g_t)) S_{t-1} + k_t u_t^T``, a rank-one
sum as in plain linear attention.

**The state's layout.** ``(B, H, D_v, D_k)`` float32: a head's ``S`` **stored
transposed**, the value's channel on the rows and the key's channel on the
lanes. The decay is then a multiply along the lanes by a row, ``S^T k`` and
``S^T q`` contract the lanes of both operands (as ``q k^T`` does), and the
rank-one update is a column ``u`` times a row ``k``: neither kernel turns a
tile.

**The chunked form** (the prompt pass's kernel). For a chunk of ``C`` tokens
after ``t0``, ``G_i = sum_{t0 < l <= i} g_l`` (a vector a token) and the state
``S_0`` before it::

    A_jm = sum_c k_j[c] k_m[c] exp(G_j[c] - G_m[c])       m < j      (what key j sees of key m's write)
    B_ij = sum_c q_i[c] k_m[c] exp(G_i[c] - G_m[c])       m <= i
    (I + Diag(b) A) U = Diag(b) (V - (K * exp(G)) S_0)     the triangular solve (the UT transform)
    O = (Q * exp(G)) S_0 + B U
    S_C = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

The decays cannot be factored out of ``k_j . k_m`` over a whole chunk:
``exp(-G_m)`` overflows float32 after 18 tokens at a log-decay of -5. They are
factored **a sub-chunk of rows**: with ``r`` the first token of row ``j``'s
sub-chunk of ``SUB`` (16) tokens, ``A_jm = (k_j * exp(G_j - G_r)) . (k_m *
exp(G_r - G_m))``; the left factor is at most 1, the right one at most 1 for
keys before the sub-chunk and at most ``exp(15 * 5)`` inside it, which float32
and bfloat16 both hold (the bound on the log-decay is what the configuration's
``kda_lower_bound`` is for; :func:`sub_chunk_safe`). Exponents of keys after
the row's sub-chunk are cut at ``_CAP`` and their products masked. The solve is
``(I + N)^-1 = (I - N)(I + N^2)(I + N^4) ...`` (``N`` strictly lower
triangular, so ``N^C = 0``): ``log2(C)`` products on the matrix unit (a factor
and the next power share their right operand and ride one product) and no loop
over rows.

Grid (row, block of heads, chunk of time), time innermost and sequential; the
heads' states are the output block itself, resident in VMEM across a row's
chunks and written to HBM once, as the row's final state. The running sum of
the log-decays within a chunk is XLA's, a product with a triangle of ones in
front of the kernel (one pass over the gates).

**Where q, k and v are shaped.** The layer's ``q = l2norm(silu(conv(x W_q))) *
D^-0.5``, ``k = l2norm(silu(conv(x W_k)))`` and ``v = silu(conv(x W_v))``
(``core/kda.py``) hold no product, and XLA's fusions spent five times their
bytes on them in front of this kernel (the l2 norm's sum over a head's 128
lanes between relayouts). A call that carries the three tap tables
(``kda_chunked(..., taps=)``, what the mixer's prompt pass makes) therefore
hands the kernel the projections' raw outputs, and each head's ``(chunk, D)``
tile is shaped where it already lies, before the recurrence reads it
(:func:`_shaped`, :func:`_convolved`): the ``K``-tap causal convolution in float32 (the tile turned
along its sublanes, its first ``K - 1`` rows taken from the previous chunk's
last raw rows, which a VMEM scratch carries from a grid step to the next and
``j == 0`` zeroes: zeros before a row's first token), silu, the l2 norm as a
reduction along the tile's own lanes (a head is one lane tile), q's scale, and
**one rounding to the operand dtype**, as XLA's form rounds. A call without
tables is the recurrence alone on shaped inputs (the tests' entry, against
:func:`kda_reference`). The one-token step, and every path where the kernels
may not run, keep XLA's form (``core/kda.py::KimiDeltaAttention._shape``).

**The step** (the second kernel). Grid (row,): a row's heads' states come in
and go out through the same HBM array (``input_output_aliases``), each decayed,
read for the prediction, corrected and read for ``o`` while it is in registers.
XLA's form of the same update is :func:`kda_update`.

Products take the inputs' dtype as operands (bfloat16 on the chip) and
accumulate in float32; the state, the decays and the solve's sums are float32.
Forward only (the prompt pass of a served decoder): differentiation raises.
Interpret mode off the TPU, for the tests.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from perceiver_io_tpu.ops.flash_attention import _dot  # a matrix-unit product accumulating in float32; float32 operands at full precision

LANES = 128
CHUNK = 128  # tokens a grid step: 2.85 ms for two rows of 2048 against 3.27 at 64 and 2.94 at 256 (``tools/kda_ab.py`` on a v5e, PERF.md 6, PR 49)
SUB = 16  # rows that share one reference for their decays (8: 2.98 ms)
HEADS_BLOCK = 4  # heads a grid step of the prompt pass: 3.00, 2.93, 2.85, 2.82 ms at 1, 2, 4, 8 (their chains of products hardly interleave)
_CAP = 80.0  # the largest exponent a factor may take: exp(80) times 128 unit products stays inside float32
L2_EPS = 1e-6  # under the root of q's and k's l2 norms
_TAIL = 8  # raw rows of q, k and v carried from a chunk to the next for the convolution's taps: one float32 sublane tile

_NN = ((1,), (0,))
_NT = ((1,), (1,))
_TN = ((0,), (0,))


def sub_chunk_safe(lower_bound: float, sub: int = SUB) -> bool:
    """Whether decays no smaller than ``exp(lower_bound)`` a token can be
    factored over ``sub`` rows in float32: the largest factor is ``exp((sub -
    1) * |lower_bound|)``."""
    return (sub - 1) * abs(lower_bound) <= _CAP


def kda_update(q, k, v, g, beta, s):
    """The recurrence's one token in XLA: ``q``, ``k`` (B, H, D_k), ``v`` (B,
    H, D_v), ``g`` (B, H, D_k) float32 log-decays, ``beta`` (B, H) float32 and
    the state ``s`` (B, H, D_v, D_k) float32. Returns ``o`` (B, H, D_v) float32
    and the state after the token. The decayed state is read once for ``k``
    and ``q`` (operands of ``q``'s dtype, the state rounded to it for the
    product), and ``o = S'^T q + (q . k) u`` follows from the rank-one update
    without a second read; decay, correction and update are float32."""
    f32 = jnp.float32
    dt = q.dtype
    s = s * jnp.exp(g.astype(f32))[:, :, None, :]
    reads = jnp.einsum("bhvc,bhnc->bhnv", s.astype(dt), jnp.stack([k, q], axis=2), preferred_element_type=f32)
    u = beta.astype(f32)[..., None] * (v.astype(f32) - reads[:, :, 0])
    qk = jnp.sum(q.astype(f32) * k.astype(f32), axis=-1, keepdims=True)
    return reads[:, :, 1] + qk * u, s + u[..., :, None] * k.astype(f32)[..., None, :]


def kda_reference(q, k, v, g, beta, state=None):
    """The recurrence as a ``lax.scan`` of a token a step in plain XLA: what the
    mixer runs where the kernel may not (the CPU with the kernels off) and what
    the tests hold the kernel to. ``q``, ``k`` (B, T, H, D_k), ``v`` (B, T, H,
    D_v), ``g`` (B, T, H, D_k) and ``beta`` (B, T, H) float32, ``state`` (B, H,
    D_v, D_k) or ``None`` for an empty one. Returns ``o`` (B, T, H, D_v) float32
    and the final state."""
    b, _, heads, d_k = q.shape
    if state is None:
        state = jnp.zeros((b, heads, v.shape[-1], d_k), jnp.float32)

    def token(s, at):
        o, s = kda_update(*at, s)
        return s, o

    f32 = jnp.float32
    state, o = lax.scan(token, state, tuple(jnp.swapaxes(t, 0, 1) for t in (q, k, v, g.astype(f32), beta.astype(f32))))
    return jnp.swapaxes(o, 0, 1), state


def kda_supported(head_dim: int) -> bool:
    """Whether the kernels lower for the chip: a head of whole lanes (any width in interpret mode)."""
    from perceiver_io_tpu.ops.flash_attention import _interpret_default

    return head_dim == LANES or _interpret_default()


# ------------------------------------------------------------ the step's kernel


def kda_step_kernel_name(batch: int, heads: int, head_dim: int) -> str:
    """``kda_step_b<batch>_h<heads>_d<head_dim>``: what a device trace prints for the call."""
    return f"kda_step_b{batch}_h{heads}_d{head_dim}"


def _step_kernel(beta_ref, q_ref, k_ref, v_ref, g_ref, s_in, y_ref, s_ref, *, heads: int, head_dim: int):
    # ``s_in`` and ``s_ref`` are one array in HBM (aliased): the state is read through the input block and written
    # through the output block, which holds no data on the chip until it is written (``ops/power_retention.py``)
    d, f32 = head_dim, jnp.float32
    dt = q_ref.dtype
    row = pl.program_id(0)
    sublane = lax.broadcasted_iota(jnp.int32, (8, d), 0)
    diagonal = lax.broadcasted_iota(jnp.int32, (d, d), 0) == lax.broadcasted_iota(jnp.int32, (d, d), 1)
    for h in range(heads):
        q, k, v = (ref[0, h:h + 1, :].astype(f32) for ref in (q_ref, k_ref, v_ref))  # (1, D) rows
        s = s_in[0, h] * jnp.exp(g_ref[0, h:h + 1, :])  # the decay: a key channel is a lane
        both = jnp.where(sublane == 0, k, jnp.where(sublane == 1, q, 0.0)).astype(dt)  # k and q in one sublane tile
        reads = _dot(both, s.astype(dt), _NT)  # (8, D_v): what the decayed state predicts for k, and S'^T q
        u = beta_ref[row * heads + h] * (v - reads[0:1])
        y_ref[0, h:h + 1, :] = reads[1:2] + jnp.sum(q * k, axis=1, keepdims=True) * u
        # the pseudo-value down the sublanes, by the identity's mask and a lane sum (no transpose for Mosaic to lower)
        u_down = jnp.sum(jnp.where(diagonal, u, 0.0), axis=1, keepdims=True)  # (D_v, 1)
        s_ref[0, h] = s + u_down * k


@jax.jit
def _step(q, k, v, g, beta, s):
    from perceiver_io_tpu.ops.flash_attention import _VMEM_LIMIT, _interpret_default

    b, heads, d = q.shape
    row_block = pl.BlockSpec((1, heads, d), lambda r: (r, 0, 0))
    state_block = pl.BlockSpec((1, heads, d, d), lambda r: (r, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_step_kernel, heads=heads, head_dim=d),
        name=kda_step_kernel_name(b, heads, d),
        grid=(b,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), row_block, row_block, row_block, row_block, state_block],
        out_specs=[row_block, state_block],
        out_shape=[jax.ShapeDtypeStruct((b, heads, d), jnp.float32), jax.ShapeDtypeStruct(s.shape, jnp.float32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret_default(),
    )(beta.astype(jnp.float32).reshape(-1), q, k, v, g.astype(jnp.float32), s)


def kda_step(q, k, v, g, beta, s):
    """:func:`kda_update` as one Pallas call over the state where it lies: grid
    (row,), a row's heads' states read once, decayed, corrected and written
    back **in place** (``input_output_aliases``); the two reads of the decayed
    state are one product on the matrix unit (``k`` and ``q`` in one sublane
    tile against the head's 128 x 128), the decay and the rank-one update
    float32 on the vector unit. Same arguments and results as
    :func:`kda_update`; ``D_k = D_v``."""
    return _step(q, k, v, g, beta, s)


# ---------------------------------------------------- the prompt pass's kernel


def kda_chunk_kernel_name(length: int, chunk: int, heads: int, head_dim: int) -> str:
    """``kda_chunk_l<length>_c<chunk>_h<heads>_d<head_dim>``: what a device trace prints for the call."""
    return f"kda_chunk_l{length}_c{chunk}_h{heads}_d{head_dim}"


class KdaPlan(NamedTuple):
    """How one traced prompt-pass call is cut (a row of :func:`kda_plans`)."""

    length: int
    chunk: int
    sub_chunk: int
    heads: int
    heads_block: int
    head_dim: int
    grid_steps: int  # a row: blocks of heads x chunks of time
    solve_products: int  # a chunk a head: the products of the triangular solve (a factor and the next power in one)
    conv_taps: int  # the taps the kernel convolves q, k and v with on its tiles; 0 where the caller hands it shaped inputs


_PLANS: dict = {}


def kda_plans() -> list:
    """One row per distinct prompt-pass geometry traced so far, for a ``compile`` event row."""
    return [plan._asdict() for _, plan in sorted(_PLANS.items())]


def chunk_of(length: int, chunk: int = CHUNK, sub: int = SUB) -> int:
    """The tokens a grid step takes of a row of ``length``: ``chunk``, or the
    row whole in sub-chunks where it is shorter (a length that is no multiple
    is padded with tokens that write nothing and forget nothing)."""
    return min(chunk, -(-length // sub) * sub)


def _heads_block(heads: int, want: int) -> int:
    return max(n for n in range(1, min(heads, want) + 1) if heads % n == 0)


def kda_plan(length: int, heads: int, head_dim: int, chunk: int = CHUNK, sub: int = SUB,
             heads_block: int = HEADS_BLOCK, conv_taps: int = 0) -> KdaPlan:
    c = chunk_of(length, chunk, sub)
    block = _heads_block(heads, heads_block)
    doublings = max((c - 1).bit_length() - 1, 0)  # I - N, then a factor I + N^(2^i) while 2^i < C
    return KdaPlan(length, c, sub, heads, block, head_dim, heads // block * -(-length // c), doublings + (doublings > 0), conv_taps)


def _inverse_unit_lower(n, dt):
    """``(I + n)^-1`` for ``n`` (C, C) float32 strictly lower triangular:
    ``(I - n)(I + n^2)(I + n^4) ...`` up to the power that vanishes. Products
    take ``dt`` operands and accumulate in float32."""
    c = n.shape[0]
    eye = (lax.broadcasted_iota(jnp.int32, (c, c), 0) == lax.broadcasted_iota(jnp.int32, (c, c), 1)).astype(jnp.float32)
    inverse, reach = eye - n, 2
    if reach >= c:
        return inverse
    power = _dot(n.astype(dt), n.astype(dt), _NN)
    while True:  # ``power`` is n^reach, the next factor's: the factor and the next power share their right operand, one product
        right = power.astype(dt)
        reach *= 2
        if reach >= c:
            return inverse + _dot(inverse.astype(dt), right, _NN)
        both = _dot(jnp.concatenate([inverse, power], axis=0).astype(dt), right, _NN)
        inverse, power = inverse + both[:c], both[c:]


def _convolved(x_ref, taps_ref, tail_ref, which: int, lanes, n_taps: int):
    """One head's raw tile of q, k or v (``which`` 0, 1, 2) under its causal
    convolution of ``n_taps`` taps, (chunk, D) float32, summed oldest tap first
    (:func:`~perceiver_io_tpu.core.ssm.causal_conv`'s order of addition). A
    token's taps reach ``n_taps - 1`` rows back: within the tile by a turn
    along the sublanes, before it from ``tail_ref``, which holds the last
    sublane tile of the previous chunk's raw rows (zeros at a row's start) and
    is left holding this chunk's."""
    x = x_ref[0, :, lanes].astype(jnp.float32)  # (chunk, D)
    before = tail_ref[which, :, lanes]  # (_TAIL, D)
    tail_ref[which, :, lanes] = x[x.shape[0] - _TAIL:]
    first_rows = lax.broadcasted_iota(jnp.int32, before.shape, 0)
    acc = None
    for tap in range(n_taps):
        back = n_taps - 1 - tap
        if back:
            turned = pltpu.roll(x, back, 0)  # row t holds x[t - back]; the tile's first ``back`` rows hold its last
            first = jnp.where(first_rows < back, pltpu.roll(before, back, 0), turned[:_TAIL])
            term = taps_ref[which, tap:tap + 1, lanes] * jnp.concatenate([first, turned[_TAIL:]], axis=0)
        else:
            term = taps_ref[which, tap:tap + 1, lanes] * x
        acc = term if acc is None else acc + term
    return acc


def _shaped(q_ref, k_ref, v_ref, taps_ref, tail_ref, lanes, n_taps: int):
    """One head's raw tiles made what the recurrence reads, as
    ``core/kda.py::KimiDeltaAttention._shape`` makes them in XLA: each under
    its convolution and silu in float32, q and k normed to length 1 over the
    head's lanes, q scaled by ``D^-0.5``, **one rounding to the operand dtype**."""
    def one(x_ref, which, unit, scale):
        y = jax.nn.silu(_convolved(x_ref, taps_ref, tail_ref, which, lanes, n_taps))
        if unit:
            y = y * lax.rsqrt(jnp.sum(y * y, axis=1, keepdims=True) + L2_EPS)  # along the tile's own lanes: no relayout
        if scale != 1.0:
            y = y * scale
        return y.astype(x_ref.dtype)

    d = lanes.stop - lanes.start
    return one(q_ref, 0, True, d ** -0.5), one(k_ref, 1, True, 1.0), one(v_ref, 2, False, 1.0)


def _chunk_kernel(beta_ref, g_ref, q_ref, k_ref, v_ref, taps_ref, y_ref, s_ref, tail_ref, *, heads_block: int, head_dim: int, chunk: int, sub: int):
    # ``taps_ref`` (the three tap tables) and ``tail_ref`` (the raw rows' tails, scratch) are None where the caller hands in shaped q, k and v
    hb, j = pl.program_id(1), pl.program_id(2)
    d, f32 = head_dim, jnp.float32
    n_taps = 0 if taps_ref is None else taps_ref.shape[1]

    @pl.when(j == 0)
    def _start():
        s_ref[...] = jnp.zeros_like(s_ref)
        if n_taps:
            tail_ref[...] = jnp.zeros_like(tail_ref)  # zeros before a row's first token

    beta_all = beta_ref[0]  # (chunk, H): every head's step, along the sublanes; a head's lane is picked by a masked sum
    head_lane = lax.broadcasted_iota(jnp.int32, beta_all.shape, 1)
    i_pos = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j_pos = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    for h in range(heads_block):
        lanes = slice(h * d, (h + 1) * d)
        if n_taps:
            # tokens past the row's end are zeros of the *raw* rows here, so the first ``n_taps - 1`` of them see real tokens
            # through their taps and have a key and a value; their step and log-decay are still zero, so their pseudo-value is
            # zero and they write nothing and forget nothing (their ``y`` is cut off by the caller)
            q, k, v = _shaped(q_ref, k_ref, v_ref, taps_ref, tail_ref, lanes, n_taps)
        else:
            q, k, v = q_ref[0, :, lanes], k_ref[0, :, lanes], v_ref[0, :, lanes]
        dt = k.dtype
        big_g = g_ref[0, :, lanes]  # (chunk, D): the log-decays summed from the chunk's first token on
        beta = jnp.sum(jnp.where(head_lane == hb * heads_block + h, beta_all, 0.0), axis=1, keepdims=True)  # (chunk, 1)
        q_f, k_f = q.astype(f32), k.astype(f32)
        state = s_ref[0, h]  # (D_v, D_k)
        total = big_g[chunk - 1:chunk]  # (1, D)

        # ---- A and B, a sub-chunk of rows against every key: both factors of a visible pair are at most exp(_CAP)
        pairs = []
        for r in range(0, chunk, sub):
            ref = big_g[r:r + 1]
            left = jnp.exp(big_g[r:r + sub] - ref)
            right = (k_f * jnp.exp(jnp.minimum(ref - big_g, _CAP))).astype(dt)
            rows = jnp.concatenate([k_f[r:r + sub] * left, q_f[r:r + sub] * left], axis=0).astype(dt)
            pairs.append(_dot(rows, right, _NT))  # (2 sub, chunk): A's rows, then B's
        a = jnp.concatenate([p[:sub] for p in pairs], axis=0)
        b = jnp.concatenate([p[sub:] for p in pairs], axis=0)
        solve = _inverse_unit_lower(jnp.where(j_pos < i_pos, beta * a, 0.0), dt)

        # ---- what the state before the chunk predicts and answers, the pseudo-values, the outputs
        seen = jnp.exp(big_g)  # what the state before the chunk is worth at token i, a key channel
        carried = _dot(jnp.concatenate([k_f * seen, q_f * seen], axis=0).astype(dt), state.astype(dt), _NT)  # (2 chunk, D_v)
        u = _dot(solve.astype(dt), (beta * (v.astype(f32) - carried[:chunk])).astype(dt), _NN)
        y = carried[chunk:] + _dot(jnp.where(j_pos <= i_pos, b, 0.0).astype(dt), u.astype(dt), _NN)
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)

        # ---- the state after the chunk
        to_end = (k_f * jnp.exp(total - big_g)).astype(dt)  # what key j's write is worth at the chunk's end
        s_ref[0, h] = state * jnp.exp(total) + _dot(u.astype(dt), to_end, _TN)


def _on_shaped_inputs(kernel, beta_ref, g_ref, q_ref, k_ref, v_ref, y_ref, s_ref):
    """The chunk kernel's entry where the caller has shaped q, k and v: no tap tables among the inputs, no tails behind the outputs."""
    kernel(beta_ref, g_ref, q_ref, k_ref, v_ref, None, y_ref, s_ref, None)


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "sub", "heads_block"))
def _chunked(q, k, v, g, beta, taps, heads: int, chunk: int, sub: int, heads_block: int):
    from perceiver_io_tpu.ops.flash_attention import _VMEM_LIMIT, _interpret_default  # at call time: tests steer the second

    b, length, width = q.shape
    d = width // heads
    n_taps = 0 if taps is None else taps[0].shape[0]
    if n_taps - 1 > _TAIL:
        raise ValueError(f"kda_chunked: {n_taps} taps reach further back than the {_TAIL} rows the kernel carries")
    plan = _PLANS[(length, chunk, sub, heads, heads_block, d, n_taps)] = kda_plan(length, heads, d, chunk, sub, heads_block, n_taps)
    c, block = plan.chunk, plan.heads_block
    n_chunks = -(-length // c)
    pad = n_chunks * c - length

    def padded(t):  # tokens past the row's end: no key, no value, no step, a decay of 1, so they write nothing and forget nothing
        return jnp.pad(t, ((0, 0), (0, pad), (0, 0))) if pad else t

    q, k, v, g, beta = padded(q), padded(k), padded(v), padded(g.astype(jnp.float32)), padded(beta.astype(jnp.float32))
    # the running sum within a chunk as a product with a triangle of ones, float32 at full precision: XLA's cumulative sum is a
    # window reduction of 128 adds an element between two relayouts of the gates
    below = jnp.tril(jnp.ones((c, c), jnp.float32))
    big_g = jnp.matmul(below, g.reshape(b, n_chunks, c, width), precision=lax.Precision.HIGHEST).reshape(b, n_chunks * c, width)

    token_block = pl.BlockSpec((1, c, block * d), lambda r, hb, j: (r, j, hb))
    operands, in_specs, scratch = [beta, big_g, q, k, v], [pl.BlockSpec((1, c, heads), lambda r, hb, j: (r, j, 0))] + [token_block] * 4, []
    kernel = functools.partial(_chunk_kernel, heads_block=block, head_dim=d, chunk=c, sub=sub)
    if n_taps:
        operands.append(jnp.stack([t.astype(jnp.float32) for t in taps]))  # (3, K, H * D): a head's taps are its lanes of a row
        in_specs.append(pl.BlockSpec((3, n_taps, block * d), lambda r, hb, j: (0, 0, hb)))
        scratch.append(pltpu.VMEM((3, _TAIL, block * d), jnp.float32))
    else:
        kernel = functools.partial(_on_shaped_inputs, kernel)
    y, s = pl.pallas_call(
        kernel,
        name=kda_chunk_kernel_name(length, c, heads, d),
        grid=(b, heads // block, n_chunks),
        in_specs=in_specs,
        out_specs=[token_block, pl.BlockSpec((1, block, d, d), lambda r, hb, j: (r, hb, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, n_chunks * c, width), q.dtype),
                   jax.ShapeDtypeStruct((b, heads, d, d), jnp.float32)],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret_default(),
    )(*operands)
    return y[:, :length], s


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def kda_chunked(q, k, v, g, beta, heads: int, chunk: int = CHUNK, sub: int = SUB, heads_block: int = HEADS_BLOCK, taps=None):
    """The chunked form over whole rows from an empty state.

    ``q``, ``k``, ``v`` (B, T, H * D), heads side by side as their projections
    write them; ``g`` (B, T, H * D) float32, each token's log-decay a key
    channel (at most 0, and no smaller than :func:`sub_chunk_safe` allows);
    ``beta`` (B, T, H) float32. Returns ``o`` (B, T, H * D) in ``q``'s dtype and
    the rows' final state (B, H, D, D) float32, stored transposed (the module
    docstring). ``D`` is 128 on the chip (:func:`kda_supported`); ``chunk`` is
    cut to a shorter row and is whole sub-chunks.

    Without ``taps`` the three are what the recurrence reads (q scaled and of
    unit length, k of unit length, v after its silu). With ``taps``, the three
    tables (K, H * D) of the causal depthwise convolutions, they are **the
    projections' raw outputs** and the kernel shapes each tile itself
    (:func:`_shaped`: convolution, silu, l2 norm, q's ``D^-0.5``, one rounding
    to their dtype), from zeros before a row's first token."""
    if chunk % sub:
        raise ValueError(f"kda_chunked: a chunk of {chunk} tokens is not whole sub-chunks of {sub}")
    return _chunked(q, k, v, g, beta, taps, heads=heads, chunk=chunk, sub=sub, heads_block=heads_block)


def _no_backward(*_):
    raise NotImplementedError("kda_chunked is forward only (the prompt pass of a served decoder): no backward kernel is written")


kda_chunked.defvjp(_no_backward, _no_backward)


__all__ = (
    "CHUNK", "SUB", "HEADS_BLOCK", "kda_chunked", "kda_step", "kda_update", "kda_reference", "kda_supported", "sub_chunk_safe",
    "kda_plan", "kda_plans", "chunk_of", "kda_chunk_kernel_name", "kda_step_kernel_name",
)
