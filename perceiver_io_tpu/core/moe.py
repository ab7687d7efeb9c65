"""Sparse experts, sigmoid-routed with a shared expert (DeepSeek-V3,
arXiv:2412.19437 section 2.1.2), softmax-routed with none (the Mellum
family: ``choose_experts_softmax``), or softmax-routed under a bias over
experts of which some have no weights (the LongCat-Flash family:
``choose_experts_softmax_biased`` and ``zero_expert_num``), as **one chip's
share** of an expert-parallel layer.

The router keeps its published width: every token is scored against all
``n_routed_experts`` (and the ``zero_expert_num`` outputs after them). The
layer is told which experts it holds (``held_experts_start``,
``n_held_experts``, counted among the experts that have weights) and computes
their part of the result for the tokens routed to them. A pair routed to an
expert held elsewhere adds nothing here, and no code stands in for the other
chips or for the exchange with them. The shared expert is computed on every
chip alike. With every expert held, the layer is the whole layer.

**Experts without weights** (``zero_expert_num`` > 0; arXiv:2509.01322 section
2.1): router outputs ``n_routed_experts`` to ``n_routed_experts +
zero_expert_num - 1`` are identity experts, ``E(x) = x``. A pair routed to one
has no row in any pass and no column in the dense path: a token's identity
pairs are summed into one weight ``w0`` and ``w0 * x`` is added under the
scope ``moe/zero``, on every chip alike (the token is here, so nothing is
exchanged for it), like the shared expert. How many experts with weights a
token runs is then 0 to ``num_experts_per_tok``.

Routing (``inference/model.py``'s ``Gate``): ``s = sigmoid(x W_g)`` in
float32; experts are *chosen* on ``s + b`` (``b`` the bias that balances load
without an auxiliary loss): the experts form ``n_group`` groups, a group's
score is the sum of its two largest, the ``topk_group`` best groups stay, and
the ``num_experts_per_tok`` largest within them are chosen. The *weights* are
the unbiased ``s`` of the chosen, renormalised to sum to one and scaled by
``routed_scaling_factor``.

The third rule (``scoring_func="softmax_biased"``): ``p = softmax(x W_r)`` in
float32 over every output, the ``num_experts_per_tok`` largest of ``p + b``
chosen, no groups; the weights are the unbiased ``p`` of the chosen times
``routed_scaling_factor`` and are **not** renormalised (the bias chooses and
never weighs, as above).

Two ways through the held experts, chosen at trace time by the number of
tokens (``_cuts(...).grouped_min_tokens``, where the two were measured to cross), each
kept on a chip measurement (``tools/moe_ab.py``; PERF.md 6, PR 28):

``grouped`` (a prompt pass, or a step of 384 tokens and more)
    the routed pairs are sorted by expert, and the pairs that fall to held
    experts go, a pass of ``_pass_rows(...)`` rows at a time (the pairs an
    even routing sends here and a quarter more), through a gather and a
    grouped matrix product (``ops/grouped_matmul.py``); one pass where the
    routing is even and no cap of the geometry's binds (Ling's passes are
    4096 rows, four or five a chunk), and as many more as the routing sent
    pairs here: no pair is dropped however skewed the routing is, the work
    follows the pairs that are really here, and the buffers are a pass's,
    not ``T * k`` rows.
    ``jax.lax.ragged_dot`` in the kernel's place measured 1.5x slower. How a
    pass's rows get back to their tokens (``grouped_combine``) is a fact of
    the configuration, the share of the experts the layer holds:

    *every expert held* (Mellum 2): the sort is a permutation of all ``T * k``
    pairs, so the passes leave their rows in the kernel's dtype in sorted
    order, the inverse permutation reads pair ``(t, j)`` back, and a token is
    the float32 sum of its ``k`` weighted rows: a row gather and one fused
    reduction where the scatter-add of the same 65 536 rows of 2304 channels
    was the prompt pass's largest single operation (PERF.md 6, PR 33).

    *a share held* (DeepSeek-V3, 16 of 256; Ling, 128 of 512): a token has 0
    to ``k`` local pairs, about a sixteenth or a quarter of all, and only
    those are moved. The flat pair list is in token order, so a pass's pairs
    sorted by their number are sorted by token: one sort of the pass's keys
    (each bringing its row of the pass and its float32 weight along, no row
    touched), one row gather into that order, and ``ops/moe_combine.py``'s
    kernel adds each weighted row into its token in float32, a tile of tokens
    owning a contiguous run of rows (``"segment_sum"``, PERF.md 6, PR 50).
    Rows past the last local pair sort last and are never read. The inverse
    gather would move all ``T * k`` rows to use that share; XLA's scatter-add
    of the same rows ran at thirty times its bytes' time.

``dense`` (a decode step: under 384 tokens, under the first two sets of cuts)
    every held expert on every token, weighted (zero where not routed). Up to
    the ridge (about 240 tokens an expert layer's worth of weights) a step is
    bound by reading the experts' weights, and this path reads all of them
    whatever the step hits: 1.95 ms at 64 tokens, 2.06 at 256, against 2.30
    and 2.43 for the grouped path with its sort, gather and combine.
    Inside the cell's generator, where the sort overlaps, the grouped path on
    a step's pairs was 5.8% faster end to end at batch 64 (it reads the 87%
    of experts a step hits) and its speed followed the seed's tokens (0.7%
    spread against 0.1%): left out here, open in PERF.md 7. ``ragged_dot``
    measured 2x slower than either. A layer under the third set
    (``_MANY_EXPERTS``, measured at 128 held of 512) never takes this path: its
    steps are grouped too; the two paths' times tie there, and the dense
    einsum's relayout of that many experts does not fit the chip.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from perceiver_io_tpu.obs import probes
from perceiver_io_tpu.ops.grouped_matmul import block_plan, grouped_matmul, visit_plan
from perceiver_io_tpu.ops.moe_combine import moe_combine


# How the work is cut, not what is computed: a function of the expert layer's
# geometry (held experts, hidden size, expert width), each value from
# ``tools/moe_ab.py`` on the v5e at the published widths of the
# geometries the program runs (PERF.md 6, PR 28, PR 32 and PR 34). The times
# in these notes were read with the grouped kernel's blocks of before PR 54
# (the contraction and the column cut at 1024, an expert's weights fetched
# again at every visit); with an expert's whole matrix a block the three
# kernels of a pass take (``tools/moe_ab.py --only products/``, PERF.md 6, PR
# 54) 5.63 ms where they took 7.27 at Mellum's pass of 65 536 rows, 0.86 for
# 1.16 at Ling's pass of 4096 and 1.85 for 2.08 at its step's 384 rows, 4.00 for
# 4.90 at DeepSeek-V3's 5120 and 4.87 for 6.31 at K-EXAONE's 10 240: every
# tiling got faster by its kernels' share, and the cuts were not read again.
class _Cuts(NamedTuple):
    grouped_min_tokens: int  # from this many tokens the grouped path is taken
    row_tile: int  # the grouped kernel's row tile
    pass_rows: int  # a pass gathers at most this many sorted pairs (and what ``_pass_rows`` reckons, if that is fewer)


# 16 held experts of width 2048, hidden 7168 (DeepSeek-V3, one chip of sixteen).
# Dense and grouped cross between 256 tokens (dense 2.06 ms, grouped 2.43) and
# 384 (2.95 against 2.52; 4.74 against 2.90 at 512). Row tile: 256 is the
# fastest from 2048 tokens up (by 9% at a prompt chunk's 8192); 128 rows are 6
# to 11% faster from 384 to 512 tokens. Rows a pass, under the segment-sum
# combine (PERF.md 6, PR 50; every pass reads and writes the tokens' whole
# float32 buffer, so few passes): 8192 tokens with 4096 pairs here take 7.77 ms
# in one pass of the 5120 rows ``_pass_rows`` reckons, 8.02 in passes of 2048
# and 9.10 of 1024: no cap. Measured again at 16 held experts of width 2048,
# hidden 6144 (K-EXAONE, one chip of eight: one local pair a token, twice this
# share's; ``tools/moe_ab.py --geom kexaone``, PERF.md 6, PR 34 and PR 50), with
# the same outcome: 128 positions dense 1.64 ms, grouped 1.71 (tile 128) to
# 1.94; 256 tokens 1.81 against 2.01; 384 tokens 2.51 against 2.16; a prompt
# chunk's 8192 pairs at tile 256 10.20 ms in one pass of 10 240 rows, 10.10 in
# passes of 4096 and 12.35 of 1024; tile 128 and tile 512 were 19% and 9%
# slower under the combine of before.
_WIDE_EXPERTS = _Cuts(grouped_min_tokens=384, row_tile=256, pass_rows=65536)
# 64 held experts of width 896, hidden 2304 (Mellum 2, every expert held, 8
# pairs a token all of them here: the grouped path combines by the gather).
# Dense and grouped cross between 256 tokens (dense 1.17 ms, grouped 1.50) and
# 512 (2.24 against 1.98); a decode step's 32 tokens take 1.08 ms dense, 1.09 to
# 1.29 grouped. Row tile: 256 and 512 within 1.5% at a prompt chunk's 8192
# tokens (128 was 8% slower). Rows a pass were set under the scatter-add (PR
# 32: the 65 536 pairs of such a chunk 17.4 ms in one pass, 19.5 in two, 23.2
# in passes of 8192, 28.5 of 1024 and 42.7 of 2048, the cliff past 1024
# updates of XLA's scatter-add, which no side runs since PR 50). Under the gather
# (PR 33) one pass takes 13.76 ms, two of 32 768 rows 13.36, passes of 16 384
# 14.97 and of 8192 14.76: no cliff, and within 3% the passes do not matter;
# one pass stays, whose body needs no loop (PERF.md 7).
_SMALL_EXPERTS = _Cuts(grouped_min_tokens=384, row_tile=256, pass_rows=65536)
# 128 held experts of width 768, hidden 2560, a quarter of the router's 512 (Ling
# 3.0 flash, one chip of four: two local pairs a token, the segment-sum combine;
# ``tools/moe_ab.py --geom ling``, PERF.md 6, PR 49 and PR 50). **Every call takes
# the grouped path**: a decode step's 128 tokens hit 111 of the 128 held experts,
# and grouped reads those (1.94 ms at tile 64, 1.96 at 32, 1.98 at 128, 2.06 at
# 16, 2.47 at 256) where dense reads all (2.01); 256 tokens 2.20 (tiles 64 and
# 128) against 2.26. And the dense path does not fit: inside the generator XLA
# lays ``experts_w1`` and ``experts_w3`` of every layer out again for its
# einsum, twelve copies of 480 MB beside 10.5 GB of weights. Row tile: a prompt
# chunk's 8192 tokens (16 384 local pairs, 128 rows an expert) take 8.07 ms at
# 128 and 7.68 at 256 in one pass (12.6 at 64, 15.3 at 32, 21.1 at 16 under the
# combine of before); 128 serves the step and the chunk within 5% of either's
# best. Rows a pass at 8192 tokens, tile 128: 10.57 ms in passes of 1024 (every
# pass reads and writes the tokens' whole float32 buffer, 0.26 ms), **7.17 of
# 4096**, 7.77 of 8192, 7.58 of 16 384 and 8.07 in the one pass of 20 480 that
# ``_pass_rows`` reckons: XLA gathers the rows of a pass of 4096 (21 MB) at the
# HBM's rate and those of a larger one a row a DMA, at 120 to 190 GB/s
# (``x[token]`` and the combine's row gather, 1.70 ms against 0.68), and the
# grouped kernels take 4.65 ms in one pass against 4.14 in passes of 4096. The
# cell decided the same way: 3255 tokens/s in one pass, 3293 in passes of 5120,
# 3298 of 4096 (the parent's scatter-add in passes of 1024: 3062).
_MANY_EXPERTS = _Cuts(grouped_min_tokens=1, row_tile=128, pass_rows=4096)
# Each set with the geometry it was measured at: an expert's size (hidden x
# width) and how many experts the layer holds.
_MEASURED = (
    (7168 * 2048, 16, _WIDE_EXPERTS),
    (2304 * 896, 64, _SMALL_EXPERTS),
    (2560 * 768, 128, _MANY_EXPERTS),
)
# A pass is the pairs an even routing sends here and a quarter more, in whole
# row tiles, up to the set's cap: a layer whose routing is within a quarter of
# even takes one pass where no cap binds, and a skewed one as many more as
# serve every pair.
_PASS_SLACK = 1.25


def _cuts(hidden: int, width: int, held: int) -> _Cuts:
    """The cuts of an expert layer that holds ``held`` experts of ``hidden`` x
    ``width``: those of the measured geometry nearest to it, by the ratios of
    the expert's size and of the number held (the sum of the two logarithms).
    Every set is a reading at its own geometry and no rule: the third's
    crossing of dense and grouped was measured at one point (128 and 256 tokens
    over 128 held experts), where the two paths' times tie within 3% and memory
    decides, so a geometry between the measured ones gets cuts nobody measured
    for it (ROADMAP.md D15)."""
    def far(measured):
        size, count, _ = measured
        return abs(math.log(hidden * width / size)) + abs(math.log(held / count))
    return min(_MEASURED, key=far)[2]


def _pass_rows(pairs: int, held_share: float, cuts: _Cuts) -> int:
    """Rows of one pass of the grouped path for ``pairs`` routed pairs of which ``held_share`` fall here if the routing is even."""
    want = int(pairs * held_share * _PASS_SLACK)
    return min(cuts.pass_rows, max(-(-want // cuts.row_tile), 1) * cuts.row_tile)


def router_logits(x: jnp.ndarray, w_gate: jnp.ndarray) -> jnp.ndarray:
    """``x W_g`` (T, E) in float32 at full precision: which experts a token
    takes hangs on differences in the last digits."""
    return jnp.dot(x.astype(jnp.float32), w_gate.astype(jnp.float32), precision=lax.Precision.HIGHEST)


def router_scores(x: jnp.ndarray, w_gate: jnp.ndarray) -> jnp.ndarray:
    """``sigmoid(x W_g)`` (T, E), float32."""
    return jax.nn.sigmoid(router_logits(x, w_gate))


def choose_experts(
    scores: jnp.ndarray,
    bias: jnp.ndarray,
    *,
    n_group: int,
    topk_group: int,
    top_k: int,
    scale: float,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``scores`` (T, E) float32 -> chosen experts (T, top_k) int32 and their
    combine weights (T, top_k) float32 (see the module docstring)."""
    t, e = scores.shape
    biased = (scores + bias.astype(jnp.float32)).reshape(t, n_group, e // n_group)
    group_score = lax.top_k(biased, 2)[0].sum(-1)
    _, kept = lax.top_k(group_score, topk_group)
    group_stays = jax.nn.one_hot(kept, n_group, dtype=jnp.bool_).any(axis=1)
    masked = jnp.where(group_stays[:, :, None], biased, -jnp.inf).reshape(t, e)
    _, chosen = lax.top_k(masked, top_k)
    w = jnp.take_along_axis(scores, chosen, axis=1)
    return chosen.astype(jnp.int32), w / w.sum(-1, keepdims=True) * scale


def choose_experts_softmax(logits: jnp.ndarray, top_k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The softmax rule (``norm_topk_prob``): ``p = softmax(logits)`` over all
    experts in float32, the ``top_k`` largest are chosen, and their weights
    are ``p`` renormalised over the chosen. No bias, no groups, no scale.
    Returns chosen (T, top_k) int32 and weights (T, top_k) float32."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, chosen = lax.top_k(p, top_k)
    return chosen.astype(jnp.int32), w / w.sum(-1, keepdims=True)


def choose_experts_softmax_biased(logits: jnp.ndarray, bias: jnp.ndarray, top_k: int, scale: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The softmax rule under a bias: ``p = softmax(logits)`` over all outputs
    in float32, the ``top_k`` largest of ``p + bias`` are chosen, and their
    weights are the unbiased ``p`` times ``scale``, not renormalised. Returns
    chosen (T, top_k) int32 and weights (T, top_k) float32."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    _, chosen = lax.top_k(p + bias.astype(jnp.float32), top_k)
    return chosen.astype(jnp.int32), jnp.take_along_axis(p, chosen, axis=1) * scale


def _silu_gate(h1, h3, dtype):
    return (jax.nn.silu(h1.astype(jnp.float32)) * h3.astype(jnp.float32)).astype(dtype)


def experts_dense(x, combine, w1, w3, w2):
    """Every held expert on every token: ``x`` (T, h), ``combine`` (T, G)
    float32 (a token's weight for each held expert, zero where not routed),
    weights (G, h, I), (G, h, I), (G, I, h). Returns (T, h) float32."""
    h1 = jnp.einsum("th,ghi->gti", x, w1)
    h3 = jnp.einsum("th,ghi->gti", x, w3)
    a = _silu_gate(h1, h3, jnp.float32) * combine.T[:, :, None]
    return jnp.einsum("gti,gih->th", a.astype(x.dtype), w2, preferred_element_type=jnp.float32)


def grouped_combine(n_held: int, n_routed: int) -> str:
    """How the grouped path's rows get back to their tokens: ``"gather"``
    where the layer holds every expert (a token's ``k`` pairs are all here, so
    the sorted order is a permutation of all pairs and its inverse finds
    them), ``"segment_sum"`` where it holds a share (a token has 0 to ``k``
    local pairs: only those are moved, into token order, and summed a token by
    ``ops/moe_combine.py``). ``n_routed`` is the router's width: a layer
    with experts that have no weights never holds every output, since an
    identity pair has no row to find."""
    return "gather" if n_held == n_routed else "segment_sum"


def experts_grouped(x, local, weights, w1, w3, w2, pass_rows: int, row_tile: int, combine: str):
    """The held experts on the pairs routed to them, sorted by expert.

    ``x`` (T, h); ``local`` (T, k) int32, a pair's held-expert index or ``G``
    where its expert is not held; ``weights`` (T, k) float32; ``combine`` is
    :func:`grouped_combine` of the layer (``"gather"`` needs every pair
    local). Returns the sum over a token's local pairs (T, h) float32, the
    rows a pass left unserved (a scalar that is zero: the passes are of
    ``pass_rows`` rows, a multiple of ``row_tile``, the grouped kernel's, and
    as many as serve every local pair) and the number of passes it took."""
    t, k = local.shape
    g = w1.shape[0]
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)  # held pairs first, by expert
    sizes = jnp.zeros((g + 1,), jnp.int32).at[flat].add(1)[:g]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
    n_local = offsets[-1]
    padded = jnp.concatenate([order, jnp.zeros((pass_rows,), jnp.int32)])  # a pass may read past the end

    def one_pass(p):
        """The kernels' rows for pass ``p``'s sorted pairs; also the pairs and which rows are pairs at all."""
        lo = p * pass_rows
        live = (lo + jnp.arange(pass_rows, dtype=jnp.int32)) < n_local
        pair = lax.dynamic_slice(padded, (lo,), (pass_rows,))
        token = jnp.where(live, pair // k, 0)  # a row past the last local pair reads token 0
        in_pass = jnp.clip(offsets, lo, lo + pass_rows) - lo
        group_sizes = in_pass[1:] - in_pass[:-1]
        xs = x[token]
        mm = lambda a, w: grouped_matmul(a, w, group_sizes, tm=row_tile)  # noqa: E731
        return mm(_silu_gate(mm(xs, w1), mm(xs, w3), x.dtype), w2), pair, live

    if combine == "gather":
        # every pair is local: the passes are counted at trace time, their rows stay in the kernel's dtype in
        # sorted order, and pair (t, j) is read back from row ``rank[t * k + j]``; no row past the last pair is read
        n_pass = -(-t * k // pass_rows)
        if n_pass == 1:
            sorted_rows = one_pass(0)[0]
        else:
            sorted_rows = lax.fori_loop(
                0, n_pass, lambda p, rows: lax.dynamic_update_slice(rows, one_pass(p)[0], (p * pass_rows, 0)),
                jnp.zeros((n_pass * pass_rows, x.shape[-1]), x.dtype))
        with jax.named_scope("moe/combine"):
            rank = jnp.argsort(order).astype(jnp.int32)
            y = (sorted_rows[rank].reshape(t, k, -1).astype(jnp.float32) * weights[:, :, None]).sum(axis=1)
        return y, jnp.zeros((), jnp.int32), jnp.asarray(n_pass, jnp.int32)

    w_flat = weights.reshape(-1)

    def add_pass(p, y):
        ys, pair, live = one_pass(p)
        with jax.named_scope("moe/combine"):
            # the flat pair list is in token order, so the pass's pairs sorted by their number are sorted by token:
            # each brings the row it has in the pass and its weight along, and the rows past the last pair go last
            in_order, row, w = lax.sort(
                (jnp.where(live, pair, t * k), jnp.arange(pass_rows, dtype=jnp.int32), w_flat[pair]), num_keys=1)
            return moe_combine(y, ys[row], w, in_order // k, row_tile=row_tile)

    n_pass = (n_local + pass_rows - 1) // pass_rows
    y = lax.fori_loop(0, n_pass, add_pass, jnp.zeros((t, x.shape[-1]), jnp.float32))
    return y, n_local - jnp.minimum(n_pass * pass_rows, n_local), n_pass


def grouped_fetches(sizes, pairs: int, hidden: int, width: int, itemsize: int, pass_rows: int, row_tile: int):
    """What the grouped path's kernels fetch of the experts' weights where the
    held experts got ``sizes`` (G,) of the call's ``pairs`` routed pairs (the
    ``moe.load`` tap, never a cell's program): ``(visits, fetches, blocks)``,
    int32 scalars summed over the passes. ``visits`` are a product's
    grid visits (row tile x expert); ``fetches`` the weight blocks the three
    products bring into VMEM, a block anew where its index differs from the
    grid step's before: with the contraction whole, at a visit whose expert is
    not the one before's, a column tile each; with it cut, every block at every
    visit. ``blocks`` are the blocks of the experts a pass hits, the fetches
    if each came once."""
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
    plans = [block_plan(pass_rows, *kn, row_tile, itemsize) for kn in ((hidden, width), (hidden, width), (width, hidden))]

    def of_pass(p):
        in_pass = jnp.clip(offsets, p * pass_rows, (p + 1) * pass_rows) - p * pass_rows
        group_sizes = in_pass[1:] - in_pass[:-1]
        _, group_ids, _, visits = visit_plan(group_sizes, pass_rows, row_tile)
        live = jnp.arange(group_ids.shape[0]) < visits
        turns = (live & (group_ids != jnp.concatenate([jnp.full((1,), -1, jnp.int32), group_ids[:-1]]))).sum()
        fetches = sum((turns if plan["weights_resident"] else visits * plan["tiles_k"]) * plan["tiles_n"] for plan in plans)
        blocks = (group_sizes > 0).sum() * sum(plan["tiles_k"] * plan["tiles_n"] for plan in plans)
        return jnp.stack([visits, fetches, blocks]).astype(jnp.int32)

    return tuple(jax.vmap(of_pass)(jnp.arange(-(-pairs // pass_rows), dtype=jnp.int32)).sum(axis=0))


class SwiGLU(nn.Module):
    """``W_2 (silu(W_1 x) * W_3 x)``, no biases."""

    hidden_size: int
    width: int
    init_scale: float
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.normal(self.init_scale)
        w1 = self.param("w1", init, (self.hidden_size, self.width), self.param_dtype)
        w3 = self.param("w3", init, (self.hidden_size, self.width), self.param_dtype)
        w2 = self.param("w2", init, (self.width, self.hidden_size), self.param_dtype)
        x = x.astype(self.dtype)
        a = _silu_gate(jnp.dot(x, w1.astype(self.dtype)), jnp.dot(x, w3.astype(self.dtype)), self.dtype)
        return jnp.dot(a, w2.astype(self.dtype))


class MoELayer(nn.Module):
    """``config`` needs ``hidden_size``, ``moe_intermediate_size``,
    ``n_routed_experts`` (the experts that have weights), ``zero_expert_num``
    (identity experts after them: the router's width is the sum),
    ``n_held_experts``, ``held_experts_start``, ``num_experts_per_tok``,
    ``n_shared_experts`` (0: no shared expert), ``init_scale`` and
    ``scoring_func``: ``"sigmoid"`` (the module docstring's rule; also needs
    ``n_group``, ``topk_group``, ``routed_scaling_factor``, and the layer has a
    ``gate_bias``), ``"softmax"`` (:func:`choose_experts_softmax`: no bias,
    groups or scale) or ``"softmax_biased"``
    (:func:`choose_experts_softmax_biased`: ``gate_bias`` and
    ``routed_scaling_factor``, no groups)."""

    config: object
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.config
        lead, h = x.shape[:-1], x.shape[-1]
        x = x.reshape(-1, h).astype(self.dtype)
        t = x.shape[0]
        g, start = c.n_held_experts, c.held_experts_start
        n_outputs = c.n_routed_experts + c.zero_expert_num  # the router's width: the identity experts come last
        init = nn.initializers.normal(c.init_scale)
        w_gate = self.param("gate", init, (h, n_outputs), self.param_dtype)
        if c.scoring_func not in ("sigmoid", "softmax", "softmax_biased"):
            raise ValueError(f"scoring_func {c.scoring_func!r}: 'sigmoid', 'softmax' or 'softmax_biased'")
        if c.scoring_func != "softmax":
            # float32 whatever the rest is stored in, as published: it is added to scores that differ in the last digits
            gate_bias = self.param("gate_bias", nn.initializers.zeros_init(), (n_outputs,), jnp.float32)
        width = c.moe_intermediate_size
        w1 = self.param("experts_w1", init, (g, h, width), self.param_dtype).astype(self.dtype)
        w3 = self.param("experts_w3", init, (g, h, width), self.param_dtype).astype(self.dtype)
        w2 = self.param("experts_w2", init, (g, width, h), self.param_dtype).astype(self.dtype)

        with jax.named_scope("moe/route"):
            if c.scoring_func == "softmax":
                chosen, weights = choose_experts_softmax(router_logits(x, w_gate), c.num_experts_per_tok)
            elif c.scoring_func == "softmax_biased":
                chosen, weights = choose_experts_softmax_biased(
                    router_logits(x, w_gate), gate_bias, c.num_experts_per_tok, c.routed_scaling_factor)
            else:
                chosen, weights = choose_experts(
                    router_scores(x, w_gate), gate_bias, n_group=c.n_group, topk_group=c.topk_group,
                    top_k=c.num_experts_per_tok, scale=c.routed_scaling_factor,
                )
            held = (chosen >= start) & (chosen < start + g)
            local = jnp.where(held, chosen - start, g)

        with jax.named_scope("moe/experts"):
            unserved = passes = jnp.zeros((), jnp.int32)
            cuts = _cuts(h, width, g)
            back = None  # how a grouped pass's rows get back to their tokens; the dense path has no such step
            if t >= cuts.grouped_min_tokens:
                rows = _pass_rows(t * c.num_experts_per_tok, g / n_outputs, cuts)
                back = grouped_combine(g, n_outputs)
                y, unserved, passes = experts_grouped(x, local, weights, w1, w3, w2, rows, cuts.row_tile, back)
            else:
                combine = (jax.nn.one_hot(local, g, dtype=jnp.float32) * weights[:, :, None]).sum(axis=1)
                y = experts_dense(x, combine, w1, w3, w2)

        if c.zero_expert_num:
            with jax.named_scope("moe/zero"):
                # a token's identity pairs as one weight: no row of a pass, no column of the dense path
                is_zero = chosen >= c.n_routed_experts
                w_zero = jnp.where(is_zero, weights, 0.0).sum(axis=1)
                y = y + w_zero[:, None] * x.astype(jnp.float32)

        if probes.active():
            load = jnp.zeros((g + 1,), jnp.int32).at[local.reshape(-1)].add(1)[:g]
            pairs_local = load.sum()
            zero = jnp.zeros((), jnp.int32)
            visits, fetches, blocks = (
                grouped_fetches(load, local.size, h, width, jnp.dtype(self.dtype).itemsize, rows, cuts.row_tile) if back else (zero,) * 3)
            taps = {
                "pairs_routed": jnp.asarray(t * c.num_experts_per_tok, jnp.int32),
                "pairs_local": pairs_local,
                "pairs_gathered": pairs_local if back else zero,
                "pairs_dropped": unserved.astype(jnp.int32),
                "passes": passes.astype(jnp.int32),
                "expert_load_max": load.max(),
                # the grouped kernels' grid visits, the weight blocks they fetch, and the blocks of the experts hit
                "expert_visits": visits,
                "expert_weight_fetches": fetches,
                "expert_weight_blocks": blocks,
            }
            if c.zero_expert_num:  # the pairs that need no expert's weights, and the most experts with weights a token runs
                n_zero = is_zero.sum(axis=1).astype(jnp.int32)
                taps.update(pairs_zero=n_zero.sum(), real_experts_per_token_max=(c.num_experts_per_tok - n_zero).max())
            probes.tap("moe.load", taps)

        if c.n_shared_experts:
            with jax.named_scope("moe/shared"):
                shared = SwiGLU(h, width * c.n_shared_experts, c.init_scale, self.dtype, self.param_dtype, name="shared")(x)
            y = y + shared.astype(jnp.float32)
        return y.astype(self.dtype).reshape(*lead, h)
