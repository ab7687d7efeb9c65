"""Time, on the chip and in one process, the ways through the held experts
and through the decode attention at a decoder-only model's published widths,
so that ``core/moe.py``, ``core/mla.py`` and ``core/gqa.py`` keep one per path
on a measurement:

    chiprun -- python tools/moe_ab.py [--geom dsv3|mellum|kexaone|ling] [--only experts_layer] [--compile-only]

``--geom dsv3`` (the default) is DeepSeek-V3's share of PR 28 (16 held experts
of 256, hidden 7168, width 2048; absorbed MLA); ``--geom mellum`` is Mellum 2
(64 experts of width 896 all held, hidden 2304; grouped-query attention over a
growing cache of 8448 slots and over a ring of 1024, batch 32 x 4 key-value
heads, 8 query heads each); ``--geom kexaone`` is K-EXAONE's share of PR 34
(16 held experts of 128, hidden 6144, width 2048: a speculative step's 128
positions dense against grouped, rows a pass at a prompt chunk's 8192 tokens
with one local pair a token; the speculative step's attention (``--only
gqa_verify``), eight steps in one program with the caches the loop's carry,
batch 64 x 8 key-value heads at a length a row: the per-row write of two
positions, XLA's scatter, alone and with the two batched products and a mask
a query (``write_xla``, ``write_and_attend_xla``), the same over a carry
pinned row-major (``..._xla_row_major``), and the program's kernel
``ops/gqa_verify.py::gqa_verify`` (``..._kernel``, where its rule takes the
capacity), over a growing cache and a ring at the parent's capacities (1537
and 129 slots, which XLA carries slot-major) and the program's (1552 and 144,
whole bfloat16 tiles); every variant prints the layouts its loop carries
(PERF.md 6, PR 44); and the prompt pass's window-128 flash forward on a chunk of four
1024-token rows, in bands or whole, blocks of 1024 down to 128); ``--geom ling``
is Ling-3.0-flash's share of PR 49 (128 held experts of 512, hidden 2560, width
768: a decode step's 128 tokens, two pairs a held expert, dense against grouped
at row tiles of 16 to 256, and rows a pass at a prompt chunk's 8192 tokens with
two local pairs a token; the expert layer alone).

- the grouped product alone (8192 live rows of 16384, 16 experts, even and
  skewed group sizes): the Pallas kernel (``ops/grouped_matmul.py``) against
  ``jax.lax.ragged_dot``;
- the expert layer's two paths as the program runs them, whole (sort, gather,
  kernels, combine against one weighted einsum), on ``T`` tokens routed
  uniformly over 256 experts of which 16 are held: ``moe.experts_dense``
  against ``moe.experts_grouped`` at several row tiles and rows a pass (``rows0``:
  the rows ``moe._pass_rows`` gives), for ``T`` from a decode step's 64 to a
  prompt chunk's 8192: where the two cross is ``moe._GROUPED_MIN_TOKENS``, the
  fastest tile ``moe._ROW_TILE``, the fastest rows a pass at 8192 tokens
  ``moe._PASS_ROWS`` (since PR 32 the three are ``moe._cuts`` of the geometry).
  The grouped path's combine is the geometry's own (``moe.grouped_combine``:
  the segment sum where a share is held, ``dsv3``, ``kexaone`` and ``ling``;
  the inverse-permutation gather at ``mellum``, where every expert is held);
  at ``mellum``'s prompt chunk each variant runs both, ``.../gather`` beside
  ``.../scatter`` (PR 33); at a share-held geometry's prompt chunk (and at
  ``ling``'s step of 128 tokens) ``..._rows1024/scatter`` is the parent's
  layer of before PR 50, XLA's scatter-add in passes of 1024 rows, kept in
  this file;
- the three grouped products of a pass as a cell runs them (``--only
  products/``, PR 54; every geometry): the rows of the cell's pass and its
  row tile, the group sizes a uniform routing of a prompt chunk's 8192 tokens
  leaves in the pass (``ling``: also a decode step's 384 rows), with the
  blocks as the program's rule cuts them (``program``: the contraction and
  the column whole, an expert's weight block stays in VMEM across its
  visits), with the cut of before PR 54 (``parent``: both in multiples of
  128 up to 1024, so the weight block's index changes at every grid step and
  every visit fetches the expert's weights again) and with the contraction
  whole beside that column (``column_1024``), each by patching the rule
  when the call is traced; prints ms a kernel by kernel name, the bytes
  each form fetches (from its visit plan and blocks) and, before any timing,
  the up and the down product of each form against ``jax.lax.ragged_dot``
  (``lhs[rows of g] @ rhs[g]`` in float32) on the chip;
- the share-held combine alone (``--only combine/``, PR 50; ``dsv3``,
  ``kexaone``, ``ling``): at a prompt chunk's 8192 tokens and the program's
  pass, the parent's scatter-add (in passes of 1024 rows, whole, and over
  token-sorted rows with the indices declared sorted) against the program's
  combine, its parts cumulatively (``index``: the sort into token order;
  ``index_gather``: and the row gather; ``program``: and
  ``ops/moe_combine.py``'s kernel), the kernel at other row tiles and with
  the add on the matrix unit (``onehot``, kept in this file); every result
  is compared with the scatter-add's, to the bit at tokens of at most two
  rows;
- grouped-query decode attention (``--geom mellum``): ``core/gqa.py``'s two
  batched products against a Pallas kernel kept in this file, over both caches;
  and the prompt pass's flash forward (``flash_attention_gqa``) on one
  8192-token row, window and full, its edge tiles in bands or whole, blocks of
  1024 or 512;
- the absorbed step's cache side (batch 64; 128 heads over a 1280 x 576 cache,
  DeepSeek-V3's, and 64 heads over 1536 x 576, LongCat-Flash's), eight steps in
  one program so that the cache is a loop's carry in the layout the loop gives
  it: ``LatentCache.append`` then ``core/mla.py::latent_decode_attention``
  (XLA's dynamic-update-slice, two batched products and float32 softmax)
  against the program's kernel ``ops/mla_absorb.py::mla_absorb``, which does
  both over a row-major cache it updates in place (PERF.md 6, PR 40);
- the expanded latent attention of a prompt pass (``--only mla_expand``): one
  layer's ``MultiHeadLatentAttention.expand`` as the body of the chunk loop
  over the stacked batch (16 chunks of 4 rows x 1024 tokens), at DeepSeek-V3's
  128 heads and LongCat-Flash's 64: the heads-major ``flash_attention`` on
  concatenated operands against ``flash_attention_mla`` on what the
  up-projections write, device ms a chunk with the kernels' share and XLA's
  apart, and the token-major path with each band of the kernel cut at the
  diagonal (``token_major_cut``, by patching the tiles when the call is
  traced: how another cut of the tile is tried) (PERF.md 6, PR 42).

Times are device times from a profiler capture of ``--iters`` calls each
(the summed duration of the device operations inside the call's annotation
window), so host dispatch is not in them. ``--compile-only`` compiles every
variant for a described v5e with no chip."""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

H, WIDTH, EXPERTS, ROUTED, TOP_K = 7168, 2048, 16, 256, 8
LAYER_TOKENS = (64, 128, 256, 384, 512, 1024, 2048, 8192)
# (row tile, rows a pass): 0 rows is what the program takes (``moe._pass_rows``: an even routing's pairs and a quarter more)
LAYER_TILINGS = ((128, 0), (256, 0), (512, 0))
# other rows a pass at a prompt chunk's tokens (the parent's scatter-add had a sweet spot at 1024: ``.../scatter``)
SHORT_PASSES = {8192: ((256, 1024), (256, 2048), (256, 8192))}
GEOM = "dsv3"


def set_geometry(name: str) -> None:
    """``--geom mellum``: every expert held, a decode step of 32 tokens and a prompt chunk of 8192."""
    global H, WIDTH, EXPERTS, ROUTED, TOP_K, LAYER_TOKENS, LAYER_TILINGS, SHORT_PASSES, GEOM
    GEOM = name
    if name == "mellum":
        H, WIDTH, EXPERTS, ROUTED, TOP_K = 2304, 896, 64, 64, 8
        LAYER_TOKENS = (32, 256, 512, 8192)
        SHORT_PASSES = {8192: ((256, 512), (256, 2048), (256, 4096), (256, 8192), (512, 8192), (256, 16384), (256, 32768), (256, 65536))}
    if name == "ling":  # 128 of 512 small experts: a step's 128 tokens hit an expert twice in the mean
        H, WIDTH, EXPERTS, ROUTED, TOP_K = 2560, 768, 128, 512, 8
        LAYER_TOKENS = (128, 256, 512, 1024, 8192)
        LAYER_TILINGS = ((16, 0), (32, 0), (64, 0), (128, 0), (256, 0))
        SHORT_PASSES = {8192: ((128, 1024), (128, 4096), (128, 8192), (128, 16384)), 128: ()}
    if name == "kexaone":
        H, WIDTH, EXPERTS, ROUTED, TOP_K = 6144, 2048, 16, 128, 8
        LAYER_TOKENS = (128, 256, 384, 512, 8192)
        SHORT_PASSES = {8192: ((256, 1024), (256, 2048), (256, 4096))}


import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

# The Pallas decode attention measured for grouped queries and not kept
# (PERF.md 6, PR 32): a grid step takes one key-value head of one row, holds
# its slots' keys and values in VMEM and reads each once for the 8 query heads
# of its group.


def _gqa_kernel(length_ref, q_ref, k_ref, v_ref, out_ref, *, sm_scale: float):
    q, k, v = q_ref[0], k_ref[0], v_ref[0]  # (group, D), (S, D), (S, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * sm_scale
    slot = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(slot < length_ref[0], s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out_ref[0] = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale",))
def gqa_decode_attention(q, k, v, length, *, sm_scale: float):
    """``softmax(q . k) @ v``: ``q`` (B * Hkv, group, D) against ``k``, ``v``
    (B * Hkv, slots, D), slots at or past ``length`` (a scalar) masked. (B * Hkv, group, D) float32."""
    b, g, d = q.shape
    s = k.shape[1]
    return pl.pallas_call(
        functools.partial(_gqa_kernel, sm_scale=sm_scale),
        name=f"gqa_decode_g{g}_s{s}_d{d}",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, g, d), lambda i, n: (i, 0, 0)),
                pl.BlockSpec((1, s, d), lambda i, n: (i, 0, 0)),
                pl.BlockSpec((1, s, d), lambda i, n: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, g, d), lambda i, n: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, g, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",), vmem_limit_bytes=64 * 1024 * 1024),
        interpret=False,  # this tool runs on the chip or compiles for one
    )(jnp.reshape(length, (1,)).astype(jnp.int32), q.astype(k.dtype), k, v)


def variants():
    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.core import moe
    from perceiver_io_tpu.core.cache import LatentCache
    from perceiver_io_tpu.core.mla import latent_decode_attention
    from perceiver_io_tpu.ops.grouped_matmul import grouped_matmul
    from perceiver_io_tpu.ops.mla_absorb import mla_absorb

    bf = jnp.bfloat16

    def ffn(mm):
        def run(xs, sizes, w1, w3, w2):
            return mm(moe._silu_gate(mm(xs, w1, sizes), mm(xs, w3, sizes), bf), w2, sizes)
        return run

    def grouped(tile, pass_rows, combine):
        def run(x, local, weights, w1, w3, w2):
            cuts = moe._cuts(H, WIDTH, EXPERTS)._replace(row_tile=tile)
            rows = pass_rows or moe._pass_rows(local.size, EXPERTS / ROUTED, cuts)  # 0: the rows the program takes
            rows = min(rows, -(-local.size // tile) * tile)
            return moe.experts_grouped(x, local, weights, w1, w3, w2, rows, tile, combine)[0]
        return run

    def grouped_scatter(tile, pass_rows):
        """The share-held side as it stood until PR 50 (``experts_grouped`` with XLA's scatter-add of a pass's weighted
        rows into the tokens' buffer), kept here as the variant the program's combine is read against."""
        def run(x, local, weights, w1, w3, w2):
            t, k = local.shape
            rows = pass_rows or min(1024, moe._pass_rows(local.size, EXPERTS / ROUTED, moe._cuts(H, WIDTH, EXPERTS)._replace(row_tile=tile)))
            flat = local.reshape(-1)
            order = jnp.argsort(flat, stable=True).astype(jnp.int32)
            sizes = jnp.zeros((EXPERTS + 1,), jnp.int32).at[flat].add(1)[:EXPERTS]
            offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
            n_local = offsets[-1]
            padded = jnp.concatenate([order, jnp.zeros((rows,), jnp.int32)])
            w_flat = weights.reshape(-1)

            def add_pass(p, y):
                lo = p * rows
                live = (lo + jnp.arange(rows, dtype=jnp.int32)) < n_local
                pair = jax.lax.dynamic_slice(padded, (lo,), (rows,))
                token = jnp.where(live, pair // k, 0)
                in_pass = jnp.clip(offsets, lo, lo + rows) - lo
                xs, group_sizes = x[token], in_pass[1:] - in_pass[:-1]
                mm = lambda a, w: grouped_matmul(a, w, group_sizes, tm=tile)  # noqa: E731
                ys = mm(moe._silu_gate(mm(xs, w1), mm(xs, w3), x.dtype), w2)
                with jax.named_scope("moe/combine"):
                    return y.at[token].add(jnp.where(live[:, None], ys.astype(jnp.float32) * w_flat[pair][:, None], 0.0))

            return jax.lax.fori_loop(0, (n_local + rows - 1) // rows, add_pass, jnp.zeros((t, x.shape[-1]), jnp.float32))
        return run

    def dense(x, local, weights, w1, w3, w2):
        combine = (jax.nn.one_hot(local, EXPERTS, dtype=jnp.float32) * weights[:, :, None]).sum(axis=1)
        return moe.experts_dense(x, combine, w1, w3, w2)

    ragged = ffn(lambda a, w, s: jax.lax.ragged_dot(a, w, s, preferred_element_type=jnp.float32).astype(bf))
    w = [jax.ShapeDtypeStruct(s, bf) for s in ((EXPERTS, H, WIDTH), (EXPERTS, H, WIDTH), (EXPERTS, WIDTH, H))]
    rows = lambda m: jax.ShapeDtypeStruct((m, H), bf)  # noqa: E731
    sizes = jax.ShapeDtypeStruct((EXPERTS,), jnp.int32)
    out = {}
    for tm in (256, 512, 1024):
        out[f"experts_kernel/pallas_tm{tm}"] = (
            ffn(lambda a, w, s, tm=tm: grouped_matmul(a, w, s, tm=tm)), (rows(16384), sizes, *w), "kernel")
    out["experts_kernel/ragged_dot"] = (ragged, (rows(16384), sizes, *w), "kernel")
    own = moe.grouped_combine(EXPERTS, ROUTED)
    for t in LAYER_TOKENS:
        layer = (rows(t), jax.ShapeDtypeStruct((t, TOP_K), jnp.int32), jax.ShapeDtypeStruct((t, TOP_K), jnp.float32), *w)
        if t <= 2048:
            out[f"experts_layer/T{t}/dense"] = (dense, layer, "layer")
        # where every expert is held a prompt chunk takes both combines, side by side
        combines = {"/gather": "gather", "/scatter": "scatter"} if own == "gather" and t in SHORT_PASSES else {"": own}
        for tile, pass_rows in LAYER_TILINGS + SHORT_PASSES.get(t, ()):
            if t * TOP_K >= tile:
                for suffix, combine in combines.items():
                    name = f"experts_layer/T{t}/grouped_tm{tile}_rows{pass_rows}{suffix}"
                    out[name] = (grouped(tile, pass_rows, combine), layer, "layer")
                # where a share is held, the parent's scatter-add beside the program's combine, at the parent's passes of 1024
                if own != "gather" and t in SHORT_PASSES and not pass_rows:
                    out[f"experts_layer/T{t}/grouped_tm{tile}_rows1024/scatter"] = (grouped_scatter(tile, pass_rows), layer, "layer")
    out.update(product_variants())
    if own != "gather":
        out.update(combine_variants())
    if GEOM == "ling":  # the expert layer alone: its attentions are the latent cells' and ``tools/kda_ab.py``'s
        return {k: v for k, v in out.items() if "experts_layer" in k or "combine/" in k or "products/" in k}
    if GEOM == "kexaone":
        return {**{k: v for k, v in out.items() if "experts_layer" in k or "combine/" in k or "products/" in k}, **kexaone_attention_variants()}
    if GEOM == "mellum":
        from perceiver_io_tpu.core.cache import KVCache
        from perceiver_io_tpu.core.gqa import cached_decode_attention

        out = {k: v for k, v in out.items() if "experts_layer" in k or "products/" in k}
        # the prompt pass's flash forward on one 8192-token row (an attention chunk of the cell): the edge
        # tiles cut into bands of 256 rows (what ``_BAND_MAX_SHARE`` 0.75 chooses) against run whole, blocks of 1024 and 512
        import importlib

        fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")

        def flash(window, bands, block):
            def run(q, k, v):
                fa._BAND_MAX_SHARE = 0.75 if bands else 0.0  # read when the call is traced
                try:
                    return fa.flash_attention_gqa(q, k, v, 32, window=window, sm_scale=128 ** -0.5, block=block)
                finally:
                    fa._BAND_MAX_SHARE = 0.75
            return run

        fq = jax.ShapeDtypeStruct((1, 8192, 32 * 128), bf)
        fkv = jax.ShapeDtypeStruct((1, 4, 8192, 128), bf)
        for kind, window in (("window", 1024), ("full", None)):
            for bands in (True, False):
                for block in (1024, 512):
                    name = f"gqa_prefill/{kind}/{'bands' if bands else 'whole'}_block{block}"
                    out[name] = (flash(window, bands, block), (fq, fkv, fkv), "gqa")
        q = jax.ShapeDtypeStruct((32 * 4, 8, 128), bf)
        for kind, slots, length in (("full", 8448, 8320), ("window", 1024, 8320)):
            kv = jax.ShapeDtypeStruct((32 * 4, slots, 128), bf)
            n = jnp.asarray(length, jnp.int32)
            out[f"gqa_decode/{kind}/xla"] = (
                lambda q, k, v, n=n: cached_decode_attention(q, KVCache(k=k, v=v, length=n), 128 ** -0.5), (q, kv, kv), "gqa")
            out[f"gqa_decode/{kind}/pallas"] = (
                lambda q, k, v, n=n: gqa_decode_attention(q, k, v, n, sm_scale=128 ** -0.5), (q, kv, kv), "gqa")
        return out
    scale, rank, steps = 192 ** -0.5, 512, 8

    def absorbed(fused: bool):
        def run(q, rows, new):
            def body(_, carry):
                cache, acc = carry
                if fused:
                    kept, o = mla_absorb(q, new, cache.rows, cache.length, sm_scale=scale, keep=rank, out_dtype=bf)
                    cache = LatentCache(rows=kept, length=cache.length + 1)
                else:
                    cache = cache.append(new)
                    o = latent_decode_attention(q, cache, scale)[..., :rank].astype(bf)
                return cache, acc + o.astype(jnp.float32).sum()

            start = LatentCache(rows=rows, length=jnp.asarray(1200, jnp.int32))
            return jax.lax.fori_loop(0, steps, body, (start, jnp.zeros((), jnp.float32)))[1]
        return run

    for heads, capacity in ((128, 1280), (64, 1536)):
        shapes = tuple(jax.ShapeDtypeStruct(s, bf) for s in ((64, heads, 576), (64, capacity, 576), (64, 1, 576)))
        out[f"mla_absorb/h{heads}_s{capacity}/xla_x{steps}"] = (absorbed(False), shapes, "mla")
        out[f"mla_absorb/h{heads}_s{capacity}/kernel_x{steps}"] = (absorbed(True), shapes, "mla")
    out.update(mla_expand_variants())
    return out


COMBINE_TOKENS = 8192  # a prompt chunk


def _rule(name: str):
    """A context in which ``ops/grouped_matmul.py`` cuts its blocks by another rule (read when a call is traced):
    ``program`` is the module's own, ``parent`` the contraction and the column in multiples of 128 up to 1024 as
    until PR 54, ``column_1024`` the contraction whole beside the parent's column."""
    import contextlib
    import importlib

    gm = importlib.import_module("perceiver_io_tpu.ops.grouped_matmul")
    upto = lambda n: next((t for t in gm._divisors(n) if t <= 1024), n)  # noqa: E731
    rules = {"program": gm._blocks, "parent": lambda k, n, *_: (upto(k), upto(n)), "column_1024": lambda k, n, *_: (k, upto(n))}

    @contextlib.contextmanager
    def patched():
        kept, gm._blocks = gm._blocks, rules[name]
        try:
            yield gm
        finally:
            gm._blocks = kept
    return patched()


def pass_rows_and_tile(step: bool = False):
    """The rows and the row tile of the pass the geometry's cell hands the grouped kernels for a prompt chunk of 8192 tokens (or Ling's decode step of 128)."""
    from perceiver_io_tpu.core import moe

    cuts = moe._cuts(H, WIDTH, EXPERTS)
    return moe._pass_rows((128 if step else COMBINE_TOKENS) * TOP_K, EXPERTS / ROUTED, cuts), cuts.row_tile


def pass_sizes(step: bool = False):
    """The group sizes of the first pass: the held experts' pairs of a uniform routing, as many as the pass's rows hold."""
    import numpy as np

    rows, _ = pass_rows_and_tile(step)
    local = routing(128 if step else COMBINE_TOKENS).reshape(-1)
    ends = np.minimum(np.cumsum(np.bincount(local, minlength=EXPERTS + 1)[:EXPERTS]), rows)
    return np.diff(np.concatenate([[0], ends])).astype(np.int32)


def fetched_bytes(sizes, rows: int, tile: int, rule: str) -> dict:
    """What the three products of a pass fetch and write under a rule, from the visit plan and the blocks: a block is
    fetched again when its index differs from the grid step's before (with K cut, at every step)."""
    import jax.numpy as jnp
    import numpy as np

    with _rule(rule) as gm:
        plans = [gm.block_plan(rows, k, n, tile, 2) for k, n in ((H, WIDTH), (H, WIDTH), (WIDTH, H))]
    _, gid, mid, visits = (np.asarray(a) for a in gm.visit_plan(jnp.asarray(sizes), rows, tile))
    visits = int(visits)
    turns = lambda ids: int(visits > 0) + int((ids[1:visits] != ids[:visits - 1]).sum())  # noqa: E731
    out = {"visits": visits, "experts_hit": int((np.asarray(sizes) > 0).sum()), "weights": 0, "lhs": 0, "out": 0, "blocks": []}
    for p in plans:
        whole = p["tiles_k"] == 1
        out["weights"] += (turns(gid) if whole else visits * p["tiles_k"]) * p["tiles_n"] * p["rhs_block_bytes"]
        out["lhs"] += (turns(mid) if whole else visits * p["tiles_k"]) * p["tiles_n"] * p["tm"] * p["tk"] * 2
        out["out"] += turns(mid) * p["tiles_n"] * p["tm"] * p["tn"] * 2
        out["blocks"].append(f"{p['tm']}x{p['tk']}x{p['tn']}")
    out["once"] = out["experts_hit"] * 3 * H * WIDTH * 2  # the hit experts' weights read once
    return out


def product_variants():
    """The module docstring's ``products/`` variants: ``(fn, shapes, "products")`` with the rule in the name."""
    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.core import moe

    bf = jnp.bfloat16
    out = {}
    for step in ((False, True) if GEOM == "ling" else (False,)):
        rows, tile = pass_rows_and_tile(step)

        def products(rule, tile=tile):
            def run(xs, sizes, w1, w3, w2):
                with _rule(rule) as gm:  # the function under the jit: a cached trace would keep the rule it was traced with
                    mm = lambda a, w: gm.grouped_matmul.__wrapped__(a, w, sizes, tm=tile)  # noqa: E731
                    return mm(moe._silu_gate(mm(xs, w1), mm(xs, w3), bf), w2)
            return run

        shapes = (jax.ShapeDtypeStruct((rows, H), bf), jax.ShapeDtypeStruct((EXPERTS,), jnp.int32),
                  *(jax.ShapeDtypeStruct(s, bf) for s in ((EXPERTS, H, WIDTH), (EXPERTS, H, WIDTH), (EXPERTS, WIDTH, H))))
        for rule in ("program", "parent", "column_1024"):
            out[f"products/{'step' if step else 'chunk'}_m{rows}_tm{tile}/{rule}"] = (products(rule), shapes, "products")
    return out


def check_products(rule: str, tile: int, xs, sizes, w1, w2) -> dict:
    """The up and the down product under a rule against XLA's ``ragged_dot`` in float32, on the live rows: the widest
    gap as a share of the widest value (one rounding to bfloat16 is 2^-8 = 0.0039 of a value)."""
    import jax
    import jax.numpy as jnp

    def gaps(xs, sizes, w1, w2):
        live = (jnp.arange(xs.shape[0]) < sizes.sum())[:, None]
        with _rule(rule) as gm:
            mm = lambda a, w: gm.grouped_matmul.__wrapped__(a, w, sizes, tm=tile)  # noqa: E731
            up, a = mm(xs, w1), xs[:, :WIDTH]  # an expert is narrower than the hidden size at every geometry
            down = mm(a, w2)
        want_up = jax.lax.ragged_dot(xs, w1, sizes, preferred_element_type=jnp.float32)
        want_down = jax.lax.ragged_dot(a, w2, sizes, preferred_element_type=jnp.float32)
        gap = lambda got, want: jnp.where(live, jnp.abs(got.astype(jnp.float32) - want), 0).max() / jnp.abs(jnp.where(live, want, 0)).max()  # noqa: E731
        return gap(up, want_up), gap(down, want_down)

    up, down = (float(g) for g in jax.jit(gaps)(xs, sizes, w1, w2))
    return {"up_gap_share": up, "down_gap_share": down, "agrees": bool(up < 2 ** -7 and down < 2 ** -7)}


def _onehot_kernel(offsets_ref, tile_ids_ref, row_tile_ids_ref, rows_ref, weights_ref, tokens_ref, y_ref, out_ref, *, tt):
    """The add on the matrix unit (not the program's): a visit's selection of rows by token times the weighed rows,
    float32 operands at the highest precision. A run of two rows is summed inside the product, so a token's bits
    are not the scatter-add's; a dead row must be finite."""
    v = pl.program_id(0)
    tile = tile_ids_ref[v]

    @pl.when((v == 0) | (tile_ids_ref[jnp.maximum(v - 1, 0)] != tile))
    def _start():
        out_ref[...] = y_ref[...]

    weighed = rows_ref[...].astype(jnp.float32) * weights_ref[...]
    mine = tokens_ref[...] - tile * tt == jax.lax.broadcasted_iota(jnp.int32, (tt, tokens_ref.shape[1]), 0)
    out_ref[...] += jnp.dot(mine.astype(jnp.float32), weighed, precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def combine_onehot(y, rows, weights, tokens, *, row_tile: int):
    """``ops/moe_combine.py::moe_combine``'s contract with the add on the matrix unit."""
    from perceiver_io_tpu.ops.moe_combine import token_tile, visits

    (t, h), r = y.shape, rows.shape[0]
    tt = token_tile(t)
    offsets, tile_ids, row_tile_ids, num_visits = visits(tokens, t, row_tile)
    return pl.pallas_call(
        functools.partial(_onehot_kernel, tt=tt),
        name=f"moe_combine_onehot_t{t}_r{r}_h{h}",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(num_visits,),
            in_specs=[
                pl.BlockSpec((row_tile, h), lambda v, off, tid, rid: (rid[v], 0)),
                pl.BlockSpec((row_tile, 1), lambda v, off, tid, rid: (rid[v], 0)),
                pl.BlockSpec((1, row_tile), lambda v, off, tid, rid: (0, rid[v])),
                pl.BlockSpec((tt, h), lambda v, off, tid, rid: (tid[v], 0)),
            ],
            out_specs=pl.BlockSpec((tt, h), lambda v, off, tid, rid: (tid[v], 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((t, h), jnp.float32),
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=100 * 1024 * 1024),
        interpret=False,  # this tool runs on the chip or compiles for one
    )(offsets, tile_ids, row_tile_ids, rows, weights[:, None], tokens[None, :], y)


def combine_variants():
    """The way back to the tokens alone, at a prompt chunk's tokens and the rows of the program's pass (``moe._pass_rows``):
    a pass's rows (random, in the grouped kernel's dtype, sorted by expert as the seeded routing sorts them) to the
    tokens' float32 buffer. ``program`` is ``core/moe.py``'s (the pairs sorted into token order, the row gather,
    ``ops/moe_combine.py``'s kernel); ``index`` and ``index_gather`` stop after its first and second part;
    ``row_tile<n>`` runs the kernel at another row tile; ``onehot`` puts the add on the matrix unit; ``scatter_add`` is
    the parent's (XLA's scatter-add in passes of 1024 rows), ``scatter_add_whole`` the same in one pass, and
    ``scatter_add_sorted`` XLA's scatter-add over the token-ordered rows, the indices declared sorted. Every variant
    that ends in the buffer is compared with ``scatter_add`` after the run."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from perceiver_io_tpu.core import moe
    from perceiver_io_tpu.ops.moe_combine import moe_combine

    t, k = COMBINE_TOKENS, TOP_K
    cuts = moe._cuts(H, WIDTH, EXPERTS)
    rows = moe._pass_rows(t * k, EXPERTS / ROUTED, cuts)

    def sorted_pairs(local):
        flat = local.reshape(-1)
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        n_local = (flat < EXPERTS).sum()
        return order[:rows], jnp.arange(rows, dtype=jnp.int32) < n_local

    def in_token_order(local, weights):
        pair, live = sorted_pairs(local)
        return lax.sort((jnp.where(live, pair, t * k), jnp.arange(rows, dtype=jnp.int32), weights.reshape(-1)[pair]), num_keys=1)

    def program(stop, kernel=moe_combine, tile=cuts.row_tile):
        def run(ys, local, weights):
            in_order, row, w = in_token_order(local, weights)
            if stop == "index":
                return in_order, row, w
            if stop == "index_gather":
                return ys[row]
            return kernel(jnp.zeros((t, H), jnp.float32), ys[row], w, in_order // k, row_tile=tile)
        return run

    def scatter_add(pass_rows):
        def run(ys, local, weights):
            pair, live = sorted_pairs(local)
            w = weights.reshape(-1)[pair]
            y = jnp.zeros((t, H), jnp.float32)
            for lo in range(0, rows, pass_rows):  # the parent ran as many as held pairs: all of an even routing's here
                part = slice(lo, lo + pass_rows)
                y = y.at[jnp.where(live[part], pair[part] // k, 0)].add(
                    jnp.where(live[part, None], ys[part].astype(jnp.float32) * w[part, None], 0.0))
            return y
        return run

    def scatter_add_sorted(ys, local, weights):
        in_order, row, w = in_token_order(local, weights)
        live = in_order < t * k
        return jnp.zeros((t, H), jnp.float32).at[jnp.where(live, in_order // k, t - 1)].add(
            jnp.where(live[:, None], ys[row].astype(jnp.float32) * w[:, None], 0.0), indices_are_sorted=True)

    shapes = (jax.ShapeDtypeStruct((rows, H), jnp.bfloat16), jax.ShapeDtypeStruct((t, k), jnp.int32), jax.ShapeDtypeStruct((t, k), jnp.float32))
    out = {f"combine/T{t}_R{rows}/{name}": (fn, shapes, "combine") for name, fn in (
        ("scatter_add", scatter_add(1024)), ("scatter_add_whole", scatter_add(rows)), ("scatter_add_sorted", scatter_add_sorted),
        ("index", program("index")), ("index_gather", program("index_gather")), ("program", program("all")),
        ("onehot", program("all", combine_onehot)))}
    for tile in (128, 256, 512):
        if tile != cuts.row_tile and rows % tile == 0:
            out[f"combine/T{t}_R{rows}/row_tile{tile}"] = (program("all", tile=tile), shapes, "combine")
    return out


EXPAND_CHUNKS, EXPAND_ROWS, EXPAND_TOKENS = 16, 4, 1024  # a prompt pass of 64 rows in attention chunks of four


def mla_expand_variants():
    """The expanded latent attention of one layer over a prompt pass's stacked batch, as the body of the chunk
    loop that ``decoder_lm.prefill`` runs (an isolated call hands XLA the layouts its author wrote down; inside
    the loop the chunk is a slice of the one buffer): the heads-major path (``flash_attention`` on
    concatenated 192-channel operands) against the token-major one (``flash_attention_mla``, ``w_uq``'s column
    sets taken in front of the loop), at both latent cells' configurations."""
    import importlib

    import jax
    import jax.numpy as jnp

    from benchmarks import run
    from perceiver_io_tpu.core import mla
    from perceiver_io_tpu.models.text import decoder_lm

    bf = jnp.bfloat16
    out = {}
    for workload, family in (("dsv3-ep16-decode-b64", "deepseek_v3"), ("longcat-ep32-decode-b64", "longcat_flash")):
        config = run.load_json("configs", run.load_json("workloads", workload)["config"])
        c = importlib.import_module(f"benchmarks.families.{family}").Family(config).model().config
        attn = mla.MultiHeadLatentAttention(c, dtype=bf, param_dtype=bf)
        pos = jnp.broadcast_to(jnp.arange(EXPAND_TOKENS, dtype=jnp.int32)[None], (EXPAND_ROWS, EXPAND_TOKENS))
        shapes = jax.eval_shape(lambda attn=attn, pos=pos: attn.init(
            jax.random.PRNGKey(0), jnp.zeros((EXPAND_ROWS, EXPAND_TOKENS, c.hidden_size), bf), pos, method="expand"))
        leaves, tree = jax.tree.flatten(shapes)

        def expand(token_major, attn=attn, pos=pos, tree=tree, c=c):
            def run_(x, *weights):
                params = jax.tree.unflatten(tree, weights)
                supported = mla.mla_flash_supported  # read when the call is traced
                mla.mla_flash_supported = supported if token_major else lambda *_: False
                try:
                    if token_major:
                        params = {**params, mla.VIEWS: mla.expand_views(params["params"], c, bf)}
                    return decoder_lm._over_chunks(lambda chunk: attn.apply(params, chunk, pos, method="expand"), x)
                finally:
                    mla.mla_flash_supported = supported
            return run_

        x = jax.ShapeDtypeStruct((EXPAND_CHUNKS, EXPAND_ROWS, EXPAND_TOKENS, c.hidden_size), bf)
        for name, token_major in (("heads_major", False), ("token_major", True)):
            out[f"mla_expand/h{c.num_attention_heads}/{name}_x{EXPAND_CHUNKS}"] = (expand(token_major), (x, *leaves), "mla_expand")

        def cut_at_diagonal(run_):
            """Each masked band of the diagonal tile in two, the slots before its first row unmasked (seven bands
            where four): measured 50% slower than the kernel as it is and not adopted (PERF.md 6, PR 42)."""
            fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")

            def tiles(n_blocks, block, window):
                def cut(band):
                    r0, r1, c0, c1, masked = band
                    edge = min(c1, r0 // fa.LANES * fa.LANES)
                    return [band] if not masked or edge <= c0 else [(r0, r1, c0, edge, False), (r0, r1, edge, c1, True)]
                return tuple((lo, hi, tuple(b for band in bands for b in (cut(band) if lo == hi == 0 else [band])))
                             for lo, hi, bands in plain(n_blocks, block, window))

            plain = fa._gqa_tiles

            def patched(*args):
                fa._gqa_tiles = tiles
                try:
                    return run_(*args)
                finally:
                    fa._gqa_tiles = plain
            return patched

        out[f"mla_expand/h{c.num_attention_heads}/token_major_cut_x{EXPAND_CHUNKS}"] = (cut_at_diagonal(expand(True)), (x, *leaves), "mla_expand")
    return out


def kexaone_attention_variants():
    """The speculative step's attention and the prompt pass's window kernel at K-EXAONE's sizes (the module docstring)."""
    import importlib

    import jax
    import jax.numpy as jnp
    from jax import lax

    from jax.experimental.layout import Layout, with_layout_constraint

    from perceiver_io_tpu.core.cache import RaggedKVCache, RaggedWindowKVCache
    from perceiver_io_tpu.core.gqa import cached_verify_attention
    from perceiver_io_tpu.ops.gqa_verify import gqa_verify, gqa_verify_supported

    fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")
    bf = jnp.bfloat16
    batch, kv_heads, group, d, steps = 64, 8, 8, 128, 8
    rows = batch * kv_heads
    row_major = Layout(major_to_minor=(0, 1, 2))
    out = {}

    def step_loop(make, what, path="xla"):
        def run(q, k, v, k_new, v_new):
            cache = make(k, v)
            window = getattr(cache, "window", None)

            def body(_, carry):
                cache, acc = carry
                if path == "kernel":  # the program's: write and attend in one call over the caches it updates in place
                    k, v, o = gqa_verify(q, k_new, v_new, cache.k, cache.v, cache.length, heads=kv_heads, window=window, sm_scale=d ** -0.5)
                    return cache.replace(k=k, v=v).keep(jnp.ones((batch,), jnp.int32)), acc + o.sum()
                cache = cache.write(k_new, v_new)
                if path == "xla_row_major":  # XLA's products over the carry the kernel asks for
                    cache = cache.replace(k=with_layout_constraint(cache.k, row_major), v=with_layout_constraint(cache.v, row_major))
                if what == "write":  # the write alone: read one row back so that it is not dead
                    return cache.keep(jnp.ones((batch,), jnp.int32)), acc + cache.k[:, :1].astype(jnp.float32).sum()
                o = cached_verify_attention(q, cache, cache.visible(2, group), d ** -0.5)
                return cache.keep(jnp.ones((batch,), jnp.int32)), acc + o.sum()

            return lax.fori_loop(0, steps, body, (cache, jnp.zeros((), jnp.float32)))[1]
        return run

    q = jax.ShapeDtypeStruct((rows, 2 * group, d), bf)
    new = jax.ShapeDtypeStruct((rows, 2, d), bf)
    length = 1280 + jnp.arange(batch, dtype=jnp.int32) % 16  # a length a row: one row in sixteen writes two tiles
    # the parent's capacities (PR 34: no whole tiles, XLA's path alone) and the program's (whole bfloat16 tiles)
    for kind, make in (
        ("full", lambda k, v: RaggedKVCache(k=k, v=v, length=length)),
        ("window", lambda k, v: RaggedWindowKVCache(k=k, v=v, length=length, window=128)),
    ):
        for slots in ((1537, 1552) if kind == "full" else (129, 144)):
            kv = jax.ShapeDtypeStruct((rows, slots, d), bf)
            for what, path in (("write", "xla"), ("write_and_attend", "xla"), ("write_and_attend", "xla_row_major"),
                               ("write_and_attend", "kernel")):
                if path == "kernel" and not gqa_verify_supported(kv.shape, bf, kv_heads, 2, group, 128 if kind == "window" else None):
                    continue
                out[f"gqa_verify/{kind}/s{slots}/{what}_{path}_x{steps}"] = (step_loop(make, what, path), (q, kv, kv, new, new), "gqa_verify")

    def flash(bands, block):
        def run(q, k, v):
            fa._BAND_MAX_SHARE = 0.75 if bands else 0.0  # read when the call is traced
            try:
                return fa.flash_attention_gqa(q, k, v, 64, window=128, sm_scale=d ** -0.5, block=block)
            finally:
                fa._BAND_MAX_SHARE = 0.75
        return run

    fq = jax.ShapeDtypeStruct((4, 1024, 64 * d), bf)
    fkv = jax.ShapeDtypeStruct((4, kv_heads, 1024, d), bf)
    for bands in (True, False):
        for block in (1024, 512, 256, 128):
            out[f"gqa_prefill/window/{'bands' if bands else 'whole'}_block{block}"] = (flash(bands, block), (fq, fkv, fkv), "gqa")
    return out


def carried_caches(compiled_text: str) -> list:
    """The layouts of the (rows, slots, D) arrays a compiled variant's loop carries: ``{2,1,0}`` is row-major, ``{2,0,1}`` slot-major."""
    import re

    carries = re.findall(r"= \(([^()]*(?:\([^()]*\)[^()]*)*)\) while\(", compiled_text)
    return sorted({m for carry in carries for m in re.findall(r"bf16\[512,\d+,128\]\{[\d,]+", carry)})


def group_sizes(skew: bool):
    import numpy as np

    live = 8192
    if EXPERTS != 16:
        return np.full((EXPERTS,), live // EXPERTS, np.int32)
    if skew:
        s = np.array([3000, 40, 900, 0, 512, 511, 513, 100, 1, 700, 300, 200, 200, 115, 50, 50])
        return (s * live // s.sum()).astype(np.int32)
    return np.full((EXPERTS,), live // EXPERTS, np.int32)


def routing(tokens: int):
    """``local`` (T, 8): each token's 8 distinct experts of those routed over, uniform; the held ones keep their index, the rest read ``EXPERTS``."""
    import numpy as np

    rng = np.random.default_rng(tokens)
    chosen = np.argsort(rng.random((tokens, ROUTED)), axis=1)[:, :TOP_K]
    return np.where(chosen < EXPERTS, chosen, EXPERTS).astype(np.int32)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--compile-only", action="store_true")
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--geom", default="dsv3", choices=("dsv3", "mellum", "kexaone", "ling"))
    p.add_argument("--only", default="", help="substrings of variant names, comma-separated; a variant runs if it holds one")
    args = p.parse_args(argv)
    set_geometry(args.geom)
    wanted = lambda name: any(part in name for part in args.only.split(","))  # noqa: E731
    import jax
    import jax.numpy as jnp
    import numpy as np

    if args.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        import importlib

        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        importlib.import_module("perceiver_io_tpu.ops.grouped_matmul")._interpret_default = lambda: False
        importlib.import_module("perceiver_io_tpu.ops.flash_attention")._interpret_default = lambda: False
        one = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
        for name, (fn, shapes, _) in variants().items():
            if wanted(name):
                shapes = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one) for s in shapes]
                try:
                    compiled = jax.jit(fn).lower(*shapes).compile()
                    print(f"{name}: compiles" + (f"; carries {carried_caches(compiled.as_text())}" if "gqa_verify" in name else ""), flush=True)
                except Exception as e:  # noqa: BLE001 - report every variant
                    print(f"{name}: REFUSED {type(e).__name__}: {str(e)[:400]}", flush=True)
        return 0

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("tools/moe_ab.py: needs a TPU (or --compile-only)")
    from benchmarks.lib import trace

    results, drawn, combined, per_token = {}, {}, {}, None
    for name, (fn, shapes, kind) in variants().items():
        if not wanted(name):
            continue
        for skew in ((False, True) if kind == "kernel" else (False,)):
            key = jax.random.PRNGKey(0)
            operands = []
            for s in shapes:
                key, k = jax.random.split(key)
                if s.dtype == jnp.int32 and kind == "products":
                    operands.append(jnp.asarray(pass_sizes(step="/step_" in name)))
                elif s.dtype == jnp.int32:
                    operands.append(jnp.asarray(group_sizes(skew) if kind == "kernel" else routing(s.shape[0])))
                elif s.dtype == jnp.float32:  # a combine's weights differ pair by pair, so that a wrong pairing of row and weight shows
                    operands.append(jax.random.uniform(k, s.shape, jnp.float32, 0.1, 0.6) if kind == "combine"
                                    else jnp.full(s.shape, 2.5 / TOP_K, jnp.float32))
                else:  # one draw a shape: the experts' weights are 1.4 GB
                    if s.shape not in drawn:
                        drawn[s.shape] = (jax.random.normal(k, s.shape, jnp.float32) * 0.05).astype(s.dtype)
                    operands.append(drawn[s.shape])
            label = name + ("/skewed" if skew else "")
            try:
                run = jax.jit(fn)
                if kind == "products":  # the products against XLA's own, before any timing
                    rule, tile = name.rsplit("/", 1)[1], pass_rows_and_tile("/step_" in name)[1]
                    checked = check_products(rule, tile, operands[0], operands[1], operands[2], operands[4])
                    moved = fetched_bytes(np.asarray(operands[1]), operands[0].shape[0], tile, rule)
                    print(f"{label}: against ragged_dot {checked}; fetches {moved}", flush=True)
                if kind == "gqa_verify":
                    print(f"{label}: carries {carried_caches(run.lower(*operands).compile().as_text())}", flush=True)
                jax.block_until_ready(run(*operands))
                trace_dir = tempfile.mkdtemp(prefix="moe-ab-")
                jax.profiler.start_trace(trace_dir)
                with jax.profiler.TraceAnnotation("bench/window"):
                    for _ in range(args.iters):
                        out = run(*operands)
                    jax.block_until_ready(out)
                jax.profiler.stop_trace()
                data = trace.load_xplane(trace.find_xplane(trace_dir))
                events = data["devices"][sorted(data["devices"])[0]]
                busy_ms = trace.busy_ns(events) / 1e6 / args.iters
                top = trace.top(trace.totals_by_name(events), 8)
                results[label] = {"device_ms": busy_ms, "top": [[n, 1e3 * s / args.iters] for n, s in top]}
                print(f"{label}: {busy_ms:.4f} ms a call; {results[label]['top']}", flush=True)
                if kind == "products":
                    results[label].update(checked, fetches=moved)
                if kind == "combine" and getattr(out, "shape", None) == (COMBINE_TOKENS, H):
                    combined[label] = np.asarray(out)
                    per_token = np.bincount(np.nonzero(np.asarray(operands[1]) < EXPERTS)[0], minlength=COMBINE_TOKENS)
                if kind == "mla_expand":  # a chunk's time, the Pallas kernels' share and XLA's apart
                    leaf = {n: ns for n, ns in trace.totals_by_name(events).items() if not n.startswith("while")}
                    kernels = sum(ns for n, ns in leaf.items() if n.startswith(("flash_", "rotary_")))
                    per = 1e6 * args.iters * EXPAND_CHUNKS
                    chunk = dict(chunk_ms=busy_ms / EXPAND_CHUNKS, kernels_chunk_ms=kernels / per,
                                 xla_chunk_ms=(sum(leaf.values()) - kernels) / per)
                    results[label].update(chunk)
                    print(f"{label}: a chunk {chunk['chunk_ms']:.4f} ms: kernels {chunk['kernels_chunk_ms']:.4f}, "
                          f"XLA {chunk['xla_chunk_ms']:.4f}", flush=True)
            except Exception as e:  # noqa: BLE001 - one variant failing must not lose the others
                results[label] = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
                print(f"{label}: FAILED {results[label]['error']}", flush=True)
            del operands
    want = next((y for label, y in combined.items() if label.endswith("/scatter_add")), None)
    for label, y in combined.items():  # every way back against the parent's: the widest difference, and the bits of tokens with at most two rows
        if want is not None and y is not want:
            few = per_token <= 2
            gap = dict(max_abs_gap=float(np.abs(y - want).max()), scale=float(np.abs(want).max()),
                       tokens_of_at_most_two_rows=int(few.sum()), of_them_not_bit_equal=int((y[few] != want[few]).any(axis=1).sum()),
                       tokens_of_more_rows=int((~few).sum()), of_them_not_bit_equal_more=int((y[~few] != want[~few]).any(axis=1).sum()))
            results[label].update(gap)
            print(f"{label} against scatter_add: {gap}", flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_ab.json" if GEOM == "dsv3" else f"chiprun_out/moe_ab_{GEOM}.json", "w") as f:
        json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
