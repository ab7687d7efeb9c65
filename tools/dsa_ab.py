"""Same-process check and timing of the sparse latent attention's four kernels at the shapes of ``dots3-ep8-decode-b4-p32k``.

    python tools/dsa_ab.py                      # on the chip
    python tools/dsa_ab.py --check-only         # the agreement alone (a minute)

**Agreement first** (one row of ``--check-length`` tokens, bfloat16 operands as
the cell runs them), each kernel of ``ops/dsa.py`` against the same arithmetic in
XLA (``core/dsa.py``): the index scores of a chunk of queries at two chunk
offsets, hidden keys ``-inf`` on both sides; the selection as ``lax.top_k``'s set,
on scores with ties at the threshold and on a chunk whose first queries have fewer
keys than ``index_topk``, written into a buffer whose other rows must stay; the
flash forward under the selection's mask and the window flash forward against a
masked softmax in float32. Interpret mode on the CPU cannot show what Mosaic does
with a lane broadcast of a one-lane slice, an int8 tile, a bitcast or an aliased
block; this can.

**Then the times**, ``--calls`` calls back to back between two host clock
readings, at the cell's shapes (one row of 32 768 tokens; a chunk of 2048
queries; 16 heads a pass), against ``benchmarks/lib/dots3_cost.py`` (the
mechanism by its definition) over the peaks, and the selection against XLA's
``core.dsa.topk_mask`` and ``lax.top_k`` on the same scores.

PERF.md 6 (PR 55) has the readings; the program has no switch for the variants.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perceiver_io_tpu.core import dsa
from perceiver_io_tpu.ops import dsa as kernels

fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")

N, CHUNK, TOPK, WINDOW = 32768, 2048, 2048, 513
INDEX_HEADS, INDEX_DIM, GROUP = 64, 128, 16
BF16_PEAK, HBM_PEAK = 197e12, 819e9  # a v5e (benchmarks/peaks.json)
SM_SCALE = 192 ** -0.5


def index_inputs(rng, n_q, n, dtype=jnp.bfloat16):
    ks = jax.random.split(rng, 3)
    return (jax.random.normal(ks[0], (1, n_q, INDEX_HEADS, INDEX_DIM), dtype), jax.random.normal(ks[1], (1, n, INDEX_DIM), dtype),
            jax.random.normal(ks[2], (1, n_q, INDEX_HEADS), jnp.float32) * (INDEX_HEADS * INDEX_DIM) ** -0.5)


def flash_inputs(rng, n, heads, dtype=jnp.bfloat16):
    ks = jax.random.split(rng, 4)
    return (jax.random.normal(ks[0], (1, n, heads * 128), dtype), jax.random.normal(ks[1], (1, n, heads * 64), dtype),
            jax.random.normal(ks[2], (1, n, heads * 256), dtype), jax.random.normal(ks[3], (1, n, 64), dtype))


def masked_softmax(q_nope, q_rope, kv, k_rope, keep, heads):
    """The masked attention in float32 at "highest" precision from the same bfloat16 operands."""
    b, n, _ = q_nope.shape
    f = lambda t: t.astype(jnp.float32)  # noqa: E731
    kvh = f(kv).reshape(b, n, heads, 256)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bihc,bjhc->bhij", f(q_nope).reshape(b, n, heads, 128), kvh[..., :128])
        s = s + jnp.einsum("bihc,bjc->bhij", f(q_rope).reshape(b, n, heads, 64), f(k_rope))
        p = jax.nn.softmax(jnp.where(keep[:, None], s * SM_SCALE, -jnp.inf), axis=-1)
        return jnp.einsum("bhij,bjhc->bihc", p, kvh[..., 128:]).reshape(b, n, heads * 128)


def check(length: int) -> bool:
    ok = True

    def say(what, good, detail=""):
        nonlocal ok
        ok &= bool(good)
        print(f"check {what}: {'ok' if good else 'FAILED'} {detail}", flush=True)

    rng = jax.random.PRNGKey(0)
    chunk = min(CHUNK, length // 2)
    keys_q, keys_k, keys_w = index_inputs(rng, length, length)
    for first in (0, length - chunk):
        q, w = keys_q[:, first:first + chunk], keys_w[:, first:first + chunk]
        got = np.asarray(kernels.index_scores(q.reshape(1, chunk, -1), keys_k, w, INDEX_HEADS, jnp.int32(first)))
        want = np.asarray(dsa.causal_scores(dsa.index_scores(q, keys_k, w), first))
        fin = np.isfinite(want)
        say(f"index scores, chunk at {first}", (np.isfinite(got) == fin).all() and np.abs(np.where(fin, got - want, 0)).max() < 1e-3,
            f"widest difference {np.abs(np.where(fin, got - want, 0)).max():.2e} on scores up to {np.abs(want[fin]).max():.2f}")
    for first, what in ((0, "a chunk whose first queries keep every key"), (length - chunk, "a chunk past index_topk")):
        scores = dsa.causal_scores(dsa.index_scores(keys_q[:, first:first + chunk], keys_k, keys_w[:, first:first + chunk]), first)
        scores = jnp.round(scores * 64) / 64  # ties, some at the threshold
        topk = min(TOPK, length // 4)
        got = np.asarray(kernels.select_mask_into(jnp.full((1, length, length), 7, jnp.int8), scores, topk, jnp.int32(first)))
        _, chosen = lax.top_k(scores, topk)
        want = np.zeros(scores.shape, bool)
        np.put_along_axis(want, np.asarray(chosen), True, axis=-1)
        want &= np.isfinite(np.asarray(scores))
        rows = got[:, first:first + chunk].astype(bool)
        others = np.delete(got, np.s_[first:first + chunk], axis=1)
        xla = np.asarray(dsa.topk_mask(scores, topk))
        say(f"selection, {what}", (rows == want).all() and (others == 7).all() and (xla == want).all(),
            f"{int((rows != want).sum())} of {want.size} differ from lax.top_k's set; {int(want.sum(-1).min())} to {int(want.sum(-1).max())} kept a query")
    heads = 4
    q_nope, q_rope, kv, k_rope = flash_inputs(jax.random.PRNGKey(1), length, heads)
    scores = dsa.causal_scores(jax.random.normal(jax.random.PRNGKey(2), (1, length, length)), 0)
    keep = dsa.topk_mask(scores, min(TOPK, length // 4))
    got = np.asarray(kernels.flash_attention_mla_masked(q_nope, q_rope, kv, k_rope, keep.astype(jnp.int8), heads, sm_scale=SM_SCALE).astype(jnp.float32))
    want = np.asarray(masked_softmax(q_nope, q_rope, kv, k_rope, keep, heads))
    say("flash forward under the mask", np.abs(got - want).max() < 0.05, f"widest difference {np.abs(got - want).max():.4f} on values up to {np.abs(want).max():.2f}")
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (1, length, heads * 256), jnp.bfloat16)
    k_low, k_high, v = (jax.random.normal(k, (1, length, heads * 128), jnp.bfloat16) for k in ks[1:])
    got = np.asarray(kernels.flash_attention_mla_window(q, k_low, k_high, v, heads, WINDOW, sm_scale=0.0625).astype(jnp.float32))
    f = lambda t, d: t.astype(jnp.float32).reshape(1, length, heads, d)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bihc,bjhc->bhij", f(q, 256), jnp.concatenate([f(k_low, 128), f(k_high, 128)], axis=-1)) * 0.0625
        i, j = jnp.arange(length)[:, None], jnp.arange(length)[None, :]
        p = jax.nn.softmax(jnp.where((j <= i) & (j > i - WINDOW), s, -jnp.inf), axis=-1)
        want = np.asarray(jnp.einsum("bhij,bjhc->bihc", p, f(v, 128)).reshape(1, length, heads * 128))
    say("window flash forward", np.abs(got - want).max() < 0.05, f"widest difference {np.abs(got - want).max():.4f} on values up to {np.abs(want).max():.2f}")
    return ok


def timed(name, fn, args, calls, least=None):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    ms = (time.perf_counter() - t0) / calls * 1e3
    note = f"; {1e3 * least:.2f} ms at the roofline of the definition, {100 * 1e3 * least / ms:.1f}%" if least else ""
    print(f"time {name}: {ms:.3f} ms a call{note}", flush=True)
    return ms


def times(calls: int):
    from benchmarks import run
    from benchmarks.lib import dots3_cost as cost

    config = run.load_json("configs", "dots3-note-ep8")
    cfg = run.importlib.import_module("benchmarks.families.dots3").Family(config).cfg
    q, k, w = index_inputs(jax.random.PRNGKey(4), CHUNK, N)
    first = jnp.int32(N - CHUNK)
    score = jax.jit(lambda q, k, w, f: kernels.index_scores(q.reshape(1, CHUNK, -1), k, w, INDEX_HEADS, f))
    select = jax.jit(lambda s: kernels.select_mask(s, TOPK))
    select_xla = jax.jit(lambda s: dsa.topk_mask(s, TOPK).astype(jnp.int8))
    top_k = jax.jit(lambda s: lax.top_k(s, TOPK)[1])
    q_nope, q_rope, kv, k_rope = flash_inputs(jax.random.PRNGKey(5), N, GROUP)
    masked = jax.jit(lambda a, b, c, d, m: kernels.flash_attention_mla_masked(a, b, c, d, m, GROUP, sm_scale=SM_SCALE))
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    wq = jax.random.normal(ks[0], (1, N, GROUP * 256), jnp.bfloat16)
    k_low, k_high, v = (jax.random.normal(k_, (1, N, GROUP * 128), jnp.bfloat16) for k_ in ks[1:])
    window = jax.jit(lambda q, a, b, v: kernels.flash_attention_mla_window(q, a, b, v, GROUP, WINDOW, sm_scale=0.0625))
    scores = score(q, k, w, first)
    # a chunk at the row's end sees every key: its share of a layer's causal pairs is 2 chunk N / (N (N + 1)) of a row's
    share = CHUNK * (N - CHUNK / 2) / cost.causal_pairs(N)
    ic = cost.index_score_cost(cfg, 1, N)
    timed("index scores, the last chunk of 2048 queries", score, (q, k, w, first), calls, max(ic["flops"] / BF16_PEAK, ic["bytes"] / HBM_PEAK) * share)
    timed("selection kernel, 2048 queries of 32768 scores", select, (scores,), calls)
    timed("selection in XLA (core.dsa.topk_mask), the same scores", select_xla, (scores,), max(calls // 4, 1))
    timed("lax.top_k, the same scores", top_k, (scores,), max(calls // 4, 1))
    mask = jnp.tile(select(scores), (1, N // CHUNK, 1))
    mask = jnp.where(jnp.arange(N)[None, :, None] >= jnp.arange(N)[None, None, :], mask, 0).astype(jnp.int8)
    mask = mask.at[:, jnp.arange(N), jnp.arange(N)].set(1)  # every query keeps a key
    sc = cost.sparse_attend_cost(cfg, 1, N)
    heads_share = GROUP / cfg["num_attention_heads"]
    timed("masked flash, 16 heads of one row", masked, (q_nope, q_rope, kv, k_rope, mask), max(calls // 4, 1),
          max(sc["flops"] / BF16_PEAK, sc["bytes"] / HBM_PEAK) * heads_share)
    wc = cost.window_attend_cost(cfg, 1, N)
    timed("window flash, 16 heads of one row", window, (wq, k_low, k_high, v), calls,
          max(wc["flops"] / BF16_PEAK, wc["bytes"] / HBM_PEAK) * GROUP / cfg["swa_num_attention_heads"])


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--check-only", action="store_true")
    p.add_argument("--check-length", type=int, default=1024)
    p.add_argument("--calls", type=int, default=8)
    args = p.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit(f"tools/dsa_ab.py: no TPU (JAX found {jax.default_backend()}); tests/test_tpu_compile.py compiles the kernels with none")
    ok = check(args.check_length)
    print(json.dumps({"checks_ok": ok}), flush=True)
    if not args.check_only:
        times(args.calls)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
