"""Time, on the chip and in one process, the ways through the held experts
and through the absorbed decode attention at DeepSeek-V3's widths, so that
``core/moe.py`` and ``core/mla.py`` keep one per path on a measurement:

    chiprun -- python tools/moe_ab.py [--only experts_layer] [--compile-only]

- the grouped product alone (8192 live rows of 16384, 16 experts, even and
  skewed group sizes): the Pallas kernel (``ops/grouped_matmul.py``) against
  ``jax.lax.ragged_dot``;
- the expert layer's two paths as the program runs them, whole (sort, gather,
  kernels, scatter-add against one weighted einsum), on ``T`` tokens routed
  uniformly over 256 experts of which 16 are held: ``moe.experts_dense``
  against ``moe.experts_grouped`` at several row tiles and rows a pass (``rows0``:
  the rows ``moe._pass_rows`` gives), for ``T`` from a decode step's 64 to a
  prompt chunk's 8192: where the two cross is ``moe._GROUPED_MIN_TOKENS``, the
  fastest tile ``moe._ROW_TILE``, the fastest rows a pass at 8192 tokens
  ``moe._PASS_ROWS``;
- absorbed decode attention (batch 64, 128 heads, 1280 x 576 cache): XLA's two
  batched products against a Pallas kernel kept in this file (measured, not kept in the program).

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
# (row tile, rows a pass): 0 rows is what the program takes (``moe._pass_rows``: at most 1024)
LAYER_TILINGS = ((128, 0), (256, 0), (512, 0))
# other rows a pass at a prompt chunk's tokens: the scatter-add of a pass has a sweet spot
SHORT_PASSES = {8192: ((256, 512), (256, 768), (256, 1536), (256, 2048), (256, 3072), (256, 5120), (256, 8192))}


# ---------------------------------------------------------------------------
# The Pallas decode attention that was measured and not kept (PERF.md 6, PR
# 28): a grid step takes one row of the batch, holds that row's whole latent
# cache in VMEM and reads it once for scores and values both. On the v5e the
# kernel alone takes 0.173 ms against 0.196 ms for XLA's two batched products
# (which run at the HBM peak), and XLA puts 0.35 ms of layout copies of the
# cache in front of it. It lives here so that the measurement can be made
# again; the program runs ``core/mla.py::latent_decode_attention``.
# ---------------------------------------------------------------------------

import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

def _kernel(length_ref, q_ref, rows_ref, out_ref, *, sm_scale: float):
    q, rows = q_ref[0], rows_ref[0]  # (H, W), (S, W)
    s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * sm_scale
    slot = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(slot < length_ref[0], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out_ref[0] = jnp.dot(p.astype(rows.dtype), rows, preferred_element_type=jnp.float32).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale",))
def mla_decode_attention(q_cat, rows, length, *, sm_scale: float):
    """``softmax(q . row) @ row``: ``q_cat`` (B, H, W) against ``rows``
    (B, capacity, W), slots at or past ``length`` (a scalar) masked. Returns
    (B, H, W) float32, all ``W`` channels (the caller keeps the latent ones)."""
    b, h, w = q_cat.shape
    s = rows.shape[1]
    return pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale),
        name=f"mla_decode_h{h}_s{s}_w{w}",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, h, w), lambda i, n: (i, 0, 0)),
                pl.BlockSpec((1, s, w), lambda i, n: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, h, w), lambda i, n: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, w), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",), vmem_limit_bytes=64 * 1024 * 1024),
        interpret=False,  # this tool runs on the chip or compiles for one
    )(jnp.reshape(length, (1,)).astype(jnp.int32), q_cat.astype(rows.dtype), rows)


def variants():
    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.core import moe
    from perceiver_io_tpu.core.cache import LatentCache
    from perceiver_io_tpu.core.mla import latent_decode_attention
    from perceiver_io_tpu.ops.grouped_matmul import grouped_matmul

    bf = jnp.bfloat16

    def ffn(mm):
        def run(xs, sizes, w1, w3, w2):
            return mm(moe._silu_gate(mm(xs, w1, sizes), mm(xs, w3, sizes), bf), w2, sizes)
        return run

    def grouped(tile, pass_rows):
        def run(x, local, weights, w1, w3, w2):
            moe._ROW_TILE = tile  # read when the path is traced
            rows = pass_rows or moe._pass_rows(local.size, EXPERTS / ROUTED)  # 0: the rows the program takes
            return moe.experts_grouped(x, local, weights, w1, w3, w2, min(rows, -(-local.size // tile) * tile))[0]
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
    for t in LAYER_TOKENS:
        layer = (rows(t), jax.ShapeDtypeStruct((t, TOP_K), jnp.int32), jax.ShapeDtypeStruct((t, TOP_K), jnp.float32), *w)
        if t <= 2048:
            out[f"experts_layer/T{t}/dense"] = (dense, layer, "layer")
        for tile, pass_rows in LAYER_TILINGS + SHORT_PASSES.get(t, ()):
            if t * TOP_K >= tile:
                out[f"experts_layer/T{t}/grouped_tm{tile}_rows{pass_rows}"] = (grouped(tile, pass_rows), layer, "layer")
    q = jax.ShapeDtypeStruct((64, 128, 576), bf)
    cache = jax.ShapeDtypeStruct((64, 1280, 576), bf)
    scale = 192 ** -0.5
    out["mla_decode/xla"] = (
        lambda q, r: latent_decode_attention(q, LatentCache(rows=r, length=jnp.asarray(1200, jnp.int32)), scale),
        (q, cache), "mla")
    out["mla_decode/pallas"] = (
        lambda q, r: mla_decode_attention(q, r, jnp.asarray(1200, jnp.int32), sm_scale=scale), (q, cache), "mla")
    return out


def group_sizes(skew: bool):
    import numpy as np

    live = 8192
    if skew:
        s = np.array([3000, 40, 900, 0, 512, 511, 513, 100, 1, 700, 300, 200, 200, 115, 50, 50])
        return (s * live // s.sum()).astype(np.int32)
    return np.full((EXPERTS,), live // EXPERTS, np.int32)


def routing(tokens: int):
    """``local`` (T, 8): each token's 8 distinct experts of 256, uniform; the held ones (0 to 15) keep their index, the rest read 16."""
    import numpy as np

    rng = np.random.default_rng(tokens)
    chosen = np.argsort(rng.random((tokens, ROUTED)), axis=1)[:, :TOP_K]
    return np.where(chosen < EXPERTS, chosen, EXPERTS).astype(np.int32)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--compile-only", action="store_true")
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--only", default="", help="substrings of variant names, comma-separated; a variant runs if it holds one")
    args = p.parse_args(argv)
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
        one = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
        for name, (fn, shapes, _) in variants().items():
            if wanted(name):
                shapes = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one) for s in shapes]
                try:
                    jax.jit(fn).lower(*shapes).compile()
                    print(f"{name}: compiles", flush=True)
                except Exception as e:  # noqa: BLE001 - report every variant
                    print(f"{name}: REFUSED {type(e).__name__}: {str(e)[:400]}", flush=True)
        return 0

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("tools/moe_ab.py: needs a TPU (or --compile-only)")
    from benchmarks.lib import trace

    results, drawn = {}, {}
    for name, (fn, shapes, kind) in variants().items():
        if not wanted(name):
            continue
        for skew in ((False, True) if kind == "kernel" else (False,)):
            key = jax.random.PRNGKey(0)
            operands = []
            for s in shapes:
                key, k = jax.random.split(key)
                if s.dtype == jnp.int32:
                    operands.append(jnp.asarray(group_sizes(skew) if kind == "kernel" else routing(s.shape[0])))
                elif s.dtype == jnp.float32:
                    operands.append(jnp.full(s.shape, 2.5 / TOP_K, jnp.float32))
                else:  # one draw a shape: the experts' weights are 1.4 GB
                    if s.shape not in drawn:
                        drawn[s.shape] = (jax.random.normal(k, s.shape, jnp.float32) * 0.05).astype(s.dtype)
                    operands.append(drawn[s.shape])
            label = name + ("/skewed" if skew else "")
            try:
                run = jax.jit(fn)
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
                top = trace.top(trace.totals_by_name(events), 4)
                results[label] = {"device_ms": busy_ms, "top": [[n, 1e3 * s / args.iters] for n, s in top]}
                print(f"{label}: {busy_ms:.4f} ms a call; {results[label]['top']}", flush=True)
            except Exception as e:  # noqa: BLE001 - one variant failing must not lose the others
                results[label] = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
                print(f"{label}: FAILED {results[label]['error']}", flush=True)
            del operands
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_ab.json", "w") as f:
        json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
