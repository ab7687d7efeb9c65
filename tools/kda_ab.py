"""Same-process check and A/B of Kimi delta attention's two kernels at the shapes of ``ling3-ep4-decode-b128-p2k``.

    python tools/kda_ab.py                      # on the chip
    python tools/kda_ab.py --compile-only       # for a described v5e, no chip

**Agreement first** (``--check-length`` tokens of one row, 32 heads of 128,
bfloat16 operands as the cell runs them, log-decays from near 0 down to the
lower bound of -5): the chunk kernel's ``o`` and final state, at every variant,
against the token-by-token ``lax.scan`` in float32 at "highest" precision (what
the plain reference computes), and the step's kernel against ``kda_update`` in
XLA from that state. Interpret mode on the CPU cannot show what Mosaic does with
a contraction over the sublanes, a masked lane sum or an aliased block; this can.

**The prompt pass's side**: ``--rows`` rows of ``--length`` tokens (an
attention chunk of the cell's prompt pass) through ``ops.kda.kda_chunked`` at
each of ``--variants`` (``<chunk>x<sub-chunk>x<heads a grid step>``, the
program's among them), ``--calls`` calls back to back between two host clock
readings, against ``lib/ling_cost.py::chunk_cost`` (the recurrence's floor, the
same at every cut) over the peaks. The time is ``kda_chunked`` whole, with the
running sums that XLA runs in front of the kernel.

**Where q, k and v are shaped** (PR 52): the same call from the projections' raw
outputs (bfloat16, ``(rows, length, H * D)``) and the three tap tables:
``xla_shape+kernel`` (the parent's program: ``core/kda.py``'s
``KimiDeltaAttention._shape`` itself as XLA fuses it, then the kernel on its
results; ``xla_shape`` is that front alone), ``fused`` (the program:
``kda_chunked`` with ``taps``, the convolution, silu and l2 norm on the tiles the
kernel holds), ``fused_mxu_sum`` (the same with the norm's lane sum as a float32
product with ones on the matrix unit: ``ops.kda._shaped`` replaced while it is
traced, tool only) and ``shape_pass+kernel`` (the issue's fallback: the same tile
arithmetic as a Pallas pass of its own in front of the kernel, q, k and v through
HBM once more; ``shape_pass`` is that front alone, tool only). Agreement comes
first there too: each against the float32 token scan over XLA's shaped inputs,
at a length that is whole chunks and at one that is not, two rows.

**A step's side**: eight decode steps of one layer's state in one program, the
state ``f32[batch, 32, 128, 128]`` carried by the loop and donated so that it is
updated in place as in the generator: the kernel (``kda_step``) and XLA's form
of the same update (``kda_update``), a step's time against the state's bytes
read and written once at the HBM peak.

PERF.md 6 (PR 49, PR 52) has the readings; the program has no switch for the variants.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import importlib
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from benchmarks.lib import ling_cost
from perceiver_io_tpu.core.kda import KimiDeltaAttention
from perceiver_io_tpu.core.ssm import rows_window
from perceiver_io_tpu.ops import kda

HEADS, D = 32, 128
CFG = {"num_attention_heads": HEADS, "head_dim": D}
BF16_PEAK, HBM_PEAK = 197e12, 819e9  # a v5e (benchmarks/peaks.json)
LOWER_BOUND = -5.0
STEPS = 8
TAPS = 4  # short_conv_kernel_size


def inputs(rng, rows, length, dtype):
    """Unit-length ``q`` (scaled) and ``k`` as the l2 norms leave them, ``v`` after a silu, log-decays a channel that
    run from a thousandth to the lower bound, steps around a half."""
    def unit(shape):
        t = rng.normal(size=shape)
        return t / np.linalg.norm(t, axis=-1, keepdims=True)

    shape = (rows, length, HEADS, D)
    v = rng.normal(size=shape)
    forget = np.exp(rng.uniform(np.log(1e-3), np.log(-LOWER_BOUND), size=(rows, 1, HEADS, D))) * np.exp(0.5 * rng.normal(size=shape))
    g = -np.minimum(forget, -LOWER_BOUND * 0.9999)
    beta = 1.0 / (1.0 + np.exp(-rng.normal(size=shape[:3])))
    return (jnp.asarray(unit(shape) * D ** -0.5, dtype), jnp.asarray(unit(shape), dtype), jnp.asarray(v / (1.0 + np.exp(-v)), dtype),
            jnp.asarray(g, jnp.float32), jnp.asarray(beta, jnp.float32))


def flat(t):
    return t.reshape(t.shape[0], t.shape[1], -1)


def chunked(variant):
    chunk, sub, block = variant
    return jax.jit(lambda q, k, v, g, b: kda.kda_chunked(flat(q), flat(k), flat(v), flat(g), b, HEADS, chunk, sub, block))


def raw_inputs(rng, rows, length, dtype):
    """The projections' raw outputs of unit scale, three tables of ``TAPS`` taps, and gates as :func:`inputs` draws them."""
    raw = tuple(jnp.asarray(rng.normal(size=(rows, length, HEADS * D)), dtype) for _ in range(3))
    taps = tuple(jnp.asarray(0.5 * rng.normal(size=(TAPS, HEADS * D)), dtype) for _ in range(3))
    _, _, _, g, beta = inputs(rng, rows, length, dtype)
    return raw, taps, flat(g), beta


def xla_shape(raw, taps):
    """``core/kda.py::KimiDeltaAttention._shape`` itself over whole rows, as the parent's prompt pass ran it in XLA (of the
    mixer's leaves it reads the three tap tables; the others are zeros of their shapes that nothing reads)."""
    mixer = KimiDeltaAttention(types.SimpleNamespace(hidden_size=8, num_attention_heads=HEADS, head_dim=D, short_conv_kernel_size=TAPS,
                                                     kda_lower_bound=LOWER_BOUND, rms_norm_eps=1e-6, init_scale=0.02), dtype=raw[0].dtype)
    windows = [rows_window(t, TAPS) for t in raw]
    leaves = jax.tree.map(lambda leaf: jnp.zeros(leaf.shape, leaf.dtype),
                          jax.eval_shape(lambda: mixer.init(jax.random.PRNGKey(0), windows, method="_shape"))["params"])
    return mixer.apply({"params": {**leaves, "conv_q": taps[0], "conv_k": taps[1], "conv_v": taps[2]}}, windows, method="_shape")


def shaped_mxu_sum(q_ref, k_ref, v_ref, taps_ref, tail_ref, lanes, n_taps):
    """``ops.kda._shaped`` with the l2 norms' lane sums on the matrix unit (tool only): a float32 product with ones, every lane the row's sum."""
    def one(x_ref, which, unit, scale):
        y = jax.nn.silu(kda._convolved(x_ref, taps_ref, tail_ref, which, lanes, n_taps))
        if unit:
            y = y * lax.rsqrt(kda._dot(y * y, jnp.ones((y.shape[1], kda.LANES), jnp.float32), kda._NN) + kda.L2_EPS)
        return (y * scale if scale != 1.0 else y).astype(x_ref.dtype)

    return one(q_ref, 0, True, D ** -0.5), one(k_ref, 1, True, 1.0), one(v_ref, 2, False, 1.0)


def _pass_kernel(q_ref, k_ref, v_ref, taps_ref, q_out, k_out, v_out, tail_ref, *, heads_block, head_dim, n_taps):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        tail_ref[...] = jnp.zeros_like(tail_ref)

    for h in range(heads_block):
        lanes = slice(h * head_dim, (h + 1) * head_dim)
        for out, tile in zip((q_out, k_out, v_out), kda._shaped(q_ref, k_ref, v_ref, taps_ref, tail_ref, lanes, n_taps)):
            out[0, :, lanes] = tile


def shape_pass(raw, taps, chunk=kda.CHUNK, heads_block=kda.HEADS_BLOCK):
    """The issue's fallback, tool only: the kernel's tile arithmetic (``ops.kda._shaped``, the raw rows' tails carried in
    VMEM) as a Pallas pass of its own in front of the kernel, q, k and v written back to HBM in bfloat16."""
    b, length, width = raw[0].shape
    chunk = kda.chunk_of(length, chunk)
    pad = -length % chunk
    raw = [jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in raw]
    block = pl.BlockSpec((1, chunk, heads_block * D), lambda r, hb, j: (r, j, hb))
    shaped = pl.pallas_call(
        functools.partial(_pass_kernel, heads_block=heads_block, head_dim=D, n_taps=TAPS),
        name=f"kda_shape_l{length}_c{chunk}_h{HEADS}_d{D}",
        grid=(b, HEADS // heads_block, (length + pad) // chunk),
        in_specs=[block, block, block, pl.BlockSpec((3, TAPS, heads_block * D), lambda r, hb, j: (0, 0, hb))],
        out_specs=[block, block, block],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in raw],
        scratch_shapes=[pltpu.VMEM((3, kda._TAIL, heads_block * D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=importlib.import_module("perceiver_io_tpu.ops.flash_attention")._interpret_default(),
    )(*raw, jnp.stack([t.astype(jnp.float32) for t in taps]))
    return [t[:, :length] for t in shaped]


def shaping_variants():
    """name -> (``ops.kda._shaped``'s stand-in while the variant is traced, or None; the function of (raw, taps, g, beta))."""
    program = (kda.CHUNK, kda.SUB, kda.HEADS_BLOCK)
    fused = lambda raw, taps, g, b: kda.kda_chunked(*raw, g, b, HEADS, *program, taps=taps)  # noqa: E731
    apart = lambda raw, taps, g, b: kda.kda_chunked(*xla_shape(raw, taps), g, b, HEADS, *program)  # noqa: E731
    by_pass = lambda raw, taps, g, b: kda.kda_chunked(*shape_pass(raw, taps), g, b, HEADS, *program)  # noqa: E731
    return {"xla_shape": (None, lambda raw, taps, g, b: xla_shape(raw, taps)), "xla_shape+kernel": (None, apart),
            "fused": (None, fused), "fused_mxu_sum": (shaped_mxu_sum, fused),
            "shape_pass": (None, lambda raw, taps, g, b: shape_pass(raw, taps)), "shape_pass+kernel": (None, by_pass)}


@contextlib.contextmanager
def shaped_by(stand_in):
    """``ops.kda._shaped`` replaced by ``stand_in`` (where one is given) for what is traced inside: the program has one form and no switch."""
    if stand_in is None:
        yield
        return
    kept, kda._shaped = kda._shaped, stand_in
    jax.clear_caches()
    try:
        yield
    finally:
        kda._shaped = kept
        jax.clear_caches()


def step_loop(update):
    def run(s, q, k, v, g, beta):
        def body(i, carry):
            s, acc = carry
            o, s = update(q[i], k[i], v[i], g[i], beta[i], s)
            return s, acc + o

        return lax.fori_loop(0, STEPS, body, (s, jnp.zeros(q.shape[1:], jnp.float32)))

    return jax.jit(run, donate_argnums=(0,))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--variants", nargs="+", default=["64x16x4", "128x16x4", "128x16x8", "128x16x2", "128x16x1", "256x16x4", "128x8x4"])
    p.add_argument("--rows", type=int, default=2)
    p.add_argument("--length", type=int, default=2048)
    p.add_argument("--check-length", type=int, default=640)
    p.add_argument("--batch", type=int, default=128, help="rows of the step's update")
    p.add_argument("--calls", type=int, default=8)
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--compile-only", action="store_true")
    p.add_argument("--check-only", action="store_true", help="agreement alone, no timing")
    p.add_argument("--out", default=None, help="write the table as JSON here")
    args = p.parse_args()
    variants = [tuple(int(n) for n in v.split("x")) for v in args.variants]
    program = (kda.CHUNK, kda.SUB, kda.HEADS_BLOCK)
    if program not in variants:
        variants.append(program)

    bf16, f32 = jnp.bfloat16, jnp.float32
    rows_state = (args.batch, HEADS, D, D)
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        one = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
        importlib.import_module("perceiver_io_tpu.ops.flash_attention")._interpret_default = lambda: False
        jax.config.update("jax_enable_compilation_cache", False)
        sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
        tokens = (args.rows, args.length, HEADS, D)
        for variant in variants:
            try:
                c = chunked(variant).lower(sds(tokens, bf16), sds(tokens, bf16), sds(tokens, bf16), sds(tokens, f32), sds(tokens[:3], f32)).compile()
                print(f"chunk {variant}: compiled, temporaries {c.memory_analysis().temp_size_in_bytes} bytes", flush=True)
            except Exception as e:  # noqa: BLE001 - report every variant
                print(f"chunk {variant}: REFUSED {type(e).__name__}: {str(e)[:300]}", flush=True)
        flat_tokens, table = (args.rows, args.length, HEADS * D), (TAPS, HEADS * D)
        raw, taps = (sds(flat_tokens, bf16),) * 3, (sds(table, bf16),) * 3
        for name, (replacement, fn) in shaping_variants().items():
            try:
                with shaped_by(replacement):
                    c = jax.jit(fn).lower(raw, taps, sds(flat_tokens, f32), sds(tokens[:3], f32)).compile()
                print(f"shaping {name}: compiled, temporaries {c.memory_analysis().temp_size_in_bytes} bytes", flush=True)
            except Exception as e:  # noqa: BLE001 - report every variant
                print(f"shaping {name}: REFUSED {type(e).__name__}: {str(e)[:600]}", flush=True)
        one_step = (STEPS, args.batch, HEADS, D)
        for name, update in (("kernel", kda.kda_step), ("xla", kda.kda_update)):
            c = step_loop(update).lower(sds(rows_state, f32), sds(one_step, bf16), sds(one_step, bf16), sds(one_step, bf16),
                                        sds(one_step, f32), sds(one_step[:3], f32)).compile()
            print(f"step {name}: compiled, temporaries {c.memory_analysis().temp_size_in_bytes} bytes", flush=True)
        return
    if jax.default_backend() != "tpu":
        raise SystemExit("kda_ab checks and times the kernels on the chip: no TPU here (use --compile-only)")

    rng = np.random.default_rng(0)
    table = {"check": {}, "chunk": {}, "shaping": {}, "step": {}}

    # ---- agreement
    q, k, v, g, beta = inputs(rng, 1, args.check_length, bf16)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = jax.jit(kda.kda_reference)(q.astype(f32), k.astype(f32), v.astype(f32), g, beta)
    want_o = np.asarray(want_o)
    for variant in variants:
        try:
            o, s = chunked(variant)(q, k, v, g, beta)
            err = {"o": float(np.abs(np.asarray(o, np.float32).reshape(want_o.shape) - want_o).max()), "o_scale": float(np.abs(want_o).max()),
                   "s": float(jnp.abs(s - want_s).max() / jnp.abs(want_s).max()), "finite": bool(jnp.isfinite(s).all())}
        except Exception as e:  # noqa: BLE001 - one variant failing must not lose the others
            err = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        table["check"]["x".join(map(str, variant))] = err
        print(f"check chunk {variant}: {json.dumps(err)}", flush=True)
    for length in (args.check_length, args.check_length - 37):  # whole chunks; a last chunk padded, real tokens under a pad token's taps
        raw, taps, g, beta = raw_inputs(rng, 2, length, bf16)  # two rows: row 1 must not see row 0's last tokens
        heads_of = lambda t: t.reshape(2, length, HEADS, D)  # noqa: E731
        with jax.default_matmul_precision("highest"):
            scan_o, scan_s = jax.jit(lambda raw, taps, g, b: kda.kda_reference(
                *(heads_of(t.astype(f32)) for t in xla_shape(raw, taps)), heads_of(g), b))(raw, taps, g, beta)
        scan_o = np.asarray(scan_o).reshape(2, length, HEADS * D)
        for name, (replacement, fn) in shaping_variants().items():
            if name in ("xla_shape", "shape_pass"):
                continue
            try:
                with shaped_by(replacement):
                    o, s = jax.jit(fn)(raw, taps, g, beta)
                    err = {"o": float(np.abs(np.asarray(o, np.float32) - scan_o).max()), "o_scale": float(np.abs(scan_o).max()),
                           "s": float(jnp.abs(s - scan_s).max() / jnp.abs(scan_s).max()), "finite": bool(jnp.isfinite(s).all())}
            except Exception as e:  # noqa: BLE001
                err = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
            table["check"][f"{name}/l{length}"] = err
            print(f"check shaping {name} at {length} tokens: {json.dumps(err)}", flush=True)
    q1, k1, v1, g1, b1 = (t[:, 0] for t in inputs(rng, args.batch, 1, bf16))
    state = lambda: jnp.broadcast_to(want_s, rows_state) + 0.0  # noqa: E731
    want = jax.jit(kda.kda_update)(q1, k1, v1, g1, b1, state())
    got = kda.kda_step(q1, k1, v1, g1, b1, state())
    err = {name: float(jnp.abs(a - b).max() / jnp.abs(b).max()) for name, a, b in zip(("o", "s"), got, want)}
    table["check"]["step"] = err
    print(f"check step (relative to the largest element): {json.dumps(err)}", flush=True)

    if args.check_only:
        return

    # ---- the prompt pass's side
    q, k, v, g, beta = inputs(rng, args.rows, args.length, bf16)
    cost = ling_cost.chunk_cost(CFG, args.rows, args.length)
    floor_ms = 1e3 * max(cost["flops"] / BF16_PEAK, cost["bytes"] / HBM_PEAK)
    for variant in variants:
        name = "x".join(map(str, variant))
        try:
            fn = chunked(variant)
            jax.block_until_ready(fn(q, k, v, g, beta))
            times = []
            for _ in range(args.rounds):
                t0 = time.perf_counter()
                out = [fn(q, k, v, g, beta) for _ in range(args.calls)]
                jax.block_until_ready(out)
                times.append((time.perf_counter() - t0) / args.calls)
            ms = 1e3 * min(times)
            table["chunk"][name] = {"ms_a_call": ms, "ms_a_row": ms / args.rows, "roofline_share": 100 * floor_ms / ms}
            print(f"chunk {name}: {ms:.3f} ms for {args.rows} rows of {args.length} against {floor_ms:.3f} ms at the roofline "
                  f"({json.dumps(table['chunk'][name])}); rounds {[round(1e3 * t, 3) for t in times]}", flush=True)
        except Exception as e:  # noqa: BLE001
            table["chunk"][name] = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
            print(f"chunk {name}: FAILED {table['chunk'][name]['error']}", flush=True)

    # ---- where q, k and v are shaped
    raw, taps, g, beta = raw_inputs(rng, args.rows, args.length, bf16)
    for name, (replacement, fn) in shaping_variants().items():
        try:
            with shaped_by(replacement):
                fn = jax.jit(fn)
                jax.block_until_ready(fn(raw, taps, g, beta))
                times = []
                for _ in range(args.rounds):
                    t0 = time.perf_counter()
                    out = [fn(raw, taps, g, beta) for _ in range(args.calls)]
                    jax.block_until_ready(out)
                    times.append((time.perf_counter() - t0) / args.calls)
            table["shaping"][name] = {"ms_a_call": 1e3 * min(times)}
            print(f"shaping {name}: {1e3 * min(times):.3f} ms for {args.rows} rows of {args.length}; rounds {[round(1e3 * t, 3) for t in times]}", flush=True)
        except Exception as e:  # noqa: BLE001
            table["shaping"][name] = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
            print(f"shaping {name}: FAILED {table['shaping'][name]['error']}", flush=True)

    # ---- a step's side
    qs, ks, vs, gs, bs = (jnp.stack(t) for t in zip(*[tuple(a[:, 0] for a in inputs(rng, args.batch, 1, bf16)) for _ in range(STEPS)]))
    least_ms = 1e3 * 2 * args.batch * HEADS * D * D * 4 / HBM_PEAK
    for name, update in (("kernel", kda.kda_step), ("xla", kda.kda_update)):
        try:
            fn = step_loop(update)
            jax.block_until_ready(fn(state(), qs, ks, vs, gs, bs))
            times = []
            for _ in range(args.rounds):
                s = state()
                jax.block_until_ready(s)
                t0 = time.perf_counter()
                jax.block_until_ready(fn(s, qs, ks, vs, gs, bs))
                times.append((time.perf_counter() - t0) / STEPS)
            table["step"][name] = {"ms_a_step": 1e3 * min(times), "hbm_share": 100 * least_ms / (1e3 * min(times))}
            print(f"step {name}: {1e3 * min(times):.3f} ms a step of {args.batch} rows against {least_ms:.3f} ms for the state's bytes at the HBM peak", flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"step {name}: {type(e).__name__}: {str(e)[:200]}", flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
