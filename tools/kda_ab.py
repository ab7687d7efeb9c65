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

**A step's side**: eight decode steps of one layer's state in one program, the
state ``f32[batch, 32, 128, 128]`` carried by the loop and donated so that it is
updated in place as in the generator: the kernel (``kda_step``) and XLA's form
of the same update (``kda_update``), a step's time against the state's bytes
read and written once at the HBM peak.

PERF.md 6 (PR 49) has the readings; the program has no switch for the variants.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.lib import ling_cost
from perceiver_io_tpu.ops import kda

HEADS, D = 32, 128
CFG = {"num_attention_heads": HEADS, "head_dim": D}
BF16_PEAK, HBM_PEAK = 197e12, 819e9  # a v5e (benchmarks/peaks.json)
LOWER_BOUND = -5.0
STEPS = 8


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
        import importlib

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
        one_step = (STEPS, args.batch, HEADS, D)
        for name, update in (("kernel", kda.kda_step), ("xla", kda.kda_update)):
            c = step_loop(update).lower(sds(rows_state, f32), sds(one_step, bf16), sds(one_step, bf16), sds(one_step, bf16),
                                        sds(one_step, f32), sds(one_step[:3], f32)).compile()
            print(f"step {name}: compiled, temporaries {c.memory_analysis().temp_size_in_bytes} bytes", flush=True)
        return
    if jax.default_backend() != "tpu":
        raise SystemExit("kda_ab checks and times the kernels on the chip: no TPU here (use --compile-only)")

    rng = np.random.default_rng(0)
    table = {"check": {}, "chunk": {}, "step": {}}

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
