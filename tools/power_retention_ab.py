"""Same-process check and A/B of power retention's two kernels at the shapes of ``brumby-pp8-decode-b32-p4k``.

    python tools/power_retention_ab.py                      # on the chip
    python tools/power_retention_ab.py --compile-only       # for a described v5e, no chip

**Agreement first** (``--check-length`` tokens of one row, 40 query heads on 8
key-value heads of 128, bfloat16 operands as the cell runs them): the chunked
kernel's ``y`` against the attention form in float32 at "highest" precision (a
masked matrix a head: what the plain reference computes), its final ``S`` and
``z`` against the token-by-token ``lax.scan`` in XLA, and the step's kernel
against ``retention_update`` in XLA from that state. Interpret mode on the CPU
cannot show what Mosaic does with a rotation, a transpose or a masked store at
128 lanes; this can.

**The prompt pass's side**: one row of ``--length`` tokens through
``ops.power_retention.power_retention`` at each of ``--chunks`` (the program's
``CHUNK`` among them), ``--calls`` calls back to back between two host clock
readings (a call is tens of milliseconds: the dispatch is nothing beside it),
against ``lib/brumby_cost.py::chunk_cost`` (the state form's floor, the same at
every chunk length) over the bf16 peak. The time is ``power_retention`` whole,
with the turns and cumulative sums that XLA runs around the kernel.

**A step's side**: eight decode steps of one layer's state in one program, the
state ``f32[batch, 8, 8320, 128]`` carried by the loop and donated so that it is
updated in place as in the generator: the kernel (``power_retention_step``)
and XLA's form of the same update (``retention_update``), a step's time against
the state's bytes read and written once at the HBM peak.

PERF.md 6 (PR 46) has the readings; the program has no switch for the variants.
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

from benchmarks.lib import brumby_cost
from perceiver_io_tpu.ops import power_retention as pr

HEADS, KV_HEADS, D = 40, 8, 128
CFG = {"num_attention_heads": HEADS, "num_key_value_heads": KV_HEADS, "head_dim": D}
BF16_PEAK, HBM_PEAK = 197e12, 819e9  # a v5e (benchmarks/peaks.json)
STEPS = 8


def attention_form(q, k, v, log_gamma, eps=pr.EPS):
    """The plain reference's form, float32 at highest: ``q`` (1, T, H, D), ``k`` and ``v`` (1, T, Hkv, D), a head at a time."""
    t, group = q.shape[1], q.shape[2] // k.shape[2]
    f32 = jnp.float32
    lam = jnp.cumsum(log_gamma.astype(f32), axis=1)
    seen = jnp.tril(jnp.ones((t, t), bool))

    def head(h):
        g = h // group
        scores = jnp.dot(q[0, :, h].astype(f32), k[0, :, g].astype(f32).T, precision="highest")
        a = jnp.where(seen, scores * scores * jnp.exp(jnp.where(seen, lam[0, :, g][:, None] - lam[0, :, g][None, :], 0.0)), 0.0)
        return jnp.dot(a, v[0, :, g].astype(f32), precision="highest") / (a.sum(-1, keepdims=True) + eps)

    return jnp.stack([head(h) for h in range(q.shape[2])], axis=1)[None]


def inputs(rng, rows, length, dtype):
    """Unit-norm-ish ``q`` and ``k`` as the q/k norms leave them, ``v`` of the projections' scale, gates that forget 1e-4 to 1e-2 a token."""
    normal = lambda shape, scale=1.0: jnp.asarray(scale * rng.normal(size=shape), dtype)  # noqa: E731
    forget = np.exp(rng.uniform(np.log(1e-4), np.log(1e-2), size=(rows, 1, KV_HEADS))) * np.exp(0.5 * rng.normal(size=(rows, length, KV_HEADS)))
    return (normal((rows, length, HEADS, D)), normal((rows, length, KV_HEADS, D)), normal((rows, length, KV_HEADS, D), 0.5),
            jnp.asarray(np.log1p(-np.minimum(forget, 0.5)), jnp.float32))


def flat(t):
    return t.reshape(t.shape[0], t.shape[1], -1)


def step_loop(update):
    def run(s, z, q, k, v, gamma):
        def body(i, carry):
            s, z, acc = carry
            y, s, z = update(q[i], k[i], v[i], gamma[i], s, z)
            return s, z, acc + y

        return lax.fori_loop(0, STEPS, body, (s, z, jnp.zeros(q.shape[1:], jnp.float32)))

    return jax.jit(run, donate_argnums=(0, 1))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--chunks", type=int, nargs="+", default=[256, 512, 1024])
    p.add_argument("--length", type=int, default=4096)
    p.add_argument("--check-length", type=int, default=1536)
    p.add_argument("--batch", type=int, default=32, help="rows of the step's update")
    p.add_argument("--calls", type=int, default=8)
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--compile-only", action="store_true")
    p.add_argument("--check-only", action="store_true", help="agreement alone, no timing")
    p.add_argument("--out", default=None, help="write the table as JSON here")
    args = p.parse_args()

    bf16, f32 = jnp.bfloat16, jnp.float32
    rows_state = (args.batch, KV_HEADS, pr.feature_rows(D), D)
    if args.compile_only:
        import importlib

        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        one = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
        importlib.import_module("perceiver_io_tpu.ops.flash_attention")._interpret_default = lambda: False
        jax.config.update("jax_enable_compilation_cache", False)
        sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
        for chunk in args.chunks:
            c = jax.jit(lambda q, k, v, g, chunk=chunk: pr.power_retention(q, k, v, g, HEADS, chunk)).lower(
                sds((1, args.length, HEADS * D), bf16), sds((1, args.length, KV_HEADS * D), bf16),
                sds((1, args.length, KV_HEADS * D), bf16), sds((1, args.length, KV_HEADS), f32)).compile()
            print(f"chunk {chunk}: compiled, temporaries {c.memory_analysis().temp_size_in_bytes} bytes", flush=True)
        for name, update in (("kernel", pr.power_retention_step), ("xla", pr.retention_update)):
            c = step_loop(update).lower(sds(rows_state, f32), sds(rows_state[:2] + (D // 2 + 1, D), f32), sds((STEPS, args.batch, HEADS, D), bf16),
                                        sds((STEPS, args.batch, KV_HEADS, D), bf16), sds((STEPS, args.batch, KV_HEADS, D), bf16),
                                        sds((STEPS, args.batch, KV_HEADS), f32)).compile()
            print(f"step {name}: compiled, temporaries {c.memory_analysis().temp_size_in_bytes} bytes", flush=True)
        return
    if jax.default_backend() != "tpu":
        raise SystemExit("power_retention_ab checks and times the kernels on the chip: no TPU here (use --compile-only)")

    rng = np.random.default_rng(0)
    table = {"check": {}, "chunk": {}, "step": {}}

    # ---- agreement
    q, k, v, g = inputs(rng, 1, args.check_length, bf16)
    want_y = np.asarray(jax.jit(attention_form)(q, k, v, g))
    _, (want_s, want_z) = jax.jit(pr.power_retention_reference)(q, k, v, g)
    for chunk in args.chunks:
        y, s, z = pr.power_retention(flat(q), flat(k), flat(v), g, HEADS, chunk)
        err = {"y": float(np.abs(np.asarray(y, np.float32).reshape(want_y.shape) - want_y).max()), "y_scale": float(np.abs(want_y).max()),
               "s": float(jnp.abs(s - want_s).max() / jnp.abs(want_s).max()), "z": float(jnp.abs(z - want_z).max() / jnp.abs(want_z).max())}
        table["check"][f"chunk{chunk}"] = err
        print(f"check chunk {chunk}: {json.dumps(err)}", flush=True)
    q1, k1, v1, g1 = inputs(rng, args.batch, 1, bf16)
    state = lambda: (jnp.broadcast_to(want_s, rows_state) + 0.0, jnp.broadcast_to(want_z, rows_state[:2] + want_z.shape[2:]) + 0.0)  # noqa: E731
    want = jax.jit(pr.retention_update)(q1[:, 0], k1[:, 0], v1[:, 0], jnp.exp(g1[:, 0]), *state())
    got = pr.power_retention_step(q1[:, 0], k1[:, 0], v1[:, 0], jnp.exp(g1[:, 0]), *state())
    err = {name: float(jnp.abs(a - b).max() / jnp.abs(b).max()) for name, a, b in zip(("y", "s", "z"), got, want)}
    table["check"]["step"] = err
    print(f"check step (relative to the largest element; the XLA form rounds its product's operands to bfloat16): {json.dumps(err)}", flush=True)

    if args.check_only:
        return

    # ---- the prompt pass's side
    q, k, v, g = inputs(rng, 1, args.length, bf16)
    fns = {chunk: jax.jit(lambda q, k, v, g, chunk=chunk: pr.power_retention(q, k, v, g, HEADS, chunk)) for chunk in args.chunks}
    for fn in fns.values():
        jax.block_until_ready(fn(flat(q), flat(k), flat(v), g))
    for chunk, fn in fns.items():
        times = []
        for _ in range(args.rounds):
            t0 = time.perf_counter()
            out = [fn(flat(q), flat(k), flat(v), g) for _ in range(args.calls)]
            jax.block_until_ready(out)
            times.append((time.perf_counter() - t0) / args.calls)
        cost = brumby_cost.chunk_cost(CFG, 1, args.length)
        ms = 1e3 * min(times)
        table["chunk"][str(chunk)] = {"ms_a_row": ms, "tflops": cost["flops"] / min(times) / 1e12,
                                      "roofline_share": 100 * max(cost["flops"] / BF16_PEAK, cost["bytes"] / HBM_PEAK) / min(times)}
        print(f"chunk {chunk}: {ms:.3f} ms a row of {args.length} ({json.dumps(table['chunk'][str(chunk)])}); rounds {[round(1e3 * t, 3) for t in times]}", flush=True)

    # ---- a step's side
    qs, ks, vs, gs = (jnp.stack(t) for t in zip(*[tuple(a[:, 0] for a in inputs(rng, args.batch, 1, bf16)) for _ in range(STEPS)]))
    least_ms = 1e3 * 2 * args.batch * KV_HEADS * (pr.feature_rows(D) * (D + 1)) * 4 / HBM_PEAK
    for name, update in (("kernel", pr.power_retention_step), ("xla", pr.retention_update)):
        try:
            fn = step_loop(update)
            jax.block_until_ready(fn(*state(), qs, ks, vs, jnp.exp(gs)))
            times = []
            for _ in range(args.rounds):
                s, z = state()
                jax.block_until_ready((s, z))
                t0 = time.perf_counter()
                jax.block_until_ready(fn(s, z, qs, ks, vs, jnp.exp(gs)))
                times.append((time.perf_counter() - t0) / STEPS)
            table["step"][name] = {"ms_a_step": 1e3 * min(times), "hbm_share": 100 * least_ms / (1e3 * min(times))}
            print(f"step {name}: {1e3 * min(times):.3f} ms a step of {args.batch} rows against {least_ms:.3f} ms for the state's bytes at the HBM peak", flush=True)
        except Exception as e:  # XLA's form holds the state twice: it may not fit beside the kernel's
            print(f"step {name}: {type(e).__name__}: {str(e)[:200]}", flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
