"""Same-process interleaved A/B of batch/microbatch GEOMETRY on the flagship
train step, under the round-4 default configuration (host-sampled dropout
indices + bf16 Adam moments).

Motivation: the microbatch lever (round 3) and the host/bf16m levers
(round 4) were each measured at fixed geometry b=4, mb=2. But the levers
shift the optimum: per-sample fwd+bwd is cheapest at chunk size 2, while the
optimizer update is a fixed ~1 ms/step cost that larger batches amortize
over more samples. b=8 mb=4 keeps the cheap b=2 chunks AND halves the
per-sample optimizer tax — never measured. Variants are geometry strings
``b<batch>mb<microbatch>``; throughput (tok/s) normalizes per sample so
geometries are directly comparable.

    python tools/geom_ab.py [--variants b4mb2 b8mb4 b8mb2 b6mb3 b2mb1]
"""

from __future__ import annotations

import argparse
import functools
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from bench import flagship_config, interleaved_slopes


def main():
    from perceiver_io_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--seq-len", type=int, default=16384)
    p.add_argument("--latents", type=int, default=1024)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--reps", type=int, default=4)
    p.add_argument(
        "--variants", nargs="*", default=["b4mb2", "b8mb4", "b8mb2", "b6mb3", "b2mb1"]
    )
    args = p.parse_args()

    from perceiver_io_tpu.models.text import CausalLanguageModel
    from perceiver_io_tpu.training import TrainState, clm_loss_fn, make_optimizer
    from perceiver_io_tpu.training.loop import make_train_step
    from perceiver_io_tpu.training.prefix_dropout import sample_prefix_keep_idx

    n = args.seq_len
    prefix_len = n - args.latents
    config = flagship_config(args.seq_len, args.latents)
    model = CausalLanguageModel(config, dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    init_x = jnp.asarray(rng.integers(0, 262, size=(1, args.latents + 1)))
    params = model.init(jax.random.PRNGKey(0), init_x, prefix_len=1)
    loss_fn = clm_loss_fn(model.apply, max_latents=args.latents)

    def build(variant):
        m = re.fullmatch(r"b(\d+)mb(\d+)", variant)
        if not m:
            raise SystemExit(f"bad variant {variant!r}; expected e.g. b4mb2")
        b, mb = int(m.group(1)), int(m.group(2))
        t = rng.integers(0, 262, size=(b, n + 1))
        batch = {
            "labels": jnp.asarray(t[:, 1:]),
            "input_ids": jnp.asarray(t[:, :-1]),
            "pad_mask": None,
            "prefix_keep_idx": jnp.asarray(
                sample_prefix_keep_idx(rng, b, prefix_len, config.cross_attention_dropout)
            ),
        }
        tx = make_optimizer(1e-3, gradient_clip=1.0, moment_dtype="bfloat16")
        state = TrainState.create(model.apply, params, tx, jax.random.PRNGKey(1))
        step = make_train_step(loss_fn, jit=False, microbatch=mb)

        @functools.partial(jax.jit, static_argnums=2)
        def run(state, batch, k):
            def body(c, _):
                l, s = c
                s, metrics = step(s, batch)
                return (l + metrics["loss"], s), ()

            (l, _), _ = jax.lax.scan(body, (jnp.float32(0), state), None, length=k)
            return l

        return b, (lambda k: float(run(state, batch, k)))

    n_short, n_long = 2, 2 + args.steps
    runs, batch_of = {}, {}
    for name in args.variants:
        batch_of[name], runs[name] = build(name)
        t0 = time.perf_counter()
        runs[name](n_short)
        runs[name](n_long)
        print(f"{name}: compiled in {time.perf_counter() - t0:.0f}s", flush=True)

    meds = interleaved_slopes(runs, n_short, n_long, reps=args.reps)
    print(f"{'variant':<10} {'ms/step':>8} {'tok/s':>12}")
    for v in args.variants:
        med = meds[v]
        if med is None:
            print(f"{v:<10}  all slope estimates non-positive (host stall?) — rerun")
            continue
        print(f"{v:<10} {med * 1e3:8.3f} {batch_of[v] * n / med:12.0f}")


if __name__ == "__main__":
    main()
