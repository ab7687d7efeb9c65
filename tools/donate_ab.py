"""Same-process A/B of buffer donation on the per-call train step (VERDICT
r3 item 1c: the 2.2 ms "copies" profile category).

The scan-based harnesses (bench.py, tools/step_ab.py) thread the state
through a `lax.scan` carry inside ONE jitted program, so `donate_argnums`
never comes into play there — XLA already aliases the carry. Donation
matters on the boundary the real Trainer uses: `make_train_step(...,
jit=True)` called once per step from Python, where an undonated state
forces XLA to allocate fresh param/moment output buffers (~590 MB at the
flagship's 37M-param f32 state + bf16 moments) and copy-retire them.

Measures the sustained per-call step time (two chain lengths of back-to-back
dispatches; the final loss fetch and the fixed dispatch cost cancel in the
slope) with donation on vs off, plus the in-graph scan step for reference.

    python tools/donate_ab.py [--steps 24] [--reps 4]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def main():
    from perceiver_io_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--seq-len", type=int, default=16384)
    p.add_argument("--latents", type=int, default=1024)
    p.add_argument("--batch-size", type=int, default=4)
    p.add_argument("--microbatch", type=int, default=2)
    p.add_argument("--steps", type=int, default=24)
    p.add_argument("--reps", type=int, default=4)
    args = p.parse_args()

    from bench import flagship_config, interleaved_slopes
    from perceiver_io_tpu.models.text import CausalLanguageModel
    from perceiver_io_tpu.training import TrainState, clm_loss_fn, make_optimizer
    from perceiver_io_tpu.training.loop import make_train_step

    b, n = args.batch_size, args.seq_len
    rng = np.random.default_rng(0)
    t = rng.integers(0, 262, size=(b, n + 1))
    batch = {
        "labels": jnp.asarray(t[:, 1:]),
        "input_ids": jnp.asarray(t[:, :-1]),
        "pad_mask": None,
    }
    config = flagship_config(args.seq_len, args.latents)
    model = CausalLanguageModel(config, dtype=jnp.bfloat16)
    params = model.init(
        jax.random.PRNGKey(0), batch["input_ids"][:, : args.latents + 1], prefix_len=1
    )

    def fresh_state():
        tx = make_optimizer(1e-3, gradient_clip=1.0, moment_dtype="bfloat16")
        # deep-copy: a donated variant consumes its state's buffers, and the
        # init params must survive to seed the other variant
        own = jax.tree.map(lambda a: a.copy(), params)
        return TrainState.create(model.apply, own, tx, jax.random.PRNGKey(1))

    def build(donate):
        step = make_train_step(
            clm_loss_fn(model.apply, max_latents=args.latents),
            jit=True,
            donate=donate,
            microbatch=args.microbatch,
        )
        # ONE long-lived state per variant: each timed chain is a window of
        # the ongoing step stream (step time is state-value independent).
        # Rebuilding the state per chain costs hundreds of per-leaf copy
        # dispatches and swamps the measurement.
        box = {"state": fresh_state()}

        def call(k):
            state, m = box["state"], None
            for _ in range(k):
                state, m = step(state, batch)
            _ = float(m["loss"])  # wait for the chain
            box["state"] = state

        return call

    variants = {"donate": build(True), "nodonate": build(False)}
    n_short, n_long = 2, 2 + args.steps
    for name, call in variants.items():
        t0 = time.perf_counter()
        call(n_short)
        call(n_long)
        print(f"{name}: compiled in {time.perf_counter() - t0:.0f}s", flush=True)

    meds = interleaved_slopes(variants, n_short, n_long, reps=args.reps)
    tok = b * args.seq_len
    print(f"{'variant':<10} {'ms/step':>8} {'tok/s':>12}")
    for v in variants:
        med = meds[v]
        if med is None:
            print(f"{v:<10}  slope estimates non-positive — rerun")
            continue
        print(f"{v:<10} {med * 1e3:8.2f} {tok / med:12.0f}")


if __name__ == "__main__":
    main()
