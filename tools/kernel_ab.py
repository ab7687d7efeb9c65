"""Same-process interleaved A/B of the flash-kernel v2 optimizations
(FAST_KERNELS: base-2 softmax, zero-bias skip, full-tile fast path, slim
stats) on the flagship train step. Cross-process comparisons are
untrustworthy on this chip (clock drifts 1.5-1.8x between burst and
sustained); here both kernel generations are traced in ONE process and the
slope measurements interleave round-robin so drift hits both equally.

    python tools/kernel_ab.py [--batch-size 4] [--steps 20]
"""

from __future__ import annotations

import argparse
import functools
import os
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
    p.add_argument("--batch-size", type=int, default=4)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--reps", type=int, default=4)
    p.add_argument(
        "--variants",
        nargs="*",
        default=["all", "none"],
        help="each is 'all', 'none', or a comma-joined feature list "
        "(base2,nobias,fastmask,slimstats)",
    )
    args = p.parse_args()

    import perceiver_io_tpu.ops.flash_attention
    fa = sys.modules["perceiver_io_tpu.ops.flash_attention"]
    from perceiver_io_tpu.models.text import CausalLanguageModel
    from perceiver_io_tpu.training import TrainState, clm_loss_fn, make_optimizer
    from perceiver_io_tpu.training.loop import make_train_step

    config = flagship_config(args.seq_len, args.latents)
    model = CausalLanguageModel(config, dtype=jnp.bfloat16)

    b, n = args.batch_size, args.seq_len
    rng = np.random.default_rng(0)
    t = rng.integers(0, config.vocab_size, size=(b, n + 1))
    batch = {
        "labels": jnp.asarray(t[:, 1:]),
        "input_ids": jnp.asarray(t[:, :-1]),
        "pad_mask": None,
    }
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"][:, : args.latents + 1], prefix_len=1)
    tx = make_optimizer(1e-3, gradient_clip=1.0)
    state = TrainState.create(model.apply, params, tx, jax.random.PRNGKey(1))
    step = make_train_step(clm_loss_fn(model.apply, max_latents=args.latents), jit=False)

    def make_run():
        @functools.partial(jax.jit, static_argnums=2)
        def run(state, batch, k):
            def body(c, i):
                l, s = c
                s, metrics = step(s, batch)
                return (l + metrics["loss"], s), ()

            (l, _), _ = jax.lax.scan(body, (jnp.float32(0), state), jnp.arange(k))
            return l

        return lambda k: float(run(state, batch, k))

    def mode(name):
        if name == "all":
            return True
        if name == "none":
            return False
        return name.split(",")

    variants = args.variants
    n_short, n_long = 2, 2 + args.steps
    runs = {}
    for name in variants:
        fa.set_fast_kernels(mode(name))
        runs[name] = make_run()
        t0 = time.perf_counter()
        runs[name](n_short)
        runs[name](n_long)
        print(f"{name}: compiled in {time.perf_counter() - t0:.0f}s", flush=True)
    fa.set_fast_kernels(True)

    meds = interleaved_slopes(runs, n_short, n_long, reps=args.reps)
    print(f"{'variant':<28} {'ms/step':>8} {'tok/s':>12}")
    for v in variants:
        med = meds[v]
        if med is None:
            print(f"{v:<28}  all slope estimates non-positive (host stall?) — rerun")
            continue
        print(f"{v:<28} {med * 1e3:8.3f} {b * n / med:12.0f}")


if __name__ == "__main__":
    main()
