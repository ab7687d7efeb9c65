"""Same-process interleaved A/B of the overlap-scheduled distributed train
step (parallel/overlap.py: chunk-interleaved gradient reduce-scatter +
bucket-chained FSDP all-gather prefetch) against the GSPMD step, across mesh
shapes — the staged measurement docs/performance.md round 7 calls for before
the overlap path graduates from its default-off gate.

Variants are ``<mesh-spec>`` x ``{overlap, gspmd}``; both members of each
mesh pair run in ONE process, visited round-robin (cross-process comparisons
drift 1.5-1.8x with the chip clock — docs/performance.md):

    # TPU pod slice / multi-chip host:
    python tools/overlap_ab.py --mesh data=4 data=2,fsdp=2 --batch-size 32

    # CPU smoke of the harness itself (numbers meaningless, wiring real):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/overlap_ab.py --micro --mesh data=2,fsdp=4 --steps 4

Each variant's per-step time comes from bench.interleaved_slopes (min-reduced
reps, median of estimates, non-positive slopes dropped). ``--microbatch``
controls the chunk count the interleaving claim rides on (>= 2 to matter).
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
    p.add_argument("--mesh", nargs="+", default=["data=2,fsdp=2"],
                   help="mesh specs to A/B, e.g. data=4 data=2,fsdp=2")
    p.add_argument("--seq-len", type=int, default=16384)
    p.add_argument("--latents", type=int, default=1024)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--microbatch", type=int, default=4)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--reps", type=int, default=4)
    p.add_argument("--bucket-mb", type=float, default=4.0)
    p.add_argument("--micro", action="store_true",
                   help="toy geometry (64-ctx, 32-ch) for harness smoke on CPU")
    args = p.parse_args()

    from perceiver_io_tpu.models.text import CausalLanguageModel
    from perceiver_io_tpu.parallel import shard_batch
    from perceiver_io_tpu.parallel.overlap import OverlapConfig, mesh_from_spec
    from perceiver_io_tpu.training import TrainState, clm_loss_fn, make_optimizer
    from perceiver_io_tpu.training.loop import make_train_step, shard_train_state

    if args.micro:
        args.seq_len, args.latents = 64, 16
        config = flagship_config(args.seq_len, args.latents)
        config.num_channels, config.num_heads, config.num_self_attention_layers = 32, 4, 2
    else:
        config = flagship_config(args.seq_len, args.latents)
    model = CausalLanguageModel(config, dtype=jnp.bfloat16)

    b, n = args.batch_size, args.seq_len
    rng = np.random.default_rng(0)
    t = rng.integers(0, config.vocab_size, size=(b, n + 1))
    base_batch = {
        "labels": jnp.asarray(t[:, 1:]),
        "input_ids": jnp.asarray(t[:, :-1]),
        "pad_mask": None,
    }
    params = model.init(
        jax.random.PRNGKey(0), base_batch["input_ids"][:, : args.latents + 1], prefix_len=1
    )
    loss = clm_loss_fn(model.apply, max_latents=args.latents)

    def build(spec_str, overlap: bool):
        try:
            mesh = mesh_from_spec(spec_str)
        except ValueError as e:
            raise SystemExit(str(e)) from None
        tx = make_optimizer(1e-3, gradient_clip=1.0, moment_dtype="bfloat16")
        state = shard_train_state(
            TrainState.create(model.apply, params, tx, jax.random.PRNGKey(1)), mesh
        )
        batch = shard_batch(dict(base_batch), mesh)
        cfg = OverlapConfig(mesh=mesh, bucket_bytes=int(args.bucket_mb * (1 << 20)))
        step = make_train_step(
            loss, jit=False, microbatch=args.microbatch, overlap=cfg if overlap else None
        )

        @functools.partial(jax.jit, static_argnums=2)
        def run(state, batch, k):
            def body(c, _):
                l, s = c
                s, metrics = step(s, batch)
                return (l + metrics["loss"], s), ()

            (l, _), _ = jax.lax.scan(body, (jnp.float32(0), state), None, length=k)
            return l

        return lambda k: float(run(state, batch, k))

    n_short, n_long = 2, 2 + args.steps
    runs = {}
    for spec_str in args.mesh:
        for overlap in (False, True):
            name = f"{spec_str}:{'overlap' if overlap else 'gspmd'}"
            runs[name] = build(spec_str, overlap)
            t0 = time.perf_counter()
            runs[name](n_short)
            runs[name](n_long)
            print(f"{name}: compiled in {time.perf_counter() - t0:.0f}s", flush=True)

    meds = interleaved_slopes(runs, n_short, n_long, reps=args.reps)
    print(f"{'variant':<28} {'ms/step':>9} {'tok/s':>12}")
    for name in runs:
        med = meds[name]
        if med is None:
            print(f"{name:<28}  all slope estimates non-positive (host stall?) — rerun")
            continue
        print(f"{name:<28} {med * 1e3:9.3f} {b * n / med:12.0f}")


if __name__ == "__main__":
    main()
