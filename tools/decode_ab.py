"""Same-process interleaved A/B of decode-scan variants at the 16k flagship
(cross-process decode numbers track the chip clock 1.5-1.8x —
docs/performance.md):

- ``pack``   — small f32 parameter leaves consolidated into ONE packed
               buffer, re-sliced inside the scan body behind an
               optimization_barrier (generation._pack_small_params,
               round-5 default)
- ``nopack`` — the round-4 behavior: each LayerNorm scale/bias and
               projection bias is its own HBM buffer in the scan body

    python tools/decode_ab.py [--batch-size 8] [--cache-dtype int8]
                              [--weight-dtype int8]
"""

from __future__ import annotations

import argparse
import os
import sys

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
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--steps", type=int, default=48)
    p.add_argument("--reps", type=int, default=4)
    p.add_argument("--cache-dtype", choices=["model", "int8"], default="model")
    p.add_argument("--weight-dtype", choices=["model", "int8"], default="model")
    p.add_argument("--variants", nargs="*", default=["pack", "nopack"])
    args = p.parse_args()

    from perceiver_io_tpu.generation import (
        GenerationConfig,
        make_generate_fn,
        pack_small_params,
    )
    from perceiver_io_tpu.models.text import CausalLanguageModel

    config = flagship_config(args.seq_len, args.latents)
    model = CausalLanguageModel(config, dtype=jnp.bfloat16)
    b = args.batch_size
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, config.vocab_size, size=(b, args.seq_len)))
    params = model.init(jax.random.PRNGKey(0), prompt[:, : args.latents + 1], prefix_len=1)

    cache_dtype = jnp.int8 if args.cache_dtype == "int8" else jnp.bfloat16
    weight_dtype = jnp.int8 if args.weight_dtype == "int8" else None

    n_short, n_long = 8, 8 + args.steps

    def build(variant):
        fns = {}
        with pack_small_params(variant == "pack"):
            for k in (n_short, n_long):
                fns[k] = make_generate_fn(
                    model,
                    args.latents,
                    GenerationConfig(max_new_tokens=k, do_sample=True, top_k=10),
                    cache_dtype=cache_dtype,
                    weight_dtype=weight_dtype,
                )
                # compile inside the pack context (trace-time flag)
                float(fns[k](params, prompt)[0, -1])
        return lambda k: float(fns[k](params, prompt)[0, -1])

    runs = {v: build(v) for v in args.variants}
    meds = interleaved_slopes(runs, n_short, n_long, reps=args.reps)
    print(f"{'variant':<10} {'ms/token':>9} {'tok/s (batch)':>14}")
    for v in args.variants:
        med = meds[v]
        if med is None:
            print(f"{v:<10}  all slope estimates non-positive (host stall?) — rerun")
            continue
        print(f"{v:<10} {med * 1e3:9.4f} {b / med:14.0f}")


if __name__ == "__main__":
    main()
