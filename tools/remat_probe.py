"""Measure HBM for the train step with/without activation checkpointing at
the 455M-class FSDP geometry (reference: examples/training/clm/train_fsdp.sh —
the config whose single-chip viability depends on remat).

Uses XLA's compile-time memory analysis (``compiled.memory_analysis()``), so
nothing is executed: works at sizes that would OOM, and reports the exact
buffer assignment the real run would use.

    python tools/remat_probe.py --num-channels 1024 --layers 16 --seq-len 6144 ...
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def hbm_bytes(config, batch_size: int, latents: int, seq_len: int):
    from perceiver_io_tpu.models.text import CausalLanguageModel
    from perceiver_io_tpu.training import TrainState, clm_loss_fn, make_optimizer
    from perceiver_io_tpu.training.loop import make_train_step

    model = CausalLanguageModel(config, dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    t = rng.integers(0, config.vocab_size, size=(batch_size, seq_len + 1))
    batch = {
        "labels": jnp.asarray(t[:, 1:]),
        "input_ids": jnp.asarray(t[:, :-1]),
        "pad_mask": None,
    }
    # init with a tiny slice: we only need the param shapes
    params = jax.eval_shape(
        lambda r: model.init(r, batch["input_ids"][:, : latents + 1], prefix_len=1),
        jax.random.PRNGKey(0),
    )
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params)
    tx = make_optimizer(1e-3, gradient_clip=1.0)
    state = TrainState.create(model.apply, params, tx, jax.random.PRNGKey(1))
    step = make_train_step(clm_loss_fn(model.apply, max_latents=latents), jit=False)

    lowered = jax.jit(step, donate_argnums=(0,)).lower(state, batch)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    return mem, n_params


def fmt(n):
    return f"{n / 2**30:.2f}G"


def main():
    from perceiver_io_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--seq-len", type=int, default=6144)
    p.add_argument("--latents", type=int, default=2048)
    p.add_argument("--num-channels", type=int, default=1024)
    p.add_argument("--layers", type=int, default=16)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--vocab-size", type=int, default=32000)
    p.add_argument("--batch-size", type=int, default=2)
    args = p.parse_args()

    from perceiver_io_tpu.models.text import CausalLanguageModelConfig

    for remat in (False, True):
        config = CausalLanguageModelConfig(
            vocab_size=args.vocab_size,
            max_seq_len=args.seq_len,
            max_latents=args.latents,
            num_channels=args.num_channels,
            num_heads=args.heads,
            num_self_attention_layers=args.layers,
            cross_attention_dropout=0.5,
            activation_checkpointing=remat,
        )
        try:
            mem, n_params = hbm_bytes(config, args.batch_size, args.latents, args.seq_len)
        except Exception as e:  # XLA raises on un-fittable allocations
            print(f"remat={remat}: COMPILE FAILED: {type(e).__name__}: {str(e)[:300]}")
            continue
        print(
            f"remat={remat}: params={n_params/1e6:.0f}M "
            f"temp={fmt(mem.temp_size_in_bytes)} "
            f"argument={fmt(mem.argument_size_in_bytes)} "
            f"output={fmt(mem.output_size_in_bytes)} "
            f"alias={fmt(mem.alias_size_in_bytes)} "
            f"peak_temp+args={fmt(mem.temp_size_in_bytes + mem.argument_size_in_bytes)}"
        )


if __name__ == "__main__":
    main()
