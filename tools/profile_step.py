"""Capture a device profile of the flagship train step and print the top
device ops (tools/xplane.py parser — no TensorFlow needed).

    python tools/profile_step.py [--batch-size 4] [--top 40] [--out /tmp/prof]

The per-op durations come from the device plane, so host dispatch
jitter does not pollute them; a handful of eagerly dispatched steps inside
the trace window is enough.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from bench import flagship_config


def decode_profile(args):
    """Trace a compiled decode scan (full 16k window) — per-op durations are
    the per-TOKEN cost times the scan length."""
    from perceiver_io_tpu.generation import GenerationConfig, make_generate_fn
    from perceiver_io_tpu.models.text import CausalLanguageModel

    config = flagship_config(args.seq_len, args.latents)
    model = CausalLanguageModel(config, dtype=jnp.bfloat16)
    b = args.batch_size
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, config.vocab_size, size=(b, args.seq_len)))
    params = model.init(jax.random.PRNGKey(0), prompt[:, : args.latents + 1], prefix_len=1)
    gen = make_generate_fn(
        model, args.latents,
        GenerationConfig(max_new_tokens=args.steps, do_sample=True, top_k=10),
        cache_dtype=jnp.int8 if args.cache_dtype == "int8" else jnp.bfloat16,
        weight_dtype=jnp.int8 if args.weight_dtype == "int8" else None,
    )
    float(gen(params, prompt)[0, -1])  # compile + warm
    jax.profiler.start_trace(args.out)
    float(gen(params, prompt)[0, -1])
    jax.profiler.stop_trace()


def image_profile(args):
    """Trace the image-classifier train step (the BENCH_extra image workload,
    bench.image_bench config) — the round-4 roofline treatment. Matches the
    bench exactly: microbatch is always 1 on the image workload (the
    --microbatch flag applies to the CLM train mode only)."""
    from perceiver_io_tpu.models.vision.image_classifier import (
        ImageClassifier,
        ImageClassifierConfig,
        ImageEncoderConfig,
    )
    from perceiver_io_tpu.core.config import ClassificationDecoderConfig
    from perceiver_io_tpu.training import TrainState, classification_loss_fn, make_optimizer
    from perceiver_io_tpu.training.loop import make_train_step

    config = ImageClassifierConfig(
        encoder=ImageEncoderConfig(
            image_shape=(224, 224, 3),
            num_frequency_bands=64,
            num_cross_attention_heads=1,
            num_self_attention_heads=8,
            num_self_attention_layers_per_block=6,
            num_self_attention_blocks=8,
            first_self_attention_block_shared=True,
        ),
        decoder=ClassificationDecoderConfig(
            num_classes=1000, num_output_query_channels=1024, num_cross_attention_heads=1
        ),
        num_latents=512,
        num_latent_channels=1024,
    )
    model = ImageClassifier(config, dtype=jnp.bfloat16)
    b = args.batch_size
    rng = np.random.default_rng(0)
    batch = {
        "image": jnp.asarray(rng.normal(size=(b, 224, 224, 3)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, 1000, size=(b,))),
    }
    params = model.init(jax.random.PRNGKey(0), batch["image"])
    tx = make_optimizer(1e-3, gradient_clip=1.0)
    state = TrainState.create(model.apply, params, tx, jax.random.PRNGKey(1))
    step = make_train_step(classification_loss_fn(model.apply))

    for _ in range(2):
        state, metrics = step(state, batch)
        float(metrics["loss"])
    jax.profiler.start_trace(args.out)
    for _ in range(args.steps):
        state, metrics = step(state, batch)
        float(metrics["loss"])
    jax.profiler.stop_trace()


def main():
    from perceiver_io_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--seq-len", type=int, default=16384)
    p.add_argument("--latents", type=int, default=1024)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--top", type=int, default=40)
    p.add_argument("--out", default="/tmp/prof_step")
    p.add_argument("--mode", choices=["train", "decode", "img"], default="train")
    # match the bench.py round-5 defaults (b32 in 8 chunks of 4) so the
    # profile reflects the step the driver actually measures
    p.add_argument("--microbatch", type=int, default=8)
    p.add_argument("--dropout-sampling", choices=["host", "graph"], default="host")
    p.add_argument("--dropout-mode", choices=["gather", "gather_embed", "mask"], default="gather")
    p.add_argument("--cache-dtype", choices=["model", "int8"], default="model")
    p.add_argument("--weight-dtype", choices=["model", "int8"], default="model")
    p.add_argument("--moment-dtype", choices=["float32", "bfloat16"], default="bfloat16")
    args = p.parse_args()

    if args.mode == "decode":
        decode_profile(args)
        return _summarize(args)
    if args.mode == "img":
        image_profile(args)
        return _summarize(args)

    from perceiver_io_tpu.models.text import CausalLanguageModel
    from perceiver_io_tpu.training import TrainState, clm_loss_fn, make_optimizer
    from perceiver_io_tpu.training.loop import make_train_step

    config = flagship_config(args.seq_len, args.latents)
    config.prefix_dropout_mode = args.dropout_mode
    model = CausalLanguageModel(config, dtype=jnp.bfloat16)
    b, n = args.batch_size, args.seq_len
    rng = np.random.default_rng(0)
    t = rng.integers(0, config.vocab_size, size=(b, n + 1))
    batch = {
        "labels": jnp.asarray(t[:, 1:]),
        "input_ids": jnp.asarray(t[:, :-1]),
        "pad_mask": None,
    }
    if args.dropout_sampling == "host":
        from perceiver_io_tpu.training.prefix_dropout import sample_prefix_keep_idx

        batch["prefix_keep_idx"] = jnp.asarray(
            sample_prefix_keep_idx(rng, b, n - args.latents, config.cross_attention_dropout)
        )
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"][:, : args.latents + 1], prefix_len=1)
    tx = make_optimizer(
        1e-3,
        gradient_clip=1.0,
        moment_dtype=None if args.moment_dtype == "float32" else args.moment_dtype,
    )
    state = TrainState.create(model.apply, params, tx, jax.random.PRNGKey(1))
    step = make_train_step(
        clm_loss_fn(model.apply, max_latents=args.latents), microbatch=args.microbatch
    )

    # warm up / compile outside the trace
    for _ in range(2):
        state, metrics = step(state, batch)
        float(metrics["loss"])

    jax.profiler.start_trace(args.out)
    for _ in range(args.steps):
        state, metrics = step(state, batch)
        float(metrics["loss"])
    jax.profiler.stop_trace()
    _summarize(args)


def _summarize(args):
    from perceiver_io_tpu.obs.xplane import rollup_planes, summarize

    # raw per-op totals first, then the named-scope rollup (obs/xplane.py)
    # from the SAME parsed planes — the scope view is what answers "which
    # module did the time go to", and the parse dominates on big captures
    planes = summarize(args.out, args.top, "")
    print("\n--- per-scope rollup (jax.named_scope / module path) ---")
    for roll in rollup_planes(planes):
        print(f"\n=== plane: {roll.plane}")
        for scope, dur, count in roll.top(args.top):
            print(f"  {dur/1e9:9.3f} ms {count:6d}x  {scope[:100]}")


if __name__ == "__main__":
    main()
