"""Same-process interleaved A/B of image-classifier step variants (round 4:
the VERDICT r3 image roofline treatment). The image step's exclusive profile
(tools/profile_step.py --mode img) puts ~22.6 ms/step (12.7%) in XLA
layernorm stat fusions — an order of magnitude more LN work than the CLM
flagship (96 LN applications per forward over the 48-layer shared SA stack).
A Pallas LayerNorm lost by 1% there and by 5% here (deleted in PR 30).

    python tools/img_ab.py [--batch-size 16] [--steps 8]
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import interleaved_slopes  # noqa: E402  (repo root on sys.path above)

import jax
import jax.numpy as jnp
import numpy as np


def main():
    from perceiver_io_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--reps", type=int, default=4)
    # base = concat input route (round-3); split = fused split-kv input
    # (round-4 default)
    p.add_argument("--variants", nargs="*", default=["base", "split"])
    args = p.parse_args()

    from perceiver_io_tpu.core.config import ClassificationDecoderConfig
    from perceiver_io_tpu.models.vision.image_classifier import (
        ImageClassifier,
        ImageClassifierConfig,
        ImageEncoderConfig,
    )
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

    from perceiver_io_tpu.core.modules import PerceiverEncoder

    def build(variant):
        tx = make_optimizer(1e-3, gradient_clip=1.0)
        state = TrainState.create(model.apply, params, tx, jax.random.PRNGKey(1))
        step = make_train_step(classification_loss_fn(model.apply), jit=False)

        @functools.partial(jax.jit, static_argnums=2)
        def run(state, batch, k):
            def body(c, _):
                l, s = c
                s, metrics = step(s, batch)
                return (l + metrics["loss"], s), ()

            (l, _), _ = jax.lax.scan(body, (jnp.float32(0), state), None, length=k)
            return l

        def call(k):
            # trace-time routing: 'base' forces the concat input route by
            # disabling the split gate; 'split' leaves the default
            orig = PerceiverEncoder._use_split_input
            if variant != "split":
                PerceiverEncoder._use_split_input = lambda self, pm, det: False
            try:
                return float(run(state, batch, k))
            finally:
                PerceiverEncoder._use_split_input = orig

        return call

    n_short, n_long = 1, 1 + args.steps
    runs = {}
    for name in args.variants:
        runs[name] = build(name)
        t0 = time.perf_counter()
        runs[name](n_short)
        runs[name](n_long)
        print(f"{name}: compiled in {time.perf_counter() - t0:.0f}s", flush=True)

    meds = interleaved_slopes(runs, n_short, n_long, reps=args.reps)
    print(f"{'variant':<16} {'ms/step':>8} {'img/s':>8}")
    for v in args.variants:
        med = meds[v]
        if med is None:
            print(f"{v:<16}  all slope estimates non-positive (host stall?) — rerun")
            continue
        print(f"{v:<16} {med * 1e3:8.2f} {b / med:8.1f}")


if __name__ == "__main__":
    main()
