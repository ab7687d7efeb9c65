"""Same-process interleaved A/B of round-4 step-level structural variants on
the flagship train step (cross-process comparisons drift 1.5-1.8x with the
chip clock — docs/performance.md):

- ``graph``   — in-graph prefix-dropout draw (top_k + sort)
- ``host``    — keep set sampled on the host, fed as ``prefix_keep_idx``
                (training/prefix_dropout.py)
- ``mask``    — keep-mask form (SURVEY §7.3): full-length prefix, dropped
                positions masked in the CA softmax (prefix_dropout_mode)
- ``bf16m``   — in-graph draw + bf16 Adam moment storage
                (optim.scale_by_adam_compact)
- ``host+bf16m`` — both levers

Since round 5, gather variants take the COMPACT route (selection before
embedding — the current default); append ``_embed`` to any variant name
(e.g. ``host+bf16m_embed``) to pin the round-4 embedded-row gather that the
historical numbers in docs/performance.md were measured on.

    python tools/step_ab.py [--batch-size 4] [--steps 20] [--microbatch 2]

Since round 14 (Specline) the harness also takes DECODE variants, so the
standing TPU A/B instruction in ROADMAP item 3 covers the speculative
ladder with the same interleaved same-process discipline: ``decode`` runs
the sequential host-driven pair (``generation.make_decode_fns``) and
``spec{K}x{D}`` (e.g. ``spec4x6``) the speculative pair with K draft
tokens per span and a depth-D self-drafter — batch 1, prompt sized for
the no-slide window, tok/s measured over the same paired-chain slope:

    python tools/step_ab.py --variants decode spec4x6 spec4x2
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
    p.add_argument("--microbatch", type=int, default=2)
    p.add_argument(
        "--variants", nargs="*", default=["graph", "host", "mask", "bf16m", "host+bf16m"]
    )
    args = p.parse_args()

    from perceiver_io_tpu.models.text import CausalLanguageModel
    from perceiver_io_tpu.training import TrainState, clm_loss_fn, make_optimizer
    from perceiver_io_tpu.training.loop import make_train_step
    from perceiver_io_tpu.training.prefix_dropout import sample_prefix_keep_idx

    b, n = args.batch_size, args.seq_len
    prefix_len = n - args.latents
    rng = np.random.default_rng(0)
    t = rng.integers(0, 262, size=(b, n + 1))
    base_batch = {
        "labels": jnp.asarray(t[:, 1:]),
        "input_ids": jnp.asarray(t[:, :-1]),
        "pad_mask": None,
    }
    keep_idx = jnp.asarray(sample_prefix_keep_idx(rng, b, prefix_len, 0.5))

    def build(variant):
        # "…_embed" forces the round-4 embedded-row gather (prefix_dropout_mode
        # "gather_embed"); plain gather variants take the round-5 compact route
        tokens = variant.split("+")
        if "mask" in tokens:
            mode = "mask"
        elif any(t.endswith("_embed") for t in tokens):
            mode = "gather_embed"
        else:
            mode = "gather"
        config = flagship_config(args.seq_len, args.latents)
        config.prefix_dropout_mode = mode
        model = CausalLanguageModel(config, dtype=jnp.bfloat16)
        params = model.init(
            jax.random.PRNGKey(0), base_batch["input_ids"][:, : args.latents + 1], prefix_len=1
        )
        moment_dtype = "bfloat16" if "bf16m" in variant else None
        tx = make_optimizer(1e-3, gradient_clip=1.0, moment_dtype=moment_dtype)
        state = TrainState.create(model.apply, params, tx, jax.random.PRNGKey(1))
        step = make_train_step(
            clm_loss_fn(model.apply, max_latents=args.latents),
            jit=False,
            microbatch=args.microbatch,
        )
        batch = dict(base_batch)
        if variant.startswith("host"):
            batch["prefix_keep_idx"] = keep_idx

        @functools.partial(jax.jit, static_argnums=2)
        def run(state, batch, k):
            def body(c, _):
                l, s = c
                s, metrics = step(s, batch)
                return (l + metrics["loss"], s), ()

            (l, _), _ = jax.lax.scan(body, (jnp.float32(0), state), None, length=k)
            return l

        return lambda k: float(run(state, batch, k))

    import re as _re

    def build_decode(variant):
        """DECODE-family variants (round 14): ``decode`` = the sequential
        host-driven pair, ``spec{K}x{D}`` = the speculative draft/verify
        pair. run(k) decodes >= k tokens from a fresh prefill; the prefill
        (and the spec path's over-shoot tail) cancels in the paired-chain
        slope exactly like decode_ab's prompt pass."""
        from perceiver_io_tpu.generation import (
            GenerationConfig,
            make_decode_fns,
            make_speculative_decode_fns,
        )

        m = _re.fullmatch(r"spec(\d+)x(\d+)", variant)
        budget = n_long + (int(m.group(1)) + 1 if m else 0)
        prompt_len = args.seq_len - budget
        num_latents = args.latents - budget
        config = flagship_config(args.seq_len, args.latents)
        model = CausalLanguageModel(config, dtype=jnp.bfloat16)
        # per-variant FIXED seed (not the shared mutated generator): the
        # prompt — and with it a spec variant's acceptance rate — must not
        # depend on which other variants ran first in --variants
        prompt = jnp.asarray(
            np.random.default_rng(7).integers(0, config.vocab_size, size=(1, prompt_len))
        )
        params = model.init(
            jax.random.PRNGKey(0), prompt[:, : num_latents + 1], prefix_len=1
        )
        gcfg = GenerationConfig(max_new_tokens=budget)
        if m:
            prefill, step = make_speculative_decode_fns(
                model, num_latents, gcfg,
                k=int(m.group(1)), draft_depth=int(m.group(2)),
            )

            def run(k):
                _, state = prefill(params, prompt, None, jax.random.PRNGKey(11))
                emitted, toks = 1, None
                while emitted < k:
                    state, toks, mm = step(state)
                    emitted += int(mm[0])
                return float(state["token"][0])
        else:
            prefill, step = make_decode_fns(model, num_latents, gcfg)

            def run(k):
                _, state = prefill(params, prompt, None, jax.random.PRNGKey(11))
                for _ in range(k - 1):
                    state, tok = step(state)
                return float(state["token"][0])

        return run

    n_short, n_long = 2, 2 + args.steps
    decode_family = {
        v for v in args.variants if v == "decode" or _re.fullmatch(r"spec\d+x\d+", v)
    }
    runs = {}
    for name in args.variants:
        runs[name] = build_decode(name) if name in decode_family else build(name)
        t0 = time.perf_counter()
        runs[name](n_short)
        runs[name](n_long)
        print(f"{name}: compiled in {time.perf_counter() - t0:.0f}s", flush=True)

    meds = interleaved_slopes(runs, n_short, n_long, reps=args.reps)
    print(f"{'variant':<16} {'ms/step':>8} {'tok/s':>12}")
    for v in args.variants:
        med = meds[v]
        if med is None:
            print(f"{v:<16}  all slope estimates non-positive (host stall?) — rerun")
            continue
        # decode-family variants are batch-1 token loops: tok/s = 1/slope;
        # train variants keep the b*n tokens-per-step convention
        tok_s = (1 / med) if v in decode_family else (b * n / med)
        print(f"{v:<16} {med * 1e3:8.3f} {tok_s:12.0f}")


if __name__ == "__main__":
    main()
