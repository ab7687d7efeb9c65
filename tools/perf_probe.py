"""Decompose the flagship train step's time on the real chip.

Measures, with bench.py's hardened scan-slope methodology, the sustained
per-iteration time of:

  fwd         loss value only
  fwd_nodrop  loss value, deterministic (no prefix-dropout gather)
  grad        value_and_grad (fwd + bwd)
  grad_nodrop value_and_grad, deterministic
  step        full train step (grad + clip + adamw update)
  opt         optimizer update alone (fixed grads)

Usage: python tools/perf_probe.py [--seq-len 16384] [--latents 1024] ...
"""

from __future__ import annotations

import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from bench import flagship_config, robust_slope, train_step_flops


def scan_time(fn, carry_init, steps, *, n_short=2, extract=None):
    """Sustained per-iteration time of ``carry = fn(carry, i)`` via the
    two-chain-length slope (fixed dispatch costs cancel).

    ``extract(carry)`` must return a scalar whose value depends on the whole
    per-iteration computation — XLA dead-code-eliminates everything that
    doesn't feed the fetched value (a step-counter leaf makes the probe
    report dispatch latency, not compute)."""
    if extract is None:
        extract = lambda c: jax.tree.leaves(c)[0].reshape(-1)[0]

    @functools.partial(jax.jit, static_argnums=1)
    def run(carry, k):
        def body(c, i):
            c = fn(c, i)
            return c, ()

        c, _ = jax.lax.scan(body, carry, jnp.arange(k))
        return extract(c)

    return robust_slope(lambda k: float(run(carry_init, k)), n_short, n_short + steps)


def main():
    from perceiver_io_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--seq-len", type=int, default=16384)
    p.add_argument("--latents", type=int, default=1024)
    p.add_argument("--batch-size", type=int, default=1)
    p.add_argument("--steps", type=int, default=40)
    p.add_argument("--only", nargs="*", default=None)
    p.add_argument(
        "--sa-einsum",
        action="store_true",
        help="A/B: self-attention (nq==nkv) on the einsum path, CA stays flash",
    )
    p.add_argument("--no-flash", action="store_true", help="A/B: einsum everywhere")
    p.add_argument("--block-q", type=int, default=None, help="A/B: flash block_q override")
    p.add_argument("--block-kv", type=int, default=None, help="A/B: flash block_kv override")
    args = p.parse_args()

    if args.block_q or args.block_kv:
        import functools as _ft

        from perceiver_io_tpu.core import attention as _attn2
        from perceiver_io_tpu.ops.flash_attention import flash_attention as _fa
        from perceiver_io_tpu.ops.flash_attention import flash_attention_packed as _fap

        kw = {}
        if args.block_q:
            kw["block_q"] = args.block_q
        if args.block_kv:
            kw["block_kv"] = args.block_kv
        # patch BOTH entries: supported shapes route through the packed path
        _attn2.flash_attention = _ft.partial(_fa, **kw)
        _attn2.flash_attention_packed = _ft.partial(_fap, **kw)

    if args.sa_einsum:
        from perceiver_io_tpu.core import attention as _attn

        orig_supported = _attn.flash_supported
        _attn.flash_supported = (
            lambda nq, nkv, dqk, dv, drop: False if nq == nkv else orig_supported(nq, nkv, dqk, dv, drop)
        )
    if args.no_flash:
        from perceiver_io_tpu.ops.flash_attention import set_default_flash

        set_default_flash(False)

    from perceiver_io_tpu.models.text import CausalLanguageModel
    from perceiver_io_tpu.training import TrainState, clm_loss_fn, make_optimizer
    from perceiver_io_tpu.training.loop import make_train_step

    config = flagship_config(args.seq_len, args.latents)
    model = CausalLanguageModel(config, dtype=jnp.bfloat16)
    import dataclasses

    det_model = CausalLanguageModel(
        dataclasses.replace(config, cross_attention_dropout=0.0), dtype=jnp.bfloat16
    )

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

    loss_fn = clm_loss_fn(model.apply, max_latents=args.latents)
    det_loss_fn = clm_loss_fn(det_model.apply, max_latents=args.latents)
    step = make_train_step(loss_fn, jit=False)

    flops = train_step_flops(config, b, prefix_dropout_keep=0.5)

    def fwd_iter(lf):
        def it(carry, i):
            l, r = carry
            r, sr = jax.random.split(r)
            loss, _ = lf(state.params, batch, sr)
            return (l + loss, r), None

        def fn(c, i):
            return it(c, i)[0]

        return fn

    def grad_iter(lf):
        grad_fn = jax.value_and_grad(lf, has_aux=True)

        def fn(carry, i):
            l, r = carry
            r, sr = jax.random.split(r)
            (loss, _), grads = grad_fn(state.params, batch, sr)
            # fold EVERY grad leaf into the carry: keeping only one leaf lets
            # XLA dead-code-eliminate the other leaves' weight-gradient outer
            # products (measured ~0.7 ms/step too fast at the 16k flagship)
            g = sum(x.reshape(-1)[0].astype(jnp.float32) for x in jax.tree.leaves(grads))
            return (l + loss + g, r)

        return fn

    def step_fn(carry, i):
        l, s = carry
        s, metrics = step(s, batch)
        return (l + metrics["loss"], s)

    (_, _), grads0 = jax.value_and_grad(loss_fn, has_aux=True)(
        state.params, batch, jax.random.PRNGKey(2)
    )

    def opt_fn(s, i):
        return s.apply_gradients(grads0)

    def param_leaf(s):
        # a parameter value is live through every optimizer update
        return jax.tree.leaves(s.params)[0].reshape(-1)[0].astype(jnp.float32)

    cases = {
        "fwd": lambda: scan_time(fwd_iter(loss_fn), (jnp.float32(0), jax.random.PRNGKey(3)), args.steps),
        "fwd_nodrop": lambda: scan_time(fwd_iter(det_loss_fn), (jnp.float32(0), jax.random.PRNGKey(3)), args.steps),
        "grad": lambda: scan_time(grad_iter(loss_fn), (jnp.float32(0), jax.random.PRNGKey(3)), args.steps),
        "grad_nodrop": lambda: scan_time(grad_iter(det_loss_fn), (jnp.float32(0), jax.random.PRNGKey(3)), args.steps),
        "step": lambda: scan_time(step_fn, (jnp.float32(0), state), args.steps),
        "opt": lambda: scan_time(opt_fn, state, args.steps, extract=param_leaf),
    }
    names = args.only or list(cases)
    print(f"{'case':<12} {'ms':>8} {'tok/s':>12} {'TFLOPS':>8}")
    for name in names:
        ms = cases[name]() * 1e3
        tfl = flops / 1e12 / (ms / 1e3)
        print(f"{name:<12} {ms:8.3f} {b * n / (ms / 1e3):12.0f} {tfl:8.1f}")


if __name__ == "__main__":
    main()
