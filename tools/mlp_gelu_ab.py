"""Same-process A/B of what the MLP keeps of its exact GELU for the backward.

    python tools/mlp_gelu_ab.py                      # on the chip
    python tools/mlp_gelu_ab.py --compile-only       # for a described v5e, no chip

Two pre-norm MLP blocks with their residuals at the hidden shape of
``ar16k-train-b32`` ([32, 1024, 512] bf16, widening 4; ``--rows``,
``--channels`` and ``--widening`` give ``imagenet-train-b16``'s), forward and
backward in one program a variant; the variants run round-robin, each round
under its own profiler capture, and the device time of every operation is read
from the capture (wall clocks drift between processes on this chip, device
times in one process do not). ``--compile-only`` prints, for each, the fusions
that hold an ``exponential`` at the hidden shape with XLA's
``estimated_cycles`` and the float32 arrays of that shape written between
fusions (``tools/step_hlo.py``'s parser).

- ``autodiff``: ``nn.gelu`` left to autodiff, what ``core.modules.MLP`` ran
  until PR 35: XLA keeps ``h`` and two predicate masks and expands ``erfc``
  again inside the forward ``dense_2``, ``dW2`` and ``dy W2^T`` GEMMs;
- ``h+erfc``: ``core.modules.gelu_exact``, the program's rule: ``e = erfc(-h /
  sqrt 2)`` evaluated once in ``dense_1``'s epilogue and kept with ``h``;
  ``dense_2`` and ``dW2`` multiply ``0.5 h e`` on their input, the backward
  takes one exponential (``erfc``'s derivative); gradients autodiff's to the bit;
- ``h+a``: ``a = gelu(h)`` evaluated once and kept with ``h``, the backward
  differentiates ``nn.gelu`` at the kept ``h`` (a second ``erfc``, inside the
  ``dy W2^T`` GEMM as under autodiff); gradients autodiff's to the bit;
- ``a+g``: ``a`` and ``g = gelu'(h)`` (float32, rounded once) from one ``erfc``,
  both behind one barrier, ``h`` dropped, the backward one multiply. XLA's
  fusions have one root, so it writes the float32 ``erfc`` out of the
  ``dense_1`` fusion and reads it back in a second one.

PERF.md 6 (PR 35) has the readings that chose ``h+erfc``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax import lax

import step_hlo
from embed_grad_ab import device_ms
from perceiver_io_tpu.core import modules

VARIANTS = ("autodiff", "h+erfc", "h+a", "a+g")


def value_and_slope(x):
    """``gelu(x)`` as the program rounds it (``erfc`` to ``x``'s dtype, then
    the product) and its float32 derivative rounded once, from one ``erfc``
    whose ``exp(-t^2)`` the density shares."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    c = np.float32(np.sqrt(0.5).astype(dt))  # nn.gelu's constant, in x's dtype
    t = -x32 * c
    e = lax.erfc(t)
    info = jnp.finfo(dt)
    rounded = lax.reduce_precision(e, info.nexp, info.nmant) if info.bits < 32 else e
    a = ((0.5 * x32) * rounded).astype(dt)
    g = (0.5 * e + x32 * (np.float32(c / np.sqrt(np.pi)) * jnp.exp(-(t * t)))).astype(dt)
    return a, g


@jax.custom_vjp
def gelu_a_g(x):
    return nn.gelu(x, approximate=False)


gelu_a_g.defvjp(lambda x: lax.optimization_barrier(value_and_slope(x)), lambda g, da: (da * g,))


@jax.custom_vjp
def gelu_h_a(x):
    return nn.gelu(x, approximate=False)


def _plain_vjp(x, da):
    return jax.vjp(lambda x: nn.gelu(x, approximate=False), x)[1](da)


gelu_h_a.defvjp(lambda x: lax.optimization_barrier((nn.gelu(x, approximate=False), x)), _plain_vjp)


def activation(variant: str):
    return {"autodiff": lambda x: nn.gelu(x, approximate=False), "h+erfc": modules.gelu_exact, "h+a": gelu_h_a, "a+g": gelu_a_g}[variant]


class Blocks(nn.Module):
    act: callable
    channels: int
    widening: int
    depth: int = 2

    @nn.compact
    def __call__(self, x):
        for i in range(self.depth):
            h = modules.LayerNorm(epsilon=modules.LAYER_NORM_EPSILON, dtype=x.dtype, name=f"norm_{i}")(x)
            h = nn.Dense(self.widening * self.channels, dtype=x.dtype, name=f"dense_1_{i}")(h)
            x = x + nn.Dense(self.channels, dtype=x.dtype, name=f"dense_2_{i}")(self.act(h))
        return modules.LayerNorm(epsilon=modules.LAYER_NORM_EPSILON, dtype=x.dtype, name="norm_out")(x)


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--variants", nargs="+", default=list(VARIANTS), choices=VARIANTS)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--rows", type=int, default=1024)
    p.add_argument("--channels", type=int, default=512)
    p.add_argument("--widening", type=int, default=4)
    p.add_argument("--calls", type=int, default=8)
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--compile-only", action="store_true")
    p.add_argument("--out", default=None, help="write the table as JSON here")
    args = p.parse_args()

    sharding = None
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        sharding = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
        jax.config.update("jax_enable_compilation_cache", False)
    elif jax.default_backend() != "tpu":
        raise SystemExit("mlp_gelu_ab times the MLP on the chip: no TPU here (use --compile-only)")

    shape = (args.batch, args.rows, args.channels)
    hidden = f"f32[{args.batch},{args.rows},{args.widening * args.channels}]"
    compiled, models = {}, {}
    for name in args.variants:
        model = models[name] = Blocks(activation(name), args.channels, args.widening)
        params = jax.eval_shape(lambda m=model: m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, args.channels), jnp.bfloat16)))
        described = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), params)
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)

        def loss(params, x, model=model):
            return model.apply(params, x).astype(jnp.float32).sum()

        with jax.default_matmul_precision("default"):
            compiled[name] = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(described, x).compile()
        text = compiled[name].as_text()
        rows = [r for r in step_hlo.entry_fusions(text, hidden) if r["exponential"]]
        print(f"{name}: temporaries {compiled[name].memory_analysis().temp_size_in_bytes} bytes; "
              f"{len(rows)} fusions hold an exponential of {hidden}; "
              f"{len(step_hlo.entry_buffers(text, hidden))} entry instructions write an array of that shape", flush=True)
        for r in rows:
            print(f"    {r['name']:32s} {r['estimated_cycles']:9d} cycles, {r['exponential']} exponential, {r['divide']} divide, "
                  f"{step_hlo.scope_column(r['op_name'], 2)}")
    if args.compile_only:
        return

    rng = np.random.default_rng(0)
    params = models[args.variants[0]].init(jax.random.PRNGKey(0), jnp.zeros((1, 8, args.channels), jnp.bfloat16))
    x = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    flat = lambda grads: np.concatenate([np.asarray(g, np.float32).ravel() for g in jax.tree.leaves(grads)])  # noqa: E731
    ref = flat(compiled["autodiff"](params, x)) if "autodiff" in compiled else None
    gaps = {}
    for name in args.variants:
        got = flat(compiled[name](params, x))
        gaps[name] = None if ref is None else float(np.linalg.norm(got - ref) / np.linalg.norm(ref))

    rounds = {name: [] for name in args.variants}
    for _ in range(args.rounds):
        for name in args.variants:
            with tempfile.TemporaryDirectory() as tmp:
                jax.profiler.start_trace(tmp)
                for _ in range(args.calls):
                    out = compiled[name](params, x)
                jax.block_until_ready(out)
                jax.profiler.stop_trace()
                rounds[name].append({k: v / args.calls for k, v in device_ms(tmp).items()})

    table = []
    print(f"\n2 blocks of {shape} bf16, widening {args.widening}: device ms a call, "
          f"median of {args.rounds} rounds of {args.calls} calls")
    for name in args.variants:
        ops = {k: float(np.median([r.get(k, 0.0) for r in rounds[name]])) for k in rounds[name][0]}
        total = float(np.median([sum(r.values()) for r in rounds[name]]))
        table.append(dict(variant=name, ms=total, ops=ops, gradient_gap_to_autodiff=gaps[name]))
        top = ", ".join(f"{k} {v:.3f}" for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:14])
        print(f"{name:<9} {total:8.3f} ms   gradient gap to autodiff {gaps[name]}\n    {top}")
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, "mlp_gelu_ab.json"), "w") as f:
            json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
