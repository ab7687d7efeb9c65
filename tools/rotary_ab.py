"""Same-process A/B of the rotary embedding of a packed (B, N, H*d) array.

    python tools/rotary_ab.py                      # on the chip
    python tools/rotary_ab.py --compile-only       # for a described v5e, no chip

At the shapes of ``ar16k-train-b32`` (keys ``bf16[32, 8704, 512]``, 8 heads of
64 channels, 32 of them rotated) one process builds each way of rotating,
forward and backward (``jax.vjp``), runs them round-robin, each round under
its own profiler capture, and reads the device time of every operation of the
call from the capture. Every variant's result is compared with ``xla``'s bit
for bit, forward and backward.

- ``xla``: ``core.position.apply_rotary_pos_emb`` on the (B, N, H, d) view,
  what the packed call sites ran until PR 37 and what widths off the 128
  lanes still take;
- ``kernel``: ``ops.rotary.rotate_packed`` as the program runs it.

PERF.md 6 (PR 37) has the readings, and those of the block cuts tried for
the kernel (the program has no switch for them).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from perceiver_io_tpu.core.position import apply_rotary_pos_emb, frequency_position_encoding
from perceiver_io_tpu.ops import rotary

BATCH, ROWS, HEADS, HEAD_DIM, ROTATED = 32, 8704, 8, 64, 32


def variant_fn(name: str, heads: int, rotated: int):
    """``(t, g, pos) -> (rotated t, gradient of t under cotangent g)`` for packed (B, N, H*d) ``t``."""

    def angles(pos):
        return frequency_position_encoding(pos, rotated)

    if name == "xla":
        def rotate(t, pos):
            b, n, c = t.shape
            t4 = t.reshape(b, n, heads, c // heads)
            return apply_rotary_pos_emb(t4, angles(pos)[:, :, None, :]).reshape(t.shape)
    else:
        def rotate(t, pos):
            return rotary.rotate_packed(t, rotary.rotary_angles(angles(pos)), heads)

    def both(t, g, pos):
        out, vjp = jax.vjp(lambda x: rotate(x, pos), t)
        return out, vjp(g)[0]

    return both


def device_ms(trace_dir: str) -> dict:
    """Device ms by operation name in one capture."""
    from perceiver_io_tpu.obs.xplane import load_capture

    out: dict = {}
    for ops in load_capture(trace_dir)["device_ops"].values():
        for name, _, duration_ns in ops:
            out[name] = out.get(name, 0.0) + duration_ns / 1e6
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--variants", nargs="+", default=["xla", "kernel"], choices=["xla", "kernel"])
    p.add_argument("--batch", type=int, default=BATCH)
    p.add_argument("--rows", type=int, default=ROWS)
    p.add_argument("--heads", type=int, default=HEADS)
    p.add_argument("--head-dim", type=int, default=HEAD_DIM)
    p.add_argument("--rotated", type=int, default=ROTATED)
    p.add_argument("--calls", type=int, default=4)
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--compile-only", action="store_true")
    p.add_argument("--out", default=None, help="write the table as JSON (and with --compile-only each module's text) here")
    args = p.parse_args()

    sharding = None
    if args.compile_only:
        import importlib

        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
        importlib.import_module("perceiver_io_tpu.ops.flash_attention")._interpret_default = lambda: False
        jax.config.update("jax_enable_compilation_cache", False)
    elif jax.default_backend() != "tpu":
        raise SystemExit("rotary_ab times the rotation on the chip: no TPU here (use --compile-only)")

    t_shape = (args.batch, args.rows, args.heads * args.head_dim)
    shapes = (
        jax.ShapeDtypeStruct(t_shape, jnp.bfloat16, sharding=sharding),
        jax.ShapeDtypeStruct(t_shape, jnp.bfloat16, sharding=sharding),
        jax.ShapeDtypeStruct(t_shape[:2], jnp.int32, sharding=sharding),
    )
    compiled = {}
    for name in args.variants:
        compiled[name] = jax.jit(variant_fn(name, args.heads, args.rotated)).lower(*shapes).compile()
        mem = compiled[name].memory_analysis()
        print(f"{name}: compiled, temporaries {getattr(mem, 'temp_size_in_bytes', None)} bytes", flush=True)
        if args.out and args.compile_only:
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, f"{name}.hlo.txt"), "w") as f:
                f.write(compiled[name].as_text())
    if args.compile_only:
        return

    rng = np.random.default_rng(0)
    t = jnp.asarray(rng.normal(size=t_shape), jnp.bfloat16)
    g = jnp.asarray(rng.normal(size=t_shape), jnp.bfloat16)
    # the cell's positions: each row's own left shift, as a padded batch has
    pos = jnp.maximum(jnp.arange(args.rows, dtype=jnp.int32)[None] - jnp.arange(args.batch, dtype=jnp.int32)[:, None], 0)
    bits = lambda x: np.asarray(x).view(np.uint16)  # noqa: E731
    ref = [bits(x) for x in compiled[args.variants[0]](t, g, pos)]
    differ = {}
    for name in args.variants:
        out = [bits(x) for x in compiled[name](t, g, pos)]
        differ[name] = [int((a != b).sum()) for a, b in zip(out, ref)]

    rounds = {name: [] for name in args.variants}
    for _ in range(args.rounds):
        for name in args.variants:
            with tempfile.TemporaryDirectory() as tmp:
                jax.profiler.start_trace(tmp)
                for _ in range(args.calls):
                    out = compiled[name](t, g, pos)
                jax.block_until_ready(out)
                jax.profiler.stop_trace()
                rounds[name].append({k: v / args.calls for k, v in device_ms(tmp).items()})

    rows = []
    print(f"\n{t_shape} bf16, {args.heads} heads, {args.rotated} of {args.head_dim} channels rotated: forward + backward, "
          f"device ms a call, median of {args.rounds} rounds of {args.calls} calls; elements that differ from "
          f"{args.variants[0]}'s (forward, backward)")
    for name in args.variants:
        ops = {k: float(np.median([r.get(k, 0.0) for r in rounds[name]])) for k in rounds[name][0]}
        total = float(np.median([sum(r.values()) for r in rounds[name]]))
        rows.append(dict(variant=name, ms=total, ops=ops, differ=differ[name]))
        top = ", ".join(f"{k} {v:.3f}" for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:6])
        print(f"{name:<10} {total:8.3f} ms   differ {differ[name]}   {top}")
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, "rotary_ab.json"), "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
