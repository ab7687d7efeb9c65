"""Same-process A/B of the position-table gradient of the compact embedding.

    python tools/embed_grad_ab.py                      # on the chip
    python tools/embed_grad_ab.py --compile-only       # for a described v5e, no chip

At the shape of ``ar16k-train-b32`` (batch 32, 7 680 kept of 15 360 positions,
512 channels, bf16) one process builds each way of computing
``d_table[p] = sum_b sum_k [idx[b,k] == p] g[b,k]``, runs them round-robin,
each round under its own profiler capture, and reads the device time of
every operation of the call from the capture: wall clocks drift between
processes on this chip, device times in one process do not. Every variant's
result is compared with the scatter-add's.

- ``inverse``: the VJP that ``ops.gathers.gather_sorted_table_rows`` had
  until PR 31 (invert the index map with two int scatters, gather ``g`` into
  (B, N, C) rows, mask, sum over the batch), kept here as what the kernel
  replaced;
- ``scatter``: XLA's scatter-add, what ``plain_gathers()`` and a channel
  count off the 128 lanes take;
- ``tiles<T>``: ``ops.gathers._embed_table_grad`` with tiles of T positions
  (the program runs ``EMBED_TILE`` and has no switch).

PERF.md 6 (PR 31) has the readings that set ``EMBED_TILE``.
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

from perceiver_io_tpu.ops import gathers

BATCH, POSITIONS, KEPT, CHANNELS = 32, 15360, 7680, 512


def inverse(idx, g, n):
    inv, kept = gathers._invert_idx(idx, n)
    d_b = jnp.take_along_axis(g, inv[..., None], axis=1)  # (B, N, C)
    return jnp.where(kept[..., None], d_b, 0).sum(axis=0)


def scatter(idx, g, n):
    flat = jnp.zeros((n, g.shape[-1]), jnp.float32).at[idx.reshape(-1)].add(g.reshape(-1, g.shape[-1]))
    return flat.astype(g.dtype)


def variant_fn(name: str):
    if name == "inverse":
        return inverse
    if name == "scatter":
        return scatter
    tile = int(name.removeprefix("tiles"))
    return lambda idx, g, n: gathers._embed_table_grad(idx, g, n, tile=tile)


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
    p.add_argument("--variants", nargs="+", default=["inverse", "scatter", "tiles128", "tiles256"])
    p.add_argument("--batch", type=int, default=BATCH)
    p.add_argument("--positions", type=int, default=POSITIONS)
    p.add_argument("--kept", type=int, default=KEPT)
    p.add_argument("--channels", type=int, default=CHANNELS)
    p.add_argument("--calls", type=int, default=8)
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--compile-only", action="store_true")
    p.add_argument("--out", default=None, help="write the table as JSON here")
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
        raise SystemExit("embed_grad_ab times the table gradient on the chip: no TPU here (use --compile-only)")

    n = args.positions
    shapes = (
        jax.ShapeDtypeStruct((args.batch, args.kept), jnp.int32, sharding=sharding),
        jax.ShapeDtypeStruct((args.batch, args.kept, args.channels), jnp.bfloat16, sharding=sharding),
    )
    compiled = {}
    for name in args.variants:
        fn = variant_fn(name)
        with jax.default_matmul_precision("default"):
            compiled[name] = jax.jit(lambda idx, g, fn=fn: fn(idx, g, n)).lower(*shapes).compile()
        mem = compiled[name].memory_analysis()
        print(f"{name}: compiled, temporaries {getattr(mem, 'temp_size_in_bytes', None)} bytes", flush=True)
        if args.out and args.compile_only:
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, f"{name}.hlo.txt"), "w") as f:
                f.write(compiled[name].as_text())
    if args.compile_only:
        return

    rng = np.random.default_rng(0)
    # the cell's keep sets: a sorted uniform draw of `kept` of `positions` per batch row
    idx = np.sort(np.stack([rng.permutation(n)[: args.kept] for _ in range(args.batch)]), axis=-1)
    idx = jnp.asarray(idx, jnp.int32)
    g = jnp.asarray(rng.normal(size=shapes[1].shape), jnp.bfloat16)
    ref = np.asarray(scatter(idx, g, n), np.float32)
    gaps = {}
    for name in args.variants:
        out = np.asarray(compiled[name](idx, g), np.float32)
        gaps[name] = float(np.linalg.norm(out - ref) / np.linalg.norm(ref))

    rounds = {name: [] for name in args.variants}
    for _ in range(args.rounds):
        for name in args.variants:
            with tempfile.TemporaryDirectory() as tmp:
                jax.profiler.start_trace(tmp)
                for _ in range(args.calls):
                    out = compiled[name](idx, g)
                jax.block_until_ready(out)
                jax.profiler.stop_trace()
                rounds[name].append({k: v / args.calls for k, v in device_ms(tmp).items()})

    rows = []
    print(f"\nbatch {args.batch}, {args.kept} of {n} positions, {args.channels} channels, bf16: "
          f"device ms a call, median of {args.rounds} rounds of {args.calls} calls")
    for name in args.variants:
        ops = {k: float(np.median([r.get(k, 0.0) for r in rounds[name]])) for k in rounds[name][0]}
        total = float(np.median([sum(r.values()) for r in rounds[name]]))
        rows.append(dict(variant=name, ms=total, ops=ops, gap_to_scatter=gaps[name]))
        top = ", ".join(f"{k} {v:.3f}" for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:6])
        print(f"{name:<10} {total:8.3f} ms   gap to scatter-add {gaps[name]:.2e}   {top}")
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, "embed_grad_ab.json"), "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
