"""Same-process A/B of a Mamba mixer's recurrence at the shapes of ``jamba2-3b-decode-b256``.

    python tools/ssm_scan_ab.py                      # on the chip
    python tools/ssm_scan_ab.py --compile-only       # for a described v5e, no chip

**The prompt pass's side**: one chunk of whole rows (16 rows of 256 tokens, 5120
channels, 16 states) from the convolved ``x``, the step size's pre-activation,
``B``, ``C`` and the gate's ``z`` to the gated ``y * silu(z)`` in bfloat16 (what
the output projection reads). Each variant is one jitted function, run
round-robin, each round under its own profiler capture; the device time of
every operation of the call is read from the capture, and every result is
compared with the token-by-token ``lax.scan``'s.

- ``program``: ``ops.selective_scan.selective_scan`` as the mixer calls it: the
  recurrence alone in the kernel, which reads ``x`` and the step size and
  writes ``y`` as ``(rows, T, D)`` and turns 8 tokens x 8 lane tiles on
  registers; the step size's bias and softplus, the skip and the gate are XLA's
  fusions around it;
- ``view4d``: the wrapper the program had until PR 47: the kernel addresses
  ``[.., D / 128, 128]`` views, which on the chip are physical copies of ``x``
  (widened), the step size and ``y`` around it (one call prints both sides'
  kernel and XLA's share);
- ``chunk64``, ``chunk256``: the same with another time chunk (``TIME_CHUNK``
  is 128);
- ``dt_fused``: the bias, the softplus and the skip inside the kernel (it reads
  the pre-activation; the gate is XLA's): what the program ran until the A/B;
- ``gate_fused``: those **and** the gate inside (``z`` a third input stream,
  ``y`` written gated, in bfloat16);
- ``lax_scan``: the token-by-token reference (256 tiny programs a row: why
  there is a kernel).

**A step's side** (``update``): eight decode steps of one layer's state update in
one program, the state ``f32[256, 16, 5120]`` carried by the loop so that it is
updated in place as in the generator, as the one XLA fusion ``core/ssm.py``
leaves it; its time a step against the state's bytes read and written once at
the HBM peak (167.8 MB: 205 us) says whether a kernel could gain anything.

PERF.md 6 (PR 41, PR 47) has the readings; the program has no switch for the variants.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from perceiver_io_tpu.ops import selective_scan as ss

ROWS, LENGTH, D_INNER, D_STATE, BATCH = 16, 256, 5120, 16, 256
SCAN_VARIANTS = ("program", "view4d", "chunk64", "chunk256", "dt_fused", "gate_fused", "lax_scan")


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _variant_kernel(bc_ref, x_ref, pre_ref, *rest, d_state: int, chunk: int, fuse_dt: bool, fuse_gate: bool):
    """The scan over ``[.., D / 128, 128]`` views, a token a loop trip (whole chunks only): the recurrence alone
    (``view4d``: ``pre_ref`` is the step size), or with the step size's bias, softplus and skip inside, and the gate as a switch."""
    z_ref, rest = (rest[0], rest[1:]) if fuse_gate else (None, rest)
    a_ref, *consts, y_ref, state_ref, h_scr = rest
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _start():
        h_scr[...] = jnp.zeros_like(h_scr)

    bias, skip = (ref[...] for ref in consts) if fuse_dt else (None, None)

    def token(t, h):
        x = x_ref[0, t]
        dt = _softplus(pre_ref[0, t] + bias) if fuse_dt else pre_ref[0, t]
        dtx = dt * x
        y = skip * x if fuse_dt else None
        base = t * (2 * d_state)
        new = []
        for n in range(d_state):
            h_n = jnp.exp(dt * a_ref[n]) * h[n] + dtx * bc_ref[base + n]
            y_n = h_n * bc_ref[base + d_state + n]
            y = y_n if y is None else y + y_n
            new.append(h_n)
        if fuse_gate:
            z = z_ref[0, t].astype(jnp.float32)
            y = y * (z / (1.0 + jnp.exp(-z)))
        y_ref[0, t] = y.astype(y_ref.dtype)
        return tuple(new)

    h = lax.fori_loop(0, chunk, token, tuple(h_scr[n] for n in range(d_state)))
    for n in range(d_state):
        h_scr[n] = h[n]

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        state_ref[0] = h_scr[...]


def _variant_scan(x, pre, b, c, z, a, dt_bias, d_skip, *, fuse_dt: bool = True, fuse_gate: bool = False):
    from perceiver_io_tpu.ops.flash_attention import _VMEM_LIMIT, _interpret_default

    rows, length, d_inner = x.shape
    d_state = b.shape[-1]
    groups, sub, lanes = ss._tile_shape(d_inner)
    chunk = ss.TIME_CHUNK
    n_chunks = length // chunk
    f32 = jnp.float32
    bc = jnp.concatenate([b.astype(f32), c.astype(f32)], axis=-1).reshape(-1)
    view = lambda t, dtype=f32: t.astype(dtype).reshape(*t.shape[:-1], groups, lanes)  # noqa: E731
    stream = pl.BlockSpec((1, chunk, sub, lanes), lambda r, i, j: (r, j, i, 0))
    consts = [view(dt_bias), view(d_skip)] if fuse_dt else []
    gate = [stream] if fuse_gate else []
    y, state = pl.pallas_call(
        functools.partial(_variant_kernel, d_state=d_state, chunk=chunk, fuse_dt=fuse_dt, fuse_gate=fuse_gate),
        name=f"ssm_scan_ab_dt{int(fuse_dt)}_gate{int(fuse_gate)}",
        grid=(rows, groups // sub, n_chunks),
        in_specs=[pl.BlockSpec((chunk * 2 * d_state,), lambda r, i, j: (r * n_chunks + j,), memory_space=pltpu.SMEM),
                  stream, stream, *gate, pl.BlockSpec((d_state, sub, lanes), lambda r, i, j: (0, i, 0)),
                  *[pl.BlockSpec((sub, lanes), lambda r, i, j: (i, 0)) for _ in consts]],
        out_specs=[stream, pl.BlockSpec((1, d_state, sub, lanes), lambda r, i, j: (r, 0, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, length, groups, lanes), jnp.bfloat16 if fuse_gate else f32),
                   jax.ShapeDtypeStruct((rows, d_state, groups, lanes), f32)],
        scratch_shapes=[pltpu.VMEM((d_state, sub, lanes), f32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret_default(),
    )(bc, view(x), view(pre), *([view(z, z.dtype)] if fuse_gate else []), view(a), *consts)
    return y.reshape(rows, length, d_inner), state.reshape(rows, d_state, d_inner)


def _tail(y, x, z, d_skip):
    """The mixer's skip and gate, as XLA runs them after the kernel."""
    y = y + d_skip.astype(jnp.float32) * x.astype(jnp.float32)
    return (y * jax.nn.silu(z.astype(jnp.float32))).astype(jnp.bfloat16)


def scan_variant(name: str):
    """``(x, pre, b, c, z, a, dt_bias, d_skip) -> (gated y in bfloat16, final state)``."""
    if name in ("program", "chunk64", "chunk256"):
        chunk = {"program": ss.TIME_CHUNK, "chunk64": 64, "chunk256": 256}[name]

        def fn(x, pre, b, c, z, a, dt_bias, d_skip):
            dt = jax.nn.softplus(pre + dt_bias.astype(jnp.float32))
            ss.TIME_CHUNK, kept = chunk, ss.TIME_CHUNK  # read at trace time
            try:
                y, h = ss._scan.__wrapped__(x, dt, b, c, a)
            finally:
                ss.TIME_CHUNK = kept
            return _tail(y, x, z, d_skip), h
    elif name == "view4d":
        def fn(x, pre, b, c, z, a, dt_bias, d_skip):
            dt = jax.nn.softplus(pre + dt_bias.astype(jnp.float32))
            y, h = _variant_scan(x, dt, b, c, z, a, dt_bias, d_skip, fuse_dt=False)
            return _tail(y, x, z, d_skip), h
    elif name == "dt_fused":
        def fn(x, pre, b, c, z, a, dt_bias, d_skip):
            y, h = _variant_scan(x, pre, b, c, z, a, dt_bias, d_skip)
            return (y * jax.nn.silu(z.astype(jnp.float32))).astype(jnp.bfloat16), h
    elif name == "gate_fused":
        def fn(x, pre, b, c, z, a, dt_bias, d_skip):
            return _variant_scan(x, pre, b, c, z, a, dt_bias, d_skip, fuse_gate=True)
    else:
        def fn(x, pre, b, c, z, a, dt_bias, d_skip):
            y, h = ss.selective_scan_reference(x, jax.nn.softplus(pre + dt_bias.astype(jnp.float32)), b, c, a)
            return _tail(y, x, z, d_skip), h
    return fn


def update_steps(state, dt, x, b, c, a):
    """Eight steps of ``core/ssm.py::MambaMixer.step``'s update, the state the loop's carry; each step's ``y`` summed so that none is dead."""
    def step(i, carry):
        h, acc = carry
        h = jnp.exp(dt[i][:, None, :] * a[None]) * h + (dt[i] * x[i])[:, None, :] * b[i][:, :, None]
        return h, acc + jnp.sum(h * c[i][:, :, None], axis=1)

    return lax.fori_loop(0, dt.shape[0], step, (state, jnp.zeros_like(x[0])))


def device_ms(trace_dir: str) -> dict:
    from perceiver_io_tpu.obs.xplane import load_capture

    out: dict = {}
    for ops in load_capture(trace_dir)["device_ops"].values():
        for name, _, duration_ns in ops:
            if name.split(".")[0] not in ("while", "conditional", "call"):  # their bodies' operations report the time again
                out[name] = out.get(name, 0.0) + duration_ns / 1e6
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--variants", nargs="+", default=list(SCAN_VARIANTS) + ["update"], choices=list(SCAN_VARIANTS) + ["update"])
    p.add_argument("--rows", type=int, default=ROWS)
    p.add_argument("--length", type=int, default=LENGTH)
    p.add_argument("--d-inner", type=int, default=D_INNER)
    p.add_argument("--d-state", type=int, default=D_STATE)
    p.add_argument("--batch", type=int, default=BATCH, help="rows of the step's update")
    p.add_argument("--calls", type=int, default=4)
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
        raise SystemExit("ssm_scan_ab times the recurrence on the chip: no TPU here (use --compile-only)")

    r, t, d, n, bsz, steps = args.rows, args.length, args.d_inner, args.d_state, args.batch, 8
    f32, bf16 = jnp.float32, jnp.bfloat16
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)  # noqa: E731
    scan_shapes = (sds((r, t, d), bf16), sds((r, t, d), f32), sds((r, t, n), f32), sds((r, t, n), f32), sds((r, t, d), bf16),
                   sds((n, d), f32), sds((d,), bf16), sds((d,), bf16))
    update_shapes = (sds((bsz, n, d), f32), sds((steps, bsz, d), f32), sds((steps, bsz, d), f32), sds((steps, bsz, n), f32),
                     sds((steps, bsz, n), f32), sds((n, d), f32))
    compiled = {}
    for name in args.variants:
        if name == "update":
            compiled[name] = jax.jit(update_steps, donate_argnums=0).lower(*update_shapes).compile()
        else:
            compiled[name] = jax.jit(scan_variant(name)).lower(*scan_shapes).compile()
        mem = compiled[name].memory_analysis()
        print(f"{name}: compiled, temporaries {getattr(mem, 'temp_size_in_bytes', None)} bytes", flush=True)
    if args.compile_only:
        return

    rng = np.random.default_rng(0)
    normal = lambda shape, dtype, scale=1.0, shift=0.0: jnp.asarray(scale * rng.normal(size=shape) + shift, dtype)  # noqa: E731
    a = -jnp.exp(jnp.log(jnp.arange(1, n + 1, dtype=f32))[:, None] + normal((n, d), f32, 0.02))
    scan_args = (normal((r, t, d), bf16, 0.5), normal((r, t, d), f32, 0.3), normal((r, t, n), f32), normal((r, t, n), f32),
                 normal((r, t, d), bf16), a, normal((d,), bf16, 0.5, -4.0), normal((d,), bf16, 0.02, 1.0))
    update_args = lambda: (jnp.zeros((bsz, n, d), f32), jax.nn.softplus(normal((steps, bsz, d), f32, 0.5, -4.0)),  # noqa: E731
                           normal((steps, bsz, d), f32, 0.5), normal((steps, bsz, n), f32), normal((steps, bsz, n), f32), a)
    want = [np.asarray(v, np.float32) for v in compiled.get("lax_scan", jax.jit(scan_variant("lax_scan")))(*scan_args)]
    differ, results = {}, {}
    for name in args.variants:
        if name != "update":
            got = results[name] = [np.asarray(v, np.float32) for v in compiled[name](*scan_args)]
            differ[name] = [float(np.abs(g - w).max()) for g, w in zip(got, want)]
    if "program" in results and "view4d" in results:  # the turn moved, the arithmetic did not
        same = [bool((g == w).all()) for g, w in zip(results["program"], results["view4d"])]
        print(f"program against view4d, to the bit: gated y {same[0]}, final state {same[1]}", flush=True)

    rounds = {name: [] for name in args.variants}
    for _ in range(args.rounds):
        for name in args.variants:
            fixed = None if name == "update" else scan_args
            with tempfile.TemporaryDirectory() as tmp:
                fresh = [update_args() for _ in range(args.calls)] if fixed is None else None  # a donated state is used once
                jax.block_until_ready(fresh)
                jax.profiler.start_trace(tmp)
                for i in range(args.calls):
                    out = compiled[name](*(fixed or fresh[i]))
                jax.block_until_ready(out)
                jax.profiler.stop_trace()
                rounds[name].append({k: v / args.calls for k, v in device_ms(tmp).items()})

    rows = []
    print(f"\n{r} rows x {t} tokens x {d} channels x {n} states: x, the step size's pre-activation, B, C, z -> y * silu(z) in bfloat16 and the "
          f"final state; device ms a call, median of {args.rounds} rounds of {args.calls} calls; widest difference from lax_scan's (y, state)")
    for name in args.variants:
        ops = {k: float(np.median([rd.get(k, 0.0) for rd in rounds[name]])) for k in rounds[name][0]}
        total = float(np.median([sum(rd.values()) for rd in rounds[name]]))
        if name == "update":
            floor = 2 * bsz * n * d * 4 / 819e9 * 1e3
            print(f"update     {total / steps:8.4f} ms a step over {steps} steps in one program (the state's bytes read and written once at "
                  f"the HBM peak: {floor:.4f} ms, {100 * floor / max(total / steps, 1e-9):.1f}%)   "
                  + ", ".join(f"{k} {v:.3f}" for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:4]))
            rows.append(dict(variant=name, ms_a_step=total / steps, floor_ms=floor, ops=ops))
            continue
        kernel = sum(v for k, v in ops.items() if k.startswith("ssm_scan"))
        rows.append(dict(variant=name, ms=total, kernel_ms=kernel, ops=ops, differ=differ[name]))
        top = ", ".join(f"{k} {v:.3f}" for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:5])
        print(f"{name:<14} {total:8.3f} ms = kernel {kernel:.3f} + XLA {total - kernel:.3f}   differ {differ[name][0]:.2e} {differ[name][1]:.2e}   {top}")
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, "ssm_scan_ab.json"), "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
