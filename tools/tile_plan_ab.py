"""Same-process A/B of tile plans, of the two backwards and of the two forwards of the flash kernels.

One process builds every variant (grid blocks, and bands inside a tile on
the diagonal) of one attention geometry, runs them round-robin, each round
under its own profiler capture, and reads the device time of the forward, dq
and dkv kernels from the captures: wall clocks drift between processes on
this chip (docs/performance.md, round 3), kernel device times in one process do not.
Every variant's outputs and gradients are compared with the first variant's.

    python tools/tile_plan_ab.py --geom sa --variants 1024x1024 512x512 1024x1024/256 plan
    python tools/tile_plan_ab.py --geom ca --variants 1024x2176 1024x2176/256

A variant is ``<block_q>x<block_kv>[/<band rows>]``: grid blocks
of that size, every tile run whole, or every tile on the diagonal cut into
bands of that many rows; ``plan`` is what ``tile_plan`` chooses itself.
``--compile-only`` lowers and compiles every variant for a described v5e (no
chip) and runs nothing. PERF.md 6 (PR 27) has the readings that set
``_BAND_ROWS`` and ``_BAND_MAX_SHARE``.

    python tools/tile_plan_ab.py --geom ca sa img_sa img_ca --backward

``--backward`` times the two backwards of a call whose queries are one block
on the same residuals: ``split`` (dkv, then dq) against ``one`` kernel, each
through its private function (``_flash[_packed]_bwd_split`` / ``_bwd_one``; the
program picks between them by shape and has no switch). PERF.md 6 (PR 29).

    python tools/tile_plan_ab.py --geom sa ca img_sa prompt --forward

``--forward`` times the two packed forwards on the same operands, each with
and without a bias row: ``plain`` (a call whose keys are one block: ``sa``,
``img_sa``) against ``online``, through ``_flash_packed_fwd_plain`` /
``_fwd_online``, and the backward the program picks with and without the bias
operand. A call of several kv blocks (``ca``, ``prompt``) has no plain form
and gets the bias comparison alone. ``sm_scale`` is 1 as in the program (the
queries arrive scaled). The program decides both from the call's shapes and
operands (``tile_plan(...).forward`` / ``.bias``). PERF.md 6 (PR 48).
"""

from __future__ import annotations

import argparse
import concurrent.futures
import importlib
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")

# the attention calls of the benchmark's cells: latent self-attention and
# cross-attention of ar16k-train-b32, the prompt pass of ar16k-decode-b64
# and, for --backward, the two calls of imagenet-train-b16: latent
# self-attention, and the heads-major cross-attention over the pixels (one
# head of 261 channels, which the wrapper pads to 264)
GEOMS = {
    "sa": dict(b=32, nq=1024, nkv=1024, h=8, d=64, bwd=True),
    "ca": dict(b=32, nq=1024, nkv=8704, h=8, d=64, bwd=True),
    "prompt": dict(b=64, nq=768, nkv=768, h=8, d=64, bwd=False),
    "img_sa": dict(b=16, nq=512, nkv=512, h=8, d=128, bwd=True, causal=False),
    "img_ca": dict(b=16, nq=512, nkv=50176, h=1, d=264, bwd=True, causal=False, packed=False),
}
BACKWARDS = ("split", "one")


def forwards(geom: dict) -> list:
    """The ``--forward`` variants of a geometry, the program's own first."""
    plan = fa.tile_plan(geom["nq"], geom["nkv"], geom.get("causal", True))
    forms = ["plain", "online"] if plan.forward == "plain" else ["online"]
    variants = [form + bias for form in forms for bias in ("", "+bias")]
    return sorted(variants, key=lambda v: v != plan.forward + ("+bias" if plan.bias else ""))


def parse_variant(text: str):
    """``512x512/256`` -> (512, 512, 256); ``plan`` -> None blocks."""
    if text == "plan":
        return None, None, None
    blocks, _, band = text.partition("/")
    bq, bkv = (int(x) for x in blocks.split("x"))
    return bq, bkv, int(band or 0)


def build(geom: dict, variant: str, sharding=None):
    """The jitted call of one variant, lowered while its plan is in force
    (the plan is read at trace time)."""
    bq, bkv, band = parse_variant(variant)
    h, d = geom["h"], geom["d"]

    def attn(q, k, v):
        return fa.flash_attention_packed(q, k, v, num_heads=h, causal=True, sm_scale=d**-0.5)

    def fwd_bwd(q, k, v, w):
        out, vjp = jax.vjp(attn, q, k, v)
        return (out,) + vjp(w)

    fn = fwd_bwd if geom["bwd"] else lambda q, k, v, w: (attn(q, k, v),)
    shapes = [(geom["b"], n, h * d) for n in (geom["nq"], geom["nkv"], geom["nkv"], geom["nq"])]
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding) for s in shapes]
    chosen = fa.tile_plan, fa._BAND_ROWS, fa._BAND_MAX_SHARE
    if bq is not None:
        fa.tile_plan = lambda n_q, n_kv, causal, *_, pad_mask=False: fa._make_plan(n_q, n_kv, causal, bq, bkv, pad_mask)
        fa._BAND_ROWS, fa._BAND_MAX_SHARE = (band, 1.0) if band else (chosen[1], 0.0)
    try:
        with jax.default_matmul_precision("default"):
            lowered = jax.jit(fn).lower(*args)
        plan = fa.tile_plan(geom["nq"], geom["nkv"], True)
    finally:
        fa.tile_plan, fa._BAND_ROWS, fa._BAND_MAX_SHARE = chosen
    return lowered, plan


def build_backward(geom: dict, which: str, sharding=None):
    """The jitted forward and ``which`` backward of one call, as its wrapper
    makes them: the plan's blocks, a zero bias row, the padded head width."""
    b, nq, nkv, h, d = (geom[key] for key in ("b", "nq", "nkv", "h", "d"))
    causal, packed = geom.get("causal", True), geom.get("packed", True)
    plan = fa.tile_plan(nq, nkv, causal)  # the heads-major wrapper picks the same blocks
    assert (nq, nkv % plan.block_kv) == (plan.block_q, 0), "a geometry for --backward has one q block and no padding"
    statics = (causal, nkv - nq, d**-0.5, plan.block_q, plan.block_kv, h)
    statics += ((d, d) if packed else ()) + (fa._geometry(nq, nkv),)
    prefix = "_flash_packed" if packed else "_flash"
    forward, backward = getattr(fa, f"{prefix}_fwd"), getattr(fa, f"{prefix}_bwd_{which}")

    def fwd_bwd(q, k, v, w):
        out, residuals = forward(q, k, v, jnp.zeros((b, 1, nkv), jnp.float32), *statics)
        return (out,) + backward(*statics, residuals, w)[:3]

    shape = (lambda n: (b, n, h * d)) if packed else (lambda n: (b * h, n, d))
    args = [jax.ShapeDtypeStruct(shape(n), jnp.bfloat16, sharding=sharding) for n in (nq, nkv, nkv, nq)]
    with jax.default_matmul_precision("default"):
        return jax.jit(fwd_bwd).lower(*args), plan


def build_forward(geom: dict, variant: str, sharding=None):
    """The jitted ``variant`` forward (``plain`` / ``online``, ``+bias`` for a
    bias row) of one call on the operands its wrapper makes (the plan's
    blocks, keys padded to them), and the backward the program picks for it."""
    b, nq, nkv, h, d = (geom[key] for key in ("b", "nq", "nkv", "h", "d"))
    causal = geom.get("causal", True)
    form, _, with_bias = variant.partition("+")
    plan = fa.tile_plan(nq, nkv, causal)
    # without its bias row a call with padded keys is sound only where the causal mask hides the padding
    assert with_bias or causal or nkv % plan.block_kv == 0, "padded keys need their bias row"
    statics = (causal, nkv - nq, 1.0, plan.block_q, plan.block_kv, h, d, d, fa._geometry(nq, nkv))
    forward = getattr(fa, f"_flash_packed_fwd_{form}")

    def fwd_bwd(q, k, v, w):
        qf, wf = (fa._pad_to(x, 1, plan.block_q) for x in (q, w))
        kf, vf = (fa._pad_to(x, 1, plan.block_kv) for x in (k, v))
        bias = None
        if with_bias:
            bias = jnp.zeros((b, 1, kf.shape[1]), jnp.float32).at[:, :, nkv:].set(fa.MASK_VALUE)
        out, lse = forward(qf, kf, vf, bias, *statics)
        if not geom["bwd"]:
            return (out[:, :nq],)
        dq, dk, dv, _ = fa._flash_packed_bwd(*statics, (qf, kf, vf, bias, out, fa._slim_lse(lse, h)), wf)
        return out[:, :nq], dq[:, :nq], dk[:, :nkv], dv[:, :nkv]

    args = [jax.ShapeDtypeStruct((b, n, h * d), jnp.bfloat16, sharding=sharding) for n in (nq, nkv, nkv, nq)]
    with jax.default_matmul_precision("default"):
        return jax.jit(fwd_bwd).lower(*args), plan


def flash_ms(trace_dir: str) -> dict:
    """Device ms per flash pass (``fwd``, ``dq``, ``dkv``, ``bwd``) in one capture."""
    from perceiver_io_tpu.obs.xplane import load_capture

    out: dict = {}
    for ops in load_capture(trace_dir)["device_ops"].values():
        for name, _, duration_ns in ops:
            if "flash_" in name:
                pass_ = name.split("flash_", 1)[1].split("_", 1)[0]
                out[pass_] = out.get(pass_, 0.0) + duration_ns / 1e6
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--geom", choices=sorted(GEOMS), nargs="+", required=True)
    p.add_argument("--variants", nargs="+", help="tile plans to compare (not with --backward)")
    p.add_argument("--backward", action="store_true", help="compare the split backward with the one-kernel one")
    p.add_argument("--forward", action="store_true", help="compare the plain forward with the online one, with and without a bias row")
    p.add_argument("--calls", type=int, default=8)
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--compile-only", action="store_true")
    p.add_argument("--out", default=None, help="write the table as JSON here")
    args = p.parse_args()
    if args.backward + args.forward + bool(args.variants) != 1:
        p.error("give --variants, --backward or --forward")
    if args.forward and not all(GEOMS[name].get("packed", True) for name in args.geom):
        p.error("--forward compares the packed forwards: img_ca is heads-major")

    sharding = None
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
        fa._interpret_default = lambda: False
        jax.config.update("jax_enable_compilation_cache", False)
    elif jax.default_backend() != "tpu":
        raise SystemExit("tile_plan_ab times kernels on the chip: no TPU here (use --compile-only)")

    rows = []
    for name in args.geom:
        variants = list(BACKWARDS) if args.backward else forwards(GEOMS[name]) if args.forward else args.variants
        rows += run_geom(name, variants, args, sharding)
    if args.out and rows:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)


def run_geom(name: str, variants: list, args, sharding) -> list:
    geom = GEOMS[name]
    lowered = {}
    for variant in variants:
        lowered[variant] = (build_backward if args.backward else build_forward if args.forward else build)(geom, variant, sharding)
        print(f"{name} {variant}: {lowered[variant][1]}", flush=True)

    def compile_one(variant):
        t0 = time.perf_counter()
        exe = lowered[variant][0].compile()
        return variant, exe, time.perf_counter() - t0

    compiled = {}
    with concurrent.futures.ThreadPoolExecutor(max_workers=4) as pool:
        for variant, exe, secs in pool.map(compile_one, variants):
            compiled[variant] = exe
            print(f"{name} {variant}: compiled in {secs:.1f} s", flush=True)
    if args.compile_only:
        return []

    rng = np.random.default_rng(0)
    operands = [jnp.asarray(rng.normal(size=a.shape), a.dtype) for a in lowered[variants[0]][0].args_info[0]]
    base = None
    gaps = {}
    for variant in variants:
        outs = [np.asarray(x, np.float32) for x in compiled[variant](*operands)]
        if base is None:
            base = outs
        gaps[variant] = [float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-30)) for a, b in zip(outs, base)]

    times = {v: [] for v in variants}
    for round_ in range(args.rounds):
        for variant in variants:
            with tempfile.TemporaryDirectory() as tmp:
                jax.profiler.start_trace(tmp)
                for _ in range(args.calls):
                    out = compiled[variant](*operands)
                jax.block_until_ready(out)
                jax.profiler.stop_trace()
                ms = flash_ms(tmp)
            times[variant].append({k: v / args.calls for k, v in ms.items()})

    rows = []
    passes = ["fwd"] + ((["bwd"] if args.forward else ["dq", "dkv"] + (["bwd"] if args.backward else [])) if geom["bwd"] else [])
    print(f"\n{name} {geom}: device ms a call, median of {args.rounds} rounds of {args.calls} calls")
    head = " ".join(f"{p_:>8}" for p_ in passes)
    print(f"{'variant':<28} {head} {'sum':>8}  run_share  gap to first (out, dq, dk, dv)")
    for variant in variants:
        med = {p_: float(np.median([t.get(p_, 0.0) for t in times[variant]])) for p_ in passes}
        plan = lowered[variant][1]
        row = dict(geom=name, variant=variant, plan=plan._asdict(), run_share=plan.run_share,
                   ms=med, ms_sum=sum(med.values()), rounds=times[variant], gap_to_first=gaps[variant])
        rows.append(row)
        print(f"{variant:<28} " + " ".join(f"{med[p_]:8.3f}" for p_ in passes)
              + f" {row['ms_sum']:8.3f}  {plan.run_share:9.3f}  " + " ".join(f"{g:.1e}" for g in gaps[variant]))
    return rows


if __name__ == "__main__":
    main()
