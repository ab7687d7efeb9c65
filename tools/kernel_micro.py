"""Flash-kernel microbenchmark on the flagship attention geometries.

Times the packed kernels ALONE (forward, and forward+backward) on the exact
CA/SA shapes of the 16k flagship at batch 4, against their matmul rooflines,
so kernel-internal changes can be iterated without 4-minute full-model
compiles. Same-process variant interleaving (see tools/kernel_ab.py for why
cross-process comparisons are untrustworthy here).

    python tools/kernel_micro.py [--variants all none] [--fwd-only]
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

# flagship attention geometries at batch 4 (16k ctx, 1024 latents, 8 x 64
# heads, 0.5 prefix dropout -> CA kv 8704)
GEOMS = {
    "ca": dict(b=4, nq=1024, nkv=8704, h=8, d=64),
    "sa": dict(b=4, nq=1024, nkv=1024, h=8, d=64),
}
PEAK_TFLOPS = 197e12  # v5e bf16
# roofline denominator: measured CA-fwd runs at >100% of a 0.5x ceiling, so
# K=64 contractions are NOT half-rate on this chip — report vs full peak
MXU_CEILING = 1.0


# score-tile matmuls executed per alive kernel: fwd kernel = s + o; the
# backward of these one-q-block geometries is ONE kernel since PR 29
# (recompute-s + dv + dp + dk + dq), so the 'dq' and 'dkv' chains below both
# keep it alive whole and read the same kernel (tools/tile_plan_ab.py
# --backward times it against the dkv + dq pair)
_CHAIN_MATMULS = {"fwd": 2, "dq": 2 + 5, "dkv": 2 + 5, "fwdbwd": 2 + 5}


def roofline_ms(g, chain: str) -> float:
    per_head = 2 * g["nq"] * g["nkv"] * g["d"]  # one tile matmul (x2 flops)
    flops = 2 * per_head * _CHAIN_MATMULS[chain] * g["h"] * g["b"]
    return flops / (PEAK_TFLOPS * MXU_CEILING) * 1e3


def main():
    from perceiver_io_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--variants", nargs="*", default=["none", "all"])
    p.add_argument("--geoms", nargs="*", default=["ca", "sa"])
    p.add_argument("--fwd-only", action="store_true")
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--block-q", type=int, default=None)
    p.add_argument("--block-kv", type=int, default=None)
    args = p.parse_args()

    import perceiver_io_tpu.ops.flash_attention
    fa = sys.modules["perceiver_io_tpu.ops.flash_attention"]

    def mode(name):
        # "bkv1088" / "bq512": round-2 kernels with BWD_BLOCK_KV/Q overridden
        if name.startswith("bkv") or name.startswith("bq"):
            return False
        return True if name == "all" else False if name == "none" else name.split(",")

    def bwd_blocks(name):
        if name.startswith("bkv"):
            return None, int(name[3:])
        if name.startswith("bq"):
            return int(name[2:]), None
        return None, None

    rng = np.random.default_rng(0)
    runs = {}  # (variant, geom, mode) -> fn(iters) -> float
    for gname in args.geoms:
        g = GEOMS[gname]
        q = jnp.asarray(rng.normal(size=(g["b"], g["nq"], g["h"] * g["d"])), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(g["b"], g["nkv"], g["h"] * g["d"])), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(g["b"], g["nkv"], g["h"] * g["d"])), jnp.bfloat16)

        for vname in args.variants:
            fa.set_fast_kernels(mode(vname))
            fa.BWD_BLOCK_Q, fa.BWD_BLOCK_KV = bwd_blocks(vname)

            def attn(q, k, v):
                return fa.flash_attention_packed(
                    q, k, v, num_heads=g["h"], causal=True, sm_scale=g["d"] ** -0.5,
                    block_q=args.block_q, block_kv=args.block_kv,
                )

            @functools.partial(jax.jit, static_argnums=3)
            def fwd_chain(q, k, v, iters):
                def body(c, _):
                    o = attn(c, k, v)
                    # feed output back through q so steps serialize
                    return o.astype(c.dtype), ()

                c, _ = jax.lax.scan(body, q, None, length=iters)
                return jnp.sum(c.astype(jnp.float32))

            def loss(q, k, v):
                return jnp.sum(attn(q, k, v).astype(jnp.float32))

            # per-kernel isolation: a gradient that is not fed back into the
            # carry is dead code and XLA REMOVES its kernel (observed:
            # impossible >100%-of-roofline readings). 'dq' keeps fwd+dq
            # kernels alive; 'dkv' keeps fwd+dkv alive (both the one
            # backward kernel where the queries are one block); a *0
            # contribution would likewise DCE the whole backward.
            eps = jnp.bfloat16(1e-3)

            @functools.partial(jax.jit, static_argnums=3)
            def dq_chain(q, k, v, iters):
                def body(c, _):
                    dq = jax.grad(loss, argnums=0)(c, k, v)
                    return (c + dq.astype(c.dtype) * eps).astype(c.dtype), ()

                c, _ = jax.lax.scan(body, q, None, length=iters)
                return jnp.sum(c.astype(jnp.float32))

            @functools.partial(jax.jit, static_argnums=3)
            def dkv_chain(q, k, v, iters):
                def body(c, _):
                    ck, cv = c
                    dk, dv = jax.grad(loss, argnums=(1, 2))(q, ck, cv)
                    return (
                        (ck + dk.astype(ck.dtype) * eps).astype(ck.dtype),
                        (cv + dv.astype(cv.dtype) * eps).astype(cv.dtype),
                    ), ()

                (ck, cv), _ = jax.lax.scan(body, (k, v), None, length=iters)
                return jnp.sum(ck.astype(jnp.float32)) + jnp.sum(cv.astype(jnp.float32))

            @functools.partial(jax.jit, static_argnums=3)
            def fwdbwd_chain(q, k, v, iters):
                def body(c, _):
                    dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(c, k, v)
                    keep = (
                        jnp.sum(dk.astype(jnp.float32)) + jnp.sum(dv.astype(jnp.float32))
                    ).astype(c.dtype)
                    return (c + dq.astype(c.dtype) * eps + keep * eps).astype(c.dtype), ()

                c, _ = jax.lax.scan(body, q, None, length=iters)
                return jnp.sum(c.astype(jnp.float32))

            chains = {"fwd": fwd_chain}
            if not args.fwd_only:
                chains.update({"dq": dq_chain, "dkv": dkv_chain, "fwdbwd": fwdbwd_chain})
            for cname, chain in chains.items():
                fn = lambda it, ch=chain, q=q, k=k, v=v: float(ch(q, k, v, it))
                # compile NOW, while this variant's trace-time flag is
                # active — jit traces lazily, so deferring the first call
                # would trace every variant with the LAST flag value
                t0 = time.perf_counter()
                fn(2)
                fn(2 + args.iters)
                print(f"{(vname, gname, cname)}: compiled in {time.perf_counter() - t0:.0f}s", flush=True)
                runs[(vname, gname, cname)] = fn
    fa.BWD_BLOCK_Q = fa.BWD_BLOCK_KV = None
    fa.set_fast_kernels(False)  # library default (round-2 kernels)

    n_short, n_long = 2, 2 + args.iters

    # interleave ALL variants inside each rep (bench.interleaved_slopes) —
    # sequential per-variant robust_slope windows minutes apart are swamped
    # by the chip's 1.5-1.8x burst-vs-sustained clock drift (observed:
    # fwd+bwd reading "faster" than fwd alone)
    meds = interleaved_slopes(runs, n_short, n_long)
    results = {k: (float("inf") if m is None else m) for k, m in meds.items()}

    print(f"\n{'variant':<22} {'geom':<4} {'pass':<7} {'ms':>8} {'roofline':>9} {'% of ceil':>9}")
    for (vname, gname, cname), t in results.items():
        ms = t * 1e3
        roof = roofline_ms(GEOMS[gname], cname)
        print(f"{vname:<22} {gname:<4} {cname:<7} {ms:8.3f} {roof:9.3f} {100 * roof / ms:8.1f}%")


if __name__ == "__main__":
    main()
