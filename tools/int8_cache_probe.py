"""Feasibility probe for an int8 KV cache on the batched decode hot loop.

Decode b=8 is bandwidth-SATURATED (ceiling_fraction ~1.0) and hard-capped at
vs_baseline 0.878 by v5e's 1.9x bandwidth deficit to A100 — as long as both
sides move bf16. Per-token-quantized int8 storage halves the dominant cache
traffic, and the scales fold into elementwise ops OUTSIDE the two cache
GEMMs (scores: per-column scale after the QK GEMM; values: fold the scale
into the attention weights before the AV GEMM), so the only question is
whether XLA reads an int8 GEMM operand at int8 bytes or materializes a
bf16-converted copy of the cache each step (which would UNDO the win — the
round-3 single-query f32-convert lesson, core/attention.py block-diag note).

This probe times the two decode GEMMs + softmax over a (B, M, C) cache in
bf16 vs int8-with-scales, shapes matched to the 16k flagship CA cache.

    python tools/int8_cache_probe.py
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


def main():
    from perceiver_io_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--slots", type=int, default=16384)
    p.add_argument("--channels", type=int, default=512)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--steps", type=int, default=64)
    p.add_argument("--reps", type=int, default=4)
    args = p.parse_args()

    b, m, c, h = args.batch, args.slots, args.channels, args.heads
    rng = np.random.default_rng(0)
    k_f = jnp.asarray(rng.normal(size=(b, m, c)), jnp.bfloat16)
    v_f = jnp.asarray(rng.normal(size=(b, m, c)), jnp.bfloat16)
    # per-token symmetric quantization
    k_np = np.asarray(k_f, np.float32)
    v_np = np.asarray(v_f, np.float32)
    ks = np.abs(k_np).max(-1, keepdims=True) / 127.0
    vs = np.abs(v_np).max(-1, keepdims=True) / 127.0
    k_q = jnp.asarray(np.round(k_np / ks).astype(np.int8))
    v_q = jnp.asarray(np.round(v_np / vs).astype(np.int8))
    k_s = jnp.asarray(ks[..., 0], jnp.bfloat16)  # (B, M)
    v_s = jnp.asarray(vs[..., 0], jnp.bfloat16)
    qd = jnp.asarray(rng.normal(size=(b, h, c)), jnp.bfloat16)

    def body_bf16(ops, carry):
        k, v = ops
        scores = jnp.einsum("bhc,bjc->bhj", qd + carry, k, preferred_element_type=jnp.float32)
        attn = jax.nn.softmax(scores)
        out = jnp.einsum("bhj,bjc->bhc", attn.astype(v.dtype), v)
        return carry + out.mean() * 1e-9

    def body_int8(ops, carry):
        k, v, s_k, s_v = ops
        scores = jnp.einsum(
            "bhc,bjc->bhj", (qd + carry).astype(jnp.bfloat16), k.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        scores = scores * s_k[:, None, :].astype(jnp.float32)
        attn = jax.nn.softmax(scores)
        aw = attn.astype(jnp.bfloat16) * s_v[:, None, :]
        out = jnp.einsum("bhj,bjc->bhc", aw, v.astype(jnp.bfloat16))
        return carry + out.mean() * 1e-9

    def make(body, ops):
        # the caches ride as ARGUMENTS (donated into the scan closure would
        # bake them into the HLO as constants — a 500 MB compile payload)
        @functools.partial(jax.jit, static_argnums=2)
        def run(ops, c0, n):
            def step(c, _):
                return body(ops, c), ()

            cf, _ = jax.lax.scan(step, c0, None, length=n)
            return cf

        return lambda n: float(run(ops, jnp.zeros((), jnp.bfloat16), n).astype(jnp.float32))

    variants = {
        "bf16": make(body_bf16, (k_f, v_f)),
        "int8": make(body_int8, (k_q, v_q, k_s, v_s)),
    }
    n_s, n_l = 4, 4 + args.steps
    for name, call in variants.items():
        t0 = time.perf_counter()
        call(n_s)
        call(n_l)
        print(f"{name}: compiled in {time.perf_counter() - t0:.0f}s", flush=True)

    # traffic per iteration: k+v bytes (+scales for int8)
    bytes_bf16 = 2 * b * m * c * 2
    bytes_int8 = 2 * b * m * c * 1 + 2 * b * m * 2
    slopes = {v: [] for v in variants}
    for _ in range(3):
        best = {v: {"s": float("inf"), "l": float("inf")} for v in variants}
        for _ in range(args.reps):
            for v, call in variants.items():
                t0 = time.perf_counter(); call(n_s)
                best[v]["s"] = min(best[v]["s"], time.perf_counter() - t0)
                t0 = time.perf_counter(); call(n_l)
                best[v]["l"] = min(best[v]["l"], time.perf_counter() - t0)
        for v in variants:
            s = (best[v]["l"] - best[v]["s"]) / (n_l - n_s)
            if s > 0:
                slopes[v].append(s)

    print(f"{'variant':<8} {'us/iter':>8} {'GB/s eff':>9}")
    for v, byt in (("bf16", bytes_bf16), ("int8", bytes_int8)):
        ss = sorted(slopes[v])
        if not ss:
            print(f"{v:<8}  non-positive slopes — rerun")
            continue
        med = (ss[(len(ss) - 1) // 2] + ss[len(ss) // 2]) / 2
        print(f"{v:<8} {med * 1e6:8.1f} {byt / med / 1e9:9.0f}")


if __name__ == "__main__":
    main()
