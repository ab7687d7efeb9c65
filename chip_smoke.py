"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the three hot paths once, on one TPU chip, through the entry points a
user calls, at the full width of the flagship (Perceiver AR CLM, 36.9M
parameters: 512 channels, 8 heads, 8 self-attention layers, vocab 262,
context 16 384, 1 024 latents, bf16 compute):

1. device    — platform must be ``tpu``; versions, device kind, peak FLOP/s
2. kernels   — ``kernel_smoke()``: every Pallas kernel against einsum
3. train     — the CLM CLI ``fit`` for a few steps (checkpoint included), then
               3 steps of the batch-32-in-8-chunks step of ``ar16k-train-b32``
4. decode    — ``make_generate_fn`` at a 16k prompt, checked against the
               uncached forward
5. serve     — ``EngineFrontEnd`` at 8 slots x 16k tokens, closed then open
               loop, checked against ``make_generate_fn``, then once more
               with the page-walk ``paged`` kernel on

One process, no children. Weights and data come from ``--seed``. Nothing is
caught: a phase that fails ends the process non-zero before the last line.
Every phase prints one JSON line; the last line of stdout is
``{"ok": true, "device": {...}}`` as JAX reports the device.

``--chips 4`` runs only the sharded train step (fsdp=4 and data=2 x fsdp=2)
and the single-device run it is compared with.

A time printed here is a sanity reading of one call, not a benchmark.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import statistics
import sys
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from perceiver_io_tpu.utils.compile_cache import enable_compile_cache
from perceiver_io_tpu.utils.device import require_tpu

# the flagship's context and latents; its widths are flagship_config's
SEQ_LEN, LATENTS = 16384, 1024
# train: the CLI fit, then the step the train cell times
CLI_BATCH, CLI_STEPS = 4, 6
BENCH_BATCH, BENCH_MICROBATCH = 32, 8
# decode
NEW_TOKENS, CHECKED_TOKENS, DECODE_BATCH = 64, 4, 8
# serve: (prompt length, new tokens) per bucket, page size, shared prefix
SLOTS, PAGE_SIZE = 8, 128
SHORT, LONG = (2048, 64), (15360, 16)
SHARED_PREFIX = 2048
# a logit within this share of the largest logit's magnitude (at least of 1)
# below the maximum counts as tied with it: bf16 keeps 8 bits of mantissa
# through nine layers, and two programs that round differently may order
# two such logits either way
LOGIT_TIE_TOL = 5e-2
# sharded and single-device losses run different reduction orders in bf16
LOSS_TOL = 3e-2

# ----------------------------------------------------------------- accounting


def programs() -> tuple:
    """Programs JAX has made ready so far (compiled or read from the
    persistent cache), the seconds that took, and how many the cache served
    and did not: the start-up record's counters (``obs/startup.py``, fed by
    JAX's own monitoring events from the package's import on)."""
    from perceiver_io_tpu.obs import default_registry

    registry = default_registry()
    total = registry.counter("startup_programs_total")
    return (int(total.value), registry.counter("startup_compile_seconds").value,
            int(total.labels(cache="hit").value), int(total.labels(cache="miss").value))


@contextlib.contextmanager
def phase(name: str):
    """Time one phase and print its JSON line: what the phase put into
    ``found``, plus programs built, compile seconds and run seconds apart."""
    found = {}
    n0, s0, h0, m0 = programs()
    t0 = time.perf_counter()
    yield found
    wall = time.perf_counter() - t0
    n1, s1, h1, m1 = programs()
    line = {
        "phase": name,
        **found,
        "programs": n1 - n0,
        "cache_hits": h1 - h0,
        "cache_misses": m1 - m0,
        "compile_s": round(s1 - s0, 2),
        "run_s": round(wall - (s1 - s0), 2),
    }
    print(json.dumps(line), flush=True)


def time_both_ways(call, fetch, n: int = 3) -> dict:
    """The same compiled call timed to ``jax.block_until_ready`` and to a
    host fetch of a value it computed, ``n`` calls each, interleaved; the
    medians in ms. If they agree, ``block_until_ready`` waits for the device
    here and the benchmark may time with it."""
    ready, fetched = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        ready.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        fetch(call())
        fetched.append(time.perf_counter() - t0)
    return {
        "block_until_ready_ms": round(statistics.median(ready) * 1e3, 3),
        "host_fetch_ms": round(statistics.median(fetched) * 1e3, 3),
    }


def read_events(run_dir: str) -> list:
    with open(os.path.join(run_dir, "events.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


# --------------------------------------------------------------------- phases


def device_phase(n_chips: int):
    from importlib.metadata import version

    from perceiver_io_tpu.obs.mfu import device_peak_flops

    with phase("device") as found:
        device = require_tpu("chip_smoke.py")
        if len(jax.devices()) != n_chips:
            raise SystemExit(f"chip_smoke.py needs {n_chips} chip(s), JAX found {len(jax.devices())}")
        peak = device_peak_flops(device)
        assert peak is not None, f"no peak FLOP/s for device kind {device.device_kind!r}"
        found.update(
            jax=version("jax"), jaxlib=version("jaxlib"), libtpu=version("libtpu"),
            platform=device.platform, device_kind=device.device_kind,
            device_count=len(jax.devices()), peak_flops_per_device=peak,
        )
    return device


def kernel_smoke() -> None:
    """Mosaic-lowering regression gate (VERDICT r4 item 8): the CPU test
    suite exercises the Pallas kernels in interpret mode only, so a real-TPU
    lowering regression could hide there. Asserts, at micro shapes (seconds, not
    minutes):

    - packed flash attention (the flagship hot path) fwd AND bwd against
      the materialized-scores einsum reference,
    - heads-major flash attention fwd (the fallback layout),
    - the cached block-diagonal decode step (bf16 and int8 KV storage)
      against the module's own einsum fallback path (reached via a 2-token
      decode; its first query sees exactly the 1-token step's slots),
    - the page-walk paged decode kernel (f32 and bf16 pools, ragged slot
      lengths, a shuffled page table) against the gather-view reference.
    """
    t0 = time.perf_counter()
    from perceiver_io_tpu.core.attention import MultiHeadAttention, init_kv_cache, prefill_mode
    from perceiver_io_tpu.ops.flash_attention import flash_attention, flash_attention_packed

    rng = np.random.default_rng(0)
    b, h, nq, nkv, d = 2, 4, 256, 512, 64

    def t(shape, scale=0.5):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.bfloat16)

    q, k, v = t((b, h, nq, d)), t((b, h, nkv, d)), t((b, h, nkv, d))
    cot = t((b, h, nq, d))

    def ref(q, k, v):
        s = jnp.einsum("bhic,bhjc->bhij", q, k, preferred_element_type=jnp.float32)
        i = jnp.arange(nq, dtype=jnp.int32)[:, None] + (nkv - nq)
        j = jnp.arange(nkv, dtype=jnp.int32)[None, :]
        s = jnp.where(j > i, -jnp.finfo(jnp.float32).max, s)
        return jnp.einsum("bhij,bhjc->bhic", jax.nn.softmax(s).astype(v.dtype), v)

    def loss_ref(q, k, v):
        return jnp.vdot(ref(q, k, v).astype(jnp.float32), cot.astype(jnp.float32))

    # packed layout (B, N, H*D): fwd + bwd — the kernels the train step runs
    def packed(x):
        return x.transpose(0, 2, 1, 3).reshape(x.shape[0], x.shape[2], -1)

    def loss_packed(qp, kp, vp):
        o = flash_attention_packed(qp, kp, vp, num_heads=h, causal=True, sm_scale=1.0)
        return jnp.vdot(o.astype(jnp.float32), packed(cot).astype(jnp.float32))

    o_ref = jax.jit(ref)(q, k, v)
    o_packed = jax.jit(
        lambda a, c, w: flash_attention_packed(a, c, w, num_heads=h, causal=True, sm_scale=1.0)
    )(packed(q), packed(k), packed(v))
    err = float(jnp.abs(o_packed - packed(o_ref)).max())
    assert err < 2e-2, f"packed flash fwd diverges from einsum: max abs {err}"

    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    g_pk = jax.jit(jax.grad(loss_packed, argnums=(0, 1, 2)))(packed(q), packed(k), packed(v))
    for name, a, bb in zip("qkv", g_ref, g_pk):
        gerr = float(jnp.abs(jnp.asarray(bb) - packed(a)).max())
        assert gerr < 5e-2, f"packed flash bwd d{name} diverges: max abs {gerr}"

    o_hm = jax.jit(lambda a, c, w: flash_attention(a, c, w, causal=True, sm_scale=1.0))(q, k, v)
    err = float(jnp.abs(o_hm - o_ref).max())
    assert err < 2e-2, f"heads-major flash fwd diverges from einsum: max abs {err}"

    # cached decode: block-diagonal single-token step vs the einsum fallback
    # (2-token step, first query) — bf16 and int8 KV storage
    c = 256
    mha = MultiHeadAttention(
        num_heads=h, num_q_input_channels=c, num_kv_input_channels=c, causal_attention=True
    )
    x = t((b, 128, c))
    tok2 = t((b, 2, c))
    params = mha.init(jax.random.PRNGKey(0), x, x)

    @functools.partial(jax.jit, static_argnames=("dt",))
    def decode_pair(params, x, tok2, dt):
        cache = init_kv_cache(b, 130, c, c, dtype=jnp.int8 if dt == "int8" else jnp.bfloat16)
        with prefill_mode():
            filled = mha.apply(params, x, x, kv_cache=cache)
        one = mha.apply(params, tok2[:, :1], tok2[:, :1], kv_cache=filled.kv_cache)
        two = mha.apply(params, tok2, tok2, kv_cache=filled.kv_cache)
        return one.last_hidden_state[:, 0], two.last_hidden_state[:, 0]

    for dt in ("bf16", "int8"):
        one, two = decode_pair(params, x, tok2, dt)
        assert bool(jnp.isfinite(one).all()), f"{dt} block-diagonal decode non-finite"
        derr = float(jnp.abs(one.astype(jnp.float32) - two.astype(jnp.float32)).max())
        assert derr < 2e-2, f"{dt} block-diagonal decode diverges from einsum path: {derr}"

    # page-walk paged decode kernel vs the gather-view reference: 4 slots of
    # 8 pages x 16 tokens, pages scattered over the pool, one slot empty
    from perceiver_io_tpu.core.cache import PagedKVCache
    from perceiver_io_tpu.ops.paged_attention import (
        paged_attention_reference,
        paged_decode_attention,
    )

    slots, page, pps = 4, 16, 8
    table = 1 + rng.permutation(slots * pps).reshape(slots, pps)  # page 0 is scratch
    for dt in (jnp.float32, jnp.bfloat16):
        cache = PagedKVCache(
            k=t((1 + slots * pps, page, h * d)).astype(dt),
            v=t((1 + slots * pps, page, h * d)).astype(dt),
            page_table=jnp.asarray(table, jnp.int32),
            length=jnp.asarray([0, 17, 100, 128], jnp.int32),
        )
        qh = t((slots, h, d)).astype(dt)
        got = jax.jit(paged_decode_attention)(qh, cache)
        want = jax.jit(paged_attention_reference)(qh, cache)
        assert bool(jnp.isfinite(got).all()), f"{dt.__name__} paged decode kernel non-finite"
        perr = float(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max())
        assert perr < 2e-2, f"{dt.__name__} paged decode kernel diverges from gather reference: {perr}"

    print(f"kernel smoke ok ({time.perf_counter() - t0:.1f}s, backend={jax.devices()[0].platform})")


def kernels_phase():
    with phase("kernels") as found:
        kernel_smoke()  # asserts; covers the paged kernel too
        found["kernel_smoke"] = "passed"


def flagship_config(seq_len: int, latents: int, remat: bool = False):
    from perceiver_io_tpu.models.text import CausalLanguageModelConfig

    # byte-level Perceiver AR, the reference "small" family scaled to 16k ctx.
    # remat off by default: at 37M params the activations fit HBM comfortably
    # and rematerialization costs ~1.8x step time (measured on v5e).
    return CausalLanguageModelConfig(
        vocab_size=262,
        max_seq_len=seq_len,
        max_latents=latents,
        num_channels=512,
        num_heads=8,
        num_self_attention_layers=8,
        cross_attention_dropout=0.5,
        activation_checkpointing=remat,
    )


def clm_argv(out: str, name: str, seed: int, steps: int, log_interval: int) -> list:
    config = flagship_config(SEQ_LEN, LATENTS)
    return [
        "fit",
        "--data.dataset=synthetic",
        f"--data.max_seq_len={SEQ_LEN}",
        f"--data.batch_size={CLI_BATCH}",
        f"--data.cache_dir={os.path.join(out, 'data_cache')}",
        f"--data.seed={seed}",
        f"--model.max_latents={LATENTS}",
        f"--model.num_channels={config.num_channels}",
        f"--model.num_heads={config.num_heads}",
        f"--model.num_self_attention_layers={config.num_self_attention_layers}",
        f"--model.cross_attention_dropout={config.cross_attention_dropout}",
        "--trainer.precision=bfloat16",
        "--optimizer.moment_dtype=bfloat16",
        f"--trainer.max_steps={steps}",
        f"--trainer.log_interval={log_interval}",
        f"--trainer.default_root_dir={out}",
        f"--trainer.name={name}",
        f"--trainer.seed={seed}",
        "--trainer.checkpoint=true",
    ]


def flagship_model():
    from perceiver_io_tpu.models.text import CausalLanguageModel

    return CausalLanguageModel(flagship_config(SEQ_LEN, LATENTS), dtype=jnp.bfloat16)


def check_fit_events(run_dir: str) -> dict:
    """The event stream of one CLI fit: started, compiled once per program,
    logged finite losses, linted without error, ended clean."""
    events = read_events(run_dir)
    kinds = [e["event"] for e in events]
    assert kinds.count("fit_start") == 1, kinds
    end = [e for e in events if e["event"] == "fit_end"]
    assert len(end) == 1 and end[0]["aborted"] is False, end
    logs = [e for e in events if e["event"] == "log"]
    assert logs, "no log event"
    losses = [e["train_loss"] for e in logs]
    assert all(isinstance(x, float) and np.isfinite(x) for x in losses), losses
    lint = [e for e in events if e["event"] == "graphlint"]
    assert len(lint) == 1 and "error" not in lint[0] and lint[0]["ok"] is True, lint
    compiles = [e for e in events if e["event"] == "compile"]
    assert compiles, "no compile event"
    train_compiles = [e for e in compiles if e.get("fn") == "train_step"]
    # no recompile after the first step: one train_step program for the fit
    assert len(train_compiles) == 1 and end[0]["recompiles"]["train_step"] == 1, (compiles, end)
    return {
        "losses": [round(x, 4) for x in losses],
        "logs": logs,
        "compile_events": {e["fn"]: round(e["wall_s"], 2) for e in compiles},
        "recompiles": end[0]["recompiles"],
    }


def train_phase(out: str, seed: int):
    from perceiver_io_tpu.scripts.text import clm
    from perceiver_io_tpu.training import TrainState, clm_loss_fn, load_pretrained, make_optimizer
    from perceiver_io_tpu.training.loop import make_train_step
    from perceiver_io_tpu.training.prefix_dropout import sample_prefix_keep_idx

    with phase("train") as found:
        state, _ = clm.main(clm_argv(out, "train", seed, CLI_STEPS, log_interval=2))
        run_dir = os.path.join(out, "train")
        fit = check_fit_events(run_dir)
        last = fit["logs"][-1]
        # the trainer's own accounting of the last (compile-free) window
        assert last.get("mfu") is not None and last["mfu"] > 0, last
        found.update(
            steps=int(state.step), losses=fit["losses"], recompiles=fit["recompiles"],
            compile_events=fit["compile_events"],
            trainer_step_ms=round(1e3 / last["steps_per_sec"], 2),
            trainer_mfu=round(last["mfu"], 4), trainer_tokens_per_sec=round(last["tokens_per_sec"], 1),
        )

        # the checkpoint the fit wrote restores to the parameters it returned
        restored, _ = load_pretrained(os.path.join(run_dir, "checkpoints"), template_params=state.params)
        same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), restored, state.params)
        assert all(jax.tree.leaves(same)), "restored checkpoint differs from the trained parameters"
        found["checkpoint_restored"] = True
        params = state.params
        del state, restored

    # the train cell's step: batch 32 in 8 chunks of 4 inside one program
    # (host-sampled keep indices, bf16 moments). The CLI has no microbatch.
    with phase("train_bench_step") as found:
        model = flagship_model()
        rng = np.random.default_rng(seed)
        t = rng.integers(0, model.config.vocab_size, size=(BENCH_BATCH, SEQ_LEN + 1))
        batch = {
            "labels": jnp.asarray(t[:, 1:]),
            "input_ids": jnp.asarray(t[:, :-1]),
            "pad_mask": None,
            "prefix_keep_idx": jnp.asarray(
                sample_prefix_keep_idx(rng, BENCH_BATCH, SEQ_LEN - LATENTS, model.config.cross_attention_dropout)
            ),
        }
        tx = make_optimizer(1e-3, gradient_clip=1.0, moment_dtype="bfloat16")
        state = TrainState.create(model.apply, params, tx, jax.random.PRNGKey(seed + 1))
        step = make_train_step(clm_loss_fn(model.apply, max_latents=LATENTS), microbatch=BENCH_MICROBATCH)
        compiled = step.lower(state, batch).compile()  # the one compile of this step
        n_kernels = compiled.as_text().count("tpu_custom_call")
        assert n_kernels > 0, "the train step holds no Mosaic kernel: flash fell back to einsum"
        losses = []

        def call():
            nonlocal state
            state, metrics = compiled(state, batch)
            return metrics["loss"]

        for _ in range(3):
            losses.append(float(call()))
        assert all(np.isfinite(losses)), losses
        timing = time_both_ways(call, float)
        found.update(
            batch=BENCH_BATCH, microbatch=BENCH_MICROBATCH, losses=[round(x, 4) for x in losses],
            tpu_custom_calls=n_kernels, **timing,
        )
    return model, state.params  # the step donated the parameters it was given


def uncached_logits_fn(model):
    """The plain reference: the full uncached forward over a window, through
    the einsum attention path (no Pallas kernel), last-position logits."""
    from perceiver_io_tpu.ops.flash_attention import default_flash

    @jax.jit
    def fn(params, window):
        with default_flash(False):
            out = model.apply(params, window, prefix_len=window.shape[1] - LATENTS)
        return out.logits[:, -1].astype(jnp.float32)

    return fn


def tie_gap(logits: np.ndarray, token: int) -> float:
    """How far below the maximum the chosen token's logit sits, in units of
    the largest logit's magnitude (at least 1)."""
    return float((logits.max() - logits[token]) / max(1.0, np.abs(logits).max()))


def agree_or_tied(reference_logits, params, prompt, want, got) -> dict:
    """Two greedy streams for one prompt: identical, or at their first
    difference both tokens are within ``LOGIT_TIE_TOL`` of the uncached
    reference's maximum (after a tie the streams condition on different
    tokens and are no longer comparable)."""
    want, got = list(map(int, want)), list(map(int, got))
    assert len(want) == len(got), (len(want), len(got))
    if want == got:
        return {"exact": True}
    at = next(i for i, (a, b) in enumerate(zip(want, got)) if a != b)
    seq = np.concatenate([np.asarray(prompt).reshape(-1), np.asarray(want[:at], np.int64)])
    logits = np.asarray(reference_logits(params, jnp.asarray(seq[None, -SEQ_LEN:], jnp.int32)))[0]
    gaps = (tie_gap(logits, want[at]), tie_gap(logits, got[at]))
    assert max(gaps) <= LOGIT_TIE_TOL, (
        f"streams differ at token {at} ({want[at]} vs {got[at]}) and it is no tie: "
        f"logit gaps to the maximum {gaps}"
    )
    return {"exact": False, "tied_at": at, "gaps": [round(g, 5) for g in gaps]}


def decode_phase(model, params, seed: int):
    from perceiver_io_tpu.generation import GenerationConfig, make_generate_fn

    rng = np.random.default_rng(seed + 2)
    vocab = model.config.vocab_size
    reference_logits = uncached_logits_fn(model)
    config = GenerationConfig(max_new_tokens=NEW_TOKENS)
    with phase("decode_b1_bf16") as found:
        prompt = jnp.asarray(rng.integers(0, vocab, size=(1, SEQ_LEN)), jnp.int32)
        generate = make_generate_fn(model, num_latents=LATENTS, config=config, cache_dtype=jnp.bfloat16)
        compiled = generate.lower(params, prompt).compile()
        assert "tpu_custom_call" in compiled.as_text(), "the decode program holds no Mosaic kernel"
        out = np.asarray(compiled(params, prompt))
        assert out.shape == (1, SEQ_LEN + NEW_TOKENS) and (out[:, :SEQ_LEN] == np.asarray(prompt)).all()
        tokens = out[0, SEQ_LEN:]
        # the first tokens against the uncached forward over the growing
        # sequence: each is the reference argmax or tied with it
        seq, gaps = np.asarray(prompt)[0], []
        for tok in tokens[:CHECKED_TOKENS]:
            logits = np.asarray(reference_logits(params, jnp.asarray(seq[None, -SEQ_LEN:])))[0]
            assert np.isfinite(logits).all()
            gaps.append(tie_gap(logits, int(tok)))
            seq = np.concatenate([seq, [tok]])
        assert max(gaps) <= LOGIT_TIE_TOL, f"cached decode left the uncached forward: logit gaps {gaps}"
        timing = time_both_ways(lambda: compiled(params, prompt), lambda o: int(o[0, -1]))
        found.update(
            prompt_len=SEQ_LEN, new_tokens=NEW_TOKENS, checked_tokens=CHECKED_TOKENS,
            argmax_matches=sum(g == 0.0 for g in gaps), logit_gaps=[round(g, 5) for g in gaps],
            tokens_per_s=round(NEW_TOKENS / (timing["block_until_ready_ms"] / 1e3), 1), **timing,
        )
    with phase("decode_b8_int8") as found:
        prompts = jnp.asarray(rng.integers(0, vocab, size=(DECODE_BATCH, SEQ_LEN)), jnp.int32)
        generate = make_generate_fn(
            model, num_latents=LATENTS, config=config, cache_dtype=jnp.int8, weight_dtype=jnp.int8
        )
        out = np.asarray(generate(params, prompts))
        new = out[:, SEQ_LEN:]
        assert new.shape == (DECODE_BATCH, NEW_TOKENS) and ((new >= 0) & (new < vocab)).all()
        t0 = time.perf_counter()
        jax.block_until_ready(generate(params, prompts))
        second = time.perf_counter() - t0
        found.update(
            batch=DECODE_BATCH, new_tokens=NEW_TOKENS, second_call_s=round(second, 3),
            tokens_per_s=round(DECODE_BATCH * NEW_TOKENS / second, 1),
        )
    return reference_logits


def serve_requests(vocab: int, seed: int, n_short: int, n_long: int, n_shared: int, start: int = 0) -> list:
    """Requests over the two buckets; ``n_shared`` of the long ones open with
    the same ``SHARED_PREFIX`` tokens. Sharers sit together so that one is
    still resident when the next joins."""
    from perceiver_io_tpu.obs.loadgen import RequestSpec

    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, size=SHARED_PREFIX)
    specs = []
    kinds = ["short"] * n_short + ["long"] * (n_long - n_shared)
    rng.shuffle(kinds)
    kinds[len(kinds) // 2 : len(kinds) // 2] = ["shared"] * n_shared
    for i, kind in enumerate(kinds):
        prompt_len, budget = SHORT if kind == "short" else LONG
        ids = rng.integers(0, vocab, size=prompt_len)
        if kind == "shared":
            ids[:SHARED_PREFIX] = shared
        specs.append(RequestSpec(
            index=start + i, prompt_len=prompt_len, max_new_tokens=budget,
            input_ids=ids[None].astype(np.int32), rng_seed=seed + i,
        ))
    return specs


def make_engine(model, params, run_dir: str):
    from perceiver_io_tpu.generation import GenerationConfig
    from perceiver_io_tpu.obs.events import EventLog
    from perceiver_io_tpu.serving.engine import EngineConfig, EngineFrontEnd

    os.makedirs(run_dir, exist_ok=True)
    return EngineFrontEnd(
        model, params, num_latents=LATENTS, base_config=GenerationConfig(),
        engine_config=EngineConfig(
            slots=SLOTS, page_size=PAGE_SIZE, max_ca_tokens=SEQ_LEN, max_sa_tokens=LATENTS + SHORT[1],
        ),
        events=EventLog(run_dir),
    )


def engine_compile_events(fe) -> int:
    """Programs the engine has built so far, from its own event stream."""
    return sum(e["event"] == "compile" for e in read_events(fe.events.log_dir))


def check_served(fe, records, specs) -> None:
    bad = [(r.index, r.outcome, r.shed_reason, r.error) for r in records if r.outcome != "ok"]
    assert not bad and len(records) == len(specs), f"requests did not complete: {bad}"
    for spec in specs:
        assert len(fe.served_tokens[spec.index]) == spec.max_new_tokens, spec.index
    books = fe.books()
    assert books["balanced"] and books["ok"] == books["submitted"], books
    assert fe.audit() == [] and fe.sharing_audit() == [], (fe.audit(), fe.sharing_audit())
    health = fe.health()
    assert health["status"] == "ok" and health["books_balanced"], health


def p50(values) -> float:
    return round(statistics.median(values), 5)


def serve_phase(model, params, reference_logits, out: str, seed: int):
    from perceiver_io_tpu.generation import GenerationConfig, make_generate_fn
    from perceiver_io_tpu.ops.flash_attention import fast_kernels

    vocab = model.config.vocab_size
    warm = serve_requests(vocab, seed + 3, n_short=4, n_long=8, n_shared=4)
    late = serve_requests(vocab, seed + 4, n_short=3, n_long=3, n_shared=0, start=len(warm))

    with phase("serve") as found:
        fe = make_engine(model, params, os.path.join(out, "serve"))
        records = fe.run_closed(warm, concurrency=SLOTS)
        check_served(fe, records, warm)
        programs_warm, engine_programs_warm = programs()[0], engine_compile_events(fe)
        # six more at a low fixed rate: nothing left to compile
        records_late = fe.run_open(late, offsets=[0.5 * i for i in range(len(late))])
        check_served(fe, records + records_late, warm + late)
        assert (programs()[0], engine_compile_events(fe)) == (programs_warm, engine_programs_warm), (
            f"compiled after warm-up: programs {programs_warm} -> {programs()[0]}, "
            f"engine {engine_programs_warm} -> {engine_compile_events(fe)}"
        )
        prefix_hits = fe.registry.counter("serve_prefix_hits_total").value
        assert prefix_hits >= 1, "no request shared the resident prefix"
        steady = [r for r in records + records_late if not r.compiled]
        found.update(
            requests=len(warm) + len(late), books=fe.books(),
            engine_programs=engine_programs_warm, programs_before_open_loop=programs_warm,
            programs_after_open_loop=programs()[0],
            ttft_p50_s=p50([r.ttft_s for r in steady]),
            inter_token_p50_s=p50([r.decode_s / (r.tokens_out - 1) for r in steady]),
            engine_steps=fe._engine_steps, batch_fill=round(fe.mean_batch_fill, 3),
            pages_used_peak=int(fe.registry.gauge("engine_kv_pages_used").peak),
            prefix_hits=int(prefix_hits),
            prefix_pages_shared=int(fe.registry.counter("serve_prefix_pages_shared").value),
        )

    with phase("serve_vs_generate") as found:
        # one request's greedy stream against make_generate_fn (f32 cache,
        # as the engine's pools are) for the same prompt
        spec = next(s for s in warm if s.prompt_len == SHORT[0])
        generate = make_generate_fn(
            model, num_latents=LATENTS, config=GenerationConfig(max_new_tokens=spec.max_new_tokens)
        )
        want = np.asarray(generate(params, jnp.asarray(spec.input_ids)))[0, spec.prompt_len:]
        found.update(
            request=spec.index,
            **agree_or_tied(reference_logits, params, spec.input_ids, want, fe.served_tokens[spec.index]),
        )

    with phase("serve_paged_kernel") as found, fast_kernels({"paged"}):
        # the same requests through the page-walk kernel: its first run on
        # hardware. A fresh engine: kernel features are read at trace time.
        fe_paged = make_engine(model, params, os.path.join(out, "serve_paged"))
        records = fe_paged.run_closed(warm, concurrency=SLOTS)
        check_served(fe_paged, records, warm)
        # the engine's own step (its jit sits under the recompile tracker)
        step_text = fe_paged._step_fn.__wrapped__.lower(fe_paged._decode_params, fe_paged._state).as_text()
        assert "tpu_custom_call" in step_text, "the paged decode step holds no Mosaic kernel"
        verdicts = [
            agree_or_tied(
                reference_logits, params, s.input_ids, fe.served_tokens[s.index], fe_paged.served_tokens[s.index]
            )
            for s in warm
        ]
        found.update(
            requests=len(warm), exact=sum(v["exact"] for v in verdicts),
            tied=[v for v in verdicts if not v["exact"]],
            inter_token_p50_s=p50([r.decode_s / (r.tokens_out - 1) for r in records if not r.compiled]),
        )


# ----------------------------------------------------------- four chips only


def mesh_coords(mesh) -> dict:
    """Device coordinates along each mesh axis of size > 1, checked to step
    between ICI neighbours (the 2x2 has no wrap-around links of its own)."""
    from perceiver_io_tpu.parallel.mesh import MESH_AXES

    out = {}
    devices = mesh.devices
    for axis, name in enumerate(MESH_AXES):
        if devices.shape[axis] == 1:
            continue
        lines = np.moveaxis(devices, axis, -1).reshape(-1, devices.shape[axis])
        out[name] = [[list(d.coords) for d in line] for line in lines]
        for line in lines:
            ring = list(line) + ([line[0]] if len(line) > 2 else [])
            for a, b in zip(ring, ring[1:]):
                hops = sum(abs(x - y) for x, y in zip(a.coords, b.coords))
                assert hops == 1, f"mesh axis {name!r} steps {a.coords} -> {b.coords}: not ICI neighbours"
    return out


def peak_bytes_in_use(devices) -> list:
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


def sharded_fit(out: str, seed: int, name: str, mesh):
    """Three steps of the CLI fit under ``mesh`` (None: one device), and what
    the sharded state and step look like."""
    from perceiver_io_tpu.analysis.graph import collective_stats
    from perceiver_io_tpu.parallel.mesh import AXIS_FSDP, shard_batch
    from perceiver_io_tpu.scripts import cli
    from perceiver_io_tpu.scripts.text import clm
    from perceiver_io_tpu.training import clm_loss_fn
    from perceiver_io_tpu.training.loop import make_train_step

    argv = clm_argv(out, name, seed, steps=3, log_interval=1)
    with phase(f"train_{name}") as found:
        # the CLI's strategies name no data x fsdp mesh, so every run takes
        # its mesh from here; all else is the CLI's own path
        with mock.patch.object(cli, "make_mesh_for", lambda _: mesh):
            state, _ = clm.main(argv)
        fit = check_fit_events(os.path.join(out, name))
        found.update(losses=fit["losses"], recompiles=fit["recompiles"])
        if mesh is None:
            return fit["losses"]
        found["mesh"] = {k: int(v) for k, v in mesh.shape.items() if v > 1}
        found["coords"] = mesh_coords(mesh)
        fsdp = mesh.shape[AXIS_FSDP]
        n_sharded = 0
        for path, leaf in jax.tree_util.tree_leaves_with_path(state.params):
            if AXIS_FSDP not in jax.tree.leaves(tuple(leaf.sharding.spec)):
                continue
            n_sharded += 1
            shards = leaf.addressable_shards
            assert len({s.device for s in shards}) == mesh.size, jax.tree_util.keystr(path)
            assert len({str(s.index) for s in shards}) == fsdp, jax.tree_util.keystr(path)
        assert n_sharded > 0, "no parameter is sharded over fsdp"
        peaks = peak_bytes_in_use(mesh.devices.flatten())
        # device 0 also held the unsharded init; the others must hold a
        # comparable share, not a token one
        assert min(peaks) > 0.25 * peaks[0], f"memory concentrated on device 0: {peaks}"
        model = flagship_model()
        batch = shard_batch(
            {
                "labels": np.zeros((CLI_BATCH, SEQ_LEN), np.int32),
                "input_ids": np.zeros((CLI_BATCH, SEQ_LEN), np.int32),
                "pad_mask": None,
                "prefix_keep_idx": np.tile(np.arange((SEQ_LEN - LATENTS) // 2, dtype=np.int32), (CLI_BATCH, 1)),
            },
            mesh,
        )
        # the step as the Trainer builds it for this mesh
        step = make_train_step(clm_loss_fn(model.apply, max_latents=LATENTS), mesh=mesh)
        text = step.lower(state, batch).compile().as_text()
        assert "tpu_custom_call" in text, "the sharded train step holds no Mosaic kernel"
        stats = collective_stats(text)
        assert "all-gather" in stats and ("reduce-scatter" in stats or "all-reduce" in stats), stats
        found.update(
            fsdp_sharded_leaves=n_sharded, peak_bytes_in_use=peaks,
            collectives={k: v["count"] for k, v in stats.items()},
        )
    return fit["losses"]


def four_chips(out: str, seed: int):
    from perceiver_io_tpu.parallel.mesh import make_mesh

    # sharded runs first: the peak-memory reading is per process, and the
    # single-device run would otherwise be device 0's peak
    sharded = {
        "fsdp4": sharded_fit(out, seed, "fsdp4", make_mesh(data=1, fsdp=4)),
        "data2_fsdp2": sharded_fit(out, seed, "data2_fsdp2", make_mesh(data=2, fsdp=2)),
    }
    single = sharded_fit(out, seed, "single", None)
    with phase("sharded_vs_single") as found:
        found["single"] = single
        for name, losses in sharded.items():
            diffs = [abs(a - b) for a, b in zip(losses, single)]
            assert len(losses) == len(single) == 3 and max(diffs) <= LOSS_TOL, (name, losses, single)
            found[name] = {"losses": losses, "max_abs_diff": round(max(diffs), 5)}


# ----------------------------------------------------------------------- main


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--out", default="chip_smoke_out", help="logs, events, checkpoints (git-ignored)")
    p.add_argument("--seed", type=int, default=0, help="weights and data")
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the sharded train step and the single-device run it is compared with")
    args = p.parse_args(argv)
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)

    cache_dir = enable_compile_cache()
    device = device_phase(args.chips)
    print(json.dumps({"phase": "cache", "dir": cache_dir,
                      "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))}), flush=True)
    if args.chips == 4:
        four_chips(out, args.seed)
    else:
        kernels_phase()
        model, params = train_phase(out, args.seed)
        reference_logits = decode_phase(model, params, args.seed)
        serve_phase(model, params, reference_logits, out, args.seed)
    n, seconds, hits, misses = programs()
    print(json.dumps({"phase": "total", "programs": n, "cache_hits": hits, "cache_misses": misses,
                      "compile_s": round(seconds, 1)}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind, "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
