"""Benchmark: Perceiver AR causal-LM training throughput at 16k context on
one TPU chip (the BASELINE.json north-star workload).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tokens/sec/chip", "vs_baseline": N}

``vs_baseline`` compares measured throughput against an analytic single-A100
estimate for the same model/step (bf16 312 TFLOPS at 40% MFU — see
ComputeEstimator parity, reference: examples/scaling/clm/scaling/flops.py).
Values > 1.0 mean faster than the A100 estimate.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

# the analytic Perceiver AR step-FLOPs model (reference: scaling/flops.py:7-88)
# lives in utils/flops.py so the trainer's MFU telemetry shares it; re-exported
# here for tools/perf_probe.py and historical callers
from perceiver_io_tpu.utils.flops import train_step_flops  # noqa: F401
from perceiver_io_tpu.utils.profiling import StepTimer, percentile

# --- analytic-baseline assumptions (documented in BASELINE.md) -------------
# The reference publishes no throughput numbers, so vs_baseline compares
# against an ANALYTIC single-A100 estimate. Compute-bound modes assume the
# eager-torch reference sustains MFU_BAR on an A100's bf16 peak — a generous
# bar (the reference materializes full f32 score tensors, modules.py:151-163,
# whose HBM traffic at 16k context costs about as much time as the attention
# matmuls themselves); MFU_LOW bounds the plausible eager MFU from below and
# yields the optimistic end of the reported vs_baseline_range. Decode is
# bandwidth-bound on both chips: the A100 gets A100_BW_FRAC of its peak
# bandwidth, and the reported ceiling_fraction situates the measurement
# against THIS chip's physical bandwidth cap.
A100_BF16_PEAK = 312e12
MFU_BAR = 0.40  # the bar every round's headline vs_baseline used
MFU_LOW = 0.20  # defended lower bound for eager materialized-score attention
A100_PEAK_BW = 1.555e12  # A100-40GB HBM2e
A100_BW_FRAC = 0.60
V5E_PEAK_BW = 819e9  # v5e HBM


def _vs_baseline_fields(flops: float, step_time: float) -> dict:
    """Headline vs_baseline (A100 @ MFU_BAR) plus the assumption-range pair."""
    conservative = (flops / (A100_BF16_PEAK * MFU_BAR)) / step_time
    optimistic = (flops / (A100_BF16_PEAK * MFU_LOW)) / step_time
    return {
        "vs_baseline": round(conservative, 3),
        # [A100 @ 40% MFU, A100 @ 20% MFU] — the denominator is an analytic
        # assumption, not a measurement; see BASELINE.md "Baseline assumptions"
        "vs_baseline_range": [round(conservative, 3), round(optimistic, 3)],
    }


def scan_step_time(step, state, batch, steps: int, timer: "StepTimer" = None) -> float:
    """Sustained per-step time of a train step: the whole k-step chain runs
    inside ONE jitted ``lax.scan`` (single dispatch, so per-call host latency
    stays out of the step) and the step time is the
    ``robust_slope`` between two chain lengths, so fixed costs cancel.

    ``timer``: optional ``StepTimer`` fed per-call wall times of the
    already-compiled 2-step chain after the slope measurement — callers
    divide by :data:`TIMER_CHAIN` for an approximate per-step distribution
    (dispatch overhead included; the slope stays the headline number)."""

    @functools.partial(jax.jit, static_argnums=2)
    def run(state, batch, k):
        def body(s, _):
            s, metrics = step(s, batch)
            return s, metrics["loss"]

        _, losses = jax.lax.scan(body, state, None, length=k)
        return losses[-1]

    slope = robust_slope(lambda k: float(run(state, batch, k)), TIMER_CHAIN, TIMER_CHAIN + steps)
    if timer is not None:
        timer.start()
        for _ in range(TIMER_REPS):
            float(run(state, batch, TIMER_CHAIN))
            timer.tick()
    return slope


# chain length / repetitions for the supplementary StepTimer percentile
# summary (compiled programs only — the short chain robust_slope already built)
TIMER_CHAIN = 2
TIMER_REPS = 7  # warmup=1 discard leaves 6 samples


# pass/fail/skipped status of this invocation's kernel_smoke gate, recorded
# in every emitted result so a --skip-smoke run is visible in committed
# artifacts (ADVICE r5); None until main() resolves it (unit tests calling
# telemetry_fields directly get no kernel_smoke key)
_SMOKE_STATUS = None

# the graphlint static-analysis verdict on the flagship train/decode graphs
# (analysis/flagship.py, micro geometry — structure-only, seconds), same
# record-in-every-artifact contract as kernel_smoke; None until main()
# resolves it (or forever, for unit callers of telemetry_fields)
_GRAPHLINT_STATUS = None

# the graphcheck contract verdict (analysis/fingerprint.py: live flagship
# train+decode fingerprints diffed against the committed contracts/), same
# record-in-every-artifact contract; the hard gate is `tasks.py perf`
_GRAPHCHECK_STATUS = None

# the measured cost of always-on training probes (obs/probes.py): probed vs
# unprobed step wall time on THIS invocation's geometry, resolved by train
# mode (a recorded number, not a vibe — docs/observability.md#probes)
_PROBE_OVERHEAD = None


def telemetry_fields(flops, step_time, step_times_s=None, times_key: str = "step_ms") -> dict:
    """The ``telemetry`` block every bench result carries: device kind, the
    active trace-time kernel feature set (the A/B lever — so a committed
    result self-describes which kernels produced it), MFU against the
    obs.mfu per-device peak-FLOPs table (None off the table), and a
    p50/p90/p99 summary of individual wall times when provided
    (``step_times_s`` already normalized to per-step/per-token seconds)."""
    from perceiver_io_tpu.obs.mfu import device_peak_flops
    from perceiver_io_tpu.ops.flash_attention import fast_features

    t = {
        "device_kind": jax.devices()[0].device_kind,
        "kernel_features": sorted(fast_features()),
    }
    if _SMOKE_STATUS is not None:
        t["kernel_smoke"] = _SMOKE_STATUS
    if _GRAPHLINT_STATUS is not None:
        t["graphlint"] = _GRAPHLINT_STATUS
    if _GRAPHCHECK_STATUS is not None:
        t["graphcheck"] = _GRAPHCHECK_STATUS
    if _PROBE_OVERHEAD is not None:
        t["probe_overhead"] = _PROBE_OVERHEAD
    if flops is not None:
        peak = device_peak_flops()
        rate = flops / step_time
        t["model_flops_per_sec"] = round(rate, 3)
        t["peak_flops_per_device"] = peak
        t["mfu"] = round(rate / peak, 4) if peak else None
    if step_times_s:
        # same low-sample rule as StepTimer.summary: under LOW_N samples the
        # percentiles are exact order statistics and the block says low_n —
        # a 3-sample p99 printed as a tail estimate would be a fake number
        from perceiver_io_tpu.utils.profiling import LOW_N, exact_percentile

        low_n = len(step_times_s) < LOW_N
        pct = exact_percentile if low_n else percentile
        t[times_key] = {
            f"p{p}": round(pct(step_times_s, p) * 1e3, 3) for p in (50, 90, 99)
        }
        if low_n:
            t[times_key]["low_n"] = True
    return {"telemetry": t}


def robust_slope(
    run, n_short: int, n_long: int, estimates: int = 3, reps: int = 4, pair_sink=None
) -> float:
    """Per-iteration time as the slope between two chain lengths, hardened
    against host-side jitter: short/long timings are interleaved (so clock
    drift hits both), min-reduced per estimate, and the **median** of several
    independent slope estimates wins. Median, not min: a stall landing on an
    estimate's short-chain reps inflates t_short and *deflates* that
    estimate's slope, so taking the min would systematically select the most
    corrupted estimate (and a negative slope would report garbage
    throughput). Non-positive estimates are dropped outright. A
    single-estimate version of this measurement has been observed 20x off
    during a multi-second host stall.

    API asymmetry with :func:`interleaved_slopes` (intentional): this
    single-run form RAISES when every estimate is non-positive, while the
    multi-variant form returns ``None`` for the affected variant (one bad
    variant must not void the others' measurements); callers of the
    multi-variant form must handle ``None``."""
    run(n_short)  # compile
    run(n_long)
    slopes = []
    for _ in range(estimates):
        t_short = t_long = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            run(n_short)
            dt_short = time.perf_counter() - t0
            t_short = min(t_short, dt_short)
            t0 = time.perf_counter()
            run(n_long)
            dt_long = time.perf_counter() - t0
            t_long = min(t_long, dt_long)
            if pair_sink is not None and dt_long > dt_short:
                # per-rep paired per-iteration sample (fixed costs cancel);
                # telemetry percentiles come from these — no extra runs. A
                # non-positive diff is a stall-corrupted rep: DROP it (as the
                # slope estimates do), a clamped 0.0 would drag p50 toward an
                # impossible zero latency
                pair_sink.append((dt_long - dt_short) / (n_long - n_short))
        s = (t_long - t_short) / (n_long - n_short)
        if s > 0:
            slopes.append(s)
    if not slopes:
        raise RuntimeError(
            "every slope estimate was non-positive — the measurement is "
            "unusable (sustained host stall?); rerun the benchmark"
        )
    slopes.sort()
    n = len(slopes)
    return (slopes[(n - 1) // 2] + slopes[n // 2]) / 2


def interleaved_slopes(runs, n_short: int, n_long: int, estimates: int = 3, reps: int = 4):
    """Multi-variant ``robust_slope``: per-iteration time for EACH named run
    in ``runs`` ({name: fn(chain_len)}), with the variants visited
    round-robin inside every rep so chip clock drift hits all of them
    equally (cross-process A/B comparisons drift 1.5-1.8x with the clock
    state — docs/performance.md). Same hardening as ``robust_slope``:
    min-reduced reps, median of ``estimates`` independent slopes,
    non-positive estimates dropped. Assumes every run was already called
    once at both chain lengths (compiled — trace-time feature flags must be
    active at COMPILE time, so the tools own their compile loops). Returns
    {name: median_seconds_per_iteration or None if all estimates were
    non-positive (host stall — rerun)}. Shared by the tools/*_ab.py
    same-process harnesses."""
    slopes = {v: [] for v in runs}
    for _ in range(estimates):
        best = {v: [float("inf"), float("inf")] for v in runs}
        for _ in range(reps):
            for v, run in runs.items():
                t0 = time.perf_counter()
                run(n_short)
                best[v][0] = min(best[v][0], time.perf_counter() - t0)
                t0 = time.perf_counter()
                run(n_long)
                best[v][1] = min(best[v][1], time.perf_counter() - t0)
        for v in runs:
            s = (best[v][1] - best[v][0]) / (n_long - n_short)
            if s > 0:
                slopes[v].append(s)
    out = {}
    for v, ss in slopes.items():
        ss = sorted(ss)
        out[v] = None if not ss else (ss[(len(ss) - 1) // 2] + ss[len(ss) // 2]) / 2
    return out


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




def image_bench(args):
    """Perceiver IO image-classifier training throughput (img/sec/chip) on
    synthetic ImageNet-shaped batches — the BASELINE.json metric's second
    workload (paper-style Fourier encoding config, reference:
    vision/image_classifier/backend.py + deepmind/vision-perceiver-fourier
    geometry scaled to fit one chip)."""
    from perceiver_io_tpu.models.vision.image_classifier import (
        ImageClassifier,
        ImageClassifierConfig,
        ImageEncoderConfig,
    )
    from perceiver_io_tpu.core.config import ClassificationDecoderConfig
    from perceiver_io_tpu.training import TrainState, classification_loss_fn, make_optimizer
    from perceiver_io_tpu.training.loop import make_train_step

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    config = ImageClassifierConfig(
        encoder=ImageEncoderConfig(
            image_shape=(224, 224, 3),
            num_frequency_bands=64,
            num_cross_attention_heads=1,
            num_self_attention_heads=8,
            num_self_attention_layers_per_block=6,
            num_self_attention_blocks=8,
            first_self_attention_block_shared=True,
        ),
        decoder=ClassificationDecoderConfig(
            num_classes=1000, num_output_query_channels=1024, num_cross_attention_heads=1
        ),
        num_latents=512,
        num_latent_channels=1024,
        activation_checkpointing=args.remat,
    )
    model = ImageClassifier(config, dtype=dtype)
    b = args.batch_size
    image_shape = config.encoder.image_shape
    n_classes = config.decoder.num_classes
    rng = np.random.default_rng(0)
    batch = {
        "image": jnp.asarray(rng.normal(size=(b,) + image_shape), jnp.float32),
        "label": jnp.asarray(rng.integers(0, n_classes, size=(b,))),
    }
    params = model.init(jax.random.PRNGKey(0), batch["image"])
    n_params = sum(p.size for p in jax.tree.leaves(params))
    tx = make_optimizer(1e-3, gradient_clip=1.0)
    state = TrainState.create(model.apply, params, tx, jax.random.PRNGKey(1))
    step = make_train_step(classification_loss_fn(model.apply), jit=False)

    timer = StepTimer(warmup=1)
    step_time = scan_step_time(step, state, batch, args.steps, timer=timer)

    # analytic step FLOPs (same style as train_step_flops): encoder CA over
    # the pixel array + the weight-shared SA stack; fwd+bwd ~ 3x fwd matmuls
    enc = config.encoder
    lat, lc = config.num_latents, config.num_latent_channels
    m = int(np.prod(image_shape[:-1]))
    in_ch = image_shape[-1] + len(image_shape[:-1]) * (2 * enc.num_frequency_bands + 1)
    qk = in_ch  # qk channels default to the adapter width
    ca = (
        2 * lat * lc * qk  # q proj
        + 2 * m * in_ch * qk * 2  # k, v proj
        + 2 * 2 * lat * m * qk  # scores + values
        + 2 * lat * qk * lc  # out proj
        + 2 * lat * 2 * enc.cross_attention_widening_factor * lc * lc  # mlp
    )
    layers = enc.num_self_attention_layers_per_block * enc.num_self_attention_blocks
    sa = layers * (
        2 * lat * 4 * lc * lc
        + 2 * 2 * lat * lat * lc
        + 2 * lat * 2 * enc.self_attention_widening_factor * lc * lc
    )
    flops = 3.0 * (ca + sa) * b

    result = {
        "metric": f"perceiver-io img-clf train img/sec/chip "
        f"@{image_shape[0]}x{image_shape[1]} "
        f"({n_params/1e6:.1f}M params, {args.dtype}, batch {b})",
        "value": round(b / step_time, 2),
        "unit": "img/sec/chip",
        **_vs_baseline_fields(flops, step_time),
        **telemetry_fields(flops, step_time, [t / TIMER_CHAIN for t in timer.steps]),
    }
    print(json.dumps(result))
    return result


def decode_bench(args):
    """KV-cache decode throughput at full 16k context (the reference's decode
    hot loop, reference: core/huggingface.py:158-185): tokens generated per
    second with the sliding-window cache already full."""
    from perceiver_io_tpu.generation import GenerationConfig, make_generate_fn
    from perceiver_io_tpu.models.text import CausalLanguageModel

    config = flagship_config(args.seq_len, args.latents)
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    cache_dtype = jnp.int8 if args.cache_dtype == "int8" else dtype
    weight_dtype = jnp.int8 if getattr(args, "weight_dtype", "model") == "int8" else None
    model = CausalLanguageModel(config, dtype=dtype)

    b = args.batch_size
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, config.vocab_size, size=(b, args.seq_len)))
    params = model.init(
        jax.random.PRNGKey(0), prompt[:, : args.latents + 1], prefix_len=1
    )

    n_short, n_long = 8, 8 + args.steps * 4
    fns = {
        k: make_generate_fn(
            model, args.latents, GenerationConfig(max_new_tokens=k, do_sample=True, top_k=10),
            cache_dtype=cache_dtype, weight_dtype=weight_dtype,
        )
        for k in (n_short, n_long)
    }

    def run(k):
        return float(fns[k](params, prompt)[0, -1])

    # per-token distribution from the slope measurement's own PAIRED chains:
    # every generate call re-runs the compute-bound prompt pass, so
    # (t_long - t_short) / Δtokens cancels it — dividing one call by its
    # token count would fold prefill/k into every "token" and contradict the
    # slope headline, and re-running extra pairs would double bench time
    token_times = []
    per_token = robust_slope(run, n_short, n_long, pair_sink=token_times)

    # analytic A100 decode baseline: the decode hot loop is HBM-bandwidth
    # bound (reference loop: core/huggingface.py:158-185) — per-token traffic
    # is one full read of the bf16 weights plus the KV windows, at 60% of
    # A100-40GB peak bandwidth (1.555 TB/s; the train baseline's analog of
    # "peak x 40% MFU", but for a bandwidth-bound phase)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    dsize = jnp.dtype(dtype).itemsize
    # the BASELINE always moves the reference's full-precision cache (the
    # torch reference has no quantized KV storage); the CHIP moves whatever
    # the configured cache dtype actually stores (int8 adds 4 scale bytes
    # per slot: bf16 k_scale + v_scale)
    csize = jnp.dtype(cache_dtype).itemsize
    scale_bytes = 4 if cache_dtype == jnp.int8 else 0
    ca_window = config.max_seq_len * 2 * config.num_channels * dsize
    sa_windows = (
        config.num_self_attention_layers * config.max_latents * 2 * config.num_channels * dsize
    )
    ca_window_chip = config.max_seq_len * (2 * config.num_channels * csize + scale_bytes)
    sa_windows_chip = config.num_self_attention_layers * config.max_latents * (
        2 * config.num_channels * csize + scale_bytes
    )
    step_bytes = n_params * dsize + b * (ca_window + sa_windows)
    # chip-side weight bytes: int8 kernels store 1 byte + a f32 scale per
    # output channel; everything else (embeddings, norms, biases) stays at
    # model dtype. The BASELINE side always moves full-precision weights
    # (the torch reference has no quantized inference), so — like the int8
    # cache — int8 weights RAISE the bandwidth cap.
    if weight_dtype is not None:
        # account the bytes from the ACTUAL quantized tree (ADVICE r4: an
        # inline reimplementation of the selection rule would silently
        # diverge if quantize_weights ever changed), evaluated shape-only
        # via eval_shape — no device work
        from perceiver_io_tpu.ops.quant import QuantizedTensor, quantize_weights

        qtree = jax.eval_shape(quantize_weights, params)

        def leaf_bytes(x):
            if isinstance(x, QuantizedTensor):
                return x.q.size * x.q.dtype.itemsize + x.scale.size * x.scale.dtype.itemsize
            return x.size * dsize

        weight_bytes_chip = sum(
            leaf_bytes(x)
            for x in jax.tree.leaves(qtree, is_leaf=lambda x: isinstance(x, QuantizedTensor))
        )
    else:
        weight_bytes_chip = n_params * dsize
    chip_bytes = weight_bytes_chip + b * (ca_window_chip + sa_windows_chip)
    a100_step_time = step_bytes / (A100_PEAK_BW * A100_BW_FRAC)
    # THIS chip's physical floor: the bytes it actually moves at 100% of v5e
    # bandwidth. vs_baseline is capped at a100_step_time/v5e_floor even at
    # perfect bandwidth utilization, so the artifact carries both the cap
    # and how close the measurement is to the chip's own ceiling (VERDICT
    # r3: the cap lived in prose, not the bench). An int8 cache RAISES the
    # cap past 1.0: the chip moves half the bytes the baseline must.
    v5e_floor = chip_bytes / V5E_PEAK_BW

    result = {
        "metric": f"perceiver-ar-clm decode tokens/sec @{args.seq_len} ctx "
        f"(full sliding-window KV cache, {args.dtype}"
        + (", int8 cache" if cache_dtype == jnp.int8 else "")
        + (", int8 weights" if weight_dtype is not None else "")
        + f", batch {b})",
        "value": round(b / per_token, 1),
        "unit": "tokens/sec",
        # both sides are one decode step (b tokens)
        "vs_baseline": round(a100_step_time / per_token, 3),
        "vs_baseline_cap": round(a100_step_time / v5e_floor, 3),
        "ceiling_fraction": round(v5e_floor / per_token, 3),
        # decode is bandwidth-bound: no MFU, but the per-token latency
        # distribution (p50/p90/p99) rides along for serving comparisons
        **telemetry_fields(None, per_token, token_times, times_key="token_ms"),
    }
    print(json.dumps(result))
    return result


def spec_decode_bench(args):
    """Speculative self-drafting decode A/B (Specline, ISSUE 14): the
    sequential host-driven pair (``make_decode_fns``) vs the draft/verify
    pair (``make_speculative_decode_fns``) on the SAME prompt/seed, greedy
    — token-exactness is ASSERTED (bit-exact streams), then decode
    tokens/sec, drafter acceptance rate and tokens-per-verify-step are
    measured over the same host loop. Both sides pay the identical
    per-token host dispatch, so the ratio isolates the serial-step
    reduction; tokens_per_step is the hardware-independent headline — the
    serial-HBM-sweep multiple a TPU inherits at its own step time. This is
    the one decode multiple certifiable WITHOUT a TPU attached: the A/B is
    about serial-step count, not kernel speed (the committed round records
    the geometry/backend in the metric string)."""
    import time

    from perceiver_io_tpu.generation import (
        GenerationConfig,
        make_decode_fns,
        make_speculative_decode_fns,
    )
    from perceiver_io_tpu.models.text import CausalLanguageModel

    config = flagship_config(args.seq_len, args.latents)
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    # the KV cache stays f32 on BOTH sides: a bf16/int8 cache quantizes
    # logits coarsely enough to produce EXACT ties, and argmax breaks a tie
    # program-dependently (the single-token block-diagonal attend vs the
    # span verify attend are different-but-equivalent reductions) — a tie
    # flip is not a correctness failure, but it would break the bit-exact
    # assert this A/B exists to make. Acceptance rate and tokens-per-step
    # are what the artifact records; they are cache-dtype-insensitive.
    cache_dtype = jnp.float32
    weight_dtype = jnp.int8 if getattr(args, "weight_dtype", "model") == "int8" else None
    model = CausalLanguageModel(config, dtype=dtype)
    k, depth, n_new = args.spec_k, args.spec_depth, args.spec_tokens

    # no-slide geometry (the speculative contract): prompt + budget inside
    # the CA window, latents + budget inside the latent window
    if args.latents <= n_new or args.seq_len <= n_new + 1:
        raise SystemExit(
            f"spec mode needs --latents > --spec-tokens and --seq-len > "
            f"--spec-tokens + 1 (got latents {args.latents}, seq_len "
            f"{args.seq_len}, spec_tokens {n_new}) — the no-slide window "
            "must leave room for the prompt and the latent stream"
        )
    prompt_len = args.seq_len - n_new
    num_latents = args.latents - n_new
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, config.vocab_size, size=(1, prompt_len)))
    params = model.init(
        jax.random.PRNGKey(0), prompt[:, : num_latents + 1], prefix_len=1
    )
    cfg = GenerationConfig(max_new_tokens=n_new)
    kw = dict(cache_dtype=cache_dtype, weight_dtype=weight_dtype)

    prefill_seq, step_seq = make_decode_fns(model, num_latents, cfg, **kw)
    prefill_spec, step_spec = make_speculative_decode_fns(
        model, num_latents, cfg, k=k, draft_depth=depth, **kw
    )

    def run_sequential():
        tok, state = prefill_seq(params, prompt, None, jax.random.PRNGKey(11))
        out = [int(tok[0])]
        t0 = time.perf_counter()
        for _ in range(n_new - 1):
            state, tok = step_seq(state)
            out.append(int(tok[0]))
        return out, time.perf_counter() - t0, n_new - 1

    def run_speculative():
        tok, state = prefill_spec(params, prompt, None, jax.random.PRNGKey(11))
        out = [int(tok[0])]
        spans = accepted = 0
        t0 = time.perf_counter()
        while len(out) < n_new:
            state, toks, m = step_spec(state)
            m0 = int(m[0])
            spans += 1
            accepted += m0 - 1
            out.extend(int(t) for t in np.asarray(toks[0, :m0]))
        dt = time.perf_counter() - t0
        return out[:n_new], dt, spans, accepted

    run_sequential()  # warmup: compiles on both sides stay out of the timing
    run_speculative()
    seq_out, seq_dt, seq_steps = run_sequential()
    spec_out, spec_dt, spans, accepted = run_speculative()
    if spec_out != seq_out:
        div = next(
            (i for i, (a, b) in enumerate(zip(seq_out, spec_out)) if a != b),
            min(len(seq_out), len(spec_out)),
        )
        raise AssertionError(
            f"speculative greedy stream diverged from sequential at token "
            f"{div} (lens {len(seq_out)}/{len(spec_out)}): "
            f"seq[{div}:{div + 4}]={seq_out[div:div + 4]} "
            f"spec[{div}:{div + 4}]={spec_out[div:div + 4]} — the "
            "token-exactness contract is broken"
        )
    acceptance = accepted / max(spans * k, 1)
    tokens_per_step = (n_new - 1) / max(spans, 1)
    seq_tok_s = seq_steps / seq_dt
    spec_tok_s = (n_new - 1) / spec_dt

    result = {
        "metric": (
            f"perceiver-ar-clm speculative decode A/B @{args.seq_len} ctx "
            f"(k={k}, draft_depth={depth}, greedy, batch 1, {args.dtype}"
            + (", int8 weights" if weight_dtype is not None else "")
            + f", {jax.default_backend()} backend)"
        ),
        "value": round(spec_tok_s, 1),
        "unit": "tokens/sec",
        "sequential_tok_s": round(seq_tok_s, 1),
        "vs_sequential": round(spec_tok_s / seq_tok_s, 3),
        "acceptance_rate": round(acceptance, 3),
        "tokens_per_step": round(tokens_per_step, 3),
        "k": k,
        "draft_depth": depth,
        "n_tokens": n_new,
        "token_exact": True,
    }
    print(json.dumps(result))
    return result


def extra_bench(args):
    """Run the non-headline benches (decode b=1 and b=8 in bf16, decode b=8
    with the int8 KV cache, decode b=1 with int8 weights, decode b=8 with
    both int8 stores, the speculative decode A/B, image training)
    and write them to one JSON artifact (``--out BENCH_extra_r<k>.json``) so
    decode/image regressions are visible round-over-round — the headline
    train metric is what the driver's plain ``python bench.py`` records."""
    import copy

    def flush(results):
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
            print(f"wrote {args.out}", flush=True)

    results = {}
    for b in (1, 8):
        a = copy.copy(args)
        a.batch_size, a.mode = b, "decode"
        results[f"decode_b{b}"] = decode_bench(a)
        flush(results)  # incremental: a killed run still leaves an artifact
    # int8 KV-cache decode (per-token quantized storage): the baseline keeps
    # the reference's full-precision cache, so halving the chip's cache
    # bytes lifts the bandwidth cap past 1.0 — the headline decode number
    a = copy.copy(args)
    a.batch_size, a.mode, a.cache_dtype = 8, "decode", "int8"
    results["decode_b8_int8"] = decode_bench(a)
    flush(results)
    # int8 WEIGHTS (per-output-channel kernels, ops/quant.py): at batch 1
    # the decode step is weights-read-bound, so this is where the weight
    # diet pays; the "full" row stacks both int8 stores at batch 8
    a = copy.copy(args)
    a.batch_size, a.mode, a.weight_dtype = 1, "decode", "int8"
    results["decode_b1_int8w"] = decode_bench(a)
    flush(results)
    a = copy.copy(args)
    a.batch_size, a.mode, a.cache_dtype, a.weight_dtype = 8, "decode", "int8", "int8"
    results["decode_b8_int8_full"] = decode_bench(a)
    flush(results)
    # speculative decode A/B (Specline): k-token self-drafting vs the
    # sequential pair — the tokens_per_step key carries the ledger floor
    # (spec_tokens_per_step), so the geometry is PINNED to the committed
    # BENCH_extra_r6 configuration (512 ctx, k=4, depth-6 drafter, 64
    # tokens): the serial-step multiple is hardware-independent and the
    # floor compares rounds, so the refresh must not silently re-measure
    # it at whatever --seq-len/--spec-depth the extra run happens to use
    a = copy.copy(args)
    a.batch_size, a.mode = 1, "spec"
    a.seq_len, a.latents = 512, 128
    a.spec_k, a.spec_depth, a.spec_tokens = 4, 6, 64
    results["decode_spec"] = spec_decode_bench(a)
    flush(results)
    a = copy.copy(args)
    # batch 16 is the largest the 224x224 Fourier config fits on one chip
    a.batch_size, a.mode = 16, "img"
    results["image_b16"] = image_bench(a)
    flush(results)


def auto_microbatch(batch_size: int) -> int:
    """Default gradient-chunk count: chunks of 4 samples (the measured
    optimum) when 4 divides the batch, else the largest chunk size that
    does — the derived count always divides the batch, so the train path's
    divisibility fallback (which silently disables chunking, ~10% slower)
    can never trigger on a default geometry."""
    chunk = 4 if batch_size % 4 == 0 else (2 if batch_size % 2 == 0 else 1)
    return max(1, batch_size // chunk)


def kernel_smoke() -> None:
    """Mosaic-lowering regression gate (VERDICT r4 item 8), run as part of
    every bench invocation: the CPU test suite exercises the Pallas kernels
    in interpret mode only, so a real-TPU lowering regression could hide
    behind a cached bench artifact. Asserts, at micro shapes (seconds, not
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


def main():
    # main() only — at import it would take over the test suite's own cache
    # config (tests import bench for robust_slope)
    from perceiver_io_tpu.utils.compile_cache import enable_compile_cache
    from perceiver_io_tpu.utils.device import require_tpu

    require_tpu("bench.py")
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--seq-len", type=int, default=16384)
    p.add_argument("--latents", type=int, default=1024)
    # batch 32 in 8 chunks of 4 is the measured round-5 optimum (the compact
    # prefix-dropout step re-opened the geometry: per-sample fwd+bwd is
    # cheapest in chunks of 4 and the fixed ~1.2 ms optimizer+bookkeeping
    # tail amortizes over 32 samples — same-process sweep b4mb2 3.24M /
    # b8mb2 3.33M / b16mb4 3.38M / b24mb6 3.45M / b32mb8 3.48M / b64mb16
    # 3.49M tok/s; chunks of 8 REGRESS 15%, docs/performance.md round-5
    # table). The A100 analytic baseline scales with batch, so vs_baseline
    # stays batch-fair.
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--steps", type=int, default=50)
    # number of gradient chunks inside the step (batch/microbatch samples
    # each), one optimizer update — mathematically the full-batch step
    p.add_argument("--microbatch", type=int, default=None)
    # round-4 winners (same-process A/B, tools/step_ab.py — docs/performance.md):
    # host-sampled prefix-dropout keep indices (kills the in-graph top_k+sort,
    # -2.8% step) and bf16 Adam moment storage (halves optimizer HBM traffic,
    # -2.5%); together -5.1% (21.66 -> 20.56 ms at batch 4)
    p.add_argument("--dropout-sampling", choices=["host", "graph"], default="host")
    p.add_argument("--moment-dtype", choices=["float32", "bfloat16"], default="bfloat16")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--cache-dtype", choices=["model", "int8"], default="model",
                   help="decode KV-cache storage: model dtype or int8+per-token scales")
    p.add_argument("--weight-dtype", choices=["model", "int8"], default="model",
                   help="decode weight storage: model dtype or int8 kernels "
                        "+ per-output-channel scales (ops/quant.py)")
    p.add_argument("--remat", action="store_true", help="activation checkpointing (needed for large seq/batch)")
    p.add_argument("--mode", choices=["train", "decode", "spec", "img", "extra"], default="train")
    p.add_argument("--spec-k", type=int, default=4,
                   help="spec mode: draft tokens per verify span (Specline)")
    p.add_argument("--spec-depth", type=int, default=2,
                   help="spec mode: drafter depth (latent SA layers shared "
                        "with the flagship trunk)")
    p.add_argument("--spec-tokens", type=int, default=64,
                   help="spec mode: decode tokens measured per side of the A/B")
    p.add_argument("--skip-smoke", action="store_true",
                   help="skip the Mosaic kernel-lowering smoke (VERDICT r4 item 8; "
                        "runs by default in every mode)")
    p.add_argument("--skip-graphlint", action="store_true",
                   help="skip the static-analysis gate over the flagship "
                        "train/decode graphs (analysis/, tools/graphlint.py; "
                        "includes the dataflow rules — rng-key-reuse, "
                        "dead-compute, cross-program-consistency — armed by "
                        "the flagship policies; runs by default in every mode)")
    p.add_argument("--skip-graphcheck", action="store_true",
                   help="skip the compiled-graph contract diff against "
                        "contracts/ (analysis/fingerprint.py, "
                        "tools/graphcheck.py; runs by default in every mode)")
    p.add_argument("--kernel-features", default=None,
                   help="trace-time flash kernel feature set for A/B runs: 'all', "
                        "'none', or a comma list (e.g. 'paged') — see "
                        "ops/flash_attention.py ALL_FEATURES; recorded in the "
                        "result's telemetry block")
    p.add_argument("--mesh", default=None, metavar="data=N[,fsdp=M]",
                   help="train mode: shard the step over this data/fsdp mesh "
                        "(state via shard_train_state, batch via shard_batch) "
                        "and record telemetry.collectives (per-kind counts + "
                        "estimated bytes from the compiled HLO) in the artifact")
    p.add_argument("--overlap", choices=["on", "off"], default="off",
                   help="with --mesh: 'on' runs the explicit overlap-scheduled "
                        "shard_map step (parallel/overlap.py: chunk-interleaved "
                        "gradient reduce-scatter + FSDP all-gather prefetch); "
                        "default off (GSPMD) until the TPU A/B lands "
                        "(docs/performance.md round 7; tools/overlap_ab.py)")
    p.add_argument("--out", default=None, help="extra mode: JSON artifact path (e.g. BENCH_extra_r3.json)")
    p.add_argument("--skip-probe-overhead", action="store_true",
                   help="train mode: skip the probed-vs-unprobed step A/B "
                        "(obs/probes.py; telemetry.probe_overhead records the "
                        "cost of always-on training probes — runs by default, "
                        "one extra compile of the probed step variant)")
    args = p.parse_args()

    if args.kernel_features is not None:
        from perceiver_io_tpu.ops.flash_attention import set_fast_kernels

        mode = {"all": True, "none": False}.get(
            args.kernel_features,
            [f for f in args.kernel_features.split(",") if f],
        )
        set_fast_kernels(mode)

    if args.batch_size is None:
        args.batch_size = 32 if args.mode == "train" else 1
    if args.microbatch is None:
        args.microbatch = auto_microbatch(args.batch_size)

    global _SMOKE_STATUS
    if args.skip_smoke:
        _SMOKE_STATUS = "skipped"
    else:
        try:
            kernel_smoke()
            _SMOKE_STATUS = "passed"
        except Exception as e:
            # make the failure visible in a committed artifact when one is
            # being written, then fail loudly — the smoke is a gate. The row
            # keeps the successful artifacts' shape (telemetry.kernel_smoke)
            # so consumers read one schema across pass/skip/fail.
            if args.mode == "extra" and args.out:
                with open(args.out, "w") as f:
                    json.dump(
                        {"kernel_smoke_failure": {"telemetry": {
                            "kernel_smoke": "failed", "kernel_smoke_error": str(e)}}},
                        f, indent=1,
                    )
            raise

    global _GRAPHLINT_STATUS
    if args.skip_graphlint:
        _GRAPHLINT_STATUS = {"status": "skipped"}
    else:
        # a lint FINDING is a recorded verdict in the artifact (the
        # CI-facing hard gate is `tasks.py graphlint` / tools/graphlint.py
        # --fail-on error); an exception inside the lint ends the run. A
        # --mesh train run also lints the SHARDED micro step (the overlap
        # scheduling claim) as the train_sharded target.
        from perceiver_io_tpu.analysis.flagship import graphlint_telemetry

        _GRAPHLINT_STATUS = graphlint_telemetry(
            mesh_spec=args.mesh if args.mode == "train" else None
        )
        print(f"graphlint {_GRAPHLINT_STATUS['status']}", flush=True)

    global _GRAPHCHECK_STATUS
    if args.skip_graphcheck:
        _GRAPHCHECK_STATUS = {"status": "skipped"}
    else:
        # same contract as graphlint_telemetry: a contract regression (or
        # missing contracts/) is a recorded verdict in the artifact, an
        # exception ends the run; the hard gate is `tasks.py perf` /
        # tools/graphcheck.py
        from perceiver_io_tpu.analysis.fingerprint import graphcheck_telemetry

        _GRAPHCHECK_STATUS = graphcheck_telemetry()
        print(f"graphcheck {_GRAPHCHECK_STATUS['status']}", flush=True)

    if args.mode == "extra":
        return extra_bench(args)
    if args.mode == "decode":
        return decode_bench(args)
    if args.mode == "spec":
        return spec_decode_bench(args)
    if args.mode == "img":
        return image_bench(args)

    from perceiver_io_tpu.models.text import CausalLanguageModel
    from perceiver_io_tpu.training import TrainState, clm_loss_fn, make_optimizer
    from perceiver_io_tpu.training.loop import make_train_step

    config = flagship_config(args.seq_len, args.latents, remat=args.remat)
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    model = CausalLanguageModel(config, dtype=dtype)

    b, n = args.batch_size, args.seq_len
    rng = np.random.default_rng(0)
    t = rng.integers(0, config.vocab_size, size=(b, n + 1))
    # next-token contract: inputs/labels shifted by one (reference: c4.py:161-162).
    # No pad_mask: packed full windows have no padding, and its absence
    # statically selects the scatter-free position-embedding path.
    batch = {
        "labels": jnp.asarray(t[:, 1:]),
        "input_ids": jnp.asarray(t[:, :-1]),
        "pad_mask": None,
    }

    prefix_len = n - args.latents
    if args.dropout_sampling == "host":
        from perceiver_io_tpu.training.prefix_dropout import sample_prefix_keep_idx

        batch["prefix_keep_idx"] = jnp.asarray(
            sample_prefix_keep_idx(rng, b, prefix_len, config.cross_attention_dropout)
        )
    params = model.init(
        jax.random.PRNGKey(0), batch["input_ids"][:, : args.latents + 1], prefix_len=1
    )
    n_params = sum(p.size for p in jax.tree.leaves(params))

    tx = make_optimizer(
        1e-3,
        gradient_clip=1.0,
        moment_dtype=None if args.moment_dtype == "float32" else args.moment_dtype,
    )
    state = TrainState.create(model.apply, params, tx, jax.random.PRNGKey(1))
    if args.microbatch < 1:
        raise SystemExit("--microbatch must be >= 1")
    microbatch = args.microbatch if b % args.microbatch == 0 else 1
    if microbatch != args.microbatch:
        print(f"note: --microbatch {args.microbatch} does not divide batch {b}; using 1")

    mesh = None
    if args.mesh:
        from perceiver_io_tpu.parallel import shard_batch
        from perceiver_io_tpu.parallel.overlap import OverlapConfig, mesh_from_spec
        from perceiver_io_tpu.training.loop import shard_train_state

        try:
            mesh = mesh_from_spec(args.mesh)
        except ValueError as e:
            raise SystemExit(str(e)) from None
        state = shard_train_state(state, mesh)
        batch = shard_batch(batch, mesh)
        need = mesh.size
        # sharded steps chunk the PER-DEVICE batch (b / submesh), so the
        # microbatch fallback re-checks divisibility at that granularity
        per_device = b // need
        if microbatch > 1 and per_device % microbatch != 0:
            print(
                f"note: --microbatch {microbatch} does not divide the per-device "
                f"batch {per_device} on mesh {args.mesh}; using 1"
            )
            microbatch = 1
    overlap_cfg = None
    if args.overlap == "on":
        if mesh is None:
            raise SystemExit("--overlap on requires --mesh")
        overlap_cfg = OverlapConfig(mesh=mesh)
    step = make_train_step(
        clm_loss_fn(model.apply, max_latents=args.latents),
        jit=False,
        microbatch=microbatch,
        overlap=overlap_cfg,
    )

    timer = StepTimer(warmup=1)
    step_time = scan_step_time(step, state, batch, args.steps, timer=timer)
    tokens_per_sec = b * n / step_time

    global _PROBE_OVERHEAD
    if not args.skip_probe_overhead and overlap_cfg is None:
        # the cost of always-on training probes as a recorded number: the
        # SAME step compiled with the Probeline stats (obs/probes.py) timed
        # over a shorter chain, against the unprobed measurement above
        from perceiver_io_tpu.obs.probes import ProbeConfig

        probed_step = make_train_step(
            clm_loss_fn(model.apply, max_latents=args.latents),
            jit=False,
            microbatch=microbatch,
            probes=ProbeConfig(),
        )

        # scan_step_time's body keeps only metrics["loss"], which would let
        # XLA dead-code-eliminate every probe reduction and time the
        # unprobed graph; the probe outputs must stay live, as they are in
        # the trainer (where they are returned to the host)
        @functools.partial(jax.jit, static_argnums=2)
        def run_probed(state, batch, k):
            def body(s, _):
                s, metrics = probed_step(s, batch)
                return s, (metrics["loss"], metrics["probes"])

            _, (losses, stats) = jax.lax.scan(body, state, None, length=k)
            return losses[-1], jax.tree.map(lambda x: x[-1], stats)

        def probed_call(k):
            loss, stats = run_probed(state, batch, k)
            jax.block_until_ready(stats)
            return float(loss)

        probed_time = robust_slope(
            probed_call, TIMER_CHAIN, TIMER_CHAIN + max(args.steps // 5, 3)
        )
        _PROBE_OVERHEAD = {
            "unprobed_step_ms": round(step_time * 1e3, 3),
            "probed_step_ms": round(probed_time * 1e3, 3),
            "overhead_frac": round(probed_time / step_time - 1.0, 4),
        }
        print(f"probe_overhead {_PROBE_OVERHEAD['overhead_frac']:+.2%} "
              f"({_PROBE_OVERHEAD['unprobed_step_ms']} -> "
              f"{_PROBE_OVERHEAD['probed_step_ms']} ms/step)", flush=True)

    # analytic A100 reference: same step FLOPs at MFU_BAR..MFU_LOW
    flops = train_step_flops(config, b, prefix_dropout_keep=0.5)

    mesh_tag = "" if mesh is None else f", mesh {args.mesh}, overlap {args.overlap}"
    result = {
        "metric": f"perceiver-ar-clm train tokens/sec/chip @{args.seq_len} ctx "
        f"({n_params/1e6:.1f}M params, {args.dtype}, batch {b}, "
        f"microbatch {microbatch}, prefix_len={prefix_len}{mesh_tag})",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec/chip",
        **_vs_baseline_fields(flops, step_time),
        **telemetry_fields(flops, step_time, [t / TIMER_CHAIN for t in timer.steps]),
    }
    if mesh is not None:
        # the audited communication footprint of the measured step: per-kind
        # collective counts + estimated bytes from the compiled HLO, so a
        # collective-count regression is visible in the committed artifact
        from perceiver_io_tpu.analysis.graph import collective_stats

        hlo = jax.jit(step).lower(state, batch).compile().as_text()
        result["telemetry"]["mesh"] = {str(k): int(v) for k, v in mesh.shape.items()}
        result["telemetry"]["overlap"] = args.overlap == "on"
        result["telemetry"]["collectives"] = collective_stats(hlo)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
