"""Decode cells of a model that routes: the ``decode`` driver's run (its
``DecodeRun``, its closed loop of one caller, its sample of finished rows and
its one plain forward a row, all imported from ``drivers/decode.py``), judged
on where the bulk of the served positions' gaps lies and not on the widest one
alone.

Why a second driver. ``drivers/decode.py`` compares one number, the widest
``reference's best logit - reference's logit at the served token`` over the
checked positions. In a network that sends a token to 8 of 128 experts that
maximum reads the far tail, and the far tail is routing flips: a token whose
eighth and ninth router scores lie within the hidden state's bfloat16 rounding
takes another expert than the float32 reference, and one expert's output moves
a logit by tenths. A sound bfloat16 program and the fp8 control both have that
tail (the control some hundred times as often, and no further out), so the
widest of 2048 gaps of a sound program reaches 1.16 where the control's can
read 0.92 (``PERF.md`` 2, PR 34): no limit on it separates them. The bulk
does: fp8 moves every position, bfloat16 one in thirty.

What is compared, each against a limit the cell's file names under ``limits``:

- ``served_gap_p99``: the 99th percentile (numpy's, linear interpolation) of
  the gaps over the checked positions. Holds the precision: the control has to
  fail this one.
- ``served_logit_gap``: the widest gap, as in ``drivers/decode.py``, under a
  limit that a sound program's flips stay clear of. Holds a token that is not
  the program's to serve (an altered token, a token from a wrong mask), which a
  percentile of two thousand positions cannot see; ``control`` prints what a
  token drawn at random reads, the second reading that limit is set from.
- ``rows_with_tokens_out_of_range``, as there."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.drivers import decode
from benchmarks.lib import compare
from benchmarks.lib.weights import family_weights, flat_dict

QUANTILE = 99.0


def position_gaps(ctx, rows, precision: str = "float32", tokens_from: str = "served"):
    """``decode.served_gaps`` with every position kept: for each sampled
    ``(call, row, served tokens)`` the float32 reference's logits at every
    served position before the first slide, and how far below the reference's
    best each token lies, as one array over all rows; and those logits' gap at
    a token drawn at random for each position (what an altered token reads).
    ``tokens_from="control"`` reads the token that the reference at
    ``precision`` puts first."""
    family, p = ctx.family, ctx.cell["params"]
    weights = flat_dict(family_weights(family, ctx.seed, family.cfg["init_scale"]))
    n = decode.plain_tokens(family, p)
    latents = p["num_latents"] + n - 1
    reference = jax.jit(family.reference_logits("float32", latents))
    lower = jax.jit(family.reference_logits(precision, latents)) if tokens_from == "control" else None
    rng = np.random.default_rng([ctx.seed, 3])
    gaps, altered = [], []
    for call, row, served in rows:
        prompt = family.prompts(ctx.seed, call, p["batch_size"], p["prompt_len"])[row]
        ids = jnp.asarray(np.concatenate([prompt, served[:n - 1]])[None])
        logits = np.asarray(reference(weights, ids))[0, -n:]
        tokens = served[:n] if lower is None else np.asarray(lower(weights, ids))[0, -n:].argmax(-1)
        best = logits.max(-1)
        gaps.append(best - logits[np.arange(n), tokens])
        altered.append(best - logits[np.arange(n), rng.integers(0, logits.shape[-1], size=n)])
    return np.concatenate(gaps), np.concatenate(altered), (p["new_tokens"] - n) * len(rows)


def judge(gaps: np.ndarray, limits: dict, note: str) -> list:
    """The two gap checks of ``gaps`` against the cell's limits."""
    return [compare.check("served_gap_p99", float(np.percentile(gaps, QUANTILE)), limits["served_gap_p99"], note),
            compare.check("served_logit_gap", float(gaps.max()), limits["served_logit_gap"], "the widest of the same positions")]


def run(ctx) -> dict:
    p = ctx.cell["params"]
    run_ = decode.DecodeRun(ctx)
    run_.call()  # warm-up through the window's own call; its tokens are not served traffic
    run_.served.clear()
    first_in_window = run_.n_fed - 1
    ctx.mark("warm call done")
    with ctx.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < ctx.window_seconds:
            run_.call()
        elapsed = time.perf_counter() - t0
    served = run_.served
    run_.free()
    del run_
    ctx.after_window()
    ctx.mark("window closed, program state freed")

    vocab = ctx.family.cfg["vocab_size"]
    bad = sum(int(((t < 0) | (t >= vocab)).any(axis=1).sum()) for t in served)
    rows = [(first_in_window + c, r, t) for c, r, t in decode.sample_rows(ctx, served)]
    gaps, _, after_slide = position_gaps(ctx, rows)
    ctx.mark("reference ran over the sampled rows")
    note = (f"{gaps.size} served tokens of {len(rows)} rows; {int((gaps == 0).sum())} are the reference's best; "
            f"{after_slide} more came after a cache slid and are not compared")
    checks = judge(gaps, ctx.cell["limits"], note) + [compare.check("rows_with_tokens_out_of_range", float(bad), 0.0, "")]
    n_rows = len(served) * p["batch_size"]
    tokens = n_rows * p["new_tokens"]
    print(f"decode: {len(served)} calls, {n_rows} rows, {tokens} tokens in {elapsed:.4f} s", flush=True)
    return {
        "attempted": n_rows,
        "failed": bad,
        "checks": checks,
        "end_to_end": {"gen_tokens_per_s": tokens / elapsed},
        "counters": {"calls": len(served), "rows": n_rows, "tokens": tokens, "elapsed_s": elapsed},
    }


def control(ctx, precision: str) -> list:
    """The gap checks with the reference at ``precision`` in the program's
    place, as ``decode.control`` makes them: the program serves one call of
    prompts, and at each position of ``checked_rows`` of those prompts with
    their served tokens the token read is the one the lower precision puts
    first. Also says what a token drawn at random reads at those positions."""
    run_ = decode.DecodeRun(ctx)
    run_.call()
    served = run_.served
    run_.free()
    del run_
    gaps, altered, _ = position_gaps(ctx, decode.sample_rows(ctx, served), precision, tokens_from="control")
    ctx.mark(f"a token drawn at random reads a gap of {altered.min():.3f} at the least, {np.percentile(altered, 1):.3f} at the "
             f"1st percentile and {np.median(altered):.3f} at the median of {altered.size} positions")
    return judge(gaps, ctx.cell["limits"],
                 f"{gaps.size} positions; at {int((gaps == 0).sum())} the control agrees with the reference")
