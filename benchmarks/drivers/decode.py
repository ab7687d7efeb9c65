"""Decode cells: the program's compiled generator, called back to back by
one caller (closed loop) on a new seeded batch of prompts each call.

A call is dispatched, the next prompts are built and placed while it runs,
and the call ends when the host holds its generated tokens. After the window
the program's state is freed and, for a sample of finished rows drawn from
the seed, one plain forward over the prompt with its served tokens gives the
reference's logits at the served positions.

That forward equals the cached decode only while no cache has slid: once the
prompt and its new tokens outgrow ``max_seq_len`` (or the latents
``max_latents``) the program drops the oldest position, and what the cache
still holds of the others was computed with it. So the check compares each
row's tokens up to the first slide (``plain_tokens``) and says how many that
is; a cell whose prompt and new tokens fill the window at most exactly has
every served token compared."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import compare
from benchmarks.lib.weights import family_weights, flat_dict


def plain_tokens(family, p: dict) -> int:
    """How many of a row's served tokens come before the first slide of a
    cache. Token 1 comes from the prompt pass; token ``j`` from a step whose
    window holds ``prompt_len + j - 1`` positions and ``num_latents + j - 1``
    latents, and the program slides once either would pass its maximum."""
    return min(p["new_tokens"], family.seq_len - p["prompt_len"] + 1, family.latents - p["num_latents"] + 1)


class DecodeRun:
    def __init__(self, ctx):
        self.ctx, self.family, self.p = ctx, ctx.family, ctx.cell["params"]
        p, family = self.p, self.family
        ctx.mark("program imported")
        model = family.model()
        self.params = family_weights(family, ctx.seed, family.cfg["init_scale"], model)
        ctx.mark("weights made from the seed")
        generate = family.generate_fn(model, p["num_latents"], p["new_tokens"], p["cache_dtype"])
        self.n_fed = 0
        self.next_prompts = self._place_prompts()
        lowered = generate.lower(self.params, self.next_prompts)
        ctx.mark("generator traced and lowered")
        self.generate = lowered.compile()
        ctx.mark("generator compiled or read from the cache")
        self.served = []  # per call: the generated tokens (B, new_tokens)

    def _place_prompts(self):
        with jax.profiler.TraceAnnotation("bench/prompt_build"):
            prompts = self.family.prompts(self.ctx.seed, self.n_fed, self.p["batch_size"], self.p["prompt_len"])
            self.n_fed += 1
            return jax.device_put(prompts)

    def call(self) -> np.ndarray:
        with jax.profiler.TraceAnnotation("bench/generate_dispatch"):
            out = self.generate(self.params, self.next_prompts)
        self.next_prompts = self._place_prompts()
        with jax.profiler.TraceAnnotation("bench/token_fetch"):
            tokens = np.asarray(out[:, self.p["prompt_len"]:])
        self.served.append(tokens)
        return tokens

    def free(self):
        self.params = self.next_prompts = self.generate = None


def served_gaps(ctx, rows, precision: str = "float32", tokens_from: str = "served") -> dict:
    """For each sampled ``(call, row, served tokens)``: the float32
    reference's logits at every served position before the first slide, from
    one forward over the prompt with those served tokens, and how far below
    the reference's best each token lies. ``tokens_from="control"`` reads
    instead the token that the reference at ``precision`` puts first at each
    of those positions."""
    family, p = ctx.family, ctx.cell["params"]
    weights = flat_dict(family_weights(family, ctx.seed, family.cfg["init_scale"]))
    n = plain_tokens(family, p)
    latents = p["num_latents"] + n - 1
    reference = jax.jit(family.reference_logits("float32", latents))
    lower = jax.jit(family.reference_logits(precision, latents)) if tokens_from == "control" else None
    widest, n_tokens, argmax_same = 0.0, 0, 0
    for call, row, served in rows:
        prompt = family.prompts(ctx.seed, call, p["batch_size"], p["prompt_len"])[row]
        ids = jnp.asarray(np.concatenate([prompt, served[:n - 1]])[None])
        logits = np.asarray(reference(weights, ids))[0, -n:]
        tokens = served[:n] if lower is None else np.asarray(lower(weights, ids))[0, -n:].argmax(-1)
        gaps = logits.max(-1) - logits[np.arange(n), tokens]
        widest = max(widest, float(gaps.max()))
        n_tokens += n
        argmax_same += int((gaps == 0).sum())
    return {"widest_gap": widest, "tokens": n_tokens, "argmax_same": argmax_same,
            "after_slide": (p["new_tokens"] - n) * len(rows)}


def sample_rows(ctx, served: list) -> list:
    """``checked_rows`` finished rows drawn from the seed, as (call, row, tokens)."""
    rng = np.random.default_rng([ctx.seed, 2])
    p = ctx.cell["params"]
    picks = rng.choice(len(served) * p["batch_size"], size=min(p["checked_rows"], len(served) * p["batch_size"]),
                       replace=False)
    return [(int(i) // p["batch_size"], int(i) % p["batch_size"], served[int(i) // p["batch_size"]][int(i) % p["batch_size"]])
            for i in sorted(picks)]


def run(ctx) -> dict:
    p = ctx.cell["params"]
    run_ = DecodeRun(ctx)
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
    rows = [(first_in_window + c, r, t) for c, r, t in sample_rows(ctx, served)]
    found = served_gaps(ctx, rows)
    ctx.mark("reference ran over the sampled rows")
    checks = [
        compare.check("served_logit_gap", found["widest_gap"], ctx.cell["limits"]["served_logit_gap"],
                      f"{found['tokens']} served tokens of {len(rows)} rows; {found['argmax_same']} are the reference's best; "
                      f"{found['after_slide']} more came after a cache slid and are not compared"),
        compare.check("rows_with_tokens_out_of_range", float(bad), 0.0, ""),
    ]
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
    """The served-token check with the reference at ``precision`` in the
    program's place. The control need not decode: the program serves one call
    of prompts, and at each position of ``checked_rows`` of those prompts with
    their served tokens the token read is the one the lower precision puts
    first."""
    run_ = DecodeRun(ctx)
    run_.call()
    served = run_.served
    run_.free()
    del run_
    found = served_gaps(ctx, sample_rows(ctx, served), precision, tokens_from="control")
    return [compare.check("served_logit_gap", found["widest_gap"], ctx.cell["limits"]["served_logit_gap"],
                          f"{found['tokens']} positions; at {found['argmax_same']} the control agrees with the reference")]
