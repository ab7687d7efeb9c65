"""Drive a benchmark cell of a model that drafts for itself (a
multi-token-prediction module) at its published widths, once, and print what
the compiled generator cannot show:

    chiprun -- python tools/mtp_probe.py [--workload kexaone-ep8-mtp-decode-b64] [--seed N] [--batch-size 32] [--new-tokens 48] [--steps 8]

- through ``make_instrumented_generate_fn(probes=True)``: the ``spec.step``
  taps' counters over ``--new-tokens`` host-driven tokens (``spec_drafts_total``
  has to equal rows x steps; ``spec_accept_rate`` is the measured acceptance),
  and the ``compile`` row's cache geometry;
- the module's draft logits against the plain reference's ``mtp_logits`` on
  the cell's ``checked_rows`` first rows: at the prompt's last position (the
  module's prompt pass) and at the first ``--steps`` speculative steps (its
  two-position step over the cache with a length a row), as the widest
  absolute difference and as the reference's best logit minus its logit at
  the program's draft (the ``served_logit_gap`` of the drafts); the stack's
  own logits at the same positions beside them.

One JSON line at the end, also written to ``chiprun_out/mtp_probe.json``."""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", default="kexaone-ep8-mtp-decode-b64")
    p.add_argument("--seed", type=int, default=2290003401)
    p.add_argument("--new-tokens", type=int, default=48)
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--batch-size", type=int, default=None,
                   help="rows (default: the cell's; the host-driven pair keeps its caches beside the prompt pass's scratch, "
                        "so a cell that fills the chip as one program needs fewer here)")
    p.add_argument("--data-root", default=None, help="where workloads/ and configs/ lie (default: benchmarks/)")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import run
    from benchmarks.lib.weights import family_weights, flat_dict
    from perceiver_io_tpu.generation import GenerationConfig, make_instrumented_generate_fn
    from perceiver_io_tpu.obs.events import EventLog

    run.enable_cache()
    root = args.data_root or run.HERE
    cell = run.load_json("workloads", args.workload, root)
    config = run.load_json("configs", cell["config"], root)
    family = importlib.import_module(f"benchmarks.families.{config['family']}").Family(config)
    c = cell["params"]
    model = family.model()
    params = family_weights(family, args.seed, family.cfg["init_scale"], model)
    ids = jnp.asarray(family.prompts(args.seed, 0, args.batch_size or c["batch_size"], c["prompt_len"]))
    cache_dtype = jnp.dtype(c["cache_dtype"])
    b, n = ids.shape
    out = {"workload": args.workload, "seed": args.seed, "device": jax.devices()[0].device_kind}

    # ---- the books of the speculative steps, through the instrumented generator
    log_dir = tempfile.mkdtemp(prefix="mtp-probe-")
    gen_cfg = GenerationConfig(max_new_tokens=args.new_tokens)
    fn = make_instrumented_generate_fn(model, config=gen_cfg, cache_dtype=cache_dtype, events=EventLog(log_dir), probes=True)
    fn(params, ids)  # compiles
    _, stats = fn(params, ids)
    snap = fn.registry.snapshot()
    counters = {**snap["counters"], **snap["gauges"]}
    steps = int(counters["spec_steps_total"]) // 2
    out["instrumented"] = {
        "rows": b, "new_tokens": args.new_tokens, "steps_a_request": steps,
        "spec_drafts_total": int(counters["spec_drafts_total"]), "rows_x_steps_x_requests": b * steps * 2,
        "spec_accepted_total": int(counters["spec_accepted_total"]), "spec_accept_rate": counters["spec_accept_rate"],
        "tpot_p50_ms": None if stats.tpot_p50_s is None else 1e3 * stats.tpot_p50_s, "ttft_s": stats.ttft_s,
    }
    rows = [json.loads(line) for line in open(os.path.join(log_dir, "events.jsonl"))]
    out["compile_row"] = next(({k: v for k, v in r.items() if k.startswith(("kv_cache", "mtp", "spec", "moe_combine", "verify_attention", "gqa_verify"))}
                               for r in rows if r.get("event") == "compile" and "kv_cache_lengths" in r), None)
    print(json.dumps(out), flush=True)
    del fn

    # ---- the draft logits against the reference, on the checked rows
    decoder = model.generation_decoder()
    argmax = lambda x: jnp.argmax(x, axis=-1).astype(jnp.int32)  # noqa: E731

    @jax.jit
    def first(params, ids):
        return decoder.spec_prefill(params, ids, None, args.steps + 2, cache_dtype, argmax)

    @jax.jit
    def step(params, window, token, draft):
        p_logits, hidden, window = decoder.spec_verify(params, window, jnp.stack([token, draft], axis=1))
        g = argmax(p_logits)
        m = jnp.where(g[:, 0] == draft, 2, 1)
        m_logits, window = decoder.spec_draft(params, window, hidden, g)
        last = (m - 1)[:, None, None]
        return decoder.spec_keep(window, m), p_logits, m_logits, m, jnp.take_along_axis(g, last[:, :, 0], axis=1)[:, 0], \
            argmax(jnp.take_along_axis(m_logits, last, axis=1)[:, 0])

    token, main0, drafts0, window = first(params, ids)
    checked = list(range(c["checked_rows"]))
    main = [[np.asarray(main0)[r]] for r in checked]
    drafts = [[np.asarray(drafts0)[r]] for r in checked]
    served = [[int(token[r])] for r in checked]
    draft = argmax(drafts0)
    for _ in range(args.steps):
        window, p_logits, m_logits, m, token, draft = step(params, window, token, draft)
        p_logits, m_logits, m = np.asarray(p_logits), np.asarray(m_logits), np.asarray(m)
        for i, r in enumerate(checked):
            for j in range(m[r]):
                main[i].append(p_logits[r, j])
                drafts[i].append(m_logits[r, j])
                served[i].append(int(p_logits[r, j].argmax()))
    del window
    w = flat_dict(params)
    worst = {"main_abs_diff": 0.0, "draft_abs_diff": 0.0, "main_gap": 0.0, "draft_gap": 0.0, "positions": 0, "drafts_reference_best": 0}
    for i, r in enumerate(checked):
        k = min(len(served[i]), args.steps + 1)
        full = jnp.concatenate([ids[r], jnp.asarray(served[i][:k], ids.dtype)])[None]
        want_main = np.asarray(jax.jit(family.reference_logits("float32", k + 1))(w, full))[0, :k]  # positions n - 1 .. n + k - 2
        want_draft = np.asarray(jax.jit(family.reference_draft_logits("float32", k))(w, full))[0]  # the same positions
        got_main, got_draft = np.stack(main[i][:k]), np.stack(drafts[i][:k])
        worst["main_abs_diff"] = max(worst["main_abs_diff"], float(np.abs(got_main - want_main).max()))
        worst["draft_abs_diff"] = max(worst["draft_abs_diff"], float(np.abs(got_draft - want_draft).max()))
        worst["main_gap"] = max(worst["main_gap"], float((want_main.max(-1) - want_main[np.arange(k), got_main.argmax(-1)]).max()))
        gaps = want_draft.max(-1) - want_draft[np.arange(k), got_draft.argmax(-1)]
        worst["draft_gap"] = max(worst["draft_gap"], float(gaps.max()))
        worst["positions"] += k
        worst["drafts_reference_best"] += int((gaps == 0).sum())
    out["against_reference"] = worst
    print(json.dumps(out), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/mtp_probe.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
