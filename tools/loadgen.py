"""Loadline CLI — drive synthetic serving load, certify it, round-trip it.

The standing serving-observability gate (``tasks.py load``; ``--smoke`` is
wired into ``tasks.py perf``): run a seeded closed-loop (or open-loop) load
against the tiny flagship-family CLM through the fully instrumented path —
flight recorder wrapping the event log, ``/metrics``+``/slo`` scrape server
up for the duration — then assert the whole surface end to end:

1. the event stream validates (``load.summary``, ``flight.dump``,
   queue-wait-stamped ``request`` rows all schema-checked);
2. a **planted SLO breach** (the recorder's TTFT bound tightened to ~0 for
   one extra request riding the already-compiled fns) produces EXACTLY one
   flight dump whose ``flight.dump`` event names the breaching request's
   span — the post-mortem path demonstrably works;
3. the live scrape surface answers: ``/metrics`` exposes
   ``histogram_quantile``-ready series, ``/slo`` serves the live report;
4. the run summarizes into a LOAD artifact body whose run-vs-itself
   :func:`obs.loadgen.diff_load` is clean (comparability rules hold);
5. the ledger's ``LOAD_r*.json`` floors hold against the latest committed
   artifact (``contracts/ledger.json`` — the same floor machinery the
   bench gate uses).

    python tools/loadgen.py                      # the full gate (200 reqs)
    python tools/loadgen.py --smoke              # CI-fast subset (24 reqs)
    python tools/loadgen.py --write-artifact     # refresh LOAD_r<next>.json
    python tools/loadgen.py --diff OLD.json NEW.json [--tolerance k=v]
    python tools/loadgen.py --mode open --rate 20 --requests 100
    python tools/loadgen.py --fleet 2            # Fleetline routed round

Exit codes (mirrors tools/obs_gate.py): 0 clean, 1 gate failure /
regression, 2 not comparable (diff mode), 3 internal error.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import sys
import tempfile
import urllib.request

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

_ROUND_RE = re.compile(r"_r(\d+)\.json$")


def build_workload():
    """The gate's model: same tiny flagship-family geometry as
    tools/obs_gate.py (the gate certifies serving telemetry, not perf)."""
    import jax
    import numpy as np

    from perceiver_io_tpu.models.text import CausalLanguageModel, CausalLanguageModelConfig

    config = CausalLanguageModelConfig(
        vocab_size=64, max_seq_len=24, max_latents=8, num_channels=32,
        num_heads=4, num_self_attention_layers=2, cross_attention_dropout=0.5,
    )
    model = CausalLanguageModel(config)
    ids = np.random.default_rng(0).integers(0, config.vocab_size, size=(1, 12))
    import jax.numpy as jnp

    params = model.init(jax.random.PRNGKey(0), jnp.asarray(ids), prefix_len=8)
    return model, params, config


def _fetch(url: str) -> str:
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.read().decode()


def run_gate(args) -> int:
    from perceiver_io_tpu.obs.events import EventLog, validate_events, write_run_manifest
    from perceiver_io_tpu.obs.flightrec import FlightRecorder, SLOBounds
    from perceiver_io_tpu.obs.loadgen import (
        WorkloadSpec,
        build_load_doc,
        diff_load,
        format_load_diff,
        run_load,
    )
    from perceiver_io_tpu.obs.slo import request_breakdowns, write_slo_report

    out_dir = args.out or tempfile.mkdtemp(prefix="loadgen_")
    keep = args.keep or args.out is not None
    problems: list = []
    try:
        n_requests = args.requests
        spec = WorkloadSpec(seed=args.seed)
        print(
            f"loadgen: {args.mode}-loop, {n_requests} requests "
            f"({'concurrency ' + str(args.concurrency) if args.mode == 'closed' else f'rate {args.rate}/s'}) "
            f"-> {out_dir}"
        )
        model, params, config = build_workload()
        events = EventLog(out_dir, main_process=True)
        manifest = write_run_manifest(
            out_dir, model_config=config, extra={"workload_spec": spec.to_dict()},
            main_process=True,
        )
        # generous standing bounds: the planted breach below, not normal CPU
        # jitter, is what should trip the recorder in this gate
        recorder = FlightRecorder(
            events, out_dir=out_dir,
            slo=SLOBounds(ttft_s=args.ttft_slo, tpot_p99_s=args.tpot_slo),
        )

        from perceiver_io_tpu.obs.metrics import MetricsRegistry
        from perceiver_io_tpu.obs.server import ObsServer

        registry = MetricsRegistry()
        with ObsServer(registry=registry, run_dir=out_dir) as server:
            report = run_load(
                model, params, spec,
                mode=args.mode, n_requests=n_requests,
                concurrency=args.concurrency, rate_rps=args.rate,
                num_latents=4, events=recorder, registry=registry,
                snapshot_interval_s=0.0,
            )
            summary = report.summary
            print(
                f"loadgen: {summary['n_requests']} requests in {summary['duration_s']:.2f}s "
                f"({summary['achieved_rps']:.1f} req/s, {summary['throughput_tok_s']:.0f} tok/s, "
                f"{summary['errors']} errors, {summary['n_cold']} cold)"
            )

            # span-joined tail attribution over the MAIN run (before the
            # plant adds its request): enriches the artifact's breakdown
            # with the compile-if-cold / service / total legs only the
            # event-stream join can see
            from perceiver_io_tpu.obs.events import merged_events

            bd = request_breakdowns(merged_events(out_dir))
            if not bd or "prefill_ms" not in bd.get("medians", {}):
                problems.append("request_breakdowns produced no prefill median")
            else:
                summary["breakdown_ms"] = {
                    key.replace("_ms", ""): val
                    for key, val in bd["medians"].items()
                }

            # --- planted SLO breach: exactly one dump, naming the span ---
            dumps_before = len(recorder.dumps)
            prev_ttft = recorder.slo.ttft_s
            recorder.slo.ttft_s = 1e-9
            plant = run_load(
                model, params, WorkloadSpec(seed=args.seed + 999),
                mode="closed", n_requests=1, concurrency=1,
                num_latents=4, events=recorder, registry=report.registry,
                generate_fns=report.generate_fns, snapshot_interval_s=1e9,
            )
            recorder.slo.ttft_s = prev_ttft
            if plant.records[0].outcome != "ok":
                problems.append(f"planted request errored: {plant.records[0].error}")
            new_dumps = recorder.dumps[dumps_before:]
            if len(new_dumps) != 1:
                problems.append(
                    f"planted SLO breach produced {len(new_dumps)} flight dumps, want exactly 1"
                )
            else:
                with open(new_dumps[0]) as f:
                    dump = json.load(f)
                if dump.get("trigger") != "slo_ttft":
                    problems.append(f"dump trigger {dump.get('trigger')!r} != 'slo_ttft'")
                if not dump.get("trigger_span_id"):
                    problems.append("flight dump does not name the breaching span")
                if not dump.get("events"):
                    problems.append("flight dump carries no ring events")
                elif not any(
                    e.get("event") == "span"
                    and e.get("span_id") == dump.get("trigger_span_id")
                    for e in dump["events"]
                ):
                    # the post-mortem contract: the ring frozen into the
                    # dump must hold the very span the dump names
                    problems.append("flight dump ring lacks the named trigger span")

            # --- scrape surface answers while the run is live ---
            metrics_text = _fetch(server.url + "/metrics")
            if 'generate_ttft_s_bucket{le="+Inf"}' not in metrics_text:
                problems.append("/metrics lacks the +Inf TTFT bucket (histogram_quantile would fail)")
            if "generate_queue_wait_s_count" not in metrics_text:
                problems.append("/metrics lacks the queue-wait histogram")
            health = json.loads(_fetch(server.url + "/healthz"))
            if health.get("status") != "ok":
                problems.append(f"/healthz status {health.get('status')!r}")
            slo_live = json.loads(_fetch(server.url + "/slo"))
            if slo_live.get("n_requests") != n_requests + 1:
                problems.append(
                    f"/slo n_requests {slo_live.get('n_requests')} != {n_requests + 1}"
                )

        # --- event stream validates, dump event in stream ---
        warnings_out: list = []
        problems += validate_events(out_dir, warnings_out=warnings_out)
        for w in warnings_out:
            print(f"loadgen: warning: {w}")
        stream = merged_events(out_dir)
        kinds = [e.get("event") for e in stream]
        if "load.summary" not in kinds:
            problems.append("no load.summary event in the stream")
        dump_rows = [e for e in stream if e.get("event") == "flight.dump"]
        if len(dump_rows) != 1:
            problems.append(f"{len(dump_rows)} flight.dump events in stream, want 1")
        else:
            breach = [e for e in stream if e.get("event") == "request"][-1]
            if dump_rows[0].get("trigger_span_id") != breach.get("span_id"):
                problems.append("flight.dump trigger_span_id != breaching request's span_id")
        loadgen_reqs = [
            e for e in stream
            if e.get("event") == "request" and e.get("queue_wait_s") is not None
        ]
        if len(loadgen_reqs) != n_requests + 1:
            problems.append(
                f"{len(loadgen_reqs)} queue-wait-stamped request rows, want {n_requests + 1}"
            )
        for key in ("achieved_rps", "throughput_tok_s", "error_rate", "ttft_s",
                    "queue_wait_s", "breakdown_ms"):
            if key not in summary:
                problems.append(f"summary missing {key!r}")
        write_slo_report(out_dir)

        # --- artifact body + run-vs-itself comparability diff ---
        doc = build_load_doc(
            args.round or _next_round(), summary, spec, manifest=manifest
        )
        self_diff = diff_load(doc, doc)
        if not (self_diff["comparable"] and self_diff["ok"]):
            problems.append("run-vs-itself load diff NOT clean (differ broken): "
                            + format_load_diff(self_diff))
        else:
            print("loadgen: run-vs-itself comparability diff clean")

        if args.write_artifact:
            # pre-validate THIS doc against the LOAD floors before it hits
            # disk: a sub-floor artifact (e.g. a --smoke-size run) would
            # become the latest round and fail every future gate run.
            # NOTE the LOAD family is ENGINE-floored since PR 13 (a
            # deliberate ratchet: committed serving rounds must sustain
            # engine-scale throughput) — sequential runs certify telemetry
            # here but produce new rounds with --engine.
            floor_fails = check_doc_floors(doc)
            if floor_fails:
                problems += [
                    f"refusing to write artifact: {f} (the LOAD family is "
                    "engine-floored — produce committed rounds with --engine)"
                    for f in floor_fails
                ]
            else:
                path = os.path.join(_REPO, f"LOAD_r{doc['n']:02d}.json")
                with open(path, "w") as f:
                    json.dump(doc, f, indent=1, sort_keys=True)
                    f.write("\n")
                print(f"loadgen: wrote {path}")

        # --- ledger floors over the committed LOAD artifacts ---
        problems += check_load_floors()

        if problems:
            print("loadgen: gate FAILED:")
            for p in problems:
                print(f"  - {p}")
            return 1
        print(
            "loadgen: OK — "
            f"ttft_p99={summary['ttft_s']['p99']}s "
            f"queue_p99={summary['queue_wait_s']['p99']}s "
            f"(1 planted breach -> 1 flight dump)"
        )
        return 0
    except Exception as e:  # noqa: BLE001 — CI must see crash != verdict
        print(f"loadgen: internal error: {e}", file=sys.stderr)
        import traceback

        traceback.print_exc()
        return 3
    finally:
        if not keep:
            shutil.rmtree(out_dir, ignore_errors=True)


def run_engine_gate(args) -> int:
    """The ENGINE leg (``--engine``): a closed-loop (or, with ``--mode
    open --rate R``, an open-loop Poisson-arrival) run through the
    continuous-batching paged-KV engine (``serving.engine.EngineFrontEnd``,
    docs/serving.md) instead of the sequential instrumented path. The
    engine warms its compile caches through the same instance before the
    measured run (one request per workload geometry) — an open-loop queue
    must not flood during the cold-start compile storm the closed loop
    self-throttles through, and the Loadline charter is warm serving
    either way. Asserts:

    1. every request served ok, books balanced, zero leaked slots AND zero
       leaked pages (allocator audit);
    2. the event stream validates — engine ``request`` rows carry
       queue-wait and the ``batch_size_at_decode`` field;
    3. a planted mid-decode kill (its own engine instance + recorder, so
       the main artifact stays clean) leaves books balanced with exactly
       one flight dump naming the dead request's span;
    4. ``/metrics`` exposes the engine gauges
       (``engine_batch_fill_frac`` / ``engine_kv_pages_used``);
    5. the summary diffs clean against itself and the LOAD floors hold —
       including the engine throughput floor and p99-TPOT ceiling.

    The summary (and so the ``load.summary`` event and the LOAD artifact
    body) carries the Evictline counters — ``evictions`` / ``resumes`` /
    ``parked_depth_peak`` (the ``serve_parked_depth`` gauge's high-water
    mark) — as optional validated fields, so eviction behavior lands under
    the standing comparability-diffed gate
    (docs/robustness.md#engine-eviction-and-recovery).
    """
    import time as _time

    from perceiver_io_tpu.obs.events import EventLog, validate_events, write_run_manifest
    from perceiver_io_tpu.obs.flightrec import FlightRecorder, SLOBounds
    from perceiver_io_tpu.obs.loadgen import (
        RequestRecord,
        WorkloadSpec,
        build_load_doc,
        diff_load,
        format_load_diff,
        summarize_load,
    )
    from perceiver_io_tpu.obs.metrics import MetricsRegistry
    from perceiver_io_tpu.obs.server import ObsServer
    from perceiver_io_tpu.serving import EngineConfig, EngineFrontEnd
    from perceiver_io_tpu.serving.faultinject import FaultInjector

    out_dir = args.out or tempfile.mkdtemp(prefix="loadgen_engine_")
    keep = args.keep or args.out is not None
    problems: list = []
    try:
        n_requests = args.requests
        spec = WorkloadSpec(seed=args.seed)
        engine_cfg = EngineConfig(
            slots=args.slots, page_size=8, max_ca_tokens=24, max_sa_tokens=16
        )
        drive = (
            f"open-loop @ {args.rate} req/s" if args.mode == "open"
            else f"closed-loop, concurrency {args.concurrency}"
        )
        print(
            f"loadgen: ENGINE {drive}, {n_requests} requests "
            f"(slots {engine_cfg.slots}) -> {out_dir}"
        )
        model, params, config = build_workload()
        events = EventLog(out_dir, main_process=True)
        manifest = write_run_manifest(
            out_dir, model_config=config,
            extra={"workload_spec": spec.to_dict(), "engine": True},
            main_process=True,
        )
        recorder = FlightRecorder(
            events, out_dir=out_dir,
            slo=SLOBounds(ttft_s=args.ttft_slo, tpot_p99_s=args.tpot_slo),
        )
        from perceiver_io_tpu.serving import FrontEndConfig

        registry = MetricsRegistry()
        fe = EngineFrontEnd(
            model, params, num_latents=4, engine_config=engine_cfg,
            # frequent enough that live batch-fill/page gauges land in the
            # stream, coarse enough that snapshot I/O stays off the hot loop
            config=FrontEndConfig(snapshot_interval_s=0.25),
            events=recorder, registry=registry,
        )
        specs = spec.draw(n_requests, int(config.vocab_size))
        # warm the compile caches through the SAME engine instance before
        # the measured run: one request per (prompt_len, budget) geometry in
        # the mix compiles its prefill/join path. An open-loop run must not
        # flood its bounded queue during the cold-start compile storm (the
        # closed loop self-throttles there, open-loop arrivals do not wait)
        # — and the Loadline charter is to measure WARM serving either way.
        warm = dataclasses_replace_indices(
            [
                WorkloadSpec(
                    seed=args.seed + 7777 + i, prompt_lens=(p,), max_new_tokens=(m,)
                ).draw(1, int(config.vocab_size))[0]
                for i, (p, m) in enumerate(
                    (p, m) for p in spec.prompt_lens for m in spec.max_new_tokens
                )
            ],
            base=1_000_000,
        )
        fe.run_closed(warm, concurrency=len(warm))
        n_warm = len(warm)
        # measured-window boundary: the warm requests above fed the same
        # registry/engine counters the artifact summarizes — drop their
        # per-token samples and mark the step/fill counters so committed
        # percentiles and engine figures cover only measured traffic
        registry.histogram("generate_tpot_s").reset()
        warm_steps, warm_fill = fe._engine_steps, fe._fill_sum
        warm_books = fe.books()
        warm_evictions, warm_resumes = warm_books["evictions"], warm_books["resumes"]
        registry.gauge("serve_parked_depth").reset_peak()
        with ObsServer(registry=registry, run_dir=out_dir, health=fe.health) as server:
            t0 = _time.perf_counter()
            if args.mode == "open":
                # the open-loop leg (ISSUE 14 satellite — the item-1
                # certification remainder): Poisson arrivals at the target
                # rate absorbed by the continuous batch; achieved_rps is
                # the externally-imposed rate actually sustained, the
                # number the engine_open_achieved_rps ledger floor pins
                recs = fe.run_open(specs, rate_rps=args.rate, seed=args.seed + 1)
            else:
                recs = fe.run_closed(specs, concurrency=args.concurrency)
            duration_s = _time.perf_counter() - t0

            metrics_text = _fetch(server.url + "/metrics")
            for gauge in ("engine_batch_fill_frac", "engine_kv_pages_used"):
                if gauge not in metrics_text:
                    problems.append(f"/metrics lacks the {gauge} gauge")
            health = json.loads(_fetch(server.url + "/healthz"))
            if health.get("books_balanced") is not True:
                problems.append(f"/healthz books_balanced {health.get('books_balanced')!r}")

        books = fe.books()
        problems += [f"engine books: {p}" for p in fe.audit()]
        problems += [f"ca pages: {p}" for p in fe.ca_alloc.audit()]
        problems += [f"sa pages: {p}" for p in fe.sa_alloc.audit()]
        if fe.ca_alloc.pages_used or fe.sa_alloc.pages_used:
            problems.append(
                f"pages leaked after drain: ca={fe.ca_alloc.pages_used} "
                f"sa={fe.sa_alloc.pages_used}"
            )
        if books["ok"] != n_requests + n_warm:
            problems.append(
                f"served {books['ok']}/{n_requests} (+{n_warm} warmup) ok: {books}"
            )

        records = [
            RequestRecord(
                index=r.index, prompt_len=r.prompt_len,
                max_new_tokens=r.max_new_tokens, batch=r.batch,
                queue_wait_s=r.queue_wait_s or 0.0,
                outcome="ok" if r.outcome == "ok" else "error",
                compiled=r.compiled, ttft_s=r.ttft_s, decode_s=r.decode_s,
                tokens_out=r.tokens_out,
            )
            for r in recs
        ]
        summary = summarize_load(
            records, duration_s, registry=registry, mode=args.mode,
            concurrency=args.concurrency if args.mode == "closed" else None,
            rate_rps=args.rate if args.mode == "open" else None,
        )
        steps = fe._engine_steps - warm_steps
        summary["engine"] = {
            "slots": engine_cfg.slots,
            "page_size": engine_cfg.page_size,
            "decode_steps": steps,
            "batch_fill_frac": round(
                (fe._fill_sum - warm_fill) / (steps * engine_cfg.slots), 6
            ) if steps else 0.0,
        }
        # Evictline telemetry on the load.summary row AND the LOAD artifact
        # body — optional validated fields (obs.events._OPTIONAL_FIELD_TYPES:
        # pre-Evictline artifacts stay valid, a non-numeric regression here
        # fails validation), so eviction behavior rides the standing
        # comparability-diffed gate. Zero under the default full-headroom
        # pool; a committed run with a tight pool records its real churn —
        # delta-based at the measured-window boundary like decode_steps/
        # batch_fill above (the odometers are lifetime counters and the
        # parked-depth peak resets after warmup), so warmup churn never
        # contaminates the committed figures.
        fe_books = fe.books()
        parked_peak = fe.registry.gauge("serve_parked_depth").peak
        summary["evictions"] = fe_books["evictions"] - warm_evictions
        summary["resumes"] = fe_books["resumes"] - warm_resumes
        summary["parked_depth_peak"] = 0 if parked_peak is None else int(parked_peak)
        if events is not None:
            events.emit("load.summary", **summary)
            registry.maybe_emit(events, min_interval_s=0.0)
        print(
            f"loadgen: engine served {summary['n_requests']} requests in "
            f"{summary['duration_s']:.2f}s ({summary['throughput_tok_s']:.0f} tok/s, "
            f"{fe._engine_steps} batched steps, {summary['errors']} errors)"
        )

        # --- planted mid-decode kill: separate instance, clean main books --
        plant_dir = os.path.join(out_dir, "plant")
        plant_events = EventLog(plant_dir, main_process=True)
        plant_rec = FlightRecorder(plant_events, out_dir=plant_dir, slo=SLOBounds())
        injector = FaultInjector().kill_at(2, 1)
        plant_fe = EngineFrontEnd(
            model, params, num_latents=4, engine_config=engine_cfg,
            events=plant_rec, injector=injector,
        )
        plant_recs = plant_fe.run_closed(spec.draw(6, int(config.vocab_size)),
                                         concurrency=4)
        plant_books = plant_fe.books()
        if not plant_books["balanced"] or plant_books["error"] != 1:
            problems.append(f"planted kill books not clean: {plant_books}")
        if plant_fe.ca_alloc.pages_used or plant_fe.sa_alloc.pages_used:
            problems.append("planted kill leaked pages")
        if len(plant_rec.dumps) != 1:
            problems.append(
                f"planted kill produced {len(plant_rec.dumps)} flight dumps, want 1"
            )
        else:
            with open(plant_rec.dumps[0]) as f:
                dump = json.load(f)
            from perceiver_io_tpu.obs.events import merged_events as _merged

            err_rows = [e for e in _merged(plant_dir)
                        if e.get("event") == "request" and e.get("outcome") == "error"]
            if len(err_rows) != 1 or dump.get("trigger_span_id") != err_rows[0].get("span_id"):
                problems.append("kill dump does not name the dead request's span")
        dead = next((r for r in plant_recs if r.outcome == "error"), None)
        if dead is None or not (0 < dead.tokens_out < dead.max_new_tokens):
            problems.append(f"planted kill not mid-decode: {dead}")

        # --- stream validation (engine rows carry the new optional field) --
        warnings_out: list = []
        problems += validate_events(out_dir, warnings_out=warnings_out)
        for w in warnings_out:
            print(f"loadgen: warning: {w}")
        from perceiver_io_tpu.obs.events import merged_events

        stream = merged_events(out_dir)
        req_rows = [e for e in stream if e.get("event") == "request"]
        if len(req_rows) != n_requests + n_warm:
            problems.append(
                f"{len(req_rows)} request rows, want {n_requests} + {n_warm} warmup"
            )
        if not any(e.get("batch_size_at_decode") for e in req_rows):
            problems.append("no request row carries batch_size_at_decode")
        if not all(e.get("queue_wait_s") is not None for e in req_rows):
            problems.append("engine request rows missing queue_wait_s")

        for key in ("achieved_rps", "throughput_tok_s", "error_rate", "ttft_s",
                    "queue_wait_s", "tpot_s", "breakdown_ms",
                    "evictions", "resumes", "parked_depth_peak"):
            if key not in summary:
                problems.append(f"engine summary missing {key!r}")

        doc = build_load_doc(
            args.round or _next_round(), summary, spec, manifest=manifest,
        )
        self_diff = diff_load(doc, doc)
        if not (self_diff["comparable"] and self_diff["ok"]):
            problems.append("run-vs-itself load diff NOT clean: "
                            + format_load_diff(self_diff))

        if args.write_artifact:
            floor_fails = check_doc_floors(doc)
            if floor_fails:
                problems += [f"refusing to write artifact: {f}" for f in floor_fails]
            else:
                path = os.path.join(_REPO, f"LOAD_r{doc['n']:02d}.json")
                with open(path, "w") as f:
                    json.dump(doc, f, indent=1, sort_keys=True)
                    f.write("\n")
                print(f"loadgen: wrote {path}")

        problems += check_load_floors()

        if problems:
            print("loadgen: engine gate FAILED:")
            for p in problems:
                print(f"  - {p}")
            return 1
        print(
            "loadgen: engine OK — "
            f"{summary['throughput_tok_s']:.0f} tok/s at ok_rate "
            f"{summary['ok_rate']} (planted mid-decode kill: books balanced, "
            "1 flight dump, pages freed)"
        )
        return 0
    except Exception as e:  # noqa: BLE001 — CI must see crash != verdict
        print(f"loadgen: internal error: {e}", file=sys.stderr)
        import traceback

        traceback.print_exc()
        return 3
    finally:
        if not keep:
            shutil.rmtree(out_dir, ignore_errors=True)


def build_prefix_workload():
    """The prefix gate's model: a WIDE flagship-family geometry (256
    channels, 8 latents, 448-token prompts). Sharing pays in skipped
    prefill compute — embed + CA k/v projections over the matched context
    run — and on the tiny c32 gate model that compute is dispatch noise,
    so a shared-vs-unshared TTFT ratio measured there would certify
    nothing. At c256 the unshared prefill is genuinely compute-bound and
    the 0.5x ratio floor measures the sharing win, not jit overhead."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.models.text import CausalLanguageModel, CausalLanguageModelConfig

    config = CausalLanguageModelConfig(
        vocab_size=256, max_seq_len=512, max_latents=32, num_channels=256,
        num_heads=8, num_self_attention_layers=2, cross_attention_dropout=0.5,
    )
    model = CausalLanguageModel(config)
    ids = np.random.default_rng(0).integers(0, config.vocab_size, size=(1, 64))
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(ids), prefix_len=56)
    return model, params, config


def run_prefix_gate(args) -> int:
    """The PREFIX-SHARING leg (``--prefix``): the Shareline certification
    run (docs/serving.md#prefix-sharing). A closed-loop workload whose
    requests share a 440-token prompt prefix is served twice on the same
    wide-model geometry — once with ``EngineConfig.prefix_sharing`` on
    (the measured/artifact leg) and once with it off (the baseline leg) —
    and the gate asserts the sharing machinery end to end:

    1. every request served ok in BOTH legs, and the two legs'
       token streams are **bit-exact identical** per request (sharing is
       an allocator/prefill optimization, never an approximation);
    2. the measured leg actually shared: prefix hit rate >= the
       ``load_prefix_hit_rate`` ledger floor, ``serve.prefix_hit`` events
       span-attributed in the validated stream, ``serve_prefix_hits_total``
       live on ``/metrics``;
    3. books balanced, page audits clean, the SHARING audit clean
       (refcount balance + index/books agreement), and the prefix index
       fully expired at drain — no node may outlive its pages;
    4. the artifact body carries a ``summary.prefix`` block whose
       ``ttft_p50_ratio`` (shared / unshared TTFT p50, same geometry)
       holds the <= 0.5 ``load_shared_ttft_ratio`` ceiling.

    The committed doc deliberately does NOT carry ``summary.engine``: the
    engine-gate floors (throughput >= 621 tok/s, p99-TPOT <= 5ms) were
    calibrated on the tiny c32 gate model and keep reading the ``--engine``
    rounds (LOAD_r02/r03); the wide-model prefix round is judged by its own
    ``summary.prefix``-matched floors plus the family-wide ok-rate/size
    floors. The engine figures are still recorded under
    ``summary.prefix.engine`` for the record."""
    import dataclasses
    import time as _time

    from perceiver_io_tpu.obs.events import EventLog, validate_events, write_run_manifest
    from perceiver_io_tpu.obs.flightrec import FlightRecorder, SLOBounds
    from perceiver_io_tpu.obs.loadgen import (
        RequestRecord,
        WorkloadSpec,
        build_load_doc,
        diff_load,
        format_load_diff,
        summarize_load,
    )
    from perceiver_io_tpu.obs.metrics import MetricsRegistry
    from perceiver_io_tpu.obs.server import ObsServer
    from perceiver_io_tpu.serving import EngineConfig, EngineFrontEnd, FrontEndConfig

    out_dir = args.out or tempfile.mkdtemp(prefix="loadgen_prefix_")
    keep = args.keep or args.out is not None
    problems: list = []
    try:
        n_requests = args.requests
        spec = WorkloadSpec(
            seed=args.seed, prompt_lens=(448,), max_new_tokens=(8, 12),
            shared_prefix_len=440,
        )
        print(
            f"loadgen: PREFIX closed-loop, concurrency {args.concurrency}, "
            f"{n_requests} requests (prompt 448, shared prefix 440) -> {out_dir}"
        )
        model, params, config = build_prefix_workload()
        specs = spec.draw(n_requests, int(config.vocab_size))

        def engine_cfg(sharing: bool) -> EngineConfig:
            return EngineConfig(
                slots=3, page_size=8, max_ca_tokens=460, max_sa_tokens=20,
                prefix_sharing=sharing,
            )

        def warm_specs():
            # per-budget SHARED waves (not one lone request per geometry):
            # the shared-prefill program only compiles when a wave actually
            # shares, and warm residency must not leak into the measured
            # window — the waves drain fully, their run expires, and the
            # first measured request republishes (hit_rate = (N-1)/N)
            warm = []
            for j, m in enumerate(spec.max_new_tokens):
                ws = WorkloadSpec(
                    seed=args.seed + 9000 + j, prompt_lens=spec.prompt_lens,
                    max_new_tokens=(m,), shared_prefix_len=spec.shared_prefix_len,
                ).draw(3, int(config.vocab_size))
                warm += [dataclasses.replace(s, index=1_000_000 + 10 * j + k)
                         for k, s in enumerate(ws)]
            return warm

        # --- measured leg: sharing ON, fully instrumented -----------------
        events = EventLog(out_dir, main_process=True)
        manifest = write_run_manifest(
            out_dir, model_config=config,
            extra={"workload_spec": spec.to_dict(), "engine": True, "prefix": True},
            main_process=True,
        )
        recorder = FlightRecorder(
            events, out_dir=out_dir,
            slo=SLOBounds(ttft_s=args.ttft_slo, tpot_p99_s=args.tpot_slo),
        )
        registry = MetricsRegistry()
        fe = EngineFrontEnd(
            model, params, num_latents=8, engine_config=engine_cfg(True),
            config=FrontEndConfig(snapshot_interval_s=0.25),
            events=recorder, registry=registry,
        )
        warm = warm_specs()
        fe.run_closed(warm, concurrency=len(warm))
        n_warm = len(warm)
        registry.histogram("generate_tpot_s").reset()
        warm_steps, warm_fill = fe._engine_steps, fe._fill_sum
        hits0, pages0 = fe._n_prefix_hits, fe._n_prefix_pages_shared
        with ObsServer(registry=registry, run_dir=out_dir, health=fe.health) as server:
            t0 = _time.perf_counter()
            recs = fe.run_closed(specs, concurrency=args.concurrency)
            duration_s = _time.perf_counter() - t0
            metrics_text = _fetch(server.url + "/metrics")
            for counter in ("serve_prefix_hits_total", "serve_prefix_pages_shared"):
                if counter not in metrics_text:
                    problems.append(f"/metrics lacks the {counter} counter")
        hits = fe._n_prefix_hits - hits0
        pages_shared = fe._n_prefix_pages_shared - pages0

        problems += [f"engine books: {p}" for p in fe.audit()]
        problems += [f"sharing audit: {p}" for p in fe.sharing_audit()]
        if fe.ca_alloc.pages_used or fe.sa_alloc.pages_used:
            problems.append(
                f"pages leaked after drain: ca={fe.ca_alloc.pages_used} "
                f"sa={fe.sa_alloc.pages_used}"
            )
        if fe.prefix_index.pages():
            problems.append(
                f"prefix index names pages after drain: {fe.prefix_index.pages()}"
            )
        books = fe.books()
        if books["ok"] != n_requests + n_warm:
            problems.append(
                f"served {books['ok']}/{n_requests} (+{n_warm} warmup) ok: {books}"
            )

        # --- baseline leg: sharing OFF, same geometry, same workload ------
        base_reg = MetricsRegistry()
        fe_base = EngineFrontEnd(
            model, params, num_latents=8, engine_config=engine_cfg(False),
            registry=base_reg,
        )
        fe_base.run_closed(warm_specs(), concurrency=n_warm)
        base_reg.histogram("generate_tpot_s").reset()
        bt0 = _time.perf_counter()
        base_recs = fe_base.run_closed(specs, concurrency=args.concurrency)
        base_duration_s = _time.perf_counter() - bt0
        if fe_base._n_prefix_hits:
            problems.append(
                f"baseline leg shared anyway: {fe_base._n_prefix_hits} hits"
            )
        base_books = fe_base.books()
        if base_books["ok"] != n_requests + n_warm:
            problems.append(f"baseline leg not clean: {base_books}")

        # --- decode_shared consistency: the two legs are bit-exact --------
        diverged = [
            s.index for s in specs
            if fe.served_tokens.get(s.index) != fe_base.served_tokens.get(s.index)
        ]
        if diverged:
            problems.append(
                f"shared vs unshared token streams diverge for "
                f"{len(diverged)} requests (first: {diverged[:5]}) — "
                "prefix sharing must be exact, not approximate"
            )
        else:
            print(
                f"loadgen: decode_shared consistency — {n_requests} request "
                "token streams bit-exact across shared/unshared legs"
            )

        def to_records(raw):
            return [
                RequestRecord(
                    index=r.index, prompt_len=r.prompt_len,
                    max_new_tokens=r.max_new_tokens, batch=r.batch,
                    queue_wait_s=r.queue_wait_s or 0.0,
                    outcome="ok" if r.outcome == "ok" else "error",
                    compiled=r.compiled, ttft_s=r.ttft_s, decode_s=r.decode_s,
                    tokens_out=r.tokens_out,
                )
                for r in raw
            ]

        summary = summarize_load(
            to_records(recs), duration_s, registry=registry, mode="closed",
            concurrency=args.concurrency,
        )
        base_summary = summarize_load(
            to_records(base_recs), base_duration_s, registry=base_reg,
            mode="closed", concurrency=args.concurrency,
        )
        steps = fe._engine_steps - warm_steps
        cfg = engine_cfg(True)
        ratio = summary["ttft_s"]["p50"] / base_summary["ttft_s"]["p50"]
        summary["prefix"] = {
            "hit_rate": round(hits / n_requests, 6),
            "hits": hits,
            "pages_shared": pages_shared,
            "tokens_skipped": pages_shared * cfg.page_size,
            "ttft_p50_shared_s": summary["ttft_s"]["p50"],
            "ttft_p50_unshared_s": base_summary["ttft_s"]["p50"],
            "ttft_p50_ratio": round(ratio, 6),
            "baseline_throughput_tok_s": base_summary["throughput_tok_s"],
            "engine": {
                "slots": cfg.slots,
                "page_size": cfg.page_size,
                "decode_steps": steps,
                "batch_fill_frac": round(
                    (fe._fill_sum - warm_fill) / (steps * cfg.slots), 6
                ) if steps else 0.0,
            },
        }
        if events is not None:
            events.emit("load.summary", **summary)
            registry.maybe_emit(events, min_interval_s=0.0)
        print(
            f"loadgen: prefix leg served {summary['n_requests']} in "
            f"{summary['duration_s']:.2f}s — hit_rate "
            f"{summary['prefix']['hit_rate']}, ttft p50 "
            f"{summary['ttft_s']['p50'] * 1e3:.2f}ms shared vs "
            f"{base_summary['ttft_s']['p50'] * 1e3:.2f}ms unshared "
            f"(ratio {summary['prefix']['ttft_p50_ratio']})"
        )

        # --- stream validation: span-attributed serve.prefix_hit rows -----
        warnings_out: list = []
        problems += validate_events(out_dir, warnings_out=warnings_out)
        for w in warnings_out:
            print(f"loadgen: warning: {w}")
        from perceiver_io_tpu.obs.events import merged_events

        stream = merged_events(out_dir)
        hit_rows = [e for e in stream if e.get("event") == "serve.prefix_hit"]
        # warm waves hit too (2 waves x 2 sharers) — the stream carries both
        if len(hit_rows) != fe._n_prefix_hits:
            problems.append(
                f"{len(hit_rows)} serve.prefix_hit rows, want {fe._n_prefix_hits}"
            )
        if hit_rows and not all(e.get("span_id") for e in hit_rows):
            problems.append("serve.prefix_hit rows missing span attribution")
        if hit_rows and not all(
            0 < e["pages_matched"] <= e["pages_total"] for e in hit_rows
        ):
            problems.append("serve.prefix_hit rows with impossible page counts")

        doc = build_load_doc(
            args.round or _next_round(), summary, spec, manifest=manifest,
        )
        if "engine" in doc.get("summary", {}):
            problems.append(
                "prefix doc must not carry summary.engine (the engine-gate "
                "floors are calibrated on the c32 gate model)"
            )
        self_diff = diff_load(doc, doc)
        if not (self_diff["comparable"] and self_diff["ok"]):
            problems.append("run-vs-itself load diff NOT clean: "
                            + format_load_diff(self_diff))

        if args.write_artifact:
            floor_fails = check_doc_floors(doc)
            if floor_fails:
                problems += [f"refusing to write artifact: {f}" for f in floor_fails]
            else:
                path = os.path.join(_REPO, f"LOAD_r{doc['n']:02d}.json")
                with open(path, "w") as f:
                    json.dump(doc, f, indent=1, sort_keys=True)
                    f.write("\n")
                print(f"loadgen: wrote {path}")

        problems += check_load_floors()

        if problems:
            print("loadgen: prefix gate FAILED:")
            for p in problems:
                print(f"  - {p}")
            return 1
        print(
            "loadgen: prefix OK — "
            f"hit_rate {summary['prefix']['hit_rate']} at ttft ratio "
            f"{summary['prefix']['ttft_p50_ratio']} (legs bit-exact, "
            "refcounts balanced, index drained)"
        )
        return 0
    except Exception as e:  # noqa: BLE001 — CI must see crash != verdict
        print(f"loadgen: internal error: {e}", file=sys.stderr)
        import traceback

        traceback.print_exc()
        return 3
    finally:
        if not keep:
            shutil.rmtree(out_dir, ignore_errors=True)


def run_fleet_gate(args) -> int:
    """The FLEET leg (``--fleet N``): the Fleetline certification round
    (docs/serving.md#fleet). A closed-loop run against N REAL engine
    replicas behind one ``FleetRouter`` submit surface, fully instrumented
    — flight recorder, ``/metrics`` with the labeled ``router_*`` series,
    ``/healthz`` answering from the FLEET health provider (one row per
    replica). Asserts:

    1. every request served ok fleet-wide, the fleet books identity
       closed (``Σ submitted == dispatched + re-admissions``, zero
       orphans), router audit clean, zero leaked pages on EVERY replica;
    2. the dispatch was a real fleet dispatch: every replica took a
       material share of the measured requests (>= 25% of fair share);
    3. the scrape surface answers fleet-wide: ``/healthz`` carries one
       row per replica with all dispatchable, ``/metrics`` exposes
       ``router_dispatch_total`` / ``router_outstanding``;
    4. the stream validates; the artifact body carries ``summary.fleet``
       and deliberately NOT ``summary.engine`` (the engine floors stay
       calibrated on the single-engine rounds), diffs clean against
       itself, and holds the ``fleet_throughput_tok_s`` ledger floor —
       >= 1.7x the single-engine LOAD_r02 floor.

    Single-host honesty: the N replicas interleave their decode steps on
    ONE host and ONE device here, so this round certifies the real
    routed fleet's absolute throughput and routing/accounting
    correctness — NOT parallel speedup, which one core cannot exhibit.
    The >= 1.7x replication-scaling claim itself is certified by the
    wall-clock-free discrete-event fleet gate (``tools/chaos.py
    sim_fleet``), where each replica owns an independent timeline; this
    leg's floor is beaten by amortization (one long-budget geometry,
    12 decode tokens per 8-token prompt, fewer join stalls per token)
    and the ``summary.fleet`` block records that provenance."""
    import time as _time

    from perceiver_io_tpu.obs.events import EventLog, validate_events, write_run_manifest
    from perceiver_io_tpu.obs.flightrec import FlightRecorder, SLOBounds
    from perceiver_io_tpu.obs.loadgen import (
        RequestRecord,
        WorkloadSpec,
        build_load_doc,
        diff_load,
        format_load_diff,
        summarize_load,
    )
    from perceiver_io_tpu.obs.metrics import MetricsRegistry
    from perceiver_io_tpu.obs.server import ObsServer
    from perceiver_io_tpu.serving import EngineConfig, EngineFrontEnd, FrontEndConfig
    from perceiver_io_tpu.serving.router import FleetRouter

    out_dir = args.out or tempfile.mkdtemp(prefix="loadgen_fleet_")
    keep = args.keep or args.out is not None
    problems: list = []
    try:
        n_replicas = args.fleet
        n_requests = args.requests
        # one long-budget geometry: joins amortize over 12 decode tokens
        # (vs the engine round's 6/10 mix), and a single compiled
        # (prompt, budget) pair keeps the warm wave minimal
        spec = WorkloadSpec(seed=args.seed, prompt_lens=(8,), max_new_tokens=(12,))
        engine_cfg = EngineConfig(
            slots=args.slots, page_size=8, max_ca_tokens=24, max_sa_tokens=16
        )
        concurrency = args.concurrency * n_replicas
        print(
            f"loadgen: FLEET closed-loop, {n_replicas} replicas "
            f"(slots {engine_cfg.slots} each), fleet concurrency {concurrency}, "
            f"{n_requests} requests -> {out_dir}"
        )
        model, params, config = build_workload()
        events = EventLog(out_dir, main_process=True)
        manifest = write_run_manifest(
            out_dir, model_config=config,
            extra={"workload_spec": spec.to_dict(), "engine": True,
                   "fleet": n_replicas},
            main_process=True,
        )
        recorder = FlightRecorder(
            events, out_dir=out_dir,
            slo=SLOBounds(ttft_s=args.ttft_slo, tpot_p99_s=args.tpot_slo),
        )
        registry = MetricsRegistry()
        router = FleetRouter(events=recorder, registry=registry)
        fes = {}
        for i in range(n_replicas):
            rid = f"r{i}"
            fes[rid] = EngineFrontEnd(
                model, params, num_latents=4, engine_config=engine_cfg,
                config=FrontEndConfig(snapshot_interval_s=0.25),
                events=recorder, registry=registry,
            )
            router.add_replica(rid, fes[rid])
        specs = spec.draw(n_requests, int(config.vocab_size))
        # warm THROUGH the router (not per-replica run_closed): the fleet
        # books identity counts every frontend submission against a router
        # dispatch, so a side-door warm request would unbalance it. An
        # idle fleet alternates submissions by the least-outstanding
        # tie-break, so 2 per replica lands every geometry on every one.
        warm = dataclasses_replace_indices(
            [
                WorkloadSpec(
                    seed=args.seed + 7777 + i, prompt_lens=(p,), max_new_tokens=(m,)
                ).draw(1, int(config.vocab_size))[0]
                for i, (p, m) in enumerate(
                    (p, m)
                    for p in spec.prompt_lens
                    for m in spec.max_new_tokens
                    for _ in range(2 * n_replicas)
                )
            ],
            base=1_000_000,
        )
        for w in warm:
            router.submit(w)
        router.pump()
        n_warm = len(warm)
        warm_share = {rid: fe.books()["submitted"] for rid, fe in fes.items()}
        if min(warm_share.values()) < 1:
            problems.append(f"a replica took no warm request: {warm_share}")
        # measured-window boundary (the engine-gate discipline): drop the
        # warm per-token samples and mark every per-replica odometer
        registry.histogram("generate_tpot_s").reset()
        warm_marks = {
            rid: (fe._engine_steps, fe._fill_sum) for rid, fe in fes.items()
        }
        registry.gauge("serve_parked_depth").reset_peak()
        with ObsServer(registry=registry, run_dir=out_dir, health=router.health) as server:
            t0 = _time.perf_counter()
            recs = router.run_closed(specs, concurrency=concurrency)
            duration_s = _time.perf_counter() - t0

            metrics_text = _fetch(server.url + "/metrics")
            for series in ("router_dispatch_total", "router_outstanding"):
                if series not in metrics_text:
                    problems.append(f"/metrics lacks the {series} series")
            health = json.loads(_fetch(server.url + "/healthz"))
            if health.get("n_replicas") != n_replicas:
                problems.append(f"/healthz not the fleet view: {health}")
            elif health.get("n_dispatchable") != n_replicas:
                problems.append(f"/healthz replicas not all dispatchable: {health}")

        books = router.books()
        problems += [f"fleet books: {p}" for p in router.audit()]
        for rid, fe in fes.items():
            if fe.ca_alloc.pages_used or fe.sa_alloc.pages_used:
                problems.append(
                    f"{rid} leaked pages: ca={fe.ca_alloc.pages_used} "
                    f"sa={fe.sa_alloc.pages_used}"
                )
            problems += [f"{rid} ca pages: {p}" for p in fe.ca_alloc.audit()]
            problems += [f"{rid} sa pages: {p}" for p in fe.sa_alloc.audit()]
        if books["outcomes"]["ok"] != n_requests + n_warm:
            problems.append(
                f"fleet served {books['outcomes']['ok']}/{n_requests} "
                f"(+{n_warm} warmup) ok: {books}"
            )
        if books["failovers"] != 0 or books["orphaned"] != 0:
            problems.append(f"clean run saw failovers/orphans: {books}")
        # real fleet dispatch: every replica took a material share
        measured_share = {
            rid: fes[rid].books()["submitted"] - warm_share[rid] for rid in fes
        }
        fair = n_requests / n_replicas
        for rid, share in measured_share.items():
            if share < 0.25 * fair:
                problems.append(
                    f"{rid} took {share}/{n_requests} measured requests "
                    f"(< 25% of fair share {fair:.0f}): not a fleet run"
                )

        records = [
            RequestRecord(
                index=r.index, prompt_len=r.prompt_len,
                max_new_tokens=r.max_new_tokens, batch=r.batch,
                queue_wait_s=r.queue_wait_s or 0.0,
                outcome="ok" if r.outcome == "ok" else "error",
                compiled=r.compiled, ttft_s=r.ttft_s, decode_s=r.decode_s,
                tokens_out=r.tokens_out,
            )
            for r in recs
        ]
        summary = summarize_load(
            records, duration_s, registry=registry, mode="closed",
            concurrency=concurrency,
        )
        per_replica = {}
        for rid, fe in fes.items():
            warm_steps, warm_fill = warm_marks[rid]
            steps = fe._engine_steps - warm_steps
            per_replica[rid] = {
                "dispatched": measured_share[rid],
                "decode_steps": steps,
                "batch_fill_frac": round(
                    (fe._fill_sum - warm_fill) / (steps * engine_cfg.slots), 6
                ) if steps else 0.0,
            }
        summary["fleet"] = {
            "n_replicas": n_replicas,
            "slots_per_replica": engine_cfg.slots,
            "dispatched": books["dispatched"],
            "requeued": books["requeued"],
            "failovers": books["failovers"],
            "replicas": per_replica,
            # provenance: this is a routed single-host run — the >=1.7x
            # replication-scaling claim is the DES gate's (sim_fleet)
            "drive": "interleaved_single_host",
            "scaling_certified_by": "tools/chaos.py sim_fleet",
        }
        if events is not None:
            events.emit("load.summary", **summary)
            registry.maybe_emit(events, min_interval_s=0.0)
        print(
            f"loadgen: fleet served {summary['n_requests']} requests in "
            f"{summary['duration_s']:.2f}s ({summary['throughput_tok_s']:.0f} "
            f"tok/s across {n_replicas} replicas, dispatch "
            f"{ {rid: v['dispatched'] for rid, v in sorted(per_replica.items())} })"
        )

        # --- stream validation: fleet lifecycle rows present --------------
        warnings_out: list = []
        problems += validate_events(out_dir, warnings_out=warnings_out)
        for w in warnings_out:
            print(f"loadgen: warning: {w}")
        from perceiver_io_tpu.obs.events import merged_events

        stream = merged_events(out_dir)
        joins = [e for e in stream if e.get("event") == "serve.replica"
                 and e.get("transition") == "join"]
        if len(joins) != n_replicas:
            problems.append(f"{len(joins)} serve.replica join rows, want {n_replicas}")
        req_rows = [e for e in stream if e.get("event") == "request"]
        if len(req_rows) != n_requests + n_warm:
            problems.append(
                f"{len(req_rows)} request rows, want {n_requests} + {n_warm} warmup"
            )

        doc = build_load_doc(
            args.round or _next_round(), summary, spec, manifest=manifest,
        )
        if "engine" in doc.get("summary", {}):
            problems.append(
                "fleet doc must not carry summary.engine (the engine-gate "
                "floors are calibrated on the single-engine rounds)"
            )
        self_diff = diff_load(doc, doc)
        if not (self_diff["comparable"] and self_diff["ok"]):
            problems.append("run-vs-itself load diff NOT clean: "
                            + format_load_diff(self_diff))

        if args.write_artifact:
            floor_fails = check_doc_floors(doc)
            if floor_fails:
                problems += [f"refusing to write artifact: {f}" for f in floor_fails]
            else:
                path = os.path.join(_REPO, f"LOAD_r{doc['n']:02d}.json")
                with open(path, "w") as f:
                    json.dump(doc, f, indent=1, sort_keys=True)
                    f.write("\n")
                print(f"loadgen: wrote {path}")

        problems += check_load_floors()

        if problems:
            print("loadgen: fleet gate FAILED:")
            for p in problems:
                print(f"  - {p}")
            return 1
        print(
            "loadgen: fleet OK — "
            f"{summary['throughput_tok_s']:.0f} tok/s at ok_rate "
            f"{summary['ok_rate']} across {n_replicas} replicas "
            "(fleet books balanced, dispatch shared, zero failovers)"
        )
        return 0
    except Exception as e:  # noqa: BLE001 — CI must see crash != verdict
        print(f"loadgen: internal error: {e}", file=sys.stderr)
        import traceback

        traceback.print_exc()
        return 3
    finally:
        if not keep:
            shutil.rmtree(out_dir, ignore_errors=True)


def dataclasses_replace_indices(specs, base: int):
    """Re-index warmup specs far above the measured range so they can never
    collide with measured requests in per-index surfaces (served_tokens,
    injector targeting)."""
    import dataclasses

    return [dataclasses.replace(s, index=base + i) for i, s in enumerate(specs)]


def _next_round() -> int:
    rounds = [
        int(m.group(1))
        for p in glob.glob(os.path.join(_REPO, "LOAD_r*.json"))
        if (m := _ROUND_RE.search(p))
    ]
    return max(rounds) + 1 if rounds else 1


def _load_floors() -> dict:
    from perceiver_io_tpu.analysis.ledger import load_ledger

    ledger = load_ledger(os.path.join(_REPO, "contracts")) or {}
    return {
        name: floor
        for name, floor in ledger.get("floors", {}).items()
        if str(floor.get("artifact", "")).startswith("LOAD_")
    }


def check_doc_floors(doc: dict) -> list:
    """LOAD-floor failures of ONE candidate doc (before it is committed) —
    the write-side guard; :func:`check_load_floors` is the read-side gate
    over whatever is already on disk. Floors whose ``match`` clause the
    candidate does not satisfy are another mode's certification (an
    open-loop doc is not judged by the closed-loop throughput floor) and
    are skipped."""
    from perceiver_io_tpu.analysis.ledger import _dig, doc_matches

    failures = []
    for name, floor in _load_floors().items():
        if not doc_matches(doc, floor.get("match")):
            continue
        value = _dig(doc, floor["key"])
        if not isinstance(value, (int, float)):
            failures.append(f"{name}: {floor['key']} = {value!r} missing or non-numeric")
            continue
        if "min" in floor and value < floor["min"]:
            failures.append(f"{name}: {floor['key']} = {value!r} below floor {floor['min']}")
        if "max" in floor and value > floor["max"]:
            failures.append(f"{name}: {floor['key']} = {value!r} above ceiling {floor['max']}")
    return failures


def check_load_floors() -> list:
    """The ledger-floor hook: enforce every ``contracts/ledger.json`` floor
    whose artifact pattern targets LOAD_r*.json (latest round wins — the
    same machinery as the committed-bench floors). No LOAD floors, no
    committed artifact yet -> nothing to enforce."""
    from perceiver_io_tpu.analysis.ledger import check_bench_floors

    load_floors = _load_floors()
    if not load_floors:
        return []
    return check_bench_floors({"floors": load_floors}, _REPO)


def run_diff(args) -> int:
    from perceiver_io_tpu.obs.loadgen import LOAD_METRICS, diff_load, format_load_diff

    tolerances = {}
    for spec in args.tolerance:
        if "=" not in spec:
            print(f"--tolerance wants METRIC=TOL, got {spec!r}", file=sys.stderr)
            return 3
        k, v = spec.split("=", 1)
        if k not in LOAD_METRICS:
            print(f"unknown metric {k!r} (known: {', '.join(sorted(LOAD_METRICS))})",
                  file=sys.stderr)
            return 3
        tolerances[k] = float(v)
    with open(args.diff[0]) as f:
        old = json.load(f)
    with open(args.diff[1]) as f:
        new = json.load(f)
    diff = diff_load(old, new, tolerances)
    print(format_load_diff(diff))
    if not diff["comparable"]:
        return 2
    return 0 if diff["ok"] else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--mode", choices=("closed", "open"), default="closed")
    p.add_argument("--requests", type=int, default=None,
                   help="request count (default: 200, or 24 with --smoke)")
    p.add_argument("--concurrency", type=int, default=None,
                   help="closed-loop inflight (default: 4, or 16 with --prefix)")
    p.add_argument("--rate", type=float, default=None, help="open-loop arrival rate (req/s)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--smoke", action="store_true",
                   help="CI-fast gate: 24 requests, same assertions")
    p.add_argument("--engine", action="store_true",
                   help="drive the continuous-batching paged-KV engine "
                        "(serving.engine) instead of the sequential path; "
                        "includes a planted mid-decode kill with a clean-books "
                        "audit (default 400 requests, 24 with --smoke); "
                        "combine with --mode open --rate R for the open-loop "
                        "engine rate leg (LOAD_r03 / engine_open_achieved_rps)")
    p.add_argument("--prefix", action="store_true",
                   help="drive the Shareline prefix-sharing certification "
                        "(docs/serving.md#prefix-sharing): shared-prefix "
                        "closed loop on a wide model, sharing-on vs "
                        "sharing-off legs asserted bit-exact, summary.prefix "
                        "floors (hit rate, 0.5x TTFT ratio); default 200 "
                        "requests, 24 with --smoke")
    p.add_argument("--fleet", type=int, default=None, metavar="N",
                   help="drive N real engine replicas behind one FleetRouter "
                        "(docs/serving.md#fleet): closed-loop fleet round with "
                        "the fleet books identity, per-replica dispatch-share "
                        "and router_* scrape assertions, summary.fleet "
                        "artifact body (fleet_throughput_tok_s floor); "
                        "default 240 requests, 24 with --smoke")
    p.add_argument("--slots", type=int, default=8,
                   help="engine decode slots (batched step width)")
    p.add_argument("--out", default=None, help="run dir (default: a temp dir)")
    p.add_argument("--keep", action="store_true", help="keep the run dir (implied by --out)")
    p.add_argument("--write-artifact", action="store_true",
                   help="write/refresh LOAD_r<round>.json at the repo root")
    p.add_argument("--round", type=int, default=None,
                   help="artifact round number (default: next free)")
    p.add_argument("--ttft-slo", type=float, default=30.0,
                   help="standing flight-recorder TTFT bound (s)")
    p.add_argument("--tpot-slo", type=float, default=30.0,
                   help="standing flight-recorder TPOT-p99 bound (s)")
    p.add_argument("--diff", nargs=2, metavar=("OLD", "NEW"),
                   help="diff two LOAD_r*.json artifacts instead of running")
    p.add_argument("--tolerance", action="append", default=[], metavar="METRIC=TOL")
    args = p.parse_args(argv)
    if args.diff:
        return run_diff(args)
    if not args.smoke:
        # --smoke certifies counts (books, dumps, the event stream) and runs
        # anywhere; every other run reports rates and latencies
        from perceiver_io_tpu.utils.device import require_tpu

        require_tpu("tools/loadgen.py without --smoke")
    if args.requests is None:
        args.requests = 24 if args.smoke else (
            240 if args.fleet else (400 if args.engine else 200)
        )
    if args.concurrency is None:
        # the prefix leg wants the admission queue never empty: a drain gap
        # drops the shared run's last refcount, expires the index, and the
        # next arrival republishes instead of sharing; the fleet leg
        # multiplies per-replica depth by N, so it wants the single-engine
        # saturation depth (LOAD_r02's 16) per replica
        args.concurrency = 16 if (args.prefix or args.fleet) else 4
    if args.mode == "open" and not args.rate:
        p.error("--mode open needs --rate")
    if args.fleet is not None:
        if args.fleet < 2:
            p.error("--fleet needs N >= 2 (one replica is the --engine leg)")
        if args.mode == "open" or args.prefix or args.engine:
            p.error("--fleet is its own closed-loop certification")
        return run_fleet_gate(args)
    if args.prefix:
        if args.mode == "open":
            p.error("--prefix is a closed-loop certification")
        return run_prefix_gate(args)
    if args.engine:
        return run_engine_gate(args)
    return run_gate(args)


if __name__ == "__main__":
    sys.exit(main())
