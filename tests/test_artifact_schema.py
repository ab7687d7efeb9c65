"""Committed-artifact schema pins: BENCH_*.json, contracts/*.json and
contracts/ledger.json must stay machine-readable — the re-anchor reviewer,
the bench-floor gate and graphcheck all parse them, and a malformed artifact
should fail tier-1 here instead of confusing the next round."""

import glob
import json
import os
import re

import pytest

from perceiver_io_tpu.analysis.fingerprint import PROGRAMS, validate_contract
from perceiver_io_tpu.analysis.ledger import validate_ledger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTRACTS = os.path.join(REPO, "contracts")

_ROUND_RE = re.compile(r"_r(\d+)\.json$")


def _rounds(pattern):
    out = {}
    for path in sorted(glob.glob(os.path.join(REPO, pattern))):
        m = _ROUND_RE.search(path)
        assert m, f"{os.path.basename(path)} must end in _r<round>.json"
        out[int(m.group(1))] = path
    return out


def test_bench_extra_rounds_well_formed():
    rounds = _rounds("BENCH_extra_r*.json")
    for n, path in rounds.items():
        base = os.path.basename(path)
        doc = json.load(open(path))
        assert isinstance(doc, dict) and doc, base
        for name, entry in doc.items():
            assert isinstance(entry, dict), f"{base}:{name}"
            assert isinstance(entry.get("metric"), str), f"{base}:{name}"
            assert isinstance(entry.get("value"), (int, float)), f"{base}:{name}"
            assert isinstance(entry.get("unit"), str), f"{base}:{name}"


def test_contract_files_validate_against_schema():
    paths = sorted(glob.glob(os.path.join(CONTRACTS, "*.json")))
    # ledger.json and hostlint_allow.json are contracts of a different
    # shape, schema-pinned by their own tests below
    program_files = [
        p for p in paths
        if os.path.basename(p) not in ("ledger.json", "hostlint_allow.json")
    ]
    assert program_files, "no program contracts committed under contracts/"
    seen = set()
    for path in program_files:
        base = os.path.basename(path)
        doc = json.load(open(path))
        problems = validate_contract(doc)
        assert problems == [], f"{base}: {problems}"
        stem = base[: -len(".json")]
        assert doc["program"] == stem, f"{base}: program field must match filename"
        assert stem in PROGRAMS, f"{base}: unknown program (known: {PROGRAMS})"
        assert doc["updated_reason"].strip(), f"{base}: empty updated_reason"
        seen.add(stem)
    # every flagship program is under contract — a dropped file would
    # silently shrink the gate
    assert seen == set(PROGRAMS), f"contracts cover {sorted(seen)}, want {sorted(PROGRAMS)}"


def test_ledger_validates_and_cites_existing_artifacts():
    doc = json.load(open(os.path.join(CONTRACTS, "ledger.json")))
    assert validate_ledger(doc) == []
    for name, floor in doc.get("floors", {}).items():
        assert glob.glob(os.path.join(REPO, floor["artifact"])), (
            f"floor {name} cites artifact pattern {floor['artifact']!r} with no match"
        )


def test_elastic_resume_event_kinds_pinned(tmp_path):
    """The elastic-resume vocabulary (ISSUE 10): ``resume.reshard`` and
    ``fault.ckpt_retry`` are KNOWN kinds with required-field enforcement —
    a reshard event missing its old/new mesh (or a retry event missing its
    attempt/delay) fails validation instead of silently confusing
    obs_report/obs_diff."""
    from perceiver_io_tpu.obs.events import (
        _REQUIRED_FIELDS,
        EVENT_SCHEMA_VERSION,
        KNOWN_EVENT_KINDS,
        validate_events,
    )

    assert "resume.reshard" in KNOWN_EVENT_KINDS
    assert "fault.ckpt_retry" in KNOWN_EVENT_KINDS
    assert set(_REQUIRED_FIELDS["resume.reshard"]) == {"old_mesh", "new_mesh", "step"}
    assert set(_REQUIRED_FIELDS["fault.ckpt_retry"]) == {"attempt", "delay_s"}

    def write_stream(rows):
        path = tmp_path / "events.jsonl"
        with open(path, "w") as f:
            for row in rows:
                f.write(json.dumps({"ts": 1.0, "schema_version": EVENT_SCHEMA_VERSION, **row}) + "\n")
        return str(path)

    good = write_stream(
        [
            {"event": "resume.reshard", "step": 5, "old_mesh": {"data": 2, "fsdp": 4},
             "new_mesh": {"data": 2, "fsdp": 2}, "leaves_resharded": 6, "bytes_moved": 400},
            {"event": "fault.ckpt_retry", "attempt": 0, "delay_s": 0.2, "op": "save"},
        ]
    )
    assert validate_events(good, strict_spans=False) == []
    # missing required fields fail loudly, and neither kind warns as unknown
    bad = write_stream([{"event": "resume.reshard", "step": 5}, {"event": "fault.ckpt_retry"}])
    warnings_out = []
    problems = validate_events(bad, strict_spans=False, warnings_out=warnings_out)
    assert any("old_mesh" in p for p in problems) and any("new_mesh" in p for p in problems)
    assert any("attempt" in p for p in problems) and any("delay_s" in p for p in problems)
    assert warnings_out == []


def test_serving_observability_event_kinds_pinned(tmp_path):
    """The Loadline vocabulary (ISSUE 11): ``load.summary`` and
    ``flight.dump`` are KNOWN kinds with required-field enforcement — a
    summary missing its achieved rate, or a dump event that doesn't name
    the triggering span, fails validation instead of silently confusing
    obs_report/obs_diff/the post-mortem reader. Queue-wait fields ride the
    (already-required) ``request`` rows as optional admission telemetry."""
    from perceiver_io_tpu.obs.events import (
        _REQUIRED_FIELDS,
        EVENT_SCHEMA_VERSION,
        KNOWN_EVENT_KINDS,
        validate_events,
    )

    assert "load.summary" in KNOWN_EVENT_KINDS
    assert "flight.dump" in KNOWN_EVENT_KINDS
    assert set(_REQUIRED_FIELDS["load.summary"]) == {"mode", "n_requests", "achieved_rps"}
    assert set(_REQUIRED_FIELDS["flight.dump"]) == {
        "trigger", "path", "n_events", "trigger_span_id",
    }
    # queue-wait is NOT required on request rows: only loadgen-issued
    # requests carry admission telemetry
    assert "queue_wait_s" not in _REQUIRED_FIELDS["request"]

    def write_stream(rows):
        path = tmp_path / "events.jsonl"
        with open(path, "w") as f:
            for row in rows:
                f.write(json.dumps({"ts": 1.0, "schema_version": EVENT_SCHEMA_VERSION, **row}) + "\n")
        return str(path)

    good = write_stream(
        [
            {"event": "load.summary", "mode": "closed", "n_requests": 200,
             "achieved_rps": 34.8, "throughput_tok_s": 280.9, "error_rate": 0.0},
            {"event": "flight.dump", "trigger": "slo_ttft", "path": "flight-slo_ttft-1.json",
             "n_events": 12, "trigger_span_id": "abc123", "seq": 1},
        ]
    )
    warnings_out = []
    assert validate_events(good, strict_spans=False, warnings_out=warnings_out) == []
    assert warnings_out == []  # neither kind warns as unknown
    bad = write_stream([{"event": "load.summary", "mode": "closed"},
                        {"event": "flight.dump", "trigger": "error"}])
    problems = validate_events(bad, strict_spans=False)
    assert any("achieved_rps" in p for p in problems)
    assert any("trigger_span_id" in p for p in problems)


def test_serving_hardening_event_kinds_and_outcomes_pinned(tmp_path):
    """The Shedline vocabulary (ISSUE 12): ``serve.breaker`` /
    ``serve.retry`` / ``serve.drain`` are KNOWN kinds with required-field
    enforcement, and the ``request`` outcome field is validated against the
    CLOSED taxonomy — a missing outcome fails, an unknown one only warns
    (forward compatibility), so shed/timeout accounting can never silently
    drift under older tooling."""
    from perceiver_io_tpu.obs.events import (
        _REQUIRED_FIELDS,
        EVENT_SCHEMA_VERSION,
        KNOWN_EVENT_KINDS,
        REQUEST_OUTCOMES,
        validate_events,
    )

    assert REQUEST_OUTCOMES == {"ok", "error", "timeout", "shed", "cancelled"}
    for kind in ("serve.breaker", "serve.retry", "serve.drain", "serve.preempt"):
        assert kind in KNOWN_EVENT_KINDS, kind
    assert set(_REQUIRED_FIELDS["serve.breaker"]) == {"state", "prev", "reason"}
    assert set(_REQUIRED_FIELDS["serve.retry"]) == {"attempt", "delay_s"}
    assert set(_REQUIRED_FIELDS["serve.drain"]) == {"books"}
    assert "outcome" in _REQUIRED_FIELDS["request"]  # missing outcome FAILS

    def write_stream(rows):
        path = tmp_path / "events.jsonl"
        with open(path, "w") as f:
            for row in rows:
                f.write(json.dumps({"ts": 1.0, "schema_version": EVENT_SCHEMA_VERSION, **row}) + "\n")
        return str(path)

    req = {"event": "request", "request_id": "r", "batch": 1, "prompt_len": 8,
           "ttft_s": 0.0, "tokens_out": 0}
    good = write_stream(
        [
            {"event": "serve.breaker", "state": "open", "prev": "closed",
             "reason": "error-rate", "error_rate": 0.5},
            {"event": "serve.retry", "attempt": 0, "delay_s": 0.01, "error": "x"},
            {"event": "serve.drain", "finished": 3, "books": {"balanced": True}},
            *({**req, "outcome": o} for o in sorted(REQUEST_OUTCOMES)),
        ]
    )
    warnings_out = []
    assert validate_events(good, strict_spans=False, warnings_out=warnings_out) == []
    assert warnings_out == []  # every closed-vocabulary outcome passes silently

    # unknown outcome: warning, never a problem (a newer taxonomy must not
    # fail an older gate); non-string outcome: a problem
    odd = write_stream([{**req, "outcome": "evicted"}, {**req, "outcome": 3}])
    warnings_out = []
    problems = validate_events(odd, strict_spans=False, warnings_out=warnings_out)
    assert any("not a string" in p for p in problems) and len(problems) == 1
    assert len(warnings_out) == 1 and "evicted" in warnings_out[0]

    # missing outcome / missing required serve.* fields: hard failures
    bad = write_stream([
        {k: v for k, v in {**req, "outcome": "ok"}.items() if k != "outcome"},
        {"event": "serve.breaker", "state": "open"},
        {"event": "serve.drain", "finished": 1},
    ])
    problems = validate_events(bad, strict_spans=False)
    assert any("[request]: missing field 'outcome'" in p for p in problems)
    assert any("[serve.breaker]: missing field 'prev'" in p for p in problems)
    assert any("[serve.drain]: missing field 'books'" in p for p in problems)


def test_engine_event_vocabulary_pinned(tmp_path):
    """The Pageline vocabulary (ISSUE 13): ``kv_pages_exhausted`` is a
    first-class shed reason, and ``batch_size_at_decode`` is an OPTIONAL
    request-row field — a row carrying either validates with zero problems
    and zero forward-compat warnings, and neither is required (older
    streams without them stay valid), so the engine's telemetry is
    forward-compatible by construction."""
    from perceiver_io_tpu.obs.events import (
        _REQUIRED_FIELDS,
        EVENT_SCHEMA_VERSION,
        validate_events,
    )
    from perceiver_io_tpu.serving import SHED_REASONS

    assert "kv_pages_exhausted" in SHED_REASONS
    # forward-compat: the new fields must NOT be required on request rows
    assert "batch_size_at_decode" not in _REQUIRED_FIELDS["request"]
    assert "shed_reason" not in _REQUIRED_FIELDS["request"]

    def write_stream(rows):
        path = tmp_path / "events.jsonl"
        with open(path, "w") as f:
            for row in rows:
                f.write(json.dumps({"ts": 1.0, "schema_version": EVENT_SCHEMA_VERSION, **row}) + "\n")
        return str(path)

    req = {"event": "request", "request_id": "r", "batch": 1, "prompt_len": 8,
           "ttft_s": 0.0, "tokens_out": 0}
    good = write_stream(
        [
            {**req, "outcome": "shed", "shed_reason": "kv_pages_exhausted"},
            {**req, "outcome": "ok", "tokens_out": 6, "batch_size_at_decode": 3.5,
             "queue_wait_s": 0.01},
        ]
    )
    warnings_out = []
    assert validate_events(good, strict_spans=False, warnings_out=warnings_out) == []
    assert warnings_out == []
    # rows WITHOUT the engine fields stay valid (older streams)
    old = write_stream([{**req, "outcome": "ok"}])
    assert validate_events(old, strict_spans=False) == []


def test_ledger_floor_ceilings_supported():
    """Ledger floors support ``max`` ceilings (ISSUE 13: the engine p99-TPOT
    ceiling rides one) alongside ``min`` floors; an entry with neither is
    invalid."""
    base = {"schema_version": 1, "features": {}}
    ok = {**base, "floors": {
        "f1": {"artifact": "X_r*.json", "key": "a.b", "min": 1.0},
        "f2": {"artifact": "X_r*.json", "key": "a.c", "max": 0.5},
        "f3": {"artifact": "X_r*.json", "key": "a.d", "min": 0, "max": 2},
    }}
    assert validate_ledger(ok) == []
    bad = {**base, "floors": {"f": {"artifact": "X_r*.json", "key": "a"}}}
    assert any("min and/or max" in p for p in validate_ledger(bad))
    # the committed ledger actually USES a ceiling for the engine tail
    doc = json.load(open(os.path.join(CONTRACTS, "ledger.json")))
    assert "max" in doc["floors"]["engine_tpot_p99_s"]
    assert "min" in doc["floors"]["engine_throughput_tok_s"]


def test_load_rounds_monotone_and_well_formed():
    """LOAD_r*.json — the committed serving-load artifacts (ISSUE 11):
    contiguous round numbering and the machine-read surface the load gate's
    floors and diff_load parse (keys, types, percentile blocks)."""
    rounds = _rounds("LOAD_r*.json")
    assert rounds, "no LOAD_r*.json artifacts committed"
    assert sorted(rounds) == list(range(1, max(rounds) + 1)), sorted(rounds)
    for n, path in rounds.items():
        base = os.path.basename(path)
        doc = json.load(open(path))
        assert doc.get("n") == n, f"{base}: field n={doc.get('n')} != filename round {n}"
        assert isinstance(doc.get("schema_version"), int), base
        assert doc.get("mode") in ("closed", "open"), base
        workload = doc.get("workload")
        assert isinstance(workload, dict) and isinstance(workload.get("spec"), dict), base
        assert isinstance(doc.get("manifest"), dict), base
        summary = doc.get("summary")
        assert isinstance(summary, dict), base
        for key, typ in (
            ("n_requests", int), ("achieved_rps", (int, float)),
            ("throughput_tok_s", (int, float)), ("error_rate", (int, float)),
            ("ok_rate", (int, float)), ("duration_s", (int, float)),
        ):
            assert isinstance(summary.get(key), typ), f"{base}: summary.{key}"
        for fam in ("ttft_s", "queue_wait_s"):
            block = summary.get(fam)
            assert isinstance(block, dict), f"{base}: summary.{fam}"
            for p in ("p50", "p99"):
                assert isinstance(block.get(p), (int, float)), f"{base}: summary.{fam}.{p}"
        assert isinstance(summary.get("breakdown_ms"), dict), base
        # warm-only percentiles are the committed contract — a cold-only
        # artifact has no steady state worth diffing
        assert summary.get("warm_only") is True, base


def test_smoke_fit_event_stream_validates(tmp_path):
    """The event stream a real (tiny) fit writes must pass validate_events —
    the runtime analog of the BENCH_* pins above: silent schema drift in
    events.jsonl fails tier-1 here instead of confusing obs_report/obs_diff
    (and the re-anchor reviewer) a round later."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.models.text import CausalLanguageModel, CausalLanguageModelConfig
    from perceiver_io_tpu.obs.events import EVENT_SCHEMA_VERSION, merged_events, validate_events
    from perceiver_io_tpu.training import (
        MetricsLogger,
        TrainState,
        Trainer,
        TrainerConfig,
        clm_loss_fn,
        make_optimizer,
    )

    config = CausalLanguageModelConfig(
        vocab_size=50, max_seq_len=24, max_latents=8, num_channels=32,
        num_heads=4, num_self_attention_layers=2, cross_attention_dropout=0.5,
    )
    model = CausalLanguageModel(config)
    t = np.random.default_rng(0).integers(0, 50, size=(4, config.max_seq_len + 1))
    batch = {"labels": jnp.asarray(t[:, 1:]), "input_ids": jnp.asarray(t[:, :-1]),
             "pad_mask": None}
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"], prefix_len=16)
    state = TrainState.create(model.apply, params, make_optimizer(1e-3), jax.random.PRNGKey(1))
    logger = MetricsLogger(str(tmp_path), use_tensorboard=False)
    trainer = Trainer(
        clm_loss_fn(model.apply, max_latents=config.max_latents),
        logger=logger,
        config=TrainerConfig(max_steps=3, log_interval=2, prefetch_batches=0),
    )
    trainer.fit(state, iter([batch] * 3), model_config=config)
    trainer.close()
    logger.close()

    assert validate_events(str(tmp_path)) == [], "smoke-fit event stream drifted"
    events = merged_events(str(tmp_path))
    assert all(e["schema_version"] == EVENT_SCHEMA_VERSION for e in events)
    kinds = {e["event"] for e in events}
    assert {"fit_start", "log", "compile", "span", "fit_end"} <= kinds


def test_speculative_event_fields_and_artifacts_pinned(tmp_path):
    """The Specline vocabulary (ISSUE 14): ``acceptance_rate`` and
    ``tokens_per_step`` are OPTIONAL request-row fields VALIDATED when
    present (numeric — a malformed value is a problem, absence is not:
    mirroring ``batch_size_at_decode``), the ``speculative`` feature stands
    measured in the ledger with its tokens-per-step floor, and the
    committed BENCH_extra round's ``decode_spec`` entry records a
    serial-step multiple above 1.0 (the acceptance criterion)."""
    from perceiver_io_tpu.analysis.ledger import feature_state, load_ledger
    from perceiver_io_tpu.obs.events import (
        _OPTIONAL_FIELD_TYPES,
        _REQUIRED_FIELDS,
        EVENT_SCHEMA_VERSION,
        validate_events,
    )

    for field in ("acceptance_rate", "tokens_per_step", "batch_size_at_decode"):
        assert field in _OPTIONAL_FIELD_TYPES["request"], field
        assert field not in _REQUIRED_FIELDS["request"], field

    def write_stream(rows):
        path = tmp_path / "events.jsonl"
        with open(path, "w") as f:
            for row in rows:
                f.write(json.dumps({"ts": 1.0, "schema_version": EVENT_SCHEMA_VERSION, **row}) + "\n")
        return str(path)

    req = {"event": "request", "request_id": "r", "batch": 1, "prompt_len": 8,
           "ttft_s": 0.0, "tokens_out": 6, "outcome": "ok"}
    good = write_stream(
        [
            {**req, "acceptance_rate": 0.45, "tokens_per_step": 2.2},
            req,  # rows WITHOUT the fields stay valid (older streams)
        ]
    )
    warnings_out = []
    assert validate_events(good, strict_spans=False, warnings_out=warnings_out) == []
    assert warnings_out == []
    bad = write_stream([{**req, "acceptance_rate": "high", "tokens_per_step": None}])
    problems = validate_events(bad, strict_spans=False)
    assert any("acceptance_rate" in p for p in problems), problems
    assert any("tokens_per_step" in p for p in problems), problems
    # bool is an int subclass — it must NOT pass the numeric check
    booly = write_stream([{**req, "acceptance_rate": True, "tokens_per_step": False}])
    problems = validate_events(booly, strict_spans=False)
    assert any("acceptance_rate" in p for p in problems), problems
    assert any("tokens_per_step" in p for p in problems), problems

    ledger = load_ledger(CONTRACTS)
    assert feature_state(ledger, "speculative") == "measured"
    assert "spec_tokens_per_step" in ledger["floors"]

    rounds = _rounds("BENCH_extra_r*.json")
    latest = json.load(open(rounds[max(rounds)]))
    spec = latest["decode_spec"]
    assert spec["tokens_per_step"] > 1.0, spec
    assert 0.0 <= spec["acceptance_rate"] <= 1.0, spec
    assert spec.get("token_exact") is True, spec


def test_evictline_event_vocabulary_pinned(tmp_path):
    """The Evictline vocabulary (ISSUE 15): ``serve.evict`` /
    ``serve.resume`` / ``serve.recover`` are KNOWN kinds with
    required-field enforcement, kept DISTINCT from ``serve.preempt`` (the
    SIGTERM/drain signal — whole-process wind-down; the three new kinds are
    per-REQUEST preemption: page-evicted, replay-resumed, journal-
    recovered), and the engine leg's eviction telemetry on ``load.summary``
    (``evictions`` / ``resumes`` / ``parked_depth_peak``) is OPTIONAL and
    numeric-validated when present — missing fields on the new kinds fail
    hard, an unknown sibling kind only warns (forward compatibility)."""
    from perceiver_io_tpu.obs.events import (
        _OPTIONAL_FIELD_TYPES,
        _REQUIRED_FIELDS,
        EVENT_SCHEMA_VERSION,
        KNOWN_EVENT_KINDS,
        validate_events,
    )

    # the whole preemption vocabulary, pinned as a SET so the two meanings
    # (process drain vs per-request eviction) can't blur: serve.preempt
    # stays a known kind with NO required fields (it predates the table),
    # the three Evictline kinds carry their consumed schemas
    for kind in ("serve.preempt", "serve.evict", "serve.resume", "serve.recover"):
        assert kind in KNOWN_EVENT_KINDS, kind
    assert "serve.preempt" not in _REQUIRED_FIELDS  # the drain signal, unchanged
    assert set(_REQUIRED_FIELDS["serve.evict"]) == {
        "request_index", "tokens_out", "pages_freed"
    }
    assert set(_REQUIRED_FIELDS["serve.resume"]) == {"request_index", "tokens_out"}
    assert set(_REQUIRED_FIELDS["serve.recover"]) == {"request_index", "tokens_resumed"}
    for field in ("evictions", "resumes", "parked_depth_peak"):
        assert field in _OPTIONAL_FIELD_TYPES["load.summary"], field
        assert field not in _REQUIRED_FIELDS["load.summary"], field

    def write_stream(rows):
        path = tmp_path / "events.jsonl"
        with open(path, "w") as f:
            for row in rows:
                f.write(json.dumps({"ts": 1.0, "schema_version": EVENT_SCHEMA_VERSION, **row}) + "\n")
        return str(path)

    summary = {"event": "load.summary", "mode": "closed", "n_requests": 8,
               "achieved_rps": 100.0}
    good = write_stream(
        [
            {"event": "serve.evict", "request_index": 3, "tokens_out": 2,
             "pages_freed": 3},
            {"event": "serve.resume", "request_index": 3, "tokens_out": 2},
            {"event": "serve.recover", "request_index": 3, "tokens_resumed": 2},
            {**summary, "evictions": 6, "resumes": 6, "parked_depth_peak": 2},
            summary,  # pre-Evictline summaries (no counters) stay valid
        ]
    )
    warnings_out = []
    assert validate_events(good, strict_spans=False, warnings_out=warnings_out) == []
    assert warnings_out == []

    # missing required fields on the new kinds: hard failures
    bad = write_stream([
        {"event": "serve.evict", "request_index": 3},
        {"event": "serve.resume", "tokens_out": 2},
        {"event": "serve.recover", "request_index": 3},
    ])
    problems = validate_events(bad, strict_spans=False)
    assert any("[serve.evict]: missing field 'tokens_out'" in p for p in problems)
    assert any("[serve.evict]: missing field 'pages_freed'" in p for p in problems)
    assert any("[serve.resume]: missing field 'request_index'" in p for p in problems)
    assert any("[serve.recover]: missing field 'tokens_resumed'" in p for p in problems)

    # malformed optional counters: problems; an unknown sibling kind from a
    # NEWER library: a warning, never a problem (forward compatibility)
    odd = write_stream([
        {**summary, "evictions": "many", "parked_depth_peak": True},
        {"event": "serve.evict2", "request_index": 1},
    ])
    warnings_out = []
    problems = validate_events(odd, strict_spans=False, warnings_out=warnings_out)
    assert any("evictions" in p for p in problems), problems
    assert any("parked_depth_peak" in p for p in problems), problems
    assert not any("serve.evict2" in p for p in problems), problems
    assert len(warnings_out) == 1 and "serve.evict2" in warnings_out[0]


def test_sim_event_vocabulary_and_tenant_pinned(tmp_path):
    """The Simline vocabulary (ISSUE 16): ``sim.summary`` is a KNOWN kind
    with required-field enforcement, and ``tenant`` is an OPTIONAL
    string-typed field on request rows and the per-request preemption
    audit trail (serve.evict/serve.resume/serve.recover) — absent it stays
    valid (single-tenant streams), present-but-non-string fails loudly."""
    from perceiver_io_tpu.obs.events import (
        _OPTIONAL_FIELD_TYPES,
        _REQUIRED_FIELDS,
        EVENT_SCHEMA_VERSION,
        KNOWN_EVENT_KINDS,
        validate_events,
    )

    assert "sim.summary" in KNOWN_EVENT_KINDS
    assert set(_REQUIRED_FIELDS["sim.summary"]) == {
        "n_requests", "n_tenants", "offered_rps", "achieved_rps",
        "fairness_jain", "max_starvation_age_s",
    }
    # forward-compat: tenant is never required, and is type-pinned to str
    assert "tenant" not in _REQUIRED_FIELDS["request"]
    for kind in ("request", "serve.evict", "serve.resume", "serve.recover"):
        assert _OPTIONAL_FIELD_TYPES[kind]["tenant"] == (str,), kind

    def write_stream(rows):
        path = tmp_path / "events.jsonl"
        with open(path, "w") as f:
            for row in rows:
                f.write(json.dumps({"ts": 1.0, "schema_version": EVENT_SCHEMA_VERSION, **row}) + "\n")
        return str(path)

    req = {"event": "request", "request_id": "r", "batch": 1, "prompt_len": 8,
           "ttft_s": 0.0, "tokens_out": 4, "outcome": "ok"}
    good = write_stream(
        [
            {"event": "sim.summary", "n_requests": 12000, "n_tenants": 3,
             "offered_rps": 10000.0, "achieved_rps": 1428.1,
             "fairness_jain": 0.9978, "max_starvation_age_s": 0.2,
             "shed_rate": 0.83, "books_balanced": True},
            {**req, "tenant": "acme"},
            req,  # tenant-free rows stay valid (older / single-tenant streams)
            {"event": "serve.evict", "request_index": 4, "tokens_out": 2,
             "pages_freed": 3, "tenant": "acme"},
            {"event": "serve.resume", "request_index": 4, "tokens_out": 2,
             "tenant": "acme"},
        ]
    )
    warnings_out = []
    assert validate_events(good, strict_spans=False, warnings_out=warnings_out) == []
    assert warnings_out == []  # sim.summary never warns as unknown
    bad = write_stream([
        {"event": "sim.summary", "n_requests": 10},
        {**req, "tenant": 7},
    ])
    problems = validate_events(bad, strict_spans=False)
    assert any("fairness_jain" in p for p in problems)
    assert any("tenant" in p and "string" in p for p in problems)


def test_shareline_event_vocabulary_pinned(tmp_path):
    """The Shareline vocabulary (ISSUE 17): ``serve.prefix_hit`` is a KNOWN
    kind requiring ``request_index`` / ``pages_matched`` / ``pages_total``
    (the hit's shape — what fraction of the prompt came for free), with
    ``tenant`` and ``tokens_skipped`` optional-and-typed, and the prefix leg
    of ``load.summary`` rides an optional ``prefix`` dict — pre-Shareline
    streams stay valid, missing required fields fail hard."""
    from perceiver_io_tpu.obs.events import (
        _OPTIONAL_FIELD_TYPES,
        _REQUIRED_FIELDS,
        EVENT_SCHEMA_VERSION,
        KNOWN_EVENT_KINDS,
        validate_events,
    )

    assert "serve.prefix_hit" in KNOWN_EVENT_KINDS
    assert set(_REQUIRED_FIELDS["serve.prefix_hit"]) == {
        "request_index", "pages_matched", "pages_total"
    }
    assert _OPTIONAL_FIELD_TYPES["serve.prefix_hit"]["tenant"] == (str,)
    assert "tokens_skipped" in _OPTIONAL_FIELD_TYPES["serve.prefix_hit"]
    assert "tokens_skipped" not in _REQUIRED_FIELDS["serve.prefix_hit"]
    assert _OPTIONAL_FIELD_TYPES["load.summary"]["prefix"] == (dict,)
    assert "prefix" not in _REQUIRED_FIELDS["load.summary"]

    def write_stream(rows):
        path = tmp_path / "events.jsonl"
        with open(path, "w") as f:
            for row in rows:
                f.write(json.dumps({"ts": 1.0, "schema_version": EVENT_SCHEMA_VERSION, **row}) + "\n")
        return str(path)

    summary = {"event": "load.summary", "mode": "closed", "n_requests": 200,
               "achieved_rps": 100.0}
    good = write_stream(
        [
            {"event": "serve.prefix_hit", "request_index": 7,
             "pages_matched": 55, "pages_total": 56,
             "tokens_skipped": 440, "tenant": "acme"},
            {"event": "serve.prefix_hit", "request_index": 8,
             "pages_matched": 1, "pages_total": 2},  # bare hit stays valid
            {**summary, "prefix": {"hit_rate": 0.995, "ttft_p50_ratio": 0.38}},
            summary,  # pre-Shareline summaries (no prefix block) stay valid
        ]
    )
    warnings_out = []
    assert validate_events(good, strict_spans=False, warnings_out=warnings_out) == []
    assert warnings_out == []
    bad = write_stream([
        {"event": "serve.prefix_hit", "request_index": 7},
        {"event": "serve.prefix_hit", "pages_matched": 1, "pages_total": 2,
         "tenant": 9},
        {**summary, "prefix": 0.995},
    ])
    problems = validate_events(bad, strict_spans=False)
    assert any("[serve.prefix_hit]: missing field 'pages_matched'" in p for p in problems)
    assert any("[serve.prefix_hit]: missing field 'pages_total'" in p for p in problems)
    assert any("[serve.prefix_hit]: missing field 'request_index'" in p for p in problems)
    assert any("tenant" in p for p in problems), problems
    assert any("prefix" in p for p in problems), problems


def test_fleet_event_vocabulary_pinned(tmp_path):
    """The Fleetline vocabulary (ISSUE 20): ``serve.replica`` (replica
    lifecycle transitions on the fleet router) and ``serve.failover`` (a
    dead replica's journal replayed onto a survivor) are KNOWN kinds with
    required-field enforcement — the failover row carries the replay
    accounting the post-mortem reads (``n_replayed`` required; the parked/
    queued/already-complete/shed split and the dead journal's path optional
    and type-pinned). Minimal transition rows stay valid (``reason`` and
    ``outstanding`` are optional), missing required fields fail hard."""
    from perceiver_io_tpu.obs.events import (
        _OPTIONAL_FIELD_TYPES,
        _REQUIRED_FIELDS,
        EVENT_SCHEMA_VERSION,
        KNOWN_EVENT_KINDS,
        validate_events,
    )

    for kind in ("serve.replica", "serve.failover"):
        assert kind in KNOWN_EVENT_KINDS, kind
    assert set(_REQUIRED_FIELDS["serve.replica"]) == {"replica_id", "transition"}
    assert set(_REQUIRED_FIELDS["serve.failover"]) == {
        "dead_replica", "survivor", "n_replayed"
    }
    assert _OPTIONAL_FIELD_TYPES["serve.replica"]["reason"] == (str,)
    assert "outstanding" in _OPTIONAL_FIELD_TYPES["serve.replica"]
    for field in ("n_parked", "n_queued", "n_already_complete", "n_shed"):
        assert field in _OPTIONAL_FIELD_TYPES["serve.failover"], field
        assert field not in _REQUIRED_FIELDS["serve.failover"], field
    assert _OPTIONAL_FIELD_TYPES["serve.failover"]["journal"] == (str,)

    def write_stream(rows):
        path = tmp_path / "events.jsonl"
        with open(path, "w") as f:
            for row in rows:
                f.write(json.dumps({"ts": 1.0, "schema_version": EVENT_SCHEMA_VERSION, **row}) + "\n")
        return str(path)

    good = write_stream(
        [
            {"event": "serve.replica", "replica_id": "r0", "transition": "join"},
            {"event": "serve.replica", "replica_id": "r0", "transition": "dead",
             "reason": "heartbeat_timeout", "outstanding": 3},
            {"event": "serve.failover", "dead_replica": "r0", "survivor": "r1",
             "n_replayed": 5, "n_parked": 2, "n_queued": 3,
             "n_already_complete": 0, "n_shed": 0,
             "journal": "runs/journal-r0.jsonl"},
            # a minimal failover row (no optional accounting) stays valid
            {"event": "serve.failover", "dead_replica": "r0", "survivor": "r1",
             "n_replayed": 0},
        ]
    )
    warnings_out = []
    assert validate_events(good, strict_spans=False, warnings_out=warnings_out) == []
    assert warnings_out == []

    # missing required fields: hard failures; malformed optionals: problems
    bad = write_stream([
        {"event": "serve.replica", "replica_id": "r0"},
        {"event": "serve.replica", "transition": "join", "reason": 7},
        {"event": "serve.failover", "dead_replica": "r0", "survivor": "r1"},
        {"event": "serve.failover", "dead_replica": "r0", "survivor": "r1",
         "n_replayed": 5, "n_parked": "two", "journal": 9},
    ])
    problems = validate_events(bad, strict_spans=False)
    assert any("[serve.replica]: missing field 'transition'" in p for p in problems)
    assert any("[serve.replica]: missing field 'replica_id'" in p for p in problems)
    assert any("[serve.failover]: missing field 'n_replayed'" in p for p in problems)
    assert any("reason" in p for p in problems), problems
    assert any("n_parked" in p for p in problems), problems
    assert any("journal" in p for p in problems), problems


def test_sim_rounds_monotone_and_well_formed():
    """SIM_r*.json — the committed discrete-event certification artifacts
    (ISSUE 16): contiguous round numbering and the machine-read surface
    the sim gate's floors and diff_sim parse (comparability identity:
    tenants + service-model fit + engine geometry; summary: fairness,
    starvation, per-tenant blocks, balanced books)."""
    rounds = _rounds("SIM_r*.json")
    assert rounds, "no SIM_r*.json artifacts committed"
    assert sorted(rounds) == list(range(1, max(rounds) + 1)), sorted(rounds)
    for n, path in rounds.items():
        base = os.path.basename(path)
        doc = json.load(open(path))
        assert doc.get("n") == n, f"{base}: field n={doc.get('n')} != filename round {n}"
        assert isinstance(doc.get("schema_version"), int), base
        assert doc.get("mode") == "sim", base
        workload = doc.get("workload")
        assert isinstance(workload, dict), base
        tenants = workload.get("tenants")
        assert isinstance(tenants, list) and len(tenants) >= 1, base
        for t in tenants:
            assert isinstance(t.get("name"), str), f"{base}: tenant name"
            assert isinstance(t.get("rate_rps"), (int, float)), f"{base}: tenant rate"
        model = doc.get("service_model")
        assert isinstance(model, dict) and isinstance(model.get("source"), str), base
        for key in ("prefill_p50_s", "prefill_p99_s", "tpot_p50_s", "tpot_p99_s"):
            assert isinstance(model.get(key), (int, float)), f"{base}: service_model.{key}"
        assert isinstance(doc.get("engine_config"), dict), base
        # no device manifest BY DESIGN: a sim run never touches a device
        assert "manifest" not in doc, base
        summary = doc.get("summary")
        assert isinstance(summary, dict), base
        for key, typ in (
            ("n_requests", int), ("n_tenants", int),
            ("offered_rps", (int, float)), ("achieved_rps", (int, float)),
            ("fairness_jain", (int, float)),
            ("max_starvation_age_s", (int, float)),
            ("shed_rate", (int, float)), ("error_rate", (int, float)),
            ("duration_s", (int, float)), ("tenants", dict),
        ):
            assert isinstance(summary.get(key), typ), f"{base}: summary.{key}"
        assert summary.get("books_balanced") is True, base
        assert set(summary["tenants"]) == {t["name"] for t in tenants}, base
        for name, block in summary["tenants"].items():
            for key in ("offered_rps", "achieved_rps", "n_requests", "ok", "shed"):
                assert isinstance(block.get(key), (int, float)), (
                    f"{base}: tenants.{name}.{key}"
                )
        for fam in ("ttft_s", "queue_wait_s"):
            block = summary.get(fam)
            assert isinstance(block, dict), f"{base}: summary.{fam}"
            for p in ("p50", "p99"):
                assert isinstance(block.get(p), (int, float)), f"{base}: summary.{fam}.{p}"


def test_hostlint_allowlist_schema_pinned():
    """contracts/hostlint_allow.json: every suppression carries a unique
    pattern and a non-empty reason — an unexplained allowlist entry is
    indistinguishable from a weakened rule, and load_allowlist refuses it."""
    from perceiver_io_tpu.analysis.hostrules import load_allowlist

    path = os.path.join(REPO, "contracts", "hostlint_allow.json")
    doc = json.load(open(path))
    assert isinstance(doc.get("entries"), list) and doc["entries"]
    patterns, entries = load_allowlist(path)
    assert len(patterns) == len(set(patterns)), "duplicate allowlist patterns"
    for e in entries:
        assert isinstance(e["pattern"], str) and e["pattern"]
        assert isinstance(e["reason"], str) and e["reason"].strip()
        # patterns target a registered rule, not a glob over everything
        rule = e["pattern"].split(":", 1)[0]
        from perceiver_io_tpu.analysis.hostrules import HOST_RULES

        assert rule in HOST_RULES, f"{e['pattern']!r} names no registered rule"


def test_hostlint_allowlist_rejects_unreasoned_entries(tmp_path):
    from perceiver_io_tpu.analysis.hostrules import load_allowlist

    p = tmp_path / "allow.json"
    p.write_text(json.dumps({"entries": [{"pattern": "event-schema:*"}]}))
    with pytest.raises(ValueError, match="no reason"):
        load_allowlist(str(p))
    p.write_text(json.dumps({"entries": [{"pattern": "event-schema:*",
                                          "reason": "   "}]}))
    with pytest.raises(ValueError, match="no reason"):
        load_allowlist(str(p))
    p.write_text(json.dumps({"entries": [{"reason": "orphaned"}]}))
    with pytest.raises(ValueError, match="no pattern"):
        load_allowlist(str(p))
