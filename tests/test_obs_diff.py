"""Runtime-regression differ (tools/obs_diff.py) + SLO aggregation
(obs/slo.py) + obs_report Spanline sections.

Acceptance pins (ISSUE 8): obs_diff flags a planted runtime regression
(degraded step p99 / goodput) as `regression`, passes run-vs-itself clean,
and exits stale/not-comparable — NOT regression — on a mesh-mismatched
pair; the SLO report's TPOT percentiles come from merged per-request
histograms. Synthetic run directories are written directly (manifest +
events.jsonl), the same seam the graphcheck tests use to plant regressions.
"""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses resolve cls.__module__ through here
    spec.loader.exec_module(mod)
    return mod


obs_diff = load_tool("obs_diff")


# ------------------------------------------------------------ run builders


def write_run(
    run_dir,
    mesh=None,
    step_ms=10.0,
    step_p99_ms=None,
    mfu=0.4,
    goodput=0.95,
    tpot_s=0.01,
    ttft_s=0.5,
    n_steps=12,
    n_requests=6,
    jax_version="0.4.37",
):
    """A synthetic but schema-valid run directory: manifest + log rows +
    step spans + request rows (with real log-bucket histograms)."""
    from perceiver_io_tpu.obs.events import EventLog, write_run_manifest
    from perceiver_io_tpu.obs.metrics import Histogram
    from perceiver_io_tpu.obs.trace import Tracer

    os.makedirs(str(run_dir), exist_ok=True)
    manifest = {
        "created_at": "2026-08-03T00:00:00",
        "jax_version": jax_version,
        "backend": "cpu",
        "device_kind": "cpu",
        "device_count": 1,
        "local_device_count": 1,
        "process_index": 0,
        "process_count": 1,
        "mesh": mesh,
        "config_hash": "abcabcabcabc",
        "model_config": {"vocab_size": 64, "max_seq_len": 24},
        "trainer_config": None,
    }
    with open(os.path.join(str(run_dir), "run_manifest.json"), "w") as f:
        json.dump(manifest, f)
    events = EventLog(str(run_dir), main_process=True)
    tracer = Tracer(events)
    events.emit("fit_start", start_step=0, max_steps=n_steps)
    p99 = step_p99_ms if step_p99_ms is not None else step_ms
    for i in range(n_steps):
        with tracer.span("step", step=i + 1) as sp:
            pass
        # overwrite the measured duration with the planted one (the row is
        # built from the closed span at the flush) — the differ reads
        # dur_ms, not wall time
        sp._dur_s = (p99 if i == n_steps - 1 else step_ms) / 1e3
    tracer.flush()
    for i in range(2):
        events.emit(
            "log", step=(i + 1) * n_steps // 2, mfu=mfu, goodput=goodput,
            tokens_per_sec=1000.0, steps_per_sec=1.0 / step_ms * 1e3, input_wait_ms=0.1,
        )
    for i in range(n_requests):
        hist = Histogram("tpot_s")
        for _ in range(20):
            hist.record(tpot_s)
        events.emit(
            "request", request_id=f"req{i}", batch=2, prompt_len=12, new_tokens=21,
            tokens_out=21, outcome="ok", compiled=(i == 0), ttft_s=ttft_s,
            decode_s=tpot_s * 20, per_token_s=tpot_s, tokens_per_sec=100.0,
            tpot_p50_s=hist.percentile(50), tpot_p90_s=hist.percentile(90),
            tpot_p99_s=hist.percentile(99),
            tpot_hist={str(k): v for k, v in hist.counts.items()},
        )
    events.emit("fit_end", step=n_steps, aborted=False)
    return str(run_dir)


# ------------------------------------------------------------------- diffs


def test_run_vs_itself_is_clean(tmp_path):
    run = write_run(tmp_path / "a")
    s = obs_diff.summarize_run(run)
    assert s["metrics"]["mfu"] == pytest.approx(0.4)
    assert s["metrics"]["step_ms_p50"] == pytest.approx(10.0)
    assert "ttft_s_p50" in s["metrics"] and "tpot_s_p99" in s["metrics"]
    diff = obs_diff.diff_runs(s, s)
    assert diff.comparable and diff.ok()
    assert diff.regressions == [] and diff.improvements == []
    assert obs_diff.main([run, run]) == 0


def test_planted_runtime_regression_flags_regression(tmp_path):
    """Acceptance: degraded step p99 + goodput + TPOT in the candidate run
    classify as regression (exit 1); the mirror image as improvement."""
    base = write_run(tmp_path / "base")
    bad = write_run(
        tmp_path / "bad",
        step_ms=10.0, step_p99_ms=40.0,  # tail blowup, median intact
        goodput=0.70, tpot_s=0.02,
    )
    diff = obs_diff.diff_runs(
        obs_diff.summarize_run(base), obs_diff.summarize_run(bad)
    )
    assert diff.comparable and not diff.ok()
    regressed = {d.metric for d in diff.regressions}
    assert "step_ms_p99" in regressed
    assert "goodput" in regressed
    assert "tpot_s_p50" in regressed and "tpot_s_p99" in regressed
    assert "step_ms_p50" not in regressed  # median unchanged: not dragged in
    assert obs_diff.main([base, str(tmp_path / "bad")]) == 1
    # the mirror direction is an improvement, exit 0
    diff_up = obs_diff.diff_runs(
        obs_diff.summarize_run(str(tmp_path / "bad")), obs_diff.summarize_run(base)
    )
    assert diff_up.ok()
    assert {d.metric for d in diff_up.improvements} >= {"goodput", "step_ms_p99"}


def test_mesh_mismatch_is_not_comparable_not_regression(tmp_path):
    """Acceptance: a mesh/geometry/jax mismatch exits stale (2), never 1 —
    the diff_fingerprints discipline."""
    flat = write_run(tmp_path / "flat")
    # same run otherwise MUCH slower — but meshes differ, so NOT a regression
    meshed = write_run(
        tmp_path / "meshed", mesh={"data": 2, "fsdp": 4}, step_ms=99.0, goodput=0.2
    )
    diff = obs_diff.diff_runs(
        obs_diff.summarize_run(flat), obs_diff.summarize_run(meshed)
    )
    assert not diff.comparable and "mesh" in diff.reason
    assert diff.deltas == []  # refused, not classified
    assert obs_diff.main([flat, meshed]) == 2
    assert "NOT COMPARABLE" in diff.format()
    # jax-version drift is refused the same way
    jaxed = write_run(tmp_path / "jaxed", jax_version="0.5.0")
    assert obs_diff.main([flat, jaxed]) == 2


def test_tolerance_overrides_and_low_n_neutrality(tmp_path):
    base = write_run(tmp_path / "a2")
    slightly = write_run(tmp_path / "b2", mfu=0.39)  # -2.5%: inside 5% tol
    d1 = obs_diff.diff_runs(
        obs_diff.summarize_run(base), obs_diff.summarize_run(slightly)
    )
    assert d1.ok()
    d2 = obs_diff.diff_runs(
        obs_diff.summarize_run(base), obs_diff.summarize_run(slightly),
        tolerances={"mfu": 0.01},
    )
    assert {d.metric for d in d2.regressions} == {"mfu"}
    # low_n percentile families classify neutral, annotated
    tiny = write_run(tmp_path / "tiny", n_steps=3)
    tiny_worse = write_run(tmp_path / "tiny_worse", n_steps=3, step_ms=50.0)
    d3 = obs_diff.diff_runs(
        obs_diff.summarize_run(tiny), obs_diff.summarize_run(tiny_worse)
    )
    step_deltas = {d.metric: d for d in d3.deltas if d.metric.startswith("step_ms")}
    assert step_deltas and all(d.kind == "neutral" for d in step_deltas.values())
    assert all("low_n" in d.detail for d in step_deltas.values())


def test_summarize_run_excludes_compile_contaminated_step_spans(tmp_path):
    """A step span that absorbed a compile (or graphlint) pass is wall-clock
    dominated by it — the differ must summarize WARM steps only, or the
    p99 gate compares compiler variance (code-review finding)."""
    run = write_run(tmp_path / "warm", step_ms=10.0)
    # the first-step pattern: a compile + graphlint event stamped with a
    # step span's id, that span's duration being ~the compile wall
    with open(os.path.join(run, "events.jsonl"), "a") as f:
        for sid, kind, extra in (
            ("cold1", "compile", {"fn": "train_step", "wall_s": 2.0, "n_compiles": 1}),
            ("cold2", "graphlint", {"ok": True}),
        ):
            f.write(json.dumps({
                "ts": 1.0, "event": "span", "schema_version": 1, "name": "step",
                "span_id": sid, "parent_id": None, "t_start": 0.0, "t_end": 3.0,
                "dur_ms": 3000.0, "process_index": 0, "attrs": {},
            }) + "\n")
            f.write(json.dumps({
                "ts": 1.0, "event": kind, "schema_version": 1, "span_id": sid, **extra,
            }) + "\n")
    s = obs_diff.summarize_run(run)
    assert s["metrics"]["step_ms_p99"] == pytest.approx(10.0)  # compile spans out
    assert s["metrics"]["step_ms_p50"] == pytest.approx(10.0)


def test_missing_telemetry_is_not_comparable(tmp_path):
    run = write_run(tmp_path / "full")
    empty = tmp_path / "empty"
    os.makedirs(str(empty))
    # no manifest at all
    assert obs_diff.main([run, str(empty)]) == 2
    # manifest but no events
    import shutil

    shutil.copy(
        os.path.join(run, "run_manifest.json"),
        os.path.join(str(empty), "run_manifest.json"),
    )
    diff = obs_diff.diff_runs(
        obs_diff.summarize_run(run), obs_diff.summarize_run(str(empty))
    )
    assert not diff.comparable and "no runtime metrics" in diff.reason


# --------------------------------------------------------------------- slo


def test_slo_report_merges_request_histograms(tmp_path):
    from perceiver_io_tpu.obs.events import merged_events
    from perceiver_io_tpu.obs.slo import build_slo_report, write_slo_report

    run = write_run(tmp_path / "slo", n_requests=5, tpot_s=0.01, ttft_s=0.25)
    report = build_slo_report(merged_events(run))
    assert report["n_requests"] == 5
    assert report["outcomes"] == {"ok": 5}
    assert report["error_rate"] == 0.0
    # warm-only: the compiled first request is excluded from latency pools
    assert report["warm_only"] is True and report["n_latency_requests"] == 4
    assert report["ttft_s"]["p50"] == pytest.approx(0.25)
    assert report["ttft_s"]["low_n"] is True  # 4 warm requests < 5
    # TPOT from MERGED histograms: 4 warm requests x 20 tokens
    assert report["tpot_s"]["n"] == 80
    assert report["tpot_s"]["p50"] == pytest.approx(0.01, rel=0.25)
    assert report["tokens_out"] == 5 * 21 * 2  # requests x tokens x batch
    # the artifact lands next to events.jsonl
    on_disk = write_slo_report(run)
    assert on_disk == json.load(open(os.path.join(run, "slo_report.json")))
    # a run with no requests: no report, nothing written
    from perceiver_io_tpu.obs.events import EventLog

    bare = str(tmp_path / "bare")
    EventLog(bare, main_process=True).emit("fit_start", start_step=0, max_steps=1)
    assert write_slo_report(bare) is None
    assert not os.path.exists(os.path.join(bare, "slo_report.json"))


def test_slo_report_counts_errors():
    from perceiver_io_tpu.obs.slo import build_slo_report

    events = [
        {"event": "request", "outcome": "ok", "batch": 1, "prompt_len": 4,
         "tokens_out": 8, "ttft_s": 0.1, "tokens_per_sec": 50.0,
         "tpot_hist": {"-27": 8}, "compiled": False},
        {"event": "request", "outcome": "error", "batch": 1, "prompt_len": 4,
         "tokens_out": 2, "ttft_s": 0.1, "tokens_per_sec": 10.0,
         "tpot_hist": {"-27": 2}, "compiled": False},
    ]
    report = build_slo_report(events)
    assert report["outcomes"] == {"ok": 1, "error": 1}
    assert report["error_rate"] == 0.5
    assert report["n_latency_requests"] == 1  # errors excluded from latency


# -------------------------------------------------------------- obs_report


def test_obs_report_renders_spanline_sections(tmp_path):
    obs_report = load_tool("obs_report")
    run = write_run(tmp_path / "render")
    text = obs_report.render(run)
    assert "== step breakdown (12 step spans) ==" in text
    assert "step_ms: p50" in text
    assert "== requests (6: ok 6) ==" in text
    assert "ttft_s:" in text and "tpot_s (" in text
    assert "(warm requests only)" in text


def test_obs_report_merges_sharded_streams(tmp_path):
    from perceiver_io_tpu.obs.events import EventLog

    obs_report = load_tool("obs_report")
    d = str(tmp_path)
    EventLog(d, process_index=0, process_count=2).emit("fit_start", start_step=0, max_steps=1)
    EventLog(d, process_index=1, process_count=2).emit("custom", x=1)
    events = obs_report.load_events(d)
    assert {e["event"] for e in events} == {"fit_start", "custom"}
