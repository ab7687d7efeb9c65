"""graphcheck (analysis/fingerprint.py + analysis/ledger.py +
tools/graphcheck.py): fingerprint extraction/serialization, the semantic
differ with a deliberately planted regression in EACH class the gate exists
to catch (extra kv-axis concat, extra all-gather, >tolerance peak-memory
growth, dropped donation), the committed contracts/ passing clean against
the live flagship graphs, the graduation-ledger state machine, bench
floors, and the graphlint CLI exit-code semantics."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from perceiver_io_tpu.analysis import ledger as L
from perceiver_io_tpu.analysis.fingerprint import (
    PROGRAMS,
    DiffTolerances,
    GraphFingerprint,
    check_contracts,
    diff_fingerprints,
    fingerprint,
    load_contract,
    save_contract,
    validate_contract,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTRACTS = os.path.join(REPO, "contracts")


# ------------------------------------------------------ extraction + roundtrip


def _toy_pair():
    a = jnp.ones((64, 64))
    return (a, a)


def test_fingerprint_roundtrip_and_stable_json():
    fn = jax.jit(lambda s, b: s + b, donate_argnums=(0,))
    fp = fingerprint(fn, _toy_pair(), name="toy")
    assert fp.donation_aliases == 1  # same-shape donation commits even on CPU
    assert fp.memory is not None and fp.memory["gate_bytes"] > 0
    assert fp.dtype_histogram.get("float32", 0) >= 1

    # stable serialization: a round-trip re-serializes byte-identically
    j1 = fp.to_json()
    j2 = GraphFingerprint.from_dict(json.loads(j1)).to_json()
    assert j1 == j2
    # and a self-diff is empty
    assert diff_fingerprints(fp, GraphFingerprint.from_dict(fp.to_dict())).ok


def test_fingerprint_trace_only_skips_compiled_fields():
    fp = fingerprint(lambda x: x * 2, (jnp.ones((4,)),), name="t", compiled=False)
    assert fp.donation_aliases is None and fp.memory is None and fp.flops is None
    assert fp.n_ops >= 1


def test_memory_breakdown_fallback_matches_entry_shapes():
    from perceiver_io_tpu.analysis.memory import estimate_from_hlo, memory_breakdown

    fn = jax.jit(lambda x, w: (x @ w).sum())
    exe = fn.lower(jnp.ones((64, 128)), jnp.ones((128, 256))).compile()
    mb = memory_breakdown(exe)
    assert mb.method == "memory_analysis"
    assert mb.argument_bytes == (64 * 128 + 128 * 256) * 4
    est = estimate_from_hlo(exe.as_text())
    assert est.method == "hlo_estimate"
    assert est.argument_bytes == mb.argument_bytes
    assert est.output_bytes == 4  # scalar f32


# ------------------------------------------------- the differ, class by class


def _doctor(fp: GraphFingerprint, **changes) -> GraphFingerprint:
    d = fp.to_dict()
    d.update(changes)
    return GraphFingerprint.from_dict(d)


@pytest.fixture(scope="module")
def base_fp():
    return fingerprint(jax.jit(lambda s, b: s + b), _toy_pair(), name="p")


def test_diff_catches_new_hot_concat(base_fp):
    planted = _doctor(
        base_fp,
        hot_concats=[{"scope": "cross_attend/kv_concat", "axis": 2, "shape": [2, 328, 64]}],
    )
    d = diff_fingerprints(base_fp, planted)
    assert not d.ok and d.regressions[0].field == "hot_concats"
    assert "NEW concat" in d.regressions[0].detail
    # the mirror image is an improvement, not a failure
    back = diff_fingerprints(planted, base_fp)
    assert back.ok and back.improvements[0].field == "hot_concats"


def test_diff_catches_duplicate_and_reshaped_concat_at_existing_site(base_fp):
    """Scopes are not unique per call site (microbatch-unrolled chunks
    re-trace the same scope): MORE concats at an existing (scope, axis,
    shape), or the same site growing a fatter shape, must regress too."""
    site = {"scope": "cross_attend/kv_concat", "axis": 1, "shape": [2, 328, 64]}
    one = _doctor(base_fp, hot_concats=[site])
    two = _doctor(base_fp, hot_concats=[dict(site), dict(site)])
    d = diff_fingerprints(one, two)
    assert not d.ok and "1 -> 2" in d.regressions[0].detail

    grown = _doctor(base_fp, hot_concats=[dict(site, shape=[2, 4096, 64])])
    d2 = diff_fingerprints(one, grown)
    assert not d2.ok and "4096" in d2.regressions[0].detail


def test_diff_catches_extra_collective(base_fp):
    planted = _doctor(base_fp, collectives={"all-gather": {"count": 1, "bytes": 4096}})
    d = diff_fingerprints(base_fp, planted)
    assert not d.ok and d.regressions[0].field == "collectives.all-gather.count"


def test_diff_catches_peak_memory_growth_beyond_tolerance(base_fp):
    mem = dict(base_fp.memory)
    grown = dict(mem, gate_bytes=int(mem["gate_bytes"] * 1.10),
                 temp_bytes=int(mem["temp_bytes"] * 2 + 4096))
    d = diff_fingerprints(base_fp, _doctor(base_fp, memory=grown))
    assert not d.ok and d.regressions[0].field == "memory.gate_bytes"

    within = dict(mem, gate_bytes=int(mem["gate_bytes"] * 1.01))
    assert diff_fingerprints(base_fp, _doctor(base_fp, memory=within)).ok


def test_diff_catches_dropped_donation(tmp_path):
    donating = fingerprint(
        jax.jit(lambda s, b: s + b, donate_argnums=(0,)), _toy_pair(), name="train_flat"
    )
    dropped = fingerprint(jax.jit(lambda s, b: s + b), _toy_pair(), name="train_flat")
    assert donating.donation_aliases == 1 and dropped.donation_aliases == 0
    d = diff_fingerprints(donating, dropped)
    assert not d.ok and d.regressions[0].field == "donation_aliases"

    # and through the contract gate end to end
    save_contract(str(tmp_path), "train_flat", donating, reason="pin donation")
    res = check_contracts(
        str(tmp_path), programs=("train_flat",), live={"train_flat": dropped}
    )
    assert res["status"] == "regressed"
    assert "donation_aliases" in res["programs"]["train_flat"]["detail"]


def test_diff_refuses_cross_environment_comparison(base_fp):
    d = diff_fingerprints(base_fp, _doctor(base_fp, backend="tpu"))
    assert not d.comparable and "backend" in d.reason and not d.ok
    d = diff_fingerprints(base_fp, _doctor(base_fp, features=["paged"]))
    assert not d.comparable and "feature" in d.reason


def test_diff_tolerances_respected(base_fp):
    mem = dict(base_fp.memory, gate_bytes=int(base_fp.memory["gate_bytes"] * 1.07))
    strict = DiffTolerances(memory_frac=0.01)
    loose = DiffTolerances(memory_frac=0.25)
    assert not diff_fingerprints(base_fp, _doctor(base_fp, memory=mem), strict).ok
    assert diff_fingerprints(base_fp, _doctor(base_fp, memory=mem), loose).ok


# ------------------------------------------------------------- contract store


def test_contract_save_load_validate_roundtrip(tmp_path, base_fp):
    with pytest.raises(ValueError, match="reason"):
        save_contract(str(tmp_path), "p", base_fp, reason="  ")
    save_contract(str(tmp_path), "p", base_fp, reason="initial pin")
    doc = load_contract(str(tmp_path), "p")
    assert doc["updated_reason"] == "initial pin"
    assert validate_contract(doc) == []
    assert GraphFingerprint.from_dict(doc["fingerprint"]).to_dict() == base_fp.to_dict()

    bad = json.loads(json.dumps(doc))
    del bad["fingerprint"]["collectives"]
    assert any("collectives" in p for p in validate_contract(bad))


def test_missing_contract_reported(tmp_path, base_fp):
    res = check_contracts(str(tmp_path), programs=("train_flat",),
                          live={"train_flat": base_fp})
    assert res["status"] == "missing"


# ----------------------------------- the committed contracts vs the live graphs


@pytest.fixture(scope="module")
def flagship_fps():
    """Extract the real flagship fingerprints ONCE for the whole module —
    the same programs tools/graphcheck.py builds (8 virtual devices from
    conftest cover the data=2,fsdp=2 submesh)."""
    from perceiver_io_tpu.analysis.fingerprint import flagship_fingerprints

    return flagship_fingerprints()


def test_committed_contracts_pass_clean(flagship_fps):
    """THE gate: the live flagship graphs match the committed contracts/ on
    main — what `tasks.py perf` runs in CI."""
    res = check_contracts(CONTRACTS, live=flagship_fps)
    for name, entry in res["programs"].items():
        assert entry["status"] == "passed", f"{name}: {entry}"
    assert res["status"] == "passed"


def test_planted_kv_concat_regression_caught(flagship_fps):
    live = flagship_fps["train_flat"]
    planted = _doctor(
        live,
        hot_concats=list(live.to_dict()["hot_concats"])
        + [{"scope": "planted/cross_attend/kv_concat", "axis": 2, "shape": [2, 328, 64]}],
    )
    res = check_contracts(CONTRACTS, programs=("train_flat",),
                          live={"train_flat": planted})
    assert res["status"] == "regressed"
    assert "NEW concat" in res["programs"]["train_flat"]["detail"]


def test_planted_extra_all_gather_caught(flagship_fps):
    live = flagship_fps["train_sharded"]
    coll = {k: dict(v) for k, v in live.collectives.items()}
    coll["all-gather"]["count"] += 1
    res = check_contracts(CONTRACTS, programs=("train_sharded",),
                          live={"train_sharded": _doctor(live, collectives=coll)})
    assert res["status"] == "regressed"
    assert "all-gather" in res["programs"]["train_sharded"]["detail"]


def test_planted_peak_memory_growth_caught(flagship_fps):
    live = flagship_fps["train_flat"]
    mem = dict(live.memory)
    mem["gate_bytes"] = int(mem["gate_bytes"] * 1.10)
    res = check_contracts(CONTRACTS, programs=("train_flat",),
                          live={"train_flat": _doctor(live, memory=mem)})
    assert res["status"] == "regressed"
    assert "memory.gate_bytes" in res["programs"]["train_flat"]["detail"]


def test_stale_contract_reported_not_regressed(flagship_fps):
    live = flagship_fps["decode"]
    res = check_contracts(CONTRACTS, programs=("decode",),
                          live={"decode": _doctor(live, backend="tpu")})
    assert res["status"] == "stale"
    assert "--update" in res["programs"]["decode"]["detail"]


# ------------------------------------------------------------------ the ledger


def test_committed_ledger_validates_and_floors_hold():
    ledger = L.load_ledger(CONTRACTS)
    assert ledger is not None, "contracts/ledger.json must be committed"
    assert L.validate_ledger(ledger) == []
    assert L.default_on_features(ledger) == ()
    # the committed BENCH artifacts meet their own pinned floors
    assert L.check_bench_floors(ledger, REPO) == []


def test_ledger_state_machine():
    ledger = {
        "schema_version": 1,
        "features": {
            "f": {"state": "staged",
                  "history": [{"state": "staged", "reason": "landed"}]}
        },
    }
    with pytest.raises(ValueError, match="illegal transition"):
        L.advance(ledger, "f", "default_on", reason="skipping measured")
    with pytest.raises(ValueError, match="reason"):
        L.advance(ledger, "f", "measured", reason="")

    measured = L.advance(ledger, "f", "measured", reason="BENCH_r07 A/B +9%",
                         evidence={"bench": "BENCH_r07"})
    on = L.advance(measured, "f", "default_on", reason="winner flipped on")
    assert L.feature_state(on, "f") == "default_on"
    assert L.default_on_features(on) == ("f",)
    # demotion jumps backward but must be reasoned (validated by advance)
    demoted = L.advance(on, "f", "staged", reason="regression found on v6e")
    assert L.feature_state(demoted, "f") == "staged"
    assert L.validate_ledger(demoted) == []


def test_ledger_validation_catches_bad_history():
    skip = {
        "schema_version": 1,
        "features": {"f": {"state": "default_on", "history": [
            {"state": "staged", "reason": "x"},
            {"state": "default_on", "reason": "jumped"},
        ]}},
    }
    assert any("illegal transition" in p for p in L.validate_ledger(skip))
    unreasoned = {
        "schema_version": 1,
        "features": {"f": {"state": "staged", "history": [{"state": "staged", "reason": " "}]}},
    }
    assert any("reason" in p for p in L.validate_ledger(unreasoned))
    mismatch = {
        "schema_version": 1,
        "features": {"f": {"state": "measured",
                           "history": [{"state": "staged", "reason": "x"}]}},
    }
    assert any("last history state" in p for p in L.validate_ledger(mismatch))


def test_bench_floor_failure_detected(tmp_path):
    ledger = {
        "schema_version": 1,
        "features": {},
        "floors": {
            "spec": {"artifact": "BENCH_extra_r*.json", "key": "decode_spec.tokens_per_step", "min": 99.0},
            "ghost": {"artifact": "NO_SUCH_r*.json", "key": "parsed.value", "min": 0.0},
        },
    }
    failures = L.check_bench_floors(ledger, REPO)
    assert any("below floor 99.0" in f for f in failures)
    assert any("no artifact matches" in f for f in failures)


def test_floor_match_clause_selects_latest_matching_round(tmp_path):
    """Mode-aware floors: one artifact family holds rounds of several modes
    (LOAD_r01 sequential-closed, r02 engine-closed, r03 engine-open) —
    a floor's ``match`` clause must pin it to the latest round of ITS mode,
    not whatever mode committed last. ``"*"`` means present-and-non-null."""
    import json as _json

    for n, doc in (
        (1, {"mode": "closed", "summary": {"v": 10.0}}),
        (2, {"mode": "closed", "summary": {"v": 9.0, "engine": {"slots": 8}}}),
        (3, {"mode": "open", "summary": {"v": 3.0, "engine": {"slots": 8}}}),
    ):
        (tmp_path / f"LOAD_r{n:02d}.json").write_text(_json.dumps(doc))
    ledger = {
        "schema_version": 1,
        "features": {},
        "floors": {
            "closed_engine": {"artifact": "LOAD_r*.json", "key": "summary.v", "min": 5.0,
                              "match": {"mode": "closed", "summary.engine": "*"}},
            "open_rate": {"artifact": "LOAD_r*.json", "key": "summary.v", "min": 5.0,
                          "match": {"mode": "open"}},
            "any_latest": {"artifact": "LOAD_r*.json", "key": "summary.v", "min": 5.0},
            "no_such_mode": {"artifact": "LOAD_r*.json", "key": "summary.v", "min": 0.0,
                             "match": {"mode": "chaotic"}},
        },
    }
    failures = L.check_bench_floors(ledger, str(tmp_path))
    # closed_engine reads r02 (9.0 >= 5.0) even though r03 committed later
    assert not any(f.startswith("closed_engine") for f in failures), failures
    # open_rate reads r03 (3.0 < 5.0) and names the round it read
    assert any(f.startswith("open_rate") and "LOAD_r03" in f for f in failures), failures
    # an unmatched floor keeps plain latest-round-wins (r03: 3.0 < 5.0)
    assert any(f.startswith("any_latest") and "LOAD_r03" in f for f in failures), failures
    # a clause nothing satisfies is a loud gap, not a silent pass
    assert any(f.startswith("no_such_mode") and "no artifact" in f for f in failures), failures
    # the committed ledger's LOAD floors carry the clauses this test pins
    committed = L.load_ledger(CONTRACTS)
    assert committed["floors"]["engine_open_achieved_rps"]["match"]["mode"] == "open"
    assert committed["floors"]["engine_throughput_tok_s"]["match"]["mode"] == "closed"


# ------------------------------------------------- graphlint CLI exit semantics


def _load_tool(name):
    path = os.path.join(REPO, "tools", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_tool_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _canned_reports(violations):
    from perceiver_io_tpu.analysis.check import Report

    return {
        "train": Report(
            name="train_step", backend="cpu", n_ops=3,
            rules_run=("hot-concat",), rules_skipped=(),
            violations=violations, allowed=[],
        )
    }


def test_graphlint_cli_exit_codes(monkeypatch, tmp_path):
    """0 = clean, 1 = violations at/above --fail-on, 3 = the linter itself
    crashed — CI must never read a rule error as either verdict."""
    from perceiver_io_tpu.analysis import flagship
    from perceiver_io_tpu.analysis.rules import Violation

    gl = _load_tool("graphlint")

    monkeypatch.setattr(flagship, "lint_flagship", lambda **kw: _canned_reports([]))
    out = str(tmp_path / "clean.json")
    assert gl.main(["--targets", "train", "--json", out]) == 0
    assert json.load(open(out))["train"]["clean"] is True

    bad = [Violation(rule="hot-concat", severity="error", scope="s", message="planted")]
    monkeypatch.setattr(flagship, "lint_flagship", lambda **kw: _canned_reports(bad))
    out = str(tmp_path / "bad.json")
    assert gl.main(["--targets", "train", "--fail-on", "error", "--json", out]) == 1
    assert json.load(open(out))["train"]["counts"]["error"] == 1
    # verdict severity below the bar: violations exist but the gate passes
    assert gl.main(["--targets", "train", "--fail-on", "none"]) == 0

    def boom(**kw):
        raise RuntimeError("rule exploded")

    monkeypatch.setattr(flagship, "lint_flagship", boom)
    assert gl.main(["--targets", "train"]) == 3


def test_graphlint_cli_unknown_rule_is_usage_error(capsys):
    """A typo'd --rules name must exit with the argparse USAGE code (2) and
    list the registered rules — not silently skip the rule (the old
    behavior) and not crash as exit 3."""
    import pytest

    gl = _load_tool("graphlint")
    with pytest.raises(SystemExit) as e:
        gl.main(["--rules", "no-such-rule,hot-concat"])
    assert e.value.code == 2
    err = capsys.readouterr().err
    assert "no-such-rule" in err and "registered rules" in err
    assert "hot-concat" in err and "rng-key-reuse" in err

    # same contract for --programs
    with pytest.raises(SystemExit) as e2:
        gl.main(["--programs", "bogus"])
    assert e2.value.code == 2
    assert "train_sharded" in capsys.readouterr().err
