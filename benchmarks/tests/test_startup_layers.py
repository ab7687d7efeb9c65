"""The six per-layer metrics under ``setup_s`` (ISSUE 51): ``lib/startup.py``
over a recorded start-up record (``data/startup_tiny-ar-train.json``: one CPU
run of the tiny train cell, the reference's and the checks' programs behind
the window included), and the entries of ``BENCHMARK.json`` found by name."""

import copy
import json
import os
import sys

import pytest

from benchmarks import run
from benchmarks.lib import startup

DATA = os.path.join(run.HERE, "tests", "data")
with open(os.path.join(DATA, "startup_tiny-ar-train.json")) as f:
    RECORDED = json.load(f)
with open(os.path.join(run.CHECKOUT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
ROWS, SETUP_S = RECORDED["rows"], RECORDED["setup_s"]
LO, HI = startup.setup_interval(RECORDED["clock"], RECORDED["process_start"], SETUP_S)
NEW = {"setup_import_s": "import_s", "setup_state_s": "state_s", "setup_trace_lower_s": "trace_lower_s",
       "setup_compile_s": "compile_s", "setup_cache_misses": "cache_misses", "setup_unattributed_s": "unattributed_s"}
NUMBERS = startup.SECONDS + ("cache_misses",)


def numbers(summary):
    return {k: summary[k] for k in NUMBERS}


def span(name, span_id, parent_id, start_s, end_s, **attrs):
    return {"name": name, "span_id": span_id, "parent_id": parent_id, "start_ns": int(start_s * 1e9),
            "end_ns": int(end_s * 1e9), "attrs": attrs}


def test_the_interval_of_set_up_is_placed_through_the_records_clock_pair():
    ns0, perf0 = RECORDED["clock"]
    assert LO == ns0 + round((RECORDED["process_start"] - perf0) * 1e9)
    assert HI - LO == round(SETUP_S * 1e9)
    assert startup.setup_interval((1_000_000_000, 50.0), 48.0, 3.0) == (-1_000_000_000, 2_000_000_000)


def test_the_parts_add_up_to_setup_s():
    s = startup.summarize(ROWS, LO, HI, SETUP_S)
    assert sum(s[k] for k in startup.SECONDS) == pytest.approx(SETUP_S, abs=1e-3)
    assert all(s[k] > 0 for k in startup.SECONDS), numbers(s)
    assert s["cache_misses"] == 0 and s["programs"]["hit"] > 0, "the recorded run had a warm cache"
    assert s["retrieval_s"] <= s["compile_s"] + s["state_s"]
    assert sum(s["packages"].values()) == pytest.approx(s["import_s"])
    assert sum(s["traced"].values()) + sum(s["lowered"].values()) == pytest.approx(s["trace_lower_s"])
    assert sum(s["compiled"].values()) == pytest.approx(s["compile_s"])
    assert "perceiver_io_tpu.training.checkpoint" in s["packages"] and "train_step" in s["traced"]


def test_what_compiled_after_the_window_is_in_no_number():
    behind = [r for r in ROWS if r["start_ns"] >= HI]
    assert sum(r["name"] == startup.COMPILE for r in behind) >= 10, "the reference and the checks compile behind the window"
    inside = [r for r in ROWS if r["start_ns"] < HI]
    assert numbers(startup.summarize(ROWS, LO, HI, SETUP_S)) == numbers(startup.summarize(inside, LO, HI, SETUP_S))
    more = [*ROWS, span(startup.COMPILE, "late", None, HI / 1e9 + 1.0, HI / 1e9 + 30.0, fn="jit(reference)", cache="miss")]
    assert numbers(startup.summarize(more, LO, HI, SETUP_S)) == numbers(startup.summarize(ROWS, LO, HI, SETUP_S))
    whole = (max(r["end_ns"] for r in ROWS) - LO) / 1e9
    assert startup.summarize(ROWS, LO, LO + round(whole * 1e9), whole)["compile_s"] > startup.summarize(
        ROWS, LO, HI, SETUP_S)["compile_s"], "with the cut moved to the run's end they would count"


def test_a_span_is_cut_at_both_ends_of_set_up():
    rows = [
        span(startup.IMPORT, "early", None, 8.0, 11.0, package="a"),  # began 2 s before the process's clock started
        span(startup.COMPILE, "straddle", None, 18.0, 23.0, fn="jit(f)", cache="hit", retrieval_s=0.5),  # 2 s inside
        span(startup.TRACE, "before", None, 1.0, 2.0, fn="g"),
        span(startup.TRACE, "after", None, 21.0, 22.0, fn="h"),
    ]
    s = startup.summarize(rows, int(10e9), int(20e9), 10.0)
    assert numbers(s) == {"import_s": 1.0, "state_s": 0.0, "trace_lower_s": 0.0, "compile_s": 2.0,
                          "unattributed_s": 7.0, "cache_misses": 0}


def test_self_time_counts_an_instant_once_in_the_innermost_span():
    rows = [
        span(startup.IMPORT, "pkg", None, 0.0, 4.0, package="p"),
        span(startup.IMPORT, "sub", "pkg", 1.0, 3.0, package="p.q"),
        span(startup.COMPILE, "eager", "sub", 1.5, 2.0, fn="jit(eager)", cache="miss"),
        span(startup.TRACE, "outer", None, 4.0, 7.0, fn="step"),
        span(startup.TRACE, "inner", "outer", 5.0, 6.0, fn="_where"),
        span(startup.IMPORT, "lazy", "outer", 6.0, 6.5, package="p.lazy"),
        span(startup.LOWER, "low", None, 7.0, 8.0, fn="jit(step)"),
        span(startup.COMPILE, "comp", None, 8.0, 9.5, fn="jit(step)", cache="hit", retrieval_s=1.25),
    ]
    s = startup.summarize(rows, 0, int(10e9), 10.0)
    assert numbers(s) == {"import_s": 2.0 + 1.5 + 0.5, "state_s": 0.0, "trace_lower_s": 2.5 + 1.0,
                          "compile_s": 0.5 + 1.5, "unattributed_s": 0.5, "cache_misses": 1}
    assert s["packages"] == {"p": 2.0, "p.q": 1.5, "p.lazy": 0.5} and s["traced"] == {"step": 1.5, "_where": 1.0}
    assert s["missed"] == ["jit(eager)"] and s["programs"] == {"hit": 1, "miss": 1, "off": 0} and s["retrieval_s"] == 1.25


def test_state_create_takes_all_that_nests_under_it():
    rows = [
        span(startup.STATE_CREATE, "state", None, 2.0, 6.0, leaves=3, param_bytes=12),
        span(startup.TRACE, "t", "state", 2.5, 3.0, fn="zeros_like"),
        span(startup.COMPILE, "c", "state", 3.0, 4.0, fn="jit(zeros_like)", cache="miss"),
        span(startup.IMPORT, "i", "c", 3.2, 3.4, package="lazy"),
        span(startup.COMPILE, "outside", None, 7.0, 8.0, fn="jit(step)", cache="hit"),
    ]
    s = startup.summarize(rows, 0, int(10e9), 10.0)
    assert numbers(s) == {"import_s": 0.0, "state_s": 4.0, "trace_lower_s": 0.0, "compile_s": 1.0,
                          "unattributed_s": 5.0, "cache_misses": 1}, "a miss inside state_create is a miss of set-up"
    assert s["state_spans"] == 4 and s["compiled"] == {"jit(step) [hit]": 1.0}


def test_overlapping_threads_are_refused_not_summed():
    rows = [span(startup.COMPILE, "a", None, 0.0, 8.0, fn="jit(f)", cache="off"),
            span(startup.COMPILE, "b", None, 1.0, 9.0, fn="jit(g)", cache="off")]
    with pytest.raises(ValueError, match="overlap"):
        startup.summarize(rows, 0, int(10e9), 10.0)


@pytest.fixture
def recorded_program(monkeypatch):
    """The recorded record in the program's place, and its run's clocks."""
    monkeypatch.setattr(startup, "program_record", lambda: (copy.deepcopy(ROWS), tuple(RECORDED["clock"])))
    monkeypatch.setattr(run, "PROCESS_START", RECORDED["process_start"])
    return {"end_to_end": {"setup_s": SETUP_S}}


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_reader_returns_its_part_and_prints_its_table(name, recorded_program, capsys):
    want = startup.summarize(ROWS, LO, HI, SETUP_S)[NEW[name]]
    assert run.load_module("layers", name).read(recorded_program) == want
    out = capsys.readouterr().out
    assert out.startswith("startup: ") and "not read" not in out
    if name == "setup_import_s":
        assert "perceiver_io_tpu.training.checkpoint" in out and "perceiver_io_tpu.training.trainer" in out
    if name == "setup_unattributed_s":
        assert f"= setup_s {SETUP_S:.3f}" in out


def test_the_readers_parts_add_up_to_setup_s_to_a_millisecond(recorded_program):
    parts = [run.load_module("layers", name).read(recorded_program) for name, key in NEW.items() if key in startup.SECONDS]
    assert sum(parts) == pytest.approx(SETUP_S, abs=1e-3)


@pytest.mark.parametrize("name", sorted(NEW))
def test_without_a_record_every_reader_returns_none_and_says_why(name, monkeypatch, capsys):
    import perceiver_io_tpu.obs

    monkeypatch.setitem(sys.modules, "perceiver_io_tpu.obs.startup", None)  # as on a parent of this PR: the import fails
    monkeypatch.delattr(perceiver_io_tpu.obs, "startup", raising=False)
    assert startup.program_record() is None
    assert run.load_module("layers", name).read({"end_to_end": {"setup_s": 30.0}}) is None
    assert "not read: the program holds no start-up record" in capsys.readouterr().out


def test_a_record_that_dropped_spans_is_not_read(monkeypatch, capsys):
    from perceiver_io_tpu.obs import startup as program

    monkeypatch.setattr(program, "dropped", lambda: 7)
    assert startup.read({"end_to_end": {"setup_s": 30.0}}) is None
    assert "dropped its 7 oldest spans" in capsys.readouterr().out


def test_process_start_is_the_running_harnesss(monkeypatch):
    monkeypatch.setattr(run, "PROCESS_START", 123.5)
    assert startup.process_start() == 123.5, "an imported harness: its own module global"
    command = type(sys)("__main__")
    command.PROCESS_START, command.run_cell = 7.25, run.run_cell
    monkeypatch.setitem(sys.modules, "__main__", command)
    assert startup.process_start() == 7.25, "run as the command: __main__'s"


def test_the_programs_own_record_reads_through_the_helper():
    """The live record of this process, cut to an interval that holds the
    package's import: the shapes agree with what the helper expects."""
    record = startup.program_record()
    assert record is not None
    rows, clock = record
    imports = [r for r in rows if r["name"] == startup.IMPORT]
    assert imports and {"name", "span_id", "parent_id", "start_ns", "end_ns", "attrs"} <= set(imports[0])
    lo, hi = min(r["start_ns"] for r in imports), max(r["end_ns"] for r in imports)
    one_thread = [r for r in rows if r["thread"] == imports[0]["thread"]]
    s = startup.summarize(one_thread, lo, hi, (hi - lo) / 1e9)
    assert s["import_s"] > 0 and "perceiver_io_tpu.core" in s["packages"]
    assert abs(clock[0] - lo) < 3600e9, "the clock pair is on the spans' epoch"


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_entry_is_found_by_name_with_its_cells(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    cells = [w["name"] for w in BENCH["workloads"]]
    train = [c for c in cells if "train_samples_per_s" in run.declared_metrics(BENCH, c, "end_to_end")]
    assert entry["workloads"] == (train if name == "setup_state_s" else cells)
    assert len(entry["workloads"]) == (2 if name == "setup_state_s" else 10)
    assert (entry["moves"], entry["source"], entry["better"], entry["layer"]) == ("setup_s", "host_clock", "lower", "start-up")
    assert entry["unit"] == ("programs" if name == "setup_cache_misses" else "s")
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert callable(run.load_module("layers", name).read)
    for cell in entry["workloads"]:
        declared = run.declared_metrics(BENCH, cell, "per_layer", run.declared_metrics(BENCH, cell, "end_to_end"))
        assert name in declared


def test_the_benchmark_gained_these_six_entries_at_the_end_and_nothing_else():
    assert [m["name"] for m in BENCH["per_layer"][-6:]] == list(NEW)
    assert len(BENCH["per_layer"]) == 52 and not any(m["moves"] == "setup_s" for m in BENCH["per_layer"][:-6])
