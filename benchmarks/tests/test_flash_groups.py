"""The two flash groups read from the kernels' names: on a piece of PR 26's
own traced run of ``ar16k-train-b32`` (TPU v5 lite; cut with
``tools/trace_cut.py``), on the same piece with one kernel renamed, and on PR
25's piece, whose kernels carry the old bare names."""

import copy
import importlib.util
import json
import os

import pytest

from benchmarks import run
from benchmarks.lib import flash_groups, flops, peaks, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
BATCH = 32  # the cell's batch size


def load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def reader(metric):
    return run.load_module("layers", metric).read


@pytest.fixture(scope="module")
def family():
    config = run.load_json("configs", "perceiver-ar-small-16k")
    return importlib.import_module(f"benchmarks.families.{config['family']}").Family(config)


def as_run(recorded, family, steps=1):
    """What ``run.py`` hands a reader, from recorded rows."""
    return {
        "trace": {"devices": recorded["devices"], "host": recorded["host"]},
        "trace_window": trace.host_window(recorded["host"], "bench/window"),
        "family": family, "peaks": peaks.load_peaks("TPU v5 lite"),
        "counters": {"steps": steps, "batch_size": BATCH},
    }


def events_of(run_):
    plane = sorted(run_["trace"]["devices"])[0]
    return trace.clip(run_["trace"]["devices"][plane], run_["trace_window"])


def test_groups_come_from_the_familys_own_calls(family):
    groups = flash_groups.split_calls(family.flash_calls(BATCH))
    assert [(c["n_q"], c["n_kv"]) for c in groups["long"]] == [(1024, 8704)]
    assert {(c["n_q"], c["n_kv"]) for c in groups["short"]} == {(1024, 1024)} and len(groups["short"]) == 8


def test_the_two_ideals_add_to_the_whole_lists(family):
    calls = family.flash_calls(BATCH)
    ideal = flash_groups.ideal_seconds(calls, peaks.load_peaks("TPU v5 lite"))
    whole = flops.roofline_seconds(calls, peaks.load_peaks("TPU v5 lite"), training=True)["seconds"]
    assert ideal["long"] + ideal["short"] == pytest.approx(whole, rel=1e-12)
    assert ideal["long"] == pytest.approx(8.37e-3, rel=5e-3) and ideal["short"] == pytest.approx(4.19e-3, rel=5e-3)


@pytest.mark.parametrize("name,want", [
    ("flash_dkv_q1024_kv8704.53", ((1024, 8704), "dkv")), ("flash_fwd_q512_kv50176", ((512, 50176), "fwd")),
    ("FLASH_DQ_Q1024_KV1024.7", ((1024, 1024), "dq")), ("flash_attention_packed.27", (None, "other")),
    ("flash_fwd_q1024_kv87040.1", ((1024, 87040), "fwd")),
])
def test_names_are_parsed(name, want):
    assert (flash_groups.geometry_of(name), flash_groups.pass_of(name)) == want


def test_long_plus_short_is_the_time_flash_roofline_sums(family, capsys):
    """On the named piece: both readers give a number, their kernel times add
    to what ``flash_roofline.train`` sums, and their time-weighted
    combination is its value."""
    run_ = as_run(load("trace_ar16k_train_named.json"), family)
    times = flash_groups.group_times(events_of(run_), family.flash_calls(BATCH))
    flash_ns = sum(dur for name, _, dur in events_of(run_) if "flash" in name.lower())
    assert times["unmatched"] == 0 and times["unmatched_names"] == []
    assert sum(times["long"].values()) + sum(times["short"].values()) == pytest.approx(flash_ns) == times["flash"]
    assert set(times["long"]) <= {"fwd", "dq", "dkv"} and set(times["short"]) <= {"fwd", "dq", "dkv"}
    long_, short, whole = (reader(f"flash_{m}roofline.train")(run_) for m in ("long_", "short_", ""))
    assert long_ is not None and short is not None and 0 < long_ < 100 and 0 < short < 100
    t_long, t_short = sum(times["long"].values()), sum(times["short"].values())
    assert (long_ * t_long + short * t_short) / (t_long + t_short) == pytest.approx(whole, abs=0.1)
    printed = capsys.readouterr().out
    assert "flash_long_roofline.train:" in printed and "q1024_kv8704" in printed and "at the roofline" in printed


def test_an_unmatched_flash_kernel_over_one_percent_gives_none_from_both(family, capsys):
    recorded = copy.deepcopy(load("trace_ar16k_train_named.json"))
    (plane, ops), = recorded["devices"].items()
    flash = [op for op in ops if "flash" in op[0].lower()]
    biggest = max(flash, key=lambda op: op[2])
    assert biggest[2] > 0.01 * sum(op[2] for op in flash)
    biggest[0] = "flash_dkv_q1024_kv9999.1"  # a geometry of no call
    run_ = as_run(recorded, family)
    assert reader("flash_long_roofline.train")(run_) is None
    assert reader("flash_short_roofline.train")(run_) is None
    assert reader("flash_roofline.train")(run_) is not None  # the lump still reads
    assert "hold the geometry of no attention call" in capsys.readouterr().out
    # under one percent the stray kernel is left out and both still read
    smallest = min(flash, key=lambda op: op[2])
    biggest[0], smallest[0] = "flash_dkv_q1024_kv8704.1", "flash_fwd_q1_kv1.1"
    if smallest[2] <= 0.01 * sum(op[2] for op in flash):
        assert reader("flash_long_roofline.train")(run_) is not None


def test_the_old_named_trace_gives_none_not_a_number(family):
    run_ = as_run(load("trace_ar16k_train.json"), family)
    assert reader("flash_roofline.train")(run_) is not None
    assert reader("flash_long_roofline.train")(run_) is None
    assert reader("flash_short_roofline.train")(run_) is None


def test_no_trace_gives_none(family):
    run_ = {"trace": None, "counters": {"steps": 3, "batch_size": BATCH}}
    assert flash_groups.read(run_, "long") is None and flash_groups.read(run_, "short") is None
