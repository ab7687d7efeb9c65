"""The harness finds configurations, cells and per-layer metrics by the
names in ``BENCHMARK.json``; a missing file fails with its name."""

import json
import os

import pytest

from benchmarks import run

ROOT = run.CHECKOUT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_is_found_by_name(entry):
    config = run.load_json("configs", entry["name"])
    assert entry["file"] == f"benchmarks/configs/{entry['name']}.json"
    assert config["name"] == entry["name"] and config["source"] == entry["source"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    family = run.importlib.import_module(f"benchmarks.families.{config['family']}").Family(config)
    assert family.train_flops(1) > 0


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda e: e["name"])
def test_cell_is_found_by_name(entry):
    cell = run.load_json("workloads", entry["name"])
    for key in ("name", "config", "traffic", "chips", "why"):
        assert cell[key] == entry[key], key
    assert hasattr(run.load_module("drivers", cell["driver"]), "run")
    assert all(v is not None for v in cell["limits"].values()), "every compared number has a limit"
    reported = run.declared_metrics(BENCH, entry["name"], "end_to_end")
    assert "setup_s" in reported and len(reported) >= 2
    assert run.declared_metrics(BENCH, entry["name"], "per_layer", reported)


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=lambda e: e["name"])
def test_layer_metric_is_found_by_name(entry):
    assert callable(run.load_module("layers", entry["name"]).read)
    assert entry["moves"] in {m["name"] for m in BENCH["end_to_end"]}


@pytest.mark.parametrize("kind,loader", [("workloads", run.load_json), ("configs", run.load_json),
                                         ("layers", run.load_module), ("drivers", run.load_module)])
def test_missing_file_fails_with_its_name(kind, loader):
    with pytest.raises(SystemExit, match="no-such-thing"):
        loader(kind, "no-such-thing")


def test_metrics_without_a_workloads_key_follow_what_they_move():
    bench = {
        "end_to_end": [{"name": "a", "workloads": ["x"]}, {"name": "setup_s"}],
        "per_layer": [{"name": "p", "moves": "a"}, {"name": "q", "moves": "a", "workloads": ["y"]}],
    }
    assert set(run.declared_metrics(bench, "x", "end_to_end")) == {"a", "setup_s"}
    assert set(run.declared_metrics(bench, "y", "end_to_end")) == {"setup_s"}
    assert set(run.declared_metrics(bench, "x", "per_layer", {"a", "setup_s"})) == {"p"}
    assert set(run.declared_metrics(bench, "y", "per_layer", {"setup_s"})) == {"q"}


def test_a_run_off_the_chip_exits_before_measuring(capsys):
    with pytest.raises(SystemExit, match="TPU chip"):
        run.main(["--workload", BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1"])
    assert '"metrics"' not in capsys.readouterr().out
