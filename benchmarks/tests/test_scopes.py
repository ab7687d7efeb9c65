"""The scope readers (``lib/scopes.py`` and the ten ``layers/*_device_*``
files) on pieces of this PR's own traced runs on a TPU v5 lite, cut with
``tools/scopes_cut.py``: two steps of ``ar16k-train-b32`` and, of one call of
``kexaone-ep8-mtp-decode-b64``, the end of the prompt pass and two steps of the
speculative loop, each with the part of the program's instruction-to-scope
table that names its rows. What the chip alone can give (the table's compile as
a cache hit) is the traced runs' own; the table's making is held by
``tests/test_scopes.py``."""

import copy
import json
import os
import sys

import pytest

from benchmarks import run
from benchmarks.lib import scopes, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
CELLS = ("ar16k-train-b32", "kexaone-ep8-mtp-decode-b64")


def recorded(cell):
    with open(os.path.join(DATA, f"scopes_{cell}.json")) as f:
        return json.load(f)


def as_run(piece, table="the piece's"):
    """What ``run.py`` hands a reader, from a recorded piece; the table comes with the run."""
    rows = piece["rows"]
    cell = dict(run.load_json("workloads", piece["cell"]), params=piece["params"])
    return {
        "cell": cell, "family": None, "counters": piece["counters"],
        "trace": {"devices": {piece["plane"]: rows}, "host": []},
        "trace_window": (min(r[1] for r in rows), max(r[1] + r[2] for r in rows)),
        "busy_s": piece["busy_ns"] / 1e9, "scope_table": piece["table"] if table == "the piece's" else table,
    }


def readers(piece):
    return {metric: run.load_module("layers", metric).read for metric in piece["expected"]}


@pytest.mark.parametrize("cell", CELLS)
def test_every_reader_gives_the_recorded_value(cell, capsys):
    piece = recorded(cell)
    assert len(piece["rows"]) > 2000 and len(piece["expected"]) >= 5
    run_ = as_run(piece)
    for metric, read in readers(piece).items():
        assert read(run_) == piece["expected"][metric], metric
    out = capsys.readouterr().out
    # one line with the cell's whole table, once a run; the parts the issue asks the readers to print
    assert out.count("scopes: device ms a ") == 1 and "placed by inheritance" in out
    if cell == "ar16k-train-b32":
        parts = next(line for line in out.splitlines() if line.startswith("attention_xla_device_ms.train: ms a step:"))
        assert all(part in parts for part in ("qkv_proj", "o_proj", "rotary", "norm"))
    else:
        parts = next(line for line in out.splitlines() if line.startswith("moe_glue_device_ms.decode: ms a call:"))
        assert all(part in parts for part in ("moe/route", "moe/experts", "moe/combine"))


@pytest.mark.parametrize("cell", CELLS)
def test_containers_are_left_out_and_the_layers_sum_to_the_busy_time(cell):
    piece = recorded(cell)
    found, _ = scopes.join(piece["rows"], piece["table"])
    by_layer = found.by(lambda name, row: row["layer"])
    assert sum(by_layer.values()) == pytest.approx(found.leaf_ns, rel=1e-12)  # every leaf row is under one layer or unscoped
    assert found.leaf_ns == pytest.approx(piece["leaf_ns"]) and found.container_ns == pytest.approx(piece["container_ns"])
    assert found.leaf_ns == pytest.approx(trace.busy_ns(piece["rows"]), rel=0.01)  # leaf time is the busy time: nothing twice
    containers = {name for name, row in piece["table"].items() if row["container"]}
    assert not containers & {name for name, _, _ in found.rows}
    if cell != "ar16k-train-b32":  # the decode loop's ``while`` spans its body's rows
        assert containers and found.container_ns > 0.2 * found.leaf_ns
        with_loop = sum(r[2] for r in piece["rows"])
        assert with_loop == pytest.approx(found.leaf_ns + found.container_ns)


def test_the_decode_phase_inside_the_loop_is_the_loops_time():
    """What ``decode_step_device_ms.decode`` sums is what the loop's ``while`` spans: the leaf rows of the phase
    ``decode`` that start inside it add up to its time (the piece also holds what the phase lays out before the loop)."""
    piece = recorded("kexaone-ep8-mtp-decode-b64")
    table = piece["table"]
    loop = max((r for r in piece["rows"] if table[r[0]]["container"] and table[r[0]]["phase"] == "decode"), key=lambda r: r[2])
    inside = [r for r in piece["rows"] if loop[1] <= r[1] < loop[1] + loop[2] and not table[r[0]]["container"]]
    assert {table[r[0]]["phase"] for r in inside} == {"decode"}
    assert sum(r[2] for r in inside) == pytest.approx(loop[2], rel=0.01)
    steps = piece["params"]["new_tokens"] - 1
    before = sum(r[2] for r in piece["rows"] if r[1] < loop[1] and table[r[0]]["phase"] == "decode" and not table[r[0]]["container"])
    assert piece["expected"]["decode_step_device_ms.decode"] == pytest.approx((sum(r[2] for r in inside) + before) / 1e6 / steps)


@pytest.mark.parametrize("cell", CELLS)
def test_names_the_table_lacks_give_none_and_say_why(cell, capsys):
    piece = recorded(cell)
    table = copy.deepcopy(piece["table"])
    heaviest = max(trace.totals_by_name(piece["rows"]).items(), key=lambda kv: kv[1] if not table[kv[0]]["container"] else 0)[0]
    del table[heaviest]
    run_ = as_run(piece, table)
    assert all(read(run_) is None for read in readers(piece).values())
    out = capsys.readouterr().out
    assert out.count("not read: ") == len(piece["expected"]) and heaviest in out and "are not in the program's table" in out
    # a name worth under a thousandth of the device time does not stop the readers
    table = copy.deepcopy(piece["table"])
    totals = trace.totals_by_name(piece["rows"])
    lightest = min(totals, key=totals.get)
    assert totals[lightest] < scopes.UNKNOWN_LIMIT * sum(totals.values())
    del table[lightest]
    assert all(read(as_run(piece, table)) is not None for read in readers(piece).values())


def test_a_program_without_instruction_scopes_reads_nothing(monkeypatch, capsys):
    """The parent commit: ``obs/xplane.py`` has no ``instruction_scopes``, every reader returns ``None``."""
    from perceiver_io_tpu.obs import xplane

    monkeypatch.delattr(xplane, "instruction_scopes")
    piece = recorded("ar16k-train-b32")
    run_ = dict(as_run(piece), scope_table=None)
    assert all(read(run_) is None for read in readers(piece).values())
    out = capsys.readouterr().out
    assert out.count("not read: the program has no obs.xplane.instruction_scopes") == len(piece["expected"])


def test_a_compile_that_missed_the_cache_reads_nothing(monkeypatch, tmp_path):
    """No persistent cache here, so the rebuild's compile is a miss: its text need not be the text of what ran."""
    config = run.load_json("configs", "tiny-ar", os.path.join(DATA))
    cell = run.load_json("workloads", "tiny-ar-train", os.path.join(DATA))
    family = __import__("importlib").import_module(f"benchmarks.families.{config['family']}").Family(config)
    table, why = scopes.program_scopes({"cell": cell, "family": family})
    assert table is None and "holds no executable of jit_train_step" in why  # refused before any compile
    import jax

    monkeypatch.setattr(scopes.glob, "glob", lambda pattern: [pattern])  # an entry of that name, and still no hit
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    try:
        table, why = scopes.program_scopes({"cell": cell, "family": family})
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
    assert table is None and "no hit in the persistent cache" in why


def test_no_trace_reads_nothing(capsys):
    piece = recorded("ar16k-train-b32")
    run_ = dict(as_run(piece), trace=None)
    assert run.load_module("layers", "mlp_device_ms.train").read(run_) is None
    assert "mlp_device_ms.train: not read: no trace" in capsys.readouterr().out


def test_what_a_phase_is_divided_by():
    train = {"counters": {"steps": 22, "batch_size": 32}, "cell": {"params": {}}}
    assert scopes.per(train) == ({"": 22.0}, "step")
    decode = {"counters": {"calls": 2}, "cell": {"params": {"new_tokens": 512}}}
    assert scopes.per(decode) == ({"": 2.0, "decode": 2 * 511.0}, "call (decode: step)")


def test_benchmark_json_declares_each_reader_with_its_cells():
    with open(os.path.join(run.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    ours = sorted(name for name in declared if name + ".py" in os.listdir(os.path.join(run.HERE, "layers"))
                  and "scopes.read(" in open(os.path.join(run.HERE, "layers", name + ".py")).read())
    assert len(ours) == 10
    for name in ours:
        metric = declared[name]
        assert metric["source"] == "device_trace" and metric["better"] == "lower" and metric["workloads"]
        drivers = {run.load_json("workloads", cell)["driver"] for cell in metric["workloads"]}
        assert drivers <= ({"train"} if name.endswith(".train") else {"decode", "decode_routed"}), name
        assert all(cell in cells for cell in metric["workloads"])
    assert sys.modules[scopes.__name__].UNSCOPED == "<unscoped>"
