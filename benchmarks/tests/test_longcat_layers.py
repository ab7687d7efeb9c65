"""The readers of the LongCat cell's per-layer metrics, on small hand-made
traces: the decode loop is the largest ``while``, the prompt pass is what is
busy outside it, the expert kernels are found by their name, the shortcut
branch by the program's own scopes (a table handed in with the run); and a
reader with nothing to read, as on the parent's program or another family's
cell, returns ``None`` and does not raise."""

import pytest

from benchmarks import run
from benchmarks.lib import longcat_cost as cost
from benchmarks.lib import trace
from benchmarks.lib.peaks import load_peaks

MS = 1e6  # ns
NAMES = ["longcat_step_hbm_share.decode", "longcat_prefill_mfu.decode", "longcat_moe_experts_roofline.decode",
         "longcat_shortcut_step_ms.decode"]


def make_run(events, calls=1, config="longcat-flash-ep32", cell="longcat-ep32-decode-b64", **more):
    cfg = run.load_json("configs", config)
    family = run.importlib.import_module(f"benchmarks.families.{cfg['family']}").Family(cfg)
    window = (0.0, 13000 * MS)
    busy = trace.busy_ns(trace.clip(events, window)) / 1e9
    return {"cell": run.load_json("workloads", cell), "family": family, "peaks": load_peaks("TPU v5 lite"),
            "counters": {"calls": calls}, "chips": 1, "trace": {"devices": {"/device:TPU:0": events}, "host": []},
            "trace_window": window, "busy_s": busy, "window_s": 13.0, **more}


# one call: four prompt-pass loops (a layer each) of 700 ms with their expert kernels inside, then 511 steps in 9 s
PREFILL = [[f"while.{i}", i * 710 * MS, 700 * MS] for i in range(4)]
EXPERTS = [[f"moe_experts_prefill_m1024_k6144_n2048.{20 + i}", 10 * MS + i * 710 * MS, 40 * MS] for i in range(4)]
SCAN = [["while.99", 3000 * MS, 9000 * MS], ["fusion.5", 3000 * MS, 5000 * MS], ["fusion.6", 8000 * MS, 2555 * MS],
        ["fusion.7", 10555 * MS, 511 * MS], ["fusion.8", 11066 * MS, 934 * MS]]
CALL = PREFILL + EXPERTS + SCAN
TABLE = {
    **{f"while.{i}": {"phase": "prefill", "layer": "chunk_io", "container": True} for i in range(4)},
    **{f"moe_experts_prefill_m1024_k6144_n2048.{20 + i}": {"phase": "prefill", "layer": "moe/experts", "container": False} for i in range(4)},
    "while.99": {"phase": "decode", "layer": "<unscoped>", "container": True},
    "fusion.5": {"phase": "decode", "layer": "mla/absorb", "container": False},
    "fusion.6": {"phase": "decode", "layer": "moe/experts", "container": False},
    "fusion.7": {"phase": "decode", "layer": "moe/zero", "container": False},
    "fusion.8": {"phase": "decode", "layer": "moe/route", "container": False},
}
TABLE = {name: {"opcode": "fusion", "path": "", "inherited": False, **row} for name, row in TABLE.items()}


def read(name, run_):
    return run.load_module("layers", name).read(run_)


def test_the_steps_are_held_to_the_experts_hit_and_the_eight_caches():
    run_ = make_run(CALL)
    cfg = run_["family"].cfg
    want = 100 * cost.decode_scan_bytes(cfg, 64, 1024, 512) / 819e9 / 9.0
    assert read("longcat_step_hbm_share.decode", run_) == pytest.approx(want)
    assert 62 < want < 65  # 5.70 s at the HBM peak over 9 s
    two = make_run(CALL + [[n, s + 12500 * MS, d] for n, s, d in CALL], calls=2)
    assert read("longcat_step_hbm_share.decode", {**two, "trace_window": (0.0, 26000 * MS)}) == pytest.approx(want)


def test_the_prompt_pass_is_what_is_busy_outside_the_steps():
    run_ = make_run(CALL)
    assert run_["busy_s"] == pytest.approx(4 * 0.7 + 9.0)
    want = 100 * cost.prefill_flops(run_["family"].cfg, 64, 1024) / 2.8 / 197e12
    assert read("longcat_prefill_mfu.decode", run_) == pytest.approx(want)
    assert 60 < want < 67  # 351 TFLOP in 2.8 s


def test_expert_kernels_are_held_to_a_quarter_of_a_pair_a_token_over_four_layers():
    run_ = make_run(CALL)
    least = 4 * 2.0 * 16384 * 37_748_736 / 197e12
    assert read("longcat_moe_experts_roofline.decode", run_) == pytest.approx(100 * least / 0.16)
    assert 15 < 100 * least / 0.16 < 16.5  # 25 ms at the roofline over 160 ms


def test_the_shortcut_branch_is_read_by_the_programs_own_scopes(capsys):
    run_ = make_run(CALL, scope_table=TABLE)
    assert read("longcat_shortcut_step_ms.decode", run_) == pytest.approx((2555 + 511 + 934) / 511)
    out = capsys.readouterr().out
    assert "moe/experts 5.000" in out and "moe/zero 1.000" in out and "moe/route 1.828" in out
    # a program that opens no ``moe/zero`` (the parent; another family): nothing to read, though the other scopes are there
    no_zero = {k: ({**v, "layer": "moe/experts"} if v["layer"] == "moe/zero" else v) for k, v in TABLE.items()}
    assert read("longcat_shortcut_step_ms.decode", make_run(CALL, scope_table=no_zero)) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_with_nothing_to_read_returns_none(name):
    assert read(name, make_run([["fusion.1", 0.0, 100 * MS], ["convolution.2", 100 * MS, 50 * MS]], scope_table={})) is None
    assert read(name, {**make_run(CALL), "trace": None}) is None
    assert read(name, {**make_run(CALL), "counters": {"steps": 3}}) is None  # a train cell's counters


@pytest.mark.parametrize("name", NAMES[:3])
@pytest.mark.parametrize("config,cell", [("deepseek-v3-ep16", "dsv3-ep16-decode-b64"), ("k-exaone-236b-ep8", "kexaone-ep8-mtp-decode-b64")])
def test_another_familys_cell_reads_none(name, config, cell):
    """A configuration without experts that have no weights has nothing these readers count, whatever its trace holds."""
    assert read(name, make_run(CALL, config=config, cell=cell)) is None
