"""The readers of the Phi-4-mini-flash cell's per-layer metrics, on small
hand-made traces: the decode loop is the largest ``while``, the prompt pass is
what is busy outside it and is held to the **cut** pass's operations, the
differential flash kernels are found by their name, the shared cache's and the
memory units' share of a step by the program's own scopes (a table handed in
with the run); and a reader with nothing to read, as on the parent's program or
another family's cell, returns ``None`` and does not raise."""

import pytest

from benchmarks import run
from benchmarks.lib import phi4flash_cost as cost
from benchmarks.lib import trace
from benchmarks.lib.peaks import load_peaks

MS = 1e6  # ns
NAMES = ["phi4flash_step_hbm_share.decode", "phi4flash_prefill_mfu.decode", "phi4flash_cross_step_ms.decode",
         "phi4flash_gmu_step_ms.decode", "phi4flash_diff_flash_roofline.decode", "phi4flash_diff_step_ms.decode",
         "phi4flash_ssm_scan_roofline.decode", "phi4flash_ssm_step_ms.decode"]


def make_run(events, calls=1, config="phi4-mini-flash", cell="phi4flash-decode-b32-p8k", **more):
    cfg = run.load_json("configs", config)
    family = run.importlib.import_module(f"benchmarks.families.{cfg['family']}").Family(cfg)
    window = (0.0, 16000 * MS)
    busy = trace.busy_ns(trace.clip(events, window)) / 1e9
    return {"cell": run.load_json("workloads", cell), "family": family, "peaks": load_peaks("TPU v5 lite"),
            "counters": {"calls": calls}, "chips": 1, "trace": {"devices": {"/device:TPU:0": events}, "host": []},
            "trace_window": window, "busy_s": busy, "window_s": 16.0, **more}


# one call: 35 prompt-pass loops of 200 ms (7 s), a differential flash kernel of 25 ms inside 8 of them, then 255 steps in 7.65 s
PREFILL = [[f"while.{i}", i * 200 * MS, 200 * MS] for i in range(35)]
FLASH = [[f"flash_diff_fwd_q8192_kv8192_w512.{40 + i}", 5 * MS + (2 * i + 1) * 400 * MS, 25 * MS] for i in range(8)]
SCANS = [[f"ssm_scan_l8192_d5120_n16.{60 + i}", 40 * MS + 2 * i * 400 * MS, 45 * MS] for i in range(9)]  # inside the other loops
LOOP = [["while.99", 7100 * MS, 7650 * MS], ["fusion.5", 7100 * MS, 3570 * MS], ["fusion.6", 10670 * MS, 510 * MS],
        ["fusion.7", 11180 * MS, 765 * MS], ["fusion.8", 11945 * MS, 255 * MS], ["fusion.9", 12200 * MS, 1530 * MS],
        ["fusion.10", 13730 * MS, 510 * MS], ["fusion.11", 14240 * MS, 255 * MS], ["fusion.12", 14495 * MS, 255 * MS]]
CALL = PREFILL + FLASH + SCANS + LOOP
TABLE = {
    **{f"while.{i}": {"phase": "prefill", "layer": "chunk_io", "container": True} for i in range(35)},
    **{f"flash_diff_fwd_q8192_kv8192_w512.{40 + i}": {"phase": "prefill", "layer": "diff/flash", "container": False} for i in range(8)},
    **{f"ssm_scan_l8192_d5120_n16.{60 + i}": {"phase": "prefill", "layer": "ssm/scan", "container": False} for i in range(9)},
    "while.99": {"phase": "decode", "layer": "<unscoped>", "container": True},
    "fusion.5": {"phase": "decode", "layer": "yoco/cross", "container": False},
    "fusion.6": {"phase": "decode", "layer": "yoco/kv", "container": False},
    "fusion.7": {"phase": "decode", "layer": "gmu", "container": False},
    "fusion.8": {"phase": "decode", "layer": "diff/step", "container": False},
    "fusion.9": {"phase": "decode", "layer": "dense_mlp", "container": False},
    "fusion.10": {"phase": "decode", "layer": "diff/proj", "container": False},
    "fusion.11": {"phase": "decode", "layer": "diff/combine", "container": False},
    "fusion.12": {"phase": "decode", "layer": "ssm/update", "container": False},
}
TABLE = {name: {"opcode": "fusion", "path": "", "inherited": False, **row} for name, row in TABLE.items()}


def read(name, run_):
    return run.load_module("layers", name).read(run_)


def test_the_steps_are_held_to_the_weights_and_eight_reads_of_the_one_cache(capsys):
    run_ = make_run(CALL)
    cfg = run_["family"].cfg
    want = 100 * cost.decode_scan_bytes(cfg, 32, 8192, 256) / 819e9 / 7.65
    assert read("phi4flash_step_hbm_share.decode", run_) == pytest.approx(want)
    assert 79 < want < 80  # 6.07 s at the HBM peak over 7.65 s: 30 ms a step against 23.8
    out = capsys.readouterr().out
    assert "30.000 ms a decode step against 23.7" in out and "8 reads of the shared cache 10.9" in out
    two = make_run(CALL + [[n, s + 15300 * MS, d] for n, s, d in CALL], calls=2)
    two["trace_window"], two["window_s"] = (0.0, 31000 * MS), 31.0
    assert read("phi4flash_step_hbm_share.decode", two) == pytest.approx(want)


def test_the_prompt_pass_is_held_to_the_cut_passs_operations(capsys):
    run_ = make_run(CALL)
    cfg = run_["family"].cfg
    outside = run_["busy_s"] - 7.65
    assert outside == pytest.approx(7.0)
    want = 100 * cost.prefill_flops(cfg, 32, 8192) / outside / 197e12
    assert read("phi4flash_prefill_mfu.decode", run_) == pytest.approx(want) and 72 < want < 73
    out = capsys.readouterr().out
    assert "997.0 TFLOP of the cut prompt pass's products (15 layers at one position a row" in out and "1898.5 TFLOP" in out
    # a program that ran all 32 layers over every position in 1.9 times the time would read about half
    slow = make_run([[n, s * 1.9, d * 1.9] if n.startswith("while.") and n != "while.99" else [n, s + 6300 * MS, d] for n, s, d in PREFILL + LOOP])
    slow["trace_window"], slow["window_s"] = (0.0, 22000 * MS), 22.0
    slow["busy_s"] = trace.busy_ns(trace.clip(slow["trace"]["devices"]["/device:TPU:0"], slow["trace_window"])) / 1e9
    assert read("phi4flash_prefill_mfu.decode", slow) == pytest.approx(want / 1.9, rel=1e-3)


def test_the_window_flash_kernels_are_held_to_their_roofline(capsys):
    run_ = make_run(CALL)
    cfg = run_["family"].cfg
    one = cost.diff_flash_cost(cfg, 32, 8192)
    want = 100 * 8 * max(one["flops"] / 197e12, one["bytes"] / 819e9) / 0.2
    assert read("phi4flash_diff_flash_roofline.decode", run_) == pytest.approx(want) and 40 < want < 41
    assert "200.00 ms of differential window flash kernels a call against 81.11 ms at the roofline (the operations bind)" in capsys.readouterr().out
    # another window's kernels, or the grouped-query forward's, are not these
    other = make_run([[n.replace("w512", "w1024").replace("flash_diff_fwd", "flash_fwd"), s, d] for n, s, d in CALL])
    assert read("phi4flash_diff_flash_roofline.decode", other) is None


def test_the_shared_cache_the_memory_units_and_the_rings_are_read_off_the_scopes(capsys):
    run_ = make_run(CALL, scope_table=TABLE)
    assert read("phi4flash_cross_step_ms.decode", run_) == pytest.approx((3570 + 510) / 255)
    assert read("phi4flash_gmu_step_ms.decode", run_) == pytest.approx(765 / 255)
    assert read("phi4flash_diff_step_ms.decode", run_) == pytest.approx((255 + 510 + 255) / 255)
    out = capsys.readouterr().out
    assert "yoco/cross 14.000, yoco/kv 2.000" in out and "3.000 ms a step under gmu" in out
    assert "diff/proj 2.000, diff/step 1.000, diff/combine 1.000" in out


def test_the_scans_and_the_mixers_steps_are_read_as_jambas_cell_reads_them(capsys):
    """The nine scans are Jamba's kernel at Jamba's geometry and the mixers open Jamba's scopes: the same readings,
    held to this configuration's nine layers and this cell's rows."""
    run_ = make_run(CALL, scope_table=TABLE)
    one = cost.scan_cost(run_["family"].cfg, 32, 8192)
    want = 100 * 9 * one["bytes"] / 819e9 / 0.405
    assert read("phi4flash_ssm_scan_roofline.decode", run_) == pytest.approx(want) and 43 < want < 44
    assert read("phi4flash_ssm_step_ms.decode", run_) == pytest.approx(1.0)
    out = capsys.readouterr().out
    assert "405.00 ms of scan kernels a call" in out and "(the bytes bind)" in out and "ssm/update 1.000" in out


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_with_nothing_to_read_returns_none(name):
    bare = {k: {**v, "layer": "<unscoped>"} for k, v in TABLE.items()}  # a program that opens none of the scopes
    assert read(name, make_run([], scope_table=bare)) is None  # an empty window
    if "step_ms" in name:
        assert read(name, make_run(CALL, scope_table=bare)) is None
    if "ssm_scan" in name:  # a prompt pass with no scan kernel in it
        assert read(name, make_run(PREFILL + FLASH + LOOP)) is None
    other = make_run(CALL, config="jamba2-3b", cell="jamba2-3b-decode-b256", scope_table=TABLE)  # another family's cell
    assert read(name, other) is None
    no_trace = make_run(CALL, scope_table=TABLE)
    no_trace["trace"] = None
    assert read(name, no_trace) is None
