"""The readers of the Jamba cell's per-layer metrics, on small hand-made
traces: the decode loop is the largest ``while``, the prompt pass is what is
busy outside it, the scan kernels are found by their name, the mixers' share
of a step by the program's own scopes (a table handed in with the run); and a
reader with nothing to read, as on the parent's program or another family's
cell, returns ``None`` and does not raise."""

import pytest

from benchmarks import run
from benchmarks.lib import jamba_cost as cost
from benchmarks.lib import trace
from benchmarks.lib.peaks import load_peaks

MS = 1e6  # ns
NAMES = ["jamba_step_hbm_share.decode", "jamba_prefill_mfu.decode", "jamba_ssm_scan_roofline.decode", "jamba_ssm_step_ms.decode"]


def make_run(events, calls=1, config="jamba2-3b", cell="jamba2-3b-decode-b256", **more):
    cfg = run.load_json("configs", config)
    family = run.importlib.import_module(f"benchmarks.families.{cfg['family']}").Family(cfg)
    window = (0.0, 11000 * MS)
    busy = trace.busy_ns(trace.clip(events, window)) / 1e9
    return {"cell": run.load_json("workloads", cell), "family": family, "peaks": load_peaks("TPU v5 lite"),
            "counters": {"calls": calls}, "chips": 1, "trace": {"devices": {"/device:TPU:0": events}, "host": []},
            "trace_window": window, "busy_s": busy, "window_s": 11.0, **more}


# one call: 28 prompt-pass loops (a layer each) of 125 ms, a scan kernel of 20 ms inside each of the 26 Mamba layers', then 383 steps in 6.128 s
PREFILL = [[f"while.{i}", i * 130 * MS, 125 * MS] for i in range(28)]
SCANS = [[f"ssm_scan_l256_d5120_n16.{40 + i}", 5 * MS + i * 130 * MS, 20 * MS] for i in range(26)]
LOOP = [["while.99", 4000 * MS, 6128 * MS], ["fusion.5", 4000 * MS, 2298 * MS], ["fusion.6", 6298 * MS, 1149 * MS],
        ["fusion.7", 7447 * MS, 766 * MS], ["fusion.8", 8213 * MS, 383 * MS], ["fusion.9", 8596 * MS, 1532 * MS]]
CALL = PREFILL + SCANS + LOOP
TABLE = {
    **{f"while.{i}": {"phase": "prefill", "layer": "chunk_io", "container": True} for i in range(28)},
    **{f"ssm_scan_l256_d5120_n16.{40 + i}": {"phase": "prefill", "layer": "ssm/scan", "container": False} for i in range(26)},
    "while.99": {"phase": "decode", "layer": "<unscoped>", "container": True},
    "fusion.5": {"phase": "decode", "layer": "ssm/update", "container": False},
    "fusion.6": {"phase": "decode", "layer": "ssm/proj_in", "container": False},
    "fusion.7": {"phase": "decode", "layer": "ssm/out", "container": False},
    "fusion.8": {"phase": "decode", "layer": "ssm/select", "container": False},
    "fusion.9": {"phase": "decode", "layer": "dense_mlp", "container": False},
}
TABLE = {name: {"opcode": "fusion", "path": "", "inherited": False, **row} for name, row in TABLE.items()}


def read(name, run_):
    return run.load_module("layers", name).read(run_)


def test_the_steps_are_held_to_the_weights_the_state_both_ways_and_the_two_caches(capsys):
    run_ = make_run(CALL)
    cfg = run_["family"].cfg
    want = 100 * cost.decode_scan_bytes(cfg, 256, 256, 384) / 819e9 / 6.128
    assert read("jamba_step_hbm_share.decode", run_) == pytest.approx(want)
    assert 83 < want < 84  # 5.12 s at the HBM peak over 6.128 s: 16 ms a step against 13.4
    out = capsys.readouterr().out
    assert "16.000 ms a decode step against 13.366 ms to move 10.95 GB" in out and "state and windows 4.77 GB" in out
    two = make_run(CALL + [[n, s + 10500 * MS, d] for n, s, d in CALL], calls=2)
    assert read("jamba_step_hbm_share.decode", {**two, "trace_window": (0.0, 22000 * MS)}) == pytest.approx(want)


def test_the_prompt_pass_is_what_is_busy_outside_the_steps():
    run_ = make_run(CALL)
    assert run_["busy_s"] == pytest.approx(28 * 0.125 + 6.128)
    want = 100 * cost.prefill_flops(run_["family"].cfg, 256, 256) / 3.5 / 197e12
    assert read("jamba_prefill_mfu.decode", run_) == pytest.approx(want)
    assert 54 < want < 55  # 375 TFLOP of products in 3.5 s; the scans' own work is not counted


def test_scan_kernels_are_held_to_one_read_and_one_write_of_their_streams(capsys):
    run_ = make_run(CALL)
    least = 26 * cost.scan_cost(run_["family"].cfg, 256, 256)["bytes"] / 819e9
    assert read("jamba_ssm_scan_roofline.decode", run_) == pytest.approx(100 * least / 0.52)
    assert 25 < 100 * least / 0.52 < 25.3  # 131 ms at the HBM peak over 520 ms
    out = capsys.readouterr().out
    assert "(the bytes bind)" in out and "1.896 T elementwise operations a second achieved" in out


def test_the_mixers_share_of_a_step_is_read_by_the_programs_own_scopes(capsys):
    run_ = make_run(CALL, scope_table=TABLE)
    assert read("jamba_ssm_step_ms.decode", run_) == pytest.approx((2298 + 1149 + 766 + 383) / 383)
    out = capsys.readouterr().out
    assert "ms a step: ssm/update 6.000, ssm/proj_in 3.000, ssm/out 2.000, ssm/select 1.000\n" in out  # the feed-forward's 4 ms are not the mixers'
    # a program that opens no ``ssm/update`` (the parent): nothing to read, though other scopes are there
    none = {k: ({**v, "layer": "dense_mlp"} if v["layer"].startswith("ssm/") else v) for k, v in TABLE.items()}
    assert read("jamba_ssm_step_ms.decode", make_run(CALL, scope_table=none)) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_with_nothing_to_read_returns_none(name):
    assert read(name, make_run([["fusion.1", 0.0, 100 * MS], ["convolution.2", 100 * MS, 50 * MS]], scope_table={})) is None
    assert read(name, {**make_run(CALL), "trace": None}) is None
    assert read(name, {**make_run(CALL), "counters": {"steps": 3}}) is None  # a train cell's counters


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("config,cell", [("deepseek-v3-ep16", "dsv3-ep16-decode-b64"), ("mellum2-12b-pp4", "mellum2-pp4-decode-b32"),
                                         ("perceiver-ar-small-16k", "ar16k-decode-b64")])
def test_another_familys_cell_reads_none(name, config, cell):
    """A configuration without a state-space layer has nothing these readers count, whatever its trace holds."""
    assert read(name, make_run(CALL, config=config, cell=cell, scope_table=TABLE)) is None
