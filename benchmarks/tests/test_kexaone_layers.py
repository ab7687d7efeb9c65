"""The readers of the K-EXAONE cell's per-layer metrics, on small hand-made
traces: the speculative loop is the largest ``while``, the prompt pass is what
is busy outside it, a kernel is found by its name (the window kernel by its
``_w128`` suffix, not the full layers' kernel), and a reader with nothing to
read, as on the parent's program or another family's cell, returns ``None``
and does not raise."""

import pytest

from benchmarks import run
from benchmarks.lib import kexaone_cost as cost
from benchmarks.lib import trace
from benchmarks.lib.peaks import load_peaks

MS = 1e6  # ns
NAMES = ["kexaone_flash_window_roofline.decode", "kexaone_moe_experts_roofline.decode", "kexaone_prefill_mfu.decode",
         "kexaone_spec_step_hbm_share.decode"]


def make_run(events, calls=1, config="k-exaone-236b-ep8", cell="kexaone-ep8-mtp-decode-b64"):
    cfg = run.load_json("configs", config)
    family = run.importlib.import_module(f"benchmarks.families.{cfg['family']}").Family(cfg)
    window = (0.0, 12000 * MS)
    busy = trace.busy_ns(trace.clip(events, window)) / 1e9
    return {"cell": run.load_json("workloads", cell), "family": family, "peaks": load_peaks("TPU v5 lite"),
            "counters": {"calls": calls}, "chips": 1, "trace": {"devices": {"/device:TPU:0": events}, "host": []},
            "trace_window": window, "busy_s": busy, "window_s": 12.0}


# one call: twelve prompt-pass loops of 200 ms with their kernels inside, then 511 speculative steps in 8 s
PREFILL = [[f"while.{i}", i * 210 * MS, 200 * MS] for i in range(12)]
WINDOW_FLASH = [[f"flash_fwd_q1024_kv1024_w128.{3 + i}", 5 * MS + i * 420 * MS, 10 * MS] for i in range(4)]
FULL_FLASH = [[f"flash_fwd_q1024_kv1024.{11 + i}", 230 * MS + i * 1050 * MS, 30 * MS] for i in range(2)]
EXPERTS = [[f"moe_experts_prefill_m1024_k6144_n2048.{20 + i}", 220 * MS + i * 420 * MS, 60 * MS] for i in range(5)]
SCAN = [["while.99", 2600 * MS, 8000 * MS], ["fusion.5", 2600 * MS, 6000 * MS]]
CALL = PREFILL + WINDOW_FLASH + FULL_FLASH + EXPERTS + SCAN


def read(name, run_):
    return run.load_module("layers", name).read(run_)


def test_the_window_kernels_are_found_by_their_suffix_and_held_to_the_band():
    run_ = make_run(CALL)
    band = cost.window_flash_cost(run_["family"].cfg, 64, 1024)
    least = 4 * band["bytes"] / 819e9  # a window of 128 is bound by its bytes
    assert read("kexaone_flash_window_roofline.decode", run_) == pytest.approx(100 * least / 0.040)
    assert 25 < 100 * least / 0.040 < 35  # 11.8 ms at the roofline over 40 ms
    assert read("kexaone_flash_window_roofline.decode", make_run(PREFILL + FULL_FLASH + SCAN)) is None
    other = [["flash_fwd_q1024_kv1024_w1280.4", 0.0, 50 * MS]]
    assert read("kexaone_flash_window_roofline.decode", make_run(PREFILL + other + SCAN)) is None


def test_expert_kernels_are_held_to_one_local_pair_a_token_over_five_blocks():
    run_ = make_run(CALL)
    least = 5 * 2.0 * 65536 * cost.expert_params(run_["family"].cfg) / 197e12
    assert read("kexaone_moe_experts_roofline.decode", run_) == pytest.approx(100 * least / 0.3)
    assert 40 < 100 * least / 0.3 < 45


def test_prompt_pass_and_speculative_steps():
    run_ = make_run(CALL)
    cfg = run_["family"].cfg
    assert run_["busy_s"] == pytest.approx(12 * 0.2 + 8.0)
    assert read("kexaone_prefill_mfu.decode", run_) == pytest.approx(100 * cost.prefill_flops(cfg, 64, 1024) / 2.4 / 197e12)
    want = 100 * cost.spec_scan_bytes(cfg, 64, 1024, 512) / 819e9 / 8.0
    assert read("kexaone_spec_step_hbm_share.decode", run_) == pytest.approx(want)
    assert 74 < want < 77  # 6.03 s at the HBM peak over 8 s
    two = make_run(CALL + [[n, s + 11000 * MS, d] for n, s, d in CALL], calls=2)
    assert read("kexaone_spec_step_hbm_share.decode", {**two, "trace_window": (0.0, 23000 * MS)}) == pytest.approx(want)


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_with_nothing_to_read_returns_none(name):
    assert read(name, make_run([["fusion.1", 0.0, 100 * MS], ["convolution.2", 100 * MS, 50 * MS]])) is None
    assert read(name, {**make_run(CALL), "trace": None}) is None
    assert read(name, {**make_run(CALL), "counters": {"steps": 3}}) is None


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("config,cell", [("deepseek-v3-ep16", "dsv3-ep16-decode-b64"), ("mellum2-12b-pp4", "mellum2-pp4-decode-b32")])
def test_another_familys_cell_reads_none(name, config, cell):
    """A configuration without the module has nothing these readers count, whatever kernels its trace holds."""
    assert read(name, make_run(CALL, config=config, cell=cell)) is None
