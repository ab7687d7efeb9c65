"""The readers of the Mellum cell's per-layer metrics, on small hand-made
traces: the decode scan is the largest ``while``, the prompt pass is what is
busy outside it, a kernel is found by its name (the window kernel by its
``_w1024`` suffix, not the full layers' kernel), and a reader with nothing to
read, as on the parent's program or another family's cell, returns ``None``
and does not raise."""

import pytest

from benchmarks import run
from benchmarks.lib import mellum_cost as cost
from benchmarks.lib import trace
from benchmarks.lib.peaks import load_peaks

MS = 1e6  # ns
NAMES = ["mellum_flash_window_roofline.decode", "mellum_moe_experts_roofline.decode", "mellum_prefill_mfu.decode",
         "mellum_decode_scan_hbm_share.decode"]


def make_run(events, calls=1, config="mellum2-12b-pp4", cell="mellum2-pp4-decode-b32"):
    cfg = run.load_json("configs", config)
    family = run.importlib.import_module(f"benchmarks.families.{cfg['family']}").Family(cfg)
    window = (0.0, 12000 * MS)
    busy = trace.busy_ns(trace.clip(events, window)) / 1e9
    return {"cell": run.load_json("workloads", cell), "family": family, "peaks": load_peaks("TPU v5 lite"),
            "counters": {"calls": calls}, "chips": 1, "trace": {"devices": {"/device:TPU:0": events}, "host": []},
            "trace_window": window, "busy_s": busy, "window_s": 12.0}


# one call: sixteen prompt-pass loops of 400 ms with their kernels inside, then a decode scan of 4 s
PREFILL = [[f"while.{i}", i * 410 * MS, 400 * MS] for i in range(16)]
WINDOW_FLASH = [[f"flash_fwd_q8192_kv8192_w1024.{3 + i}", 5 * MS + i * 820 * MS, 50 * MS] for i in range(6)]
FULL_FLASH = [[f"flash_fwd_q8192_kv8192.{11 + i}", 450 * MS + i * 3000 * MS, 170 * MS] for i in range(2)]
EXPERTS = [[f"moe_experts_prefill_m32768_k2304_n896.{20 + i}", 420 * MS + i * 820 * MS, 300 * MS] for i in range(8)]
SCAN = [["while.99", 6600 * MS, 4000 * MS], ["fusion.5", 6600 * MS, 3000 * MS]]
CALL = PREFILL + WINDOW_FLASH + FULL_FLASH + EXPERTS + SCAN


def read(name, run_):
    return run.load_module("layers", name).read(run_)


def test_the_window_kernels_are_found_by_their_suffix_and_held_to_the_band():
    run_ = make_run(CALL)
    band = cost.window_flash_cost(run_["family"].cfg, 32, 8192)
    least = 6 * band["flops"] / 197e12  # bound by its operations
    assert read("mellum_flash_window_roofline.decode", run_) == pytest.approx(100 * least / 0.300)
    assert 40 < 100 * least / 0.300 < 45  # 125 ms of visible band at the peak over 300 ms
    # the full layers' kernel alone is not a window kernel
    assert read("mellum_flash_window_roofline.decode", make_run(PREFILL + FULL_FLASH + SCAN)) is None
    # nor is a window of another size that starts with the same digits
    other = [["flash_fwd_q8192_kv8192_w10240.4", 0.0, 50 * MS]]
    assert read("mellum_flash_window_roofline.decode", make_run(PREFILL + other + SCAN)) is None


def test_expert_kernels_are_held_to_eight_pairs_a_token():
    run_ = make_run(CALL)
    least = 8 * 2.0 * 262144 * 8 * cost.expert_params(run_["family"].cfg) / 197e12
    assert read("mellum_moe_experts_roofline.decode", run_) == pytest.approx(100 * least / 2.4)
    assert 40 < 100 * least / 2.4 < 50


def test_prompt_pass_and_decode_scan():
    run_ = make_run(CALL)
    cfg = run_["family"].cfg
    assert run_["busy_s"] == pytest.approx(16 * 0.4 + 4.0)
    assert read("mellum_prefill_mfu.decode", run_) == pytest.approx(100 * cost.prefill_flops(cfg, 32, 8192) / 6.4 / 197e12)
    want = 100 * cost.decode_scan_bytes(cfg, 32, 8192, 256) / 819e9 / 4.0
    assert read("mellum_decode_scan_hbm_share.decode", run_) == pytest.approx(want)
    assert 65 < want < 70  # 2.68 s at the HBM peak over 4 s
    two = make_run(CALL + [[n, s + 11000 * MS, d] for n, s, d in CALL], calls=2)
    assert read("mellum_decode_scan_hbm_share.decode", {**two, "trace_window": (0.0, 23000 * MS)}) == pytest.approx(want)


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_with_nothing_to_read_returns_none(name):
    assert read(name, make_run([["fusion.1", 0.0, 100 * MS], ["convolution.2", 100 * MS, 50 * MS]])) is None
    assert read(name, {**make_run(CALL), "trace": None}) is None
    assert read(name, {**make_run(CALL), "counters": {"steps": 3}}) is None


@pytest.mark.parametrize("name", NAMES[2:])
def test_another_familys_cell_reads_none(name):
    """The two readers that need no kernel of their own still read nothing on
    a configuration without ``layer_types``."""
    other = make_run(PREFILL + SCAN, config="deepseek-v3-ep16", cell="dsv3-ep16-decode-b64")
    assert read(name, other) is None
