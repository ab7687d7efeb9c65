"""The readers of the decoder-only cell's per-layer metrics, on small
hand-made traces: the decode scan is the largest ``while``, the prompt pass
is what is busy outside it, a kernel is found by its name, and a reader whose
kernel the trace does not hold returns ``None`` and does not raise."""

import pytest

from benchmarks import run
from benchmarks.lib import dsv3_cost as cost
from benchmarks.lib.peaks import load_peaks

MS = 1e6  # ns
NAMES = ["decode_scan_hbm_share.decode", "prefill_mfu.decode", "moe_experts_roofline.decode"]


def make_run(events, calls=1):
    family = run.importlib.import_module("benchmarks.families.deepseek_v3").Family(
        run.load_json("configs", "deepseek-v3-ep16"))
    window = (0.0, 7000 * MS)
    from benchmarks.lib import trace

    busy = trace.busy_ns(trace.clip(events, window)) / 1e9
    return {"cell": run.load_json("workloads", "dsv3-ep16-decode-b64"), "family": family,
            "peaks": load_peaks("TPU v5 lite"), "counters": {"calls": calls}, "chips": 1,
            "trace": {"devices": {"/device:TPU:0": events}, "host": []}, "trace_window": window,
            "busy_s": busy, "window_s": 7.0}


# one call: ten prompt-pass loops of 200 ms (each with its kernels inside), then a decode scan of 4 s
PREFILL = [[f"while.{i}", i * 220 * MS, 200 * MS] for i in range(10)]
KERNELS = [["moe_experts_prefill_m8192_k7168_n2048", 10 * MS + i * 220 * MS, 6 * MS] for i in range(10)]
SCAN = [["while.77", 2300 * MS, 4000 * MS], ["fusion.5", 2300 * MS, 3000 * MS]]


def read(name, run_):
    return run.load_module("layers", name).read(run_)


def test_the_decode_scan_is_the_largest_while():
    events = PREFILL + KERNELS + SCAN
    assert cost.decode_while_ns(events) == 4000 * MS
    assert cost.decode_while_ns(KERNELS) is None
    run_ = make_run(events)
    cfg = run_["family"].cfg
    want = 100 * cost.decode_scan_bytes(cfg, 64, 1024, 256) / 819e9 / 4.0
    assert read("decode_scan_hbm_share.decode", run_) == pytest.approx(want)
    assert 64 < want < 69  # 2.67 s at the peak over 4 s


def test_the_prompt_pass_is_what_is_busy_outside_the_scan():
    run_ = make_run(PREFILL + KERNELS + SCAN)
    assert run_["busy_s"] == pytest.approx(6.0)
    want = 100 * cost.prefill_flops(run_["family"].cfg, 64, 1024) / 2.0 / 197e12
    assert read("prefill_mfu.decode", run_) == pytest.approx(want)
    assert 55 < want < 60


def test_expert_kernels_are_found_by_name_and_held_to_their_roofline():
    run_ = make_run(PREFILL + KERNELS + SCAN)
    cfg = run_["family"].cfg
    pairs = 65536 * 0.5
    least = 4 * max(2 * pairs * cost.expert_params(cfg) / 197e12,
                    2 * (16 * cost.expert_params(cfg) + pairs * (2 * 7168 + 3 * 2048)) / 819e9)
    assert read("moe_experts_roofline.decode", run_) == pytest.approx(100 * least / 0.060)
    assert 0 < 100 * least / 0.060 < 100


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_with_nothing_to_read_returns_none(name):
    no_kernels = make_run([["fusion.1", 0.0, 100 * MS], ["convolution.2", 100 * MS, 50 * MS]])
    assert read(name, no_kernels) is None
    untraced = {**make_run(PREFILL + SCAN), "trace": None}
    assert read(name, untraced) is None
    no_calls = {**make_run(PREFILL + KERNELS + SCAN), "counters": {"steps": 3}}
    assert read(name, no_calls) is None


def test_the_kernel_reader_returns_none_where_no_kernel_ran():
    assert read("moe_experts_roofline.decode", make_run(PREFILL + SCAN)) is None
