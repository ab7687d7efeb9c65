"""The readers of the Brumby cell's per-layer metrics, on small hand-made
traces: the decode loop is the largest ``while``, the prompt pass is what is
busy outside it, the chunk kernels are found by their name and held to the state
form's floor whatever chunk length the name carries, the retention layers' share of a step by the program's own
scopes (a table handed in with the run); and a reader with nothing to read, as
on the parent's program or another family's cell, returns ``None`` and does not raise."""

import pytest

from benchmarks import run
from benchmarks.lib import brumby_cost as cost
from benchmarks.lib import trace
from benchmarks.lib.peaks import load_peaks

MS = 1e6  # ns
NAMES = ["brumby_step_hbm_share.decode", "brumby_prefill_mfu.decode", "brumby_ret_chunk_roofline.decode", "brumby_ret_step_ms.decode"]


def make_run(events, calls=1, config="brumby-14b-pp8", cell="brumby-pp8-decode-b32-p4k", **more):
    cfg = run.load_json("configs", config)
    family = run.importlib.import_module(f"benchmarks.families.{cfg['family']}").Family(cfg)
    window = (0.0, 11000 * MS)
    busy = trace.busy_ns(trace.clip(events, window)) / 1e9
    return {"cell": run.load_json("workloads", cell), "family": family, "peaks": load_peaks("TPU v5 lite"),
            "counters": {"calls": calls}, "chips": 1, "trace": {"devices": {"/device:TPU:0": events}, "host": []},
            "trace_window": window, "busy_s": busy, "window_s": 11.0, **more}


# one call: 10 prompt-pass loops (a layer's retention and its feed-forward) of 350 ms, 32 chunk kernels of 4 ms inside each
# of the 5 retention loops, then 255 steps in 5.61 s, each layer's step kernel 3 ms of a step's 22
PREFILL = [[f"while.{i}", i * 360 * MS, 350 * MS] for i in range(10)]
CHUNKS = [[f"power_ret_chunk_l4096_c256_h40_d128.{40 + 32 * i + r}", (2 * i * 360 + 5 + 10 * r) * MS, 4 * MS] for i in range(5) for r in range(32)]
LOOP = [["while.99", 4000 * MS, 5610 * MS], ["power_ret_step_b32_h40_d128.5", 4000 * MS, 5 * 255 * 3 * MS], ["fusion.6", 7825 * MS, 510 * MS],
        ["fusion.7", 8335 * MS, 255 * MS], ["fusion.8", 8590 * MS, 127.5 * MS], ["fusion.9", 8717.5 * MS, 892.5 * MS]]
CALL = PREFILL + CHUNKS + LOOP
TABLE = {
    **{f"while.{i}": {"phase": "prefill", "layer": "chunk_io", "container": True} for i in range(10)},
    **{name: {"phase": "prefill", "layer": "ret/chunk", "container": False} for name, _, _ in CHUNKS},
    "while.99": {"phase": "decode", "layer": "<unscoped>", "container": True},
    "power_ret_step_b32_h40_d128.5": {"phase": "decode", "layer": "ret/update", "container": False},
    "fusion.6": {"phase": "decode", "layer": "ret/proj", "container": False},
    "fusion.7": {"phase": "decode", "layer": "ret/out", "container": False},
    "fusion.8": {"phase": "decode", "layer": "ret/gate", "container": False},
    "fusion.9": {"phase": "decode", "layer": "dense_mlp", "container": False},
}
TABLE = {name: {"opcode": "fusion", "path": "", "inherited": False, **row} for name, row in TABLE.items()}


def read(name, run_):
    return run.load_module("layers", name).read(run_)


def test_the_steps_are_held_to_the_weights_and_the_state_both_ways(capsys):
    run_ = make_run(CALL)
    cfg = run_["family"].cfg
    want = 100 * cost.decode_scan_bytes(cfg, 32, 256) / 819e9 / 5.61
    assert read("brumby_step_hbm_share.decode", run_) == pytest.approx(want)
    assert 87 < want < 88  # 4.91 s at the HBM peak over 5.61 s: 22 ms a step against 19.2
    out = capsys.readouterr().out
    assert "22.000 ms a decode step against 19.249 ms to move 15.77 GB" in out and "state 10.91 GB" in out
    two = make_run(CALL + [[n, s + 10500 * MS, d] for n, s, d in CALL], calls=2)
    assert read("brumby_step_hbm_share.decode", {**two, "trace_window": (0.0, 22000 * MS)}) == pytest.approx(want)


def test_the_prompt_pass_is_what_is_busy_outside_the_steps():
    run_ = make_run(CALL)
    assert run_["busy_s"] == pytest.approx(10 * 0.35 + 5.61)
    want = 100 * cost.prefill_flops(run_["family"].cfg, 32, 4096) / 3.5 / 197e12
    assert read("brumby_prefill_mfu.decode", run_) == pytest.approx(want)
    assert 72 < want < 73  # 0.50 PFLOP in 3.5 s
    # the same count whatever the program cuts a row into: longer chunks, or no kernel at all (the lax.scan path)
    longer = [[name.replace("_c256_", "_c1024_"), start, ns] for name, start, ns in CHUNKS]
    assert read("brumby_prefill_mfu.decode", make_run(PREFILL + longer + LOOP)) == pytest.approx(want)
    assert read("brumby_prefill_mfu.decode", make_run(PREFILL + LOOP)) == pytest.approx(want)


def test_chunk_kernels_are_held_to_their_matrix_unit_work(capsys):
    # beside the kernels, 40 ms a call of XLA's own under the program's ``ret/chunk`` scope: printed, not in the value
    glue = [["fusion.30", 3600 * MS, 40 * MS]]
    table = {**TABLE, "fusion.30": {**TABLE["fusion.9"], "phase": "prefill", "layer": "ret/chunk"}}
    run_ = make_run(CALL + glue, scope_table=table)
    least = 5 * cost.chunk_cost(run_["family"].cfg, 32, 4096)["flops"] / 197e12
    assert read("brumby_ret_chunk_roofline.decode", run_) == pytest.approx(100 * least / 0.64)
    assert 52 < 100 * least / 0.64 < 53  # 0.337 s at the bf16 peak over 640 ms of kernels
    out = capsys.readouterr().out
    assert "640.00 ms of chunk kernels a call against 337.49 ms" in out and "(the operations bind)" in out and "TFLOP/s achieved" in out
    assert f"the ret/chunk scope whole 680.00 ms a call, 40.00 of them XLA's around the kernels: {100 * least / 0.68:.2f}% by the scope's time" in out
    # a program that cuts a row into longer chunks is held to the same floor: more in-chunk work is not more useful work
    longer = [[name.replace("_c256_", "_c1024_"), start, ns] for name, start, ns in CHUNKS]
    assert read("brumby_ret_chunk_roofline.decode", make_run(PREFILL + longer + LOOP, scope_table={})) == pytest.approx(100 * least / 0.64)
    # the step's kernels: 15 ms a step over five layers against 13.3 ms for the state's bytes
    assert "the step's kernels 15.000 ms a step against 13.316 ms" in out and "88.8% of their roofline" in out


def test_the_retention_layers_share_of_a_step_is_read_by_the_programs_own_scopes(capsys):
    run_ = make_run(CALL, scope_table=TABLE)
    assert read("brumby_ret_step_ms.decode", run_) == pytest.approx((5 * 255 * 3 + 510 + 255 + 127.5) / 255)
    out = capsys.readouterr().out
    assert "ms a step: ret/update 15.000, ret/proj 2.000, ret/out 1.000, ret/gate 0.500\n" in out  # the feed-forward's 3.5 ms are not the layers'
    # a program that opens no ``ret/update`` (the parent): nothing to read, though other scopes are there
    none = {k: ({**v, "layer": "dense_mlp"} if v["layer"].startswith("ret/") else v) for k, v in TABLE.items()}
    assert read("brumby_ret_step_ms.decode", make_run(CALL, scope_table=none)) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_with_nothing_to_read_returns_none(name):
    assert read(name, make_run([["fusion.1", 0.0, 100 * MS], ["convolution.2", 100 * MS, 50 * MS]], scope_table={})) is None
    assert read(name, {**make_run(CALL), "trace": None}) is None
    assert read(name, {**make_run(CALL), "counters": {"steps": 3}}) is None  # a train cell's counters


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("config,cell", [("deepseek-v3-ep16", "dsv3-ep16-decode-b64"), ("jamba2-3b", "jamba2-3b-decode-b256"),
                                         ("perceiver-ar-small-16k", "ar16k-decode-b64")])
def test_another_familys_cell_reads_none(name, config, cell):
    """A configuration without a retention layer has nothing these readers count, whatever its trace holds."""
    assert read(name, make_run(CALL, config=config, cell=cell, scope_table=TABLE)) is None
