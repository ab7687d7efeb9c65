"""The readers of the Ling cell's per-layer metrics, on small hand-made traces
with the program's own table of scopes handed in: the step's share of the HBM
peak and the prompt pass's of the bf16 peak by the phases' device time, the
chunk kernels found by their name and held to the recurrence's floor whatever
chunk length the name carries, the delta layers' share of a step by the
program's scopes, the prompt pass's expert kernels apart from a step's; and a
reader with nothing to read, as on the parent's program or another family's
cell, returns ``None`` and does not raise."""

import pytest

from benchmarks import run
from benchmarks.lib import ling_cost as cost
from benchmarks.lib import trace
from benchmarks.lib.peaks import load_peaks

MS = 1e6  # ns
NAMES = ["ling_step_hbm_share.decode", "ling_prefill_mfu.decode", "ling_kda_chunk_roofline.decode", "ling_kda_step_ms.decode",
         "ling_moe_experts_roofline.decode"]


def make_run(events, calls=1, config="ling3-flash-ep4", cell="ling3-ep4-decode-b128-p2k", **more):
    cfg = run.load_json("configs", config)
    family = run.importlib.import_module(f"benchmarks.families.{cfg['family']}").Family(cfg)
    window = (0.0, 11000 * MS)
    busy = trace.busy_ns(trace.clip(events, window)) / 1e9
    return {"cell": run.load_json("workloads", cell), "family": family, "peaks": load_peaks("TPU v5 lite"),
            "counters": {"calls": calls}, "chips": 1, "trace": {"devices": {"/device:TPU:0": events}, "host": []},
            "trace_window": window, "busy_s": busy, "window_s": 11.0, **more}


# one call: a prompt pass of 3 s (64 chunk kernels of 10 ms a delta layer, 400 ms of expert kernels, the rest XLA's), then 255
# steps in 5.1 s: 20 ms a step, of which the six step kernels 4.8, their projections 3, the experts' kernels 9
CHUNKS = [[f"kda_chunk_l2048_c128_h32_d128.{40 + 64 * i + r}", (500 * i + 7 * r) * MS, 5 * MS] for i in range(6) for r in range(64)]
PROMPT = [["fusion.1", 3000 * MS - 680 * MS, 680 * MS], ["moe_experts_prefill_m1024_k2560_n768.3", 3000 * MS, 400 * MS]]
LOOP = [["while.99", 3400 * MS, 5100 * MS], ["kda_step_b128_h32_d128.5", 3400 * MS, 255 * 4.8 * MS], ["fusion.6", 4624 * MS, 255 * 2 * MS],
        ["fusion.7", 5134 * MS, 255 * 0.5 * MS], ["fusion.8", 5261.5 * MS, 255 * 0.5 * MS], ["fusion.9", 5389 * MS, 255 * 3.2 * MS],
        ["moe_experts_prefill_m384_k2560_n768.4", 6205 * MS, 255 * 9 * MS]]
CALL = CHUNKS + PROMPT + LOOP
TABLE = {
    **{name: {"phase": "prefill", "layer": "kda/chunk", "container": False} for name, _, _ in CHUNKS},
    "fusion.1": {"phase": "prefill", "layer": "dense_mlp", "container": False},
    "moe_experts_prefill_m1024_k2560_n768.3": {"phase": "prefill", "layer": "moe/experts", "container": False},
    "while.99": {"phase": "decode", "layer": "<unscoped>", "container": True},
    "kda_step_b128_h32_d128.5": {"phase": "decode", "layer": "kda/update", "container": False},
    "fusion.6": {"phase": "decode", "layer": "kda/proj", "container": False},
    "fusion.7": {"phase": "decode", "layer": "kda/conv", "container": False},
    "fusion.8": {"phase": "decode", "layer": "kda/out", "container": False},
    "fusion.9": {"phase": "decode", "layer": "mla/absorb", "container": False},
    "moe_experts_prefill_m384_k2560_n768.4": {"phase": "decode", "layer": "moe/experts", "container": False},
}
TABLE = {name: {"opcode": "fusion", "path": "", "inherited": False, **row} for name, row in TABLE.items()}
PREFILL_S = 6 * 64 * 0.005 + 0.68 + 0.4


def read(name, run_):
    return run.load_module("layers", name).read(run_)


def test_the_steps_are_held_to_the_experts_they_hit_the_states_both_ways_and_the_cache(capsys):
    run_ = make_run(CALL, scope_table=TABLE)
    cfg = run_["family"].cfg
    want = 100 * cost.decode_scan_bytes(cfg, 128, 2048, 256) / 819e9 / 5.1
    assert read("ling_step_hbm_share.decode", run_) == pytest.approx(want)
    assert 77 < want < 78  # 15.5 ms a step at the HBM peak over 20
    out = capsys.readouterr().out
    assert "20.000 ms a decode step against 15.521 ms to move 12.71 GB a step" in out
    assert "experts 7.85 GB, other_weights 1.20 GB, state 3.33 GB, cache 0.32 GB; 13.92 GB with every held expert read" in out
    two = make_run(CALL + [[n, s + 10500 * MS, d] for n, s, d in CALL], calls=2, scope_table=TABLE)
    assert read("ling_step_hbm_share.decode", {**two, "trace_window": (0.0, 22000 * MS)}) == pytest.approx(want)


def test_the_prompt_pass_is_the_phase_prefill():
    run_ = make_run(CALL, scope_table=TABLE)
    want = 100 * cost.prefill_flops(run_["family"].cfg, 128, 2048) / PREFILL_S / 197e12
    assert read("ling_prefill_mfu.decode", run_) == pytest.approx(want)
    assert 52 < want < 53  # 310 TFLOP in 3.0 s
    # the same count whatever the program cuts a row into
    longer = [[name.replace("_c128_", "_c256_"), start, ns] for name, start, ns in CHUNKS]
    table = {**TABLE, **{name: TABLE[CHUNKS[0][0]] for name, _, _ in longer}}
    assert read("ling_prefill_mfu.decode", make_run(longer + PROMPT + LOOP, scope_table=table)) == pytest.approx(want)


def test_chunk_kernels_are_held_to_the_recurrences_floor(capsys):
    # beside the kernels, 60 ms a call of XLA's own under the program's ``kda/chunk`` scope: printed, not in the value
    glue = [["fusion.30", 9000 * MS, 60 * MS]]
    table = {**TABLE, "fusion.30": {**TABLE["fusion.1"], "layer": "kda/chunk"}}
    run_ = make_run(CALL + glue, scope_table=table)
    least = 6 * cost.chunk_cost(run_["family"].cfg, 128, 2048)["bytes"] / 819e9
    assert read("ling_kda_chunk_roofline.decode", run_) == pytest.approx(100 * least / 1.92)
    assert 5.0 < 100 * least / 1.92 < 5.1  # 96.6 ms at the HBM peak over 1.92 s of kernels
    out = capsys.readouterr().out
    assert "1920.00 ms of chunk kernels a call against 96.61 ms" in out and "(4.95 TFLOP and 79.12 GB a call; the bytes bind)" in out
    assert f"the kda/chunk scope whole 1980.00 ms a call, 60.00 of them XLA's around the kernels: {100 * least / 1.98:.2f}% by the scope's time" in out
    # a program that cuts a row into longer chunks is held to the same floor
    longer = [[name.replace("_c128_", "_c256_"), start, ns] for name, start, ns in CHUNKS]
    assert read("ling_kda_chunk_roofline.decode", make_run(longer + PROMPT + LOOP, scope_table={})) == pytest.approx(100 * least / 1.92)
    # the step's kernels: 4.8 ms a step over six layers against 3.93 ms for the state's bytes
    assert "the step's kernels 4.800 ms a step against 3.933 ms to read and write the state (3.22 GB) once" in out and "81.9% of their roofline" in out


def test_the_delta_layers_share_of_a_step_is_read_by_the_programs_own_scopes(capsys):
    run_ = make_run(CALL, scope_table=TABLE)
    assert read("ling_kda_step_ms.decode", run_) == pytest.approx(4.8 + 2 + 0.5 + 0.5)
    out = capsys.readouterr().out
    assert "ms a step: kda/update 4.800, kda/proj 2.000, kda/conv 0.500, kda/out 0.500\n" in out  # the attention's 3.2 ms are not the layers'
    # a program that opens no ``kda/update`` (the parent): nothing to read, though other scopes are there
    none = {k: ({**v, "layer": "dense_mlp"} if v["layer"].startswith("kda/") else v) for k, v in TABLE.items()}
    assert read("ling_kda_step_ms.decode", make_run(CALL, scope_table=none)) is None


def test_the_prompt_passs_expert_kernels_are_read_apart_from_a_steps(capsys):
    run_ = make_run(CALL, scope_table=TABLE)
    cfg = run_["family"].cfg
    least = 6 * cost.expert_kernel_cost(cfg, 128 * 2048)["flops"] / 197e12
    assert read("ling_moe_experts_roofline.decode", run_) == pytest.approx(100 * least / 0.4)
    assert 47 < 100 * least / 0.4 < 48  # 188 ms at the bf16 peak over 400 ms of the prompt pass's kernels; a step's 2.3 s are left out
    assert "400.00 ms of the prompt pass's expert kernels a call against 188.37 ms" in capsys.readouterr().out
    # with no table of scopes every kernel of the name counts: the share errs low and says nothing false
    assert read("ling_moe_experts_roofline.decode", make_run(CALL, scope_table={})) == pytest.approx(100 * least / (0.4 + 255 * 0.009))


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_with_nothing_to_read_returns_none(name):
    assert read(name, make_run([["fusion.1", 0.0, 100 * MS], ["convolution.2", 100 * MS, 50 * MS]], scope_table={})) is None
    assert read(name, {**make_run(CALL), "trace": None}) is None
    assert read(name, {**make_run(CALL), "counters": {"steps": 3}}) is None  # a train cell's counters


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("config,cell", [("deepseek-v3-ep16", "dsv3-ep16-decode-b64"), ("brumby-14b-pp8", "brumby-pp8-decode-b32-p4k"),
                                         ("perceiver-ar-small-16k", "ar16k-decode-b64")])
def test_another_familys_cell_reads_none(name, config, cell):
    """A configuration without a delta layer has nothing these readers count, whatever its trace holds."""
    assert read(name, make_run(CALL, config=config, cell=cell, scope_table=TABLE)) is None
