"""The readers of the dots3 cell's per-layer metrics, on a small hand-made trace
with the program's own table of scopes handed in: the kernels found by their
names and held to the mechanism's definition, the selection and a step's
mechanism by the program's scopes; and a reader with nothing to read, as on the
parent's program or another family's cell, returns ``None`` and does not raise."""

import pytest

from benchmarks import run
from benchmarks.lib import dots3_cost as cost
from benchmarks.lib import trace
from benchmarks.lib.peaks import load_peaks

MS = 1e6  # ns
NAMES = ["dots3_prefill_mfu.decode", "dots3_step_hbm_share.decode", "dots3_index_score_roofline.decode", "dots3_sparse_attend_roofline.decode",
         "dots3_window_flash_roofline.decode", "dots3_moe_experts_roofline.decode", "dots3_select_device_ms.decode", "dots3_dsa_step_ms.decode"]


def make_run(events, calls=1, config="dots3-note-ep8", cell="dots3-ep8-decode-b4-p32k", **more):
    cfg = run.load_json("configs", config)
    family = run.importlib.import_module(f"benchmarks.families.{cfg['family']}").Family(cfg)
    window = (0.0, 30000 * MS)
    busy = trace.busy_ns(trace.clip(events, window)) / 1e9
    return {"cell": run.load_json("workloads", cell), "family": family, "peaks": load_peaks("TPU v5 lite"),
            "counters": {"calls": calls}, "chips": 1, "trace": {"devices": {"/device:TPU:0": events}, "host": []},
            "trace_window": window, "busy_s": busy, "window_s": 30.0, **more}


# one call: a prompt pass of 8 s (index scores 800 ms, selection 400, masked flash 3000, window flash 300, experts 1500, the rest
# XLA's 2000), then 255 steps in 3.06 s: 12 ms a step, of which the mechanism 1.5 (index 0.1, score 0.4, select 0.5, gather 0.2, attend 0.3)
PROMPT = [["dsa_index_scores_q2048_kv32768_h64.3", 0.0, 800 * MS], ["dsa_select_q2048_kv32768_k2048.4", 800 * MS, 380 * MS],
          ["fusion.2", 1180 * MS, 20 * MS], ["flash_mla_masked_fwd_q32768_kv32768_h16.5", 1200 * MS, 3000 * MS],
          ["flash_mla_window_fwd_q32768_kv32768_h16_w513.6", 4200 * MS, 300 * MS], ["moe_experts_prefill_m10240_k5120_n1536.7", 4500 * MS, 1500 * MS],
          ["fusion.1", 6000 * MS, 2000 * MS]]
LOOP = [["while.99", 8000 * MS, 3060 * MS], ["fusion.10", 8000 * MS, 255 * 0.1 * MS], ["fusion.11", 8025.5 * MS, 255 * 0.4 * MS],
        ["sort.12", 8127.5 * MS, 255 * 0.5 * MS], ["gather.13", 8255 * MS, 255 * 0.2 * MS], ["fusion.14", 8306 * MS, 255 * 0.3 * MS],
        ["fusion.15", 8382.5 * MS, 255 * 10.5 * MS]]
CALL = PROMPT + LOOP
TABLE = {
    "dsa_index_scores_q2048_kv32768_h64.3": {"phase": "prefill", "layer": "dsa/score"},
    "dsa_select_q2048_kv32768_k2048.4": {"phase": "prefill", "layer": "dsa/select"},
    "fusion.2": {"phase": "prefill", "layer": "dsa/select"},
    "flash_mla_masked_fwd_q32768_kv32768_h16.5": {"phase": "prefill", "layer": "dsa/attend"},
    "flash_mla_window_fwd_q32768_kv32768_h16_w513.6": {"phase": "prefill", "layer": "mla/window"},
    "moe_experts_prefill_m10240_k5120_n1536.7": {"phase": "prefill", "layer": "moe/experts"},
    "fusion.1": {"phase": "prefill", "layer": "dense_mlp"},
    "while.99": {"phase": "decode", "layer": "<unscoped>", "container": True},
    "fusion.10": {"phase": "decode", "layer": "dsa/index"},
    "fusion.11": {"phase": "decode", "layer": "dsa/step_score"},
    "sort.12": {"phase": "decode", "layer": "dsa/step_select"},
    "gather.13": {"phase": "decode", "layer": "dsa/step_gather"},
    "fusion.14": {"phase": "decode", "layer": "dsa/step_attend"},
    "fusion.15": {"phase": "decode", "layer": "moe/experts"},
}
TABLE = {name: {"opcode": "fusion", "path": "", "inherited": False, "container": False, **row} for name, row in TABLE.items()}


def read(name, run_):
    return run.load_module("layers", name).read(run_)


@pytest.fixture(scope="module")
def cfg():
    return make_run(CALL)["family"].cfg


def test_the_kernels_are_held_to_the_definition_whatever_their_names_say(cfg, capsys):
    run_ = make_run(CALL, scope_table=TABLE)
    peak, hbm = run_["peaks"]["bf16_flops_per_s"], run_["peaks"]["hbm_bytes_per_s"]
    scores = cost.index_score_cost(cfg, 4, 32768)
    assert read("dots3_index_score_roofline.decode", run_) == pytest.approx(100 * 2 * max(scores["flops"] / peak, scores["bytes"] / hbm) / 0.8)
    attend = cost.sparse_attend_cost(cfg, 4, 32768)
    got = read("dots3_sparse_attend_roofline.decode", run_)
    assert got == pytest.approx(100 * 2 * max(attend["flops"] / peak, attend["bytes"] / hbm) / 3.0) and got < 12  # the dense rectangle is 8 x the definition
    window = cost.window_attend_cost(cfg, 4, 32768)
    assert read("dots3_window_flash_roofline.decode", run_) == pytest.approx(100 * 3 * max(window["flops"] / peak, window["bytes"] / hbm) / 0.3)
    experts = cost.expert_kernel_cost(cfg, 4 * 32768)
    assert read("dots3_moe_experts_roofline.decode", run_) == pytest.approx(100 * 4 * max(experts["flops"] / peak, experts["bytes"] / hbm) / 1.5)
    # other tilings' names are the same kernels
    renamed = [[name.replace("q2048", "q4096").replace("_h16", "_h32"), t, d] for name, t, d in CALL]
    assert read("dots3_index_score_roofline.decode", make_run(renamed)) == pytest.approx(read("dots3_index_score_roofline.decode", run_))
    assert read("dots3_sparse_attend_roofline.decode", make_run(renamed)) == pytest.approx(got)
    assert "the dense causal rectangle is" in capsys.readouterr().out


def test_the_phases_shares_by_the_programs_scopes(cfg):
    run_ = make_run(CALL, scope_table=TABLE)
    assert read("dots3_prefill_mfu.decode", run_) == pytest.approx(100 * cost.prefill_flops(cfg, 4, 32768) / 8.0 / run_["peaks"]["bf16_flops_per_s"])
    least = cost.decode_scan_bytes(cfg, 4, 32768, 256) / run_["peaks"]["hbm_bytes_per_s"]
    assert read("dots3_step_hbm_share.decode", run_) == pytest.approx(100 * least / (255 * 12.0e-3))
    assert read("dots3_select_device_ms.decode", run_) == pytest.approx(400.0)
    assert read("dots3_dsa_step_ms.decode", run_) == pytest.approx(1.5)


def test_two_calls_read_as_one(cfg):
    twice = CALL + [[name, t + 11060 * MS, d] for name, t, d in CALL]
    one, two = make_run(CALL, scope_table=TABLE), make_run(twice, calls=2, scope_table=TABLE)
    for name in NAMES:
        assert read(name, two) == pytest.approx(read(name, one)), name


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_with_nothing_to_read_returns_none(name):
    other = make_run(CALL, config="deepseek-v3-ep16", cell="dsv3-ep16-decode-b64", scope_table=TABLE)
    assert read(name, other) is None  # another family's cell
    bare = [["fusion.1", 0.0, 100 * MS], ["while.99", 100 * MS, 200 * MS]]
    table = {"fusion.1": {**TABLE["fusion.1"]}, "while.99": {**TABLE["while.99"]}}
    if name not in ("dots3_prefill_mfu.decode",):
        assert read(name, make_run(bare, scope_table=table)) is None  # the parent's program: no such kernel, no such scope
    untraced = make_run(CALL)
    untraced["trace"] = None
    assert read(name, untraced) is None


def test_every_reader_is_declared_for_the_cell_alone():
    import json

    bench = json.load(open(run.os.path.join(run.CHECKOUT, "BENCHMARK.json")))
    ours = {m["name"]: m for m in bench["per_layer"] if m["name"].startswith("dots3_")}
    assert sorted(ours) == sorted(NAMES)
    assert all(m["workloads"] == ["dots3-ep8-decode-b4-p32k"] and m["moves"] == "gen_tokens_per_s" for m in ours.values())
    assert all(ours[n]["unit"] == ("ms" if n.endswith("_ms.decode") else "%") for n in NAMES)
