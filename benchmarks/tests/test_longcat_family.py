"""The LongCat-Flash family behind the harness: found by name, meets the
``decode`` driver's interface on a tiny cell with no edit to a driver
(``decode_routed`` judges the gaps' bulk), and ``correct`` is true for the
sound program, false for a program without the identity experts' add, false
for one without a scale factor and false for the fp8 control."""

import argparse
import dataclasses
import json

import jax
import numpy as np
import pytest

from benchmarks import control, run

DATA = run.os.path.join(run.HERE, "tests", "data")
BENCH = run.os.path.join(DATA, "BENCHMARK-longcat.json")
CELL = "tiny-longcat-decode"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def run_tiny(seed=2**31 + 3):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.3, trace=0, keep_trace=None)
    return run.run_cell(args, jax.devices(), data_root=DATA, bench_path=BENCH)


def family_of(name, root=run.HERE):
    config = run.load_json("configs", name, root)
    return run.importlib.import_module(f"benchmarks.families.{config['family']}").Family(config), config


def test_the_real_configuration_builds_the_published_widths():
    family, config = family_of("longcat-flash-ep32")
    c = family.model().config
    assert (c.hidden_size, c.num_attention_heads, c.q_lora_rank, c.kv_lora_rank) == (6144, 64, 1536, 512)
    assert (c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim, c.mla_scale_q_lora, c.mla_scale_kv_lora) == (128, 64, 128, True, True)
    assert (c.n_routed_experts, c.zero_expert_num, c.n_held_experts, c.held_experts_start, c.num_experts_per_tok) == (512, 256, 16, 0, 12)
    assert (c.moe_intermediate_size, c.intermediate_size, c.n_shared_experts, c.first_k_dense_replace) == (2048, 12288, 0, 0)
    assert (c.scoring_func, c.routed_scaling_factor, c.block, c.num_hidden_layers) == ("softmax_biased", 6.0, "shortcut", 4)
    assert (c.vocab_size, c.max_position_embeddings, c.rope_theta, c.rms_norm_eps) == (16384, 131072, 1e7, 1e-5)
    assert c.rope_scaling is None and c.layer_types is None and c.num_nextn_predict_layers == 0
    assert family.cfg["init_scale"] == 0.02 and family.latents == family.seq_len == 131072
    shapes = family.param_shapes(family.model())
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert 5.1727e9 < n < 5.1728e9  # 10.35 GB of bfloat16 (the count of lib/longcat_cost.py plus the norms' scales and the biases)
    assert shapes["params"]["layer_3"]["moe"]["gate"].shape == (6144, 768)
    assert shapes["params"]["layer_3"]["moe"]["gate_bias"].dtype == np.float32
    assert config["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert config["published"] == {"num_layers": 28, "n_routed_experts": 512, "vocab_size": 131072}
    assert {"mla_scale_form", "norm_topk_prob", "router", "block", "rotary", "init_scale", "dtypes"} <= set(config["assumed"])
    assert "32 chips share each layer" in config["deployment"] and config["router_width"] == 768
    bench = json.load(open(run.os.path.join(run.CHECKOUT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "longcat-flash-ep32")
    cell = next(w for w in bench["workloads"] if w["name"] == "longcat-ep32-decode-b64")
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200 and entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"] and entry["file"] == "benchmarks/configs/longcat-flash-ep32.json"
    assert cell["why"] == run.load_json("workloads", "longcat-ep32-decode-b64")["why"] and cell["chips"] == 1
    ours = [m for m in bench["per_layer"] if m["name"].startswith("longcat_")]
    assert len(ours) == 4 and all(m["workloads"] == ["longcat-ep32-decode-b64"] for m in ours)
    assert all(run.os.path.isfile(run.os.path.join(run.HERE, "layers", m["name"] + ".py")) for m in ours)


def test_every_key_of_the_catalog_row_is_in_the_file_unchanged_but_the_three_cuts():
    if not run.os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LongCat-Flash-Chat")
    config = run.load_json("configs", "longcat-flash-ep32")
    assert config["source"] == row["source_url"]
    assert sorted(k for k, v in row["config"].items() if config.get(k) != v) == ["n_routed_experts", "num_layers", "vocab_size"]


def test_the_cell_fits_the_decode_drivers_arithmetic():
    family, _ = family_of("longcat-flash-ep32")
    decode = run.load_module("drivers", "decode")
    p = run.load_json("workloads", "longcat-ep32-decode-b64")["params"]
    assert decode.plain_tokens(family, p) == p["new_tokens"] == 512  # nothing slides: every served token is compared
    assert p["num_latents"] + decode.plain_tokens(family, p) - 1 == 512  # what the driver asks the reference for
    prompts = family.prompts(2**31 + 7, 0, 4, 32)
    assert prompts.shape == (4, 32) and prompts.max() < 16384 and prompts.min() >= 0


def test_a_program_without_the_block_is_told_so():
    """On a parent checkout the program's configuration lacks the block's keys: the family stops with a message, at once."""
    family, _ = family_of("tiny-longcat", DATA)
    family.cfg["a_key_the_program_lacks"] = 1
    with pytest.raises(SystemExit, match="has no .'a_key_the_program_lacks'."):
        family.model()


def test_sound_run_is_correct_and_reports_its_metrics(capsys):
    result = run_tiny()
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    out = capsys.readouterr().out
    assert "36 served tokens of 3 rows" in out and "0 more came after a cache slid" in out


@pytest.mark.parametrize("wrong", [dict(zero_expert_num=0), dict(mla_scale_kv_lora=False), dict(routed_scaling_factor=1.0)],
                         ids=["no_identity_experts", "an_unscaled_latent", "an_unscaled_branch"])
def test_a_program_that_leaves_a_mechanism_out_is_not_correct(monkeypatch, wrong):
    from benchmarks.families import longcat_flash

    real = longcat_flash.Family.model

    def other(self):
        model = real(self)
        config = dataclasses.replace(model.config, **wrong)
        if "zero_expert_num" in wrong:  # the router keeps its 24 outputs: the last 8 become experts held elsewhere
            config = dataclasses.replace(config, n_routed_experts=model.config.n_routed_experts + model.config.zero_expert_num)
        return model.clone(config=config)

    monkeypatch.setattr(longcat_flash.Family, "model", other)
    assert run_tiny()["correct"] is False


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 9])
def test_the_fp8_control_is_not_correct(seed):
    cell = run.load_json("workloads", CELL, DATA)
    config = run.load_json("configs", cell["config"], DATA)
    checks = control.control_checks(cell, config, seed, "fp8")
    # by the limit that holds the precision alone: the widest gap's limit is for a token that is not the program's
    assert [c["name"] for c in checks if not c["ok"]] == ["served_gap_p99"], checks
    assert [c["name"] for c in checks] == ["served_gap_p99", "served_logit_gap"]


def test_the_seeded_bias_reaches_program_and_reference_at_the_files_scale(monkeypatch):
    """``router_bias_scale``: a program handed the seeded bias as drawn, beside a reference handed it scaled, is not correct."""
    from benchmarks.families import deepseek_v3, longcat_flash

    family, config = family_of("tiny-longcat", DATA)
    assert family.router_bias_scale == config["router_bias_scale"] == 0.05
    assert family_of("longcat-flash-ep32")[0].router_bias_scale == 0.1
    monkeypatch.setattr(longcat_flash.Family, "generate_fn", deepseek_v3.Family.generate_fn)
    assert run_tiny()["correct"] is False


def test_why_the_seeded_bias_is_scaled():
    """The router at its published width under seeded weights (numpy, 512
    tokens): with the bias as ``lib/weights.py`` draws it (0.02) some output
    takes nearly every token and a token keeps 2 of its own 12 picks; at the
    file's 0.1 of that no output takes a tenth of the tokens, a token keeps 10
    of its 12, and the held experts see the even routing's 0.25 pairs a token
    within a factor of two."""
    rng = np.random.default_rng(0)
    tokens, width, top_k = 512, 768, 12
    logits = rng.standard_normal((tokens, 6144), dtype=np.float32) @ (0.02 * rng.standard_normal((6144, width), dtype=np.float32))
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    own = np.argsort(-p, -1)[:, :top_k]
    bias = 0.02 * rng.standard_normal(width).astype(np.float32)

    def routed(scale):
        chosen = np.argsort(-(p + scale * bias), -1)[:, :top_k]
        load = np.bincount(chosen.ravel(), minlength=width) / tokens
        kept = np.mean([len(set(a) & set(b)) for a, b in zip(chosen, own)])
        return load.max(), kept, load[:16].sum()

    hottest, kept, _ = routed(1.0)
    assert hottest > 0.9 and kept < 4
    hottest, kept, local = routed(family_of("longcat-flash-ep32")[0].router_bias_scale)
    assert hottest < 0.1 and 9 < kept < 12 and 0.125 < local < 0.5
