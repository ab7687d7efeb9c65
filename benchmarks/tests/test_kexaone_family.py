"""The K-EXAONE family behind the harness: found by name, meets the ``decode``
driver's interface on a tiny cell with no edit to the driver (``decode_routed``
runs that driver's ``DecodeRun`` and judges the gaps' bulk; the generator
drafts with the module and still returns prompts with their new tokens), and
``correct`` is true for the sound program, false for a program that keeps a
rejected draft, false for window layers that see everything before them and false for the fp8 control."""

import argparse
import json

import jax
import numpy as np
import pytest

from benchmarks import control, run

DATA = run.os.path.join(run.HERE, "tests", "data")
BENCH = run.os.path.join(DATA, "BENCHMARK-exaone.json")
CELL = "tiny-exaone-decode"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def run_tiny(seed=2**31 + 3):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.3, trace=0, keep_trace=None)
    return run.run_cell(args, jax.devices(), data_root=DATA, bench_path=BENCH)


def family_of(name, root=run.HERE):
    config = run.load_json("configs", name, root)
    return run.importlib.import_module(f"benchmarks.families.{config['family']}").Family(config), config


def test_the_real_configuration_builds_the_published_widths():
    family, config = family_of("k-exaone-236b-ep8")
    c = family.model().config
    assert (c.hidden_size, c.num_attention_heads, c.num_key_value_heads, c.head_dim) == (6144, 64, 8, 128)
    assert (c.n_routed_experts, c.n_held_experts, c.held_experts_start, c.num_experts_per_tok) == (128, 16, 0, 8)
    assert (c.moe_intermediate_size, c.intermediate_size, c.n_shared_experts, c.first_k_dense_replace) == (2048, 18432, 1, 1)
    assert (c.scoring_func, c.n_group, c.topk_group, c.routed_scaling_factor) == ("sigmoid", 1, 1, 2.5)
    assert (c.vocab_size, c.sliding_window, c.max_position_embeddings, c.rope_theta, c.rms_norm_eps) == (19200, 128, 262144, 1e6, 1e-5)
    assert c.layer_types == ("sliding_attention",) * 3 + ("full_attention", "sliding_attention") and c.rope_scaling is None
    assert (c.num_nextn_predict_layers, c.mtp_layer_types, c.qk_norm, c.full_attention_rotary) == (1, ("full_attention",), True, False)
    assert family.cfg["init_scale"] == 0.02 and family.latents == family.seq_len == 262144
    shapes = family.param_shapes(family.model())
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert 4.543e9 < n < 4.544e9  # 9.09 GB of bfloat16 (the count of lib/kexaone_cost.py plus the norms' scales and biases)
    assert "mtp" in shapes["params"] and shapes["params"]["mtp"]["w_eh"].shape == (12288, 6144)
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 153600}
    assert {"norm_placement", "qk_norm", "rotary_layers", "mtp_module", "mtp_feed_forward", "window_convention", "init_scale",
            "dtypes"} <= set(config["assumed"])
    assert "8 that share each layer" in config["deployment"] and "4:1" in config["changed"]["num_hidden_layers"]["why"]
    bench = json.load(open(run.os.path.join(run.CHECKOUT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "k-exaone-236b-ep8")
    cell = next(w for w in bench["workloads"] if w["name"] == "kexaone-ep8-mtp-decode-b64")
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200 and entry["source"] == config["source"]
    assert cell["why"] == run.load_json("workloads", "kexaone-ep8-mtp-decode-b64")["why"]


def test_every_key_of_the_catalog_row_is_in_the_file_unchanged_but_the_three_cuts():
    if not run.os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "K-EXAONE-236B-A23B")
    config = run.load_json("configs", "k-exaone-236b-ep8")
    assert config["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if config.get(k) != v] == ["num_experts", "num_hidden_layers", "vocab_size"]


def test_the_cell_fits_the_decode_drivers_arithmetic():
    family, _ = family_of("k-exaone-236b-ep8")
    decode = run.load_module("drivers", "decode")
    p = run.load_json("workloads", "kexaone-ep8-mtp-decode-b64")["params"]
    assert decode.plain_tokens(family, p) == p["new_tokens"] == 512  # nothing slides: every served token is compared
    assert p["num_latents"] + decode.plain_tokens(family, p) - 1 == 512  # what the driver asks the reference for
    prompts = family.prompts(2**31 + 7, 0, 4, 32)
    assert prompts.shape == (4, 32) and prompts.max() < 19200 and prompts.min() >= 0


def test_a_program_without_the_module_is_told_so():
    """On a parent checkout the program's configuration lacks the module's keys: the family stops with a message, at once."""
    family, _ = family_of("tiny-exaone", DATA)
    family.cfg["a_key_the_program_lacks"] = 1
    with pytest.raises(SystemExit, match="has no .'a_key_the_program_lacks'."):
        family.model()


def test_sound_run_is_correct_and_reports_its_metrics(capsys):
    result = run_tiny()
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    out = capsys.readouterr().out
    assert "36 served tokens of 3 rows" in out and "0 more came after a cache slid" in out


def test_keeping_a_rejected_draft_is_not_correct(monkeypatch):
    """A generator that keeps both positions whatever the stack said serves the module's drafts as tokens."""
    from perceiver_io_tpu import generation

    real = generation._speculative_accept

    def accept_all(config, drafts, q_logits, p_logits, rng, done):
        agree = jax.nn.one_hot(drafts[:, 0], p_logits.shape[-1]) * 1e4
        return real(config, drafts, q_logits, p_logits.at[:, 0].add(agree), rng, done)

    monkeypatch.setattr(generation, "_speculative_accept", accept_all)
    assert run_tiny()["correct"] is False


def test_window_layers_run_as_full_layers_are_not_correct(monkeypatch):
    from benchmarks.families import exaone_moe

    real = exaone_moe.Family.model

    def full_everywhere(self):
        model = real(self)
        return model.clone(config=run.importlib.import_module("dataclasses").replace(model.config, sliding_window=4096))

    monkeypatch.setattr(exaone_moe.Family, "model", full_everywhere)
    assert run_tiny()["correct"] is False


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 9])
def test_the_fp8_control_is_not_correct(seed):
    cell = run.load_json("workloads", CELL, DATA)
    config = run.load_json("configs", cell["config"], DATA)
    checks = control.control_checks(cell, config, seed, "fp8")
    # by the limit that holds the precision alone: the widest gap's limit is for a token that is not the program's
    assert [c["name"] for c in checks if not c["ok"]] == ["served_gap_p99"], checks
    assert [c["name"] for c in checks] == ["served_gap_p99", "served_logit_gap"]
