"""The dots3 family behind the harness: found by name, meets the
``decode_routed`` driver's interface on a tiny cell with no edit to a driver,
hands program and reference the scaled output projections, and ``correct`` is
true for the sound program, false for a program whose selection, window, gate,
ring or index cache is wrong, and false for the fp8 control."""

import argparse
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import control, run
from benchmarks.families import dots3
from benchmarks.lib import dots3_cost
from benchmarks.reference import dots3 as reference

DATA = run.os.path.join(run.HERE, "tests", "data")
BENCH = run.os.path.join(DATA, "BENCHMARK-dots3.json")
CELL = "tiny-dots3-decode"
REAL = "dots3-ep8-decode-b4-p32k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OURS = {"dots3_prefill_mfu.decode", "dots3_step_hbm_share.decode", "dots3_index_score_roofline.decode", "dots3_sparse_attend_roofline.decode",
        "dots3_window_flash_roofline.decode", "dots3_moe_experts_roofline.decode", "dots3_select_device_ms.decode", "dots3_dsa_step_ms.decode"}


def run_tiny(seed=2**31 + 3):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.3, trace=0, keep_trace=None)
    return run.run_cell(args, jax.devices(), data_root=DATA, bench_path=BENCH)


def family_of(name, root=run.HERE):
    config = run.load_json("configs", name, root)
    return run.importlib.import_module(f"benchmarks.families.{config['family']}").Family(config), config


def test_the_real_configuration_builds_the_published_widths_and_one_chip_of_eight():
    family, config = family_of("dots3-note-ep8")
    c = family.model().config
    assert (c.hidden_size, c.num_hidden_layers, c.vocab_size, c.intermediate_size, c.moe_intermediate_size) == (5120, 5, 19008, 13824, 1536)
    assert (c.num_attention_heads, c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim) == (128, 1024, 512, 128, 64, 128)
    assert (c.index_n_heads, c.index_head_dim, c.index_topk, c.sliding_window_size) == (64, 128, 2048, 513)
    assert (c.swa_num_attention_heads, c.swa_q_lora_rank, c.swa_kv_lora_rank, c.swa_qk_nope_head_dim, c.swa_qk_rope_head_dim, c.swa_v_head_dim) == (64, 1024, 1024, 192, 64, 128)
    assert c.layer_types == ("full_attention", "sliding_attention", "sliding_attention", "sliding_attention", "full_attention")
    assert (c.n_routed_experts, c.n_held_experts, c.held_experts_start, c.n_shared_experts, c.num_experts_per_tok) == (256, 32, 0, 1, 8)
    assert (c.n_group, c.topk_group, c.routed_scaling_factor, c.scoring_func, c.first_k_dense_replace) == (1, 1, 1.0, "sigmoid", 1)
    assert (c.rope_theta, c.swa_rope_theta, c.rope_scaling, c.rms_norm_eps) == (8e7, 50000.0, None, 1e-5)
    assert c.mla_scale_q_lora and c.mla_scale_kv_lora and c.mla_head_gate and c.windowed_latent and c.latent_ring_slots == 544
    assert c.max_position_embeddings == 524288 and family.latents == family.seq_len == 524288
    assert family.cfg["init_scale"] == 0.02 and family.out_scale == 4.0
    shapes = family.param_shapes(family.model())
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) == dots3_cost.held_params(family.cfg) == 4_087_154_176  # 8.17 GB of bfloat16
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 46, "n_routed_experts": 256, "vocab_size": 152064}
    assert set(config["reduced"]) <= set(config["changed"]) and config["held_layers"] == [0, 2, 3, 4, 5]
    assert {"init_scale", "seeded_attention_out_scale", "dtypes", "apply_mla_qkv_lora_rescale", "attention_gate_type", "indexer", "window",
            "rotary", "router", "context", "cache"} <= set(config["assumed"])
    assert set(config["not_built"]) == {"towers", "drafting_module"} and "one chip of 8" in config["deployment"]
    bench = json.load(open(run.os.path.join(run.CHECKOUT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "dots3-note-ep8")
    cell = next(w for w in bench["workloads"] if w["name"] == REAL)
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200 and entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"] and entry["file"] == "benchmarks/configs/dots3-note-ep8.json"
    assert cell["why"] == run.load_json("workloads", REAL)["why"] and cell["chips"] == 1
    assert cell["traffic"] == run.load_json("workloads", REAL)["traffic"] == "decode-b4-p32768-n256"
    assert len(bench["workloads"]) >= 12 and not any(w["chips"] == 4 for w in bench["workloads"])
    ours = [m for m in bench["per_layer"] if m["name"].startswith("dots3_")]
    assert {m["name"] for m in ours} == OURS and all(m["workloads"] == [REAL] and m["moves"] == "gen_tokens_per_s" for m in ours)
    assert all(run.os.path.isfile(run.os.path.join(run.HERE, "layers", m["name"] + ".py")) for m in ours)
    listed = {m["name"] for group in ("end_to_end", "per_layer") for m in bench[group] if REAL in m.get("workloads", ())}
    assert listed == OURS | {"gen_tokens_per_s", "device_idle_share.decode", "prefill_device_share.decode", "decode_step_device_ms.decode",
                             "moe_glue_device_ms.decode", "mla_expand_device_ms.decode", "unscoped_device_share.decode", "setup_import_s",
                             "setup_trace_lower_s", "setup_compile_s", "setup_cache_misses", "setup_unattributed_s"}


def test_every_key_of_the_catalog_row_is_in_the_file_unchanged_but_the_three_cuts():
    if not run.os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "dots3-note-prev")
    config = run.load_json("configs", "dots3-note-ep8")
    assert config["source"] == row["source_url"]
    assert sorted(k for k, v in row["config"].items() if k not in config or config[k] != v) == sorted(config["reduced"])
    assert {k: row["config"][k] for k in config["reduced"]} == config["published"]
    kinds = config["layer_types"]
    assert len(kinds) == 46 and kinds.count("full_attention") == 13 and kinds[:2] == ["full_attention"] * 2
    assert kinds[2:] == (["sliding_attention"] * 3 + ["full_attention"]) * 11
    assert [kinds[i] for i in config["held_layers"]] == ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]


def test_the_cell_fits_the_decode_drivers_arithmetic():
    family, _ = family_of("dots3-note-ep8")
    decode = run.load_module("drivers", "decode")
    cell = run.load_json("workloads", REAL)
    p = cell["params"]
    assert (p["batch_size"], p["prompt_len"], p["new_tokens"], p["cache_dtype"], p["num_latents"], p["checked_rows"]) == (4, 32768, 256, "bfloat16", 1, 4)
    assert decode.plain_tokens(family, p) == p["new_tokens"] == 256  # nothing slides: every served token is compared
    assert cell["driver"] == "decode_routed" and set(cell["limits"]) == {"served_gap_p99", "served_logit_gap"}
    prompts = family.prompts(2**31 + 7, 0, 4, 32)
    assert prompts.shape == (4, 32) and prompts.max() < 19008 and prompts.min() >= 0


def test_a_program_without_these_layer_kinds_is_told_so():
    """On a parent checkout the program's configuration refuses the file's keys: the family stops with a message, at once."""
    family, _ = family_of("tiny-dots3", DATA)
    family.cfg["a_key_the_program_lacks"] = 1
    with pytest.raises(SystemExit, match="refuses the file's: .*a_key_the_program_lacks"):
        family.model()


def test_a_configuration_the_family_does_not_build_is_refused():
    config = run.load_json("configs", "tiny-dots3", DATA)
    for wrong in (dict(scoring_func="softmax"), dict(topk_method="greedy"), dict(norm_topk_prob=False), dict(rope_scaling={"factor": 2}),
                  dict(apply_mla_qkv_lora_rescale=False), dict(attention_gate_type="elementwise"), dict(swa_attention_gate_type=None),
                  dict(num_key_value_heads=2), dict(held_layers=[1, 2, 3, 4, 5]), dict(held_layers=[0, 2, 3, 4]), dict(held_layers=[0, 2, 3, 4, 60])):
        with pytest.raises(ValueError, match="families/dots3.py"):
            dots3.Family({**config, **wrong})


def test_the_family_hands_on_the_scaled_value_projections_alone():
    flat = {"params/layer_0/attn/w_ukv": jnp.ones((3, 2 * 5)), "params/layer_0/attn/w_o": jnp.ones((2, 2)), "params/layer_1/ffn/shared/w2": jnp.ones((2, 2))}
    out = dots3.scaled(flat, 4.0, {10: (3, 2)})
    np.testing.assert_array_equal(np.asarray(out["params/layer_0/attn/w_ukv"][0]), [1, 1, 1, 4, 4, 1, 1, 1, 4, 4])
    assert float(out["params/layer_0/attn/w_o"][0, 0]) == 1.0 and float(out["params/layer_1/ffn/shared/w2"][0, 0]) == 1.0 and list(out) == list(flat)
    family, _ = family_of("dots3-note-ep8")
    shapes = {"params/layer_0/attn/w_ukv": jnp.zeros((2, 128 * 256)), "params/layer_1/attn/w_ukv": jnp.zeros((2, 64 * 320))}
    assert {k: v.shape for k, v in family.seeded(shapes).items()} == {k: v.shape for k, v in shapes.items()}


def test_sound_run_is_correct_and_reports_its_metrics(capsys):
    result = run_tiny()
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    out = capsys.readouterr().out
    assert "36 served tokens of 3 rows" in out and "0 more came after a cache slid" in out


@pytest.mark.parametrize("wrong", ["the_most_recent_keys_selected", "every_key_selected", "the_index_keys_unrotated", "the_window_one_short",
                                   "the_gate_left_out", "the_index_cache_one_row_stale", "the_ring_seen_whole", "a_token_altered"])
def test_a_program_with_a_fault_of_the_mechanism_is_not_correct(monkeypatch, wrong):
    from perceiver_io_tpu import generation
    from perceiver_io_tpu.core import cache, dsa
    from perceiver_io_tpu.core import mla as mla_core

    def program(**changed):  # the program's configuration alone: the reference keeps the file's
        real = dots3.Family.model
        monkeypatch.setattr(dots3.Family, "model", lambda self: real(self).clone(config=dataclasses.replace(real(self).config, **changed)))

    if wrong == "the_most_recent_keys_selected":  # a score that rises with the key's position: the pass and the steps both take the last ones
        monkeypatch.setattr(dsa, "index_scores", lambda q, k, w: jnp.broadcast_to(
            jnp.arange(k.shape[1], dtype=jnp.float32), (q.shape[0], q.shape[1], k.shape[1])))
    elif wrong == "every_key_selected":
        program(index_topk=10 ** 6)
    elif wrong == "the_index_keys_unrotated":
        monkeypatch.setattr(dsa.SparseLatentAttention, "_index_keys", lambda self, x, pos: self.index_k_norm(self._mm(x, self.w_ik)))
    elif wrong == "the_window_one_short":
        program(sliding_window_size=4)
    elif wrong == "the_gate_left_out":
        monkeypatch.setattr(mla_core.MultiHeadLatentAttention, "_project_out", lambda self, o, x: self._mm(o, self.w_o))
    elif wrong == "the_index_cache_one_row_stale":  # a step scores the cache as it stood before its own key was written
        monkeypatch.setattr(dsa.SparseLatentAttention, "_candidates", staticmethod(
            lambda cache_: jnp.arange(cache_.capacity, dtype=jnp.int32) < cache_.length - 1))
    elif wrong == "the_ring_seen_whole":
        monkeypatch.setattr(cache.LatentRingCache, "visible", lambda self: jnp.ones((self.capacity,), bool))
    else:
        monkeypatch.setattr(generation, "_sample", lambda logits, rng, config: (jnp.argmax(logits, axis=-1) + 1) % logits.shape[-1])
    assert run_tiny()["correct"] is False


def _visible(ring, window):
    t = ring.length - 1
    age = (t - jnp.arange(ring.capacity, dtype=jnp.int32)) % ring.capacity
    return (age < window) & (age <= t)


@pytest.mark.parametrize("wrong", reference.WRONG)
def test_each_wrong_model_of_the_reference_is_not_correct_as_a_control(wrong):
    cell = run.load_json("workloads", CELL, DATA)
    config = run.load_json("configs", cell["config"], DATA)
    checks = control.control_checks(cell, config, 5, f"float32:{wrong}")
    assert not all(c["ok"] for c in checks), checks


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 9])
def test_the_fp8_control_is_not_correct(seed):
    cell = run.load_json("workloads", CELL, DATA)
    config = run.load_json("configs", cell["config"], DATA)
    checks = control.control_checks(cell, config, seed, "fp8")
    assert "served_gap_p99" in [c["name"] for c in checks if not c["ok"]], checks
