"""The Ling family behind the harness: found by name, meets the
``decode_routed`` driver's interface on a tiny cell with no edit to a driver,
hands program and reference a gate that remembers and a scaled router bias, and
``correct`` is true for the sound program, false for a program whose prompt pass
drops its carry, loses the state or the windows at the hand-off, leaves the
correction term out, holds the wrong group of experts or is handed the leaves
as drawn, and false for the fp8 control."""

import argparse
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import control, run
from benchmarks.families import ling

DATA = run.os.path.join(run.HERE, "tests", "data")
BENCH = run.os.path.join(DATA, "BENCHMARK-ling.json")
CELL = "tiny-ling-decode"
REAL = "ling3-ep4-decode-b128-p2k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OURS = {"ling_step_hbm_share.decode", "ling_prefill_mfu.decode", "ling_kda_chunk_roofline.decode", "ling_kda_step_ms.decode",
        "ling_moe_experts_roofline.decode"}


def run_tiny(seed=2**31 + 3):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.3, trace=0, keep_trace=None)
    return run.run_cell(args, jax.devices(), data_root=DATA, bench_path=BENCH)


def family_of(name, root=run.HERE):
    config = run.load_json("configs", name, root)
    return run.importlib.import_module(f"benchmarks.families.{config['family']}").Family(config), config


def test_the_real_configuration_builds_the_published_widths_and_one_chip_of_four():
    family, config = family_of("ling3-flash-ep4")
    c = family.model().config
    assert (c.hidden_size, c.num_hidden_layers, c.vocab_size, c.intermediate_size, c.moe_intermediate_size) == (2560, 7, 39296, 6144, 768)
    assert (c.num_attention_heads, c.head_dim, c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim) == (32, 128, None, 512, 128, 64, 128)
    assert c.layer_types == ("kda", "kda", "kda", "kda", "latent_attention", "kda", "kda") and c.first_k_dense_replace == 1
    assert (c.n_routed_experts, c.n_held_experts, c.held_experts_start, c.n_shared_experts, c.num_experts_per_tok) == (512, 128, 0, 1, 8)
    assert (c.n_group, c.topk_group, c.routed_scaling_factor, c.scoring_func, c.mla_head_gate) == (8, 4, 2.5, "sigmoid", True)
    assert (c.short_conv_kernel_size, c.kda_lower_bound, c.rope_theta, c.rope_scaling, c.rms_norm_eps) == (4, -5.0, 6e6, None, 1e-6)
    assert c.max_position_embeddings == 131072 and family.latents == family.seq_len == 131072
    assert family.cfg["init_scale"] == 0.02 and family.seeding == dict(forget_min=1e-4, forget_max=1e-2, bias_scale=0.1)
    shapes = family.param_shapes(family.model())
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) == 5_231_790_016  # 10.46 GB of bfloat16
    assert "5 231 790 016" in config["parameters"]
    assert config["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "num_experts", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 42, "first_k_dense_replace": 2, "num_experts": 512, "vocab_size": 157184}
    assert set(config["reduced"]) <= set(config["changed"])
    assert {"layer_order", "kda_gate", "kda_heads", "kda_conv", "kda_output", "mla", "mla_gate", "qk_norm", "router", "swiglu_limits",
            "seeded_gate", "seeded_router_bias", "init_scale", "dtypes"} <= set(config["assumed"])
    assert set(config["left_out"]) == {"vision_tower", "mtp_module", "swiglu_clamp"}
    assert config["dtypes"]["delta_state"] == "float32" and "one chip of four" in config["deployment"] and "one stage of seven" in config["deployment"]
    bench = json.load(open(run.os.path.join(run.CHECKOUT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "ling3-flash-ep4")
    cell = next(w for w in bench["workloads"] if w["name"] == REAL)
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200 and entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"] and entry["file"] == "benchmarks/configs/ling3-flash-ep4.json"
    assert cell["why"] == run.load_json("workloads", REAL)["why"] and cell["chips"] == 1 and cell["traffic"] == run.load_json("workloads", REAL)["traffic"]
    assert len(bench["workloads"]) >= 10 and not any(w["chips"] == 4 for w in bench["workloads"])
    ours = [m for m in bench["per_layer"] if m["name"].startswith("ling_")]
    assert {m["name"] for m in ours} == OURS and all(m["workloads"] == [REAL] and m["moves"] == "gen_tokens_per_s" for m in ours)
    assert all(run.os.path.isfile(run.os.path.join(run.HERE, "layers", m["name"] + ".py")) for m in ours)
    listed = {m["name"] for group in ("end_to_end", "per_layer") for m in bench[group] if REAL in m.get("workloads", ())}
    assert listed == OURS | {"gen_tokens_per_s", "device_idle_share.decode", "prefill_device_share.decode", "decode_step_device_ms.decode",
                             "decode_attention_device_ms.decode", "moe_glue_device_ms.decode", "mla_expand_device_ms.decode",
                             "unscoped_device_share.decode"}


def test_every_key_of_the_catalog_row_is_in_the_file_unchanged_but_the_four_cuts():
    if not run.os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ling-3.0-flash-VL")
    config = run.load_json("configs", "ling3-flash-ep4")
    assert config["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if k not in config or config[k] != v] == config["reduced"]
    assert {k: row["config"][k] for k in config["reduced"]} == config["published"]
    # the layer order by the file's rule, over the model whole: 35 delta layers and 7 of latent attention, the sixth of every six
    whole = ling.published_layer_types({**config, "first_published_layer": 0, "num_hidden_layers": 42})
    assert whole.count("kda") == 35 and [i for i, kind in enumerate(whole) if kind == "latent_attention"] == [5, 11, 17, 23, 29, 35, 41]
    assert whole[1:8] == config["layer_types"]


def test_the_cell_fits_the_decode_drivers_arithmetic():
    family, _ = family_of("ling3-flash-ep4")
    decode = run.load_module("drivers", "decode")
    cell = run.load_json("workloads", REAL)
    p = cell["params"]
    assert (p["batch_size"], p["prompt_len"], p["new_tokens"], p["cache_dtype"], p["num_latents"]) == (128, 2048, 256, "bfloat16", 1)
    assert decode.plain_tokens(family, p) == p["new_tokens"] == 256  # nothing slides: every served token is compared
    assert p["checked_rows"] * p["new_tokens"] == 1024 and cell["driver"] == "decode_routed" and set(cell["limits"]) == {"served_gap_p99", "served_logit_gap"}
    prompts = family.prompts(2**31 + 7, 0, 4, 32)
    assert prompts.shape == (4, 32) and prompts.max() < 39296 and prompts.min() >= 0


def test_a_program_without_the_delta_layer_is_told_so():
    """On a parent checkout the program's configuration refuses the file's keys: the family stops with a message, at once."""
    family, _ = family_of("tiny-ling", DATA)
    family.cfg["a_key_the_program_lacks"] = 1
    with pytest.raises(SystemExit, match="refuses the file's: .*a_key_the_program_lacks"):
        family.model()
    family.cfg.pop("a_key_the_program_lacks")
    family.cfg["layer_types"] = ("a_kind_the_program_lacks",) * len(family.cfg["layer_types"])  # what a parent commit makes of "kda"
    with pytest.raises(SystemExit, match="refuses the file's: layer_types"):
        family.model()


def test_a_configuration_the_family_does_not_build_is_refused():
    config = run.load_json("configs", "tiny-ling", DATA)
    clamped = [0] * 42
    clamped[3] = 4
    for wrong in (dict(score_function="softmax"), dict(kda_safe_gate=False), dict(use_kda_lora=True), dict(q_lora_rank=8),
                  dict(gated_attention_proj_granularity_type="element_wise"), dict(layer_types=["kda"] * 7), dict(layer_group_size=4),
                  dict(norm_topk_prob=False), dict(expert_swiglu_limit_list=clamped), dict(first_published_layer=2)):
        with pytest.raises(ValueError, match="families/ling.py"):
            ling.Family({**config, **wrong})


def test_sound_run_is_correct_and_reports_its_metrics(capsys):
    result = run_tiny()
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    out = capsys.readouterr().out
    assert "36 served tokens of 3 rows" in out and "0 more came after a cache slid" in out


def _halves_without_a_carry(real):
    def form(q, k, v, g, beta, state=None):
        half = q.shape[1] // 2
        o0, _ = real(q[:, :half], k[:, :half], v[:, :half], g[:, :half], beta[:, :half])
        o1, end = real(q[:, half:], k[:, half:], v[:, half:], g[:, half:], beta[:, half:])  # from an empty state: the carry is dropped
        return jnp.concatenate([o0, o1], axis=1), end

    return form


def _without_the_correction(q, k, v, g, beta, s):
    s = s * jnp.exp(g)[:, :, None, :]
    s = s + (beta[..., None] * v)[..., :, None] * k[..., None, :]
    return jnp.einsum("bhvc,bhc->bhv", s, q, precision="highest"), s


@pytest.mark.parametrize("wrong", ["a_carry_dropped_in_the_prompt_pass", "a_state_lost_at_the_hand_off", "the_windows_lost_at_the_hand_off",
                                   "the_correction_term_left_out", "the_held_experts_offset_by_one_group", "the_leaves_as_drawn",
                                   "a_token_altered"])
def test_a_program_that_loses_its_past_is_not_correct(monkeypatch, wrong):
    from perceiver_io_tpu import generation
    from perceiver_io_tpu.core import cache
    from perceiver_io_tpu.core import kda as kda_core
    from perceiver_io_tpu.models.text import decoder_lm
    from perceiver_io_tpu.ops import kda

    def hand_on(change):
        real = decoder_lm._Decoder._caches
        monkeypatch.setattr(decoder_lm._Decoder, "_caches", lambda self, *args: tuple(
            change(c) if isinstance(c, cache.DeltaState) else c for c in real(self, *args)))

    if wrong == "a_carry_dropped_in_the_prompt_pass":  # the prompt pass run as two halves, the second from an empty state
        monkeypatch.setattr(kda_core, "kda_reference", _halves_without_a_carry(kda_core.kda_reference))
    elif wrong == "a_state_lost_at_the_hand_off":  # the prompt pass hands the steps an empty state
        hand_on(lambda state: state.replace(s=jnp.zeros_like(state.s)))
    elif wrong == "the_windows_lost_at_the_hand_off":
        hand_on(lambda state: jax.tree.map(jnp.zeros_like, state).replace(s=state.s))
    elif wrong == "the_correction_term_left_out":  # gated linear attention in the delta rule's place, prompt pass and steps alike
        monkeypatch.setattr(kda, "kda_update", _without_the_correction)
        monkeypatch.setattr(kda_core, "kda_update", _without_the_correction)
    elif wrong == "the_held_experts_offset_by_one_group":  # the weights of the first two groups answer for the second and the third
        real = ling.Family.model
        monkeypatch.setattr(ling.Family, "model", lambda self: real(self).clone(config=dataclasses.replace(real(self).config, held_experts_start=4)))
    elif wrong == "the_leaves_as_drawn":  # the reference remembers, the program forgets
        from benchmarks.families import deepseek_v3

        monkeypatch.setattr(ling.Family, "generate_fn", deepseek_v3.Family.generate_fn)
    else:
        monkeypatch.setattr(generation, "_sample", lambda logits, rng, config: (jnp.argmax(logits, axis=-1) + 1) % logits.shape[-1])
    assert run_tiny()["correct"] is False


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 9])
def test_the_fp8_control_is_not_correct(seed):
    cell = run.load_json("workloads", CELL, DATA)
    config = run.load_json("configs", cell["config"], DATA)
    checks = control.control_checks(cell, config, seed, "fp8")
    assert "served_gap_p99" in [c["name"] for c in checks if not c["ok"]], checks
