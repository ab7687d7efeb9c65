"""The Brumby family behind the harness: found by name, meets the ``decode``
driver's interface on a tiny cell with no edit to a driver, hands program and
reference a gate that remembers, and ``correct`` is true for the sound
program, false for a program whose prompt pass drops its carry, loses the state
at the hand-off, decodes every step at position 0 or is handed the leaves as
drawn, and false for the fp8 control."""

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import control, run
from benchmarks.families import brumby

DATA = run.os.path.join(run.HERE, "tests", "data")
BENCH = run.os.path.join(DATA, "BENCHMARK-brumby.json")
CELL = "tiny-brumby-decode"
REAL = "brumby-pp8-decode-b32-p4k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def run_tiny(seed=2**31 + 3):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.3, trace=0, keep_trace=None)
    return run.run_cell(args, jax.devices(), data_root=DATA, bench_path=BENCH)


def family_of(name, root=run.HERE):
    config = run.load_json("configs", name, root)
    return run.importlib.import_module(f"benchmarks.families.{config['family']}").Family(config), config


def test_the_real_configuration_builds_the_published_widths_and_one_stage_of_eight():
    family, config = family_of("brumby-14b-pp8")
    c = family.model().config
    assert (c.hidden_size, c.num_hidden_layers, c.vocab_size, c.intermediate_size) == (5120, 5, 151936, 17408)
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim, c.qk_norm, c.rope_theta) == (40, 8, 128, True, 1000000)
    assert c.layer_types == ("power_retention",) * 5 and c.first_k_dense_replace == 5 and c.rope_scaling is None
    assert (config["retention_degree"], c.tie_word_embeddings, c.rms_norm_eps) == (2, False, 1e-6)
    assert c.max_position_embeddings == 32768 and family.latents == family.seq_len == 32768
    assert family.cfg["init_scale"] == 0.02 and family.forget_range == (1e-4, 1e-2)
    shapes = family.param_shapes(family.model())
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) == 3_207_594_280  # 6.42 GB of bfloat16
    assert config["reduced"] == ["num_hidden_layers"] and config["published"] == {"num_hidden_layers": 40}
    assert set(config["changed"]) == {"num_hidden_layers"}
    assert {"degree", "gate", "normalisation", "qk_norm_and_rotary", "state_form", "seeded_gate", "dtypes", "init_scale", "context"} <= set(config["assumed"])
    assert config["dtypes"]["retention_state"] == "float32" and "eight pipeline stages of 5" in config["deployment"] and "24 GB" in config["trains"]
    bench = json.load(open(run.os.path.join(run.CHECKOUT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "brumby-14b-pp8")
    cell = next(w for w in bench["workloads"] if w["name"] == REAL)
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200 and entry["source"] == config["source"]
    assert entry["reduced"] == ["num_hidden_layers"] and entry["file"] == "benchmarks/configs/brumby-14b-pp8.json"
    assert cell["why"] == run.load_json("workloads", REAL)["why"] and cell["chips"] == 1 and cell["traffic"] == run.load_json("workloads", REAL)["traffic"]
    assert len(bench["workloads"]) >= 9 and not any(w["chips"] == 4 for w in bench["workloads"])
    ours = [m for m in bench["per_layer"] if m["name"].startswith("brumby_")]
    assert len(ours) == 4 and all(m["workloads"] == [REAL] for m in ours)
    assert all(run.os.path.isfile(run.os.path.join(run.HERE, "layers", m["name"] + ".py")) for m in ours)
    listed = {m["name"] for group in ("end_to_end", "per_layer") for m in bench[group] if REAL in m.get("workloads", ())}
    assert listed == {m["name"] for m in ours} | {"gen_tokens_per_s", "device_idle_share.decode", "prefill_device_share.decode",
                                                  "decode_step_device_ms.decode", "unscoped_device_share.decode"}


def test_every_key_of_the_catalog_row_is_in_the_file_unchanged_but_the_depth():
    if not run.os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Brumby-14B-Base")
    config = run.load_json("configs", "brumby-14b-pp8")
    assert config["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if k not in config or config[k] != v] == ["num_hidden_layers"]
    assert row["config"]["num_hidden_layers"] == config["published"]["num_hidden_layers"] == 40


def test_the_cell_fits_the_decode_drivers_arithmetic():
    family, _ = family_of("brumby-14b-pp8")
    decode = run.load_module("drivers", "decode")
    p = run.load_json("workloads", REAL)["params"]
    assert (p["batch_size"], p["prompt_len"], p["new_tokens"], p["cache_dtype"], p["num_latents"]) == (32, 4096, 256, "bfloat16", 1)
    assert decode.plain_tokens(family, p) == p["new_tokens"] == 256  # nothing slides: every served token is compared
    assert p["checked_rows"] * p["new_tokens"] == 1024
    prompts = family.prompts(2**31 + 7, 0, 4, 32)
    assert prompts.shape == (4, 32) and prompts.max() < 151936 and prompts.min() >= 0


def test_a_program_without_the_retention_layer_is_told_so():
    """On a parent checkout the program's configuration refuses the layer's kind: the family stops with a message, at once."""
    family, _ = family_of("tiny-brumby", DATA)
    family.cfg["a_key_the_program_lacks"] = 1
    with pytest.raises(SystemExit, match="refuses the file's: .*a_key_the_program_lacks"):
        family.model()
    family.cfg.pop("a_key_the_program_lacks")
    family.cfg["layer_types"] = ("a_kind_the_program_lacks",) * len(family.cfg["layer_types"])  # what a parent commit makes of "power_retention"
    with pytest.raises(SystemExit, match="refuses the file's: layer_types"):
        family.model()


def test_a_configuration_the_family_does_not_build_is_refused():
    config = run.load_json("configs", "tiny-brumby", DATA)
    for wrong in (dict(attention_bias=True), dict(sliding_window=64), dict(layer_types=["power_retention", "full_attention", "power_retention"]),
                  dict(rope_scaling={"factor": 4}), dict(retention_degree=3)):
        with pytest.raises(ValueError, match="families/brumby.py"):
            brumby.Family({**config, **wrong})


def test_the_gates_bias_is_made_to_remember():
    """``b_g = logit(1 - r)``, ``r`` log-uniform in the file's range read off the seeded leaf; other leaves as drawn."""
    noise = 0.02 * jax.random.normal(jax.random.PRNGKey(0), (4096,))
    forget = 1 - jax.nn.sigmoid(brumby.remembering("b_g", noise, 0.02, 1e-4, 1e-2))
    assert 1e-4 <= float(forget.min()) < 1.2e-4 and 0.8e-2 < float(forget.max()) <= 1e-2
    quartiles = np.quantile(np.log(np.asarray(forget)), [0.25, 0.5, 0.75])  # log-uniform: the quartiles of the logarithm lie evenly
    assert np.allclose(quartiles, np.log(1e-4) + np.array([0.25, 0.5, 0.75]) * np.log(100), atol=0.15)
    assert brumby.remembering("w_g", noise, 0.02, 1e-4, 1e-2) is noise and brumby.remembering("w_q", noise, 0.02, 1e-4, 1e-2) is noise
    # as drawn every state halves a token; made to remember, the slowest fifth of the heads keep a third of a state over the cell's 4096 tokens
    assert float(jax.nn.sigmoid(noise).max()) < 0.53 and float(jnp.mean((1 - forget) ** 4096 > 0.3)) > 0.2


def test_sound_run_is_correct_and_reports_its_metrics(capsys):
    result = run_tiny()
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    out = capsys.readouterr().out
    assert "36 served tokens of 3 rows" in out and "0 more came after a cache slid" in out


def _halves_without_a_carry(real):
    def form(q, k, v, log_gamma, state=None):
        half = q.shape[1] // 2
        y0, _ = real(q[:, :half], k[:, :half], v[:, :half], log_gamma[:, :half])
        y1, end = real(q[:, half:], k[:, half:], v[:, half:], log_gamma[:, half:])  # from an empty state: the carry is dropped
        return jnp.concatenate([y0, y1], axis=1), end

    return form


@pytest.mark.parametrize("wrong", ["a_carry_dropped_in_the_prompt_pass", "a_state_lost_at_the_hand_off", "position_0_at_every_step",
                                   "the_leaves_as_drawn", "a_token_altered"])
def test_a_program_that_loses_its_past_is_not_correct(monkeypatch, wrong):
    from perceiver_io_tpu import generation
    from perceiver_io_tpu.core import cache, retention
    from perceiver_io_tpu.models.text import decoder_lm

    if wrong == "a_carry_dropped_in_the_prompt_pass":  # the prompt pass run as two halves, the second from an empty state
        monkeypatch.setattr(retention, "power_retention_reference", _halves_without_a_carry(retention.power_retention_reference))
    elif wrong == "a_state_lost_at_the_hand_off":  # the prompt pass hands the steps an empty state of the right length
        monkeypatch.setattr(decoder_lm, "RetentionState",
                            lambda s, z, length: cache.RetentionState(jnp.zeros_like(s), jnp.zeros_like(z), length))
    elif wrong == "position_0_at_every_step":  # a state read as having no length
        monkeypatch.setattr(decoder_lm, "RecurrentState", (cache.RecurrentState, cache.RetentionState))
    elif wrong == "the_leaves_as_drawn":  # the reference remembers, the program forgets
        from benchmarks.families import deepseek_v3

        monkeypatch.setattr(brumby.Family, "generate_fn", deepseek_v3.Family.generate_fn)
    else:
        monkeypatch.setattr(generation, "_sample", lambda logits, rng, config: (jnp.argmax(logits, axis=-1) + 1) % logits.shape[-1])
    assert run_tiny()["correct"] is False


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 9])
def test_the_fp8_control_is_not_correct(seed):
    cell = run.load_json("workloads", CELL, DATA)
    config = run.load_json("configs", cell["config"], DATA)
    checks = control.control_checks(cell, config, seed, "fp8")
    assert [c["name"] for c in checks if not c["ok"]] == ["served_logit_gap"], checks
