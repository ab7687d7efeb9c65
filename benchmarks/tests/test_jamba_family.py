"""The Jamba family behind the harness: found by name, meets the ``decode``
driver's interface on a tiny cell with no edit to a driver, hands program and
reference a recurrence that remembers, and ``correct`` is true for the sound
program, false for a program whose state drops its carry, forgets its window
or is handed the leaves as drawn, and false for the fp8 control."""

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import control, run
from benchmarks.families import jamba

DATA = run.os.path.join(run.HERE, "tests", "data")
BENCH = run.os.path.join(DATA, "BENCHMARK-jamba.json")
CELL = "tiny-jamba-decode"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def run_tiny(seed=2**31 + 3):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.3, trace=0, keep_trace=None)
    return run.run_cell(args, jax.devices(), data_root=DATA, bench_path=BENCH)


def family_of(name, root=run.HERE):
    config = run.load_json("configs", name, root)
    return run.importlib.import_module(f"benchmarks.families.{config['family']}").Family(config), config


def test_the_real_configuration_builds_the_published_widths_uncut():
    family, config = family_of("jamba2-3b")
    c = family.model().config
    assert (c.hidden_size, c.num_hidden_layers, c.vocab_size, c.intermediate_size) == (2560, 28, 65536, 8192)
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim, c.full_attention_rotary) == (20, 1, 128, False)
    assert (c.mamba_expand, c.mamba_d_state, c.mamba_dt_rank, c.mamba_d_conv) == (2, 16, 160, 4)
    assert c.tie_word_embeddings and c.first_k_dense_replace == 28 and c.rms_norm_eps == 1e-6
    assert [i for i, kind in enumerate(c.layer_types) if kind == "full_attention"] == [7, 21]  # i % 14 == 7
    assert set(c.layer_types) == {"mamba", "full_attention"} and c.max_position_embeddings == 262144
    assert family.cfg["init_scale"] == 0.02 and family.latents == family.seq_len == 262144 and family.dt_range == (1e-3, 1e-1)
    shapes = family.param_shapes(family.model())
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n == 3_029_337_472 and "head" not in shapes["params"]  # 6.06 GB of bfloat16, the table once
    mixer = shapes["params"]["layer_0"]["mixer"]
    assert mixer["a_log"].shape == (16, 5120) and mixer["conv_w"].shape == (4, 5120) and mixer["w_in"].shape == (2560, 10240)
    assert config["reduced"] == [] and config["changed"] == {}
    assert {"layer_order", "inner_norms", "head_dim", "seeded_recurrence", "dtypes", "init_scale", "context"} <= set(config["assumed"])
    assert config["dtypes"]["ssm_state"] == "float32" and "nothing is cut" in config["deployment"] and "23 GB" in config["trains"]
    bench = json.load(open(run.os.path.join(run.CHECKOUT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "jamba2-3b")
    cell = next(w for w in bench["workloads"] if w["name"] == "jamba2-3b-decode-b256")
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200 and entry["source"] == config["source"]
    assert entry["reduced"] == [] and entry["file"] == "benchmarks/configs/jamba2-3b.json"
    assert cell["why"] == run.load_json("workloads", "jamba2-3b-decode-b256")["why"] and cell["chips"] == 1
    assert len(bench["workloads"]) == 8 and not any(w["chips"] == 4 for w in bench["workloads"])
    ours = [m for m in bench["per_layer"] if m["name"].startswith("jamba_")]
    assert len(ours) == 4 and all(m["workloads"] == ["jamba2-3b-decode-b256"] for m in ours)
    assert all(run.os.path.isfile(run.os.path.join(run.HERE, "layers", m["name"] + ".py")) for m in ours)


def test_every_key_of_the_catalog_row_is_in_the_file_unchanged():
    if not run.os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "AI21-Jamba2-3B")
    config = run.load_json("configs", "jamba2-3b")
    assert config["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if k not in config or config[k] != v] == []


def test_the_cell_fits_the_decode_drivers_arithmetic():
    family, _ = family_of("jamba2-3b")
    decode = run.load_module("drivers", "decode")
    p = run.load_json("workloads", "jamba2-3b-decode-b256")["params"]
    assert (p["batch_size"], p["prompt_len"], p["new_tokens"], p["cache_dtype"]) == (256, 256, 384, "bfloat16")
    assert decode.plain_tokens(family, p) == p["new_tokens"] == 384  # nothing slides: every served token is compared
    assert p["checked_rows"] * p["new_tokens"] == 1536
    prompts = family.prompts(2**31 + 7, 0, 4, 32)
    assert prompts.shape == (4, 32) and prompts.max() < 65536 and prompts.min() >= 0


def test_a_program_without_the_state_space_layer_is_told_so():
    """On a parent checkout the program's configuration lacks the mixer's keys: the family stops with a message, at once."""
    family, _ = family_of("tiny-jamba", DATA)
    family.cfg["a_key_the_program_lacks"] = 1
    with pytest.raises(SystemExit, match="has no .'a_key_the_program_lacks'."):
        family.model()


def test_a_configuration_the_family_does_not_build_is_refused():
    config = run.load_json("configs", "tiny-jamba", DATA)
    for wrong in (dict(num_experts=2), dict(mamba_proj_bias=True), dict(sliding_window=64)):
        with pytest.raises(ValueError, match="families/jamba.py"):
            jamba.Family({**config, **wrong})


def test_the_leaves_of_a_recurrence_are_made_to_remember():
    """``A`` around -1..-N over the states, the step size log-uniform in the file's range, the skip around 1; other leaves as drawn."""
    noise = 0.02 * jax.random.normal(jax.random.PRNGKey(0), (16, 4096))
    a_log = jamba.remembering("a_log", noise, 0.02, 1e-3, 1e-1)
    assert np.allclose(a_log - noise, np.log(np.arange(1, 17))[:, None], atol=1e-6)
    dt = jax.nn.softplus(jamba.remembering("dt_bias", noise[0], 0.02, 1e-3, 1e-1))
    assert 1e-3 <= float(dt.min()) < 2e-3 and 5e-2 < float(dt.max()) <= 1e-1
    quartiles = np.quantile(np.log(np.asarray(dt)), [0.25, 0.5, 0.75])  # log-uniform: the quartiles of the logarithm lie evenly
    assert np.allclose(quartiles, np.log(1e-3) + np.array([0.25, 0.5, 0.75]) * np.log(100), atol=0.15)
    assert np.allclose(jamba.remembering("d_skip", noise[0], 0.02, 1e-3, 1e-1), 1 + noise[0])
    assert jamba.remembering("w_in", noise, 0.02, 1e-3, 1e-1) is noise
    # as drawn: every state halves a token; made to remember, a tenth of the channels keep half of a state for 300 tokens
    assert float(jnp.exp(-jax.nn.softplus(noise[0]) * jnp.exp(noise[0])).max()) < 0.56
    assert float(jnp.mean(jnp.exp(-dt * 300) > 0.5)) > 0.1


def test_sound_run_is_correct_and_reports_its_metrics(capsys):
    result = run_tiny()
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    out = capsys.readouterr().out
    assert "36 served tokens of 3 rows" in out and "0 more came after a cache slid" in out


def _halves_without_a_carry(real):
    def scan(x, dt, b, c, a, state=None):
        half = x.shape[1] // 2
        if state is not None or half < 2:
            return real(x, dt, b, c, a, state)
        y0, _ = real(x[:, :half], dt[:, :half], b[:, :half], c[:, :half], a)
        y1, h = real(x[:, half:], dt[:, half:], b[:, half:], c[:, half:], a)  # from zero: the carry is dropped
        return jnp.concatenate([y0, y1], axis=1), h

    return scan


@pytest.mark.parametrize("wrong", ["a_carry_dropped_in_the_prompt_pass", "a_window_of_zeros", "the_leaves_as_drawn", "a_token_altered"])
def test_a_program_that_loses_its_past_is_not_correct(monkeypatch, wrong):
    from perceiver_io_tpu import generation
    from perceiver_io_tpu.core import ssm

    if wrong == "a_carry_dropped_in_the_prompt_pass":  # the scan run as two halves, the second from an empty state
        monkeypatch.setattr(ssm, "selective_scan_reference", _halves_without_a_carry(ssm.selective_scan_reference))
    elif wrong == "a_window_of_zeros":  # the prompt pass hands the steps no convolution inputs
        real = ssm.RecurrentState
        monkeypatch.setattr(ssm, "RecurrentState", lambda conv, ssm: real(conv=jnp.zeros_like(conv), ssm=ssm))
    elif wrong == "the_leaves_as_drawn":  # the reference remembers, the program forgets
        from benchmarks.families import deepseek_v3

        monkeypatch.setattr(jamba.Family, "generate_fn", deepseek_v3.Family.generate_fn)
    else:
        monkeypatch.setattr(generation, "_sample", lambda logits, rng, config: (jnp.argmax(logits, axis=-1) + 1) % logits.shape[-1])
    assert run_tiny()["correct"] is False


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 9])
def test_the_fp8_control_is_not_correct(seed):
    cell = run.load_json("workloads", CELL, DATA)
    config = run.load_json("configs", cell["config"], DATA)
    checks = control.control_checks(cell, config, seed, "fp8")
    assert [c["name"] for c in checks if not c["ok"]] == ["served_logit_gap"], checks
