"""The decoder-only family behind the harness: found by name, meets the
``decode`` driver's interface on a tiny cell with no edit to the driver, and
``correct`` is true for the sound program, false for a broken one and false
for the fp8 control."""

import argparse

import jax
import numpy as np
import pytest

from benchmarks import control, run

DATA = run.os.path.join(run.HERE, "tests", "data")
BENCH = run.os.path.join(DATA, "BENCHMARK-dsv3.json")
CELL = "tiny-dsv3-decode"


def run_tiny(seed=2**31 + 3):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.3, trace=0, keep_trace=None)
    return run.run_cell(args, jax.devices(), data_root=DATA, bench_path=BENCH)


def family_of(name, root=run.HERE):
    config = run.load_json("configs", name, root)
    return run.importlib.import_module(f"benchmarks.families.{config['family']}").Family(config), config


def test_the_real_configuration_builds_the_published_widths():
    family, config = family_of("deepseek-v3-ep16")
    c = family.model().config
    assert (c.hidden_size, c.num_attention_heads, c.q_lora_rank, c.kv_lora_rank) == (7168, 128, 1536, 512)
    assert (c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim) == (128, 64, 128)
    assert (c.intermediate_size, c.moe_intermediate_size, c.num_experts_per_tok) == (18432, 2048, 8)
    assert (c.n_routed_experts, c.n_held_experts, c.held_experts_start) == (256, 16, 0)  # the router keeps its width
    assert (c.n_group, c.topk_group, c.routed_scaling_factor, c.n_shared_experts) == (8, 4, 2.5, 1)
    assert (c.num_hidden_layers, c.first_k_dense_replace, c.vocab_size) == (5, 1, 16160)
    assert c.rope_scaling.factor == 40 and c.rope_scaling.original_max_position_embeddings == 4096
    assert family.cfg["init_scale"] == 0.02 and family.latents == family.seq_len == 163840  # the published context
    shapes = family.param_shapes(family.model())
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert 4.565e9 < n < 4.567e9  # 9.13 GB of bfloat16
    dtypes = {str(s.dtype) for s in jax.tree.leaves(shapes)}
    assert dtypes == {"bfloat16", "float32"}  # float32: the router's biases alone
    # the published numbers stay in the file, beside what is held here
    assert config["published"] == {"num_hidden_layers": 61, "first_k_dense_replace": 3, "n_routed_experts": 256,
                                   "vocab_size": 129280}
    assert "16 chips share each layer" in config["deployment"]


def test_the_cell_fits_the_decode_drivers_arithmetic():
    family, _ = family_of("deepseek-v3-ep16")
    decode = run.load_module("drivers", "decode")
    p = run.load_json("workloads", "dsv3-ep16-decode-b64")["params"]
    assert p["num_latents"] == p["prompt_len"]  # every position passes the whole stack
    assert decode.plain_tokens(family, p) == p["new_tokens"]  # nothing slides: every served token is compared
    prompts = family.prompts(7, 0, 4, 32)
    assert prompts.shape == (4, 32) and prompts.max() < family.cfg["vocab_size"] and prompts.min() >= 0
    assert (prompts != family.prompts(7, 1, 4, 32)).any() and (prompts == family.prompts(7, 0, 4, 32)).all()


def test_sound_run_is_correct_and_reports_its_metrics(capsys):
    result = run_tiny()
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    out = capsys.readouterr().out
    assert "36 served tokens of 3 rows" in out and "0 more came after a cache slid" in out


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from perceiver_io_tpu import generation

    monkeypatch.setattr(generation, "_sample",
                        lambda logits, rng, config: (jax.numpy.argmax(logits, axis=-1) + 1) % logits.shape[-1])
    assert run_tiny()["correct"] is False


def test_a_stale_cache_is_not_correct(monkeypatch):
    """The absorbed step reading one slot too few (the new token's own row
    left out) serves tokens the reference does not put first."""
    from perceiver_io_tpu.core import mla

    real = mla.latent_decode_attention
    monkeypatch.setattr(mla, "latent_decode_attention",
                        lambda q, cache, scale: real(q, cache.replace(length=cache.length - 1), scale))
    assert run_tiny()["correct"] is False


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 9])
def test_the_fp8_control_is_not_correct(seed):
    cell = run.load_json("workloads", CELL, DATA)
    config = run.load_json("configs", cell["config"], DATA)
    checks = control.control_checks(cell, config, seed, "fp8")
    assert [c["name"] for c in checks if not c["ok"]] == ["served_logit_gap"], checks


def _one_line_strings():
    """Every string of ``BENCHMARK.json`` that the driver holds to 1 to 200 printable characters on one line."""
    with open(run.os.path.join(run.CHECKOUT, "BENCHMARK.json")) as f:
        bench = run.json.load(f)
    for group, key in (("configs", "why"), ("configs", "source"), ("workloads", "why"), ("per_layer", "layer")):
        for entry in bench[group]:
            yield pytest.param(entry[key], id=f"{group}-{entry['name']}-{key}")


@pytest.mark.parametrize("text", _one_line_strings())
def test_the_benchmark_files_lines_fit_the_drivers_form(text):
    # the driver refused this PR once for a ``why`` of 205 characters, before any run
    assert 1 <= len(text) <= 200 and text.isascii() and text.isprintable()
