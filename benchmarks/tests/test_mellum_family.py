"""The Mellum family behind the harness: found by name, meets the ``decode``
driver's interface on a tiny cell with no edit to the driver, and ``correct``
is true for the sound program, false for a program whose window layers see
everything before them, false for a stale ring and false for the fp8
control."""

import argparse
import json

import jax
import numpy as np
import pytest

from benchmarks import control, run

DATA = run.os.path.join(run.HERE, "tests", "data")
BENCH = run.os.path.join(DATA, "BENCHMARK-mellum.json")
CELL = "tiny-mellum-decode"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def run_tiny(seed=2**31 + 3):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.3, trace=0, keep_trace=None)
    return run.run_cell(args, jax.devices(), data_root=DATA, bench_path=BENCH)


def family_of(name, root=run.HERE):
    config = run.load_json("configs", name, root)
    return run.importlib.import_module(f"benchmarks.families.{config['family']}").Family(config), config


def test_the_real_configuration_builds_the_published_widths():
    family, config = family_of("mellum2-12b-pp4")
    c = family.model().config
    assert (c.hidden_size, c.num_attention_heads, c.num_key_value_heads, c.head_dim) == (2304, 32, 4, 128)
    assert (c.n_routed_experts, c.n_held_experts, c.num_experts_per_tok, c.moe_intermediate_size) == (64, 64, 8, 896)
    assert (c.n_shared_experts, c.first_k_dense_replace, c.scoring_func) == (0, 0, "softmax")
    assert (c.vocab_size, c.sliding_window, c.max_position_embeddings, c.rope_theta) == (98304, 1024, 131072, 500000.0)
    assert c.layer_types == ("sliding_attention",) * 3 + ("full_attention",) + ("sliding_attention",) * 3 + ("full_attention",)
    y = c.rope_scaling
    assert (y.factor, y.beta_fast, y.beta_slow, y.original_max_position_embeddings) == (16.0, 32.0, 1.0, 8192)
    assert y.attention_factor == 1.2772588722239782
    assert family.cfg["init_scale"] == 0.02 and family.latents == family.seq_len == 131072
    shapes = family.param_shapes(family.model())
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert 3.794e9 < n < 3.796e9  # 7.59 GB of bfloat16 (the count of lib/mellum_cost.py plus the norms' scales)
    assert {str(s.dtype) for s in jax.tree.leaves(shapes)} == {"bfloat16"}
    assert config["reduced"] == ["num_hidden_layers"] and config["published"] == {"num_hidden_layers": 28}
    assert {"qk_norm", "window_convention", "init_scale", "dtypes"} <= set(config["assumed"])
    assert "mtp_head" in config["changed"] and "four pipeline stages" in config["deployment"]


def test_every_key_of_the_catalog_row_is_in_the_file_unchanged_but_the_depth():
    if not run.os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    config = run.load_json("configs", "mellum2-12b-pp4")
    assert config["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if config.get(k) != v] == ["num_hidden_layers"]


def test_the_cell_fits_the_decode_drivers_arithmetic():
    family, _ = family_of("mellum2-12b-pp4")
    decode = run.load_module("drivers", "decode")
    p = run.load_json("workloads", "mellum2-pp4-decode-b32")["params"]
    assert decode.plain_tokens(family, p) == p["new_tokens"] == 256  # nothing slides: every served token is compared
    assert p["num_latents"] + decode.plain_tokens(family, p) - 1 == 256  # what the driver asks the reference for
    prompts = family.prompts(2**31 + 7, 0, 4, 32)
    assert prompts.shape == (4, 32) and prompts.max() < 98304 and prompts.min() >= 0
    assert (prompts != family.prompts(2**31 + 7, 1, 4, 32)).any()


def test_sound_run_is_correct_and_reports_its_metrics(capsys):
    result = run_tiny()
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    out = capsys.readouterr().out
    assert "36 served tokens of 3 rows" in out and "0 more came after a cache slid" in out


def test_window_layers_run_as_full_layers_are_not_correct(monkeypatch):
    """At these tiny widths (init 0.3: attention far from uniform) the check
    sees a wrong mask; PERF.md says what it reads at the published widths."""
    from benchmarks.families import mellum

    real = mellum.Family.model

    def full_everywhere(self):
        model = real(self)
        return model.clone(config=run.importlib.import_module("dataclasses").replace(model.config, sliding_window=4096))

    monkeypatch.setattr(mellum.Family, "model", full_everywhere)
    assert run_tiny()["correct"] is False


def test_a_stale_ring_is_not_correct(monkeypatch):
    """A decode step that leaves the new token's own key out of every cache
    (one slot too few) serves tokens the reference does not put first."""
    from perceiver_io_tpu.core import gqa

    real = gqa.cached_decode_attention
    monkeypatch.setattr(gqa, "cached_decode_attention",
                        lambda q, cache, scale: real(q, cache.replace(length=cache.length - 1), scale))
    assert run_tiny()["correct"] is False


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 9])
def test_the_fp8_control_is_not_correct(seed):
    cell = run.load_json("workloads", CELL, DATA)
    config = run.load_json("configs", cell["config"], DATA)
    checks = control.control_checks(cell, config, seed, "fp8")
    assert [c["name"] for c in checks if not c["ok"]] == ["served_logit_gap"], checks
