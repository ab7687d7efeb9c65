"""The Phi-4-mini-flash family behind the harness: found by name, meets the
``decode`` driver's interface on a tiny cell with no edit to a driver, builds the
published widths uncut, and ``correct`` is true for the sound program, false for
a program whose second softmax map is dropped, whose memory is taken after the
gate or whose cut prompt pass hands the readers another memory, and false for
the fp8 control."""

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import control, run
from benchmarks.families import phi4flash

DATA = run.os.path.join(run.HERE, "tests", "data")
BENCH = run.os.path.join(DATA, "BENCHMARK-phi4flash.json")
CELL = "tiny-phi4flash-decode"
REAL = "phi4flash-decode-b32-p8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def run_tiny(seed=2**31 + 3):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.3, trace=0, keep_trace=None)
    return run.run_cell(args, jax.devices(), data_root=DATA, bench_path=BENCH)


def family_of(name, root=run.HERE):
    config = run.load_json("configs", name, root)
    return run.importlib.import_module(f"benchmarks.families.{config['family']}").Family(config), config


def test_the_real_configuration_builds_the_published_widths_uncut():
    family, config = family_of("phi4-mini-flash")
    c = family.model().config
    assert (c.hidden_size, c.num_hidden_layers, c.vocab_size, c.intermediate_size) == (2560, 32, 200064, 10240)
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim, c.sliding_window) == (40, 20, 64, 512)
    assert (c.mamba_expand, c.mamba_d_state, c.mamba_dt_rank, c.mamba_d_conv, c.mamba_inner_norms) == (2, 16, 160, 4, False)
    assert c.tie_word_embeddings and c.first_k_dense_replace == 32 and c.layer_norm_eps == 1e-5 and c.differential_attention
    assert (c.memory_layer, c.shared_cache_layer, c.prompt_layers, c.max_position_embeddings) == (16, 17, 17, 262144)
    assert c.layer_types[14:20] == ("mamba", "sliding_attention", "mamba", "full_attention", "gmu", "cross_attention")
    shapes = family.param_shapes(family.model())
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n == 3_852_562_944 and "head" not in shapes["params"]  # 7.71 GB of bfloat16, the table once
    assert shapes["params"]["layer_17"]["attn"]["w_qkv"].shape == (2560, 5120) and shapes["params"]["layer_19"]["attn"]["w_q"].shape == (2560, 2560)
    assert config["reduced"] == [] and config["changed"] == {} and len(config["sources"]) == 5
    assert {"layer_order", "mamba_sizes", "memory", "head_pairing", "lambda", "subnorm", "attention_bias", "window_edge", "dtypes",
            "init_scale", "seeded_recurrence", "what_correct_cannot_see"} <= set(config["assumed"])
    assert "nothing is cut" in config["deployment"] and "no backward" in config["trains"] and "3 852 562 944" in config["parameters"]
    bench = json.load(open(run.os.path.join(run.CHECKOUT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "phi4-mini-flash")
    cell = next(w for w in bench["workloads"] if w["name"] == REAL)
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200 and entry["source"] == config["source"]
    assert entry["reduced"] == [] and entry["file"] == "benchmarks/configs/phi4-mini-flash.json"
    assert cell["why"] == run.load_json("workloads", REAL)["why"] and cell["chips"] == 1 and cell["traffic"] == "decode-b32-p8192-n256"
    assert not any(w["chips"] == 4 for w in bench["workloads"])
    ours = [m for m in bench["per_layer"] if m["name"].startswith("phi4flash_")]
    assert len(ours) == 8 and all(m["workloads"] == [REAL] and m["moves"] == "gen_tokens_per_s" for m in ours)
    assert all(run.os.path.isfile(run.os.path.join(run.HERE, "layers", m["name"] + ".py")) for m in ours)
    shared = [m["name"] for m in bench["per_layer"] if REAL in m.get("workloads", ()) and not m["name"].startswith("phi4flash_")]
    assert sorted(shared) == sorted(["device_idle_share.decode", "prefill_device_share.decode", "decode_step_device_ms.decode",
                                     "unscoped_device_share.decode", "setup_import_s", "setup_trace_lower_s", "setup_compile_s",
                                     "setup_cache_misses", "setup_unattributed_s"])


def test_every_key_of_the_catalog_row_is_in_the_file_unchanged():
    if not run.os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Phi-4-mini-flash-reasoning")
    config = run.load_json("configs", "phi4-mini-flash")
    assert config["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if k not in config or config[k] != v] == []


def test_the_cell_fits_the_decode_drivers_arithmetic():
    family, _ = family_of("phi4-mini-flash")
    decode = run.load_module("drivers", "decode")
    cell = run.load_json("workloads", REAL)
    p = cell["params"]
    assert (p["batch_size"], p["prompt_len"], p["new_tokens"], p["cache_dtype"], p["num_latents"]) == (32, 8192, 256, "bfloat16", 1)
    assert decode.plain_tokens(family, p) == p["new_tokens"] == 256  # nothing slides: every served token is compared
    assert p["checked_rows"] * p["new_tokens"] == 1024 and set(cell["limits"]) == {"served_logit_gap"}
    prompts = family.prompts(2**31 + 7, 0, 4, 32)
    assert prompts.shape == (4, 32) and prompts.max() < 200064 and prompts.min() >= 0


def test_a_program_without_the_layer_kinds_is_told_so():
    """On a parent checkout the program's configuration refuses the file's: the family stops with a message, at once."""
    family, _ = family_of("tiny-phi4flash", DATA)
    family.cfg["a_key_the_program_lacks"] = 1
    with pytest.raises(SystemExit, match="refuses the file's"):
        family.model()
    family, _ = family_of("tiny-phi4flash", DATA)
    family.cfg["layer_types"] = tuple("no_such_kind" if k == "gmu" else k for k in family.cfg["layer_types"])
    with pytest.raises(SystemExit, match="refuses the file's"):
        family.model()


def test_a_configuration_the_family_does_not_build_is_refused():
    config = run.load_json("configs", "tiny-phi4flash", DATA)
    for wrong in (dict(mlp_bias=True), dict(lm_head_bias=True), dict(mb_per_layer=3), dict(hidden_act="gelu")):
        with pytest.raises(ValueError, match="families/phi4flash.py"):
            phi4flash.Family({**config, **wrong})


def test_sound_run_is_correct_and_reports_its_metrics(capsys):
    result = run_tiny()
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    out = capsys.readouterr().out
    assert "36 served tokens of 3 rows" in out and "0 more came after a cache slid" in out


@pytest.mark.parametrize("wrong", ["one_softmax_map", "the_memory_after_the_gate", "the_memory_negated_at_the_last_position", "a_token_altered"])
def test_a_program_that_reads_the_wrong_thing_is_not_correct(monkeypatch, wrong):
    from perceiver_io_tpu import generation
    from perceiver_io_tpu.core import diff_attention, ssm
    from perceiver_io_tpu.models.text import decoder_lm

    # (cross layers that read the cache one row stale move a logit by a hundredth of what these do and no served
    # token: ``correct`` does not see them, ``tests/test_phi4flash.py`` does, on the logits themselves)
    if wrong == "one_softmax_map":
        monkeypatch.setattr(diff_attention.DifferentialAttention, "_lam", lambda self: jnp.zeros((), jnp.float32))
    elif wrong == "the_memory_after_the_gate":
        real_out = ssm.MambaMixer._out

        def gated(self, y, x, z):
            out = real_out(self, y, x, z)
            return (out[0], out[1] * jax.nn.silu(z.astype(jnp.float32))) if self.memory else out

        monkeypatch.setattr(ssm.MambaMixer, "_out", gated)
    elif wrong == "the_memory_negated_at_the_last_position":  # the cut pass hands the readers another memory than the memory layer left
        real_last = decoder_lm.DecoderLanguageModel.last_position
        monkeypatch.setattr(decoder_lm.DecoderLanguageModel, "last_position", lambda self, x, memory, rows: real_last(self, x, -memory, rows))
    else:
        monkeypatch.setattr(generation, "_sample", lambda logits, rng, config: (jnp.argmax(logits, axis=-1) + 1) % logits.shape[-1])
    assert run_tiny()["correct"] is False


@pytest.mark.parametrize("seed", [1, 2**31 + 9])
def test_the_fp8_control_is_not_correct_and_a_wrong_reference_is_seen(seed):
    cell = run.load_json("workloads", CELL, DATA)
    config = run.load_json("configs", cell["config"], DATA)
    checks = control.control_checks(cell, config, seed, "fp8")
    assert [c["name"] for c in checks if not c["ok"]] == ["served_logit_gap"], checks
    for wrong in ("lam0", "memory_after_gate"):  # the builder's controls of what the limit sees
        checks = control.control_checks(cell, config, seed, f"float32:{wrong}")
        assert [c["name"] for c in checks if not c["ok"]] == ["served_logit_gap"], (wrong, checks)


def test_the_convolution_taps_are_handed_on_to_program_and_reference_alike():
    """``conv_w`` reaches both as the file's ``seeded_conv_centre`` around the seeded noise, float32, beside Jamba's three leaves; nothing else moves."""
    family, config = family_of("phi4-mini-flash")
    noise = jnp.full((4, 8), 0.02, jnp.bfloat16)
    taps = family._remembering("conv_w", noise)
    assert taps.dtype == jnp.float32 and np.allclose(taps, config["seeded_conv_centre"] + np.float32(noise)) and config["seeded_conv_centre"] >= 0.25
    assert np.allclose(family._remembering("d_skip", noise), 1.0 + np.float32(noise))
    assert family._remembering("w_in", noise) is noise and family._remembering("conv_b", noise) is noise
