"""``correct`` through the whole of a run, below the look for a chip: true
for the sound program, false with the timed path broken underneath, and
false for the control (the reference in the program's place at fp8)."""

import argparse

import jax
import pytest

from benchmarks import control, run

DATA = run.os.path.join(run.HERE, "tests", "data")
BENCH = run.os.path.join(DATA, "BENCHMARK.json")
CELLS = ["tiny-ar-train", "tiny-image-train"]
DECODE_CELLS = ["tiny-ar-decode"]


def run_tiny(cell, seed=2**31 + 3, trace=0):
    args = argparse.Namespace(workload=cell, seed=seed, seconds=0.3, trace=trace, keep_trace=None)
    return run.run_cell(args, jax.devices(), data_root=DATA, bench_path=BENCH)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_reports_its_metrics(cell, capsys):
    result = run_tiny(cell)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    out = capsys.readouterr().out
    for name in ("loss_gap", "grad_norm_gap", "update_norm_gap", "programs_built_in_window"):
        assert f"check {name}:" in out and "(limit " in out


@pytest.mark.parametrize("cell", CELLS)
def test_a_step_that_returns_its_state_unchanged_is_not_correct(cell, monkeypatch, capsys):
    from perceiver_io_tpu.training import loop

    real = loop.make_train_step

    def broken(loss_fn, **kwargs):
        step = real(loss_fn, **{**kwargs, "jit": False})

        def keep_state(state, batch):
            _, metrics = step(state, batch)
            return state, metrics

        return jax.jit(keep_state)

    monkeypatch.setattr(loop, "make_train_step", broken)
    result = run_tiny(cell)
    assert result["correct"] is False
    assert "check update_norm_gap: 1.0" in capsys.readouterr().out


@pytest.mark.parametrize("cell", CELLS)
def test_part_of_the_batch_left_out_is_not_correct(cell, monkeypatch):
    from perceiver_io_tpu.training import loop

    real = loop.make_train_step

    def half_batch(loss_fn, **kwargs):
        def half_loss(params, batch, rng):
            half = {k: (None if v is None else v[: v.shape[0] // 2]) for k, v in batch.items()}
            return loss_fn(params, half, rng)

        return real(half_loss, **kwargs)

    monkeypatch.setattr(loop, "make_train_step", half_batch)
    assert run_tiny(cell)["correct"] is False


@pytest.mark.parametrize("cell", DECODE_CELLS)
def test_sound_decode_run_is_correct(cell):
    result = run_tiny(cell)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"gen_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("cell", DECODE_CELLS)
def test_a_token_altered_where_it_is_produced_is_not_correct(cell, monkeypatch, capsys):
    from perceiver_io_tpu import generation

    monkeypatch.setattr(generation, "_sample",
                        lambda logits, rng, config: (jax.numpy.argmax(logits, axis=-1) + 1) % logits.shape[-1])
    result = run_tiny(cell)
    assert result["correct"] is False
    assert "check served_logit_gap:" in capsys.readouterr().out


def test_a_cell_whose_caches_slide_is_compared_up_to_the_first_slide(capsys):
    decode = run.load_module("drivers", "decode")
    cell = run.load_json("workloads", "tiny-ar-decode-slide", DATA)
    config = run.load_json("configs", cell["config"], DATA)
    family = run.importlib.import_module(f"benchmarks.families.{config['family']}").Family(config)
    p = cell["params"]
    assert decode.plain_tokens(family, p) == 5  # 64 - 60 + 1 latents, before 160 - 150 + 1 positions
    assert decode.plain_tokens(family, {**p, "prompt_len": 160, "num_latents": 64}) == 1  # the prompt pass alone
    assert decode.plain_tokens(family, {**p, "prompt_len": 100, "num_latents": 32}) == p["new_tokens"]
    result = run_tiny(cell["name"])
    assert result["correct"] is True and result["metrics"]["gen_tokens_per_s"]["value"] > 0
    assert "15 served tokens of 3 rows" in capsys.readouterr().out  # 57 more came after a slide


def test_a_token_altered_before_the_first_slide_is_not_correct(monkeypatch):
    from perceiver_io_tpu import generation

    monkeypatch.setattr(generation, "_sample",
                        lambda logits, rng, config: (jax.numpy.argmax(logits, axis=-1) + 1) % logits.shape[-1])
    assert run_tiny("tiny-ar-decode-slide")["correct"] is False


def test_step_times_line_names_the_stalled_steps():
    train = run.load_module("drivers", "train")
    ends = [0.1, 0.2, 0.3, 0.9, 1.0]
    line = train.step_times_line(0.0, ends)
    assert "median 100.00 ms" in line and "1 of 5 steps over 1.5 x median" in line and "(3, 600.0)" in line
    assert "500 ms over it in all" in line


@pytest.mark.parametrize("cell,seed", [(c, s) for c in CELLS + DECODE_CELLS for s in (1, 2, 2**31 + 9)])
def test_the_fp8_control_is_not_correct(cell, seed):
    cell_file = run.load_json("workloads", cell, DATA)
    config = run.load_json("configs", cell_file["config"], DATA)
    checks = control.control_checks(cell_file, config, seed, "fp8")
    assert not all(c["ok"] for c in checks), checks
    assert {c["name"] for c in checks if not c["ok"]} & {"grad_norm_gap", "loss_gap", "served_logit_gap"}
