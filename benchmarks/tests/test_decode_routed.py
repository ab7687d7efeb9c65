"""``drivers/decode_routed.py``: the ``decode`` driver's run judged on the
99th percentile of the served positions' gaps and, under a limit of its own,
on the widest. The gaps are ``decode.served_gaps``' own; a sample shaped like a
sound program's (a few flips, one of them far out) passes both limits of the
K-EXAONE cell, one shaped like the fp8 control's fails the percentile alone,
and one altered token fails the widest alone."""

import argparse
import json
import types

import jax
import numpy as np
import pytest

from benchmarks import run
from benchmarks.drivers import decode, decode_routed

DATA = run.os.path.join(run.HERE, "tests", "data")
CELL = run.load_json("workloads", "kexaone-ep8-mtp-decode-b64")
LIMITS = CELL["limits"]


def sample(rng, n, moved_share, scale, far=()):
    """``n`` gaps: ``moved_share`` of the positions read a half-normal gap of deviation ``scale``, the rest 0; ``far`` are set by hand."""
    gaps = np.where(rng.random(n) < moved_share, np.abs(rng.normal(0.0, scale, n)), 0.0)
    gaps[: len(far)] = far
    return gaps


def failed(gaps, limits=LIMITS):
    return [c["name"] for c in decode_routed.judge(gaps, limits, "") if not c["ok"]]


@pytest.mark.parametrize("seed", range(4))
def test_a_sound_samples_flips_pass_and_the_controls_bulk_does_not(seed):
    rng = np.random.default_rng(seed)
    # the chip's readings (PERF.md 2, PR 34): 3.6% of a sound run's 2048 positions are not the reference's best, their gaps
    # 0.09 at the mean, and the widest flip of 295 000 positions read 1.164; the control moves 30% of them by 0.24 at the mean
    sound = sample(rng, 2048, 0.036, 0.11, far=(1.164, 0.898))
    control = sample(rng, 2048, 0.30, 0.30)
    assert 0.5 < np.percentile(control, 99) < 0.8  # where the chip's control reads (0.629 to 0.731)
    assert failed(sound) == []
    assert failed(control) == ["served_gap_p99"] and control.max() < LIMITS["served_logit_gap"]
    altered = sound.copy()
    altered[7] = 2.355  # the 1st percentile of a token drawn at random over the 19 200 logits, at the least (4 seeds on the chip)
    assert failed(altered) == ["served_logit_gap"]


def test_the_percentile_is_numpys_linear_one():
    gaps = np.arange(101.0)
    p99, widest = decode_routed.judge(gaps, {"served_gap_p99": 0.0, "served_logit_gap": 0.0}, "")
    assert p99["value"] == 99.0 and widest["value"] == 100.0 and decode_routed.QUANTILE == 99.0


def test_the_gaps_are_the_decode_drivers_own():
    """Position by position the gaps are what ``decode.served_gaps`` takes its widest from, for the served tokens and for the control's."""
    cell = run.load_json("workloads", "tiny-exaone-decode", DATA)
    config = run.load_json("configs", cell["config"], DATA)
    family = run.importlib.import_module(f"benchmarks.families.{config['family']}").Family(config)
    ctx = types.SimpleNamespace(seed=2**31 + 5, cell=cell, config=config, family=family, mark=run.mark)
    run_ = decode.DecodeRun(ctx)
    run_.call()
    rows = decode.sample_rows(ctx, run_.served)
    for precision, tokens_from in (("float32", "served"), ("fp8", "control")):
        theirs = decode.served_gaps(ctx, rows, precision, tokens_from)
        gaps, altered, after_slide = decode_routed.position_gaps(ctx, rows, precision, tokens_from)
        assert gaps.shape == altered.shape == (theirs["tokens"],) and after_slide == theirs["after_slide"] == 0
        assert float(gaps.max()) == theirs["widest_gap"] and int((gaps == 0).sum()) == theirs["argmax_same"]
        assert (altered >= 0).all() and altered.max() > 0
    assert gaps.max() > 1.0  # the fp8 control at this size: far from the reference


def test_the_cell_names_the_driver_and_both_limits_between_their_readings():
    assert CELL["driver"] == "decode_routed" and set(LIMITS) == {"served_gap_p99", "served_logit_gap"}
    # PERF.md 2: the percentile, sound at most 0.147 and control at least 0.615; the widest, a sound run's flips at most
    # 1.164 and a token drawn at random 2.355 at the 1st percentile: room on both sides of each
    assert 0.147 * 1.5 < LIMITS["served_gap_p99"] < 0.615 / 1.5
    assert 1.164 * 1.5 < LIMITS["served_logit_gap"] < 2.355
    for word in ("served_gap_p99", "served_logit_gap", "0.147", "0.615", "1.164", "2.355"):
        assert word in CELL["limits_from"]


def test_a_tiny_cell_runs_through_the_driver(capsys):
    args = argparse.Namespace(workload="tiny-exaone-decode", seed=2**31 + 11, seconds=0.3, trace=0, keep_trace=None)
    result = run.run_cell(args, jax.devices(), data_root=DATA, bench_path=run.os.path.join(DATA, "BENCHMARK-exaone.json"))
    assert result["correct"] is True and set(result["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    out = capsys.readouterr().out
    assert "check served_gap_p99:" in out and "check served_logit_gap:" in out and "36 served tokens of 3 rows" in out
    assert json.loads(json.dumps(result))["failed"] == 0
