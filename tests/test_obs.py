"""Telemetry subsystem (obs/): a CPU-backed Trainer.fit run must produce
events.jsonl + run_manifest.json with non-null MFU/throughput fields and a
compile event; the xplane per-scope rollup must reproduce the raw per-op
totals on a hand-built varint-encoded golden; MetricsLogger must survive a
resume without corrupting its CSV; StepTimer delivers the percentile
summary its docstring promises; obs_report renders it all."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.models.text import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.obs import (
    EventLog,
    RecompileTracker,
    clm_train_telemetry,
    config_hash,
    device_peak_flops,
)
from perceiver_io_tpu.obs.mfu import GoodputTracker
from perceiver_io_tpu.training import (
    MetricsLogger,
    TrainState,
    Trainer,
    TrainerConfig,
    clm_loss_fn,
    make_optimizer,
)


def tiny_clm():
    config = CausalLanguageModelConfig(
        vocab_size=50, max_seq_len=24, max_latents=8, num_channels=32,
        num_heads=4, num_self_attention_layers=2, cross_attention_dropout=0.5,
    )
    return CausalLanguageModel(config), config


def clm_batch(config, batch=4, seed=0):
    rng = np.random.default_rng(seed)
    t = rng.integers(0, config.vocab_size, size=(batch, config.max_seq_len + 1))
    return {
        "labels": jnp.asarray(t[:, 1:]),
        "input_ids": jnp.asarray(t[:, :-1]),
        "pad_mask": None,
    }


def run_tiny_fit(tmp_path, max_steps=4, log_interval=2):
    """A short CPU-backed training run with full telemetry (the ISSUE's
    acceptance workload)."""
    model, config = tiny_clm()
    batch = clm_batch(config)
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"], prefix_len=16)
    state = TrainState.create(model.apply, params, make_optimizer(1e-3), jax.random.PRNGKey(1))
    tokens_per_sample, flops_per_sample = clm_train_telemetry(config)
    logger = MetricsLogger(str(tmp_path), use_tensorboard=False)
    trainer = Trainer(
        clm_loss_fn(model.apply, max_latents=config.max_latents),
        logger=logger,
        config=TrainerConfig(
            max_steps=max_steps,
            log_interval=log_interval,
            prefetch_batches=0,
            tokens_per_sample=tokens_per_sample,
            flops_per_sample=flops_per_sample,
        ),
    )
    state = trainer.fit(state, iter([batch] * max_steps), model_config=config)
    trainer.close()
    logger.close()
    return state


def read_events(run_dir):
    with open(os.path.join(str(run_dir), "events.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


# ---------------------------------------------------------------- trainer


def test_trainer_emits_events_manifest_and_mfu(tmp_path):
    run_tiny_fit(tmp_path)
    events = read_events(tmp_path)
    kinds = [e["event"] for e in events]
    assert kinds[0] == "fit_start"
    assert kinds[-1] == "fit_end"
    assert "compile" in kinds  # the train step's first trace+compile surfaced

    # every log row carries non-null throughput accounting; MFU is null
    # because the CPU is not in the peak table
    logs = [e for e in events if e["event"] == "log"]
    assert len(logs) == 2  # steps 2 and 4 at log_interval=2
    for row in logs:
        assert row["tokens_per_sec"] > 0
        assert row["model_flops_per_sec"] > 0
        assert row["mfu"] is None
        assert 0.0 <= row["goodput"] <= 1.0
        assert "train_loss" in row

    # the same fields land in metrics.csv (the human-facing mirror)
    import csv

    with open(os.path.join(str(tmp_path), "metrics.csv"), newline="") as f:
        rows = list(csv.DictReader(f))
    assert rows and "mfu" not in rows[-1]
    assert float(rows[-1]["tokens_per_sec"]) > 0

    # fit_end carries the goodput breakdown and the recompile audit
    end = events[-1]
    assert end["recompiles"]["train_step"] == 1
    assert end["total_s"] > 0 and end["compile_s"] > 0
    assert 0.0 <= end["goodput"] <= 1.0

    manifest = json.load(open(os.path.join(str(tmp_path), "run_manifest.json")))
    assert manifest["jax_version"] == jax.__version__
    assert manifest["device_kind"]
    assert manifest["device_count"] >= 1
    assert manifest["mesh"] is None  # no mesh in this run
    assert len(manifest["config_hash"]) == 12
    # the hash is stable across identical configs
    _, config = tiny_clm()
    assert config_hash(config, None) == config_hash(config, None)


def test_trainer_aborted_run_still_emits_fit_end(tmp_path):
    """A run killed mid-loop must still leave the goodput/recompile audit —
    it is exactly the run that needs diagnosing."""
    model, config = tiny_clm()
    batch = clm_batch(config)
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"], prefix_len=16)
    state = TrainState.create(model.apply, params, make_optimizer(1e-3), jax.random.PRNGKey(1))
    tokens_per_sample, flops_per_sample = clm_train_telemetry(config)
    logger = MetricsLogger(str(tmp_path), use_tensorboard=False)
    trainer = Trainer(
        clm_loss_fn(model.apply, max_latents=config.max_latents),
        logger=logger,
        config=TrainerConfig(
            max_steps=10, log_interval=2, prefetch_batches=0,
            tokens_per_sample=tokens_per_sample, flops_per_sample=flops_per_sample,
        ),
    )
    def dying_loader():
        yield batch
        yield batch
        raise RuntimeError("data source died")

    with pytest.raises(RuntimeError, match="data source died"):
        trainer.fit(state, dying_loader(), model_config=config)
    trainer.close()
    logger.close()
    end = [e for e in read_events(tmp_path) if e["event"] == "fit_end"]
    assert len(end) == 1 and end[0]["aborted"] is True
    assert end[0]["recompiles"]["train_step"] == 1
    assert end[0]["compile_s"] > 0


def test_trainer_telemetry_off_without_logger(tmp_path):
    model, config = tiny_clm()
    batch = clm_batch(config)
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"], prefix_len=16)
    state = TrainState.create(model.apply, params, make_optimizer(1e-3), jax.random.PRNGKey(1))
    trainer = Trainer(
        clm_loss_fn(model.apply, max_latents=config.max_latents),
        config=TrainerConfig(max_steps=1, log_interval=1, prefetch_batches=0),
    )
    trainer.fit(state, iter([batch]), model_config=config)
    trainer.close()
    assert not os.path.exists(os.path.join(str(tmp_path), "events.jsonl"))


def test_clm_train_telemetry_matches_the_cost_model():
    """The trainer's MFU numerator is ``utils.flops.train_step_flops`` at
    the configured prefix-dropout rate: the package's one cost model."""
    _, config = tiny_clm()
    tokens, flops = clm_train_telemetry(config)
    assert tokens == config.max_latents
    from perceiver_io_tpu.utils.flops import train_step_flops

    keep = 1.0 - config.cross_attention_dropout
    assert flops == pytest.approx(train_step_flops(config, 1, prefix_dropout_keep=keep))
    # non-CLM configs have no analytic model: None, not a bogus number
    assert clm_train_telemetry(object()) is None


# ------------------------------------------------------------- recompiles


def test_recompile_tracker_counts_shape_driven_recompiles(tmp_path):
    events = EventLog(str(tmp_path), main_process=True)
    tracker = RecompileTracker(events=events, goodput=GoodputTracker())
    f = tracker.wrap(jax.jit(lambda x: x * 2), "f")
    f(jnp.ones((2,)))
    f(jnp.ones((2,)))  # cache hit: no event
    f(jnp.ones((3,)))  # new shape: silent recompile surfaces
    assert tracker.counts()["f"] == 2
    compiles = [e for e in read_events(tmp_path) if e["event"] == "compile"]
    assert len(compiles) == 2
    # the shape signatures differ — that's what identifies the leak
    assert compiles[0]["arg_shapes"] != compiles[1]["arg_shapes"]
    assert all(c["wall_s"] >= 0 for c in compiles)
    assert tracker.total_compile_s >= 0


# ---------------------------------------------------------- xplane golden


def _vint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _varint_field(fnum: int, n: int) -> bytes:
    return _vint(fnum << 3) + _vint(n)


def _len_field(fnum: int, payload: bytes) -> bytes:
    return _vint((fnum << 3) | 2) + _vint(len(payload)) + payload


def golden_xplane() -> (bytes, dict):
    """A hand-encoded XSpace: one device plane, one "XLA Ops" line, six ops —
    two with scope paths in their display names, one raw HLO op, one with
    the path in an XEventMetadata ``tf_op`` stat (str_value), one with an
    interned per-event stat (ref_value), one unscoped. Field numbers match
    the parser's contract (obs/xplane.py wire-format notes)."""
    ops = {
        1: ("jit(train_step)/perceiver_ar/cross_attend/fusion.1", 3000),
        2: ("jit(train_step)/perceiver_ar/cross_attend/dot.7", 1500),
        3: ("jit(train_step)/perceiver_ar/self_attend/fusion.2", 2000),
        4: ("copy.3", 500),
        5: ("fusion.9", 1000),  # scope via metadata tf_op stat
        6: ("dot.11", 250),  # scope via per-event interned ref stat
    }
    # stat_metadata: 50 = the "tf_op" stat key; 60 = an interned path string
    ref_path = "jit(train_step)/decode/sample/dot.11"
    stat_metadata = b"".join(
        _len_field(5, _varint_field(1, sid) + _len_field(2, _varint_field(1, sid) + _len_field(2, sname.encode())))
        for sid, sname in ((50, "tf_op"), (60, ref_path))
    )

    def event(mid, dur, stats=b""):
        return _len_field(4, _varint_field(1, mid) + _varint_field(3, dur) + stats)

    ref_stat = _len_field(4, _varint_field(1, 50) + _varint_field(7, 60))  # XEvent.stats
    events = b"".join(
        event(mid, dur, stats=ref_stat if mid == 6 else b"")
        for mid, (_, dur) in ops.items()
    )
    line = _len_field(2, b"XLA Ops") + events

    tf_op_stat = _len_field(
        5, _varint_field(1, 50) + _len_field(5, b"jit(train_step)/perceiver_ar/mlp/fusion.9")
    )  # XEventMetadata.stats

    def meta(mid, name):
        payload = _varint_field(1, mid) + _len_field(2, name.encode())
        if mid == 5:
            payload += tf_op_stat
        return _len_field(4, _varint_field(1, mid) + _len_field(2, payload))

    metadata = b"".join(meta(mid, name) for mid, (name, _) in ops.items())
    plane = _len_field(2, b"/device:TPU:0") + _len_field(3, line) + metadata + stat_metadata
    return _len_field(1, plane), ops


def test_xplane_golden_parse_and_scope_rollup(tmp_path):
    from perceiver_io_tpu.obs import xplane as ox

    buf, ops = golden_xplane()
    path = os.path.join(str(tmp_path), "golden.xplane.pb")
    with open(path, "wb") as f:
        f.write(buf)

    # raw per-op totals (the tools/xplane.py view)
    planes = list(ox.iter_planes(path))
    assert len(planes) == 1
    plane = planes[0]
    assert plane.name == "/device:TPU:0"
    total = sum(dur for _, dur in ops.values())
    assert plane.total_ps == total == 8250
    assert plane.per_op[ops[1][0]] == 3000
    assert plane.per_line == {"XLA Ops": total}
    # the stat-carried paths were resolved (metadata stat + interned event stat)
    assert plane.op_scopes["fusion.9"] == "jit(train_step)/perceiver_ar/mlp/fusion.9"
    assert plane.op_scopes["dot.11"] == "jit(train_step)/decode/sample/dot.11"

    # per-scope rollup: aggregates by module path, reproduces the totals
    rolls = ox.rollup(path)
    assert len(rolls) == 1
    scopes = rolls[0].scopes
    assert scopes["perceiver_ar/cross_attend"] == (4500, 2)  # fusion.1 + dot.7
    assert scopes["perceiver_ar/self_attend"] == (2000, 1)
    assert scopes["perceiver_ar/mlp"] == (1000, 1)  # via XEventMetadata tf_op stat
    assert scopes["decode/sample"] == (250, 1)  # via per-event ref stat
    assert scopes[ox.UNSCOPED] == (500, 1)
    assert rolls[0].total_ps == plane.total_ps  # acceptance: same totals

    # depth truncation merges sibling scopes
    deep = ox.rollup(path, depth=1)[0].scopes
    assert deep["perceiver_ar"] == (7500, 4)

    # the tools/xplane.py CLI entry resolves to the same numbers
    import importlib.util

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "tools_xplane", os.path.join(root, "tools", "xplane.py")
    )
    tools_xplane = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tools_xplane)
    out = []
    cli_planes = tools_xplane.summarize(path, top=10, print_fn=out.append)
    assert cli_planes[0].total_ps == rolls[0].total_ps
    assert any("XLA Ops" in line for line in out)  # the CLI rendering ran


def test_scope_of_rules():
    from perceiver_io_tpu.obs.xplane import UNSCOPED, scope_of

    assert scope_of("jit(f)/jit(main)/a/b/op") == "a/b"
    assert scope_of("transpose(jit(f))/a/op") == "a"
    assert scope_of("jit(f)/a/b/op", depth=1) == "a"
    assert scope_of("fusion.12") == UNSCOPED
    assert scope_of("jit(f)/op") == UNSCOPED
    # the one rule (op_scope): forward and backward of a module land in one bucket, loop parts are no scope
    forward = "jit(train_step)/jvp(CausalLanguageModel)/perceiver_ar/self_attend/mlp/dot_general"
    backward = "jit(train_step)/transpose(jvp(CausalLanguageModel))/perceiver_ar/self_attend/mlp/dot_general"
    assert scope_of(forward) == scope_of(backward) == "CausalLanguageModel/perceiver_ar/self_attend/mlp"
    assert scope_of("jit(fn)/decode/while/body/closed_call/decode/sample/argmax") == "decode/decode/sample"


def test_rollup_buckets_follow_the_one_rule():
    """``rollup_planes`` puts an operation where ``op_scope`` puts it: the
    backward's ``transpose(jvp(..))`` names join the forward's bucket."""
    import collections

    from perceiver_io_tpu.obs.xplane import UNSCOPED, PlaneSummary, op_scope, rollup_planes

    names = {
        "fusion.1": "jit(train_step)/jvp(CausalLanguageModel)/perceiver_ar/self_attend/mlp/dot_general",
        "fusion.2": "jit(train_step)/transpose(jvp(CausalLanguageModel))/perceiver_ar/self_attend/mlp/dot_general",
        "fusion.3": "jit(train_step)/optimizer/jit(_adamw_update)/mul",
        "copy.4": "copy.4",
    }
    plane = PlaneSummary(name="/device:TPU:0", per_op=collections.Counter({"fusion.1": 300, "fusion.2": 500, "fusion.3": 40, "copy.4": 7}),
                         counts=collections.Counter({op: 1 for op in names}), op_scopes=dict(names))
    scopes = rollup_planes([plane])[0].scopes
    assert scopes == {"CausalLanguageModel/perceiver_ar/self_attend/mlp": (800, 2), "optimizer": (40, 1), UNSCOPED: (7, 1)}
    assert {op_scope(n).layer for n in names.values()} == {"mlp", "optimizer", UNSCOPED}
    assert rollup_planes([plane], depth=1)[0].scopes["CausalLanguageModel"] == (800, 2)


# ------------------------------------------------------- metrics resume


def test_metrics_logger_resume_keeps_single_header(tmp_path):
    d = str(tmp_path)
    l1 = MetricsLogger(d, use_tensorboard=False, main_process=True)
    l1.log(1, {"a": 1.0})
    l1.close()

    # restart: a new logger against the same metrics.csv, with a widening key
    l2 = MetricsLogger(d, use_tensorboard=False, main_process=True)
    l2.log(2, {"a": 2.0, "b": 3.0})
    l2.log(3, {"a": 4.0})
    l2.close()

    import csv

    with open(os.path.join(d, "metrics.csv"), newline="") as f:
        raw = f.read().splitlines()
    # exactly one header row, first line, widened to include b
    assert sum(1 for line in raw if line.startswith("step,")) == 1
    header = raw[0].split(",")
    assert "a" in header and "b" in header
    with open(os.path.join(d, "metrics.csv"), newline="") as f:
        rows = list(csv.DictReader(f))
    assert [int(float(r["step"])) for r in rows] == [1, 2, 3]
    assert rows[0]["b"] == ""  # pre-widening row backfilled empty
    assert float(rows[1]["b"]) == 3.0


def test_metrics_logger_resume_foreign_header_rewritten(tmp_path):
    """A metrics.csv whose header lacks the step/time contract keys must be
    rewritten on resume — appending to _keys alone would misalign rows."""
    import csv

    d = str(tmp_path)
    path = os.path.join(d, "metrics.csv")
    with open(path, "w", newline="") as f:
        f.write("loss\n0.9\n")
    logger = MetricsLogger(d, use_tensorboard=False, main_process=True)
    logger.log(1, {"loss": 0.4})
    logger.close()
    with open(path, newline="") as f:
        raw = f.read().splitlines()
    header = raw[0].split(",")
    assert header[0] == "loss" and "step" in header and "time" in header
    assert len(raw) == 3  # one header + the old row + the new row
    with open(path, newline="") as f:
        rows = list(csv.DictReader(f))
    assert float(rows[0]["loss"]) == 0.9 and rows[0]["step"] == ""
    assert float(rows[1]["loss"]) == 0.4 and int(float(rows[1]["step"])) == 1


# -------------------------------------------------------------- profiling


def test_steptimer_percentile_summary():
    from perceiver_io_tpu.utils.profiling import StepTimer, percentile

    timer = StepTimer(warmup=1)
    timer._times = [99.0] + [float(i) for i in range(1, 11)]  # warmup discarded
    assert timer.percentile(50) == pytest.approx(5.5)
    assert timer.percentile(0) == 1.0 and timer.percentile(100) == 10.0
    s = timer.summary()
    assert s["p50"] == pytest.approx(5.5)
    assert s["p90"] == pytest.approx(9.1)
    assert s["p99"] == pytest.approx(9.91)
    assert s["mean"] == pytest.approx(5.5)
    assert s["n"] == 10
    with pytest.raises(ValueError):
        StepTimer().percentile(50)
    with pytest.raises(ValueError):
        percentile([1.0], 150)


def test_steptimer_summary_low_n_uses_exact_order_statistics():
    """Satellite fix: under 5 samples the summary must report exact order
    statistics (nearest rank — the p99 of 3 samples IS the max) and mark
    the row low_n, instead of interpolating a fake tail."""
    from perceiver_io_tpu.utils.profiling import StepTimer, exact_percentile

    timer = StepTimer(warmup=1)
    timer._times = [99.0, 1.0, 10.0, 2.0]  # 3 retained samples
    s = timer.summary()
    assert s["low_n"] is True and s["n"] == 3
    assert s["p50"] == 2.0  # the middle observation, not an interpolation
    assert s["p90"] == 10.0 and s["p99"] == 10.0  # the max — no fake tail
    assert s["mean"] == pytest.approx(13.0 / 3)
    # ≥5 samples: interpolated percentiles, no low_n mark
    timer._times = [99.0] + [float(i) for i in range(1, 6)]
    s5 = timer.summary()
    assert "low_n" not in s5 and s5["p99"] == pytest.approx(4.96)
    assert exact_percentile([3.0, 1.0, 2.0], 0) == 1.0
    with pytest.raises(ValueError):
        exact_percentile([], 50)


# -------------------------------------------------------------- goodput


def test_goodput_tracker_buckets():
    t = [0.0]
    clock = lambda: t[0]  # noqa: E731
    g = GoodputTracker(clock=clock)
    t[0] = 10.0
    with g.measure("compile"):
        t[0] = 12.0
    with g.measure("eval"):
        t[0] = 13.0
    s = g.summary()
    assert s["total_s"] == pytest.approx(13.0)
    assert s["compile_s"] == pytest.approx(2.0)
    assert s["eval_s"] == pytest.approx(1.0)
    assert s["productive_s"] == pytest.approx(10.0)
    assert s["goodput"] == pytest.approx(10.0 / 13.0, abs=1e-3)


def test_device_peak_flops_table():
    assert device_peak_flops() is None  # the current (CPU) device has no peak

    class Fake:
        def __init__(self, kind, platform="tpu"):
            self.device_kind = kind
            self.platform = platform

    assert device_peak_flops(Fake("TPU v5 lite")) == 197e12
    assert device_peak_flops(Fake("TPU v4")) == 275e12
    assert device_peak_flops(Fake("NVIDIA A100-SXM4-40GB", "gpu")) == 312e12
    assert device_peak_flops(Fake("warp drive", "quantum")) is None


# ------------------------------------------------------------ obs_report


def test_obs_report_renders_run_summary(tmp_path):
    run_tiny_fit(tmp_path)
    import importlib.util

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(root, "tools", "obs_report.py")
    )
    obs_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(obs_report)
    text = obs_report.render(str(tmp_path))
    assert "== manifest ==" in text
    assert "jax_version" in text
    assert "== compiles ==" in text and "train_step" in text
    assert "mfu" in text and "tokens_per_sec" in text
    assert "== goodput (fit_end) ==" in text
    # no spurious recompile warning on a clean single-shape run
    assert "WARNING: recompiles" not in text

    # a RESUMED run appends a second legitimate first-compile (fresh process,
    # n_compiles resets to 1) — still no leak warning; a genuine same-process
    # recompile (n_compiles=2) must warn
    with open(os.path.join(str(tmp_path), "events.jsonl"), "a") as f:
        f.write(json.dumps({"ts": 0, "event": "compile", "fn": "train_step",
                            "wall_s": 1.0, "n_compiles": 1}) + "\n")
    assert "WARNING: recompiles" not in obs_report.render(str(tmp_path))
    with open(os.path.join(str(tmp_path), "events.jsonl"), "a") as f:
        f.write(json.dumps({"ts": 0, "event": "compile", "fn": "train_step",
                            "wall_s": 1.0, "n_compiles": 2}) + "\n")
    assert "WARNING: recompiles after the first on: train_step" in obs_report.render(str(tmp_path))


# ------------------------------------------------------------ generation


def test_instrumented_generation_stats_and_request_events(tmp_path):
    """Acceptance pin: one `request` event per request, carrying TTFT and
    histogram-derived TPOT p50/p99 (not means), tokens in/out, cache
    geometry and outcome; spans + compile events attributed per request."""
    from perceiver_io_tpu.generation import GenerationConfig, make_instrumented_generate_fn

    model, config = tiny_clm()
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, config.vocab_size, size=(2, 12)))
    params = model.init(jax.random.PRNGKey(0), prompt, prefix_len=8)
    events = EventLog(str(tmp_path), main_process=True)
    fn = make_instrumented_generate_fn(
        model, num_latents=4, config=GenerationConfig(max_new_tokens=6), events=events
    )
    out, stats = fn(params, prompt)
    assert out.shape == (2, 18)
    assert stats.compiled  # first call pays the compiles
    assert stats.prefill_s > 0 and stats.decode_s >= 0
    assert stats.ttft_s == stats.prefill_s
    assert stats.tokens_per_sec > 0
    assert stats.batch == 2 and stats.prompt_len == 12 and stats.new_tokens == 6
    assert stats.tokens_out == 6 and stats.outcome == "ok"

    out2, stats2 = fn(params, prompt)
    assert not stats2.compiled  # warm call: no recompile
    assert np.array_equal(np.asarray(out), np.asarray(out2))  # same rng default
    # TPOT percentiles are histogram-derived and ordered
    assert stats2.tpot_p50_s > 0
    assert stats2.tpot_p50_s <= stats2.tpot_p90_s <= stats2.tpot_p99_s

    evs = read_events(tmp_path)
    reqs = [e for e in evs if e["event"] == "request"]
    assert len(reqs) == 2  # one request event per request
    for r in reqs:
        assert r["ttft_s"] > 0
        assert r["tpot_p50_s"] > 0 and r["tpot_p99_s"] >= r["tpot_p50_s"]
        assert sum(r["tpot_hist"].values()) == 5  # 5 decode steps recorded
        assert r["outcome"] == "ok" and r["tokens_out"] == 6
        assert r["ca_capacity"] == 18 and r["sa_capacity"] == 10
        assert r["schema_version"] == 1
    # the cross-request registry records WARM samples only (a dashboard
    # histogram never resets, so one compile sample would poison its tail
    # forever): request 1's compiling prefill + first decode step are out
    assert fn.registry.counter("generate_cold_requests_total").value == 1
    assert fn.registry.histogram("generate_ttft_s").n == 1
    assert fn.registry.histogram("generate_tpot_s").n == 9  # 4 warm + 5 warm
    # both compiled programs surfaced as compile events on the first call,
    # attributed to the request span that paid them
    compiles = [e for e in evs if e["event"] == "compile"]
    assert {e["fn"] for e in compiles} == {"generate_prefill", "generate_decode_step"}
    span_ids = {e["span_id"] for e in evs if e["event"] == "span"}
    assert reqs[0]["span_id"] in span_ids
    assert all(c["span_id"] == reqs[0]["span_id"] for c in compiles)
    # the stream validates (schema_version + required fields + span refs)
    from perceiver_io_tpu.obs.events import validate_events

    assert validate_events(str(tmp_path)) == []


def test_streamed_decode_matches_compiled_scan():
    """make_decode_fns' host-driven loop must be token-exact equal to
    generate()'s compiled scan — same body, same rng chain — including
    sampling and EOS freezing."""
    from perceiver_io_tpu.generation import GenerationConfig, generate, make_decode_fns

    model, config = tiny_clm()
    rng = np.random.default_rng(1)
    prompt = jnp.asarray(rng.integers(0, config.vocab_size, size=(2, 12)))
    params = model.init(jax.random.PRNGKey(0), prompt, prefix_len=8)
    for gc in (
        GenerationConfig(max_new_tokens=6, do_sample=True, temperature=0.8, top_k=10),
        GenerationConfig(max_new_tokens=5, eos_token_id=3),
    ):
        ref = generate(model, params, prompt, num_latents=4, config=gc, rng=jax.random.PRNGKey(7))
        prefill_fn, step_fn = make_decode_fns(model, num_latents=4, config=gc)
        token, state = prefill_fn(params, prompt, None, jax.random.PRNGKey(7))
        toks = [token]
        for _ in range(1, gc.max_new_tokens):
            state, token = step_fn(state)
            toks.append(token)
        streamed = jnp.concatenate([prompt] + [t[:, None] for t in toks], axis=1)
        assert np.array_equal(np.asarray(ref), np.asarray(streamed))


def test_instrumented_generation_abort_emits_error_request(tmp_path):
    """A request that dies mid-decode must still emit its `request` event
    with outcome="error" and the partial TPOT data, then re-raise (the
    fit_end except-and-reraise guarantee, request-level)."""
    from perceiver_io_tpu.generation import GenerationConfig, make_instrumented_generate_fn

    model, config = tiny_clm()
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, config.vocab_size, size=(2, 12)))
    params = model.init(jax.random.PRNGKey(0), prompt, prefix_len=8)
    events = EventLog(str(tmp_path), main_process=True)

    def die_at_3(i, token):
        if i == 3:
            raise RuntimeError("consumer died mid-decode")

    fn = make_instrumented_generate_fn(
        model, num_latents=4, config=GenerationConfig(max_new_tokens=8),
        events=events, on_token=die_at_3,
    )
    with pytest.raises(RuntimeError, match="consumer died mid-decode"):
        fn(params, prompt)
    reqs = [e for e in read_events(tmp_path) if e["event"] == "request"]
    assert len(reqs) == 1
    r = reqs[0]
    assert r["outcome"] == "error"
    assert "consumer died mid-decode" in r["error"]
    assert r["tokens_out"] == 4  # tokens 0..3 were produced before the abort
    assert sum(r["tpot_hist"].values()) == 3  # partial TPOT samples survive
    assert r["ttft_s"] > 0
    # the error outcome rides the span and the registry error counter
    spans = [e for e in read_events(tmp_path) if e["event"] == "span"]
    assert any(s["attrs"].get("outcome") == "error" for s in spans)
    assert fn.registry.counter("generate_request_errors_total").value == 1


# ------------------------------------------------------------------ spans


def test_tracer_span_nesting_ids_and_ambient(tmp_path):
    from perceiver_io_tpu.obs.trace import Tracer, current_span_id

    events = EventLog(str(tmp_path), main_process=True)
    tracer = Tracer(events)
    assert current_span_id() is None
    with tracer.span("outer", kind="test") as outer:
        assert current_span_id() == outer.span_id
        with tracer.span("inner") as inner:
            assert inner.parent_id == outer.span_id
            assert current_span_id() == inner.span_id
            inner.set("k", 7)
        assert current_span_id() == outer.span_id
    assert current_span_id() is None
    tracer.flush()
    rows = [e for e in read_events(tmp_path) if e["event"] == "span"]
    by_name = {r["name"]: r for r in rows}
    assert by_name["inner"]["parent_id"] == by_name["outer"]["span_id"]
    assert by_name["inner"]["attrs"] == {"k": 7}
    assert by_name["outer"]["attrs"] == {"kind": "test"}
    assert by_name["outer"]["parent_id"] is None
    for r in rows:
        assert r["dur_ms"] >= 0 and r["t_end"] >= r["t_start"]
        assert r["process_index"] == 0

    # ambient fallback: a FOREIGN thread's emit attaches to the ambient span
    import threading

    seen = {}
    with tracer.span("fit", ambient=True) as fit:
        t = threading.Thread(target=lambda: seen.update(sid=current_span_id()))
        t.start()
        t.join()
    assert seen["sid"] == fit.span_id

    # decorator form
    @tracer.traced("worker")
    def work():
        return current_span_id()

    sid = work()
    tracer.flush()
    names = [e["name"] for e in read_events(tmp_path) if e["event"] == "span"]
    assert "worker" in names and sid is not None


def test_event_rows_carry_schema_version_and_current_span(tmp_path):
    from perceiver_io_tpu.obs.events import EVENT_SCHEMA_VERSION
    from perceiver_io_tpu.obs.trace import Tracer

    events = EventLog(str(tmp_path), main_process=True)
    tracer = Tracer(events)
    events.emit("custom", a=1)
    with tracer.span("step") as sp:
        events.emit("fault.skip", step=3, reason="nonfinite", skips=1)
    tracer.flush()
    rows = read_events(tmp_path)
    assert all(r["schema_version"] == EVENT_SCHEMA_VERSION for r in rows)
    assert "span_id" not in rows[0]  # no open span at emit time
    fault = [r for r in rows if r["event"] == "fault.skip"][0]
    assert fault["span_id"] == sp.span_id  # stamped by the open span


def test_trainer_emits_step_spans_with_phases(tmp_path):
    run_tiny_fit(tmp_path)
    events = read_events(tmp_path)
    spans = [e for e in events if e["event"] == "span"]
    steps = [s for s in spans if s["name"] == "step"]
    fits = [s for s in spans if s["name"] == "fit"]
    assert len(fits) == 1 and len(steps) == 4  # one span per step
    for s in steps:
        assert s["parent_id"] == fits[0]["span_id"]
        assert "input_wait_ms" in s["attrs"] and "dispatch_ms" in s["attrs"]
        assert "step" in s["attrs"]
    assert [s["attrs"]["step"] for s in steps] == [1, 2, 3, 4]
    # fit_start and log rows are attributed (fit / step span respectively)
    by_event = {e["event"]: e for e in events}
    assert by_event["fit_start"]["span_id"] == fits[0]["span_id"]
    assert by_event["log"]["span_id"] in {s["span_id"] for s in steps}
    # the whole stream validates, span references included
    from perceiver_io_tpu.obs.events import validate_events

    assert validate_events(str(tmp_path)) == []


def test_host_device_breakdown_lays_spans_on_one_capture():
    """The correlation hook: span rows laid on a capture's timeline give, per
    span name, count, total and self time and the device idle time under it
    (innermost span covering the gap's midpoint), which obs_report prints."""
    from perceiver_io_tpu.obs.trace import NO_SPAN, host_device_breakdown

    t0 = 1_700_000_000_000_000_000  # the capture's profile_start_time

    def row(name, sid, parent, start, end, **extra):
        return {"event": "span", "name": name, "span_id": sid, "parent_id": parent,
                "start_ns": t0 + start, "end_ns": t0 + end, "dur_ms": (end - start) / 1e6,
                "attrs": {}, **extra}

    ms = 1_000_000
    rows = [
        row("step", "s1", None, 0 * ms, 10 * ms),
        row("train/input_wait", "w1", "s1", 0 * ms, 2 * ms),
        row("train/dispatch", "d1", "s1", 2 * ms, 3 * ms),
        row("step", "s2", None, 10 * ms, 20 * ms),
        row("train/input_wait", "w2", "s2", 10 * ms, 14 * ms),
        row("train/dispatch", "d2", "s2", 14 * ms, 15 * ms),
        # a detached span over everything: counted, never given idle time
        row("request", "r1", None, 0, 20 * ms, detached=True),
        # recorded before the capture started: not on its timeline
        row("step", "s0", None, -10 * ms, -1 * ms),
    ]
    capture = {
        "profile_start_ns": t0, "length_ns": 30 * ms, "annotations": [],
        "device_ops": {"/device:TPU:0": [
            ("fusion.1", 1 * ms, 8 * ms), ("nested", 2 * ms, 1 * ms),  # busy 1..9
            ("fusion.1", 14.5 * ms, 5.5 * ms),  # busy 14.5..20
        ]},
    }
    bd = host_device_breakdown(rows, capture)
    s = bd["spans"]
    assert s["step"]["count"] == 3 and s["step"]["total_ms"] == pytest.approx(29.0)
    # self time: the step less its two phases (s0 has no children)
    assert s["step"]["self_ms"] == pytest.approx(7.0 + 5.0 + 9.0)
    assert s["train/input_wait"] == {"count": 2, "total_ms": pytest.approx(6.0),
                                     "self_ms": pytest.approx(6.0), "idle_ms": pytest.approx(1.0 + 5.5)}
    assert "idle_ms" not in s["train/dispatch"] and "idle_ms" not in s["request"]
    assert s["request"]["count"] == 1
    assert bd["device"] == {"window_ms": pytest.approx(20.0), "busy_ms": pytest.approx(13.5),
                            "idle_ms": pytest.approx(6.5)}
    assert sum(v.get("idle_ms", 0.0) for v in s.values()) == pytest.approx(bd["device"]["idle_ms"])
    # a gap no nested span covers goes to "(no span)"
    gap = host_device_breakdown(rows[:3] + rows[5:6], capture)
    assert gap["spans"][NO_SPAN]["idle_ms"] == pytest.approx(14.5 - 9.0)
    # no capture: the host side alone; a capture without device ops: the same
    assert "device" not in host_device_breakdown(rows)
    assert "device" not in host_device_breakdown(rows, {**capture, "device_ops": {}})


def test_fault_and_resume_events_carry_resolvable_span_ids(tmp_path):
    """Acceptance pin (chaos-scenario span attribution): every fault.* and
    resume event of a preempt + sentinel-rollback + auto-resume run carries
    a span_id whose span row is present in the same stream."""
    from perceiver_io_tpu.training import (
        MetricsLogger,
        SentinelConfig,
        TrainState,
        Trainer,
        TrainerConfig,
        make_optimizer,
    )

    def loss_fn(params, batch, rng):
        pred = batch["x"] @ params["w"]
        loss = jnp.mean((pred - batch["y"]) ** 2)
        return loss, {"loss": loss}

    def fresh_state():
        return TrainState.create(
            None, {"w": jnp.zeros((3,))}, make_optimizer(1e-2), jax.random.PRNGKey(0)
        )

    def batches(poison_at=()):
        rng = np.random.default_rng(0)
        import itertools

        for i in itertools.count(1):
            x = rng.normal(size=(4, 3)).astype(np.float32)
            y = (x @ np.ones(3)).astype(np.float32)
            if i in poison_at:
                x = x.copy()
                x[0, 0] = np.nan
            yield {"x": x, "y": y}

    cfg = dict(
        log_interval=1, checkpoint_dir=str(tmp_path / "ckpt"), prefetch_batches=0,
        input_double_buffer=False, graphlint=False, graphcheck=False,
    )
    logger = MetricsLogger(str(tmp_path / "logs"), use_tensorboard=False)
    # phase 1: checkpoint at step 3 (val), sentinel skips at the poison
    # steps 5-6 then rolls back to it, programmatic preemption at step 7
    tr = Trainer(
        loss_fn,
        config=TrainerConfig(
            max_steps=9, val_interval=3,
            sentinel=SentinelConfig(skip_limit=2, rollback_limit=2), **cfg
        ),
        logger=logger,
    )
    orig = tr._train_step

    def tripping(state, batch, _orig=orig):
        out = _orig(state, batch)
        if int(out[0].step) == 7:
            tr._preempt_guard.trip()
        return out

    tr._train_step = tripping
    val_batch = next(batches())
    tr.fit(
        fresh_state(), batches(poison_at=(5, 6)), val_loader=[val_batch], model_config=None
    )
    tr.close()
    # phase 2: auto-resume appends a resume event to the same stream
    tr2 = Trainer(loss_fn, config=TrainerConfig(max_steps=8, **cfg), logger=logger)
    tr2.fit(fresh_state(), batches(), resume="auto")
    tr2.close()
    logger.close()

    events = []
    with open(tmp_path / "logs" / "events.jsonl") as f:
        events = [json.loads(line) for line in f if line.strip()]
    span_ids = {e["span_id"] for e in events if e["event"] == "span"}
    audited = [
        e for e in events if e["event"].startswith("fault.") or e["event"] == "resume"
    ]
    kinds = {e["event"] for e in audited}
    assert "fault.skip" in kinds and "fault.rollback" in kinds
    assert "fault.preempt" in kinds and "resume" in kinds
    for e in audited:
        assert e.get("span_id") in span_ids, f"{e['event']} not span-attributed: {e}"
    from perceiver_io_tpu.obs.events import validate_events

    assert validate_events(str(tmp_path / "logs")) == []


# --------------------------------------------------- events: shards, schema


def test_eventlog_shards_per_process_and_merge(tmp_path):
    from perceiver_io_tpu.obs.events import EventLog, merged_events

    d = str(tmp_path)
    # synthetic two-process program: each process writes its own shard
    e0 = EventLog(d, process_index=0, process_count=2)
    e1 = EventLog(d, process_index=1, process_count=2)
    assert os.path.basename(e0.path) == "events-p0.jsonl"
    assert os.path.basename(e1.path) == "events-p1.jsonl"
    assert e1._active  # non-zero processes WRITE in sharded mode
    e0.emit("a", seq=0)
    e1.emit("b", seq=0)
    e0.emit("c", seq=1)
    merged = merged_events(d)
    assert [e["event"] for e in merged] in (["a", "b", "c"], ["b", "a", "c"])

    # clock-skew tolerance: a shard whose wall clock stepped BACKWARDS keeps
    # its own file order (per-process history is authoritative)
    import json as _json

    with open(os.path.join(d, "events-p1.jsonl"), "a") as f:
        f.write(_json.dumps({"ts": 1.0, "event": "late", "schema_version": 1}) + "\n")
    merged = merged_events(d)
    names = [e["event"] for e in merged]
    assert names.index("late") > names.index("b")  # never reordered before b


def test_validate_events_catches_drift(tmp_path):
    from perceiver_io_tpu.obs.events import EventLog, validate_events

    d = str(tmp_path)
    events = EventLog(d, main_process=True)
    events.emit("fit_start", start_step=0, max_steps=2)
    events.emit("fit_end", step=2, aborted=False)
    assert validate_events(d) == []

    # a torn TAIL line is tolerated (killed runs are expected)...
    with open(events.path) as f:
        clean = f.read()
    with open(events.path, "a") as f:
        f.write('{"ts": 1, "event": "log", "step"')
    assert validate_events(d) == []
    # ...but planted drift is not: missing schema_version, missing required
    # field, unresolvable span reference
    with open(events.path, "w") as f:
        f.write(clean)
    with open(events.path, "a") as f:
        f.write(json.dumps({"ts": 1.0, "event": "log", "step": 1}) + "\n")  # no version
        f.write(json.dumps({"ts": 1.0, "event": "compile", "schema_version": 1}) + "\n")
        f.write(
            json.dumps(
                {"ts": 1.0, "event": "fault.skip", "schema_version": 1, "span_id": "dead"}
            )
            + "\n"
        )
    problems = validate_events(d)
    assert any("schema_version" in p for p in problems)
    assert any("compile" in p and "fn" in p for p in problems)
    assert any("dead" in p for p in problems)


# ------------------------------------------------------------------ metrics


def test_metrics_registry_counters_gauges_histograms():
    from perceiver_io_tpu.obs.metrics import MetricsRegistry, bucket_index

    reg = MetricsRegistry()
    c = reg.counter("requests_total", help="total requests")
    c.inc()
    c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("queue_depth")
    g.set(5)
    g.add(-2)
    assert g.value == 3
    h = reg.histogram("latency_s")
    for v in (0.001, 0.002, 0.002, 0.004, 0.1, 0.1, 0.1, 0.1, 0.1, 0.1):
        h.record(v)
    assert h.n == 10 and h.min == 0.001 and h.max == 0.1
    # bucket-derived percentiles: within one bucket width of the truth
    assert h.percentile(50) == pytest.approx(0.1, rel=0.25)
    assert h.percentile(99) == pytest.approx(0.1, rel=0.25)
    assert h.percentile(10) == pytest.approx(0.001, rel=0.25)  # nearest rank: 1st of 10
    # same name returns the same metric; wrong type raises
    assert reg.counter("requests_total") is c
    with pytest.raises(TypeError):
        reg.gauge("requests_total")
    # snapshot carries everything, histogram percentiles included
    snap = reg.snapshot()
    assert snap["counters"]["requests_total"] == 3
    assert snap["gauges"]["queue_depth"] == 3
    assert snap["histograms"]["latency_s"]["n"] == 10
    assert "p99" in snap["histograms"]["latency_s"]
    assert "low_n" not in snap["histograms"]["latency_s"]
    # low-sample histograms say so
    h2 = reg.histogram("rare_s")
    h2.record(1.0)
    assert reg.snapshot()["histograms"]["rare_s"]["low_n"] is True
    # one-sample percentile clamps to the observation, not the bucket mid
    assert h2.percentile(99) == 1.0
    assert bucket_index(0.0) == bucket_index(-1.0)  # clamped, no crash


def test_metrics_prometheus_and_event_snapshot(tmp_path):
    from perceiver_io_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("gen_requests", help="requests served").inc(4)
    reg.gauge("inflight").set(2)
    h = reg.histogram("ttft_seconds")
    h.record(0.5)
    h.record(1.5)
    text = reg.to_prometheus()
    assert "# TYPE gen_requests counter" in text
    assert "gen_requests 4" in text
    assert "# TYPE inflight gauge" in text
    assert "# TYPE ttft_seconds histogram" in text
    assert 'ttft_seconds_bucket{le="+Inf"} 2' in text
    assert "ttft_seconds_count 2" in text
    # cumulative bucket counts are monotone
    import re

    cums = [int(m) for m in re.findall(r'ttft_seconds_bucket\{le="[^+]*"\} (\d+)', text)]
    assert cums == sorted(cums)

    events = EventLog(str(tmp_path), main_process=True)
    reg.emit_snapshot(events)
    assert not reg.maybe_emit(events, min_interval_s=60)  # rate-limited
    rows = [e for e in read_events(tmp_path) if e["event"] == "metrics"]
    assert len(rows) == 1
    assert rows[0]["counters"]["gen_requests"] == 4
    assert rows[0]["histograms"]["ttft_seconds"]["n"] == 2


def test_histogram_counts_merge_exactly():
    """The property SLO aggregation rests on: merging two histograms' sparse
    counts equals recording every sample into one histogram."""
    from perceiver_io_tpu.obs.metrics import (
        Histogram,
        merge_counts,
        percentile_from_counts,
    )

    a, b, both = Histogram("a"), Histogram("b"), Histogram("both")
    rng = np.random.default_rng(3)
    for _ in range(200):
        v = float(rng.lognormal(-5, 1))
        (a if rng.random() < 0.5 else b).record(v)
        both.record(v)
    merged = merge_counts(a.counts, {str(k): v for k, v in b.counts.items()})
    assert merged == both.counts
    for p in (50, 90, 99):
        assert percentile_from_counts(merged, p) == pytest.approx(
            percentile_from_counts(both.counts, p)
        )


def test_histogram_empty_percentile_and_to_dict():
    """ISSUE 9 satellite: an empty histogram reports None percentiles (not
    a crash, not a fake 0) and a stat-free to_dict."""
    from perceiver_io_tpu.obs.metrics import Histogram, percentile_from_counts

    h = Histogram("empty_s")
    for p in (0, 50, 99, 100):
        assert h.percentile(p) is None
    assert percentile_from_counts({}, 50) is None
    d = h.to_dict()
    assert d["n"] == 0 and d["min"] is None and d["max"] is None
    assert "p50" not in d and "p99" not in d and "low_n" not in d
    with pytest.raises(ValueError):
        h.percentile(101)


def test_histogram_merge_exactly_associative_across_three_shards():
    """ISSUE 9 satellite: merging >= 3 shards' sparse counts is EXACTLY
    associative and commutative — any merge tree gives the same counts and
    the same percentiles (the property multi-process SLO aggregation and
    the obs_report fallback both lean on)."""
    from perceiver_io_tpu.obs.metrics import Histogram, merge_counts, percentile_from_counts

    rng = np.random.default_rng(7)
    shards = [Histogram(f"s{i}") for i in range(4)]
    ref = Histogram("ref")
    for _ in range(500):
        v = float(rng.lognormal(-6, 2))
        shards[int(rng.integers(0, 4))].record(v)
        ref.record(v)
    counts = [s.counts for s in shards]
    left = merge_counts(merge_counts(merge_counts(counts[0], counts[1]), counts[2]), counts[3])
    right = merge_counts(counts[0], merge_counts(counts[1], merge_counts(counts[2], counts[3])))
    flat = merge_counts(*counts)
    rev = merge_counts(*reversed(counts))
    assert left == right == flat == rev == ref.counts
    for p in (50, 90, 99):
        assert percentile_from_counts(flat, p) == percentile_from_counts(ref.counts, p)


def test_histogram_to_prometheus_bucket_monotonicity():
    """ISSUE 9 satellite: the exposition's cumulative buckets must be
    non-decreasing with strictly increasing le bounds, +Inf == count — on a
    histogram with GAPS between occupied buckets (the sparse-counts case a
    naive cumulative walk gets wrong)."""
    import re

    from perceiver_io_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    h = reg.histogram("gappy_s")
    for v in (1e-6, 1e-6, 1e-3, 5.0, 5.0, 5.0):  # three distant clusters
        h.record(v)
    text = reg.to_prometheus()
    pairs = re.findall(r'gappy_s_bucket\{le="([^"}]+)"\} (\d+)', text)
    les = [le for le, _ in pairs]
    cums = [int(c) for _, c in pairs]
    assert les[-1] == "+Inf" and cums[-1] == h.n == 6
    finite_les = [float(le) for le in les[:-1]]
    assert finite_les == sorted(finite_les) and len(set(finite_les)) == len(finite_les)
    assert cums == sorted(cums)  # non-decreasing cumulative counts
    assert "gappy_s_count 6" in text


def test_prometheus_exposition_golden():
    """ISSUE 11 satellite: the exposition FORMAT is the contract a real
    Prometheus scraper parses — pin it byte-for-byte. Per histogram: the
    cumulative sparse buckets, the ``+Inf`` bucket equal to ``_count``, and
    the ``_sum``/``_count`` series ``histogram_quantile``/``rate`` need;
    metrics name-sorted; HELP only where help text exists; names
    sanitized."""
    from perceiver_io_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("reqs").inc(3)
    reg.gauge("depth").set(2)
    h = reg.histogram("lat_s", help="request latency")
    h.record(1.0)  # bucket 0: le = 2**0.25
    h.record(2.0)  # bucket 4: le = 2**1.25
    assert reg.to_prometheus() == (
        "# TYPE depth gauge\n"
        "depth 2\n"
        "# HELP lat_s request latency\n"
        "# TYPE lat_s histogram\n"
        'lat_s_bucket{le="1.18921"} 1\n'
        'lat_s_bucket{le="2.37841"} 2\n'
        'lat_s_bucket{le="+Inf"} 2\n'
        "lat_s_sum 3\n"
        "lat_s_count 2\n"
        "# TYPE reqs counter\n"
        "reqs 3\n"
    )
    # dotted names sanitize to the Prometheus charset; empty registry is ""
    reg2 = MetricsRegistry()
    reg2.counter("a.b/c").inc()
    assert "a_b_c 1" in reg2.to_prometheus()
    assert MetricsRegistry().to_prometheus() == ""
    # an empty histogram still exposes a complete (+Inf/_sum/_count) family
    reg3 = MetricsRegistry()
    reg3.histogram("never_s")
    assert reg3.to_prometheus() == (
        "# TYPE never_s histogram\n"
        'never_s_bucket{le="+Inf"} 0\n'
        "never_s_sum 0\n"
        "never_s_count 0\n"
    )


def test_validate_events_unknown_kinds_warn_forward_compatibly(tmp_path):
    """ISSUE 9 satellite: kinds outside KNOWN_EVENT_KINDS are NEVER
    problems (older tooling survives newer streams) but are collected into
    warnings_out; probe/probe.blast rows get required-field checks."""
    from perceiver_io_tpu.obs.events import KNOWN_EVENT_KINDS, EventLog, validate_events

    d = str(tmp_path)
    events = EventLog(d, main_process=True)
    events.emit("fit_start", start_step=0, max_steps=1)
    events.emit("probe", step=1, scopes={"000:embed": {"rms": 1.0}})
    events.emit(
        "probe.blast", trigger="skip", scope="embed", step=1,
        affected=["embed"], n_affected=1,
    )
    events.emit("shiny.future_kind", payload=123)
    events.emit("shiny.future_kind", payload=456)  # second occurrence: one warning
    warnings_out = []
    problems = validate_events(d, warnings_out=warnings_out)
    assert problems == [], problems  # unknown kind is NOT a failure
    assert len(warnings_out) == 1 and "shiny.future_kind" in warnings_out[0]
    assert validate_events(d) == []  # no warnings_out: same verdict, no crash
    assert "probe" in KNOWN_EVENT_KINDS and "probe.blast" in KNOWN_EVENT_KINDS
    assert "fault.rollback" in KNOWN_EVENT_KINDS

    # planted drift in the probe kinds IS a failure
    events.emit("probe", scopes={})  # missing step
    events.emit("probe.blast", trigger="skip")  # missing scope/step/affected
    problems = validate_events(d)
    assert any("[probe]" in p and "step" in p for p in problems)
    assert any("[probe.blast]" in p and "scope" in p for p in problems)


def test_prometheus_exposition_golden_labeled():
    """ISSUE 16 satellite: labeled children (Simline per-tenant series)
    render INSIDE the parent's family — one # TYPE line, the unlabeled
    series first (the all-label total), then each child with its
    key-sorted, value-escaped label set — pinned byte-for-byte. The
    unlabeled golden above passing unchanged is the other half of the
    contract: a label-free registry's exposition is byte-identical to the
    pre-label format."""
    from perceiver_io_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    c = reg.counter("serve_reqs")
    c.inc(2)                            # the all-tenant total
    c.labels(tenant="acme").inc(1)
    c.labels(tenant='b"corp').inc(1)    # quote must escape in the value
    reg.gauge("depth").labels(tenant="acme").set(4)
    h = reg.histogram("lat_s")
    h.record(1.0)                       # bucket le = 2**0.25
    h.labels(tenant="acme").record(2.0)  # bucket le = 2**1.25
    assert reg.to_prometheus() == (
        "# TYPE depth gauge\n"
        "depth 0\n"
        'depth{tenant="acme"} 4\n'
        "# TYPE lat_s histogram\n"
        'lat_s_bucket{le="1.18921"} 1\n'
        'lat_s_bucket{le="+Inf"} 1\n'
        "lat_s_sum 1\n"
        "lat_s_count 1\n"
        'lat_s_bucket{tenant="acme",le="2.37841"} 1\n'
        'lat_s_bucket{tenant="acme",le="+Inf"} 1\n'
        'lat_s_sum{tenant="acme"} 2\n'
        'lat_s_count{tenant="acme"} 1\n'
        "# TYPE serve_reqs counter\n"
        "serve_reqs 2\n"
        'serve_reqs{tenant="acme"} 1\n'
        'serve_reqs{tenant="b\\"corp"} 1\n'
    )


def test_labeled_metrics_children_semantics_and_snapshot(tmp_path):
    """ISSUE 16 satellite: labels() is get-or-create on the sorted label
    set, children record independently of the parent, nesting is refused,
    and the metrics-event snapshot carries labeled series (plus gauge
    high-water marks in gauge_peaks) under rendered series names."""
    from perceiver_io_tpu.obs.events import EventLog, validate_events
    from perceiver_io_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    c = reg.counter("reqs")
    assert c.labels(tenant="a") is c.labels(tenant="a")  # get-or-create
    assert c.labels(tenant="a") is not c.labels(tenant="b")
    c.labels(tenant="a").inc(3)
    assert c.value == 0  # children never write the parent implicitly
    with pytest.raises(ValueError):
        c.labels(tenant="a").labels(zone="z")  # one level only
    with pytest.raises(ValueError):
        c.labels()
    g = reg.gauge("pages")
    g.labels(tenant="a").set(7)
    g.labels(tenant="a").set(2)
    assert g.labels(tenant="a").peak == 7  # high-water mark survives the drop
    snap = reg.snapshot()
    assert snap["counters"]['reqs{tenant="a"}'] == 3
    assert snap["gauges"]['pages{tenant="a"}'] == 2
    assert snap["gauge_peaks"]['pages{tenant="a"}'] == 7
    assert "pages" not in snap["gauge_peaks"]  # parent never written: no peak
    # the snapshot still validates as a metrics event row
    events = EventLog(str(tmp_path), main_process=True)
    reg.emit_snapshot(events)
    warnings_out = []
    assert validate_events(str(tmp_path), warnings_out=warnings_out) == []
    assert warnings_out == []


def test_metrics_registry_rate_limits_on_injected_clock(tmp_path):
    """Hostlint fix pin (clock-discipline): maybe_emit's rate limit runs on
    the injected clock, so a virtual-time (ManualClock) run emits snapshots
    on the virtual timeline instead of silently reading the wall."""
    from perceiver_io_tpu.obs.metrics import MetricsRegistry

    t = [100.0]
    reg = MetricsRegistry(clock=lambda: t[0])
    reg.counter("n").inc()
    events = EventLog(str(tmp_path), main_process=True)
    assert reg.maybe_emit(events, min_interval_s=30)
    assert not reg.maybe_emit(events, min_interval_s=30)  # inside the window
    t[0] += 29.0
    assert not reg.maybe_emit(events, min_interval_s=30)  # still inside
    t[0] += 1.5
    assert reg.maybe_emit(events, min_interval_s=30)  # virtual window passed
    rows = [e for e in read_events(tmp_path) if e["event"] == "metrics"]
    assert len(rows) == 2
