"""Preemption-safe training contracts (training/faults.py, docs/robustness.md).

The chaos harness (tools/chaos.py, ``tasks.py chaos``) certifies the same
behaviors end-to-end as a gate; these tests pin each piece — guard, sentinel
ladder, retry/backoff, quarantine, in-graph skip, trainer wiring — so a
regression names the broken part, not just the broken scenario.
"""

import itertools
import json
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.training import (
    DivergenceHalt,
    DivergenceSentinel,
    FetchRetriesExhausted,
    MetricsLogger,
    PreemptionGuard,
    QuarantineIterator,
    RetryPolicy,
    SentinelConfig,
    TrainState,
    Trainer,
    TrainerConfig,
    call_with_retry,
    make_optimizer,
)
from perceiver_io_tpu.training.loop import make_train_step


# ---------------------------------------------------------------------------
# fixture: trivial linear-regression step (compiles in milliseconds)
# ---------------------------------------------------------------------------


def loss_fn(params, batch, rng):
    pred = batch["x"] @ params["w"]
    loss = jnp.mean((pred - batch["y"]) ** 2)
    return loss, {"loss": loss}


def fresh_state(seed=0):
    tx = make_optimizer(1e-2)
    return TrainState.create(None, {"w": jnp.zeros((3,))}, tx, jax.random.PRNGKey(seed))


def batches(seed=0, n=3, poison_at=()):
    rng = np.random.default_rng(seed)
    for i in itertools.count(1):
        x = rng.normal(size=(4, n)).astype(np.float32)
        y = (x @ np.ones(n)).astype(np.float32)
        if i in poison_at:
            x = x.copy()
            x[0, 0] = np.nan
        yield {"x": x, "y": y}


def make_trainer(tmp_path, max_steps, sentinel=False, **cfg_kw):
    cfg = TrainerConfig(
        max_steps=max_steps,
        log_interval=1,
        checkpoint_dir=str(tmp_path / "ckpt"),
        prefetch_batches=0,
        input_double_buffer=False,
        graphlint=False,
        sentinel=sentinel,
        **cfg_kw,
    )
    logger = MetricsLogger(str(tmp_path / "logs"), use_tensorboard=False)
    return Trainer(loss_fn, config=cfg, logger=logger)


def record_losses(trainer, hook=None):
    losses = []
    orig = trainer._train_step

    def wrapped(state, batch, _orig=orig):
        state, metrics = _orig(state, batch)
        losses.append(float(metrics["loss"]))
        if hook is not None:
            hook(trainer, state)
        return state, metrics

    trainer._train_step = wrapped
    return losses


def events_of(tmp_path, kind):
    path = tmp_path / "logs" / "events.jsonl"
    if not os.path.exists(path):
        return []
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return [r for r in rows if r["event"] == kind]


# ---------------------------------------------------------------------------
# PreemptionGuard
# ---------------------------------------------------------------------------


def test_preemption_guard_catches_sigterm_and_uninstall_restores():
    guard = PreemptionGuard(signals=(signal.SIGTERM,))
    before = signal.getsignal(signal.SIGTERM)
    assert guard.install()
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        assert guard.requested
        assert guard.signal_count == 1
    finally:
        guard.uninstall()
    assert signal.getsignal(signal.SIGTERM) == before


def test_preemption_guard_second_sigint_escalates():
    guard = PreemptionGuard(signals=(signal.SIGINT,))
    assert guard.install()
    try:
        signal.raise_signal(signal.SIGINT)
        assert guard.requested  # first: cooperative
        with pytest.raises(KeyboardInterrupt):  # second: previous handler
            signal.raise_signal(signal.SIGINT)
    finally:
        guard.uninstall()


def test_preemption_guard_trip_is_programmatic():
    guard = PreemptionGuard()
    assert not guard.requested
    guard.trip()
    assert guard.requested


# ---------------------------------------------------------------------------
# DivergenceSentinel policy ladder
# ---------------------------------------------------------------------------


def test_sentinel_skip_escalates_to_rollback_then_halt():
    s = DivergenceSentinel(SentinelConfig(skip_limit=3, rollback_limit=1))
    assert s.observe(1, float("nan"), skipped=True).action == "skip"
    assert s.observe(2, float("nan"), skipped=True).action == "skip"
    d = s.observe(3, float("nan"), skipped=True)
    assert d.action == "rollback" and d.reason == "persistent-nonfinite"
    # after a rollback the consecutive counter restarts
    assert s.observe(4, float("nan"), skipped=True).action == "skip"
    assert s.observe(5, float("nan"), skipped=True).action == "skip"
    # second trip exceeds rollback_limit=1 -> halt
    assert s.observe(6, float("nan"), skipped=True).action == "halt"


def test_sentinel_nonfinite_without_skip_goes_straight_to_rollback():
    """No in-graph skip held the update (overlap step): the NaN already
    landed in params — waiting out skip_limit would train on garbage."""
    s = DivergenceSentinel(SentinelConfig(skip_limit=3, in_graph_skip=False))
    d = s.observe(1, float("nan"), skipped=False)
    assert d.action == "rollback" and d.reason == "nonfinite-applied"


def test_sentinel_spike_needs_history_and_patience():
    cfg = SentinelConfig(min_history=5, spike_factor=10.0, spike_patience=2, window=10)
    s = DivergenceSentinel(cfg)
    for i in range(5):
        assert s.observe(i, 1.0, skipped=False).action == "ok"
    d1 = s.observe(6, 100.0, skipped=False)  # spike 1: noted, not tripped
    assert d1.action == "ok" and d1.reason == "spike-noted"
    d2 = s.observe(7, 100.0, skipped=False)  # spike 2: patience reached
    assert d2.action == "rollback" and d2.reason == "loss-spike"
    # an isolated spike between normal losses never escalates
    s2 = DivergenceSentinel(cfg)
    for i in range(5):
        s2.observe(i, 1.0, skipped=False)
    assert s2.observe(6, 100.0, skipped=False).reason == "spike-noted"
    assert s2.observe(7, 1.0, skipped=False).action == "ok"
    assert s2.observe(8, 100.0, skipped=False).reason == "spike-noted"


def test_sentinel_rollback_unavailable_escalates():
    s = DivergenceSentinel(SentinelConfig())
    assert s.notify_rollback_unavailable().action == "halt"


# ---------------------------------------------------------------------------
# retry / backoff
# ---------------------------------------------------------------------------


def test_retry_backoff_schedule_and_exhaustion():
    sleeps = []
    calls = []

    def always_fails():
        calls.append(1)
        raise OSError("flaky")

    policy = RetryPolicy(max_retries=3, base_delay=0.1, max_delay=10.0, jitter=0.25)
    with pytest.raises(FetchRetriesExhausted) as ei:
        call_with_retry(always_fails, policy, sleep=sleeps.append)
    assert len(calls) == 4  # initial + 3 retries
    assert isinstance(ei.value.__cause__, OSError)
    # exponential with bounded jitter: delay(k) in base*2^k * [0.75, 1.25]
    assert len(sleeps) == 3
    for k, d in enumerate(sleeps):
        nominal = 0.1 * 2**k
        assert 0.75 * nominal <= d <= 1.25 * nominal
    # deterministic: the same policy reproduces the same schedule
    sleeps2 = []
    with pytest.raises(FetchRetriesExhausted):
        call_with_retry(always_fails, policy, sleep=sleeps2.append)
    assert sleeps == sleeps2


def test_retry_succeeds_midway_and_reports():
    state = {"left": 2}
    seen = []

    def flaky():
        if state["left"] > 0:
            state["left"] -= 1
            raise TimeoutError("transient")
        return "ok"

    policy = RetryPolicy(max_retries=5, base_delay=0.01)
    out = call_with_retry(flaky, policy, on_retry=lambda a, e, d: seen.append(a), sleep=lambda _: None)
    assert out == "ok"
    assert seen == [0, 1]


def test_retry_policy_delay_jitter_bounds():
    """The probe-spacing contract the serving breaker reuses (ISSUE 12):
    delay(k) = min(base * 2^k, max_delay) scaled by exactly [1-j, 1+j),
    deterministic per (seed, attempt), and the max_delay cap applies BEFORE
    the jitter scale (a capped delay still decorrelates)."""
    policy = RetryPolicy(max_retries=9, base_delay=0.1, max_delay=2.0, jitter=0.25)
    for k in range(10):
        nominal = min(0.1 * 2**k, 2.0)
        d = policy.delay(k)
        assert (1 - 0.25) * nominal <= d <= (1 + 0.25) * nominal, (k, d)
        assert d == policy.delay(k)  # deterministic per attempt
    # deep attempts: capped nominal, jitter still spreads them
    deep = {policy.delay(k) for k in range(6, 10)}
    assert len(deep) > 1 and all(1.5 <= d <= 2.5 for d in deep)
    # jitter=0: the exact uncapped/capped schedule, no randomness
    exact = RetryPolicy(base_delay=0.1, max_delay=2.0, jitter=0.0)
    assert [exact.delay(k) for k in range(6)] == [0.1, 0.2, 0.4, 0.8, 1.6, 2.0]
    # different seeds draw different scales at the same attempt
    assert RetryPolicy(jitter=0.25, seed=1).delay(0) != RetryPolicy(jitter=0.25, seed=2).delay(0)


def test_call_with_retry_reraise_original_for_serving_path():
    """The serving-path mode (ISSUE 12): ``reraise=True`` re-raises the
    ORIGINAL exception instance on exhaustion — the front end (and the
    breaker's half-open probes riding it) classify terminal outcomes by the
    real exception type, never a retry wrapper. The loader default is
    unchanged: one stable ``FetchRetriesExhausted`` with the cause chained."""
    boom = OSError("persistent store outage")
    calls, seen = [], []

    def always_fails():
        calls.append(1)
        raise boom

    policy = RetryPolicy(max_retries=2, base_delay=0.01)
    with pytest.raises(OSError) as ei:
        call_with_retry(always_fails, policy, on_retry=lambda a, e, d: seen.append(a),
                        sleep=lambda _: None, reraise=True)
    assert ei.value is boom  # the exact instance, not a wrapper
    assert len(calls) == 3 and seen == [0, 1]
    # default mode still wraps (the Batches/loader contract is untouched)
    with pytest.raises(FetchRetriesExhausted) as ei:
        call_with_retry(always_fails, policy, sleep=lambda _: None)
    assert ei.value.__cause__ is boom


def test_retry_non_transient_propagates_immediately():
    calls = []

    def bad():
        calls.append(1)
        raise ValueError("a bug, not flakiness")

    with pytest.raises(ValueError):
        call_with_retry(bad, RetryPolicy(max_retries=5), sleep=lambda _: None)
    assert len(calls) == 1


def test_fetch_retry_emitter_writes_events(tmp_path):
    from perceiver_io_tpu.obs.events import EventLog
    from perceiver_io_tpu.training import fetch_retry_emitter

    log = EventLog(str(tmp_path), main_process=True)
    on_retry = fetch_retry_emitter(log)
    state = {"left": 1}

    def flaky():
        if state["left"] > 0:
            state["left"] -= 1
            raise OSError("blip")
        return 42

    assert call_with_retry(flaky, RetryPolicy(base_delay=0.0), on_retry=on_retry, sleep=lambda _: None) == 42
    with open(tmp_path / "events.jsonl") as f:
        rows = [json.loads(line) for line in f]
    assert len(rows) == 1 and rows[0]["event"] == "fault.fetch_retry"
    assert rows[0]["attempt"] == 0 and "blip" in rows[0]["error"]


def test_batches_retry_absorbs_transient_fetch_errors():
    from perceiver_io_tpu.data.loader import Batches

    class Flaky:
        def __init__(self, fail_index, failures):
            self.fail_index = fail_index
            self.failures = failures

        def __len__(self):
            return 8

        def __getitem__(self, i):
            if i == self.fail_index and self.failures > 0:
                self.failures -= 1
                raise OSError("transient")
            return {"x": np.full((2,), i, np.float32)}

    clean = list(Batches(Flaky(5, 0), 2))
    retries = []
    resilient = list(
        Batches(
            Flaky(5, 2), 2,
            retry=RetryPolicy(max_retries=3, base_delay=0.0, jitter=0.0),
            on_retry=lambda a, e, d: retries.append(a),
        )
    )
    assert len(retries) == 2
    assert len(resilient) == len(clean)
    for a, b in zip(clean, resilient):
        np.testing.assert_array_equal(a["x"], b["x"])
    # exhausted retries surface as FetchRetriesExhausted, not silence
    with pytest.raises(FetchRetriesExhausted):
        list(Batches(Flaky(5, 99), 2, retry=RetryPolicy(max_retries=1, base_delay=0.0)))


# ---------------------------------------------------------------------------
# poison-batch quarantine
# ---------------------------------------------------------------------------


def test_quarantine_iterator_drops_poison_and_names_leaf():
    good = {"x": np.ones(3, np.float32), "ids": np.arange(3)}
    poison = {"x": np.array([1.0, np.nan, 2.0], np.float32), "ids": np.arange(3)}
    seen = []
    it = QuarantineIterator(
        iter([good, poison, good]), on_quarantine=lambda path, n: seen.append((path, n))
    )
    out = list(it)
    assert len(out) == 2
    assert it.n_quarantined == 1
    assert seen and "x" in seen[0][0]
    # int leaves can't be "non-finite": an all-int poison candidate passes
    assert QuarantineIterator(iter([{"ids": np.arange(3)}])).__next__() is not None


def test_quarantine_iterator_bounds_consecutive_drops():
    poison = {"x": np.array([np.nan], np.float32)}
    it = QuarantineIterator(itertools.repeat(poison), max_consecutive=4)
    with pytest.raises(RuntimeError, match="consecutive poison"):
        next(it)
    assert it.n_quarantined == 4


# ---------------------------------------------------------------------------
# in-graph sentinel skip (make_train_step(sentinel=True))
# ---------------------------------------------------------------------------


def test_in_graph_skip_holds_params_and_advances_step():
    step = make_train_step(loss_fn, donate=False, sentinel=True)
    state = fresh_state()
    gen = batches()
    clean = next(gen)
    state1, m1 = step(state, clean)
    assert float(m1["sentinel_skipped"]) == 0.0
    assert int(state1.step) == 1
    assert not np.array_equal(np.asarray(state1.params["w"]), np.asarray(state.params["w"]))

    poison = {k: v.copy() for k, v in next(gen).items()}
    poison["x"][0, 0] = np.nan
    state2, m2 = step(state1, poison)
    assert float(m2["sentinel_skipped"]) == 1.0
    assert int(state2.step) == 2  # step advances: the batch schedule holds
    np.testing.assert_array_equal(
        np.asarray(state2.params["w"]), np.asarray(state1.params["w"])
    )
    for a, b in zip(jax.tree.leaves(state2.opt_state), jax.tree.leaves(state1.opt_state)):
        if hasattr(a, "shape") and a.shape:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # rng still advanced (dropout streams must not repeat the skipped draw)
    assert not np.array_equal(np.asarray(state2.rng), np.asarray(state1.rng))

    # and the skipped trajectory continues finitely
    state3, m3 = step(state2, next(gen))
    assert np.isfinite(float(m3["loss"]))


# ---------------------------------------------------------------------------
# trainer wiring: preempt -> auto-resume equivalence, rollback, halt
# ---------------------------------------------------------------------------


def test_trainer_preempt_then_auto_resume_matches_uninterrupted(tmp_path):
    n_steps, kill_at = 10, 4

    ref_tr = make_trainer(tmp_path / "ref", n_steps)
    ref = record_losses(ref_tr)
    ref_tr.fit(fresh_state(), batches())
    ref_tr.close()

    run = tmp_path / "run"
    t1 = make_trainer(run, n_steps)

    def trip(trainer, state):
        if int(state.step) == kill_at:
            trainer._preempt_guard.trip()

    part1 = record_losses(t1, hook=trip)
    out1 = t1.fit(fresh_state(), batches())
    t1.close()
    assert int(out1.step) == kill_at
    assert events_of(run, "fault.preempt")
    fe = events_of(run, "fit_end")
    assert fe[-1]["preempted"] is True and fe[-1]["aborted"] is False

    t2 = make_trainer(run, n_steps)
    part2 = record_losses(t2)
    out2 = t2.fit(fresh_state(), batches(), resume="auto")
    t2.close()
    assert int(out2.step) == n_steps
    ev = events_of(run, "resume")
    assert ev[-1] == {
        **ev[-1],
        "from_step": 0,
        "to_step": kill_at,
        "fast_forward_batches": kill_at,
    }
    combined = part1 + part2
    assert len(combined) == len(ref)
    assert max(abs(a - b) for a, b in zip(ref, combined)) <= 1e-6

    # metrics.csv: truncation + re-logging leaves each step exactly once
    import csv

    with open(run / "logs" / "metrics.csv", newline="") as f:
        steps = [int(float(r["step"])) for r in csv.DictReader(f)]
    assert steps == list(range(1, n_steps + 1))


def test_trainer_auto_resume_without_checkpoint_starts_fresh(tmp_path):
    tr = make_trainer(tmp_path, 3)
    losses = record_losses(tr)
    out = tr.fit(fresh_state(), batches(), resume="auto")
    tr.close()
    assert int(out.step) == 3 and len(losses) == 3
    assert not events_of(tmp_path, "resume")


def test_trainer_sentinel_skip_event_and_recovery(tmp_path):
    tr = make_trainer(tmp_path, 6, sentinel=True)
    losses = record_losses(tr)
    tr.fit(fresh_state(), batches(poison_at=(3,)))
    tr.close()
    skips = events_of(tmp_path, "fault.skip")
    assert len(skips) == 1 and skips[0]["step"] == 3
    assert np.isfinite(losses[3:]).all()


def test_trainer_sentinel_rollback_restores_checkpoint(tmp_path):
    tr = make_trainer(
        tmp_path, 8,
        sentinel=SentinelConfig(skip_limit=2, rollback_limit=2),
        val_interval=3,
    )
    losses = record_losses(tr)
    tr.fit(
        fresh_state(),
        batches(poison_at=(5, 6)),
        val_loader=[next(batches(seed=7))],
    )
    tr.close()
    rb = events_of(tmp_path, "fault.rollback")
    assert len(rb) == 1
    assert rb[0]["from_step"] == 6 and rb[0]["to_step"] == 3
    assert rb[0]["reason"] == "persistent-nonfinite"
    assert np.isfinite(losses[-1])


def test_trainer_rollback_reinits_optimizer_for_weights_only_checkpoints(tmp_path):
    """A weights-only checkpoint cannot restore moments, so rollback must
    REINITIALIZE the optimizer instead of replaying with the (possibly
    poisoned) diverged moments (code-review finding)."""
    tr = make_trainer(
        tmp_path, 8,
        sentinel=SentinelConfig(skip_limit=2, rollback_limit=2),
        val_interval=3,
        save_weights_only=True,
    )
    losses = record_losses(tr)
    tr.fit(
        fresh_state(),
        batches(poison_at=(5, 6)),
        val_loader=[next(batches(seed=7))],
    )
    tr.close()
    rb = events_of(tmp_path, "fault.rollback")
    assert len(rb) == 1 and rb[0]["opt_reinit"] is True
    assert np.isfinite(losses[-1])


def test_trainer_sentinel_halt_raises_and_emits(tmp_path):
    tr = make_trainer(
        tmp_path, 8,
        sentinel=SentinelConfig(skip_limit=1, rollback_limit=0),
        val_interval=2,
    )
    with pytest.raises(DivergenceHalt):
        tr.fit(
            fresh_state(),
            batches(poison_at=tuple(range(3, 100))),
            val_loader=[next(batches(seed=7))],
        )
    tr.close()
    assert events_of(tmp_path, "fault.halt")
    fe = events_of(tmp_path, "fit_end")
    assert fe and fe[-1]["aborted"] is True


def test_trainer_halt_when_no_checkpoint_to_roll_back_to(tmp_path):
    cfg = TrainerConfig(
        max_steps=6, log_interval=1, prefetch_batches=0, input_double_buffer=False,
        graphlint=False, sentinel=SentinelConfig(skip_limit=1),
    )
    tr = Trainer(loss_fn, config=cfg, logger=MetricsLogger(str(tmp_path / "l"), use_tensorboard=False))
    with pytest.raises(DivergenceHalt):
        tr.fit(fresh_state(), batches(poison_at=(2,)))
    tr.close()


def test_trainer_quarantines_poison_batches(tmp_path):
    tr = make_trainer(tmp_path, 5, quarantine_poison_batches=True)
    losses = record_losses(tr)
    tr.fit(fresh_state(), batches(poison_at=(2,)))
    tr.close()
    ev = events_of(tmp_path, "fault.poison_batch")
    assert len(ev) == 1 and "x" in ev[0]["leaf"]
    assert np.isfinite(losses).all()  # the poison batch never reached the step


# ---------------------------------------------------------------------------
# MetricsLogger.truncate_after
# ---------------------------------------------------------------------------


def test_metrics_truncate_after(tmp_path):
    logger = MetricsLogger(str(tmp_path), use_tensorboard=False)
    for step in (1, 2, 3, 4):
        logger.log(step, {"loss": float(step)})
    assert logger.truncate_after(2) == 2
    import csv

    with open(tmp_path / "metrics.csv", newline="") as f:
        rows = list(csv.DictReader(f))
    assert [int(float(r["step"])) for r in rows] == [1, 2]
    # idempotent + appendable afterwards
    assert logger.truncate_after(2) == 0
    logger.log(3, {"loss": 3.0})
    with open(tmp_path / "metrics.csv", newline="") as f:
        rows = list(csv.DictReader(f))
    assert [int(float(r["step"])) for r in rows] == [1, 2, 3]
