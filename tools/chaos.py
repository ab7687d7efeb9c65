#!/usr/bin/env python
"""Chaos harness — deterministic fault injection against the train loop.

Certifies the faults subsystem (training/faults.py, docs/robustness.md) the
same way dryrun_multichip certifies sharding: by RUNNING the failure and
asserting recovery, not by unit-testing pieces. ``python tasks.py chaos`` is
the gate. Scenarios:

- ``preempt``       — a REAL SIGTERM mid-fit: the trainer saves at the step
                      boundary and returns; a fresh trainer with
                      ``resume="auto"`` fast-forwards the data stream and the
                      combined loss trajectory matches the uninterrupted run
                      to <= 1e-6.
- ``preempt_mesh``  — the same kill/resume cycle under a {data:2, fsdp:4}
                      mesh (8 virtual CPU devices; the harness respawns
                      itself like dryrun_multichip), so auto-resume is
                      certified against ``shard_train_state`` placements.
- ``fetch_error``   — transient loader fetch failures at a chosen step are
                      absorbed by ``Batches(retry=RetryPolicy(...))``: the
                      trajectory is IDENTICAL to the fault-free run.
- ``nan_skip``      — a single NaN batch trips the in-graph sentinel skip:
                      params hold, step advances, one ``fault.skip`` event.
- ``nan_rollback``  — persistent NaN batches escalate past ``skip_limit``
                      into rollback-to-last-checkpoint; the run completes
                      with finite loss and a ``fault.rollback`` event.
- ``torn_save``     — a checkpoint step dir torn post-commit is quarantined;
                      ``restore`` falls back to the previous good step and
                      never selects the torn one.

Elastic-resume scenarios (docs/robustness.md#elastic-resume) — the pod
comes back with a DIFFERENT shape. Each runs its kill and resume halves in
separate subprocesses with different virtual-device counts (the only honest
way to change topology), sharing the checkpoint dir; the combined loss
trajectory must match the uninterrupted reference <= 1e-6, the restore must
emit a span-attributed ``resume.reshard`` event with the right old/new
meshes, and the resumed train step must lint clean on the new mesh:

- ``elastic_shrink`` — kill under {data:2, fsdp:4} on 8 devices, resume
                       under {data:2, fsdp:2} on 4 (preempted pod-slice
                       downsize).
- ``elastic_grow``   — kill under {data:2, fsdp:2} on 4, resume under
                       {data:2, fsdp:4} on 8 (mid-run scale-up).
- ``flat_to_mesh``   — kill unsharded on 1 device, resume under
                       {data:2, fsdp:2} on 4 (single-host prototype moved
                       onto a pod).
- ``mesh_to_flat``   — kill under {data:2, fsdp:2} on 4, resume unsharded
                       on 1 (pod gone; limp home on one chip).

Serving scenarios (Shedline, perceiver_io_tpu/serving,
docs/robustness.md#serving-hardening) — the hardened front end under
injected serving failures, all wall-clock-free on a ``ManualClock``; every
scenario closes with a clean-books audit (every submitted request at
exactly one terminal outcome, zero leaked worker slots):

- ``serve_overload``        — open-loop arrivals outpace an injected 100 ms
                              service time: admission sheds (first-class
                              ``shed`` events, never silent), queue depth
                              stays bounded, warm TTFT p99 of ADMITTED
                              requests holds the declared SLO, and
                              ``/healthz``+``/slo`` report it all live.
- ``serve_kill_mid_decode`` — a request dies between tokens: books close
                              (``error``), the slot comes back, exactly one
                              flight dump names the dead request's span.
- ``serve_deadline``        — an injected stall blows a deadline
                              mid-decode: the ``on_token`` seam cancels,
                              the ``timeout`` event carries the partial
                              TTFT/TPOT, one ``timeout`` dump names it.
- ``serve_drain``           — a REAL SIGTERM mid-run: admission stops
                              (late submissions shed ``draining``), queued
                              work finishes, ``serve.drain`` carries the
                              balanced final books.
- ``serve_breaker``         — consecutive injected errors open the circuit
                              breaker (shed ``breaker_open``, one
                              ``breaker`` dump); the RetryPolicy-spaced
                              half-open probe closes it on the manual clock.
- ``serve_spec_kill_mid_span`` — Specline: a kill lands MID-SPAN inside the
                              speculative engine (a verify step emits
                              m ∈ [1, k+1] tokens; the per-token seam fires
                              for each): the slot retires at the killed
                              token, span remainder dropped, pages freed,
                              books balanced, acceptance telemetry on every
                              event row, one dump names the dead span.
- ``serve_evict_storm``     — Evictline: a page pool sized BELOW the live
                              demand forces real page-pressure evictions;
                              every fit-able request still reaches ``ok``
                              (zero ``kv_pages_exhausted`` sheds), resumed
                              streams are token-exact vs the uninterrupted
                              sequential reference (greedy AND temperature),
                              the extended books identity (``submitted ==
                              terminal + queued + in_flight + parked``)
                              closes, and every ``serve.evict``/
                              ``serve.resume`` event is span-attributed.
- ``serve_crash_recover``   — Evictline: the ENGINE dies mid-decode (an
                              injected ``EngineCrash`` no accounting seam
                              catches — the SIGKILL analog); a second
                              engine recovers every non-terminal request
                              from the write-ahead journal and serves it
                              token-exactly; the combined books balance
                              ACROSS the restart (journal ``submitted ==
                              terminal``), span-attributed
                              ``serve.recover`` events name each
                              re-admission.

Fleetline scenarios (serving/router.py — N engine replicas behind one
``FleetRouter`` submit surface, docs/serving.md#fleet):

- ``serve_fleet_failover`` — a REPLICA dies mid-decode (an injected
                              ``EngineCrash`` at a replica-step
                              coordinate): the router replays its
                              write-ahead journal onto the survivor,
                              which finishes every journaled request
                              token-exactly; the FLEET books balance
                              across the handoff (every index exactly
                              one terminal outcome, zero double-served
                              tokens), the dead journal closes with
                              handoff markers, and exactly one flight
                              dump names the dead replica.
- ``serve_fleet_brownout``  — one replica browns out (injected service-
                              time inflation): the EWMA health check
                              flips it ``degraded`` and least-outstanding
                              dispatch drains traffic onto the healthy
                              replica while the slow one STAYS in the
                              fleet — no failover, books balanced.
- ``serve_fleet_drain``     — a mid-run graceful drain: dispatch to the
                              draining replica stops (post-drain
                              submissions land only on the survivor),
                              its outstanding work finishes, and ZERO
                              sheds are attributable to the drain.

Simline scenarios (serving/sim.py — the REAL engine control plane under a
ManualClock with sampled service times; no jax, no model,
docs/serving.md#multi-tenant-telemetry):

- ``sim_tenant_storm``      — one tenant floods at 10x each victim's rate,
                              far over join capacity: admission degrades
                              PROPORTIONALLY (demand-normalized Jain >=
                              0.9, neither victim starves), every shed is
                              a tenant-stamped first-class row, books
                              balance at the full offered scale.
- ``sim_noisy_neighbor``    — a long-budget bulk tenant forces REAL
                              Evictline evictions on a half-size page
                              pool shared with a latency tenant: both
                              tenants fully served, and per-tenant
                              ``SLOBounds`` prove isolation — the latency
                              tenant's planted TTFT bound trips flight
                              dumps naming ONLY its rows while the bulk
                              tenant's generous bound never fires.
- ``sim_fleet``             — Fleetline scale certification: the SAME
                              10k-req/s merged workload through 1 then 2
                              replicas on the discrete-event fleet loop
                              (per-replica clocks, causal next-event
                              drive); 2 replicas must deliver >= 1.7x
                              the token throughput with the committed
                              ``sim_fairness_jain``/``sim_starvation_age_s``
                              floors held on BOTH runs.

``--scenarios`` accepts fnmatch globs: ``--scenarios 'serve_*'`` runs the
serving family standalone, ``--scenarios 'elastic_*,preempt'`` composes.
``--smoke`` shrinks the Evictline scenarios (greedy-only, fewer requests)
for the ``tasks.py perf`` CI leg; assertions are identical.

Every injection is count-/step-deterministic (no wall-clock, no randomness
outside seeded generators), so failures reproduce exactly.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import signal
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

TOL = 1e-6


# ---------------------------------------------------------------------------
# fixture: a tiny linear-regression step — compiles in milliseconds, losses
# are deterministic functions of (seed, step), and the parameter is large
# enough ((8, 4) floats) for fsdp to actually shard it under min_weight_size=0
# ---------------------------------------------------------------------------


def _loss_fn():
    import jax.numpy as jnp

    from perceiver_io_tpu.obs.probes import probe

    def loss_fn(params, batch, rng):
        # Probeline tap: when the trainer runs probed (the sentinel
        # scenarios), the prediction's numerics stats ride out of the step —
        # a NaN input batch makes "chaos.pred" the FIRST non-finite scope,
        # which the blast-radius report must name
        pred = probe("chaos.pred", batch["x"] @ params["w"])
        loss = jnp.mean((pred - batch["y"]) ** 2)
        return loss, {"loss": loss}

    return loss_fn


def _fresh_state():
    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.training import TrainState, make_optimizer

    tx = make_optimizer(1e-2)
    return TrainState.create(None, {"w": jnp.zeros((8, 4))}, tx, jax.random.PRNGKey(0))


def _batches(seed=0, batch_size=8, poison_at=()):
    """Infinite deterministic batch stream; ``poison_at`` (1-based fetch
    indices) yields batches with NaN inputs — the NaN-grad injection."""
    import numpy as np

    rng = np.random.default_rng(seed)
    for i in itertools.count(1):
        x = rng.normal(size=(batch_size, 8)).astype(np.float32)
        y = (x @ np.ones((8, 4))).astype(np.float32)
        if i in poison_at:
            x = x.copy()
            x[0, 0] = np.nan
        yield {"x": x, "y": y}


def _make_trainer(run_dir, max_steps, mesh=None, sentinel=False, **cfg_kw):
    from perceiver_io_tpu.training import MetricsLogger, Trainer, TrainerConfig

    cfg_kw.setdefault("graphlint", False)
    config = TrainerConfig(
        max_steps=max_steps,
        log_interval=1,
        checkpoint_dir=os.path.join(run_dir, "ckpt"),
        prefetch_batches=0,
        input_double_buffer=False,
        sentinel=sentinel,
        # sentinel scenarios run PROBED: a trip must produce a span-
        # attributed blast-radius report naming the planted scope
        probes=bool(sentinel),
        fsdp_min_weight_size=0,
        **cfg_kw,
    )
    logger = MetricsLogger(os.path.join(run_dir, "logs"), use_tensorboard=False)
    return Trainer(_loss_fn(), mesh=mesh, config=config, logger=logger)


def _record_losses(trainer, hook=None):
    """Wrap the trainer's step to host-fetch each loss (and optionally run a
    per-step injection hook)."""
    losses = []
    orig = trainer._train_step

    def wrapped(state, batch, _orig=orig):
        state, metrics = _orig(state, batch)
        losses.append(float(metrics["loss"]))
        if hook is not None:
            hook(trainer, state, metrics)
        return state, metrics

    trainer._train_step = wrapped
    return losses


def _assert_trajectories_match(ref, got, what):
    assert len(got) == len(ref), f"{what}: {len(got)} losses vs reference {len(ref)}"
    worst = max(abs(a - b) for a, b in zip(ref, got))
    assert worst <= TOL, f"{what}: trajectory diverged, max |d_loss| = {worst:.3e}"
    return worst


def _events(run_dir, kind):
    path = os.path.join(run_dir, "logs", "events.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(l) for l in f if json.loads(l).get("event") == kind]


def _assert_span_attributed(run_dir):
    """Spanline contract (ISSUE 8, extended by Evictline, Shareline and
    Fleetline): every fault.*/resume — and every per-request preemption,
    sharing or fleet-handoff event (``serve.evict``/``serve.resume``/
    ``serve.recover``/``serve.prefix_hit``/``serve.failover``) — in a
    chaos run must carry a span_id whose span row is in the same stream:
    an incident nobody can attribute to its step/request is an incident
    half-logged. Accepts both layouts (training runs log under ``logs/``,
    serving scenarios at the run dir root)."""
    path = os.path.join(run_dir, "logs", "events.jsonl")
    if not os.path.exists(path):
        path = os.path.join(run_dir, "events.jsonl")
    with open(path) as f:
        rows = [json.loads(l) for l in f if l.strip()]
    span_ids = {r.get("span_id") for r in rows if r.get("event") == "span"}
    audited = [
        r for r in rows
        if r.get("event", "").startswith("fault.")
        or r.get("event") in ("resume", "resume.reshard", "probe.blast",
                              "serve.evict", "serve.resume", "serve.recover",
                              "serve.prefix_hit", "serve.failover")
    ]
    for r in audited:
        assert r.get("span_id") in span_ids, (
            f"{r['event']} event not attributable to a span in-stream: {r}"
        )
    return len(audited)


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------


def scenario_preempt(tmp, mesh=None, tag="preempt"):
    """Kill-at-step-N via a real SIGTERM; auto-resume must reproduce the
    uninterrupted run's loss trajectory."""
    n_steps, kill_at = 12, 5
    ref_dir = os.path.join(tmp, f"{tag}_ref")
    tr = _make_trainer(ref_dir, n_steps, mesh=mesh)
    ref = _record_losses(tr)
    tr.fit(_fresh_state(), _batches())
    tr.close()

    run_dir = os.path.join(tmp, f"{tag}_run")
    t1 = _make_trainer(run_dir, n_steps, mesh=mesh)

    def kill(trainer, state, metrics):
        if int(state.step) == kill_at:
            # the real signal path: SIGTERM -> PreemptionGuard -> flag; the
            # loop notices at the next step boundary and saves
            os.kill(os.getpid(), signal.SIGTERM)

    part1 = _record_losses(t1, hook=kill)
    out1 = t1.fit(_fresh_state(), _batches())
    t1.close()
    assert int(out1.step) == kill_at, f"expected stop at {kill_at}, got {int(out1.step)}"
    assert _events(run_dir, "fault.preempt"), "no fault.preempt event emitted"

    t2 = _make_trainer(run_dir, n_steps, mesh=mesh)
    part2 = _record_losses(t2)
    out2 = t2.fit(_fresh_state(), _batches(), resume="auto")
    t2.close()
    assert int(out2.step) == n_steps
    ev = _events(run_dir, "resume")
    assert ev and ev[-1]["to_step"] == kill_at and ev[-1]["fast_forward_batches"] == kill_at
    worst = _assert_trajectories_match(ref, part1 + part2, tag)
    # no partial step dir may survive anywhere a restore could see it
    ckpt = os.path.join(run_dir, "ckpt")
    leftovers = [n for n in os.listdir(ckpt) if ".orbax-checkpoint-tmp" in n]
    assert not leftovers, f"tmp checkpoint leftovers: {leftovers}"
    n_attr = _assert_span_attributed(run_dir)
    print(f"chaos: {tag} ok — killed at {kill_at}, resumed, "
          f"{len(ref)} losses match <= {TOL:g} (worst {worst:.1e}), "
          f"{n_attr} fault/resume events span-attributed")


def scenario_preempt_mesh(tmp):
    """scenario_preempt under a {data:2, fsdp:4} mesh — certifies resume
    against shard_train_state placements (needs 8 devices; the entrypoint
    respawns with virtual CPU devices when short)."""
    import jax

    from perceiver_io_tpu.parallel import make_mesh

    assert len(jax.devices()) >= 8, "preempt_mesh needs 8 devices (respawn failed?)"
    mesh = make_mesh(devices=jax.devices()[:8], data=2, fsdp=4)
    scenario_preempt(tmp, mesh=mesh, tag="preempt_mesh")


def scenario_fetch_error(tmp):
    """Transient fetch errors at step N are retried with backoff inside the
    loader — the trajectory is identical to the fault-free run."""
    import numpy as np

    from perceiver_io_tpu.data.loader import Batches
    from perceiver_io_tpu.training.faults import RetryPolicy

    n_steps, fail_at_step, batch_size = 10, 4, 8

    class Dataset:
        def __init__(self, flaky=False):
            rng = np.random.default_rng(0)
            self.x = rng.normal(size=(n_steps * batch_size, 8)).astype(np.float32)
            self.flaky = flaky
            self.failures_left = 2 if flaky else 0
            self.fail_index = (fail_at_step - 1) * batch_size  # first fetch of step N
            self.retries_seen = 0

        def __len__(self):
            return len(self.x)

        def __getitem__(self, i):
            if self.flaky and i == self.fail_index and self.failures_left > 0:
                self.failures_left -= 1
                raise OSError("injected transient fetch failure")
            return {"x": self.x[i], "y": self.x[i] @ np.ones((8, 4), np.float32)}

    def run(flaky):
        tag = "flaky" if flaky else "clean"
        ds = Dataset(flaky=flaky)
        retries = []
        loader = Batches(
            ds, batch_size,
            retry=RetryPolicy(max_retries=3, base_delay=0.001, max_delay=0.002),
            on_retry=lambda a, e, d: retries.append((a, round(d, 6))),
        )
        tr = _make_trainer(os.path.join(tmp, f"fetch_{tag}"), n_steps)
        losses = _record_losses(tr)
        tr.fit(_fresh_state(), loader)
        tr.close()
        return losses, retries

    ref, _ = run(flaky=False)
    got, retries = run(flaky=True)
    assert len(retries) == 2, f"expected 2 retries, saw {retries}"
    worst = _assert_trajectories_match(ref, got, "fetch_error")
    print(f"chaos: fetch_error ok — 2 transient failures retried "
          f"(backoff {[d for _, d in retries]}), trajectory identical (worst {worst:.1e})")


def scenario_nan_skip(tmp):
    """One poison batch => one in-graph sentinel skip: params hold across the
    skipped step, the run completes, exactly one fault.skip event."""
    import numpy as np

    n_steps, poison_fetch = 10, 4
    run_dir = os.path.join(tmp, "nan_skip")
    tr = _make_trainer(run_dir, n_steps, sentinel=True)
    snapshots = []

    def snap(trainer, state, metrics):
        w = np.asarray(state.params["w"])
        snapshots.append((int(state.step), float(metrics["loss"]), w.copy()))

    losses = _record_losses(tr, hook=snap)
    tr.fit(_fresh_state(), _batches(poison_at=(poison_fetch,)))
    tr.close()
    assert len(losses) == n_steps
    skip_events = _events(run_dir, "fault.skip")
    assert len(skip_events) == 1 and skip_events[0]["step"] == poison_fetch, skip_events
    # params across the skipped step: unchanged (post-step-3 == post-step-4)
    w_before = snapshots[poison_fetch - 2][2]
    w_at = snapshots[poison_fetch - 1][2]
    assert np.array_equal(w_before, w_at), "skip did not hold params"
    assert not np.isnan(losses[poison_fetch:]).any(), "NaN leaked past the skip"
    blasts = _events(run_dir, "probe.blast")
    assert blasts and blasts[0]["scope"] == "chaos.pred" and blasts[0]["trigger"] == "skip", (
        f"skip not blast-attributed to the planted scope: {blasts}"
    )
    _assert_span_attributed(run_dir)
    print(f"chaos: nan_skip ok — poison batch at step {poison_fetch} skipped in-graph, "
          f"params held, blast named {blasts[0]['scope']!r}, final loss {losses[-1]:.4f} finite")


def scenario_nan_rollback(tmp):
    """Persistent NaN batches exhaust skip_limit and trip a rollback to the
    last checkpoint; the run then completes with finite loss."""
    import numpy as np

    from perceiver_io_tpu.training.faults import SentinelConfig

    n_steps = 12
    run_dir = os.path.join(tmp, "nan_rollback")
    tr = _make_trainer(
        run_dir, n_steps,
        sentinel=SentinelConfig(skip_limit=2, rollback_limit=2),
        val_interval=4,
    )
    losses = _record_losses(tr)
    # checkpoint lands at step 4 (val_interval); fetches 6+7 are poison —
    # two consecutive skips hit skip_limit=2 => rollback to step 4. The
    # injection is FETCH-indexed, so the replayed interval gets clean data.
    tr.fit(_fresh_state(), _batches(poison_at=(6, 7)), val_loader=[next(_batches(seed=9))])
    tr.close()
    rb = _events(run_dir, "fault.rollback")
    assert len(rb) == 1, f"expected 1 rollback, got {rb}"
    assert rb[0]["from_step"] == 7 and rb[0]["to_step"] == 4, rb
    finite = [l for l in losses if np.isfinite(l)]
    assert np.isfinite(losses[-1]) and len(finite) >= n_steps, "run did not recover"
    # Probeline blast radius (ISSUE 9): the trip must be ATTRIBUTED — a
    # probe.blast event naming the planted non-finite scope ("chaos.pred"
    # is the first probe in topological order; the NaN enters there), tied
    # to the offending step's span like every other fault event
    blasts = _events(run_dir, "probe.blast")
    assert blasts, "no probe.blast event despite a probed sentinel rollback"
    assert any(b.get("scope") == "chaos.pred" for b in blasts), (
        f"blast reports name {[b.get('scope') for b in blasts]}, "
        "expected the planted scope 'chaos.pred'"
    )
    assert all(b.get("trigger") in ("skip", "rollback", "halt") for b in blasts), blasts
    _assert_span_attributed(run_dir)
    print(f"chaos: nan_rollback ok — skip_limit tripped at step 7, rolled back to 4, "
          f"blast named {blasts[0]['scope']!r} (radius {blasts[0]['n_affected']}), "
          f"run completed with final loss {losses[-1]:.4f}")


def scenario_torn_save(tmp):
    """A torn (post-commit mutilated) step dir is quarantined and never
    selectable by restore/latest_step."""
    import shutil

    from perceiver_io_tpu.training.checkpoint import QUARANTINE_DIR, CheckpointManager

    ckpt = os.path.join(tmp, "torn", "ckpt")
    m = CheckpointManager(ckpt, max_to_keep=3, monitor="val_loss")
    s = _fresh_state()
    m.save(s.replace(step=s.step + 1), metrics={"val_loss": 1.0})
    s2 = s.replace(step=s.step + 2)
    m.save(s2, metrics={"val_loss": 0.5})
    m.close()
    # tear the newest step: drop its payload directory post-commit
    shutil.rmtree(os.path.join(ckpt, "2", "default"))

    m2 = CheckpointManager(ckpt, max_to_keep=3, monitor="val_loss")
    assert m2.latest_step() == 1, f"torn step selectable: latest={m2.latest_step()}"
    restored = m2.restore(_fresh_state())
    assert int(restored.step) == 1
    qdir = os.path.join(ckpt, QUARANTINE_DIR)
    assert os.path.isdir(qdir) and any(n.startswith("2") for n in os.listdir(qdir))
    m2.close()
    print("chaos: torn_save ok — mutilated step 2 quarantined, restore fell back to step 1")


# ---------------------------------------------------------------------------
# elastic resume: kill under one mesh/device-count, resume under another
# ---------------------------------------------------------------------------

# tag -> (kill mesh shape or None=flat, kill devices, resume shape, resume devices)
ELASTIC_SCENARIOS = {
    "elastic_shrink": (dict(data=2, fsdp=4), 8, dict(data=2, fsdp=2), 4),
    "elastic_grow": (dict(data=2, fsdp=2), 4, dict(data=2, fsdp=4), 8),
    "flat_to_mesh": (None, 1, dict(data=2, fsdp=2), 4),
    "mesh_to_flat": (dict(data=2, fsdp=2), 4, None, 1),
}


def _mesh_or_none(shape):
    if shape is None:
        return None
    import jax

    from perceiver_io_tpu.parallel import make_mesh

    need = 1
    for v in shape.values():
        need *= v
    assert len(jax.devices()) >= need, (
        f"mesh {shape} needs {need} devices, have {len(jax.devices())} (respawn failed?)"
    )
    return make_mesh(devices=jax.devices()[:need], **shape)


def _mesh_desc(mesh_axes):
    """Non-trivial axes of a fingerprint mesh dict ({} for flat/None)."""
    return {k: v for k, v in (mesh_axes or {}).items() if int(v) > 1}


def _elastic(tmp, tag, phase):
    """One mesh-elastic kill/resume cycle. ``phase=None`` orchestrates: the
    kill half (reference run + SIGTERM-at-step-5 run, both under the OLD
    mesh) and the resume half (``resume="auto"`` under the NEW mesh) each
    run in their own subprocess with that mesh's device count — a real
    topology change, not a same-process mesh swap. The resume phase does
    the asserting: combined trajectory == reference <= 1e-6, a
    span-attributed ``resume.reshard`` with the right old/new meshes, and
    a clean graphlint/graphcheck verdict on the resumed step."""
    kill_shape, kill_devices, resume_shape, resume_devices = ELASTIC_SCENARIOS[tag]
    n_steps, kill_at = 12, 5
    base = os.path.join(tmp, tag)

    if phase == "kill":
        mesh = _mesh_or_none(kill_shape)
        # uninterrupted reference under the ORIGINAL mesh — the trajectory
        # the kill+resume cycle must reproduce
        tr = _make_trainer(os.path.join(base, "ref"), n_steps, mesh=mesh)
        ref = _record_losses(tr)
        tr.fit(_fresh_state(), _batches())
        tr.close()

        t1 = _make_trainer(os.path.join(base, "run"), n_steps, mesh=mesh)

        def kill(trainer, state, metrics):
            if int(state.step) == kill_at:
                os.kill(os.getpid(), signal.SIGTERM)

        part1 = _record_losses(t1, hook=kill)
        out1 = t1.fit(_fresh_state(), _batches())
        t1.close()
        assert int(out1.step) == kill_at, f"{tag}: stopped at {int(out1.step)}, not {kill_at}"
        assert _events(os.path.join(base, "run"), "fault.preempt"), "no fault.preempt event"
        with open(os.path.join(base, "phase1.json"), "w") as f:
            json.dump({"ref": ref, "part1": part1}, f)
        return

    if phase == "resume":
        mesh = _mesh_or_none(resume_shape)
        with open(os.path.join(base, "phase1.json")) as f:
            d = json.load(f)
        run_dir = os.path.join(base, "run")
        # graphlint ON: the resumed step must lint clean ON THE NEW MESH
        t2 = _make_trainer(run_dir, n_steps, mesh=mesh, graphlint=True)
        part2 = _record_losses(t2)
        out2 = t2.fit(_fresh_state(), _batches(), resume="auto")
        t2.close()
        assert int(out2.step) == n_steps
        worst = _assert_trajectories_match(d["ref"], d["part1"] + part2, tag)

        ev = _events(run_dir, "resume")
        assert ev and ev[-1]["to_step"] == kill_at, ev
        assert ev[-1]["fast_forward_batches"] == kill_at, ev
        rr = _events(run_dir, "resume.reshard")
        assert rr, f"{tag}: no resume.reshard event despite a mesh change"
        r = rr[-1]
        assert r["step"] == kill_at, r
        assert _mesh_desc(r["old_mesh"]) == (kill_shape or {}), (
            f"{tag}: reshard old_mesh {r['old_mesh']} != killed mesh {kill_shape}"
        )
        assert _mesh_desc(r["new_mesh"]) == (resume_shape or {}), (
            f"{tag}: reshard new_mesh {r['new_mesh']} != resume mesh {resume_shape}"
        )
        assert r.get("leaves_resharded", 0) > 0 and r.get("bytes_moved", 0) > 0, r
        gl = _events(run_dir, "graphlint")
        assert gl and gl[-1].get("ok") is True and "error" not in gl[-1], (
            f"{tag}: resumed step failed graphlint on the new mesh: {gl}"
        )
        gc = _events(run_dir, "graphcheck")
        assert gc and "error" not in gc[-1], (
            f"{tag}: resumed step failed graphcheck fingerprinting: {gc}"
        )
        n_attr = _assert_span_attributed(run_dir)
        with open(os.path.join(base, "result.json"), "w") as f:
            json.dump(
                {"worst": worst, "reshard": r, "span_attributed": n_attr}, f
            )
        print(
            f"chaos: {tag} resume phase ok — mesh {_mesh_desc(r['old_mesh']) or 'flat'}"
            f" -> {_mesh_desc(r['new_mesh']) or 'flat'}, "
            f"{r['leaves_resharded']} leaves / {r['bytes_moved']}B resharded in "
            f"{r['wall_s']:.3f}s, trajectory worst {worst:.1e}, "
            f"{n_attr} events span-attributed, graphlint clean"
        )
        return

    # orchestrator: two subprocesses, two topologies, one checkpoint dir
    os.makedirs(base, exist_ok=True)
    rc = _respawn([tag], n_devices=kill_devices, phase="kill", tmp=tmp)
    assert rc == 0, f"{tag}: kill phase failed (rc={rc})"
    rc = _respawn([tag], n_devices=resume_devices, phase="resume", tmp=tmp)
    assert rc == 0, f"{tag}: resume phase failed (rc={rc})"
    with open(os.path.join(base, "result.json")) as f:
        result = json.load(f)
    print(
        f"chaos: {tag} ok — killed at step {kill_at} on {kill_devices} device(s), "
        f"resumed on {resume_devices}, {len(result['reshard'])}-field reshard event, "
        f"12 losses match <= {TOL:g} (worst {result['worst']:.1e})"
    )


def scenario_elastic_shrink(tmp, phase=None):
    _elastic(tmp, "elastic_shrink", phase)


def scenario_elastic_grow(tmp, phase=None):
    _elastic(tmp, "elastic_grow", phase)


def scenario_flat_to_mesh(tmp, phase=None):
    _elastic(tmp, "flat_to_mesh", phase)


def scenario_mesh_to_flat(tmp, phase=None):
    _elastic(tmp, "mesh_to_flat", phase)


# ---------------------------------------------------------------------------
# serving scenarios (Shedline): the hardened front end under injected
# serving failures — deterministic on a ManualClock, clean books certified
# ---------------------------------------------------------------------------

_SERVE_MODEL = {}


def _serving_model():
    """The serve_* scenarios run THE SAME tiny gate model as `tasks.py
    load` (tools/loadgen.py ``build_workload`` — one definition, so a
    geometry tweak there cannot desynchronize the two gates); cached per
    process."""
    if not _SERVE_MODEL:
        import importlib.util

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "loadgen_cli", os.path.join(repo, "tools", "loadgen.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        model, params, _config = mod.build_workload()
        _SERVE_MODEL.update(model=model, params=params)
    return _SERVE_MODEL["model"], _SERVE_MODEL["params"]


def _serve_env(tmp, tag, slo_ttft=None):
    """``(recorder, clock, run_dir)`` for one scenario — the recorder IS
    the event sink (it wraps a fresh EventLog over ``run_dir``)."""
    from perceiver_io_tpu.obs.events import EventLog
    from perceiver_io_tpu.obs.flightrec import FlightRecorder, SLOBounds
    from perceiver_io_tpu.serving import ManualClock

    run_dir = os.path.join(tmp, tag)
    events = EventLog(run_dir, main_process=True)
    recorder = FlightRecorder(events, out_dir=run_dir, slo=SLOBounds(ttft_s=slo_ttft))
    return recorder, ManualClock(), run_dir


def _serve_spec():
    from perceiver_io_tpu.obs.loadgen import WorkloadSpec

    # one compiled geometry (prompt 10, 4 new tokens): the scenarios certify
    # accounting, not the compile cache
    return WorkloadSpec(seed=7, prompt_lens=(10,), max_new_tokens=(4,))


def _audit_serving(frontend, run_dir, tag):
    """The clean-books + stream-integrity audit every serve_* scenario ends
    with: books balance exactly, zero leaked slots, the event stream
    validates with NO problems and NO forward-compat warnings."""
    from perceiver_io_tpu.obs.events import validate_events

    problems = frontend.audit()
    assert not problems, f"{tag}: books audit failed: {problems}"
    warnings_out = []
    stream_problems = validate_events(run_dir, warnings_out=warnings_out)
    assert not stream_problems, f"{tag}: event stream invalid: {stream_problems}"
    assert not warnings_out, f"{tag}: unexpected schema warnings: {warnings_out}"
    return frontend.books()


def _stream(run_dir):
    from perceiver_io_tpu.obs.events import merged_events

    return merged_events(run_dir)


def scenario_serve_overload(tmp):
    """Open-loop overload: arrivals at 50 req/s against an injected 100 ms
    service time. Admission must shed (honestly stamped), queue depth must
    stay bounded by the deadline, and warm TTFT p99 for ADMITTED requests
    must hold the declared SLO — all live on /healthz and /slo."""
    import json as _json
    import urllib.request

    from perceiver_io_tpu.obs.server import ObsServer
    from perceiver_io_tpu.obs.slo import build_slo_report
    from perceiver_io_tpu.serving import FaultInjector, FrontEndConfig, RequestFrontEnd

    ttft_slo, deadline, service = 1.0, 0.5, 0.1
    model, params = _serving_model()
    events, clock, run_dir = _serve_env(tmp, "serve_overload", slo_ttft=ttft_slo)
    injector = FaultInjector(clock=clock).stall_at(None, 1, service)
    fe = RequestFrontEnd(
        model, params, num_latents=4,
        config=FrontEndConfig(max_queue=32, est_service_s=service),
        events=events, clock=clock, sleep=clock.sleep, injector=injector,
    )
    with ObsServer(registry=fe.registry, run_dir=run_dir, health=fe.health) as server:
        recs = fe.run_open(_serve_spec().draw(40, 64), rate_rps=50.0,
                           deadline_s=deadline, seed=11)
        assert len(recs) == 40  # every arrival got a record, shed or served
        with urllib.request.urlopen(server.url + "/healthz", timeout=10) as r:
            health = _json.loads(r.read())
        with urllib.request.urlopen(server.url + "/slo", timeout=10) as r:
            slo_live = _json.loads(r.read())
    books = _audit_serving(fe, run_dir, "serve_overload")
    assert books["shed"] > 0 and books["ok"] > 0, books
    # borderline admits (projected wait ~= deadline) die mid-decode as
    # timeouts — also terminal, also accounted: nothing vanishes
    assert books["ok"] + books["timeout"] == books["admitted"], books
    # bounded queue: the deadline projection admits at most ~deadline/service
    # requests' worth of work ahead — far below the 32-deep queue cap
    bound = int(deadline / service) + 2
    assert books["max_queue_depth"] <= bound, (
        f"queue depth {books['max_queue_depth']} > deadline-implied bound {bound}"
    )
    report = build_slo_report(_stream(run_dir))
    assert report["n_requests"] == 40 and report["outcomes"]["shed"] == books["shed"]
    assert report.get("shed_rate", 0) > 0, "shed traffic not accounted in the SLO report"
    # the NON-vacuous admission guarantee, on the injected clock: admitted
    # requests waited at most ~their deadline (disable shedding and queue
    # waits grow to multiple seconds here — this is the assertion that
    # fails when admission control breaks; TTFT is real wall time on a
    # tiny CPU model, so its SLO check below guards the serving path, not
    # the queue)
    queue_p99 = report["queue_wait_s"]["p99"]
    assert queue_p99 <= deadline, (
        f"admitted-request queue-wait p99 {queue_p99}s exceeds the "
        f"{deadline}s deadline — admission projection is not bounding the queue"
    )
    ttft_p99 = report["ttft_s"]["p99"]
    assert ttft_p99 <= ttft_slo, (
        f"warm TTFT p99 {ttft_p99}s breaches the declared {ttft_slo}s SLO"
    )
    # every shed left a first-class request row — never a silent drop
    shed_rows = [e for e in _stream(run_dir)
                 if e.get("event") == "request" and e.get("outcome") == "shed"]
    assert len(shed_rows) == books["shed"]
    assert all(e.get("shed_reason") for e in shed_rows)
    assert health["breaker"]["state"] == "closed" and health["books_balanced"] is True
    assert slo_live["n_requests"] == 40
    print(
        f"chaos: serve_overload ok — {books['ok']} served / {books['timeout']} "
        f"deadline-timeout / {books['shed']} shed "
        f"(reasons {sorted({e['shed_reason'] for e in shed_rows})}), queue depth "
        f"<= {books['max_queue_depth']}, admitted queue-wait p99 {queue_p99}s <= "
        f"{deadline}s deadline, warm ttft_p99 {ttft_p99}s <= {ttft_slo}s SLO, "
        "books balanced, /healthz+/slo live"
    )


def scenario_serve_kill_mid_decode(tmp):
    """A request dies between tokens: the slot is freed, books close with
    exactly one ``error``, and exactly one flight dump names the dead
    request's span."""
    from perceiver_io_tpu.serving import FaultInjector, RequestFrontEnd

    model, params = _serving_model()
    recorder, clock, run_dir = _serve_env(tmp, "serve_kill")
    injector = FaultInjector(clock=clock).kill_at(3, 2)
    fe = RequestFrontEnd(model, params, num_latents=4, events=recorder,
                         clock=clock, sleep=clock.sleep, injector=injector)
    recs = fe.run_closed(_serve_spec().draw(8, 64), concurrency=2)
    books = _audit_serving(fe, run_dir, "serve_kill_mid_decode")
    assert [r.outcome for r in recs].count("error") == 1 and books["error"] == 1
    assert books["admitted"] == 8 and books["ok"] == 7, books
    dead = next(r for r in recs if r.outcome == "error")
    assert dead.index == 3 and 0 < dead.tokens_out < dead.max_new_tokens, vars(dead)
    assert [i["kind"] for i in injector.injected] == ["kill"]
    dumps = recorder.dumps
    assert len(dumps) == 1 and "flight-error" in os.path.basename(dumps[0]), dumps
    with open(dumps[0]) as f:
        dump = json.load(f)
    err_rows = [e for e in _stream(run_dir)
                if e.get("event") == "request" and e.get("outcome") == "error"]
    assert len(err_rows) == 1
    assert dump["trigger_span_id"] == err_rows[0]["span_id"], (
        "flight dump does not name the dead request's span"
    )
    assert any(e.get("event") == "span" and e.get("span_id") == dump["trigger_span_id"]
               for e in dump["events"]), "dump ring lacks the named span"
    print(
        f"chaos: serve_kill_mid_decode ok — request 3 killed after "
        f"{dead.tokens_out} token(s), slot freed, books balanced "
        f"(7 ok / 1 error), 1 flight dump names its span"
    )


def scenario_serve_deadline(tmp):
    """An injected stall blows a request's deadline mid-decode: the
    ``on_token`` seam cancels it, the ``timeout`` request event carries the
    partial TTFT/TPOT, and one ``timeout`` dump names the span."""
    from perceiver_io_tpu.serving import FaultInjector, RequestFrontEnd

    model, params = _serving_model()
    recorder, clock, run_dir = _serve_env(tmp, "serve_deadline")
    injector = FaultInjector(clock=clock).stall_at(2, 1, 5.0)  # >> deadline
    fe = RequestFrontEnd(model, params, num_latents=4, events=recorder,
                         clock=clock, sleep=clock.sleep, injector=injector)
    recs = fe.run_closed(_serve_spec().draw(5, 64), concurrency=1, deadline_s=1.0)
    books = _audit_serving(fe, run_dir, "serve_deadline")
    timed_out = [r for r in recs if r.outcome == "timeout"]
    assert len(timed_out) == 1 and timed_out[0].index == 2, recs
    assert books["ok"] == 4 and books["timeout"] == 1, books
    # the partial stream is accounted: >=1 token out before the cut
    assert 0 < timed_out[0].tokens_out < timed_out[0].max_new_tokens
    rows = [e for e in _stream(run_dir)
            if e.get("event") == "request" and e.get("outcome") == "timeout"]
    assert len(rows) == 1
    row = rows[0]
    assert row["tokens_out"] == timed_out[0].tokens_out
    assert row["ttft_s"] > 0 and row.get("tpot_hist"), (
        "timeout event lacks the partial TTFT/TPOT it must carry"
    )
    dumps = recorder.dumps
    assert len(dumps) == 1 and "flight-timeout" in os.path.basename(dumps[0]), dumps
    with open(dumps[0]) as f:
        dump = json.load(f)
    assert dump["trigger_span_id"] == row["span_id"]
    print(
        f"chaos: serve_deadline ok — request 2 cancelled mid-decode after "
        f"{timed_out[0].tokens_out} token(s) (5.0s stall vs 1.0s deadline), "
        "timeout event carries partial TTFT/TPOT, 1 timeout dump names its span"
    )


def scenario_serve_drain(tmp):
    """A REAL SIGTERM mid-run: the PreemptionGuard flips the front end into
    drain — admission stops (late submissions shed ``draining``), queued
    work finishes, and ``serve.drain`` carries the balanced final books."""
    from perceiver_io_tpu.serving import RequestFrontEnd

    model, params = _serving_model()
    recorder, clock, run_dir = _serve_env(tmp, "serve_drain")
    fe = RequestFrontEnd(model, params, num_latents=4, events=recorder,
                         clock=clock, sleep=clock.sleep)
    guard = fe.install_guard()
    try:
        specs = _serve_spec().draw(7, 64)
        for s in specs[:5]:
            fe.submit(s)
        fe.pump(max_requests=2)
        os.kill(os.getpid(), signal.SIGTERM)  # the real signal path
        fe.pump()  # guard noticed at the boundary; queued work still finishes
        late = [fe.submit(s) for s in specs[5:]]
        books = fe.drain()
    finally:
        guard.uninstall()
    assert guard.requested and books["draining"] is True
    assert all(r.outcome == "shed" and r.shed_reason == "draining" for r in late), late
    assert books["ok"] == 5 and books["shed"] == 2 and books["balanced"], books
    _audit_serving(fe, run_dir, "serve_drain")
    stream = _stream(run_dir)
    assert any(e.get("event") == "serve.preempt" for e in stream), (
        "no serve.preempt event for the SIGTERM"
    )
    drains = [e for e in stream if e.get("event") == "serve.drain"]
    assert len(drains) == 1 and drains[0]["books"]["balanced"] is True, drains
    assert drains[0]["books"]["in_flight"] == 0 and drains[0]["books"]["queued"] == 0
    print(
        "chaos: serve_drain ok — SIGTERM mid-run, 3 queued requests finished, "
        "2 late submissions shed as draining, serve.drain books balanced"
    )


def scenario_serve_breaker(tmp):
    """Consecutive injected errors open the circuit breaker: admissions
    shed ``breaker_open`` with a ``breaker`` flight dump; after the
    RetryPolicy-spaced probe delay (stepped on the manual clock) the
    half-open probe closes it again."""
    from perceiver_io_tpu.serving import (
        BreakerConfig,
        FaultInjector,
        FrontEndConfig,
        RequestFrontEnd,
    )
    from perceiver_io_tpu.training.faults import RetryPolicy

    model, params = _serving_model()
    recorder, clock, run_dir = _serve_env(tmp, "serve_breaker")
    injector = FaultInjector(clock=clock)
    for i in (1, 2, 3):
        injector.kill_at(i, 1)
    cfg = FrontEndConfig(breaker=BreakerConfig(
        window=4, min_requests=3, error_rate_to_open=0.5,
        probe_backoff=RetryPolicy(base_delay=2.0, max_delay=10.0, jitter=0.0),
    ))
    fe = RequestFrontEnd(model, params, num_latents=4, config=cfg, events=recorder,
                         clock=clock, sleep=clock.sleep, injector=injector)
    specs = _serve_spec().draw(10, 64)
    recs = fe.run_closed(specs[:8], concurrency=1)
    assert fe.breaker.state == "open", fe.breaker.state
    breaker_sheds = [r for r in recs if r.shed_reason == "breaker_open"]
    assert breaker_sheds, "breaker open but nothing shed"
    # probe spacing is the RetryPolicy schedule: jitter=0 -> exactly base_delay
    early = fe.submit(specs[8])
    assert early.outcome == "shed" and early.shed_reason == "breaker_open"
    clock.advance(2.0)
    probe = fe.submit(specs[9])
    fe.pump()
    assert probe.probe is True and probe.outcome == "ok", vars(probe)
    assert fe.breaker.state == "closed"
    books = _audit_serving(fe, run_dir, "serve_breaker")
    transitions = [(e["prev"], e["state"], e["reason"])
                   for e in _stream(run_dir) if e.get("event") == "serve.breaker"]
    assert transitions == [
        ("closed", "open", "error-rate"),
        ("open", "half_open", "probe-delay-elapsed"),
        ("half_open", "closed", "probe-succeeded"),
    ], transitions
    assert any("flight-breaker" in os.path.basename(p) for p in recorder.dumps), (
        recorder.dumps
    )
    print(
        f"chaos: serve_breaker ok — {books['error']} injected errors opened the "
        f"breaker ({len(breaker_sheds) + 1} shed breaker_open, 1 breaker dump), "
        "2.0s probe delay on the manual clock, half-open probe closed it"
    )


def scenario_serve_engine_kill_mid_decode(tmp):
    """Pageline: a request dies between tokens INSIDE a live decode batch —
    only its slot retires (the rest of the batch keeps decoding), its pages
    return to the free list, books close with exactly one ``error``, and
    exactly one flight dump names the dead request's span."""
    from perceiver_io_tpu.serving import EngineConfig, EngineFrontEnd, FaultInjector

    model, params = _serving_model()
    recorder, clock, run_dir = _serve_env(tmp, "serve_engine_kill")
    injector = FaultInjector(clock=clock).kill_at(3, 2)
    fe = EngineFrontEnd(
        model, params, num_latents=4,
        engine_config=EngineConfig(slots=4, page_size=8, max_ca_tokens=24,
                                   max_sa_tokens=16),
        events=recorder, clock=clock, sleep=clock.sleep, injector=injector,
    )
    recs = fe.run_closed(_serve_spec().draw(8, 64), concurrency=4)
    books = _audit_serving(fe, run_dir, "serve_engine_kill_mid_decode")
    assert [r.outcome for r in recs].count("error") == 1 and books["error"] == 1
    assert books["admitted"] == 8 and books["ok"] == 7, books
    dead = next(r for r in recs if r.outcome == "error")
    assert dead.index == 3 and 0 < dead.tokens_out < dead.max_new_tokens, vars(dead)
    # page-exact clean books: every page back on the free list, allocator
    # invariants hold (no double-ownership, no leak)
    assert fe.ca_alloc.pages_used == 0 and fe.sa_alloc.pages_used == 0, (
        fe.ca_alloc.pages_used, fe.sa_alloc.pages_used
    )
    assert fe.ca_alloc.audit() == [] and fe.sa_alloc.audit() == []
    dumps = recorder.dumps
    assert len(dumps) == 1 and "flight-error" in os.path.basename(dumps[0]), dumps
    with open(dumps[0]) as f:
        dump = json.load(f)
    err_rows = [e for e in _stream(run_dir)
                if e.get("event") == "request" and e.get("outcome") == "error"]
    assert len(err_rows) == 1
    assert dump["trigger_span_id"] == err_rows[0]["span_id"], (
        "flight dump does not name the dead request's span"
    )
    # the batch stayed live: the victim's event shows >1 requests in its
    # decode batch, and the survivors' streams completed in full
    assert err_rows[0].get("batch_size_at_decode", 0) > 1, err_rows[0]
    ok_rows = [e for e in _stream(run_dir)
               if e.get("event") == "request" and e.get("outcome") == "ok"]
    assert all(e["tokens_out"] == 4 for e in ok_rows), ok_rows
    print(
        f"chaos: serve_engine_kill_mid_decode ok — request 3 killed after "
        f"{dead.tokens_out} token(s) in a live batch "
        f"(batch_size {err_rows[0]['batch_size_at_decode']}), slot + pages freed, "
        "books balanced (7 ok / 1 error), 1 flight dump names its span"
    )


def scenario_serve_engine_pages(tmp):
    """Pageline page-pool discipline: an impossible request (KV footprint
    over the pool) sheds ``kv_pages_exhausted`` at admission; a pool sized
    BELOW the slot count exerts backpressure (requests wait for pages, none
    shed) and still serves everything; the allocator's books stay exact."""
    from perceiver_io_tpu.obs.loadgen import RequestSpec
    from perceiver_io_tpu.serving import EngineConfig, EngineFrontEnd

    import numpy as np

    model, params = _serving_model()
    recorder, clock, run_dir = _serve_env(tmp, "serve_engine_pages")
    # pool_headroom 0.5: pages for ~2 of the 4 slots — joins must wait
    fe = EngineFrontEnd(
        model, params, num_latents=4,
        engine_config=EngineConfig(slots=4, page_size=8, max_ca_tokens=24,
                                   max_sa_tokens=16, pool_headroom=0.5),
        events=recorder, clock=clock, sleep=clock.sleep,
    )
    specs = list(_serve_spec().draw(8, 64))
    # an impossible request: prompt + budget over max_ca_tokens
    rng = np.random.default_rng(3)
    specs.append(RequestSpec(index=len(specs), prompt_len=20, max_new_tokens=16,
                             input_ids=rng.integers(0, 64, size=(1, 20)),
                             rng_seed=7))
    recs = fe.run_closed(specs, concurrency=9)
    books = _audit_serving(fe, run_dir, "serve_engine_pages")
    assert books["ok"] == 8 and books["shed"] == 1 and books["balanced"], books
    shed = [r for r in recs if r.outcome == "shed"]
    assert len(shed) == 1 and shed[0].shed_reason == "kv_pages_exhausted", shed
    shed_rows = [e for e in _stream(run_dir)
                 if e.get("event") == "request" and e.get("outcome") == "shed"]
    assert len(shed_rows) == 1 and shed_rows[0]["shed_reason"] == "kv_pages_exhausted"
    assert fe.ca_alloc.pages_used == 0 and fe.ca_alloc.audit() == []
    assert fe.sa_alloc.pages_used == 0 and fe.sa_alloc.audit() == []
    # backpressure really happened: the half-size CA pool (6 pages, 2 per
    # request) caps the live batch at 3 of 4 slots — the 4th join must wait
    # for a retire, so mean fill can never reach the full-pool value
    assert fe.mean_batch_fill <= 0.75 + 1e-6, fe.mean_batch_fill
    print(
        "chaos: serve_engine_pages ok — half-size pool backpressured joins "
        f"(mean batch fill {fe.mean_batch_fill:.2f}, page-capped at 3 of 4 "
        "slots), 8 served / 1 impossible request shed kv_pages_exhausted, "
        "page books exact"
    )


def scenario_serve_spec_kill_mid_span(tmp):
    """Specline: a request dies MID-SPAN inside the speculative engine —
    a verify step emits m ∈ [1, k+1] tokens and streams each through the
    per-token seam, so the kill takes effect at its exact token index even
    when that index lands inside a span: the slot retires ``error`` there,
    the span's remaining tokens are dropped (never served), pages return,
    books balance, every request row carries acceptance telemetry, and one
    flight dump names the dead request's span."""
    from perceiver_io_tpu.serving import EngineConfig, EngineFrontEnd, FaultInjector

    model, params = _serving_model()
    recorder, clock, run_dir = _serve_env(tmp, "serve_spec_kill")
    injector = FaultInjector(clock=clock).kill_at(3, 2)
    fe = EngineFrontEnd(
        model, params, num_latents=4,
        # max_sa_tokens == the gate model's max_latents: the speculative
        # no-slide contract, validated at construction
        engine_config=EngineConfig(slots=4, page_size=8, max_ca_tokens=24,
                                   max_sa_tokens=8, spec_k=2, spec_depth=1),
        events=recorder, clock=clock, sleep=clock.sleep, injector=injector,
    )
    recs = fe.run_closed(_serve_spec().draw(8, 64), concurrency=4)
    books = _audit_serving(fe, run_dir, "serve_spec_kill_mid_span")
    assert [r.outcome for r in recs].count("error") == 1 and books["error"] == 1
    assert books["admitted"] == 8 and books["ok"] == 7, books
    dead = next(r for r in recs if r.outcome == "error")
    assert dead.index == 3 and 0 < dead.tokens_out < dead.max_new_tokens, vars(dead)
    # the kill's token index is exact: tokens 0..2 served, nothing after
    assert dead.tokens_out == 3 and len(fe.served_tokens[3]) == 3
    assert fe.ca_alloc.pages_used == 0 and fe.sa_alloc.pages_used == 0
    assert fe.ca_alloc.audit() == [] and fe.sa_alloc.audit() == []
    rows = [e for e in _stream(run_dir) if e.get("event") == "request"]
    assert len(rows) == 8
    # the measurement satellite holds under chaos: every row carries the
    # acceptance pair, and the spec step really batched multiple tokens
    assert all(isinstance(e.get("acceptance_rate"), (int, float)) for e in rows)
    assert all(e.get("tokens_per_step", 0) >= 1.0 for e in rows)
    assert any(e["tokens_per_step"] > 1.0 for e in rows), (
        "no request emitted more than one token per verify step — the "
        "mid-SPAN property is vacuous"
    )
    dumps = recorder.dumps
    assert len(dumps) == 1 and "flight-error" in os.path.basename(dumps[0]), dumps
    with open(dumps[0]) as f:
        dump = json.load(f)
    err_rows = [e for e in rows if e.get("outcome") == "error"]
    assert len(err_rows) == 1
    assert dump["trigger_span_id"] == err_rows[0]["span_id"], (
        "flight dump does not name the dead request's span"
    )
    ok_rows = [e for e in rows if e.get("outcome") == "ok"]
    assert all(e["tokens_out"] == 4 for e in ok_rows), ok_rows
    tps = [e["tokens_per_step"] for e in ok_rows]
    print(
        f"chaos: serve_spec_kill_mid_span ok — request 3 killed at token 3 "
        f"mid-span (k=2 spec engine, tokens/step up to {max(tps):.2f}), span "
        "remainder dropped, slot + pages freed, books balanced "
        "(7 ok / 1 error), acceptance telemetry on all 8 rows, 1 dump names the span"
    )


# ---------------------------------------------------------------------------
# Evictline scenarios: page-pressure eviction with token-exact resume, and
# journal-backed engine crash recovery (docs/robustness.md
# #engine-eviction-and-recovery)
# ---------------------------------------------------------------------------

# set by --smoke: the Evictline scenarios shrink to their CI-fast shape
# (greedy-only, fewer requests) with IDENTICAL assertions
SMOKE = False


def _evict_gen_configs():
    """(tag, GenerationConfig) pairs the Evictline scenarios certify
    token-exactness under — greedy AND temperature sampling (the rng-chain
    alignment claim is vacuous under argmax alone); --smoke keeps greedy."""
    from perceiver_io_tpu.generation import GenerationConfig

    configs = [("greedy", GenerationConfig())]
    if not SMOKE:
        configs.append(
            ("temperature", GenerationConfig(do_sample=True, temperature=0.8, top_k=10))
        )
    return configs


def _sequential_reference(model, params, spec, base_config):
    """The uninterrupted stream: the spec decoded alone through the
    contiguous host-driven pair with its pinned rng chain — what an
    evicted/recovered request's served tokens must equal exactly."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.generation import make_decode_fns

    cfg = _dc.replace(base_config, max_new_tokens=spec.max_new_tokens)
    prefill, step = make_decode_fns(model, 4, cfg)
    tok, state = prefill(
        params, jnp.asarray(spec.input_ids), None, jax.random.PRNGKey(spec.rng_seed)
    )
    out = [int(tok[0])]
    for _ in range(spec.max_new_tokens - 1):
        state, tok = step(state)
        out.append(int(tok[0]))
    return out


def _evict_workload(n):
    """Mixed-geometry specs under the no-slide eviction bound of the gate
    model (max_latents 8, num_latents 4 => budgets <= 4)."""
    from perceiver_io_tpu.obs.loadgen import WorkloadSpec

    return WorkloadSpec(seed=13, prompt_lens=(8, 12), max_new_tokens=(3, 4)).draw(n, 64)


def scenario_serve_evict_storm(tmp):
    """Evictline page-pressure preemption: a pool sized at half the slot
    demand (pool_headroom 0.5) forces real evictions — yet every fit-able
    request reaches ``ok`` with ZERO ``kv_pages_exhausted`` sheds (the
    pre-Evictline behavior this scenario exists to retire), each resumed
    stream is token-exact vs the uninterrupted sequential reference
    (greedy and temperature — the rng chain advanced one split per emitted
    token), the extended books identity closes, pages come back exact, and
    every ``serve.evict``/``serve.resume`` event resolves to an in-stream
    span."""
    from perceiver_io_tpu.serving import EngineConfig, EngineFrontEnd

    model, params = _serving_model()
    n = 6 if SMOKE else 8
    for tag, base in _evict_gen_configs():
        recorder, clock, run_dir = _serve_env(tmp, f"serve_evict_storm_{tag}")
        fe = EngineFrontEnd(
            model, params, num_latents=4, base_config=base,
            engine_config=EngineConfig(slots=4, page_size=8, max_ca_tokens=16,
                                       max_sa_tokens=8, pool_headroom=0.5,
                                       eviction=True),
            events=recorder, clock=clock, sleep=clock.sleep,
        )
        specs = _evict_workload(n)
        recs = fe.run_closed(specs, concurrency=n)
        books = _audit_serving(fe, run_dir, f"serve_evict_storm_{tag}")
        # the storm was real: page pressure preempted in-flight work...
        assert books["evictions"] >= 1 and books["resumes"] >= 1, books
        assert books["evictions"] == books["resumes"], books
        # ...and STILL nothing shed and everything served: ok_rate 1.0
        assert books["ok"] == n and books["shed"] == 0, books
        assert all(r.outcome == "ok" for r in recs), [vars(r) for r in recs]
        assert books["parked"] == 0 and books["in_flight"] == 0, books
        stream = _stream(run_dir)
        shed_rows = [e for e in stream if e.get("event") == "request"
                     and e.get("outcome") == "shed"]
        assert not shed_rows, f"fit-able requests shed under eviction: {shed_rows}"
        # token-exactness: every served stream equals the uninterrupted
        # reference — the evicted-and-resumed ones prove the replay seam
        for spec in specs:
            want = _sequential_reference(model, params, spec, base)
            got = fe.served_tokens[spec.index]
            assert got == want, (
                f"serve_evict_storm[{tag}] request {spec.index}: "
                f"engine {got} != sequential {want}"
            )
        # page-exact books after the storm
        assert fe.ca_alloc.pages_used == 0 and fe.sa_alloc.pages_used == 0
        assert fe.ca_alloc.audit() == [] and fe.sa_alloc.audit() == []
        evicts = [e for e in stream if e.get("event") == "serve.evict"]
        resumes = [e for e in stream if e.get("event") == "serve.resume"]
        assert len(evicts) == books["evictions"], (len(evicts), books["evictions"])
        assert len(resumes) == books["resumes"], (len(resumes), books["resumes"])
        assert all(e.get("pages_freed", 0) > 0 for e in evicts), evicts
        n_attr = _assert_span_attributed(run_dir)
        # the parked-depth gauge saw the storm (its peak feeds loadgen)
        assert fe.registry.gauge("serve_parked_depth").peak >= 1
        print(
            f"chaos: serve_evict_storm[{tag}] ok — {books['evictions']} "
            f"evictions / {books['resumes']} resumes under a half-size pool, "
            f"{n}/{n} served ok (0 sheds), all streams token-exact, "
            f"{n_attr} evict/resume events span-attributed"
        )


def scenario_serve_prefix_storm(tmp):
    """Shareline prefix storm: N requests sharing one page-aligned prompt
    prefix hit the engine together. Exactly ONE of them prefills the
    shared run (counter-asserted: N-1 admission hits — the queue never
    drains mid-storm, so the run stays resident from first publish to
    last release), every stream is token-exact vs the uninterrupted
    UNSHARED sequential reference (greedy AND temperature — sharing is an
    allocator optimization, never an approximation), every hit lands a
    span-attributed ``serve.prefix_hit`` row, and at drain the refcounts
    balance: zero pages used, sharing audit clean, the radix index fully
    expired (no node outlives its pages)."""
    from perceiver_io_tpu.obs.loadgen import WorkloadSpec
    from perceiver_io_tpu.serving import EngineConfig, EngineFrontEnd

    model, params = _serving_model()
    n = 6 if SMOKE else 8
    for tag, base in _evict_gen_configs():
        recorder, clock, run_dir = _serve_env(tmp, f"serve_prefix_storm_{tag}")
        fe = EngineFrontEnd(
            model, params, num_latents=4, base_config=base,
            engine_config=EngineConfig(slots=4, page_size=8,
                                       max_ca_tokens=24, max_sa_tokens=16),
            events=recorder, clock=clock, sleep=clock.sleep,
        )
        # prompt 16, latents 4 => context region 12 tokens => exactly one
        # full page (8 tokens) is shareable; the 8-token shared prefix
        # covers it, the 8-token unique tail keeps every stream distinct
        specs = WorkloadSpec(seed=31, prompt_lens=(16,), max_new_tokens=(3, 4),
                             shared_prefix_len=8).draw(n, 64)
        assert len({tuple(s.input_ids[0]) for s in specs}) == n
        recs = fe.run_closed(specs, concurrency=n)
        books = _audit_serving(fe, run_dir, f"serve_prefix_storm_{tag}")
        assert books["ok"] == n and books["shed"] == 0, books
        assert all(r.outcome == "ok" for r in recs), [vars(r) for r in recs]
        # exactly one prefill of the shared run: the first join published,
        # every other admission matched (concurrency == n keeps the run
        # refcounted end to end — no drain gap, no republish)
        assert fe._n_prefix_hits == n - 1, (
            f"serve_prefix_storm[{tag}]: {fe._n_prefix_hits} admission hits "
            f"for {n} same-prefix requests, want {n - 1} (one publisher)"
        )
        assert fe._n_prefix_pages_shared == n - 1, fe._n_prefix_pages_shared
        # token-exactness: every stream equals the unshared sequential
        # reference — shared-prefix prefill changed nothing observable
        for spec in specs:
            want = _sequential_reference(model, params, spec, base)
            got = fe.served_tokens[spec.index]
            assert got == want, (
                f"serve_prefix_storm[{tag}] request {spec.index}: "
                f"shared {got} != unshared reference {want}"
            )
        # refcounts balanced at drain: nothing leaked, nothing double-freed,
        # and the index expired with its pages (stale matches impossible)
        assert fe.sharing_audit() == [], fe.sharing_audit()
        assert fe.ca_alloc.pages_used == 0 and fe.sa_alloc.pages_used == 0
        assert fe.ca_alloc.stats().pages_shared == 0
        assert fe.prefix_index.pages() == (), fe.prefix_index.pages()
        stream = _stream(run_dir)
        hit_rows = [e for e in stream if e.get("event") == "serve.prefix_hit"]
        assert len(hit_rows) == n - 1, (len(hit_rows), n - 1)
        assert all(0 < e["pages_matched"] <= e["pages_total"] for e in hit_rows)
        n_attr = _assert_span_attributed(run_dir)
        assert n_attr >= n - 1, (n_attr, n - 1)
        print(
            f"chaos: serve_prefix_storm[{tag}] ok — {n} same-prefix requests, "
            f"1 prefill of the shared run + {fe._n_prefix_hits} admission "
            f"hits, all streams token-exact vs the unshared reference, "
            f"refcounts balanced at drain ({n_attr} events span-attributed)"
        )


def scenario_serve_crash_recover(tmp):
    """Evictline crash recovery: the engine is torn down mid-decode by an
    injected ``EngineCrash`` (a BaseException no accounting seam catches —
    in-flight slots freeze, no terminal records land, exactly a SIGKILL);
    a SECOND engine recovers from the write-ahead journal, re-admits every
    non-terminal request (mid-decode ones parked with their served prefix,
    unjoined ones re-queued) and serves them token-exactly vs the
    uninterrupted reference (greedy and temperature). The combined books
    balance ACROSS the restart — journal ``submitted == terminal`` with
    every outcome accounted once — and each re-admission lands a
    span-attributed ``serve.recover`` event."""
    from perceiver_io_tpu.serving import (
        EngineConfig,
        EngineCrash,
        EngineFrontEnd,
        FaultInjector,
        RequestJournal,
    )

    model, params = _serving_model()
    n = 4 if SMOKE else 6
    for tag, base in _evict_gen_configs():
        recorder, clock, run_dir = _serve_env(tmp, f"serve_crash_recover_{tag}")
        jpath = os.path.join(run_dir, "journal.jsonl")
        specs = _evict_workload(n)
        engine_cfg = EngineConfig(slots=4, page_size=8, max_ca_tokens=16,
                                  max_sa_tokens=8)
        injector = FaultInjector(clock=clock).crash_at(2, 1)
        fe1 = EngineFrontEnd(
            model, params, num_latents=4, base_config=base,
            engine_config=engine_cfg, events=recorder, clock=clock,
            sleep=clock.sleep, injector=injector, journal=jpath,
        )
        crashed = False
        try:
            fe1.run_closed(specs, concurrency=n)
        except EngineCrash:
            crashed = True
        assert crashed, "injected EngineCrash did not propagate (a seam ate it)"
        books1 = fe1.books()
        assert books1["terminal"] < books1["submitted"], (
            f"crash left nothing owed — the recovery is vacuous: {books1}"
        )
        # the second incarnation: fresh engine, same event stream, same
        # journal file — recover() re-admits everything still owed
        fe2 = EngineFrontEnd(
            model, params, num_latents=4, base_config=base,
            engine_config=engine_cfg, events=recorder, clock=clock,
            sleep=clock.sleep,
        )
        journal = RequestJournal(jpath)
        owed = len(journal.pending())
        assert owed == books1["submitted"] - books1["terminal"], (owed, books1)
        info = fe2.recover(journal)
        assert info["recovered"] == owed, (info, owed)
        assert info["parked"] >= 1, (
            f"no request recovered MID-decode (all prompt-only): {info} — "
            "the token-exact replay claim is vacuous"
        )
        fe2.pump()
        books2 = _audit_serving(fe2, run_dir, f"serve_crash_recover_{tag}")
        assert books2["recovered"] == owed and books2["parked"] == 0, books2
        # combined books balance ACROSS the restart: every submitted index
        # reached exactly one terminal outcome, in one incarnation or the other
        jb = journal.books()
        assert jb["balanced"] and jb["submitted"] == n, jb
        assert jb["pending"] == 0 and jb["outcomes"] == {"ok": n}, jb
        assert journal.audit() == [], journal.audit()
        # token-exact across the restart: served streams (second engine's
        # replay included) equal the uninterrupted reference
        served = dict(fe1.served_tokens)
        served.update(fe2.served_tokens)
        for spec in specs:
            want = _sequential_reference(model, params, spec, base)
            got = served.get(spec.index)
            assert got == want, (
                f"serve_crash_recover[{tag}] request {spec.index}: "
                f"recovered {got} != uninterrupted {want}"
            )
        stream = _stream(run_dir)
        recovers = [e for e in stream if e.get("event") == "serve.recover"]
        assert len(recovers) == owed, (len(recovers), owed)
        n_attr = _assert_span_attributed(run_dir)
        assert fe2.ca_alloc.pages_used == 0 and fe2.sa_alloc.pages_used == 0
        print(
            f"chaos: serve_crash_recover[{tag}] ok — engine crashed with "
            f"{owed} requests owed ({info['parked']} mid-decode), second "
            f"engine recovered all {owed} from the journal, books balanced "
            f"across the restart ({n}/{n} ok), streams token-exact, "
            f"{n_attr} events span-attributed"
        )


# ---------------------------------------------------------------------------
# Fleetline scenarios: N engine replicas behind one FleetRouter submit
# surface (serving/router.py; docs/serving.md#fleet) — replica death,
# brownout and graceful drain, wall-clock-free on the injected clock
# ---------------------------------------------------------------------------


def _audit_fleet(router, run_dir, tag, expect_drained=True):
    """The fleet analog of ``_audit_serving``: the fleet books identity
    closes (``Σ submitted == dispatched + re-admissions``, every orphan
    re-homed exactly once), every live replica's own audit is empty, every
    dead replica's journal is handoff-closed, and the event stream
    validates with NO problems and NO forward-compat warnings."""
    from perceiver_io_tpu.obs.events import validate_events

    problems = router.audit(expect_drained=expect_drained)
    assert not problems, f"{tag}: fleet audit failed: {problems}"
    warnings_out = []
    stream_problems = validate_events(run_dir, warnings_out=warnings_out)
    assert not stream_problems, f"{tag}: event stream invalid: {stream_problems}"
    assert not warnings_out, f"{tag}: unexpected schema warnings: {warnings_out}"
    return router.books()


def scenario_serve_fleet_failover(tmp):
    """Fleetline failover: TWO real engines behind the router; an injected
    replica kill (``EngineCrash`` at a replica-step coordinate — the
    SIGKILL analog, no accounting seam catches it) lands MID-DECODE on
    r0. The router must declare r0 dead, replay its write-ahead journal
    onto r1 through the recover handoff seam, and the survivor must
    finish every journaled request TOKEN-EXACTLY vs the uninterrupted
    sequential reference. The fleet books balance across the handoff —
    every submitted index reaches exactly one terminal outcome fleet-wide,
    the orphan count equals the re-admissions (zero double-served
    tokens), the dead journal closes with handoff markers — and exactly
    one flight dump (trigger ``failover``) names the dead replica."""
    from perceiver_io_tpu.serving import (
        EngineConfig,
        EngineFrontEnd,
        FaultInjector,
    )
    from perceiver_io_tpu.serving.router import FleetRouter

    model, params = _serving_model()
    n = 4 if SMOKE else 6
    for tag, base in _evict_gen_configs():
        recorder, clock, run_dir = _serve_env(tmp, f"serve_fleet_failover_{tag}")
        injector = FaultInjector(clock=clock).kill_replica_at("r0", 2)
        router = FleetRouter(clock=clock, events=recorder, injector=injector)
        engine_cfg = EngineConfig(slots=4, page_size=8, max_ca_tokens=16,
                                  max_sa_tokens=8)
        fes = {}
        for rid in ("r0", "r1"):
            fes[rid] = EngineFrontEnd(
                model, params, num_latents=4, base_config=base,
                engine_config=engine_cfg, events=recorder, clock=clock,
                sleep=clock.sleep,
                journal=os.path.join(run_dir, f"journal-{rid}.jsonl"),
            )
            router.add_replica(rid, fes[rid])
        specs = _evict_workload(n)
        router.run_closed(specs, concurrency=n)
        books = _audit_fleet(router, run_dir, f"serve_fleet_failover_{tag}")
        # the kill was real and the fleet absorbed it: one failover, the
        # dead replica's frozen work re-homed exactly once, all served
        assert books["failovers"] == 1, books
        assert books["orphaned"] >= 1, (
            f"r0 died owing nothing — the failover is vacuous: {books}"
        )
        assert books["orphaned"] == books["readmitted"], books
        assert books["outcomes"]["ok"] == n and books["outcomes"]["shed"] == 0, books
        assert router._replicas["r0"].state == "dead"
        assert router._replicas["r1"].state == "active"
        # mid-decode proof: at least one request crossed the handoff with
        # tokens already served (parked on the survivor, resumed there)
        fo_rows = [e for e in _stream(run_dir) if e.get("event") == "serve.failover"]
        assert len(fo_rows) == 1, fo_rows
        fo = fo_rows[0]
        assert fo["dead_replica"] == "r0" and fo["survivor"] == "r1", fo
        assert fo["n_replayed"] == books["readmitted"], (fo, books)
        assert fo["n_parked"] >= 1, (
            f"no request crossed the handoff MID-decode: {fo} — "
            "the token-exact replay claim is vacuous"
        )
        # the dead journal is CLOSED by handoff markers: nothing pending,
        # every non-terminal entry explicitly handed to the survivor
        jb = fes["r0"].journal.books()
        assert jb["balanced"] and jb["handed_off"] >= 1, jb
        assert len(fes["r0"].journal.pending()) == 0, jb
        assert fes["r0"].journal.audit() == [], fes["r0"].journal.audit()
        # token-exact ACROSS the handoff: merged served streams (survivor
        # wins for handed-off indices) equal the uninterrupted reference
        served = dict(fes["r0"].served_tokens)
        served.update(fes["r1"].served_tokens)
        for spec in specs:
            want = _sequential_reference(model, params, spec, base)
            got = served.get(spec.index)
            assert got == want, (
                f"serve_fleet_failover[{tag}] request {spec.index}: "
                f"fleet {got} != sequential {want}"
            )
        # exactly one flight dump, and it names the dead replica
        dumps = sorted(
            f for f in os.listdir(run_dir) if f.startswith("flight-failover-")
        )
        assert len(dumps) == 1, dumps
        with open(os.path.join(run_dir, dumps[0])) as f:
            payload = json.load(f)
        assert payload["trigger"] == "failover", payload["trigger"]
        assert payload["trigger_event"]["dead_replica"] == "r0", payload
        n_attr = _assert_span_attributed(run_dir)
        # the survivor's pages came back exact after the storm
        assert fes["r1"].ca_alloc.pages_used == 0 and fes["r1"].sa_alloc.pages_used == 0
        assert fes["r1"].ca_alloc.audit() == [] and fes["r1"].sa_alloc.audit() == []
        print(
            f"chaos: serve_fleet_failover[{tag}] ok — r0 killed mid-decode "
            f"owing {books['orphaned']} ({fo['n_parked']} mid-stream), r1 "
            f"replayed all {fo['n_replayed']} from the journal, fleet books "
            f"balanced across the handoff ({n}/{n} ok), streams token-exact, "
            f"1 flight dump, {n_attr} events span-attributed"
        )


def scenario_serve_fleet_brownout(tmp):
    """Fleetline brownout: replica r1's service times are inflated 5x by
    the injector (a slow host, not a dead one). The router's per-step
    EWMA health check must flip r1 ``degraded`` (a ``serve.replica``
    transition row) and least-outstanding dispatch must drain traffic
    onto the healthy r0 — while r1 STAYS in the fleet (no failover, its
    in-flight work finishes). Books balance at full scale."""
    from perceiver_io_tpu.serving import EngineConfig, FaultInjector, FrontEndConfig
    from perceiver_io_tpu.serving.sim import TenantSpec, run_fleet_sim

    window = 0.04 if SMOKE else 0.08
    tenants = [
        TenantSpec("burst", rate_rps=5000.0, n_requests=int(5000 * window), seed=11),
        TenantSpec("steady", rate_rps=3500.0, n_requests=int(3500 * window), seed=22),
    ]
    recorder, _clock, run_dir = _serve_env(tmp, "serve_fleet_brownout")
    injector = FaultInjector().brownout_replica("r1", 5.0)
    report = run_fleet_sim(
        tenants, n_replicas=2, service_model=_sim_service_model(),
        engine_config=EngineConfig(slots=16, page_size=8, max_ca_tokens=32,
                                   max_sa_tokens=16),
        # queue deep enough that ROUTING PREFERENCE decides placement:
        # a saturated healthy replica would shed and re-dispatch overflow
        # onto the slow one, muddying the drain signal
        config=FrontEndConfig(max_queue=1024, admission_projection=False,
                              breaker=None),
        events=recorder, injector=injector,
    )
    s = report.summary
    books = _audit_fleet(report.router, run_dir, "serve_fleet_brownout")
    assert s["books_balanced"] and s["failovers"] == 0, (s, books)
    assert books["outcomes"]["shed"] == 0, (
        f"queue overflow contaminated the routing signal: {books['outcomes']}"
    )
    # the health check SAW the brownout: r1 degraded, r0 clean
    assert s["replicas"]["r1"]["degraded"] is True, s["replicas"]
    assert s["replicas"]["r0"]["degraded"] is False, s["replicas"]
    # ...and dispatch ACTED on it: traffic drained onto the healthy
    # replica (the browned-out one still served its early admissions)
    r0_sub = s["replicas"]["r0"]["submitted"]
    r1_sub = s["replicas"]["r1"]["submitted"]
    assert r0_sub >= 3 * max(r1_sub, 1), (
        f"brownout did not drain traffic: r0 {r0_sub} vs r1 {r1_sub}"
    )
    assert s["replicas"]["r1"]["state"] == "active", s["replicas"]
    assert s["replicas"]["r1"]["submitted"] >= 1, (
        f"r1 never dispatched — the drain claim is vacuous: {s['replicas']}"
    )
    # the flip is a first-class transition row naming the slow replica
    degraded_rows = [
        e for e in _stream(run_dir)
        if e.get("event") == "serve.replica" and e.get("transition") == "degraded"
    ]
    assert degraded_rows and all(
        e["replica_id"] == "r1" for e in degraded_rows
    ), degraded_rows
    print(
        f"chaos: serve_fleet_brownout ok — r1 browned out 5x and flipped "
        f"degraded, dispatch drained onto r0 ({r0_sub} vs {r1_sub} submitted), "
        f"no failover, {s['n_requests']} requests booked balanced"
    )


def scenario_serve_fleet_drain(tmp):
    """Fleetline graceful drain: r0 is drained MID-RUN with work in
    flight. Dispatch to it must stop immediately (every post-drain
    submission lands on r1), its outstanding work must finish (state
    ``drained``, not a shed in sight), and the fleet books must close
    with ZERO sheds attributable to the drain — because the replica's own
    ``drain()`` gate is never raised while it still owes tokens."""
    from perceiver_io_tpu.serving import EngineConfig, EngineFrontEnd
    from perceiver_io_tpu.serving.router import FleetRouter

    model, params = _serving_model()
    n = 4 if SMOKE else 6
    tag, base = _evict_gen_configs()[0]  # greedy: the drain certifies routing
    recorder, clock, run_dir = _serve_env(tmp, "serve_fleet_drain")
    router = FleetRouter(clock=clock, events=recorder)
    engine_cfg = EngineConfig(slots=4, page_size=8, max_ca_tokens=16,
                              max_sa_tokens=8)
    fes = {}
    for rid in ("r0", "r1"):
        fes[rid] = EngineFrontEnd(
            model, params, num_latents=4, base_config=base,
            engine_config=engine_cfg, events=recorder, clock=clock,
            sleep=clock.sleep,
        )
        router.add_replica(rid, fes[rid])
    specs = _evict_workload(n + 2)
    for spec in specs[:n]:
        router.submit(spec)
    router.step()  # both replicas now mid-decode
    assert router._outstanding(fes["r0"]) >= 1, (
        "r0 idle at drain time — the mid-run claim is vacuous"
    )
    r0_submitted_at_drain = fes["r0"].books()["submitted"]
    router.drain_replica("r0")
    late = [router.submit(spec) for spec in specs[n:]]
    router.pump()
    books = _audit_fleet(router, run_dir, "serve_fleet_drain")
    # zero sheds attributable to the drain — or to anything else
    assert books["outcomes"]["shed"] == 0, books
    assert books["outcomes"]["ok"] == n + 2, books
    assert router._replicas["r0"].state == "drained"
    # dispatch stopped AT the drain: r0 took nothing after it...
    assert fes["r0"].books()["submitted"] == r0_submitted_at_drain, (
        fes["r0"].books(), r0_submitted_at_drain
    )
    # ...and every late submission landed on the survivor, served ok
    assert all(router._assigned[r.index] == "r1" for r in late), router._assigned
    assert all(r.outcome == "ok" for r in late), [vars(r) for r in late]
    # the drain lifecycle is first-class in the stream: drain -> drained
    transitions = [
        e["transition"] for e in _stream(run_dir)
        if e.get("event") == "serve.replica" and e.get("replica_id") == "r0"
    ]
    assert transitions == ["join", "drain", "drained"], transitions
    assert fes["r0"].ca_alloc.pages_used == 0 and fes["r1"].ca_alloc.pages_used == 0
    print(
        f"chaos: serve_fleet_drain ok — r0 drained mid-run with "
        f"{r0_submitted_at_drain} in its books, finished them all, "
        f"{len(late)} post-drain submissions routed to r1, "
        f"{n + 2}/{n + 2} ok with zero sheds"
    )


# ---------------------------------------------------------------------------
# Simline scenarios: multi-tenant pressure at simulated scale — the real
# engine control plane under a ManualClock with sampled service times
# (serving/sim.py; docs/serving.md#multi-tenant-telemetry). No jax, no
# model: tens of thousands of simulated requests in host-loop time.
# ---------------------------------------------------------------------------


def _sim_service_model():
    """A fixed synthetic service model for the chaos scenarios: the gate
    artifact (tools/sim.py) fits from a committed LOAD round; chaos wants
    pinned numbers so the pressure geometry never drifts with the
    artifact."""
    from perceiver_io_tpu.serving.sim import ServiceTimeModel

    return ServiceTimeModel(
        prefill_p50_s=0.002, prefill_p99_s=0.004,
        tpot_p50_s=0.0005, tpot_p99_s=0.001, source="chaos_synthetic",
    )


def scenario_sim_tenant_storm(tmp):
    """Simline tenant storm: one tenant floods at 10x each victim's rate,
    far over the engine's join capacity. Admission must degrade
    PROPORTIONALLY — demand-normalized shares stay near-equal (Jain >=
    0.9), neither victim starves (its achieved share holds within 35% of
    the flooder's, queue-wait p99 bounded), and every shed is a
    first-class tenant-stamped row with the books balancing at the full
    offered scale."""
    from perceiver_io_tpu.obs.slo import build_slo_report
    from perceiver_io_tpu.serving import EngineConfig, FrontEndConfig
    from perceiver_io_tpu.serving.sim import TenantSpec, run_sim

    window = 1.0 if SMOKE else 2.0
    tenants = [
        TenantSpec("victim_a", rate_rps=60.0, n_requests=int(60 * window),
                   prompt_lens=(8,), max_new_tokens=(4,), seed=11),
        TenantSpec("victim_b", rate_rps=60.0, n_requests=int(60 * window),
                   prompt_lens=(8, 12), max_new_tokens=(4, 6), seed=22),
        TenantSpec("flood", rate_rps=600.0, n_requests=int(600 * window),
                   prompt_lens=(8,), max_new_tokens=(4,), seed=33),
    ]
    recorder, clock, run_dir = _serve_env(tmp, "sim_tenant_storm")
    report = run_sim(
        tenants, service_model=_sim_service_model(),
        engine_config=EngineConfig(slots=8, page_size=8, max_ca_tokens=24,
                                   max_sa_tokens=8),
        config=FrontEndConfig(max_queue=64, admission_projection=False),
        events=recorder, clock=clock, seed=5,
    )
    s = report.summary
    books = _audit_serving(report.frontend, run_dir, "sim_tenant_storm")
    assert s["books_balanced"] and s["error_rate"] == 0.0, s["books"]
    # the storm was real: offered far over capacity, sheds happened
    assert s["shed_rate"] > 0.2, f"no real pressure: shed_rate {s['shed_rate']}"
    # ...and degraded FAIRLY: demand-normalized shares near-equal
    assert s["fairness_jain"] >= 0.9, (
        f"flood tenant skewed admission: fairness {s['fairness_jain']}, "
        f"tenants {s['tenants']}"
    )
    flood_share = s["tenants"]["flood"]["achieved_rps"] / 600.0
    for victim in ("victim_a", "victim_b"):
        share = s["tenants"][victim]["achieved_rps"] / 60.0
        assert share >= 0.65 * flood_share, (
            f"{victim} starved: share {share:.3f} vs flood {flood_share:.3f}"
        )
        qw = s["tenants"][victim].get("queue_wait_s")
        assert qw is not None and qw["p99"] <= 1.0, (
            f"{victim} queue-wait p99 unbounded under the storm: {qw}"
        )
    # every shed is a first-class tenant-stamped row — never a silent drop
    stream = _stream(run_dir)
    shed_rows = [e for e in stream if e.get("event") == "request"
                 and e.get("outcome") == "shed"]
    assert len(shed_rows) == books["shed"], (len(shed_rows), books["shed"])
    assert all(e.get("shed_reason") and e.get("tenant") for e in shed_rows)
    per_tenant_shed = sum(t["shed"] for t in s["tenants"].values())
    assert per_tenant_shed == books["shed"], (per_tenant_shed, books)
    assert any(e.get("event") == "sim.summary" for e in stream)
    slo = build_slo_report(stream, by_tenant=True)
    assert set(slo["tenants"]) == {"victim_a", "victim_b", "flood"}, slo.keys()
    print(
        f"chaos: sim_tenant_storm ok — flood offered 600 req/s vs 60+60 "
        f"victims ({s['n_requests']} requests, shed_rate {s['shed_rate']}), "
        f"fairness {s['fairness_jain']}, victim shares within 35% of the "
        f"flooder's, {books['shed']} sheds all tenant-stamped, books balanced"
    )


def scenario_sim_noisy_neighbor(tmp):
    """Simline noisy neighbor: a long-prompt/long-budget bulk tenant shares
    the engine with a latency-sensitive tenant under a page pool sized
    BELOW the combined demand (Evictline on) — the bulk pressure forces
    REAL evictions through the real allocator, yet both tenants reach
    ``ok`` on every request, parked work all resumes, and the PER-TENANT
    SLO machinery proves isolation: the latency tenant's planted
    near-zero TTFT bound (``SLOBounds.tenants``) trips flight dumps naming
    ONLY its rows while the bulk tenant's generous bound never fires."""
    from perceiver_io_tpu.obs.flightrec import SLOBounds
    from perceiver_io_tpu.obs.slo import build_slo_report
    from perceiver_io_tpu.serving import EngineConfig, FrontEndConfig
    from perceiver_io_tpu.serving.sim import TenantSpec, run_sim

    from perceiver_io_tpu.serving.sim import ServiceTimeModel

    n = 40 if SMOKE else 80
    tenants = [
        TenantSpec("lat", rate_rps=30.0, n_requests=n,
                   prompt_lens=(8,), max_new_tokens=(3, 4), seed=44),
        TenantSpec("bulk", rate_rps=30.0, n_requests=n,
                   prompt_lens=(16,), max_new_tokens=(12, 16), seed=55),
    ]
    recorder, clock, run_dir = _serve_env(tmp, "sim_noisy_neighbor")
    # the per-tenant bounds under test: lat's is a planted always-breach,
    # bulk's is generous — a shared bound could not tell them apart
    recorder.slo = SLOBounds(
        ttft_s=10.0, tenants={"lat": SLOBounds(ttft_s=1e-9)}
    )
    # a slower service model than _sim_service_model(): a bulk request
    # must OCCUPY its slot long enough (~90ms) that ~3 of them overlap on
    # the half-size pool — that overlap IS the page pressure under test
    slow = ServiceTimeModel(
        prefill_p50_s=0.005, prefill_p99_s=0.010,
        tpot_p50_s=0.004, tpot_p99_s=0.008, source="chaos_synthetic_slow",
    )
    report = run_sim(
        tenants, service_model=slow,
        engine_config=EngineConfig(slots=4, page_size=8, max_ca_tokens=32,
                                   max_sa_tokens=24, pool_headroom=0.5,
                                   eviction=True),
        config=FrontEndConfig(max_queue=64, admission_projection=False),
        events=recorder, clock=clock, seed=6,
    )
    s = report.summary
    fe = report.frontend
    books = _audit_serving(fe, run_dir, "sim_noisy_neighbor")
    # the pressure was real page pressure: evictions through the REAL
    # allocator, everything parked came back, pages exact after drain
    assert books["evictions"] >= 1 and books["evictions"] == books["resumes"], books
    assert books["parked"] == 0 and fe.ca_alloc.pages_used == 0, books
    assert fe.ca_alloc.audit() == [] and fe.sa_alloc.audit() == []
    # ...and STILL both tenants fully served: the neighbor was noisy, not lethal
    for name in ("lat", "bulk"):
        blk = s["tenants"][name]
        assert blk["ok"] == n and blk["shed"] == 0, (name, blk)
    stream = _stream(run_dir)
    evict_rows = [e for e in stream if e.get("event") == "serve.evict"]
    assert evict_rows and all(e.get("tenant") for e in evict_rows), (
        "serve.evict rows must be tenant-stamped"
    )
    # per-tenant SLO series: both sub-reports present, each over its own rows
    slo = build_slo_report(stream, by_tenant=True)
    assert set(slo["tenants"]) == {"lat", "bulk"}
    assert slo["tenants"]["lat"]["n_requests"] == n
    # the isolation proof: lat's planted bound tripped dumps naming ONLY
    # lat rows; bulk's TTFTs (same distribution) never tripped its own
    assert recorder.dumps, "lat's planted TTFT bound produced no flight dump"
    for path in recorder.dumps:
        with open(path) as f:
            dump = json.load(f)
        assert dump["trigger"] == "slo_ttft", dump["trigger"]
        assert dump["trigger_event"].get("tenant") == "lat", (
            f"dump names a non-lat row: {dump['trigger_event']}"
        )
    # the bulk tenant really held pages the victim didn't: per-tenant
    # pages-held peaks reflect the asymmetric footprints
    lat_peak = s["tenants"]["lat"]["pages_held_peak"] or 0
    bulk_peak = s["tenants"]["bulk"]["pages_held_peak"] or 0
    assert bulk_peak > lat_peak, (lat_peak, bulk_peak)
    print(
        f"chaos: sim_noisy_neighbor ok — bulk tenant forced "
        f"{books['evictions']} evictions (pool_headroom 0.5), {n}+{n} "
        f"requests all ok, per-tenant bounds tripped {len(recorder.dumps)} "
        f"dumps all naming 'lat' rows, pages peak bulk {bulk_peak:.0f} > "
        f"lat {lat_peak:.0f}, books balanced"
    )


def scenario_sim_prefix_skew(tmp):
    """Simline prefix skew (Shareline at simulated scale): an "agent"
    tenant whose prompts all open with one shared template prefix shares
    the engine with an "adhoc" tenant of unique prompts, both offered
    over the join capacity. The REAL sharing machinery runs (radix index,
    refcounted grants, expire-on-release) with the service model charging
    a matched join only its unmatched tokens — so the agent tenant's
    joins are structurally cheaper. The certification: that cheapness
    must show up WHERE it belongs (agent TTFT p50 well under adhoc's,
    every hit tenant-stamped + span-attributed) and NOWHERE else —
    admission stays demand-proportional (Jain >= 0.9, the
    ``sim_fairness_jain`` floor's bar), the adhoc tenant is not starved,
    refcounts balance and the index drains with its pages."""
    from perceiver_io_tpu.serving import EngineConfig, FrontEndConfig
    from perceiver_io_tpu.serving.sim import TenantSpec, run_sim

    window = 1.0 if SMOKE else 2.0
    tenants = [
        TenantSpec("agent", rate_rps=400.0, n_requests=int(400 * window),
                   prompt_lens=(16,), max_new_tokens=(4,), seed=71,
                   shared_prefix_len=8),
        TenantSpec("adhoc", rate_rps=400.0, n_requests=int(400 * window),
                   prompt_lens=(16,), max_new_tokens=(4,), seed=72),
    ]
    recorder, clock, run_dir = _serve_env(tmp, "sim_prefix_skew")
    report = run_sim(
        tenants, service_model=_sim_service_model(),
        engine_config=EngineConfig(slots=8, page_size=8, max_ca_tokens=24,
                                   max_sa_tokens=8),
        config=FrontEndConfig(max_queue=64, admission_projection=False),
        events=recorder, clock=clock, seed=9,
    )
    s = report.summary
    fe = report.frontend
    books = _audit_serving(fe, run_dir, "sim_prefix_skew")
    assert s["books_balanced"] and s["error_rate"] == 0.0, books
    assert s["shed_rate"] > 0.1, f"no real pressure: shed_rate {s['shed_rate']}"
    # the sharing was real: most of the agent tenant's admitted requests
    # matched at admission (the template run stays resident under
    # continuous pressure; a full-drain republish is the only miss)
    agent_ok = s["tenants"]["agent"]["ok"]
    assert fe._n_prefix_hits >= 0.5 * agent_ok, (fe._n_prefix_hits, agent_ok)
    assert s.get("prefix_hits") == fe._n_prefix_hits, s.get("prefix_hits")
    # ...attributed to the right tenant: every hit is the agent's, none
    # the adhoc tenant's (its unique prompts can never match)
    hits_c = fe.registry.counter("serve_prefix_hits_total")
    assert hits_c.labels(tenant="agent").value == fe._n_prefix_hits
    assert hits_c.labels(tenant="adhoc").value == 0
    # the service-time skew lands where it belongs: matched joins are
    # charged only their unmatched tokens, so agent TTFT p50 runs well
    # under adhoc's on the same engine
    agent_p50 = s["tenants"]["agent"]["ttft_s"]["p50"]
    adhoc_p50 = s["tenants"]["adhoc"]["ttft_s"]["p50"]
    assert agent_p50 <= 0.75 * adhoc_p50, (agent_p50, adhoc_p50)
    # ...and NOT in admission: cheaper joins must not skew fairness below
    # the committed sim_fairness_jain bar, nor starve the unique tenant
    assert s["fairness_jain"] >= 0.9, (
        f"prefix sharing skewed admission: fairness {s['fairness_jain']}, "
        f"tenants {s['tenants']}"
    )
    agent_share = s["tenants"]["agent"]["achieved_rps"] / 400.0
    adhoc_share = s["tenants"]["adhoc"]["achieved_rps"] / 400.0
    assert adhoc_share >= 0.65 * agent_share, (
        f"adhoc tenant starved: share {adhoc_share:.3f} vs agent {agent_share:.3f}"
    )
    # refcounts balanced at drain, index expired with its pages
    assert fe.sharing_audit() == [], fe.sharing_audit()
    assert fe.ca_alloc.pages_used == 0 and fe.prefix_index.pages() == ()
    stream = _stream(run_dir)
    hit_rows = [e for e in stream if e.get("event") == "serve.prefix_hit"]
    assert len(hit_rows) == fe._n_prefix_hits, (len(hit_rows), fe._n_prefix_hits)
    assert all(e.get("tenant") == "agent" for e in hit_rows)
    n_attr = _assert_span_attributed(run_dir)
    print(
        f"chaos: sim_prefix_skew ok — {s['n_requests']} requests "
        f"(shed_rate {s['shed_rate']}), agent hit {fe._n_prefix_hits}x "
        f"(ttft p50 {agent_p50 * 1e3:.2f}ms vs adhoc {adhoc_p50 * 1e3:.2f}ms), "
        f"fairness {s['fairness_jain']} held, refcounts balanced, "
        f"{n_attr} events span-attributed"
    )


def scenario_sim_fleet(tmp):
    """Fleetline scale certification: the SAME merged workload — 10k
    offered req/s across three tenants — through 1 then 2 replicas on
    the discrete-event fleet loop (per-replica ManualClocks, causal
    next-event drive, fleet duration = the latest replica timeline). Two
    replicas must deliver >= 1.7x the single-replica token throughput
    (the replication claim: near-linear scaling, honestly measured on
    independent timelines), and BOTH runs must hold the committed
    ``sim_fairness_jain`` (>= 0.9) and ``sim_starvation_age_s`` (<= 1.0)
    floors with fleet books balanced — scale that costs fairness or
    starves a tenant is not scale the ledger accepts."""
    from perceiver_io_tpu.serving import EngineConfig, FrontEndConfig
    from perceiver_io_tpu.serving.sim import TenantSpec, run_fleet_sim

    window = 0.06 if SMOKE else 0.12
    def _tenants():
        return [
            TenantSpec("burst", rate_rps=5000.0,
                       n_requests=int(5000 * window), seed=11),
            TenantSpec("steady", rate_rps=3500.0,
                       n_requests=int(3500 * window), seed=22),
            TenantSpec("trickle", rate_rps=1500.0,
                       n_requests=int(1500 * window), seed=33),
        ]

    engine_cfg = EngineConfig(slots=16, page_size=8, max_ca_tokens=32,
                              max_sa_tokens=16)
    fe_cfg = FrontEndConfig(max_queue=256, admission_projection=False,
                            breaker=None)
    summaries = {}
    for n_replicas in (1, 2):
        recorder, _clock, run_dir = _serve_env(tmp, f"sim_fleet_{n_replicas}")
        report = run_fleet_sim(
            _tenants(), n_replicas=n_replicas,
            service_model=_sim_service_model(), engine_config=engine_cfg,
            config=fe_cfg, events=recorder,
        )
        s = report.summary
        _audit_fleet(report.router, run_dir, f"sim_fleet_{n_replicas}")
        assert s["books_balanced"], s["books"]
        assert s["offered_rps"] >= 10000.0, s["offered_rps"]
        # the committed sim floors hold at EVERY fleet size
        assert s["fairness_jain"] >= 0.9, (
            f"sim_fleet[{n_replicas}]: fairness {s['fairness_jain']} "
            f"under the committed floor: {s['tenants']}"
        )
        assert s["max_starvation_age_s"] <= 1.0, (
            f"sim_fleet[{n_replicas}]: starvation "
            f"{s['max_starvation_age_s']}s over the committed ceiling"
        )
        summaries[n_replicas] = s
    ratio = summaries[2]["throughput_tok_s"] / summaries[1]["throughput_tok_s"]
    assert ratio >= 1.7, (
        f"2 replicas scaled only {ratio:.3f}x "
        f"({summaries[1]['throughput_tok_s']} -> "
        f"{summaries[2]['throughput_tok_s']} tok/s) — under the 1.7x bar"
    )
    print(
        f"chaos: sim_fleet ok — {summaries[2]['n_requests']} requests at "
        f"{summaries[2]['offered_rps']:.0f} offered rps, "
        f"{summaries[1]['throughput_tok_s']:.1f} -> "
        f"{summaries[2]['throughput_tok_s']:.1f} tok/s ({ratio:.2f}x >= 1.7x), "
        f"fairness {summaries[2]['fairness_jain']} / starvation "
        f"{summaries[2]['max_starvation_age_s']}s floors held at both sizes"
    )


SCENARIOS = {
    "preempt": scenario_preempt,
    "preempt_mesh": scenario_preempt_mesh,
    "fetch_error": scenario_fetch_error,
    "nan_skip": scenario_nan_skip,
    "nan_rollback": scenario_nan_rollback,
    "torn_save": scenario_torn_save,
    "elastic_shrink": scenario_elastic_shrink,
    "elastic_grow": scenario_elastic_grow,
    "flat_to_mesh": scenario_flat_to_mesh,
    "mesh_to_flat": scenario_mesh_to_flat,
    "serve_overload": scenario_serve_overload,
    "serve_kill_mid_decode": scenario_serve_kill_mid_decode,
    "serve_deadline": scenario_serve_deadline,
    "serve_drain": scenario_serve_drain,
    "serve_breaker": scenario_serve_breaker,
    "serve_engine_kill_mid_decode": scenario_serve_engine_kill_mid_decode,
    "serve_engine_pages": scenario_serve_engine_pages,
    "serve_spec_kill_mid_span": scenario_serve_spec_kill_mid_span,
    "serve_evict_storm": scenario_serve_evict_storm,
    "serve_prefix_storm": scenario_serve_prefix_storm,
    "serve_crash_recover": scenario_serve_crash_recover,
    "serve_fleet_failover": scenario_serve_fleet_failover,
    "serve_fleet_brownout": scenario_serve_fleet_brownout,
    "serve_fleet_drain": scenario_serve_fleet_drain,
    "sim_tenant_storm": scenario_sim_tenant_storm,
    "sim_noisy_neighbor": scenario_sim_noisy_neighbor,
    "sim_prefix_skew": scenario_sim_prefix_skew,
    "sim_fleet": scenario_sim_fleet,
}


def _respawn(scenarios, n_devices=8, phase=None, tmp=None) -> int:
    """Re-run scenarios in a subprocess with ``n_devices`` virtual CPU
    devices (utils/compat.run_with_virtual_devices). ``phase``/``tmp`` pass
    through to the child's argv — the elastic scenarios use this to run
    their kill and resume halves on DIFFERENT topologies over one shared
    scratch dir."""
    from perceiver_io_tpu.utils.compat import run_with_virtual_devices

    argv = [os.path.abspath(__file__), "--scenarios", ",".join(scenarios)]
    if phase:
        argv += ["--phase", phase]
    if tmp:
        argv += ["--tmp", tmp]
    return run_with_virtual_devices(n_devices, argv, cwd=REPO, timeout=540).returncode


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--scenarios",
        default=",".join(SCENARIOS),
        help="comma-separated scenario names and/or fnmatch globs "
        f"(e.g. 'serve_*' or 'elastic_*,preempt') over: {', '.join(SCENARIOS)}",
    )
    parser.add_argument("--tmp", default=None, help="scratch dir (default: mkdtemp)")
    parser.add_argument(
        "--smoke", action="store_true",
        help="CI-fast Evictline scenarios (greedy-only, fewer requests; "
        "same assertions) — the tasks.py perf serve-chaos leg",
    )
    parser.add_argument(
        "--phase",
        default=None,
        choices=("kill", "resume"),
        help="internal: run one half of an elastic scenario (the orchestrator "
        "respawns each half with its own virtual-device count)",
    )
    args = parser.parse_args(argv)
    global SMOKE
    SMOKE = bool(args.smoke)
    # each comma token is a literal name or an fnmatch glob; a token that
    # matches nothing is a usage error (a typo'd selector silently running
    # zero scenarios would read as a green gate)
    import fnmatch

    wanted = []
    for token in (t.strip() for t in args.scenarios.split(",")):
        if not token:
            continue
        matches = [s for s in SCENARIOS if fnmatch.fnmatch(s, token)]
        if not matches:
            parser.error(
                f"scenario selector {token!r} matches nothing "
                f"(known: {', '.join(SCENARIOS)})"
            )
        wanted.extend(m for m in matches if m not in wanted)
    if args.phase and any(s not in ELASTIC_SCENARIOS for s in wanted):
        parser.error("--phase applies only to the elastic scenarios")

    from perceiver_io_tpu.utils.compat import has_virtual_cpu_devices

    run_local = list(wanted)
    rc = 0
    if "preempt_mesh" in run_local and not has_virtual_cpu_devices(8):
        # mesh case needs 8 devices: run it in a virtual-device subprocess,
        # everything else in this process (the elastic scenarios manage
        # their OWN per-phase subprocesses and never need a parent respawn)
        run_local.remove("preempt_mesh")
        rc = _respawn(["preempt_mesh"])
        if rc != 0:
            print("chaos: preempt_mesh FAILED (respawned subprocess)", file=sys.stderr)

    import tempfile

    tmp = args.tmp or tempfile.mkdtemp(prefix="chaos_")
    for name in run_local:
        if name in ELASTIC_SCENARIOS:
            SCENARIOS[name](tmp, phase=args.phase)
        else:
            SCENARIOS[name](tmp)
    if rc == 0 and not args.phase:
        print(f"chaos: all {len(wanted)} scenario(s) passed")
    return rc


if __name__ == "__main__":
    sys.exit(main())
