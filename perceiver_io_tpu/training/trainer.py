"""Trainer — the host-side loop around the jitted SPMD train/eval steps.

This is the TPU-native replacement for the reference's PyTorch-Lightning
``Trainer.fit`` (reference: SURVEY §3.1): arg-free host loop, jitted
``train_step`` (gradients + optimizer + metrics in one XLA program),
periodic validation with metric aggregation, best-k checkpointing monitored
on ``val_loss``, learning-rate monitoring, and sample-logging callbacks at
validation end. Distribution comes from the mesh: batches are sharded along
``data``, parameters/optimizer state along ``fsdp`` — XLA GSPMD inserts all
collectives (the NCCL-free equivalent of DDP/FSDP strategies, SURVEY §2.7).
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, Optional, Sequence

import jax
import numpy as np

from perceiver_io_tpu.obs.events import EventLog, write_run_manifest
from perceiver_io_tpu.obs.mfu import GoodputTracker, device_peak_flops
from perceiver_io_tpu.obs.recompile import RecompileTracker
from perceiver_io_tpu.parallel.mesh import AXIS_SEQ, shard_batch
from perceiver_io_tpu.training.checkpoint import CheckpointManager
from perceiver_io_tpu.training.loop import (
    batch_sharded_kernels,
    make_train_step,
    shard_train_state,
)
from perceiver_io_tpu.training.metrics import MetricsLogger
from perceiver_io_tpu.training.state import TrainState


def _leading_dim(batch) -> int:
    """Batch size of a batch pytree: the leading dim of its first array leaf
    (0 when the batch carries no arrays) — telemetry multiplies the
    per-sample token/FLOP accounting by this."""
    for leaf in jax.tree_util.tree_leaves(batch):
        shape = getattr(leaf, "shape", None)
        if shape:
            return int(shape[0])
    return 0


@dataclass
class TrainerConfig:
    max_steps: int = 1000
    log_interval: int = 50
    val_interval: Optional[int] = None  # None = validate only at the end
    checkpoint_dir: Optional[str] = None
    max_checkpoints: int = 1
    monitor: str = "val_loss"
    mode: str = "min"
    save_weights_only: bool = False
    fsdp_min_weight_size: int = 2**14
    metric_prefix_train: str = "train_"
    metric_prefix_val: str = "val_"
    # host-side batch production overlapped with device compute via a
    # producer thread (data/loader.py PrefetchIterator); 0 disables
    prefetch_batches: int = 2
    # device-side input double-buffering: after each step is dispatched
    # (async under JAX), the NEXT batch is device_put onto its batch
    # sharding while the step runs, so the host->device transfer stops
    # serializing with compute. Log rows carry ``input_wait_ms`` — host
    # time BLOCKED waiting for the consumed batch, near zero when the
    # buffer hits
    input_double_buffer: bool = True
    # --- robustness (training/faults.py; docs/robustness.md) --------------
    # SIGTERM/SIGINT request a final checkpoint at the next step boundary
    # and a clean return instead of killing the loop mid-save (preemption-
    # safe exit; the save itself needs checkpoint_dir). Installed per fit,
    # main thread only.
    preemption_save: bool = True
    # divergence sentinel: True (default thresholds) or a SentinelConfig.
    # In-graph grad/loss finiteness + skip compiles into the train step
    # (where supported); host-side windowed spike detection walks the
    # skip -> rollback-to-last-checkpoint -> halt ladder, every trip an
    # events.jsonl ``fault.*`` event
    sentinel: "bool | object" = False
    # drop batches carrying non-finite float leaves before they reach the
    # step (poison-batch quarantine), emitting ``fault.poison_batch`` with
    # the offending leaf path
    quarantine_poison_batches: bool = False
    # Probeline in-graph numerics telemetry (obs/probes.py,
    # docs/observability.md#probes): True (default ProbeConfig) or a
    # ProbeConfig compiles per-scope activation stats + per-bucket grad
    # norms/update ratios into the train step as aux outputs; the trainer
    # keeps a ring of the last-k snapshots ON DEVICE (ProbeConfig.ring),
    # emits a `probe` event at each log boundary, and on a sentinel
    # skip/rollback/halt dumps a `probe.blast` blast-radius event naming
    # the first scope (topological order) whose stats went non-finite,
    # span-attributed to the offending step. Off (default) the step's
    # compiled graph is bitwise unchanged.
    probes: "bool | object" = False
    # --- telemetry (obs/) -------------------------------------------------
    # structured events.jsonl + run_manifest.json next to metrics.csv
    # (written only when a logger is attached)
    events: bool = True
    # host spans (obs/trace.py): a `fit` span wrapping the run (published
    # ambient, so producer-thread events — fault.poison_batch /
    # fault.fetch_retry — attach to it), a per-step `step` span carrying
    # input_wait_ms/dispatch_ms attrs with its `train/input_wait`,
    # `train/dispatch` and (at a log boundary) `train/metrics_fetch` child
    # spans, and `checkpoint`/`eval` spans; each also enters the profiler's
    # host plane under its name while a capture runs; every
    # fault.*/resume/graphlint/compile event emitted inside one is stamped
    # with its span_id, making incidents attributable to the exact step.
    # Span rows are buffered and flushed at log boundaries and fit exits
    # (per-step file appends would tax a millisecond-scale TPU step).
    spans: bool = True
    # analytic per-sample accounting for MFU/throughput log fields: latent
    # tokens per sample and fwd+bwd model FLOPs per sample
    # (obs.mfu.clm_train_telemetry derives both from a CLM config); None
    # disables the tokens_per_sec / model_flops_per_sec / mfu columns
    tokens_per_sample: Optional[int] = None
    flops_per_sample: Optional[float] = None
    # peak FLOP/s of one device for the MFU denominator; None = look the
    # device kind up in obs.mfu.PEAK_FLOPS
    peak_flops_per_device: Optional[float] = None
    # static-analysis gate (analysis/): at the first step of each fit, the
    # train step's jaxpr is linted with the trace-only always-wrong rules
    # plus the dataflow rules (rng-key-reuse on the ACTUAL step+loss rng
    # plumbing; dead-compute; sharding-flow when the fit-time state/batch
    # carry NamedShardings) and the result lands in events.jsonl as a
    # `graphlint` event. Runs only when events are active (a logger is
    # attached); one extra trace per fit. docs/static-analysis.md has the
    # rule catalog.
    graphlint: bool = True
    graphlint_rules: tuple = (
        "const-capture", "callback-in-jit", "rng-key-reuse", "dead-compute",
        "sharding-flow",
    )
    graphlint_allow: tuple = ()
    # graph-contract telemetry (analysis/fingerprint.py): alongside the
    # graphlint event, the trace-level fingerprint of the ACTUAL train step
    # (op count, hot-scope concat inventory, captured-const bytes, dtype
    # histogram, kernel features) is emitted as a `graphcheck` event — the
    # run-local record tools/graphcheck.py's flagship contracts can be
    # compared against when a training regression is suspected. Trace-only:
    # no extra compile. docs/static-analysis.md has the workflow.
    graphcheck: bool = True


class Trainer:
    """``Trainer(loss_fn, ...).fit(state, train_iter, val_loader)``.

    - ``loss_fn(params, batch, rng) -> (loss, metrics)`` — differentiated.
    - ``eval_loss_fn(params, batch, rng) -> (loss, metrics)`` — run without
      gradient under ``jit`` for validation (pass the deterministic variant).
    - ``mesh`` — optional ``jax.sharding.Mesh``; enables SPMD sharding of the
      state (fsdp axis) and every batch (data axis).
    - ``callbacks`` — callables ``cb(trainer, state, step)`` run after each
      validation (sample generation, mask-fill logging, …).
    """

    def __init__(
        self,
        loss_fn: Callable,
        eval_loss_fn: Optional[Callable] = None,
        mesh=None,
        config: Optional[TrainerConfig] = None,
        logger: Optional[MetricsLogger] = None,
        lr_schedule: Optional[Callable] = None,
        callbacks: Sequence[Callable] = (),
    ):
        self.config = config or TrainerConfig()
        self.mesh = mesh
        # a non-trivial seq axis also shards the token dim of every batch
        # (sequence/context parallelism); decided once — the mesh is fixed
        self._batch_seq_dim = (
            1 if mesh is not None and mesh.shape.get(AXIS_SEQ, 1) > 1 else None
        )
        self.logger = logger
        self.lr_schedule = lr_schedule
        self.callbacks = list(callbacks)
        # recompile tracking wraps the steps ONCE here so the jit-cache
        # watermark persists across sequential fit() calls — a recompile in
        # fit #2 (resume with a new batch shape) is exactly what must surface
        self.recompiles = RecompileTracker()
        self._events: Optional[EventLog] = None
        self._manifest_written = False
        # divergence sentinel (training/faults.py): resolve the config once;
        # the in-graph skip half is compiled into the step below, the
        # host-side ladder walker is created fresh per fit()
        self._sentinel_cfg = None
        if self.config.sentinel:
            from perceiver_io_tpu.training.faults import SentinelConfig

            self._sentinel_cfg = (
                self.config.sentinel
                if isinstance(self.config.sentinel, SentinelConfig)
                else SentinelConfig()
            )
        in_graph_sentinel = self._sentinel_cfg is not None and self._sentinel_cfg.in_graph_skip
        # Probeline (obs/probes.py): resolve the probe config once; the
        # in-graph stats compile into the step below, the ring/blast host
        # side lives in fit()
        self._probe_cfg = None
        if self.config.probes:
            from perceiver_io_tpu.obs.probes import ProbeConfig

            self._probe_cfg = (
                self.config.probes
                if isinstance(self.config.probes, ProbeConfig)
                else ProbeConfig()
            )
        # the step hands the state back in the layout fit() places it in
        layout = dict(mesh=mesh, min_weight_size=self.config.fsdp_min_weight_size)
        self._train_step = self.recompiles.wrap(
            make_train_step(
                loss_fn,
                sentinel=in_graph_sentinel,
                probes=self._probe_cfg,
                **layout,
            ),
            "train_step",
        )
        # the raw (unjitted) step for the graphlint trace: linting through
        # the recompile-tracked jit wrapper would pollute its compile
        # bookkeeping, and the raw fn traces identically
        self._lint_step = make_train_step(
            loss_fn, jit=False, sentinel=in_graph_sentinel, probes=self._probe_cfg, **layout
        )
        # the fit-scoped preemption guard, exposed so tests and the chaos
        # harness can trip it deterministically (tools/chaos.py)
        self._preempt_guard = None
        eval_fn = eval_loss_fn
        if eval_fn is None:
            # dropout must be off during validation (Lightning model.eval()
            # parity); losses built by this package accept a deterministic
            # kwarg on the inner fn — use it when available
            import inspect

            if "deterministic" in inspect.signature(loss_fn).parameters:
                eval_fn = lambda params, batch, rng: loss_fn(params, batch, rng, deterministic=True)  # noqa: E731
            else:
                eval_fn = loss_fn

        def eval_step(params, batch, rng):
            with batch_sharded_kernels(mesh):
                _, metrics = eval_fn(params, batch, rng)
            return metrics

        self._eval_step = self.recompiles.wrap(jax.jit(eval_step), "eval_step")
        # prefetch recovery across sequential fit() calls on the SAME
        # iterator object (resume, curriculum phases): batches the producer
        # pulled but fit() never consumed are re-injected next time instead
        # of being silently dropped (ADVICE r3; data/loader.py close()).
        # A deque drained lazily: whatever a later fit does not consume
        # (no-op fit, prefetch disabled, early max_steps) simply stays put.
        from collections import deque

        self._residual_batches: "deque" = deque()
        self._residual_src = None  # weakref to the iterator they came from
        self._pending_prefetch = None  # a close()d prefetch whose producer was still alive
        self.checkpoints: Optional[CheckpointManager] = None
        if self.config.checkpoint_dir is not None:
            self.checkpoints = CheckpointManager(
                self.config.checkpoint_dir,
                max_to_keep=self.config.max_checkpoints,
                monitor=self.config.monitor,
                mode=self.config.mode,
                save_weights_only=self.config.save_weights_only,
                # overlap checkpoint IO with continued training; fit() waits
                # before returning so callers always see committed state
                enable_async=True,
                # transient-FS retry on save/restore I/O (fault.ckpt_retry
                # events once fit wires the sink below)
                retry=True,
            )

    # -- helpers ----------------------------------------------------------

    def _prepare_batch(self, batch):
        if self.mesh is not None:
            return shard_batch(batch, self.mesh, seq_dim=self._batch_seq_dim)
        return batch

    def _log(self, step: int, metrics: Dict[str, float]) -> None:
        if self.logger is not None:
            self.logger.log(step, metrics)

    def _ensure_events(self) -> Optional[EventLog]:
        """The run's event sink (events.jsonl beside metrics.csv), created on
        first use; None when telemetry is off or no logger is attached."""
        if not self.config.events or self.logger is None:
            return None
        if self._events is None:
            self._events = EventLog(
                self.logger.log_dir, main_process=getattr(self.logger, "_active", None)
            )
        return self._events

    def _graphlint(self, events: EventLog, state: TrainState, batch, closed=None) -> None:
        """Lint the train step's jaxpr (trace-only rules) and emit the
        result as a ``graphlint`` event. A lint finding is an event, never
        a failure; an exception inside the analysis propagates — a gate
        that cannot run must not read as a clean run."""
        from perceiver_io_tpu import analysis
        from perceiver_io_tpu.analysis.flagship import DEAD_COMPUTE_MIN_FLOPS

        report = analysis.check(
            self._lint_step,
            (state, batch),
            rules=self.config.graphlint_rules,
            allow=self.config.graphlint_allow,
            # arm the dataflow rules against the ACTUAL trained step:
            # sharding_flow=True reads whatever NamedShardings the
            # fit-time state/batch carry (unsharded runs propagate
            # nothing and stay silent)
            policy=analysis.LintPolicy(
                check_rng=True,
                dead_compute_min_flops=DEAD_COMPUTE_MIN_FLOPS,
                sharding_flow=True,
            ),
            name="train_step",
            closed_jaxpr=closed,
        )
        events.emit(
            "graphlint",
            step=int(state.step),
            ok=report.ok(),
            clean=report.clean,
            rules=list(report.rules_run),
            counts={s: report.count(s) for s in ("error", "warn", "info")},
            violations=[v.to_dict() for v in report.violations[:20]],
            n_allowed=len(report.allowed),
        )

    def _graphcheck(self, events: EventLog, state: TrainState, batch, closed=None) -> None:
        """Emit the trace-level fingerprint of the train step as a
        ``graphcheck`` event (trace-only — no compile; exceptions
        propagate, as in :meth:`_graphlint`)."""
        from perceiver_io_tpu.analysis.fingerprint import fingerprint

        fp = fingerprint(
            self._lint_step, (state, batch), name="train_step", compiled=False,
            closed_jaxpr=closed,
        )
        events.emit(
            "graphcheck",
            step=int(state.step),
            name=fp.name,
            n_ops=fp.n_ops,
            features=list(fp.features),
            hot_concats=[dict(c) for c in fp.hot_concats[:20]],
            captured_const_bytes=fp.captured_const_bytes,
            dtype_histogram=fp.dtype_histogram,
        )

    # -- API --------------------------------------------------------------

    def validate(self, state: TrainState, val_loader: Iterable) -> Dict[str, float]:
        """Mean of per-batch metrics over the loader (the all-reduce the
        reference does via ``sync_dist=True`` happens inside the jitted step
        through GSPMD; host-side we only average over batches)."""
        sums: Dict[str, float] = {}
        count = 0
        rng = jax.random.PRNGKey(0)
        for batch in val_loader:
            batch = self._prepare_batch(batch)
            rng, step_rng = jax.random.split(rng)
            metrics = self._eval_step(state.params, batch, step_rng)
            for k, v in metrics.items():
                sums[k] = sums.get(k, 0.0) + float(v)
            count += 1
        if count == 0:
            return {}
        return {self.config.metric_prefix_val + k: v / count for k, v in sums.items()}

    def fit(
        self,
        state: TrainState,
        train_iter,
        val_loader: Optional[Iterable] = None,
        model_config=None,
        resume: "bool | str" = False,
    ) -> TrainState:
        """``resume=False`` starts fresh; ``resume=True`` restores the latest
        checkpoint into ``state`` (legacy: no data-stream alignment);
        ``resume="auto"`` is the preemption-safe mode — restore the latest
        VALID checkpoint when one exists (fresh start otherwise), fast-forward
        the data iterator by the restored step count so the stream realigns,
        truncate ``metrics.csv`` rows past the restore point, and emit a
        ``resume`` event. With a fresh/restartable iterator a preempted and
        auto-resumed run reproduces the uninterrupted run's loss trajectory
        (state RNG rides in the checkpoint; certified by ``tools/chaos.py``).
        Auto-resume drops residual batches parked by a previous fit on this
        Trainer: they encode the OLD stream position, which the fast-forward
        replaces."""
        cfg = self.config
        if self.mesh is not None:
            # idempotent (re-)placement: a state restored/placed on another
            # mesh in a previous life is re-resolved onto THIS mesh — the
            # elastic-resume entry point (docs/robustness.md#elastic-resume)
            state = shard_train_state(state, self.mesh, min_weight_size=cfg.fsdp_min_weight_size)
        auto_resume = resume == "auto"
        fast_forward_n = 0
        resume_info = None
        if resume and self.checkpoints is None:
            raise ValueError("resume requires checkpoint_dir")

        # --- telemetry: event sink, run manifest, goodput, MFU inputs -----
        # (set up BEFORE the resume restore, so the restore path's
        # resume.reshard / fault.ckpt_retry events land in the stream,
        # inside the resume span)
        events = self._ensure_events()
        goodput = GoodputTracker()
        self.recompiles.events = events
        self.recompiles.goodput = goodput
        if self.checkpoints is not None:
            self.checkpoints.event_sink = events
        if events is not None and not self._manifest_written:
            write_run_manifest(
                self.logger.log_dir,
                mesh=self.mesh,
                model_config=model_config,
                trainer_config=cfg,
                main_process=getattr(self.logger, "_active", None),
            )
            self._manifest_written = True
        n_dev = self.mesh.size if self.mesh is not None else 1
        peak = cfg.peak_flops_per_device
        if peak is None:
            peak = device_peak_flops()
        # host spans (obs/trace.py): the fit span opens BEFORE fit_start so
        # fit_start/resume — and, via the ambient fallback, producer-thread
        # fault events — are stamped with its span_id
        tracer = None
        fit_span = None
        span_stack = contextlib.ExitStack()
        if events is not None and cfg.spans:
            from perceiver_io_tpu.obs.trace import Tracer

            tracer = Tracer(events)
            fit_span = span_stack.enter_context(tracer.span("fit", ambient=True))
        from perceiver_io_tpu.obs.trace import maybe_span

        if resume:
            # the resume span wraps preflight + restore, so every restore-
            # path event (resume.reshard, fault.ckpt_retry) is attributable
            try:
                with maybe_span(tracer, "resume"):
                    if auto_resume:
                        self._residual_batches.clear()
                        if self.checkpoints.latest_step() is not None:
                            pre_step = int(state.step)
                            # preflight: one actionable error on config/shape
                            # incompatibility instead of a deep orbax ValueError
                            self.checkpoints.preflight(state, model_config=model_config)
                            with goodput.measure("checkpoint"):
                                state = self.checkpoints.restore(state)
                            fast_forward_n = max(0, int(state.step) - pre_step)
                            resume_info = {
                                "from_step": pre_step,
                                "to_step": int(state.step),
                                "fast_forward_batches": fast_forward_n,
                            }
                            if self.logger is not None:
                                self.logger.truncate_after(int(state.step))
                    elif self.checkpoints.latest_step() is not None:
                        state = self.checkpoints.restore(state)
            except BaseException:
                # restore/preflight died BEFORE fit_start: close + flush the
                # fit span so the stream stays well-formed (no fit_end — no
                # fit_start was emitted), then propagate the real error
                span_stack.close()
                if tracer is not None:
                    tracer.flush()
                raise
        if fit_span is not None:
            fit_span.set("start_step", int(state.step))

        if events is not None:
            events.emit("fit_start", start_step=int(state.step), max_steps=cfg.max_steps)
            if resume_info is not None:
                events.emit("resume", **resume_info)

        # fit-scoped fault handling (training/faults.py): a fresh sentinel
        # ladder per fit, and a preemption guard installed for the duration
        # of the loop (uninstalled on every exit path below)
        sentinel = None
        if self._sentinel_cfg is not None:
            from perceiver_io_tpu.training.faults import DivergenceSentinel

            sentinel = DivergenceSentinel(self._sentinel_cfg)
        # Probeline ring (obs/probes.py): the last-k probe snapshots parked
        # as DEVICE arrays — no host sync on the step path; fetched only at
        # log boundaries (`probe` event) and on sentinel trips (blast)
        probe_ring = None
        if self._probe_cfg is not None:
            from collections import deque

            probe_ring = deque(maxlen=max(int(self._probe_cfg.ring), 1))
        guard = None
        if cfg.preemption_save:
            from perceiver_io_tpu.training.faults import PreemptionGuard

            guard = PreemptionGuard()
            guard.install()
            self._preempt_guard = guard
        preempted = False

        # an aborted run must still get its goodput/recompile audit, and
        # a fit_start must always be paired with a fit_end — the try
        # covers everything from iterator/prefetch setup (which can
        # raise, e.g. a still-blocked previous producer) through the
        # final checkpoint save. Except-and-reraise, NOT exc_info in a
        # finally: that misfires when fit() runs inside a caller's
        # except handler.
        try:
            train_iter = iter(train_iter)
            src = train_iter
            if fast_forward_n:
                # consume the batches the pre-preemption run already trained
                # on; the restored step counter and in-checkpoint RNG then
                # see exactly the stream an uninterrupted run would
                import itertools

                for _ in itertools.islice(train_iter, fast_forward_n):
                    pass
            if self._pending_prefetch is not None:
                # a previous fit's producer outlived its bounded close() join
                # (source iterator blocked); collect whatever it has since
                # produced before touching the source again
                self._pending_prefetch.close()
                if self._pending_prefetch.alive():
                    raise RuntimeError(
                        "the previous fit's prefetch producer is still blocked "
                        "inside the training iterator; a second fit on it would "
                        "race the producer thread"
                    )
                self._residual_batches.extend(self._pending_prefetch.residual)
                self._pending_prefetch = None
            same_src = self._residual_src is not None and self._residual_src() is src
            if not same_src:
                # stale residuals belong to a different (gone) iterator — drop
                # them rather than mix them into this fit's recovery deque
                self._residual_batches.clear()
            residual_dq = self._residual_batches if same_src else None
            if residual_dq:
                import itertools

                def _drain(dq=residual_dq):
                    while dq:
                        yield dq.popleft()

                # lazy drain: unconsumed items REMAIN in the deque for the next fit
                train_iter = itertools.chain(_drain(), train_iter)
            if cfg.quarantine_poison_batches:
                # upstream of the prefetch wrapper: the per-leaf finiteness
                # scan then runs in the producer thread, off the step path
                from perceiver_io_tpu.training.faults import QuarantineIterator

                def _on_poison(path, n, _ev=events):
                    if _ev is not None:
                        _ev.emit("fault.poison_batch", leaf=path, n_quarantined=n)

                train_iter = QuarantineIterator(train_iter, on_quarantine=_on_poison)
            prefetch = None
            start_step = int(state.step)
            if cfg.prefetch_batches > 0 and start_step < cfg.max_steps:
                # only when steps will actually run — a no-op fit must not pull
                # (and discard) items from a shared stateful iterator
                from perceiver_io_tpu.data.loader import PrefetchIterator

                train_iter = prefetch = PrefetchIterator(train_iter, depth=cfg.prefetch_batches)
            window: list = []
            window_samples = 0
            pending_batch = None
            pending_exc = None
            input_wait_s = 0.0
            # the open per-iteration span: closed at the NEXT iteration's
            # top (or in the finally below) rather than a with-block, so the
            # log/eval/checkpoint tail of an iteration stays inside its step
            # span and fault events emitted anywhere in the iteration carry
            # its span_id
            step_span = None
            # perf_counter, matching GoodputTracker's clock: the goodput
            # subtraction must not mix monotonic and wall (NTP-steppable) time
            t0 = time.perf_counter()
            window_overhead0 = goodput.overhead()
            lint_pending = events is not None and (cfg.graphlint or cfg.graphcheck)
            try:
                i = start_step
                while i < cfg.max_steps:
                    if guard is not None and guard.requested:
                        # preemption requested (SIGTERM/SIGINT): this step
                        # boundary is the last consistent point to stop —
                        # the final save happens below, after the prefetch
                        # cleanup parks unconsumed batches
                        preempted = True
                        break
                    if tracer is not None:
                        if step_span is not None:
                            tracer.end(step_span)
                        step_span = tracer.start("step")
                    # input_wait: host time BLOCKED obtaining the batch this
                    # step consumes — the double buffer below drives it to ~0
                    t_in = time.perf_counter()
                    with maybe_span(tracer, "train/input_wait"):
                        if pending_exc is not None:
                            # a deferred prefetch failure surfaces HERE, where the
                            # pre-double-buffer loop would have hit it — after the
                            # previous step's log/eval/checkpoint ran
                            exc, pending_exc = pending_exc, None
                            raise exc
                        if pending_batch is not None:
                            batch, pending_batch = pending_batch, None
                        else:
                            batch = self._prepare_batch(next(train_iter))
                    step_wait_s = time.perf_counter() - t_in
                    input_wait_s += step_wait_s
                    if step_span is not None:
                        step_span.set("input_wait_ms", round(step_wait_s * 1e3, 3))
                    if lint_pending:
                        lint_pending = False
                        with goodput.measure("graphlint"):
                            closed = None
                            if cfg.graphlint and cfg.graphcheck:
                                # one trace for both emitters: tracing a
                                # large step takes seconds
                                from perceiver_io_tpu.analysis import graph

                                closed = graph.trace(self._lint_step, state, batch)
                            if cfg.graphlint:
                                self._graphlint(events, state, batch, closed)
                            if cfg.graphcheck:
                                self._graphcheck(events, state, batch, closed)
                    t_dispatch = time.perf_counter()
                    with maybe_span(tracer, "train/dispatch"):
                        state, metrics = self._train_step(state, batch)
                    if (
                        probe_ring is not None
                        and isinstance(metrics, dict)
                        and "probes" in metrics
                    ):
                        # park the snapshot (device arrays + the post-step
                        # step counter, unfetched) and keep metrics clean
                        # for the float()-ing log window
                        metrics = dict(metrics)
                        probe_ring.append((state.step, metrics.pop("probes")))
                    if step_span is not None:
                        # host wall of ISSUING the step (trace+compile on a
                        # miss, dispatch otherwise) — device compute is async
                        # and comes from the xplane rollup side of the join
                        step_span.set(
                            "dispatch_ms", round((time.perf_counter() - t_dispatch) * 1e3, 3)
                        )
                    if cfg.input_double_buffer and i + 1 < cfg.max_steps:
                        # the step above is dispatched asynchronously: issue
                        # the NEXT batch's device_put now so the host->device
                        # transfer rides under the running step. ANY iterator
                        # failure (exhaustion or a pipeline error) is deferred
                        # to the next iteration's blocking fetch so the
                        # just-completed step still gets its log/eval/
                        # checkpoint, exactly like the pre-buffer loop
                        try:
                            pending_batch = self._prepare_batch(next(train_iter))
                        except StopIteration:
                            pending_batch = None
                        except Exception as e:  # noqa: BLE001 — re-raised next iteration
                            pending_batch, pending_exc = None, e
                    window.append(metrics)
                    window_samples += _leading_dim(batch)
                    step = i = int(state.step)
                    if step_span is not None:
                        step_span.set("step", step)

                    if sentinel is not None:
                        decision = self._sentinel_decide(sentinel, events, metrics, step)
                        skipped_now = (
                            isinstance(metrics, dict)
                            and float(metrics.get("sentinel_skipped", 0.0)) > 0.5
                        )
                        if skipped_now and window:
                            # the held step's non-finite metrics must not
                            # poison the log-window mean (the skip itself is
                            # on record as a fault.skip event)
                            window.pop()
                            window_samples -= _leading_dim(batch)
                        # blast-radius attribution (obs/probes.py): a trip
                        # with probe snapshots on record names the FIRST
                        # scope (topological order) of the EARLIEST ring
                        # entry whose stats went non-finite — emitted inside
                        # the still-open step span, so the `probe.blast`
                        # event is attributable to the offending step
                        trigger = None
                        if decision is not None and decision.action in ("rollback", "halt"):
                            trigger = decision.action
                        elif skipped_now:
                            trigger = "skip"
                        if trigger is not None and probe_ring is not None and events is not None:
                            from perceiver_io_tpu.obs import probes as _probes

                            report = _probes.blast_report(probe_ring)
                            if report is not None:
                                events.emit("probe.blast", trigger=trigger, **report)
                                # an attributed incident is done: drop its
                                # snapshots so a LATER independent trip
                                # within ring-length steps attributes to its
                                # own origin, not this stale one
                                probe_ring.clear()
                        if decision is not None and decision.action == "rollback":
                            from_step = step
                            # roll back to the last valid checkpoint; the
                            # restored step counter rewinds any step-indexed
                            # LR schedule with it (LR-rewind), and the
                            # replayed interval is booked as overhead, not
                            # goodput
                            prev_opt = state.opt_state
                            with goodput.measure("rollback"):
                                state = self.checkpoints.restore(state)
                            opt_reinit = state.opt_state is prev_opt
                            if opt_reinit:
                                # weights-only checkpoint: restore left the
                                # (possibly poisoned) optimizer moments in
                                # place — reinitialize them fresh rather than
                                # replay the interval with diverged state
                                state = state.replace(
                                    opt_state=state.tx.init(state.params)
                                )
                            step = i = int(state.step)
                            sentinel.reset_window()
                            if events is not None:
                                events.emit(
                                    "fault.rollback",
                                    from_step=from_step,
                                    to_step=step,
                                    reason=decision.reason,
                                    rollbacks=sentinel.rollbacks,
                                    opt_reinit=opt_reinit,
                                    **decision.detail,
                                )
                            # the metrics window spans the diverged steps —
                            # reset it so the next log row is post-rollback
                            window, window_samples, t0 = [], 0, time.perf_counter()
                            input_wait_s = 0.0
                            window_overhead0 = goodput.overhead()
                            if probe_ring is not None:
                                # remaining snapshots describe the rolled-back
                                # trajectory (a spike-triggered rollback emits
                                # no blast, so the emit-time clear above may
                                # not have run) — the replay starts fresh
                                probe_ring.clear()
                            continue
                        if decision is not None and decision.action == "halt":
                            if events is not None:
                                events.emit(
                                    "fault.halt",
                                    step=step,
                                    reason=decision.reason,
                                    **decision.detail,
                                )
                            from perceiver_io_tpu.training.faults import DivergenceHalt

                            raise DivergenceHalt(
                                f"divergence sentinel halted the run at step {step} "
                                f"({decision.reason})"
                            )

                    # (an entirely-skipped window has no rows to average —
                    # the fault.skip events already tell that story)
                    if (step % cfg.log_interval == 0 or step == cfg.max_steps) and window:
                        # the host read of the window's device scalars
                        with maybe_span(tracer, "train/metrics_fetch", steps=len(window)):
                            avg = {
                                cfg.metric_prefix_train + k: float(np.mean([float(m[k]) for m in window]))
                                for k in window[-1]
                            }
                        if self.lr_schedule is not None:
                            avg["lr"] = float(self.lr_schedule(step))
                        # throughput/MFU over GROSS window wall time: a window
                        # that absorbed a compile or eval reports the dip, and
                        # the goodput column says how much of it was overhead
                        elapsed = max(time.perf_counter() - t0, 1e-9)
                        avg["steps_per_sec"] = len(window) / elapsed
                        if cfg.tokens_per_sample:
                            avg["tokens_per_sec"] = cfg.tokens_per_sample * window_samples / elapsed
                        if cfg.flops_per_sample:
                            flops_per_sec = cfg.flops_per_sample * window_samples / elapsed
                            avg["model_flops_per_sec"] = flops_per_sec
                            if peak:
                                avg["mfu"] = flops_per_sec / (peak * n_dev)
                        # per-window input wait (ms per step): blocked host
                        # time fetching batches — the double-buffer win shows
                        # up here as ~0 rows in events.jsonl
                        avg["input_wait_ms"] = input_wait_s * 1e3 / len(window)
                        # per-WINDOW goodput (overhead delta since the last log
                        # row), so the column attributes THIS window's dip; the
                        # run-cumulative breakdown comes once, at fit_end
                        window_overhead = goodput.overhead() - window_overhead0
                        avg["goodput"] = min(
                            max(elapsed - window_overhead, 0.0) / elapsed, 1.0
                        )
                        self._log(step, avg)
                        if events is not None:
                            if cfg.flops_per_sample and not peak:
                                # device off obs.mfu.PEAK_FLOPS: an explicit
                                # null, not a missing key (events only — the
                                # csv column holds floats)
                                avg = {**avg, "mfu": None}
                            events.emit("log", step=step, **avg)
                            if probe_ring:
                                # the log boundary is the agreed host-sync
                                # point: fetch the LATEST snapshot only and
                                # emit it as a `probe` row (per-scope trend
                                # input for tools/obs_report.py)
                                from perceiver_io_tpu.obs import probes as _probes

                                s_dev, snap = probe_ring[-1]
                                events.emit(
                                    "probe",
                                    step=int(s_dev),
                                    scopes=_probes.snapshot_to_host(snap),
                                )
                        if tracer is not None:
                            tracer.flush()  # span rows land once per window
                        window, window_samples, t0 = [], 0, time.perf_counter()
                        input_wait_s = 0.0
                        window_overhead0 = goodput.overhead()

                    at_val = cfg.val_interval is not None and step % cfg.val_interval == 0
                    if (at_val or step == cfg.max_steps) and val_loader is not None:
                        # eval bucket = wall time MINUS any eval_step compile the
                        # RecompileTracker already booked into the compile bucket,
                        # so the two buckets never double-count the same seconds
                        eval_t0 = time.perf_counter()
                        compile_s0 = self.recompiles.total_compile_s
                        with maybe_span(tracer, "eval"):
                            val_metrics = self.validate(state, val_loader)
                        goodput.add(
                            "eval",
                            (time.perf_counter() - eval_t0)
                            - (self.recompiles.total_compile_s - compile_s0),
                        )
                        self._log(step, val_metrics)
                        if events is not None:
                            events.emit("eval", step=step, **val_metrics)
                        if self.checkpoints is not None:
                            with goodput.measure("checkpoint"), maybe_span(tracer, "checkpoint"):
                                self.checkpoints.save(state, metrics=val_metrics, config=model_config)
                        for cb in self.callbacks:
                            cb(self, state, step)
            finally:
                if step_span is not None:
                    tracer.end(step_span)
                    step_span = None
                parked = False
                if prefetch is not None:
                    prefetch.close()
                    # the prefetch pulled items ahead of the step loop — they
                    # logically precede anything still parked in the deque
                    self._residual_batches.extendleft(reversed(prefetch.residual))
                    if prefetch.alive():
                        # producer stuck in the source iterator; hold the wrapper
                        # so the next fit can harvest (and refuses to race it)
                        self._pending_prefetch = prefetch
                    parked = True
                if pending_batch is not None:
                    # a double-buffered batch pulled but never consumed (the
                    # loop raised): it came out of train_iter BEFORE anything
                    # recovered from the prefetch queue, so it goes in front
                    self._residual_batches.appendleft(pending_batch)
                    pending_batch = None
                    parked = True
                if parked:
                    try:
                        import weakref

                        self._residual_src = weakref.ref(src)
                    except TypeError:  # not weakref-able (e.g. plain list_iterator)
                        self._residual_src = None
                # commit any in-flight async save even when the loop raises
                # (callback/iterator error, KeyboardInterrupt) — otherwise a
                # hard exit abandons the last checkpoint
                if self.checkpoints is not None:
                    with goodput.measure("checkpoint"):
                        self.checkpoints.wait_until_finished()
            if preempted:
                if events is not None:
                    events.emit(
                        "fault.preempt",
                        step=int(state.step),
                        signals=0 if guard is None else guard.signal_count,
                    )
                if cfg.checkpoint_dir is not None:
                    # final preemption save: a monitor-free KEEP-ALL manager
                    # over the same directory — full state (exact resume
                    # needs the optimizer), no fresh val metric required,
                    # and retention can never evict the best-val step
                    with goodput.measure("checkpoint"), maybe_span(tracer, "checkpoint"):
                        pm = CheckpointManager(
                            cfg.checkpoint_dir, max_to_keep=None, monitor=None,
                            retry=True, event_sink=events,
                        )
                        # the marker metric keeps orbax's metrics item present
                        # (restore paths read it); _monitor_value never lets a
                        # non-monitor key win best_step
                        pm.save(state, metrics={"preempted": 1.0}, config=model_config, force=True)
                        pm.close()
            elif val_loader is None and self.checkpoints is not None:
                # no validation: leave a final latest-state checkpoint via a
                # monitor-free manager (Lightning save-last parity) so NaN metrics
                # never pollute best-k retention
                final_mngr = CheckpointManager(
                    self.config.checkpoint_dir,
                    max_to_keep=self.config.max_checkpoints,
                    monitor=None,
                    save_weights_only=self.config.save_weights_only,
                    retry=True,
                    event_sink=events,
                )
                with goodput.measure("checkpoint"), maybe_span(tracer, "checkpoint"):
                    final_mngr.save(state, config=model_config)
                    final_mngr.close()
        except BaseException:
            self._release_guard(guard)
            # close + flush the fit span BEFORE fit_end: an aborted run's
            # stream still resolves every span_id its fault events carry
            span_stack.close()
            if tracer is not None:
                tracer.flush()
            if events is not None:
                events.emit(
                    "fit_end",
                    step=int(state.step),
                    aborted=True,
                    recompiles=self.recompiles.counts(),
                    **goodput.summary(),
                )
            raise
        self._release_guard(guard)
        span_stack.close()
        if tracer is not None:
            tracer.flush()
        if events is not None:
            events.emit(
                "fit_end",
                step=int(state.step),
                aborted=False,
                preempted=preempted,
                recompiles=self.recompiles.counts(),
                **goodput.summary(),
            )
        return state

    def _release_guard(self, guard) -> None:
        if guard is not None:
            guard.uninstall()
            if self._preempt_guard is guard:
                self._preempt_guard = None

    def _sentinel_decide(self, sentinel, events, metrics, step: int):
        """Feed one completed step to the sentinel; handle the skip/spike
        rungs (events only) inline and return the decision when the trainer
        must act (rollback/halt), escalating rollback to halt when there is
        no checkpoint to roll back to."""
        skipped = False
        loss_val = None
        if isinstance(metrics, dict):
            if "sentinel_skipped" in metrics:
                skipped = float(metrics["sentinel_skipped"]) > 0.5
            if "loss" in metrics:
                loss_val = float(metrics["loss"])
        decision = sentinel.observe(step, loss_val, skipped)
        if decision.action == "skip":
            if events is not None:
                events.emit(
                    "fault.skip", step=step, reason=decision.reason, skips=sentinel.skips
                )
            return None
        if decision.action == "ok":
            if decision.reason == "spike-noted" and events is not None:
                events.emit("fault.spike", step=step, **decision.detail)
            return None
        if decision.action == "rollback" and (
            self.checkpoints is None or self.checkpoints.latest_step() is None
        ):
            decision = sentinel.notify_rollback_unavailable()
        return decision

    def close(self) -> None:
        """Release the checkpoint manager (waits for in-flight async saves).
        ``run_training`` calls this; long-lived callers constructing many
        Trainers should too."""
        if self.checkpoints is not None:
            self.checkpoints.close()
            self.checkpoints = None
        if self._events is not None:
            self._events.close()
