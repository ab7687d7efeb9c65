"""Structured run events: a JSONL event sink + the run manifest.

``events.jsonl`` is the machine-readable companion of ``metrics.csv`` — one
JSON object per line, every line carrying ``ts`` (epoch seconds),
``event`` (the kind) and ``schema_version``. The trainer emits
``fit_start`` / ``log`` / ``compile`` / ``eval`` / ``span`` (host
step/fit/checkpoint spans — obs/trace.py) / ``graphlint`` (the
static-analysis verdict on the train step's traced graph — analysis/, one
event per fit) / ``resume`` / ``resume.reshard`` (a checkpoint landed on a different mesh —
elastic resume, docs/robustness.md#elastic-resume) and the ``fault.*``
family (``fault.preempt`` / ``fault.skip`` / ``fault.spike`` /
``fault.rollback`` / ``fault.halt`` / ``fault.poison_batch`` /
``fault.fetch_retry`` / ``fault.ckpt_retry`` — the fault-handling audit
trail, training/faults.py, docs/robustness.md) / ``fit_end`` events through
one :class:`EventLog`; instrumented generation emits per-request
``request`` rows (obs/slo.py aggregates them; under a load generator each
row also carries ``queue_wait_s``/``arrival_ts`` admission telemetry) and
``metrics`` registry snapshots (obs/metrics.py); probed runs add ``probe``
numerics snapshots and ``probe.blast`` blast-radius reports
(obs/probes.py); load-generated runs close with a ``load.summary`` row
(obs/loadgen.py) and flight-recorder dumps announce themselves as
``flight.dump`` rows naming the triggering span (obs/flightrec.py).
``tools/obs_report.py`` renders a run directory back into a summary
table; ``tools/obs_diff.py`` diffs two runs.

``run_manifest.json`` pins what the run actually ran on: mesh shape,
device kind/count, jax version, and a stable hash of the model/trainer
configs — the context every perf number needs to be comparable later.

Single-process runs gate writes to process 0 like
``training.metrics.MetricsLogger`` (reference ``@rank_zero_only``
semantics). Multi-process programs instead shard: every process writes its
OWN ``events-p{process_index}.jsonl`` (a cross-host shared sink would
interleave torn lines), and :func:`merged_events` k-way-merges the shards
back into one stream with a monotonic-clock-skew-tolerant sort —
``obs_report``/``obs_diff``/``obs.slo`` all read through it.

Every row carries ``schema_version`` (:data:`EVENT_SCHEMA_VERSION`);
:func:`validate_events` checks a stream against the per-kind required-field
table plus span referential integrity, so schema drift fails a gate instead
of silently confusing the next consumer. Rows emitted inside an open
``obs.trace`` span are stamped with its ``span_id``.
"""

from __future__ import annotations

import dataclasses
import glob
import hashlib
import heapq
import json
import os
import socket
import time
import warnings
from typing import Dict, Iterable, List, Optional

# bump when a row's meaning changes incompatibly; validate_events pins it
EVENT_SCHEMA_VERSION = 1


def _process_topology() -> tuple:
    """``(process_index, process_count)`` — (0, 1) before/without jax."""
    try:
        import jax

        return int(jax.process_index()), int(jax.process_count())
    except Exception:  # noqa: BLE001 — telemetry must work before jax init
        return 0, 1


class EventLog:
    """Append-only JSONL event sink (``<log_dir>/events.jsonl``).

    Each :meth:`emit` opens/appends/closes — crash-safe (a killed run keeps
    every event already emitted) and cheap at the trainer's log-interval
    event rate. Non-JSON values are stringified rather than raised on: a
    telemetry write must never take the training loop down.
    """

    def __init__(
        self,
        log_dir: str,
        filename: str = "events.jsonl",
        main_process: Optional[bool] = None,
        process_index: Optional[int] = None,
        process_count: Optional[int] = None,
    ):
        if process_index is None or process_count is None:
            pi, pc = _process_topology()
            process_index = pi if process_index is None else process_index
            process_count = pc if process_count is None else process_count
        self.process_index = int(process_index)
        self.process_count = int(process_count)
        if self.process_count > 1 and filename == "events.jsonl":
            # multi-process hygiene: one shard per process (every process
            # writes — the fault/span events of process 3 matter too);
            # merged_events() rebuilds the single stream
            filename = f"events-p{self.process_index}.jsonl"
            main_process = True
        elif main_process is None:
            from perceiver_io_tpu.parallel.dist import is_main_process

            main_process = is_main_process()
        self._active = bool(main_process)
        self.log_dir = os.path.abspath(log_dir)
        self.path = os.path.join(self.log_dir, filename)
        if self._active:
            try:
                os.makedirs(self.log_dir, exist_ok=True)
            except OSError as e:
                # same contract as emit(): telemetry setup must never take
                # the training loop down (read-only/dead log filesystem)
                self._active = False
                warnings.warn(f"EventLog disabled, cannot create {self.log_dir}: {e}")

    def _row(self, event: str, fields: Dict) -> Dict:
        row = {
            "ts": round(time.time(), 6),
            "event": str(event),
            "schema_version": EVENT_SCHEMA_VERSION,
        }
        row.update(fields)
        if "span_id" not in row:
            # attribute the row to the innermost open host span (obs/trace):
            # fault.* / resume / compile events become joinable to the step
            # or request they happened in. span rows carry their own id.
            from perceiver_io_tpu.obs.trace import current_span_id

            sid = current_span_id()
            if sid is not None:
                row["span_id"] = sid
        return row

    @staticmethod
    def _line(row: Dict) -> str:
        # strict JSON: NaN/Inf (a diverged loss is exactly the run this
        # log diagnoses) become null, not the invalid-JSON NaN extension
        # that breaks jq / JSON.parse consumers of events.jsonl
        try:
            return json.dumps(row, default=str, allow_nan=False)
        except ValueError:
            return json.dumps(_nan_to_none(row), default=str, allow_nan=False)

    def emit(self, event: str, **fields) -> None:
        if not self._active:
            return
        try:
            line = self._line(self._row(event, fields))
            with open(self.path, "a") as f:
                f.write(line + "\n")
        except OSError as e:
            # the never-take-the-loop-down contract: a dead log filesystem
            # (disk full, run dir removed mid-run) deactivates the sink
            # instead of killing a long training run over telemetry
            self._active = False
            warnings.warn(f"EventLog deactivated, cannot write {self.path}: {e}")

    def emit_rows(self, event: str, rows: Iterable[Dict]) -> None:
        """Batch append: many rows of one kind through a single file open —
        the span-buffer flush path (``obs.trace.Tracer``), where per-row
        opens would tax the step loop."""
        if not self._active:
            return
        try:
            lines = [self._line(self._row(event, dict(r))) for r in rows]
            if not lines:
                return
            with open(self.path, "a") as f:
                f.write("\n".join(lines) + "\n")
        except OSError as e:
            self._active = False
            warnings.warn(f"EventLog deactivated, cannot write {self.path}: {e}")

    def close(self) -> None:  # symmetry with MetricsLogger; nothing buffered
        pass


def _nan_to_none(obj):
    """Replace non-finite floats with None, recursively."""
    if isinstance(obj, float):
        return obj if obj == obj and obj not in (float("inf"), float("-inf")) else None
    if isinstance(obj, dict):
        return {k: _nan_to_none(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_nan_to_none(v) for v in obj]
    return obj


def _jsonable(obj):
    """Best-effort JSON form of a config object (dataclass / dict / repr)."""
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {k: _jsonable(v) for k, v in dataclasses.asdict(obj).items()}
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    return str(obj)


def config_hash(*objs) -> str:
    """Stable short hash of one or more config objects — the run identity a
    log row can be joined on (same configs, same hash, any process/host)."""
    payload = json.dumps([_jsonable(o) for o in objs], sort_keys=True, default=str)
    return hashlib.sha256(payload.encode()).hexdigest()[:12]


def write_run_manifest(
    log_dir: str,
    mesh=None,
    model_config=None,
    trainer_config=None,
    extra: Optional[Dict] = None,
    main_process: Optional[bool] = None,
    filename: str = "run_manifest.json",
) -> Dict:
    """Write ``run_manifest.json`` next to the event log; returns the
    manifest dict (on every process — only process 0 writes)."""
    import jax

    devices = jax.devices()
    manifest = {
        "created_at": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "hostname": socket.gethostname(),
        "jax_version": jax.__version__,
        "backend": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "local_device_count": jax.local_device_count(),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "mesh": None if mesh is None else {str(k): int(v) for k, v in mesh.shape.items()},
        "config_hash": config_hash(model_config, trainer_config),
        "model_config": _jsonable(model_config),
        "trainer_config": _jsonable(trainer_config),
    }
    if extra:
        manifest.update(_jsonable(extra))
    if main_process is None:
        from perceiver_io_tpu.parallel.dist import is_main_process

        main_process = is_main_process()
    if main_process:
        try:
            os.makedirs(os.path.abspath(log_dir), exist_ok=True)
            with open(os.path.join(log_dir, filename), "w") as f:
                json.dump(manifest, f, indent=2, default=str)
        except OSError as e:
            # same contract as EventLog.emit: a telemetry write must never
            # take the training loop down
            warnings.warn(f"run manifest not written to {log_dir}: {e}")
    return manifest


# ---------------------------------------------------------------------------
# reading the stream back: shard discovery, merge, validation
# ---------------------------------------------------------------------------


def event_shards(run_dir: str) -> List[str]:
    """The event files of a run directory: ``events.jsonl`` (single-process)
    and/or ``events-p*.jsonl`` (one per process), index-sorted."""
    out = []
    single = os.path.join(run_dir, "events.jsonl")
    if os.path.exists(single):
        out.append(single)

    def _pidx(path):
        try:
            return int(os.path.basename(path)[len("events-p") : -len(".jsonl")])
        except ValueError:
            return 1 << 30
    out.extend(sorted(glob.glob(os.path.join(run_dir, "events-p*.jsonl")), key=_pidx))
    return out


def read_event_file(path: str) -> List[Dict]:
    """Parse one shard; a torn tail line (killed run) is skipped, torn lines
    elsewhere too (the validator, not the reader, complains about those)."""
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rows.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return rows


def merged_events(run_dir: str) -> List[Dict]:
    """One event stream for the run, whatever the process count.

    K-way merge of the shards by timestamp with a **monotonic-clock-skew
    guard**: within a shard, file order is authoritative (it is the order
    the process actually emitted in), so each row's sort key is the running
    max of its shard's timestamps — a row whose wall clock stepped backwards
    (NTP slew mid-run) cannot be sorted before its own predecessors; across
    shards, skewed clocks degrade interleaving accuracy but never reorder
    any single process's history. Ties break on (shard index, row index),
    keeping the merge deterministic."""
    streams = []
    for shard_i, path in enumerate(event_shards(run_dir)):
        rows = read_event_file(path)
        keyed = []
        ts_eff = float("-inf")
        for row_i, row in enumerate(rows):
            try:
                ts = float(row.get("ts", 0.0))
            except (TypeError, ValueError):
                ts = 0.0
            ts_eff = max(ts_eff, ts)
            keyed.append(((ts_eff, shard_i, row_i), row))
        streams.append(keyed)
    return [row for _, row in heapq.merge(*streams, key=lambda kr: kr[0])]


# per-kind required fields (validate_events); kinds not listed are allowed —
# the table pins the CONSUMED schema, not an exhaustive vocabulary
_REQUIRED_FIELDS: Dict[str, tuple] = {
    "fit_start": ("start_step", "max_steps"),
    "fit_end": ("step", "aborted"),
    "log": ("step",),
    "eval": ("step",),
    "compile": ("fn", "wall_s", "n_compiles"),
    "resume": ("from_step", "to_step"),
    # elastic resume (training/checkpoint.py, docs/robustness.md#elastic-
    # resume): a checkpoint landed on a different mesh than it was saved
    # under — old/new mesh shapes, leaves/bytes moved, restore wall time
    "resume.reshard": ("old_mesh", "new_mesh", "step"),
    # transient checkpoint-I/O retry (save/restore wrapped in RetryPolicy —
    # same discipline as the loader's fault.fetch_retry)
    "fault.ckpt_retry": ("attempt", "delay_s"),
    "span": ("name", "span_id", "t_start", "t_end", "dur_ms", "process_index", "attrs"),
    "request": ("request_id", "batch", "prompt_len", "ttft_s", "outcome", "tokens_out"),
    "metrics": ("counters", "gauges", "histograms"),
    "graphlint": (),
    "graphcheck": (),
    # Probeline (obs/probes.py): per-scope numerics snapshots at log
    # boundaries, and the blast-radius attribution a sentinel trip dumps
    "probe": ("step", "scopes"),
    "probe.blast": ("trigger", "scope", "step", "affected"),
    # Loadline (obs/loadgen.py): one summary row per load-generator run —
    # the artifact body's load-bearing fields; queue_wait_s/arrival_ts ride
    # the per-request `request` rows (optional — only loadgen-issued
    # requests carry admission telemetry)
    "load.summary": ("mode", "n_requests", "achieved_rps"),
    # flight recorder (obs/flightrec.py): a dump fired — the post-mortem
    # entry point must name what tripped it and which span to start from
    "flight.dump": ("trigger", "path", "n_events", "trigger_span_id"),
    # Shedline (perceiver_io_tpu/serving, docs/robustness.md#serving-
    # hardening): circuit-breaker state transitions, pre-decode retry
    # attempts, and the drain summary carrying the final books
    "serve.breaker": ("state", "prev", "reason"),
    "serve.retry": ("attempt", "delay_s"),
    "serve.drain": ("books",),
    # Evictline (serving/engine.py + serving/journal.py, docs/robustness.md
    # #engine-eviction-and-recovery). Vocabulary note: `serve.preempt`
    # (below, in KNOWN_EVENT_KINDS) is the SIGTERM/drain signal — the whole
    # PROCESS winding down; these three are per-REQUEST preemption: a slot
    # evicted under page pressure (its pages reclaimed, the request parked
    # resumable), a parked request resumed by token-exact prefill replay,
    # and a journaled request re-admitted into a fresh engine after a crash.
    "serve.evict": ("request_index", "tokens_out", "pages_freed"),
    "serve.resume": ("request_index", "tokens_out"),
    "serve.recover": ("request_index", "tokens_resumed"),
    # Shareline (serving/prefix.py + serving/pages.py, docs/serving.md
    # #prefix-sharing): a joining request's prompt matched a resident page
    # run in the radix prefix index and its prefill skipped those pages —
    # pages_matched of pages_total prompt pages came for free
    "serve.prefix_hit": ("request_index", "pages_matched", "pages_total"),
    # Simline (serving/sim.py, docs/observability.md#sim-artifacts): one
    # summary row per discrete-event simulation run — the SIM_r* artifact
    # body's load-bearing fields (per-tenant detail rides `tenants`)
    "sim.summary": (
        "n_requests", "n_tenants", "offered_rps", "achieved_rps",
        "fairness_jain", "max_starvation_age_s",
    ),
    # Fleetline (serving/router.py, docs/serving.md#fleet): replica
    # lifecycle transitions on the fleet router (join / drain / drained /
    # dead / degraded / restored), and the journal failover — a dead
    # replica's write-ahead journal replayed onto a survivor, the
    # fleet-level half of the Evictline recovery audit trail
    "serve.replica": ("replica_id", "transition"),
    "serve.failover": ("dead_replica", "survivor", "n_replayed"),
}

# OPTIONAL fields validated WHEN PRESENT (type-checked, never required —
# forward compatibility: older streams without them stay valid, newer
# streams with them validate their types instead of sailing through):
# the engine's request-row telemetry — batch_size_at_decode (Pageline) and
# the speculative-decode quality pair (Specline: per-request drafter
# acceptance rate and decode tokens emitted per batched verify step)
_OPTIONAL_FIELD_TYPES: Dict[str, Dict[str, tuple]] = {
    "request": {
        "batch_size_at_decode": (int, float),
        "acceptance_rate": (int, float),
        "tokens_per_step": (int, float),
        # Simline: the submitting tenant's identity (multi-tenant serving;
        # docs/serving.md#multi-tenant-telemetry) — optional so
        # single-tenant streams stay valid, a string when present
        "tenant": (str,),
        # the expert layers' routed-pair books of a decoder-only model
        # (core/moe.py taps): pairs a held expert served over pairs routed,
        # and pairs no pass served (zero, or the layer dropped tokens)
        "moe_local_share": (int, float),
        "moe_pairs_dropped": (int, float),
    },
    # Evictline: the engine leg of tools/loadgen.py stamps its eviction
    # behavior into the load.summary row (and the LOAD_r* artifact body) —
    # optional so pre-Evictline streams/artifacts stay valid, type-checked
    # when present so a regression in the counters cannot sail through
    "load.summary": {
        "evictions": (int, float),
        "resumes": (int, float),
        "parked_depth_peak": (int, float),
        # Shareline: the prefix leg of tools/loadgen.py stamps its sharing
        # figures (hit rate, shared/unshared TTFT ratio) into the summary
        # row — optional so pre-Shareline streams stay valid
        "prefix": (dict,),
    },
    # Simline tenant identity on the per-request preemption audit trail
    "serve.evict": {"tenant": (str,)},
    "serve.resume": {"tenant": (str,)},
    "serve.recover": {"tenant": (str,)},
    # Shareline: tenant identity and the token count the skip saved
    "serve.prefix_hit": {"tenant": (str,), "tokens_skipped": (int, float)},
    # Fleetline: the replica's outstanding depth at the transition and a
    # free-form reason ("heartbeat_timeout", "injected_kill", "sigterm") —
    # optional so minimal transition rows stay valid
    "serve.replica": {"reason": (str,), "outstanding": (int, float)},
    # Fleetline: how many of the dead replica's requests were parked vs
    # re-queued on the survivor, and the dead journal's path for post-mortem
    "serve.failover": {
        "n_parked": (int, float), "n_queued": (int, float),
        "n_already_complete": (int, float), "n_shed": (int, float),
        "journal": (str,),
    },
}

# the closed terminal-outcome vocabulary of `request` rows (the serving
# front end's clean-books invariant rides on it): "shed" is stamped at
# admission by perceiver_io_tpu.serving, "timeout"/"cancelled" by the
# generation cancellation seam, "ok"/"error" by the instrumented wrapper.
# validate_events warns on outcomes outside it (forward compatibility —
# a newer stream must not fail an older gate) and FAILS on a missing or
# non-string outcome.
REQUEST_OUTCOMES = frozenset({"ok", "error", "timeout", "shed", "cancelled"})

# the full vocabulary THIS version of the library emits. validate_events
# flags kinds outside it as WARNINGS (never problems): an older tool
# reading a newer stream must keep working — forward compatibility is a
# warning list, not a hard failure.
KNOWN_EVENT_KINDS = frozenset(_REQUIRED_FIELDS) | frozenset(
    {
        "fault.preempt", "fault.skip", "fault.spike", "fault.rollback",
        "fault.halt", "fault.poison_batch", "fault.fetch_retry",
        "serve.preempt",  # SIGTERM noticed by the serving front end (drain begins)
        "generate",  # pre-`request` legacy rows (obs_report still reads them)
    }
)


def validate_events(
    path: str, strict_spans: bool = True, warnings_out: Optional[List[str]] = None
) -> List[str]:
    """Validate an event stream (a run directory or one shard file);
    returns a list of problems (empty = valid).

    Checks every row parses as strict JSON, carries ``ts``/``event``/
    ``schema_version`` (pinned to :data:`EVENT_SCHEMA_VERSION`), and has the
    per-kind required fields; a torn line is tolerated only as the LAST line
    of its shard. With ``strict_spans`` every ``span_id``/``parent_id``
    reference must resolve to a ``span`` row in the same (merged) stream —
    the property that makes fault events attributable after the fact.

    Event kinds outside :data:`KNOWN_EVENT_KINDS` are NEVER problems —
    older tooling must survive newer streams. Pass a list as
    ``warnings_out`` to collect them as forward-compatibility warnings
    (one per unknown kind, first occurrence)."""
    problems: List[str] = []
    unknown_seen: set = set()
    shards = event_shards(path) if os.path.isdir(path) else [path]
    if not shards:
        return [f"{path}: no events.jsonl / events-p*.jsonl"]
    rows: List[Dict] = []
    for shard in shards:
        name = os.path.basename(shard)
        with open(shard) as f:
            lines = [ln for ln in (l.strip() for l in f) if ln]
        for i, line in enumerate(lines):
            try:
                row = json.loads(line)
            except json.JSONDecodeError:
                if i == len(lines) - 1:
                    continue  # torn tail of a killed run: expected
                problems.append(f"{name}:{i + 1}: unparseable line mid-file")
                continue
            if not isinstance(row, dict):
                problems.append(f"{name}:{i + 1}: row is not an object")
                continue
            rows.append(row)
            kind = row.get("event")
            if not isinstance(kind, str):
                problems.append(f"{name}:{i + 1}: missing/invalid 'event'")
                continue
            if (
                warnings_out is not None
                and kind not in KNOWN_EVENT_KINDS
                and kind not in unknown_seen
            ):
                unknown_seen.add(kind)
                warnings_out.append(
                    f"{name}:{i + 1}: unknown event kind {kind!r} "
                    "(newer stream? tolerated — forward-compatible)"
                )
            if not isinstance(row.get("ts"), (int, float)):
                problems.append(f"{name}:{i + 1} [{kind}]: missing/invalid 'ts'")
            if row.get("schema_version") != EVENT_SCHEMA_VERSION:
                problems.append(
                    f"{name}:{i + 1} [{kind}]: schema_version "
                    f"{row.get('schema_version')!r} != {EVENT_SCHEMA_VERSION}"
                )
            for field in _REQUIRED_FIELDS.get(kind, ()):
                if field not in row:
                    problems.append(f"{name}:{i + 1} [{kind}]: missing field {field!r}")
            for field, types in _OPTIONAL_FIELD_TYPES.get(kind, {}).items():
                # bool is an int subclass — "numeric" here means a real
                # measurement, so True/False fail like any other non-number
                # (and fail string-typed fields like tenant outright)
                if field in row and (
                    isinstance(row[field], bool)
                    or not isinstance(row[field], types)
                ):
                    want = "numeric" if int in types or float in types else "a string"
                    problems.append(
                        f"{name}:{i + 1} [{kind}]: optional field {field!r} "
                        f"must be {want} when present, got {row[field]!r}"
                    )
            if kind == "request" and "outcome" in row:
                # outcome is validated against the CLOSED vocabulary: a
                # missing outcome is a hard failure (required field above),
                # an unknown one only a forward-compat warning — an older
                # gate must survive a newer library's taxonomy
                outcome = row["outcome"]
                if not isinstance(outcome, str):
                    problems.append(
                        f"{name}:{i + 1} [request]: outcome {outcome!r} is not a string"
                    )
                elif (
                    warnings_out is not None
                    and outcome not in REQUEST_OUTCOMES
                    and ("outcome", outcome) not in unknown_seen
                ):
                    unknown_seen.add(("outcome", outcome))
                    warnings_out.append(
                        f"{name}:{i + 1} [request]: unknown outcome {outcome!r} "
                        f"(known: {', '.join(sorted(REQUEST_OUTCOMES))}; "
                        "newer stream? tolerated — forward-compatible)"
                    )
    if strict_spans:
        span_ids = {r.get("span_id") for r in rows if r.get("event") == "span"}
        for r in rows:
            kind = r.get("event")
            sid = r.get("span_id")
            if kind != "span" and sid is not None and sid not in span_ids:
                problems.append(f"[{kind}] span_id {sid!r} has no span row in the stream")
            if kind == "span":
                pid = r.get("parent_id")
                if pid is not None and pid not in span_ids:
                    problems.append(f"[span {r.get('name')}] parent_id {pid!r} unresolvable")
    return problems
