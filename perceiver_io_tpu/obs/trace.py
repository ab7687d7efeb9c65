"""Request/step-level host spans — the tracing half of the Spanline surface.

PR 1's telemetry is run-scoped (a fit averaged 3.4M tok/s); nothing in the
stream says what any one *step* or *generate request* experienced, and the
``fault.*`` audit trail cannot point at the step that ate an incident. A
:class:`Span` is a host wall-clock interval with an id, a parent, a name and
attrs, persisted as a ``span`` row in ``events.jsonl`` (same sink as every
other event); while a span is open it is the *current* span, and
``obs.events.EventLog.emit`` stamps its id onto every row emitted inside it
— so ``fault.rollback`` / ``resume`` / ``graphlint`` / ``compile`` events
are attributable to the exact step (or request) they happened in.

Two scoping mechanisms compose:

- a **contextvar** stack (per-thread/task): ``Tracer.span`` nests — a
  ``checkpoint`` span opened inside a ``step`` span records the step as its
  parent, and events emitted inside attach to the innermost span;
- an **ambient** fallback (process-global): the trainer opens its ``fit``
  span with ``ambient=True`` so events emitted from *other threads* (the
  prefetch producer's ``fault.poison_batch`` / ``fault.fetch_retry``) still
  land inside the fit span instead of floating unattributed.

**The profiler's clock.** Every span a :class:`Tracer` opens (``span``,
``start``/``end``, ``traced``) also enters a ``jax.profiler.TraceAnnotation``
of the span's name carrying its ``span_id``: while a profiler session runs,
the span lies in the capture's host plane beside the device operations;
with no session the annotation costs a flag test. A span row's
``start_ns``/``end_ns`` are ``time.time_ns()``, the clock the profiler
stamps its events with: a capture's event times are nanoseconds since the
``profile_start_time`` its ``Task Environment`` plane records, so
``start_ns - profile_start_time`` lays any span row, the detached ones too
(:meth:`Tracer.detached`: spans that close out of LIFO order, like the
engine's per-slot ``request``, and so cannot be annotations), on the
capture's timeline. :func:`host_device_breakdown` does exactly that.

**Rows are written behind the work.** Span rows wait in the
:class:`Tracer` (``EventLog.emit_rows`` — one file open per flush, not per
span), because a per-step file append would tax a 3 ms TPU step. Event rows
sent through :meth:`Tracer.emit` keep their place in that queue, so a span
row recorded before a ``request`` row is also written before it; outside a
:meth:`Tracer.hold` an emitted event flushes the queue at once, inside one
everything waits for the holder's :meth:`Tracer.flush`. The serving engine
holds for the length of an ``engine/step`` and flushes after it; the trainer
flushes at every log boundary and on every ``fit_end`` path, so a clean or
cleanly-aborted run keeps all its spans.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from perceiver_io_tpu._startup import RECORD as _STARTUP

_CURRENT: "contextvars.ContextVar[Optional[Span]]" = contextvars.ContextVar(
    "obs_current_span", default=None
)
_AMBIENT: List["Span"] = []
_AMBIENT_LOCK = threading.Lock()


def new_span_id() -> str:
    """16-hex random span id (collision-safe per run, short enough to read)."""
    return os.urandom(8).hex()


def _process_index() -> int:
    try:
        import jax

        return int(jax.process_index())
    except Exception:  # noqa: BLE001 — tracing must work before jax init
        return 0


@dataclass
class Span:
    """One host wall-clock interval. ``start_ns``/``end_ns`` are epoch
    nanoseconds on ``time.time_ns()`` (the profiler's clock, module
    docstring) and ``t_start``/``t_end`` the same instants in epoch seconds
    (the ``ts`` convention of events.jsonl); the duration is measured on
    ``perf_counter`` so it cannot be NTP-stepped mid-span."""

    name: str
    span_id: str = field(default_factory=new_span_id)
    parent_id: Optional[str] = None
    process_index: int = field(default_factory=_process_index)
    attrs: Dict = field(default_factory=dict)
    detached: bool = False
    # the two clock reads come last, so that nothing lies between them and
    # the profiler annotation a Tracer enters next
    start_ns: int = field(default_factory=time.time_ns)
    end_ns: Optional[int] = None
    _perf0: float = field(default_factory=time.perf_counter, repr=False)
    _dur_s: Optional[float] = field(default=None, repr=False)

    def set(self, key: str, value) -> None:
        """Attach/overwrite one attr (shows up under ``attrs`` in the row)."""
        self.attrs[str(key)] = value

    def close(self) -> None:
        if self._dur_s is None:
            self._dur_s = time.perf_counter() - self._perf0
            self.end_ns = self.start_ns + int(self._dur_s * 1e9)

    @property
    def t_start(self) -> float:
        return self.start_ns / 1e9

    @property
    def t_end(self) -> Optional[float]:
        return None if self.end_ns is None else self.end_ns / 1e9

    @property
    def dur_ms(self) -> float:
        return 1e3 * (self._dur_s if self._dur_s is not None else time.perf_counter() - self._perf0)

    def to_row(self) -> Dict:
        """The ``span`` event row (sans ``ts``/``schema_version`` — the
        EventLog stamps those)."""
        self.close()
        row = {
            "name": self.name,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "t_start": round(self.t_start, 6),
            "t_end": round(self.t_end, 6),
            "start_ns": self.start_ns,
            "end_ns": self.end_ns,
            "dur_ms": round(self.dur_ms, 3),
            "process_index": self.process_index,
            "attrs": dict(self.attrs),
        }
        if self.detached:
            row["detached"] = True
        return row


_TRACE_ANNOTATION = None


def _enter_annotation(span: "Span"):
    """Enter a ``jax.profiler.TraceAnnotation`` named after ``span`` (a
    no-op unless a profiler session is running); returns it for
    :func:`_exit_annotation`, or None where jax cannot be imported."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation

            _TRACE_ANNOTATION = TraceAnnotation
        except Exception:  # noqa: BLE001 — tracing must work without jax
            _TRACE_ANNOTATION = False
    if not _TRACE_ANNOTATION:
        return None
    ann = _TRACE_ANNOTATION(span.name, span_id=span.span_id)
    ann.__enter__()
    return ann


def _exit_annotation(ann) -> None:
    if ann is not None:
        ann.__exit__(None, None, None)


def current_span() -> Optional[Span]:
    """The innermost open span of this thread/task, falling back to the
    process-ambient span (the trainer's ``fit``) for foreign threads."""
    s = _CURRENT.get()
    if s is not None:
        return s
    with _AMBIENT_LOCK:
        return _AMBIENT[-1] if _AMBIENT else None


def current_span_id() -> Optional[str]:
    s = current_span()
    return None if s is None else s.span_id


class _OpenSpan:
    """The context manager behind :meth:`Tracer.span` (a class, not a
    generator: the engine opens a dozen spans a step)."""

    __slots__ = ("tracer", "name", "ambient", "attrs", "span", "annotation", "token")

    def __init__(self, tracer: "Tracer", name: str, ambient: bool, attrs: Dict):
        self.tracer, self.name, self.ambient, self.attrs = tracer, name, ambient, attrs

    def __enter__(self) -> Span:
        s = self.span = Span(name=self.name, parent_id=current_span_id(), attrs=self.attrs)
        self.annotation = _enter_annotation(s)
        self.token = _CURRENT.set(s)
        if self.ambient:
            with _AMBIENT_LOCK:
                _AMBIENT.append(s)
        return s

    def __exit__(self, *exc) -> None:
        s = self.span
        _exit_annotation(self.annotation)
        _CURRENT.reset(self.token)
        if self.ambient:
            with _AMBIENT_LOCK:
                if s in _AMBIENT:
                    _AMBIENT.remove(s)
        self.tracer.record(s)


class Tracer:
    """Span factory and write-behind row queue bound to one event sink
    (``obs.events.EventLog`` or anything with ``emit_rows``/``emit``).
    ``events=None`` keeps the span context live (ids still stamp onto other
    sinks' rows) but records nothing."""

    def __init__(self, events=None, flush_every: int = 256):
        self.events = events
        self.flush_every = max(int(flush_every), 1)
        # (event kind, row, or the closed Span whose row is built at the
        # flush), in the order to write
        self._rows: List[tuple] = []
        self._n_events = 0  # the event (non-span) rows among them
        self._held = 0
        self._lock = threading.Lock()

    def span(self, name: str, ambient: bool = False, **attrs) -> "_OpenSpan":
        """Open a span: a context manager that yields it, so the body can
        ``.set(...)`` attrs. ``ambient=True`` additionally publishes it as
        the process-wide fallback for the duration (see module docstring)."""
        return _OpenSpan(self, str(name), ambient, attrs)

    def start(self, name: str, **attrs) -> Span:
        """Non-context form (pair with :meth:`end`) for open/close sites
        that straddle a loop iteration — the trainer's per-step span closes
        at the NEXT iteration's top, which no ``with`` block can express."""
        s = Span(name=str(name), parent_id=current_span_id(), attrs=dict(attrs))
        s._annotation = _enter_annotation(s)
        s._cv_token = _CURRENT.set(s)
        return s

    def end(self, span: Span) -> None:
        _exit_annotation(getattr(span, "_annotation", None))
        span._annotation = None
        token = getattr(span, "_cv_token", None)
        if token is not None:
            try:
                _CURRENT.reset(token)
            except ValueError:  # closed from a foreign context; defensive
                pass
            span._cv_token = None
        self.record(span)

    def detached(self, name: str, **attrs) -> Span:
        """A span outside the nesting stack, for lifetimes that overlap and
        close out of LIFO order (one per engine slot): no parent, never the
        current span, no profiler annotation. The caller closes it with
        :meth:`record`; its ``start_ns``/``end_ns`` place it on a capture's
        timeline all the same."""
        return Span(name=str(name), parent_id=None, attrs=dict(attrs), detached=True)

    def traced(self, name: Optional[str] = None, **attrs) -> Callable:
        """Decorator form: ``@tracer.traced("load_batch")`` wraps each call
        in a span (default name: the function's ``__name__``)."""

        def deco(fn):
            span_name = name or fn.__name__

            def wrapped(*args, **kwargs):
                with self.span(span_name, **attrs):
                    return fn(*args, **kwargs)

            wrapped.__name__ = fn.__name__
            wrapped.__wrapped__ = fn
            return wrapped

        return deco

    def record(self, span: Span) -> None:
        """Close ``span`` and queue its row; nothing is written before the
        queue holds ``flush_every`` rows, and nothing at all under a hold."""
        span.close()
        self._take_startup()
        with self._lock:
            self._rows.append(("span", span))
            full = len(self._rows) >= self.flush_every and not self._held
        if full:
            self.flush()

    def emit(self, event: str, **fields) -> None:
        """Send an event row through the queue, behind the span rows recorded
        so far (the ``EventLog.emit`` duck type, so a ``Tracer`` can stand
        where a sink is expected). The row keeps the current span of THIS
        call (its ``ts`` is the sink's, stamped when it is written); it is
        written at once with everything queued before it, or under a
        :meth:`hold` by the holder's flush."""
        if "span_id" not in fields:
            sid = current_span_id()
            if sid is not None:
                fields["span_id"] = sid
        self._take_startup()
        with self._lock:
            self._rows.append((str(event), fields))
            self._n_events += 1
            held = self._held
        if not held:
            self.flush()

    def _take_startup(self) -> None:
        """The first ``Tracer`` of the process that has a sink queues the
        start-up record's spans in front of its own first row
        (``obs/startup.py``): what ran before any tracer existed."""
        if self.events is not None and not _STARTUP.handed:
            from perceiver_io_tpu.obs import startup

            startup.hand_to(self)

    @contextlib.contextmanager
    def hold(self):
        """Keep every row in memory for the length of the block: no
        ``record`` or ``emit`` inside writes anything. The holder calls
        :meth:`flush` afterwards (the engine: once per step at most, when
        :meth:`flush_due`)."""
        with self._lock:
            self._held += 1
        try:
            yield self
        finally:
            with self._lock:
                self._held -= 1

    def flush_due(self) -> bool:
        """Whether a holder should flush now: an event row is waiting (a
        reader of the stream expects it promptly), or ``flush_every`` rows
        are."""
        with self._lock:
            return self._n_events > 0 or len(self._rows) >= self.flush_every

    def flush(self) -> None:
        """Write all queued rows in their order (no-op when empty or
        sink-less): runs of span rows in one batch each, event rows one by
        one through ``emit`` so a ``FlightRecorder`` sees its triggers."""
        with self._lock:
            queued, self._rows, self._n_events = self._rows, [], 0
        if not queued or self.events is None:
            return
        rows = [(kind, r.to_row() if isinstance(r, Span) else r) for kind, r in queued]
        emit_rows = getattr(self.events, "emit_rows", None)
        i = 0
        while i < len(rows):
            kind, row = rows[i]
            if kind != "span" or emit_rows is None:
                self.events.emit(kind, **row)
                i += 1
                continue
            j = i
            while j < len(rows) and rows[j][0] == "span":
                j += 1
            emit_rows("span", [r for _, r in rows[i:j]])
            i = j


def maybe_span(tracer: Optional[Tracer], name: str, **attrs):
    """``tracer.span(name, ...)`` — or a null context yielding None when
    tracing is off, so call sites stay one-liners."""
    if tracer is None:
        return contextlib.nullcontext(None)
    return tracer.span(name, **attrs)


# ---------------------------------------------------------------------------
# host/device correlation: span rows laid on one profiler capture's timeline
# ---------------------------------------------------------------------------

NO_SPAN = "(no span)"


def _merged(intervals) -> List[tuple]:
    out: List[list] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def host_device_breakdown(span_rows, capture=None) -> Dict:
    """Per span name, where the host's time went and which span the device
    idled under — what ``tools/obs_report.py`` prints.

    ``span_rows`` are ``span`` event rows (dicts). ``capture`` is one
    profiler capture taken while they were recorded: a directory or
    ``.xplane.pb`` path, or what ``obs.xplane.load_capture`` returned for it
    (None → host side only). Returns ``{"spans": {name: {...}}}`` with, per
    name, ``count``, ``total_ms`` and ``self_ms`` (total less the time of
    the spans opened directly inside); with a capture that holds device
    operations also ``idle_ms`` per name and a ``device`` entry
    (``window_ms``, ``busy_ms``, ``idle_ms``). The window runs from the
    first to the last nested span inside the capture; each gap between
    device operations in it goes to the innermost (shortest) nested span
    covering the gap's midpoint, or to ``"(no span)"`` (the rule of
    ``benchmarks/lib/trace.py::idle_gaps``). Detached spans (``request``)
    are counted but take no idle time: they overlap everything.
    """
    spans = [r for r in span_rows if r.get("event", "span") == "span"]
    child_ms: Dict[str, float] = {}
    opened = {r.get("span_id"): r.get("start_ns", 0) for r in spans}
    for r in spans:
        # a child that began before its parent opened (the start-up record's
        # spans, handed over under ``fit``) took none of the parent's time
        if r.get("parent_id") is not None and r.get("start_ns", 0) >= opened.get(r["parent_id"], 0):
            child_ms[r["parent_id"]] = child_ms.get(r["parent_id"], 0.0) + float(r["dur_ms"])
    by_name: Dict[str, Dict] = {}
    for r in spans:
        agg = by_name.setdefault(r["name"], {"count": 0, "total_ms": 0.0, "self_ms": 0.0})
        dur = float(r["dur_ms"])
        agg["count"] += 1
        agg["total_ms"] += dur
        agg["self_ms"] += max(dur - child_ms.get(r.get("span_id"), 0.0), 0.0)
    out: Dict = {"spans": by_name}
    if capture is None:
        return out
    if isinstance(capture, (str, os.PathLike)):
        from perceiver_io_tpu.obs.xplane import load_capture

        capture = load_capture(capture)
    t0, length = capture["profile_start_ns"], capture["length_ns"]
    nested = [
        (r["start_ns"] - t0, r["end_ns"] - t0, r["name"])
        for r in spans
        if not r.get("detached") and "start_ns" in r
        and r["start_ns"] - t0 >= 0 and r["end_ns"] - t0 <= length
    ]
    ops = next((ops for _, ops in sorted(capture["device_ops"].items())), None)
    if not nested or not ops:
        return out
    lo, hi = min(a for a, _, _ in nested), max(b for _, b, _ in nested)
    busy = _merged((max(a, lo), min(a + d, hi)) for _, a, d in ops)
    gaps, at = [], lo
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    # one sweep over time: nested spans form a tree, so the open ones are a
    # stack whose top is the innermost (a capture holds a span per few
    # device operations, and a gap between every two of those)
    nested.sort(key=lambda n: (n[0], -n[1]))
    idle: Dict[str, float] = {}
    stack: List[tuple] = []
    k = 0
    for a, b in gaps:
        mid = (a + b) / 2
        while k < len(nested) and nested[k][0] <= mid:
            while stack and stack[-1][1] < nested[k][0]:
                stack.pop()
            stack.append(nested[k])
            k += 1
        while stack and stack[-1][1] < mid:
            stack.pop()
        name = stack[-1][2] if stack else NO_SPAN
        idle[name] = idle.get(name, 0.0) + (b - a) / 1e6
    for name, ms in idle.items():
        by_name.setdefault(name, {"count": 0, "total_ms": 0.0, "self_ms": 0.0})["idle_ms"] = ms
    busy_ms = sum(b - a for a, b in busy) / 1e6
    out["device"] = {
        "window_ms": (hi - lo) / 1e6,
        "busy_ms": busy_ms,
        "idle_ms": (hi - lo) / 1e6 - busy_ms,
    }
    return out
