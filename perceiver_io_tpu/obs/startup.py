"""Start-up read from inside the program: what a process does before its
first step, as spans and counters on the profiler's clock.

The record itself is ``perceiver_io_tpu/_startup.py`` (stdlib only, so the
package root loads it before anything else); this module adopts it:

- **stamped spans**: ``startup/import`` (attr ``package``, and ``module`` for
  the three imports ``training/__init__.py`` brackets), stamped first
  statement to last of every package ``__init__.py``; ``startup/state_create``
  (attrs ``leaves``, ``param_bytes``) in ``TrainState.create``, which times
  what the host did and waits for no device. Opened while a profiler session
  runs they are ``TraceAnnotation``s too (``obs.trace._enter_annotation``).
- **JAX's own events**, through listeners registered once, here:
  ``startup/trace``, ``startup/lower`` and ``startup/compile`` (attr ``fn``,
  JAX's ``fun_name``) from ``/jax/core/compile/jaxpr_trace_duration``,
  ``jaxpr_to_mlir_module_duration`` and ``backend_compile_duration``, which
  carry ``time.time()`` at both ends: the epoch ``Span.start_ns`` is on. A
  ``startup/compile`` says whether the persistent cache served it (``cache``:
  ``"hit"``, ``"miss"`` or ``"off"``, from the cache's events since the compile
  before it closed) and, on a hit, what the read took (``retrieval_s``). A jit
  traced inside another's trace (a program holds thousands) is no span of its
  own: its time stays in the outer ``startup/trace``, so it is counted once.
- **counters** in ``obs.metrics.default_registry()``, all self time (what
  nests under a span, a small program compiled in the middle of a trace say,
  is counted in its own family and not in the span's):
  ``startup_import_seconds{package}``, ``startup_trace_seconds{fn}``,
  ``startup_lower_seconds{fn}``, ``startup_compile_seconds{fn,cache}``,
  ``startup_programs_total{cache}``, ``startup_cache_retrieval_seconds``; the
  unlabeled series is the total. They keep counting after start-up: a compile
  in the middle of a ``fit`` is the same event.

The first ``Tracer`` that has a sink takes the record once
(:func:`hand_to`): the spans become ``span`` rows of its ``events.jsonl`` with
their ``parent_id``s, under the ``fit`` span where there is one.
``obs.recompile.RecompileTracker`` reads the spans that closed inside a call
(:func:`compile_split`); ``tools/obs_report.py`` prints :func:`startup_table`.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Iterable, List

from perceiver_io_tpu._startup import RECORD, StartupSpan
from perceiver_io_tpu.obs import trace as obs_trace
from perceiver_io_tpu.obs.metrics import default_registry

IMPORT, STATE_CREATE = "startup/import", "startup/state_create"
TRACE, LOWER, COMPILE = "startup/trace", "startup/lower", "startup/compile"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_JAX_SPANS = {
    _TRACE_EVENT: TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER,
    "/jax/core/compile/backend_compile_duration": COMPILE,
}
_SECONDS = {IMPORT: "startup_import_seconds", TRACE: "startup_trace_seconds", LOWER: "startup_lower_seconds",
            COMPILE: "startup_compile_seconds"}
# a thread's state between JAX's events: what the persistent cache said since the last
# compile closed (``said``, ``retrieval_s``) and how many traces are open (``tracing``)
_CACHE = threading.local()


def import_label(attrs: Dict) -> str:
    """A ``startup/import`` span's dotted name: the package, or one of its
    bracketed modules."""
    package = str(attrs.get("package"))
    return f"{package}.{attrs['module']}" if attrs.get("module") else package


def _on_open(span: StartupSpan) -> None:
    span._annotation = obs_trace._enter_annotation(span)


def _on_close(span: StartupSpan) -> None:
    obs_trace._exit_annotation(span._annotation)
    span._annotation = None


# (family, label values) -> (the family's counter, the labeled child): a trace holds
# thousands of inner jits, and get-or-create under two locks is most of an event's cost
_COUNTERS: Dict[tuple, tuple] = {}


def _counters(family: str, **labels) -> tuple:
    key = (family, *labels.values())
    found = _COUNTERS.get(key)
    if found is None:
        total = default_registry().counter(family)
        found = _COUNTERS[key] = (total, total.labels(**labels))
    return found


def _count(span: StartupSpan) -> None:
    name, attrs = span.name, span.attrs
    if name == IMPORT:
        both = _counters(_SECONDS[name], package=import_label(attrs))
    elif name == COMPILE:
        both = _counters(_SECONDS[name], fn=str(attrs.get("fn")), cache=attrs["cache"])
        for counter in _counters("startup_programs_total", cache=attrs["cache"]):
            counter.inc()
        if "retrieval_s" in attrs:
            default_registry().counter("startup_cache_retrieval_seconds").inc(attrs["retrieval_s"])
    elif name in _SECONDS:
        both = _counters(_SECONDS[name], fn=str(attrs.get("fn")))
    else:
        return
    for counter in both:
        counter.inc(span.self_s)


def _on_event(event: str, **_) -> None:
    # a compile asks (``compile_requests_use_cache``), then is served or writes its entry
    if event == "/jax/compilation_cache/cache_hits":
        _CACHE.said = "hit"
    elif event in ("/jax/compilation_cache/cache_misses", "/jax/compilation_cache/compile_requests_use_cache"):
        _CACHE.said = "miss"


def _on_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/compilation_cache/cache_retrieval_time_sec":
        _CACHE.retrieval_s = float(duration)


def _on_scalar(event: str, value: float, **_) -> None:
    # JAX reports an event's start as a scalar of the same name: a trace has opened on this thread
    if event == _TRACE_EVENT:
        _CACHE.tracing = getattr(_CACHE, "tracing", 0) + 1


def _on_time_span(event: str, start_time: float, end_time: float, fun_name=None, **_) -> None:
    name = _JAX_SPANS.get(event)
    if name is None:
        return
    if name == TRACE:
        _CACHE.tracing = still_open = max(getattr(_CACHE, "tracing", 1) - 1, 0)
        if still_open:
            return  # an inner jit's trace, thousands a program: its time stays in the trace that holds it
    attrs = {"fn": fun_name}
    if name == COMPILE:
        attrs["cache"] = getattr(_CACHE, "said", None) or "off"
        if attrs["cache"] == "hit" and getattr(_CACHE, "retrieval_s", None) is not None:
            attrs["retrieval_s"] = _CACHE.retrieval_s
        _CACHE.said = _CACHE.retrieval_s = None
    RECORD.add(name, int(start_time * 1e9), int(end_time * 1e9), attrs)


def _install() -> None:
    """Adopt the record and register JAX's listeners, once a process."""
    if RECORD.on_closed is not None:
        return
    RECORD.on_open, RECORD.on_close, RECORD.on_closed = _on_open, _on_close, _count
    for earlier in RECORD.held():  # the packages imported before this one
        _count(earlier)
    try:
        from jax import monitoring
    except Exception:  # noqa: BLE001 — the record works without jax
        return
    monitoring.register_event_listener(_on_event)
    monitoring.register_scalar_listener(_on_scalar)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_time_span_listener(_on_time_span)


@contextlib.contextmanager
def span(name: str, **attrs):
    """A stamped span of the record around the block; yields it, so the body
    can ``.set(...)`` attrs."""
    s = RECORD.open(name, **attrs)
    try:
        yield s
    finally:
        RECORD.close(s)


def clock() -> tuple:
    """``(time.time_ns(), time.perf_counter())`` read together when the
    record was made: places any span on either clock."""
    return RECORD.clock


def dropped() -> int:
    """Closed spans the record no longer holds (it keeps the newest
    ``_startup.MAX_SPANS`` and, apart from them, every import span): a reader
    of set-up that finds any has lost the first traces, lowers or compiles of
    the process, never its imports."""
    return RECORD.dropped


def mark() -> int:
    """A cursor for :func:`rows`/:func:`compile_split`: what closes after
    this call lies behind it."""
    return RECORD.closed


def rows(since: int = 0) -> List[Dict]:
    """The record's closed spans as rows (``StartupSpan.to_row``), oldest
    first."""
    return [s.to_row() for s in RECORD.since(since)]


def compile_split(since: int) -> Dict:
    """What the spans closed since ``since`` say about one call that
    compiled: ``trace_s``, ``lower_s`` and ``backend_s`` (self times, so a
    jit inside a jit counts once) and ``cache``: ``"miss"`` if any program
    missed the persistent cache, else ``"hit"`` if any was read from it, else
    ``"off"``; None where the call built no program."""
    out = {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0, "cache": None}
    keys = {TRACE: "trace_s", LOWER: "lower_s", COMPILE: "backend_s"}
    rank = {None: 0, "off": 1, "hit": 2, "miss": 3}
    for s in RECORD.since(since):
        if s.name in keys:
            out[keys[s.name]] += s.self_s
        if s.name == COMPILE and rank[s.attrs["cache"]] > rank[out["cache"]]:
            out["cache"] = s.attrs["cache"]
    return {k: round(v, 6) if isinstance(v, float) else v for k, v in out.items()}


def hand_to(tracer) -> None:
    """Queue the record's spans on ``tracer`` as ``span`` rows, once a
    process: the first ``Tracer`` with a sink calls this before its own first
    row. A span without a parent goes under the ambient span (the trainer's
    ``fit``) where one is open."""
    if RECORD.handed:
        return
    RECORD.handed = True
    with obs_trace._AMBIENT_LOCK:
        under = obs_trace._AMBIENT[-1].span_id if obs_trace._AMBIENT else None
    process = obs_trace._process_index()
    for s in RECORD.held():
        tracer.record(obs_trace.Span(
            name=s.name, span_id=s.span_id, parent_id=s.parent_id or under, process_index=process,
            attrs={**s.attrs, "self_ms": round(1e3 * s.self_s, 3)},
            start_ns=s.start_ns, end_ns=s.end_ns, _dur_s=s.dur_s,
        ))


def startup_table(span_rows: Iterable[Dict]) -> List[Dict]:
    """The start-up table of a stream's ``span`` rows: a row a (span name,
    package or ``fn`` [, ``cache``]) with its ``count``, ``total_ms`` and
    ``self_ms``, largest self time first. What ``tools/obs_report.py``
    prints."""
    table: Dict[tuple, Dict] = {}
    for r in span_rows:
        name = r.get("name", "")
        if not name.startswith("startup/"):
            continue
        attrs = r.get("attrs") or {}
        what = import_label(attrs) if name == IMPORT else attrs.get("fn") or ""
        key = (name, what, attrs.get("cache"))
        agg = table.setdefault(key, {"name": name, "what": what, "cache": attrs.get("cache"),
                                     "count": 0, "total_ms": 0.0, "self_ms": 0.0})
        agg["count"] += 1
        agg["total_ms"] += float(r["dur_ms"])
        agg["self_ms"] += float(attrs.get("self_ms", r.get("self_ms", r["dur_ms"])))
    return sorted(table.values(), key=lambda a: -a["self_ms"])


_install()
