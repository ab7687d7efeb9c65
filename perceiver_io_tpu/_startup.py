"""The process-wide start-up record: closed spans in memory, stdlib only.

What a process does before its first step (imports, the optimizer state,
tracing, lowering, compiling or reading programs back from the cache) runs
before any ``obs.trace.Tracer`` exists, so it is kept here and handed to the
first ``Tracer`` that has a sink (``obs/startup.py``, which adopts this
module, feeds it JAX's own compile events and keeps the counters). The
package root loads this file first, which is why it imports nothing but the
standard library: ``obs/__init__.py`` pulls in jax.

A span is on the clocks ``obs.trace.Span`` uses: ``start_ns``/``end_ns`` on
``time.time_ns()`` (the profiler's clock), the duration on ``perf_counter``.
``RECORD.clock`` is one reading of both, so a reader can place any span on
either. Nesting is by containment, found when a span closes: whatever closed
on the same thread since the span opened and has no parent yet is its child.
That one rule covers the stamped spans (``open``/``close``) and the spans
somebody else timed (``add``: JAX reports a compile when it is over, inner
ones first), and gives every span its **self time**, the duration less its
children's. Always on: two clock reads and a list append a span, no I/O.
"""

from __future__ import annotations

import collections
import heapq
import itertools
import os
import threading
import time

# the record keeps this many closed spans; older ones fall off the front (a
# serving process that compiles now and then must not grow without bound)
MAX_SPANS = 16384
# the package imports are kept apart from that ring, for as long as the process lives: a package is imported once
# (twenty rows a process), they are a process's first spans, and a process of many compiles (a test worker: an
# interpret-mode Pallas call closes 500 to 1300 trace spans) would else push them off the front before anyone reads them
IMPORT = "startup/import"
# a child reported by another clock read (JAX's ``time.time()``) may start a
# float's rounding before the parent that holds it
_START_SLACK_NS = 1000
# span ids: 8 random hex digits a process, then a count (``obs.trace``'s are 16 random ones)
_ID_PREFIX = os.urandom(4).hex()
_ID_COUNT = itertools.count()


class StartupSpan:
    """One closed (or still open) interval of the record."""

    __slots__ = ("name", "attrs", "span_id", "parent_id", "start_ns", "end_ns", "dur_s", "self_s",
                 "thread", "seq", "_perf0", "_annotation")

    def __init__(self, name: str, attrs: dict, start_ns: int):
        self.name, self.attrs, self.start_ns = name, attrs, start_ns
        self.span_id = f"{_ID_PREFIX}{next(_ID_COUNT):08x}"
        self.parent_id = self.end_ns = self.dur_s = self.self_s = self.seq = self._annotation = None
        self.thread = threading.get_ident()
        self._perf0 = time.perf_counter()

    def set(self, key: str, value) -> None:
        self.attrs[str(key)] = value

    def to_row(self) -> dict:
        """The span as a ``span`` event row's fields (``obs.trace.Span.to_row``'s
        names), with ``self_ms`` beside them."""
        return {
            "name": self.name, "span_id": self.span_id, "parent_id": self.parent_id,
            "start_ns": self.start_ns, "end_ns": self.end_ns, "dur_ms": round(1e3 * self.dur_s, 3),
            "self_ms": round(1e3 * self.self_s, 3), "thread": self.thread, "attrs": dict(self.attrs),
        }


class Record:
    def __init__(self):
        self.clock = (time.time_ns(), time.perf_counter())
        self.spans = collections.deque(maxlen=MAX_SPANS)  # the ring: every closed span but the imports
        self.imports = []  # the ``startup/import`` spans, never dropped
        self.closed = 0  # spans ever closed; a span's ``seq`` is the count when it closed
        self.handed = False  # whether a Tracer has taken the record (obs/startup.py)
        # set by obs/startup.py: a stamped span's profiler annotation entered and left, the counters
        self.on_open = self.on_close = self.on_closed = None
        self._local = threading.local()

    def open(self, name: str, **attrs) -> StartupSpan:
        """Open a span here and now; pair with :meth:`close`."""
        span = StartupSpan(name, attrs, time.time_ns())
        if self.on_open is not None:
            self.on_open(span)
        return span

    def close(self, span: StartupSpan) -> None:
        span.dur_s = time.perf_counter() - span._perf0
        span.end_ns = span.start_ns + int(span.dur_s * 1e9)
        if span._annotation is not None:
            self.on_close(span)
        self._closed(span)

    def add(self, name: str, start_ns: int, end_ns: int, attrs: dict) -> StartupSpan:
        """A span that somebody else timed, reported as it closes."""
        span = StartupSpan(name, attrs, int(start_ns))
        span.end_ns = max(int(end_ns), span.start_ns)
        span.dur_s = (span.end_ns - span.start_ns) / 1e9
        self._closed(span)
        return span

    def _closed(self, span: StartupSpan) -> None:
        roots = self._local.__dict__.setdefault("roots", [])
        children_s = 0.0
        while roots and roots[-1].start_ns >= span.start_ns - _START_SLACK_NS:
            child = roots.pop()
            child.parent_id = span.span_id
            children_s += child.dur_s
        span.self_s = max(span.dur_s - children_s, 0.0)
        roots.append(span)
        if len(roots) > 2 * MAX_SPANS:
            del roots[:MAX_SPANS]
        span.seq = self.closed
        (self.imports if span.name == IMPORT else self.spans).append(span)
        self.closed += 1
        if self.on_closed is not None:
            self.on_closed(span)

    @property
    def dropped(self) -> int:
        """Closed spans that fell off the ring's front."""
        return self.closed - len(self.spans) - len(self.imports)

    def since(self, mark: int) -> list:
        """The spans closed since ``mark`` was read off :attr:`closed` (those
        of them the record still holds), in the order they closed."""
        ring = list(itertools.takewhile(lambda s: s.seq >= mark, reversed(self.spans)))[::-1]
        return list(heapq.merge((s for s in self.imports if s.seq >= mark), ring, key=lambda s: s.seq))

    def held(self) -> list:
        """Every span the record holds, imports and ring, in the order they closed."""
        return self.since(0)


RECORD = Record()
