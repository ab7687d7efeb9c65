"""Set-up read from inside the program: the program's start-up record,
clipped to set-up and summed by self time.

The program keeps closed spans of what it does before its first step
(``perceiver_io_tpu/obs/startup.py``: ``startup/import`` a package,
``startup/state_create``, and ``startup/trace`` / ``startup/lower`` /
``startup/compile`` a function from JAX's own events), each with a
``start_ns``/``end_ns`` on ``time.time_ns()`` and the ``parent_id`` of the span
that holds it. Set-up runs from ``PROCESS_START`` of ``benchmarks/run.py`` to
where ``Context.window`` takes ``t0``, which on ``perf_counter`` is
``PROCESS_START + setup_s``; the record's one reading of both clocks places
that on the spans' clock. A span is cut to those two ends, so what the
reference, the checks and ``lib/scopes.py``'s rebuild compile after the window
is in no number here. A span's **self time** is its (cut) length less its
children's, so every instant of one thread is counted once, in the innermost
span that holds it, and the parts add up to ``setup_s``:

    setup_import_s        self time of ``startup/import`` outside ``state_create``
    setup_state_s         ``startup/state_create`` with all that nests under it
    setup_trace_lower_s   self time of ``startup/trace`` and ``startup/lower`` outside it
    setup_compile_s       self time of ``startup/compile`` outside it
    setup_unattributed_s  ``setup_s`` less those four

``setup_cache_misses`` counts the ``startup/compile`` spans of set-up whose
``cache`` is ``"miss"``. JAX's listeners are process-wide: a program the
benchmark's own code builds in set-up (the weight draw's ``jit``) is counted
with the program's, and the printed tables name every ``fn``. Where the program
holds no record (a parent of PR 51) every reader gets ``None`` and says why."""

from __future__ import annotations

import sys

IMPORT, STATE_CREATE = "startup/import", "startup/state_create"
TRACE, LOWER, COMPILE = "startup/trace", "startup/lower", "startup/compile"
SECONDS = ("import_s", "state_s", "trace_lower_s", "compile_s", "unattributed_s")
# the identity with ``setup_s`` holds to this, and no part may be under zero by more
TOLERANCE_S = 1e-3
TOP = 12  # rows a printed table shows before it sums the rest


def process_start() -> float:
    """``PROCESS_START`` of the ``benchmarks/run.py`` that runs the cell: of
    ``__main__`` when it is the command, else of the imported module."""
    main = sys.modules.get("__main__")
    if hasattr(main, "PROCESS_START") and hasattr(main, "run_cell"):
        return main.PROCESS_START
    return sys.modules["benchmarks.run"].PROCESS_START


def program_record():
    """``(rows, clock)`` of the program's record, or None where it has none.
    Raises ``ValueError`` where the record has dropped its oldest spans."""
    try:
        from perceiver_io_tpu.obs import startup
    except ImportError:
        return None
    if startup.dropped():
        raise ValueError(f"the record dropped its {startup.dropped()} oldest spans: set-up's first are among them")
    return startup.rows(), startup.clock()


def setup_interval(clock, start_perf: float, setup_s: float) -> tuple:
    """Set-up's two ends on the spans' clock, in ns: ``clock`` is the
    record's ``(time.time_ns(), time.perf_counter())`` pair."""
    ns0, perf0 = clock
    lo = ns0 + int(round((start_perf - perf0) * 1e9))
    return lo, lo + int(round(setup_s * 1e9))


def _import_label(attrs: dict) -> str:
    return f"{attrs.get('package')}.{attrs['module']}" if attrs.get("module") else str(attrs.get("package"))


def summarize(rows, lo_ns: int, hi_ns: int, setup_s: float) -> dict:
    """The parts of set-up from the record's ``rows``, cut to
    ``[lo_ns, hi_ns]``. Raises ``ValueError`` where the parts do not add up
    to ``setup_s`` (spans of several threads that overlap)."""
    cut = {}
    for r in rows:
        a, b = max(r["start_ns"], lo_ns), min(r["end_ns"], hi_ns)
        if b > a:
            cut[r["span_id"]] = (r, (b - a) / 1e9)
    own = {sid: s for sid, (_, s) in cut.items()}
    for sid, (r, s) in cut.items():
        if r["parent_id"] in own:
            own[r["parent_id"]] -= s

    under_state = {}

    def in_state(sid) -> bool:
        if sid not in cut:
            return False
        if sid not in under_state:
            r = cut[sid][0]
            under_state[sid] = r["name"] == STATE_CREATE or in_state(r["parent_id"])
        return under_state[sid]

    out = {k: 0.0 for k in SECONDS}
    out.update(cache_misses=0, packages={}, traced={}, lowered={}, compiled={}, missed=[],
               programs={"hit": 0, "miss": 0, "off": 0}, retrieval_s=0.0, state_spans=0, spans=len(cut))
    for sid, (r, _) in cut.items():
        name, attrs, self_s = r["name"], r.get("attrs") or {}, max(own[sid], 0.0)
        if name == COMPILE:
            out["programs"][attrs.get("cache", "off")] += 1
            out["retrieval_s"] += attrs.get("retrieval_s", 0.0)
            if attrs.get("cache") == "miss":
                out["cache_misses"] += 1
                out["missed"].append(str(attrs.get("fn")))
        if in_state(sid):
            out["state_s"] += self_s
            out["state_spans"] += 1
            continue
        key, table, label = {
            IMPORT: ("import_s", "packages", _import_label(attrs)),
            TRACE: ("trace_lower_s", "traced", str(attrs.get("fn"))),
            LOWER: ("trace_lower_s", "lowered", str(attrs.get("fn"))),
            COMPILE: ("compile_s", "compiled", f"{attrs.get('fn')} [{attrs.get('cache')}]"),
        }.get(name, (None, None, None))
        if key is not None:
            out[key] += self_s
            out[table][label] = out[table].get(label, 0.0) + self_s
    out["unattributed_s"] = setup_s - (out["import_s"] + out["state_s"] + out["trace_lower_s"] + out["compile_s"])
    out["setup_s"] = setup_s
    if out["unattributed_s"] < -TOLERANCE_S or abs(sum(out[k] for k in SECONDS) - setup_s) > TOLERANCE_S:
        raise ValueError(f"the spans of set-up hold {setup_s - out['unattributed_s']:.3f} s and set-up lasted "
                         f"{setup_s:.3f} s: spans of several threads overlap")
    return out


def read(run):
    """The summary of this run's set-up, or None with a printed reason."""
    setup_s = run["end_to_end"]["setup_s"]
    try:
        record = program_record()
        if record is None:
            print("startup: not read: the program holds no start-up record (perceiver_io_tpu.obs.startup)", flush=True)
            return None
        rows, clock = record
        return summarize(rows, *setup_interval(clock, process_start(), setup_s), setup_s)
    except ValueError as e:
        print(f"startup: not read: {e}", flush=True)
        return None


def _largest(table: dict) -> str:
    items = sorted(table.items(), key=lambda kv: -kv[1])
    shown = ", ".join(f"{k} {v:.3f}" for k, v in items[:TOP])
    rest = sum(v for _, v in items[TOP:])
    return shown + (f", {len(items) - TOP} more {rest:.3f}" if len(items) > TOP else "")


def metric(run, key: str):
    """What a reader under ``layers/`` returns: one part of the summary, its
    table printed beside it."""
    s = read(run)
    if s is None:
        return None
    if key == "import_s":
        print(f"startup: import {s['import_s']:.3f} s, self time by package: {_largest(s['packages'])}", flush=True)
    elif key == "state_s":
        print(f"startup: state_create {s['state_s']:.3f} s over {s['state_spans']} spans, its own programs among them",
              flush=True)
    elif key == "trace_lower_s":
        print(f"startup: trace {sum(s['traced'].values()):.3f} s by fn: {_largest(s['traced'])}", flush=True)
        print(f"startup: lower {sum(s['lowered'].values()):.3f} s by fn: {_largest(s['lowered'])}", flush=True)
    elif key == "compile_s":
        p = s["programs"]
        print(f"startup: compile {s['compile_s']:.3f} s outside state_create; programs of set-up: {p['hit']} hits "
              f"({s['retrieval_s']:.3f} s of cache reads), {p['miss']} misses, {p['off']} without the cache; "
              f"by fn: {_largest(s['compiled'])}", flush=True)
    elif key == "cache_misses":
        missed = s["missed"]
        more = f", {len(missed) - TOP} more" if len(missed) > TOP else ""
        print(f"startup: {s['cache_misses']} programs missed the cache: {', '.join(missed[:TOP]) or 'none'}{more}",
              flush=True)
    elif key == "unattributed_s":
        print("startup: " + " + ".join(f"{k} {s[k]:.3f}" for k in SECONDS) + f" = setup_s {s['setup_s']:.3f}"
              f" ({s['spans']} spans of the record lie in set-up)", flush=True)
    return s[key]
