"""Start-up read from inside the program (ISSUE 51): imports, the optimizer
state and JAX's trace / lower / compile events as spans of one process-wide
record on the profiler's clock, with self times that count nothing twice;
the counters; the hand-over to the first ``Tracer`` with a sink; and the
``compile`` row's split. Tiny programs only: nothing here compiles a cell."""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from perceiver_io_tpu import _startup
from perceiver_io_tpu.obs import EventLog, RecompileTracker, Tracer, default_registry, merged_events, startup, validate_events
from perceiver_io_tpu.obs.trace import host_device_breakdown

JAX_SPANS = (startup.TRACE, startup.LOWER, startup.COMPILE)


def fresh(scale: float):
    """A jitted function of fresh identity and a text of its own (JAX's trace
    cache and the persistent cache have not seen it)."""

    def tiny(x):
        return jnp.tanh(x * scale).sum()

    return jax.jit(tiny)


def spans_of(rows, fn_part: str):
    return {r["name"]: r for r in rows if fn_part in str(r["attrs"].get("fn"))}


@pytest.fixture
def persistent_cache(tmp_path):
    """A persistent compilation cache of this test's own that keeps every
    program, however quick its compile."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache")
    was = {n: getattr(jax.config, n) for n in names}
    for n, v in zip(names, (str(tmp_path / "cache"), 0.0, 0, True)):
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    yield
    for n, v in was.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


@pytest.fixture
def unhanded():
    """The record as a process's first ``Tracer`` finds it: not yet taken."""
    was = _startup.RECORD.handed
    _startup.RECORD.handed = False
    yield
    _startup.RECORD.handed = was


def test_a_tiny_jit_arrives_as_three_spans_on_the_profilers_clock():
    mark, before_ns = startup.mark(), time.time_ns()
    fresh(1.25)(jnp.ones(3))
    after_ns = time.time_ns()
    got = spans_of(startup.rows(mark), "tiny")
    assert set(got) == set(JAX_SPANS)
    assert got[startup.TRACE]["attrs"]["fn"] == "tiny" and got[startup.COMPILE]["attrs"]["fn"] == "jit(tiny)"
    for name in JAX_SPANS:
        r = got[name]
        assert before_ns - 1000 <= r["start_ns"] <= r["end_ns"] <= after_ns + 1000, name
        assert r["dur_ms"] == pytest.approx((r["end_ns"] - r["start_ns"]) / 1e6, abs=1e-3)
    order = [got[n]["start_ns"] for n in JAX_SPANS]
    assert order == sorted(order), "traced, then lowered, then compiled"
    assert got[startup.COMPILE]["attrs"]["cache"] in ("hit", "miss", "off")


def test_a_jit_inside_a_jit_is_not_counted_twice():
    @jax.jit
    def inner_one(x):
        return jnp.sin(x) * 1.5

    @jax.jit
    def outer_one(x):
        return inner_one(x) + inner_one(x * 2.0).sum()

    registry = default_registry()
    five, seven = jnp.ones(5), jnp.ones(7)  # made here: an eager op is a program of its own
    before, mark = registry.counter("startup_trace_seconds").value, startup.mark()
    outer_one.lower(five)
    traced = [r for r in startup.rows(mark) if r["name"] == startup.TRACE]
    assert [r["attrs"]["fn"] for r in traced] == ["outer_one"], "an inner jit's trace is no span of its own"
    outer = traced[0]
    assert outer["self_ms"] == pytest.approx(outer["dur_ms"], abs=5e-3), "and its time stays in the outer trace's"
    assert registry.counter("startup_trace_seconds").value - before == pytest.approx(outer["dur_ms"] / 1e3, abs=1e-5)
    assert startup.compile_split(mark)["trace_s"] == pytest.approx(outer["dur_ms"] / 1e3, abs=1e-5)
    mark = startup.mark()
    inner_one.lower(seven)
    assert [r["attrs"]["fn"] for r in startup.rows(mark) if r["name"] == startup.TRACE] == ["inner_one"], (
        "the same function traced on its own is a span: the count of open traces went back to zero")


def test_a_program_compiled_in_the_middle_of_a_trace_is_the_compiles_time():
    def eager_inside(x):
        with jax.ensure_compile_time_eval():  # concrete values: eager programs, built while the trace is open
            scale = float(jnp.arange(11.0).sum())
        return x * scale

    three = jnp.ones(3)
    mark = startup.mark()
    jax.jit(eager_inside).lower(three)
    rows = startup.rows(mark)
    outer = next(r for r in rows if r["name"] == startup.TRACE and r["attrs"]["fn"] == "eager_inside")
    held = [r for r in rows if r["parent_id"] == outer["span_id"]]
    assert {r["name"] for r in held} >= {startup.COMPILE}, "the eager programs nest under the trace, by containment"
    assert outer["self_ms"] == pytest.approx(outer["dur_ms"] - sum(r["dur_ms"] for r in held), abs=5e-3)
    ids = {r["span_id"] for r in rows}
    top = [r for r in rows if r["parent_id"] not in ids]
    assert sum(r["self_ms"] for r in rows) == pytest.approx(sum(r["dur_ms"] for r in top), abs=0.05), (
        "the self times of a tree add up to its roots' durations")


def test_the_second_build_of_a_text_is_a_hit_with_its_retrieval(persistent_cache):
    scale = 2.0 + (time.time_ns() % 10**6) / 1e6  # a text no cache has seen
    mark = startup.mark()
    fresh(scale).lower(jnp.ones(3)).compile()
    first = spans_of(startup.rows(mark), "tiny")[startup.COMPILE]
    assert first["attrs"]["cache"] == "miss" and "retrieval_s" not in first["attrs"]
    hits = default_registry().counter("startup_programs_total").labels(cache="hit").value
    mark = startup.mark()
    fresh(scale).lower(jnp.ones(3)).compile()
    second = spans_of(startup.rows(mark), "tiny")[startup.COMPILE]
    assert second["attrs"]["cache"] == "hit" and second["attrs"]["retrieval_s"] > 0
    assert second["attrs"]["retrieval_s"] <= second["dur_ms"] / 1e3 + 1e-6
    assert default_registry().counter("startup_programs_total").labels(cache="hit").value == hits + 1
    assert startup.compile_split(mark)["cache"] == "hit"


def test_a_compile_with_the_cache_off_says_so():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        mark = startup.mark()
        fresh(3.5).lower(jnp.ones(3)).compile()
        assert spans_of(startup.rows(mark), "tiny")[startup.COMPILE]["attrs"]["cache"] == "off"
        assert startup.compile_split(mark)["cache"] == "off"
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_import_spans_nest_and_their_self_times_add_up_to_the_roots():
    imports = [r for r in startup.rows() if r["name"] == startup.IMPORT]
    by_package = {startup.import_label(r["attrs"]): r for r in imports}
    root, core, obs = (by_package[p] for p in ("perceiver_io_tpu", "perceiver_io_tpu.core", "perceiver_io_tpu.obs"))
    assert root["parent_id"] is None or root["parent_id"] not in {r["span_id"] for r in imports}
    assert core["parent_id"] == root["span_id"], "the root's __init__ imports core"
    assert obs["start_ns"] >= core["start_ns"] and obs["end_ns"] <= core["end_ns"], "core pulls obs in (obs.probes)"

    def tree(r):
        return [r] + [x for c in imports if c["parent_id"] == r["span_id"] for x in tree(c)]

    under_root = tree(root)
    assert len(under_root) >= 4
    assert sum(r["self_ms"] for r in under_root) == pytest.approx(root["dur_ms"], abs=0.05)
    assert all(r["self_ms"] <= r["dur_ms"] + 1e-3 for r in imports)


def test_the_three_named_modules_of_training_are_bracketed():
    import perceiver_io_tpu.training  # noqa: F401

    labels = {startup.import_label(r["attrs"]): r for r in startup.rows() if r["name"] == startup.IMPORT}
    package = labels["perceiver_io_tpu.training"]
    for module in ("checkpoint", "faults", "trainer"):
        r = labels[f"perceiver_io_tpu.training.{module}"]
        assert r["attrs"] == {"package": "perceiver_io_tpu.training", "module": module}
        assert r["parent_id"] == package["span_id"]
    counted = default_registry().counter("startup_import_seconds").labels(package="perceiver_io_tpu.training.checkpoint")
    assert counted.value == pytest.approx(labels["perceiver_io_tpu.training.checkpoint"]["self_ms"] / 1e3, abs=1e-3)


def test_every_package_stamps_its_import():
    import importlib

    root = os.path.dirname(_startup.__file__)
    packages = ["perceiver_io_tpu"] + [f"perceiver_io_tpu.{d}" for d in sorted(os.listdir(root))
                                       if os.path.isfile(os.path.join(root, d, "__init__.py"))]
    assert len(packages) == 13
    for p in packages:
        importlib.import_module(p)
    stamped = {startup.import_label(r["attrs"]) for r in startup.rows() if r["name"] == startup.IMPORT}
    assert set(packages) <= stamped


def test_the_record_module_imports_nothing_but_the_standard_library():
    import subprocess

    code = ("import sys, importlib.util as u; s = u.spec_from_file_location('_startup', sys.argv[1]); "
            "m = u.module_from_spec(s); s.loader.exec_module(m); "
            "print(sorted(k for k in sys.modules if k.split('.')[0] in ('jax', 'numpy', 'flax', 'perceiver_io_tpu')))")
    out = subprocess.run([sys.executable, "-c", code, _startup.__file__], capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "[]"


def test_state_create_is_a_span_that_holds_its_programs():
    import optax

    from perceiver_io_tpu.training import TrainState

    params = {"w": jnp.ones((4, 3)), "b": jnp.zeros((3,), jnp.bfloat16)}
    mark = startup.mark()
    state = TrainState.create(lambda *a: None, params, optax.adam(1e-3), jax.random.PRNGKey(0))
    assert int(state.step) == 0
    rows = startup.rows(mark)
    created = [r for r in rows if r["name"] == startup.STATE_CREATE]
    assert len(created) == 1 and created[0]["attrs"] == {"leaves": 2, "param_bytes": 4 * 3 * 4 + 3 * 2}
    inside = [r for r in rows if r["parent_id"] == created[0]["span_id"]]
    assert all(created[0]["start_ns"] - 1000 <= r["start_ns"] and r["end_ns"] <= created[0]["end_ns"] + 1000 for r in inside)
    assert created[0]["self_ms"] <= created[0]["dur_ms"]


def test_the_counters_count_self_time_by_function_and_cache():
    registry = default_registry()
    names = ("startup_trace_seconds", "startup_lower_seconds", "startup_compile_seconds", "startup_programs_total")
    before = {n: registry.counter(n).value for n in names}
    mark = startup.mark()
    fresh(4.5)(jnp.ones(2))
    rows = startup.rows(mark)
    for n, span in zip(names, JAX_SPANS):
        gained = registry.counter(n).value - before[n]
        assert gained == pytest.approx(sum(r["self_ms"] for r in rows if r["name"] == span) / 1e3, abs=1e-4), n
    assert registry.counter("startup_programs_total").value - before["startup_programs_total"] == sum(
        r["name"] == startup.COMPILE for r in rows)
    text = registry.to_prometheus()
    assert 'startup_trace_seconds{fn="tiny"}' in text and 'startup_import_seconds{package="perceiver_io_tpu.core"}' in text
    assert 'fn="jit(tiny)"' in text and "startup_programs_total{cache=" in text


def test_the_first_tracer_with_a_sink_takes_the_record_once(tmp_path, unhanded):
    fresh(5.5)(jnp.ones(2))
    quiet = Tracer(None)
    with quiet.span("no sink"):
        pass
    assert not _startup.RECORD.handed, "a Tracer without a sink takes nothing"
    tracer = Tracer(EventLog(str(tmp_path), main_process=True))
    with tracer.span("fit", ambient=True) as fit:
        with tracer.span("step"):
            pass
    tracer.flush()
    assert _startup.RECORD.handed
    assert validate_events(str(tmp_path)) == []
    spans = [r for r in merged_events(str(tmp_path)) if r["event"] == "span"]
    started = [r for r in spans if r["name"].startswith("startup/")]
    ids = {r["span_id"] for r in spans}
    assert all(r["parent_id"] in ids for r in started), "every row hangs under a row of the stream"
    names = {r["name"] for r in started}
    assert {startup.IMPORT, startup.TRACE, startup.LOWER, startup.COMPILE} <= names
    roots = [r for r in started if r["attrs"].get("package") == "perceiver_io_tpu"]
    assert roots and roots[0]["parent_id"] == fit.span_id, "a span without a parent goes under fit"
    core = next(r for r in started if r["attrs"].get("package") == "perceiver_io_tpu.core")
    assert core["parent_id"] == roots[0]["span_id"]
    assert started.index(core) < [r["name"] for r in spans].index("step"), "start-up rows come first"
    record = startup.rows()
    assert sum(r["attrs"]["self_ms"] for r in started) == pytest.approx(
        sum(r["self_ms"] for r in record if r["end_ns"] <= fit.start_ns), rel=1e-6, abs=0.5)
    # a span that began before ``fit`` opened takes none of its self time
    table = host_device_breakdown(spans)["spans"]
    assert table["fit"]["self_ms"] == pytest.approx(table["fit"]["total_ms"] - table["step"]["total_ms"], abs=1e-2)
    again = Tracer(EventLog(str(tmp_path / "second"), main_process=True))
    with again.span("fit"):
        pass
    again.flush()
    assert [r["name"] for r in merged_events(str(tmp_path / "second")) if r["event"] == "span"] == ["fit"]


def test_obs_report_prints_the_startup_table(tmp_path, unhanded):
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    tracer = Tracer(EventLog(str(tmp_path), main_process=True))
    with tracer.span("fit", ambient=True):
        pass
    tracer.flush()
    text = obs_report.render(str(tmp_path))
    assert "== start-up (" in text and "perceiver_io_tpu.core" in text and "startup/compile" in text
    table = startup.startup_table(r for r in merged_events(str(tmp_path)) if r["event"] == "span")
    assert [a["self_ms"] for a in table] == sorted((a["self_ms"] for a in table), reverse=True)
    assert all(a["self_ms"] <= a["total_ms"] + 1e-6 for a in table if a["name"] != startup.TRACE)


def test_the_compile_row_splits_its_wall_time(tmp_path):
    log = EventLog(str(tmp_path), main_process=True)
    tracked = RecompileTracker(events=log).wrap(fresh(6.5), "tiny_fn")
    tracked(jnp.ones(3))
    tracked(jnp.ones(3))
    tracked(jnp.ones(4))
    rows = [r for r in merged_events(str(tmp_path)) if r["event"] == "compile"]
    assert len(rows) == 2, "one row a shape"
    for r in rows:
        assert r["cache"] in ("hit", "miss", "off")
        assert all(r[k] > 0 for k in ("trace_s", "lower_s", "backend_s"))
        assert r["trace_s"] + r["lower_s"] + r["backend_s"] <= r["wall_s"] + 1e-6
    assert validate_events(str(tmp_path)) == []


def test_a_call_that_builds_nothing_has_no_cache_verdict():
    mark = startup.mark()
    assert startup.compile_split(mark) == {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0, "cache": None}


def test_the_record_keeps_its_newest_spans_and_says_what_it_dropped():
    record = _startup.Record()
    record.spans = type(record.spans)(maxlen=8)
    for i in range(20):
        record.add("startup/trace", 1000 * i, 1000 * i + 500, {"fn": f"f{i}"})
    assert (record.closed, len(record.spans), record.dropped) == (20, 8, 12)
    assert [s.attrs["fn"] for s in record.since(17)] == ["f17", "f18", "f19"]
    assert len(record.since(0)) == 8, "what fell off the front cannot be read back"
    assert record.clock[0] == pytest.approx(time.time_ns(), abs=5e9)


def test_spans_of_two_threads_do_not_adopt_each_other():
    import threading

    record = _startup.Record()
    outer = record.open("startup/state_create")
    worker = threading.Thread(target=lambda: record.add("startup/compile", time.time_ns(), time.time_ns() + 10, {"fn": "w"}))
    worker.start()
    worker.join()
    mine = record.add("startup/compile", time.time_ns(), time.time_ns() + 10, {"fn": "m"})
    record.close(outer)
    by_fn = {s.attrs.get("fn"): s for s in record.spans}
    assert mine.parent_id == outer.span_id and by_fn["w"].parent_id is None
    assert outer.self_s == pytest.approx(outer.dur_s - mine.dur_s)


def test_chip_smoke_reads_its_programs_from_the_registry():
    sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
    try:
        import chip_smoke
    finally:
        sys.path.pop(0)
    assert not hasattr(chip_smoke, "Programs") and not hasattr(chip_smoke, "PROGRAMS")
    n0, s0, h0, m0 = chip_smoke.programs()
    fresh(7.5)(jnp.ones(2))
    n1, s1, h1, m1 = chip_smoke.programs()
    assert n1 == n0 + 1 and s1 > s0 and (h1 - h0) + (m1 - m0) <= 1


def test_a_stamped_span_is_an_annotation_while_a_profiler_session_runs(tmp_path):
    import glob

    from perceiver_io_tpu.obs.xplane import load_capture

    jax.profiler.start_trace(str(tmp_path))
    try:
        with startup.span("startup/state_create", leaves=0, param_bytes=0) as s:
            jnp.ones(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    capture = load_capture(glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[-1])
    ann = {a[3]: a for a in capture["annotations"]}
    assert s.span_id in ann and ann[s.span_id][0] == "startup/state_create"
    assert json.dumps(s.to_row())  # a row is plain JSON
