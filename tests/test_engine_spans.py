"""Program spans on the profiler's clock (ISSUE 26): the engine's step spans
and the trainer's loop spans appear in a ``jax.profiler`` capture's host plane
under their names, nested as their rows say; a row's ``start_ns`` lies on the
capture's timeline; nothing is written while an ``engine/step`` is open; an
engine without a ``Tracer`` creates no span at all; and the flash kernels of a
lowered train step are named by pass and geometry."""

import glob
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.models.text import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.obs import trace as obs_trace
from perceiver_io_tpu.obs.events import EventLog
from perceiver_io_tpu.obs.loadgen import WorkloadSpec
from perceiver_io_tpu.obs.xplane import load_capture
from perceiver_io_tpu.serving import EngineConfig, EngineFrontEnd

VOCAB = 64
ENGINE_SPANS = {
    "engine/step", "engine/fill", "engine/join", "engine/page_grant", "engine/prefill",
    "engine/decode_dispatch", "engine/token_fetch", "engine/account", "engine/retire",
}
# a shared CPU host can deschedule the process between a row's stamp and its
# annotation, a scheduler quantum at a time: of 48 captures taken beside six
# busy workers (PR 51) 46 read a worst span of 17 to 193 us and two read 4.06
# and 4.13 ms, over the 2 ms this was held to until then. A row may lie six
# such quanta off; the clocks' identity is held by the median, which no
# descheduling moves (on the chip the comparison is held to 50 us, PERF.md)
CLOCK_TOLERANCE_NS = 25_000_000
CLOCK_MEDIAN_TOLERANCE_NS = 1_000_000


@pytest.fixture(scope="module")
def model_and_params():
    config = CausalLanguageModelConfig(
        vocab_size=VOCAB, max_seq_len=24, max_latents=8, num_channels=32,
        num_heads=4, num_self_attention_layers=2, cross_attention_dropout=0.5,
    )
    model = CausalLanguageModel(config)
    ids = np.random.default_rng(0).integers(0, VOCAB, size=(1, 12))
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(ids), prefix_len=8)
    return model, params


def _engine(model, params, **kw):
    return EngineFrontEnd(
        model, params, num_latents=4,
        engine_config=EngineConfig(slots=4, page_size=8, max_ca_tokens=24, max_sa_tokens=16),
        **kw,
    )


def _specs(n=4, seed=21):
    return WorkloadSpec(seed=seed, prompt_lens=(8, 12), max_new_tokens=(3, 5)).draw(n, VOCAB)


class RecordingSink:
    """An event sink that notes, for every write, the innermost span open at
    the time (its name, or None)."""

    def __init__(self):
        self.writes = []  # (method, event kind, name of the current span)
        self.rows = []

    @staticmethod
    def _current():
        span = obs_trace.current_span()
        return None if span is None else span.name

    def emit(self, event, **fields):
        self.writes.append(("emit", event, self._current()))
        self.rows.append({"event": event, **fields})

    def emit_rows(self, event, rows):
        self.writes.append(("emit_rows", event, self._current()))
        self.rows.extend({"event": event, **r} for r in rows)


@pytest.fixture(scope="module")
def traced_pump(model_and_params, tmp_path_factory):
    """One toy engine pumped under a CPU profiler capture, with a Tracer:
    its span rows and the capture."""
    model, params = model_and_params
    out = tmp_path_factory.mktemp("engine_capture")
    warm = _engine(model, params)  # compile outside the capture: it stays small
    warm.run_closed(_specs(), concurrency=4)
    fe = _engine(model, params, events=EventLog(str(out / "run"), main_process=True))
    jax.profiler.start_trace(str(out / "trace"))
    try:
        recs = fe.run_closed(_specs(), concurrency=4)
    finally:
        jax.profiler.stop_trace()
    assert all(r.outcome == "ok" for r in recs)
    from perceiver_io_tpu.obs.events import read_event_file

    rows = [r for r in read_event_file(str(out / "run" / "events.jsonl"))]
    spans = [r for r in rows if r["event"] == "span"]
    pb = glob.glob(str(out / "trace" / "**" / "*.xplane.pb"), recursive=True)
    assert pb, "the profiler wrote no capture"
    return {"rows": rows, "spans": spans, "capture": load_capture(pb[-1]), "engine": fe}


def test_capture_holds_engine_step_with_its_children_inside(traced_pump):
    """Every nested engine span is a host-plane annotation under its name,
    inside its parent's annotation, and its row names the right parent."""
    spans, capture = traced_pump["spans"], traced_pump["capture"]
    ann = {a[3]: a for a in capture["annotations"]}
    by_id = {r["span_id"]: r for r in spans}
    nested = [r for r in spans if r["name"].startswith("engine/")]
    assert ENGINE_SPANS <= {r["name"] for r in nested}
    allowed_parents = {
        "engine/step": {None}, "engine/flush": {None},
        "engine/fill": {"engine/step"}, "engine/join": {"engine/fill"},
        "engine/resume": {"engine/fill"}, "engine/page_grant": {"engine/join", "engine/resume", "engine/fill"},
        "engine/prefill": {"engine/join", "engine/resume"},
        "engine/decode_dispatch": {"engine/step"}, "engine/token_fetch": {"engine/step"},
        "engine/account": {"engine/step"}, "engine/retire": {"engine/account", "engine/step"},
    }
    for r in nested:
        assert r["span_id"] in ann, f"{r['name']} is not in the capture's host plane"
        name, start, dur, _ = ann[r["span_id"]]
        assert name == r["name"]
        parent = by_id.get(r["parent_id"])
        assert (parent["name"] if parent else None) in allowed_parents[r["name"]], r
        if parent is not None:
            _, p_start, p_dur, _ = ann[parent["span_id"]]
            assert p_start <= start and start + dur <= p_start + p_dur, (r["name"], parent["name"])
    steps = [r for r in nested if r["name"] == "engine/step"]
    assert [r["attrs"]["step"] for r in steps] == sorted(r["attrs"]["step"] for r in steps)
    assert sum(r["attrs"]["tokens"] for r in steps) + len(_specs()) == sum(
        len(t) for t in traced_pump["engine"].served_tokens.values()
    ), "tokens over the steps plus one prefill token a request are all the served tokens"


def test_span_rows_lie_on_the_captures_clock(traced_pump):
    """``start_ns - profile_start_time`` is the annotation's start: any span
    row, the detached ``request`` rows too, can be laid on the capture."""
    spans, capture = traced_pump["spans"], traced_pump["capture"]
    ann = {a[3]: a for a in capture["annotations"]}
    t0 = capture["profile_start_ns"]
    off = []
    for r in spans:
        if r["span_id"] not in ann:
            continue
        _, start, dur, _ = ann[r["span_id"]]
        assert abs((r["start_ns"] - t0) - start) < CLOCK_TOLERANCE_NS, r["name"]
        assert abs((r["end_ns"] - t0) - (start + dur)) < CLOCK_TOLERANCE_NS, r["name"]
        off += [abs((r["start_ns"] - t0) - start), abs((r["end_ns"] - t0) - (start + dur))]
    assert len(off) >= 40
    assert float(np.median(off)) < CLOCK_MEDIAN_TOLERANCE_NS
    detached = [r for r in spans if r.get("detached")]
    assert detached and all(r["name"] == "request" and r["span_id"] not in ann for r in detached)
    assert all(0 <= r["start_ns"] - t0 <= capture["length_ns"] for r in detached)


def test_request_rows_follow_their_span_rows(traced_pump):
    """The order of writing keeps a request's span row before its request
    row, with no flush between them."""
    seen = set()
    n = 0
    for r in traced_pump["rows"]:
        if r["event"] == "span":
            seen.add(r["span_id"])
        elif r["event"] == "request":
            assert r["span_id"] in seen
            n += 1
    assert n == len(_specs())


def test_breakdown_accounts_for_the_step(traced_pump):
    """``host_device_breakdown`` over the rows: every name with its count and
    self time; a CPU capture has no device plane, so no idle is put down."""
    bd = obs_trace.host_device_breakdown(traced_pump["spans"], traced_pump["capture"])
    s = bd["spans"]
    assert s["engine/step"]["count"] == s["engine/fill"]["count"] >= s["engine/decode_dispatch"]["count"] > 0
    assert s["engine/join"]["count"] == s["engine/prefill"]["count"] == len(_specs())
    assert 0 <= s["engine/step"]["self_ms"] < s["engine/step"]["total_ms"]
    assert "device" not in bd and all("idle_ms" not in v for v in s.values())


def test_engine_without_events_creates_no_span(model_and_params, monkeypatch):
    model, params = model_and_params
    made = []
    real_init = obs_trace.Span.__init__

    def counting_init(self, *args, **kwargs):
        made.append(kwargs.get("name"))
        real_init(self, *args, **kwargs)

    monkeypatch.setattr(obs_trace.Span, "__init__", counting_init)
    monkeypatch.setattr(obs_trace, "_enter_annotation", lambda span: made.append(span))
    fe = _engine(model, params, events=None)
    for spec in _specs():
        fe.submit(spec)
    assert fe.pump() == len(_specs())
    assert fe._tracer is None and made == []


def test_nothing_is_written_while_an_engine_step_is_open(model_and_params):
    model, params = model_and_params
    sink = RecordingSink()
    fe = _engine(model, params, events=sink)
    recs = fe.run_closed(_specs(6, seed=5), concurrency=3)
    assert all(r.outcome == "ok" for r in recs)
    assert any(kind == "request" for _, kind, _ in sink.writes)
    assert any(method == "emit_rows" for method, _, _ in sink.writes)
    # engine/step is a top-level span: a write under it, or under anything
    # inside it, would see a current span other than engine/flush
    for method, kind, current in sink.writes:
        assert current in (None, "engine/flush"), (method, kind, current)
    # every write after a step happened under engine/flush, at most once a step
    flushes = [r for r in sink.rows if r["event"] == "span" and r["name"] == "engine/flush"]
    steps = [r for r in sink.rows if r["event"] == "span" and r["name"] == "engine/step"]
    assert 0 < len(flushes) <= len(steps)
    reg = fe.registry
    assert reg.counter("engine_steps_total").value == fe._engine_steps == sum(
        1 for r in steps if r["attrs"]["tokens"] or r["attrs"]["active"]
    )
    assert reg.counter("engine_prefills_total").value == 6
    assert reg.counter("engine_prefill_tokens_total").value == sum(s.prompt_len for s in _specs(6, seed=5))


def test_tracer_keeps_the_order_of_writing():
    sink = RecordingSink()
    tr = obs_trace.Tracer(sink)
    with tr.span("a"):
        pass
    assert sink.writes == []  # a span row waits
    tr.emit("request", outcome="ok")  # an event row takes the queue with it
    assert [(m, k) for m, k, _ in sink.writes] == [("emit_rows", "span"), ("emit", "request")]
    assert sink.writes[0][2] is None
    with tr.hold():
        with tr.span("b") as b:
            tr.emit("compile", wall_s=0.0)
        tr.emit("request", outcome="ok")
        assert len(sink.writes) == 2 and tr.flush_due()
    tr.flush()
    assert [k for _, k, _ in sink.writes[2:]] == ["compile", "span", "request"]
    assert sink.rows[2]["span_id"] == b.span_id  # stamped when emitted, not when written
    assert not tr.flush_due()


def test_trainer_step_span_holds_its_phases(tmp_path):
    from perceiver_io_tpu.obs.events import read_event_file
    from perceiver_io_tpu.training import MetricsLogger, TrainState, Trainer, TrainerConfig, make_optimizer

    def loss_fn(params, batch, rng):
        loss = jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)
        return loss, {"loss": loss}

    state = TrainState.create(None, {"w": jnp.zeros((3,))}, make_optimizer(1e-2), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batches = [{"x": rng.normal(size=(4, 3)).astype(np.float32), "y": np.ones(4, np.float32)} for _ in range(4)]
    trainer = Trainer(
        loss_fn, logger=MetricsLogger(str(tmp_path)),
        config=TrainerConfig(max_steps=4, log_interval=2, prefetch_batches=0),
    )
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        trainer.fit(state, iter(batches))
    finally:
        jax.profiler.stop_trace()
    trainer.close()
    spans = [r for r in read_event_file(str(tmp_path / "events.jsonl")) if r["event"] == "span"]
    by_id = {r["span_id"]: r for r in spans}
    steps = [r for r in spans if r["name"] == "step"]
    assert len(steps) == 4
    for name, count in (("train/input_wait", 4), ("train/dispatch", 4), ("train/metrics_fetch", 2)):
        rows = [r for r in spans if r["name"] == name]
        assert len(rows) == count, name
        for r in rows:
            parent = by_id[r["parent_id"]]
            assert parent["name"] == "step"
            assert parent["start_ns"] <= r["start_ns"] and r["end_ns"] <= parent["end_ns"]
    assert all({"input_wait_ms", "dispatch_ms"} <= set(r["attrs"]) for r in steps)
    pb = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"), recursive=True)
    names = {a[0] for a in load_capture(pb[-1])["annotations"]}
    assert {"fit", "step", "train/input_wait", "train/dispatch", "train/metrics_fetch"} <= names


def _pallas_names(jaxpr, out, stacks=None):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
            if stacks is not None:
                stacks.append(str(eqn.source_info.name_stack))
        for v in eqn.params.values():
            for x in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(x, "jaxpr", x)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _pallas_names(inner, out, stacks)
    return out


@pytest.mark.parametrize("latents,seq,keep", [(128, 384, 128), (512, 1024, 256)], ids=["one-tile", "re-blocked"])
def test_train_step_names_flash_kernels_by_pass_and_geometry(latents, seq, keep):
    """On CPU the kernels run interpreted, but the ``pallas_call`` equations
    of the traced step keep the names the chip's trace prints: forward and
    backward (the latents are one q block, so one kernel and no dq / dkv
    pair), with the cross-attention's kept-prefix-plus-latents KV length and
    the self-attention's. The names hold the call's lengths, not its blocks:
    a self-attention whose tile plan skips the scores the mask hides (512
    latents) is named like one that runs one whole tile (128)."""
    from perceiver_io_tpu.training import clm_loss_fn

    fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")
    config = CausalLanguageModelConfig(
        vocab_size=VOCAB, max_seq_len=seq, max_latents=latents, num_channels=64,
        num_heads=2, num_self_attention_layers=2, cross_attention_dropout=0.5,
    )
    model = CausalLanguageModel(config)
    ids = jnp.zeros((1, seq), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids, prefix_len=seq - latents))
    batch = {
        "input_ids": jnp.zeros((2, seq), jnp.int32), "labels": jnp.zeros((2, seq), jnp.int32), "pad_mask": None,
        "prefix_keep_idx": jnp.tile(jnp.arange(keep, dtype=jnp.int32), (2, 1)),
    }
    loss = clm_loss_fn(model.apply, max_latents=latents)
    with fa.default_flash(True):
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: loss(p, batch, jax.random.PRNGKey(1))[0]))(params)
    stacks = []
    names = _pallas_names(jaxpr.jaxpr, [], stacks)
    # a kernel's name is its innermost scope (XLA names the custom call after it): the scope vocabulary's layers
    # (obs/xplane.py: ``rotary`` beside the kernels, the blocks around them) are opened outside it
    assert len(stacks) == len(names) and all(stack.split("/")[-1] == name for stack, name in zip(stacks, names))
    assert all("rotary" not in stack.split("/") for stack in stacks)
    cross, self_ = f"q{latents}_kv{keep + latents}", f"q{latents}_kv{latents}"
    want = {f"flash_{p}_{g}": n for g, n in ((cross, 1), (self_, 2)) for p in ("fwd", "bwd")}
    got = {n: names.count(n) for n in set(names)}
    assert got == want, got
    # the traced calls left their plans where the ``compile`` event row reads them
    rows = [r for r in fa.tile_plans() if r["causal"]]
    shares = {r["geometry"]: r["run_share"] for r in rows}
    assert {r["backward"] for r in rows if r["geometry"] in (cross, self_)} == {"one"}
    assert shares[self_] == (1.0 if latents == 128 else fa.tile_plan(latents, latents, True).run_share)
    assert fa.tile_plan(512, 512, True).run_share <= 0.75


def test_train_step_names_rotary_kernels_under_the_rotary_layer():
    """At a packed width on the 128 lanes the rotation of queries and keys is
    the kernel ``rotary_<fwd|bwd>_n<rows>_c<channels>`` (ops/rotary.py), called
    inside the ``rotary`` scope its call sites open: ``obs.xplane.op_scope``
    gives its ``op_name`` the layer ``rotary`` in ``forward`` and in
    ``backward``, and the name holds no ``flash`` (the benchmark's readers
    select the flash kernels by that word)."""
    from perceiver_io_tpu.obs.xplane import op_scope
    from perceiver_io_tpu.training import clm_loss_fn

    fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")
    latents, seq, keep = 128, 384, 128
    config = CausalLanguageModelConfig(
        vocab_size=VOCAB, max_seq_len=seq, max_latents=latents, num_channels=128,
        num_heads=2, num_self_attention_layers=2, cross_attention_dropout=0.5,
    )
    model = CausalLanguageModel(config)
    ids = jnp.zeros((1, seq), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids, prefix_len=seq - latents))
    batch = {
        "input_ids": jnp.zeros((2, seq), jnp.int32), "labels": jnp.zeros((2, seq), jnp.int32), "pad_mask": None,
        "prefix_keep_idx": jnp.tile(jnp.arange(keep, dtype=jnp.int32), (2, 1)),
    }
    loss = clm_loss_fn(model.apply, max_latents=latents)
    with fa.default_flash(True):
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: loss(p, batch, jax.random.PRNGKey(1))[0]))(params)
    # (kernel name, op_name as the compiled program carries it: the name stacks of the enclosing equations joined)
    calls = []

    def walk(jaxpr, outer):
        for eqn in jaxpr.eqns:
            stack = "/".join(p for p in (outer, str(eqn.source_info.name_stack)) if p)
            if eqn.primitive.name == "pallas_call":
                calls.append((eqn.params["name"], f"jit(train_step)/{stack}/pallas_call"))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, stack)

    walk(jaxpr.jaxpr, "")
    rotary = [(name, op_scope(op_name)) for name, op_name in calls if name.startswith("rotary")]
    assert all("flash" in name or name.startswith(("rotary", "embed_pos_grad")) for name, _ in calls)
    # the cross-attention's keys (kept prefix + latents) and queries, the one rotary latent layer's queries and keys
    want = {f"rotary_{p}_n{n}_c128": count for p in ("fwd", "bwd") for n, count in ((keep + latents, 1), (latents, 3))}
    assert {n: [name for name, _ in rotary].count(n) for n in want} == want and len(rotary) == 8
    for name, scope in rotary:
        assert scope.layer == "rotary" and scope.path.endswith(f"rotary/{name}"), (name, scope)
        assert scope.phase == ("forward" if "_fwd_" in name else "backward"), (name, scope)
