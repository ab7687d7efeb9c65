"""Pageline engine tests (ISSUE 13): batched paged decode is TOKEN-EXACT vs
the sequential contiguous path (greedy + temperature sampling, pinned rng
chains, batch sizes 1 / 4 / ragged mixed-length), the continuous-batching
front end keeps clean books AND clean page books under cancel/kill/shed, the
``decode_paged`` graphcheck program contains no kv-axis concatenate and only
budgeted page-table gathers, and the cross-program-consistency rule holds
paged appends to their declared discipline."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.generation import GenerationConfig, make_decode_fns
from perceiver_io_tpu.models.text import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.obs.loadgen import WorkloadSpec
from perceiver_io_tpu.serving import EngineConfig, EngineFrontEnd

NUM_LATENTS = 4
VOCAB = 64


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


def _engine(model, params, base_config=None, slots=4, **kw):
    return EngineFrontEnd(
        model, params, num_latents=NUM_LATENTS, base_config=base_config,
        engine_config=EngineConfig(slots=slots, page_size=8,
                                   max_ca_tokens=24, max_sa_tokens=16),
        **kw,
    )


def _sequential_tokens(model, params, spec, base_config=None):
    """The reference stream: the spec's request decoded alone through the
    contiguous host-driven pair, with its pinned rng chain."""
    cfg = dataclasses.replace(
        base_config or GenerationConfig(), max_new_tokens=spec.max_new_tokens
    )
    prefill, step = make_decode_fns(model, NUM_LATENTS, cfg)
    tok, state = prefill(
        params, jnp.asarray(spec.input_ids), None, jax.random.PRNGKey(spec.rng_seed)
    )
    out = [int(tok[0])]
    for _ in range(spec.max_new_tokens - 1):
        state, tok = step(state)
        out.append(int(tok[0]))
    return out


# ------------------------------------------------------------ token exactness


@pytest.mark.parametrize(
    "sampling",
    ["greedy", "temperature"],
)
@pytest.mark.parametrize(
    "shape",
    [
        "batch1",  # one request alone in the batch
        "batch4",  # four same-geometry requests decoding together
        "ragged",  # mixed prompt lengths AND budgets joining/retiring live
    ],
)
def test_engine_token_exact_vs_sequential(model_and_params, sampling, shape):
    """The ISSUE 13 acceptance pin: every request served by the batched
    paged engine produces EXACTLY the token stream the sequential
    contiguous path produces for the same prompt and rng seed — greedy and
    temperature sampling, across batch shapes including ragged
    mixed-length batches where slots join and retire mid-flight."""
    model, params = model_and_params
    base = (
        GenerationConfig()
        if sampling == "greedy"
        else GenerationConfig(do_sample=True, temperature=0.8, top_k=10)
    )
    if shape == "batch1":
        wspec = WorkloadSpec(seed=11, prompt_lens=(10,), max_new_tokens=(5,))
        specs = wspec.draw(1, VOCAB)
    elif shape == "batch4":
        wspec = WorkloadSpec(seed=12, prompt_lens=(10,), max_new_tokens=(5,))
        specs = wspec.draw(4, VOCAB)
    else:
        wspec = WorkloadSpec(seed=13, prompt_lens=(8, 12), max_new_tokens=(4, 9))
        specs = wspec.draw(8, VOCAB)
    fe = _engine(model, params, base_config=base)
    recs = fe.run_closed(specs, concurrency=max(4, len(specs)))
    assert all(r.outcome == "ok" for r in recs), [vars(r) for r in recs]
    assert fe.books()["balanced"] and fe.audit() == []
    for spec in specs:
        want = _sequential_tokens(model, params, spec, base_config=base)
        got = fe.served_tokens[spec.index]
        assert got == want, (
            f"request {spec.index} (prompt {spec.prompt_len}, "
            f"budget {spec.max_new_tokens}, {sampling}, {shape}): "
            f"engine {got} != sequential {want}"
        )


def test_engine_eos_retires_slot_early(model_and_params):
    """EOS terminates a slot (the whole point of continuous batching —
    finished requests stop occupying the batch) and the stream matches the
    sequential path up to the EOS token."""
    model, params = model_and_params
    wspec = WorkloadSpec(seed=5, prompt_lens=(10,), max_new_tokens=(8,))
    specs = wspec.draw(4, VOCAB)
    # pick an eos id that actually fires MID-STREAM for request 0 under
    # greedy: the first token of its eos-free stream that differs from the
    # prefill sample (a first-token eos would just pad the whole stream)
    seq0 = _sequential_tokens(model, params, specs[0])
    eos = next(t for t in seq0[1:] if t != seq0[0])
    base = GenerationConfig(eos_token_id=int(eos))
    fe = _engine(model, params, base_config=base)
    recs = fe.run_closed(specs, concurrency=4)
    assert fe.books()["balanced"] and all(r.outcome == "ok" for r in recs)
    hit = [r for r in recs if r.tokens_out < r.max_new_tokens]
    assert hit, "no request terminated at EOS — the pin is vacuous"
    for spec in specs:
        want = _sequential_tokens(model, params, spec, base_config=base)
        got = fe.served_tokens[spec.index]
        assert got == want[: len(got)]
        if len(got) < spec.max_new_tokens:
            assert got[-1] == int(eos)


# --------------------------------------------------------------- clean books


def test_engine_pages_exhausted_shed_and_books(model_and_params, tmp_path):
    """A request whose KV footprint can never fit sheds kv_pages_exhausted
    (a first-class PR-12 shed with its own request row); everything else is
    served; books AND page books balance."""
    from perceiver_io_tpu.obs.events import EventLog, validate_events
    from perceiver_io_tpu.obs.loadgen import RequestSpec
    from perceiver_io_tpu.serving import SHED_REASONS

    assert "kv_pages_exhausted" in SHED_REASONS
    model, params = model_and_params
    events = EventLog(str(tmp_path), main_process=True)
    fe = _engine(model, params, events=events)
    specs = list(WorkloadSpec(seed=2, prompt_lens=(10,), max_new_tokens=(4,)).draw(3, VOCAB))
    rng = np.random.default_rng(9)
    specs.append(RequestSpec(index=3, prompt_len=20, max_new_tokens=16,
                             input_ids=rng.integers(0, VOCAB, size=(1, 20)),
                             rng_seed=1))
    recs = fe.run_closed(specs, concurrency=4)
    books = fe.books()
    assert books["ok"] == 3 and books["shed"] == 1 and books["balanced"], books
    shed = next(r for r in recs if r.outcome == "shed")
    assert shed.shed_reason == "kv_pages_exhausted"
    assert fe.ca_alloc.pages_used == 0 and fe.ca_alloc.audit() == []
    assert fe.sa_alloc.pages_used == 0 and fe.sa_alloc.audit() == []
    problems = validate_events(str(tmp_path))
    assert problems == [], problems


def test_engine_sa_footprint_over_slot_capacity_sheds(model_and_params):
    """Admission and allocation agree on the SA footprint (review finding):
    a request whose LATENT stream (num_latents + budget) exceeds the
    per-slot SA capacity sheds kv_pages_exhausted at submit — it must never
    reach _try_join, whose uncapped grant would outgrow the page table."""
    from perceiver_io_tpu.obs.loadgen import RequestSpec

    model, params = model_and_params
    fe = _engine(model, params)  # max_sa_tokens=16, num_latents=4
    rng = np.random.default_rng(8)
    # ca fits (6+16=22 <= 24) but sa does not (4+16=20 > 16)
    spec = RequestSpec(index=0, prompt_len=6, max_new_tokens=16,
                       input_ids=rng.integers(0, VOCAB, size=(1, 6)), rng_seed=1)
    rec = fe.submit(spec)
    assert rec.outcome == "shed" and rec.shed_reason == "kv_pages_exhausted", vars(rec)
    assert fe.books()["balanced"]
    assert fe.ca_alloc.pages_used == 0 and fe.sa_alloc.pages_used == 0


def test_engine_kill_at_first_token_books_one_token(model_and_params, tmp_path):
    """A kill raised by the token-0 seam (at join) retires the slot BEFORE
    the next batched step (review finding): tokens_out stays 1 — exactly
    what the sequential path books for the same kill — and no post-kill
    token reaches the served stream."""
    from perceiver_io_tpu.obs.events import EventLog
    from perceiver_io_tpu.serving import FaultInjector

    model, params = model_and_params
    events = EventLog(str(tmp_path), main_process=True)
    injector = FaultInjector().kill_at(1, 0)
    fe = _engine(model, params, events=events, injector=injector)
    specs = WorkloadSpec(seed=6, prompt_lens=(10,), max_new_tokens=(6,)).draw(3, VOCAB)
    recs = fe.run_closed(specs, concurrency=3)
    books = fe.books()
    assert books["error"] == 1 and books["ok"] == 2 and books["balanced"], books
    dead = next(r for r in recs if r.outcome == "error")
    assert dead.index == 1 and dead.tokens_out == 1, vars(dead)
    assert len(fe.served_tokens[1]) == 1
    assert fe.ca_alloc.pages_used == 0 and fe.sa_alloc.pages_used == 0


def test_engine_cancel_mid_decode_frees_pages(model_and_params, tmp_path):
    """Cancel a request INSIDE a live batch: its slot retires ``cancelled``
    at the next token boundary, its pages return to the free list, the rest
    of the batch finishes, books balance."""
    from perceiver_io_tpu.obs.events import EventLog

    model, params = model_and_params
    events = EventLog(str(tmp_path), main_process=True)
    fe = _engine(model, params, events=events)
    specs = WorkloadSpec(seed=3, prompt_lens=(10,), max_new_tokens=(8,)).draw(4, VOCAB)
    out = [fe.submit(s) for s in specs]
    fe._fill_slots()
    assert len(fe._active_ids()) == 4
    used_before = fe.ca_alloc.pages_used
    assert used_before > 0
    fe._engine_step()  # tokens flowing
    assert fe.cancel(2)
    fe.pump()
    books = fe.books()
    assert books["cancelled"] == 1 and books["ok"] == 3 and books["balanced"], books
    dead = out[2]
    assert dead.outcome == "cancelled" and 0 < dead.tokens_out < dead.max_new_tokens
    assert fe.ca_alloc.pages_used == 0 and fe.sa_alloc.pages_used == 0
    assert fe.ca_alloc.audit() == [] and fe.sa_alloc.audit() == []


def test_engine_events_carry_batch_size_and_gauges(model_and_params, tmp_path):
    """The obs satellite: engine request rows carry the OPTIONAL
    ``batch_size_at_decode`` field (stream still validates, no forward-compat
    warnings), and the engine gauges land in the shared registry."""
    from perceiver_io_tpu.obs.events import EventLog, merged_events, validate_events

    model, params = model_and_params
    events = EventLog(str(tmp_path), main_process=True)
    fe = _engine(model, params, events=events)
    specs = WorkloadSpec(seed=4, prompt_lens=(10,), max_new_tokens=(6,)).draw(6, VOCAB)
    fe.run_closed(specs, concurrency=6)
    warnings_out = []
    assert validate_events(str(tmp_path), warnings_out=warnings_out) == []
    assert warnings_out == []
    rows = [e for e in merged_events(str(tmp_path)) if e.get("event") == "request"]
    assert len(rows) == 6
    assert all(isinstance(e.get("batch_size_at_decode"), (int, float)) for e in rows)
    assert all(e.get("queue_wait_s") is not None for e in rows)
    assert all(e.get("tpot_hist") is not None for e in rows)
    reg = fe.registry
    assert reg.gauge("engine_batch_fill_frac").value >= 0.0
    assert 0.0 < fe.mean_batch_fill <= 1.0
    snap = reg.snapshot()
    assert "engine_kv_pages_used" in snap["gauges"]
    assert "engine_batch_fill_frac" in snap["gauges"]


# ----------------------------------------------------- decode_paged contract


def _decode_paged_target():
    from perceiver_io_tpu.analysis.flagship import build_targets

    return build_targets("micro", targets=("decode_paged",))["decode_paged"]


def test_decode_paged_graph_no_kv_concat_and_budgeted_gathers(model_and_params):
    """The ISSUE 13 graph pin (a jaxpr walk): the
    batched paged decode step's traced graph contains NO concatenate over a
    kv-capacity axis, and exactly the BUDGETED page-table gathers — the
    k/v gather-view pair per cache plus one page-id lookup per append (the
    embedding/sampling gathers live outside the paged scopes)."""
    from perceiver_io_tpu.analysis import graph as G

    t = _decode_paged_target()
    closed = G.trace(t.fn, *t.args)
    caches = t.args[1]["cache"]
    n_caches = len(caches)
    forbidden_axes = {c.capacity for c in caches}
    paged_gathers = 0
    for op in G.iter_ops(closed):
        if op.primitive == "concatenate" and op.outvars:
            axis = int(op.params.get("dimension", -1))
            shape = op.outvars[0].shape
            assert not (
                0 <= axis < len(shape) and shape[axis] in forbidden_axes
            ), f"kv-axis concatenate crept into decode_paged: {shape} axis {axis} @ {op.scope}"
        if op.primitive == "gather" and "paged_kv" in op.scope:
            paged_gathers += 1
    # per cache: k view + v view (paged_kv_view) + the append's page-id
    # table lookup (paged_kv_append) = 3; float pools carry no scale planes
    assert paged_gathers == 3 * n_caches, (
        f"{paged_gathers} page-table gathers for {n_caches} caches — "
        f"budget is exactly {3 * n_caches}; an unbudgeted gather regressed "
        "the paged read path"
    )


def test_decode_paged_contract_committed_and_green():
    """The 7th flagship program is under contract and the live graph
    matches it (the same check ``tasks.py perf`` runs)."""
    import os

    from perceiver_io_tpu.analysis.fingerprint import PROGRAMS, check_contracts

    assert "decode_paged" in PROGRAMS
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    result = check_contracts(os.path.join(repo, "contracts"), programs=("decode_paged",))
    assert result["status"] == "passed", result["programs"]["decode_paged"]


# ------------------------------------------- cross-program-consistency (paged)


def test_cross_program_rule_accepts_declared_paged_companion():
    """The rule extension (ISSUE 13 satellite): the decode_paged target's
    DECLARED page-table-indexed appends pass; stripping the declaration
    turns the same scatter appends into violations — the paged layout is a
    declared companion, not an allowlist hole."""
    import dataclasses as dc

    from perceiver_io_tpu import analysis

    t = _decode_paged_target()
    ok = analysis.check(
        t.fn, t.args, rules=("cross-program-consistency",), policy=t.policy
    )
    assert ok.clean, ok.format()

    undeclared = dc.replace(t.policy, paged_cache_scopes=())
    bad = analysis.check(
        t.fn, t.args, rules=("cross-program-consistency",), policy=undeclared
    )
    assert not bad.clean
    assert any("declared paged companion" in v.message for v in bad.violations), (
        bad.format()
    )


def test_cache_sites_survey_sees_paged_appends():
    """The dataflow survey half: scatter appends under ``paged_kv_append``
    are inventoried with page-table index provenance (a gather in the write
    index's chain) and a dynamic origin."""
    from perceiver_io_tpu.analysis import dataflow as D

    t = _decode_paged_target()
    df = D.analyze(t.fn, *t.args)
    sites = D.cache_sites(df)
    paged = [s for s in sites if s.primitive == "scatter"]
    caches = t.args[1]["cache"]
    assert len(paged) == 2 * len(caches)  # one k + one v scatter per cache
    for s in paged:
        assert "paged_kv_append" in s.scope
        assert s.index_via_gather, s
        assert s.index_origin != "static", s
