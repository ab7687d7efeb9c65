"""Probeline (obs/probes.py, ISSUE 9): probes-off must reproduce today's
graphs bitwise; probes-on must return per-scope stats as aux outputs of the
SAME compiled program (no callbacks, zero collectives, live — never DCE'd),
the trainer must ring-buffer snapshots and dump a span-attributed
blast-radius report on sentinel trips, and the decode pair must carry the
KV-occupancy/logit-entropy health gauges through the instrumented wrapper."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.models.text import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.obs import probes as P
from perceiver_io_tpu.training import (
    MetricsLogger,
    TrainState,
    Trainer,
    TrainerConfig,
    clm_loss_fn,
    make_optimizer,
)
from perceiver_io_tpu.training.loop import make_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_clm():
    config = CausalLanguageModelConfig(
        vocab_size=50, max_seq_len=24, max_latents=8, num_channels=32,
        num_heads=4, num_self_attention_layers=2, cross_attention_dropout=0.5,
    )
    return CausalLanguageModel(config), config


def clm_batch(config, batch=4, seed=0):
    rng = np.random.default_rng(seed)
    t = rng.integers(0, config.vocab_size, size=(batch, config.max_seq_len + 1))
    return {
        "labels": jnp.asarray(t[:, 1:]),
        "input_ids": jnp.asarray(t[:, :-1]),
        "pad_mask": None,
    }


def clm_state(model, config, batch):
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"], prefix_len=16)
    return TrainState.create(model.apply, params, make_optimizer(1e-3), jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def setup():
    model, config = tiny_clm()
    batch = clm_batch(config)
    state = clm_state(model, config, batch)
    loss_fn = clm_loss_fn(model.apply, max_latents=config.max_latents)
    return model, config, batch, state, loss_fn


# ---------------------------------------------------------------------------
# probes-off bitwise identity
# ---------------------------------------------------------------------------


def test_probes_off_train_step_is_bitwise_todays_graph(setup):
    """probes=None must trace the EXACT graph the pre-probe step traced —
    including after a collecting() context opened and closed (no leak)."""
    _, _, batch, state, loss_fn = setup
    baseline = str(jax.make_jaxpr(make_train_step(loss_fn, jit=False))(state, batch))
    assert "probes" not in baseline  # no probe scope, no aux stats

    with P.collecting(P.ProbeConfig()):
        pass  # a closed collector must leave nothing behind
    after = str(jax.make_jaxpr(make_train_step(loss_fn, jit=False, probes=None))(state, batch))
    assert after == baseline


def test_probe_is_identity_and_noop_without_collector(setup):
    x = jnp.arange(6.0).reshape(2, 3)
    assert P.probe("anything", x) is x  # no collector: the very same array

    def f(x):
        return P.probe("scope", x) * 2.0

    plain = str(jax.make_jaxpr(f)(x))
    with P.collecting(P.ProbeConfig(scopes=("nomatch*",))):
        unmatched = str(jax.make_jaxpr(f)(x))
    assert unmatched == plain  # scope filter: non-matching sites trace nothing

    def g(x):  # the real usage shape: stats returned as aux outputs
        with P.collecting(P.ProbeConfig()) as col:
            y = P.probe("scope", x) * 2.0
        return y, col.stats

    probed = str(jax.make_jaxpr(g)(x))
    assert probed != plain and "reduce_max" in probed  # absmax reduction traced


def test_probes_off_decode_fns_bitwise(setup):
    from perceiver_io_tpu.generation import GenerationConfig, make_decode_fns

    model, config, _, state, _ = setup
    gcfg = GenerationConfig(max_new_tokens=4)
    prompt = jnp.asarray(np.random.default_rng(0).integers(0, 50, size=(2, 12)))
    pre_off, step_off = make_decode_fns(model, 4, gcfg)
    _, st = pre_off(state.params, prompt)
    assert "probe" not in st
    jx = str(jax.make_jaxpr(step_off)(st))
    assert "probes" not in jx

    pre_on, step_on = make_decode_fns(model, 4, gcfg, probes=True)
    _, st_on = pre_on(state.params, prompt)
    assert set(st_on["probe"]) == {"logit_entropy", "kv_cache_frac", "nonfinite_logit_frac"}


# ---------------------------------------------------------------------------
# stats semantics
# ---------------------------------------------------------------------------


def test_activation_stats_values():
    x = jnp.asarray([[3.0, -4.0], [0.0, 0.0]])
    st = {k: float(v) for k, v in P.activation_stats(x).items()}
    assert st["rms"] == pytest.approx(math.sqrt(25 / 4))
    assert st["absmax"] == 4.0
    assert st["nonfinite_frac"] == 0.0
    assert st["zero_frac"] == 0.5
    bad = {k: float(v) for k, v in P.activation_stats(jnp.asarray([1.0, np.nan])).items()}
    assert bad["nonfinite_frac"] == 0.5 and math.isnan(bad["rms"])


def test_probed_train_step_returns_topologically_ordered_scopes(setup):
    _, _, batch, state, loss_fn = setup
    step = jax.jit(make_train_step(loss_fn, jit=False, probes=P.ProbeConfig()))
    _, metrics = step(state, batch)
    snap = metrics["probes"]
    host = P.snapshot_to_host(snap)
    keys = sorted(host)
    names = [P.scope_of(k) for k in keys]
    # forward activations first (embed before logits), then grad buckets,
    # then update ratios — the topological order blast attribution walks
    assert names.index("perceiver_ar.embed") < names.index("logits")
    grads = [n for n in names if n.startswith("grad.")]
    updates = [n for n in names if n.startswith("update.")]
    acts = [n for n in names if not n.startswith(("grad.", "update."))]
    assert acts and grads and updates
    assert max(keys.index(k) for k, n in zip(keys, names) if n in acts) < min(
        keys.index(k) for k, n in zip(keys, names) if n in grads
    )
    assert max(keys.index(k) for k, n in zip(keys, names) if n in grads) < min(
        keys.index(k) for k, n in zip(keys, names) if n in updates
    )
    # per-layer grad buckets resolved to depth 4
    assert any("self_attention.layer_0" in n for n in grads)
    for st in host.values():
        for v in st.values():
            assert math.isfinite(v)


def test_probed_step_no_callbacks_and_outputs_live(setup):
    """The two structural guarantees: no host callback primitive in the
    probed program (callback-in-jit stays clean), and every probe op is
    LIVE in the dataflow graph — the aux-output plumbing actually carries
    the stats out (not silently DCE'd)."""
    _, _, batch, state, loss_fn = setup
    step = make_train_step(loss_fn, jit=False, probes=P.ProbeConfig())
    jx = str(jax.make_jaxpr(step)(state, batch))
    assert "callback" not in jx
    report = P.probes_live_report(step, (state, batch))
    assert report["probe_scopes"] > 0 and report["probe_ops"] > 0
    assert report["dead_scopes"] == [], report["dead_scopes"]


def test_probed_contract_zero_added_collectives():
    """The committed train_probed contract vs train_flat: probes add ZERO
    collectives, identical captured-const bytes, and the probed program is
    graphcheck-clean against its own committed fingerprint (the acceptance
    pin for 'bounded const/temp bytes, no new communication')."""
    with open(os.path.join(REPO, "contracts", "train_flat.json")) as f:
        flat = json.load(f)["fingerprint"]
    with open(os.path.join(REPO, "contracts", "train_probed.json")) as f:
        probed = json.load(f)["fingerprint"]
    assert probed["collectives"] == flat["collectives"]
    assert probed["captured_const_bytes"] == flat["captured_const_bytes"]
    # the train step donates on every backend (PR 23), so the contracts
    # record real alias counts: the update-ratio stats' read of the old
    # params must not cost the step its donation
    assert probed["donation_aliases"] == flat["donation_aliases"]
    # bounded temp growth: the stats buffers must stay a small fraction of
    # the step's working set (5% gate at micro geometry)
    assert probed["memory"]["gate_bytes"] <= flat["memory"]["gate_bytes"] * 1.10


@pytest.mark.slow
def test_train_probed_program_matches_committed_contract():
    from perceiver_io_tpu.analysis.fingerprint import check_contracts

    res = check_contracts(os.path.join(REPO, "contracts"), programs=("train_probed",))
    assert res["status"] == "passed", res["programs"]


# ---------------------------------------------------------------------------
# blast-radius attribution
# ---------------------------------------------------------------------------


def test_blast_report_names_first_nonfinite_scope_of_earliest_snapshot():
    clean = {
        P.ordered_key(0, "embed"): {"rms": jnp.float32(1.0), "nonfinite_frac": jnp.float32(0.0)},
        P.ordered_key(1, "logits"): {"rms": jnp.float32(2.0), "nonfinite_frac": jnp.float32(0.0)},
    }
    poisoned = {
        P.ordered_key(0, "embed"): {"rms": jnp.float32(1.0), "nonfinite_frac": jnp.float32(0.0)},
        P.ordered_key(1, "logits"): {
            "rms": jnp.float32(float("nan")), "nonfinite_frac": jnp.float32(0.25)
        },
    }
    worse = {
        P.ordered_key(0, "embed"): {
            "rms": jnp.float32(float("nan")), "nonfinite_frac": jnp.float32(1.0)
        },
        P.ordered_key(1, "logits"): {
            "rms": jnp.float32(float("nan")), "nonfinite_frac": jnp.float32(1.0)
        },
    }
    assert P.blast_report([(jnp.int32(3), clean)]) is None
    rep = P.blast_report(
        [(jnp.int32(3), clean), (jnp.int32(4), poisoned), (jnp.int32(5), worse)]
    )
    # EARLIEST non-finite snapshot (step 4), FIRST affected scope in order
    assert rep["step"] == 4 and rep["scope"] == "logits"
    assert rep["affected"] == ["logits"] and rep["n_affected"] == 1


def test_trainer_probed_fit_emits_probe_rows_and_blast(tmp_path):
    """End-to-end mini chaos: a probed+sentineled fit over a stream with one
    NaN batch must (a) emit `probe` rows at log boundaries that
    validate_events accepts, (b) emit a `probe.blast` naming the first
    non-finite scope, span-attributed to the offending step."""
    from perceiver_io_tpu.obs.events import validate_events

    def loss_fn(params, batch, rng):
        pred = P.probe("toy.pred", batch["x"] @ params["w"])
        loss = jnp.mean((pred - batch["y"]) ** 2)
        return loss, {"loss": loss}

    rng = np.random.default_rng(0)

    def batches(n, poison_at=()):
        out = []
        for i in range(1, n + 1):
            x = rng.normal(size=(4, 8)).astype(np.float32)
            if i in poison_at:
                x = x.copy()
                x[0, 0] = np.nan
            out.append({"x": x, "y": x @ np.ones((8, 2), np.float32)})
        return out

    state = TrainState.create(
        None, {"w": jnp.zeros((8, 2))}, make_optimizer(1e-2), jax.random.PRNGKey(0)
    )
    logger = MetricsLogger(str(tmp_path), use_tensorboard=False)
    trainer = Trainer(
        loss_fn,
        logger=logger,
        config=TrainerConfig(
            max_steps=8, log_interval=2, prefetch_batches=0, graphlint=False,
            graphcheck=False, sentinel=True, probes=True,
        ),
    )
    trainer.fit(state, iter(batches(8, poison_at=(3, 6))))
    trainer.close()
    logger.close()

    rows = [json.loads(l) for l in open(tmp_path / "events.jsonl") if l.strip()]
    probe_rows = [r for r in rows if r["event"] == "probe"]
    assert probe_rows, "no probe rows at log boundaries"
    for r in probe_rows:
        scopes = {P.scope_of(k) for k in r["scopes"]}
        assert "toy.pred" in scopes and any(s.startswith("grad.") for s in scopes)
    blasts = [r for r in rows if r["event"] == "probe.blast"]
    assert blasts and blasts[0]["scope"] == "toy.pred"
    assert blasts[0]["trigger"] == "skip" and blasts[0]["step"] == 3
    # a SECOND independent incident attributes to its OWN step — the ring
    # was cleared when the first blast was emitted, so no stale snapshot
    # can re-attribute a later trip to step 3
    assert len(blasts) == 2 and blasts[1]["step"] == 6, blasts
    span_ids = {r.get("span_id") for r in rows if r["event"] == "span"}
    assert blasts[0].get("span_id") in span_ids, "blast not span-attributed"
    # the planted scope's stats on record: nonfinite_frac > 0 (strict-JSON
    # nulls stand in for the NaN rms)
    assert blasts[0]["stats"]["nonfinite_frac"] > 0
    problems = validate_events(str(tmp_path))
    assert problems == [], problems


def test_blast_fires_on_host_detected_divergence_too(tmp_path):
    """With in_graph_skip=False (the overlap-step situation) a non-finite
    loss goes straight to the rollback rung — escalating to halt when no
    checkpoint exists — and the blast must still name the planted scope."""
    from perceiver_io_tpu.training.faults import DivergenceHalt, SentinelConfig

    def loss_fn(params, batch, rng):
        pred = P.probe("toy.pred", batch["x"] @ params["w"])
        loss = jnp.mean((pred - batch["y"]) ** 2)
        return loss, {"loss": loss}

    rng = np.random.default_rng(0)

    def batches(n, poison):
        for i in range(1, n + 1):
            x = rng.normal(size=(4, 8)).astype(np.float32)
            if i == poison:
                x = x.copy()
                x[0, 0] = np.nan
            yield {"x": x, "y": (x @ np.ones((8, 2))).astype(np.float32)}

    state = TrainState.create(
        None, {"w": jnp.zeros((8, 2))}, make_optimizer(1e-2), jax.random.PRNGKey(0)
    )
    logger = MetricsLogger(str(tmp_path), use_tensorboard=False)
    trainer = Trainer(
        loss_fn,
        logger=logger,
        config=TrainerConfig(
            max_steps=6, log_interval=1, prefetch_batches=0, graphlint=False,
            graphcheck=False, sentinel=SentinelConfig(in_graph_skip=False),
            probes=P.ProbeConfig(ring=3),
        ),
    )
    with pytest.raises(DivergenceHalt):
        trainer.fit(state, batches(6, poison=3))
    trainer.close()
    logger.close()
    rows = [json.loads(l) for l in open(tmp_path / "events.jsonl") if l.strip()]
    blasts = [r for r in rows if r["event"] == "probe.blast"]
    assert blasts and blasts[0]["scope"] == "toy.pred" and blasts[0]["trigger"] == "halt"


def test_trainer_probes_off_adds_nothing(tmp_path):
    """A probes-off fit writes no probe/probe.blast rows (schema unchanged)."""
    model, config = tiny_clm()
    batch = clm_batch(config)
    state = clm_state(model, config, batch)
    logger = MetricsLogger(str(tmp_path), use_tensorboard=False)
    trainer = Trainer(
        clm_loss_fn(model.apply, max_latents=config.max_latents),
        logger=logger,
        config=TrainerConfig(
            max_steps=2, log_interval=1, prefetch_batches=0, graphlint=False,
            graphcheck=False,
        ),
    )
    trainer.fit(state, iter([batch] * 2), model_config=config)
    trainer.close()
    logger.close()
    kinds = {json.loads(l)["event"] for l in open(tmp_path / "events.jsonl") if l.strip()}
    assert "probe" not in kinds and "probe.blast" not in kinds


def test_flagship_build_targets_rejects_probes_with_mesh():
    """probes= on a sharded flagship build must raise, not silently lint
    the unprobed graph."""
    from perceiver_io_tpu.analysis.flagship import build_targets

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "fsdp"))
    with pytest.raises(ValueError, match="unsharded"):
        build_targets("micro", targets=("train",), mesh=mesh, probes=P.ProbeConfig())


# ---------------------------------------------------------------------------
# decode health gauges
# ---------------------------------------------------------------------------


def test_decode_health_values_are_sane(setup):
    from perceiver_io_tpu.generation import GenerationConfig, make_decode_fns

    model, config, _, state, _ = setup
    prompt = jnp.asarray(np.random.default_rng(1).integers(0, 50, size=(2, 12)))
    prefill, step = make_decode_fns(
        model, 4, GenerationConfig(max_new_tokens=4), probes=True
    )
    _, st = prefill(state.params, prompt)
    h0 = jax.device_get(st["probe"])
    # fresh init: logits near-uniform, entropy near ln(V); occupancy = the
    # prompt's fill over prompt+slack capacity
    assert 0.5 * math.log(50) < float(h0["logit_entropy"]) <= math.log(50) + 1e-3
    assert float(h0["kv_cache_frac"]) == pytest.approx(12 / 16)
    assert float(h0["nonfinite_logit_frac"]) == 0.0
    st, _ = step(st)
    h1 = jax.device_get(st["probe"])
    assert float(h1["kv_cache_frac"]) == pytest.approx(13 / 16)


def test_instrumented_generate_publishes_decode_health(tmp_path, setup):
    from perceiver_io_tpu.generation import GenerationConfig, make_instrumented_generate_fn
    from perceiver_io_tpu.obs.events import EventLog

    model, config, _, state, _ = setup
    events = EventLog(str(tmp_path), main_process=True)
    fn = make_instrumented_generate_fn(
        model, num_latents=4, config=GenerationConfig(max_new_tokens=5),
        events=events, probes=True, snapshot_interval_s=0.0,
    )
    prompt = jnp.asarray(np.random.default_rng(2).integers(0, 50, size=(2, 10)))
    _, stats = fn(state.params, prompt)
    rows = [json.loads(l) for l in open(tmp_path / "events.jsonl") if l.strip()]
    req = [r for r in rows if r["event"] == "request"][-1]
    assert 0 < req["kv_cache_frac"] <= 1.0
    assert req["logit_entropy_mean"] > 0 and req["logit_entropy_last"] > 0
    assert req["nonfinite_logit_frac"] == 0.0
    snap = fn.registry.snapshot()
    assert snap["gauges"]["generate_kv_cache_frac"] == pytest.approx(req["kv_cache_frac"])
    assert snap["histograms"]["generate_logit_entropy"]["n"] == 5
