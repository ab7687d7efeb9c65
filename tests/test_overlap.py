"""Overlap-scheduled distributed train step (parallel/overlap.py): loss/grad
equivalence against the GSPMD path and the unsharded step on the 8-virtual-
device CPU mesh, bucketing boundary cases, and the graphlint surface of the
scheduling claim (`collective-overlap` must PASS on the overlap step and
FAIL on a deliberately dependency-serialized schedule — the rule has to
discriminate, not rubber-stamp)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from perceiver_io_tpu import analysis
from perceiver_io_tpu.analysis.rules import LintPolicy
from perceiver_io_tpu.parallel import make_mesh, shard_batch
from perceiver_io_tpu.parallel.overlap import (
    OverlapConfig,
    _leaf_plan,
    _plan_buckets,
    expected_collectives,
    make_overlap_train_step,
    parse_mesh_spec,
)
from perceiver_io_tpu.training import TrainState, make_optimizer
from perceiver_io_tpu.training.loop import make_train_step, shard_train_state
from jax import shard_map


# --------------------------------------------------------------- toy harness
# A parameter tree covering every bucketing boundary case, with an analytic
# uniform-weighting loss so gradient sync is verifiable to the digit:
#   big      — alone >= bucket_bytes: its own single-leaf bucket (fast path)
#   exact    — exactly bucket_bytes: closes its bucket at the boundary
#   small_*  — coalesce into one multi-leaf bucket
#   odd      — no dim divisible by fsdp: replicated fallback
#   tiny     — below min_weight_size: replicated
BUCKET_BYTES = 64 * 64 * 4  # 16 KiB


def toy_params():
    rng = np.random.default_rng(0)

    def t(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return {
        "big": t(128, 64),      # 32 KiB > bucket -> own bucket
        "exact": t(64, 64),     # exactly bucket_bytes
        "small_a": t(16, 8),
        "small_b": t(8, 16),
        "odd": t(7, 3),         # 7 and 3 not divisible by fsdp -> replicated
        "tiny": t(4,),
    }


def toy_loss(params, batch, rng):
    # per-sample weight = x_i.sum(); loss = mean_i(w_i) * sum(all params)
    w = jnp.mean(jnp.sum(batch["x"], axis=-1))
    total = sum(jnp.sum(v) for v in jax.tree.leaves(params))
    loss = w * total
    return loss, {"loss": loss}


toy_loss.uniform_weighting = True


def toy_state(params):
    tx = make_optimizer(1e-2, optimizer="sgd")
    return TrainState.create(lambda *a, **k: None, params, tx, jax.random.PRNGKey(1))


def toy_batch(batch_size=16):
    rng = np.random.default_rng(3)
    return {"x": jnp.asarray(rng.standard_normal((batch_size, 8)), jnp.float32)}


MESHES = [dict(data=8), dict(data=2, fsdp=4), dict(data=4, fsdp=2)]


# ------------------------------------------------------------- bucket plans


def test_plan_buckets_boundary_cases():
    params = toy_params()
    flat = jax.tree_util.tree_leaves(params)
    leaves = _leaf_plan([(p.shape, p.dtype) for p in flat], fsdp_size=4, min_weight_size=32)
    sharded, replicated = _plan_buckets(leaves, BUCKET_BYTES)

    by_index = {lf.index: lf for lf in leaves}
    names = sorted(params)  # dict pytrees flatten in sorted-key order
    dims = {names[i]: lf.dim for i, lf in by_index.items()}
    # non-divisible leaf falls back to replicated, below-threshold leaf too
    assert dims["odd"] is None and dims["tiny"] is None
    assert dims["big"] is not None and dims["exact"] is not None

    def bucket_names(buckets):
        return [[names[lf.index] for lf in b] for b in buckets]

    sh = bucket_names(sharded)
    # big exceeds the bucket size -> closes its own (single-leaf fast path);
    # exact closes at the boundary; the smalls coalesce
    assert ["big"] in sh and ["exact"] in sh
    assert any(set(b) == {"small_a", "small_b"} for b in sh)
    assert any(set(b) == {"odd", "tiny"} for b in bucket_names(replicated))


def test_plan_buckets_splits_dtypes():
    leaves = _leaf_plan(
        [((8, 8), jnp.float32), ((8, 8), jnp.bfloat16), ((8, 8), jnp.float32)],
        fsdp_size=4,
        min_weight_size=0,
    )
    sharded, _ = _plan_buckets(leaves, bucket_bytes=1 << 20)
    # coalescing concatenates flattened leaves — one dtype per bucket
    assert all(len({lf.dtype for lf in b}) == 1 for b in sharded)
    assert len(sharded) == 3  # f32 / bf16 / f32: a dtype change closes the bucket


def test_parse_mesh_spec():
    assert parse_mesh_spec("data=2,fsdp=4") == {"data": 2, "fsdp": 4}
    assert parse_mesh_spec("data=8") == {"data": 8}
    with pytest.raises(ValueError):
        parse_mesh_spec("data=2,tensor=4")
    with pytest.raises(ValueError):
        parse_mesh_spec("8x2")


def test_expected_collectives_counts():
    params = toy_params()
    mesh = make_mesh(data=2, fsdp=4, devices=jax.devices()[:8])
    exp = expected_collectives(
        params, mesh, microbatch=2, bucket_bytes=BUCKET_BYTES, min_weight_size=32
    )
    # 3 sharded buckets (big / exact / smalls), 1 replicated bucket
    assert exp["all-gather"] == 3
    assert exp["reduce-scatter"] == 2 * 3
    assert exp["all-reduce"] == 2 * (3 + 1) + 1


def test_shard_batch_reports_indivisible_leaf():
    mesh = make_mesh(data=2, fsdp=2, devices=jax.devices()[:4])
    with pytest.raises(ValueError, match=r"\['labels'\].*leading dim 6.*4 shards"):
        shard_batch({"x": np.zeros((8, 4)), "labels": np.zeros((6,))}, mesh)


# --------------------------------------------------- step equivalence (toy)


@pytest.mark.parametrize("shape", MESHES, ids=str)
@pytest.mark.parametrize("microbatch", [1, 2])
def test_overlap_toy_step_matches_gspmd_and_unsharded(shape, microbatch):
    params = toy_params()
    batch = toy_batch()
    mesh = make_mesh(devices=jax.devices()[:8], **shape)
    cfg = OverlapConfig(mesh=mesh, bucket_bytes=BUCKET_BYTES, min_weight_size=32)

    ref_state, ref_m = make_train_step(toy_loss, donate=False, microbatch=microbatch)(
        toy_state(params), batch
    )
    gspmd_state, gspmd_m = make_train_step(toy_loss, donate=False, microbatch=microbatch)(
        shard_train_state(toy_state(params), mesh, min_weight_size=32),
        shard_batch(dict(batch), mesh),
    )
    ov_state, ov_m = make_overlap_train_step(
        toy_loss, cfg, microbatch=microbatch, donate=False
    )(
        shard_train_state(toy_state(params), mesh, min_weight_size=32),
        shard_batch(dict(batch), mesh),
    )

    np.testing.assert_allclose(float(ov_m["loss"]), float(gspmd_m["loss"]), atol=1e-5)
    np.testing.assert_allclose(float(ov_m["loss"]), float(ref_m["loss"]), atol=1e-5)
    for name, a, b, c in zip(
        params,
        jax.tree.leaves(ov_state.params),
        jax.tree.leaves(gspmd_state.params),
        jax.tree.leaves(ref_state.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, err_msg=name)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-5, err_msg=name)

    # the sync math is verifiable analytically: grad of every leaf is the
    # GLOBAL batch mean of per-sample weights (sgd lr 1e-2)
    w = float(jnp.mean(jnp.sum(batch["x"], axis=-1)))
    before = params["big"]
    after = np.asarray(jax.tree.leaves(ov_state.params)[0])  # 'big' is first
    np.testing.assert_allclose(after, np.asarray(before) - 1e-2 * w, atol=1e-5)


def test_overlap_rejects_padded_batches_and_bad_meshes():
    params = toy_params()
    mesh = make_mesh(data=2, fsdp=4, devices=jax.devices()[:8])
    step = make_overlap_train_step(
        # undeclared loss: the pad sniff must fire (per-shard means reweight)
        lambda p, b, r: toy_loss(p, b, r),
        OverlapConfig(mesh=mesh, min_weight_size=32),
        donate=False,
        jit=False,
    )
    batch = dict(toy_batch(), pad_mask=np.zeros((16, 8), bool))
    with pytest.raises(ValueError, match="uniform"):
        step(toy_state(params), batch)

    with pytest.raises(ValueError, match="tensor/sequence"):
        make_overlap_train_step(
            toy_loss, OverlapConfig(mesh=make_mesh(data=2, tensor=4, devices=jax.devices()[:8]))
        )


# ------------------------------------------------- graphlint: the scheduling


def _overlap_report(microbatch=2, rules=("collective-budget", "collective-overlap")):
    params = toy_params()
    mesh = make_mesh(data=2, fsdp=4, devices=jax.devices()[:8])
    cfg = OverlapConfig(mesh=mesh, bucket_bytes=BUCKET_BYTES, min_weight_size=32)
    step = make_overlap_train_step(toy_loss, cfg, microbatch=microbatch, donate=False, jit=False)
    state = shard_train_state(toy_state(params), mesh, min_weight_size=32)
    batch = shard_batch(toy_batch(), mesh)
    exp = expected_collectives(
        params, mesh, microbatch=microbatch, bucket_bytes=BUCKET_BYTES, min_weight_size=32
    )
    budget = dict(exp)
    # the GSPMD optimizer update outside the shard_map region adds per-leaf
    # global-norm partials; only all-reduce needs that headroom
    budget["all-reduce"] += len(jax.tree_util.tree_leaves(params)) + 8
    return analysis.check(
        step,
        (state, batch),
        rules=rules,
        policy=LintPolicy(expect_overlap=True, collective_budget=budget),
        name="toy_overlap_step",
    )


def test_collective_kind_and_count_within_budget():
    """analysis.check pins the overlap step's collective kinds/counts: the
    explicit all-gather/reduce-scatter structure is exactly the bucket plan
    (XLA may combine, never add)."""
    report = _overlap_report()
    assert "collective-budget" in report.rules_run
    assert report.ok(), report.format()


def test_collective_overlap_rule_passes_on_overlap_step():
    report = _overlap_report(rules=("collective-overlap",))
    assert "collective-overlap" in report.rules_run
    assert report.clean, report.format()


def test_collective_overlap_rule_fails_on_serialized_schedule():
    """The discriminator: a chain where every compute op is upstream or
    downstream of every collective — no schedule can overlap it, and the
    rule must say so rather than rubber-stamp."""
    mesh = make_mesh(data=1, fsdp=4, devices=jax.devices()[:4])

    def serialized(x):
        for _ in range(2):
            x = jax.lax.psum_scatter(x, "fsdp", scatter_dimension=0, tiled=True)
            x = jnp.tanh(x @ jnp.ones((x.shape[-1], x.shape[-1]), x.dtype))
            x = jax.lax.all_gather(x, "fsdp", axis=0, tiled=True)
        return x

    fn = shard_map(serialized, mesh=mesh, in_specs=P("fsdp"), out_specs=P("fsdp"))
    report = analysis.check(
        fn,
        (jnp.ones((16, 64)),),
        rules=("collective-overlap",),
        policy=LintPolicy(expect_overlap=True),
        name="serialized_chain",
    )
    assert not report.ok()
    kinds = {v.op for v in report.violations}
    assert kinds == {"all-gather", "reduce-scatter"}
    assert all("serialized" in v.message for v in report.violations)


def test_collective_overlap_rule_inert_without_declaration():
    report = _overlap_report(rules=("collective-overlap",))
    undeclared = analysis.check(
        lambda x: x + 1, (jnp.ones(4),), rules=("collective-overlap",), policy=LintPolicy()
    )
    assert "collective-overlap" in undeclared.rules_skipped
    assert report.rules_run  # sanity: the declared path did run


# --------------------------------------------- trainer integration + events


def test_trainer_overlap_fit_logs_input_wait(tmp_path):
    """Trainer with overlap=True: fits on a data x fsdp mesh through the
    shard_map step, and the per-window log rows carry input_wait_ms (the
    device-side double-buffer satellite)."""
    from perceiver_io_tpu.training.metrics import MetricsLogger
    from perceiver_io_tpu.training.trainer import Trainer, TrainerConfig

    mesh = make_mesh(data=2, fsdp=2, devices=jax.devices()[:4])
    logger = MetricsLogger(str(tmp_path), use_tensorboard=False)
    trainer = Trainer(
        toy_loss,
        mesh=mesh,
        logger=logger,
        config=TrainerConfig(
            max_steps=3, log_interval=1, overlap=True, overlap_bucket_mb=0.01,
            fsdp_min_weight_size=32, prefetch_batches=0,
        ),
    )
    batches = [toy_batch(8) for _ in range(3)]
    state = trainer.fit(toy_state(toy_params()), iter(batches))
    logger.close()
    assert int(state.step) == 3

    import csv

    rows = list(csv.DictReader((tmp_path / "metrics.csv").open()))
    waits = [float(r["input_wait_ms"]) for r in rows if r.get("input_wait_ms")]
    assert waits and all(w >= 0.0 for w in waits)


def test_trainer_overlap_requires_mesh():
    from perceiver_io_tpu.training.trainer import Trainer, TrainerConfig

    with pytest.raises(ValueError, match="mesh"):
        Trainer(toy_loss, config=TrainerConfig(overlap=True))


def test_overlap_rng_differs_per_shard():
    """The step key must be folded with the device's mesh index: a
    replicated key would draw identical dropout masks on every batch shard.
    Observable via the variance of a per-device uniform draw: E[r^2] >
    E[r]^2 across devices iff the draws differ."""

    def rng_loss(params, batch, rng):
        u = jax.random.uniform(rng, ())
        loss = jnp.mean(batch["x"]) * sum(jnp.sum(v) for v in jax.tree.leaves(params)) * 0.0
        return loss, {"loss": loss, "r": u, "r2": u * u}

    rng_loss.uniform_weighting = True
    mesh = make_mesh(data=4, fsdp=2, devices=jax.devices()[:8])
    cfg = OverlapConfig(mesh=mesh, bucket_bytes=BUCKET_BYTES, min_weight_size=32)
    _, metrics = make_overlap_train_step(rng_loss, cfg, microbatch=1, donate=False)(
        shard_train_state(toy_state(toy_params()), mesh, min_weight_size=32),
        shard_batch(toy_batch(8), mesh),
    )
    variance = float(metrics["r2"]) - float(metrics["r"]) ** 2
    assert variance > 1e-4, f"per-device rng draws are identical (var={variance:.2e})"


def test_trainer_double_buffer_defers_pipeline_errors(tmp_path):
    """A pipeline error hit during the overlapped prefetch must surface at
    the NEXT iteration's fetch — after the completed step's log row — not
    abort the step that already ran."""
    from perceiver_io_tpu.training.metrics import MetricsLogger
    from perceiver_io_tpu.training.trainer import Trainer, TrainerConfig

    def batches():
        yield toy_batch(8)
        yield toy_batch(8)
        raise RuntimeError("pipe burst")

    logger = MetricsLogger(str(tmp_path), use_tensorboard=False)
    trainer = Trainer(
        toy_loss,
        logger=logger,
        config=TrainerConfig(max_steps=5, log_interval=1, prefetch_batches=0,
                             input_double_buffer=True),
    )
    with pytest.raises(RuntimeError, match="pipe burst"):
        trainer.fit(toy_state(toy_params()), batches())
    trainer.close()
    logger.close()

    import csv

    rows = list(csv.DictReader((tmp_path / "metrics.csv").open()))
    # both completed steps logged before the deferred error surfaced
    assert [r["step"] for r in rows if r.get("train_loss")] == ["1", "2"]


def test_trainer_double_buffer_consumes_exactly_max_steps():
    """The double buffer must not steal a batch past the last step: 3 steps
    consume exactly 3 batches (prefetch skipped on the final iteration)."""
    from perceiver_io_tpu.training.trainer import Trainer, TrainerConfig

    trainer = Trainer(
        toy_loss,
        config=TrainerConfig(max_steps=3, log_interval=10, prefetch_batches=0,
                             input_double_buffer=True),
    )
    it = iter([toy_batch(8) for _ in range(5)])
    state = trainer.fit(toy_state(toy_params()), it)
    assert int(state.step) == 3
    assert len(list(it)) == 2  # two batches untouched


# --------------------------------------------------- real-model equivalence


@pytest.mark.slow
def test_overlap_clm_step_matches_gspmd_all_meshes():
    """The dryrun bar as a pytest: the tiny Perceiver AR CLM train step,
    overlap-on vs overlap-off (GSPMD) vs unsharded, across the three
    data/fsdp mesh shapes — loss and post-update params within 1e-5."""
    from perceiver_io_tpu.models.text import CausalLanguageModel, CausalLanguageModelConfig
    from perceiver_io_tpu.training import clm_loss_fn

    config = CausalLanguageModelConfig(
        vocab_size=64, max_seq_len=64, max_latents=16, num_channels=32,
        num_heads=4, num_self_attention_layers=2, cross_attention_dropout=0.0,
    )
    model = CausalLanguageModel(config)
    rng = np.random.default_rng(0)
    t = rng.integers(0, 64, size=(16, 65))
    batch = {
        "labels": jnp.asarray(t[:, 1:]),
        "input_ids": jnp.asarray(t[:, :-1]),
        "pad_mask": None,
    }
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"], prefix_len=48)
    loss = clm_loss_fn(model.apply, max_latents=16, deterministic=True)

    def fresh():
        tx = make_optimizer(1e-3, gradient_clip=1.0)
        return TrainState.create(model.apply, params, tx, jax.random.PRNGKey(1))

    ref_state, ref_m = make_train_step(loss, donate=False, microbatch=2)(fresh(), batch)

    for shape in MESHES:
        mesh = make_mesh(devices=jax.devices()[:8], **shape)
        cfg = OverlapConfig(mesh=mesh, bucket_bytes=32 * 1024, min_weight_size=0)
        sb = shard_batch(dict(batch), mesh)
        gspmd_state, gspmd_m = make_train_step(loss, donate=False, microbatch=2)(
            shard_train_state(fresh(), mesh, min_weight_size=0), sb
        )
        ov_state, ov_m = make_overlap_train_step(loss, cfg, microbatch=2, donate=False)(
            shard_train_state(fresh(), mesh, min_weight_size=0), sb
        )
        np.testing.assert_allclose(
            float(ov_m["loss"]), float(gspmd_m["loss"]), atol=1e-5, err_msg=str(shape)
        )
        np.testing.assert_allclose(
            float(ov_m["loss"]), float(ref_m["loss"]), atol=1e-5, err_msg=str(shape)
        )
        for a, b, c in zip(
            jax.tree.leaves(ov_state.params),
            jax.tree.leaves(gspmd_state.params),
            jax.tree.leaves(ref_state.params),
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
            np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-5)
