"""The distributed train step (GSPMD: ``training/loop.py::make_train_step`` on
``shard_train_state`` / ``shard_batch`` inputs) on the 8-virtual-device CPU
mesh: loss, post-update parameters and step counter against the unsharded
step, the mesh-spec helpers of ``parallel/mesh.py``, and the Trainer on a
data x fsdp mesh with its device-side input double buffer."""

import csv

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.parallel import make_mesh, shard_batch
from perceiver_io_tpu.parallel.mesh import mesh_from_spec, parse_mesh_spec, required_devices
from perceiver_io_tpu.training import TrainState, make_optimizer
from perceiver_io_tpu.training.loop import make_train_step, shard_train_state

# --------------------------------------------------------------- toy harness
# A parameter tree covering every fsdp placement case, with an analytic
# uniform-weighting loss so gradient sync is verifiable to the digit:
#   big, exact, small_*  — sharded along their largest fsdp-divisible axis
#   odd      — no dim divisible by fsdp: replicated fallback
#   tiny     — below min_weight_size: replicated
MIN_WEIGHT_SIZE = 32


def toy_params():
    rng = np.random.default_rng(0)

    def t(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return {
        "big": t(128, 64),
        "exact": t(64, 64),
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


# --------------------------------------------------------- mesh-spec helpers


def test_parse_mesh_spec():
    assert parse_mesh_spec("data=2,fsdp=4") == {"data": 2, "fsdp": 4}
    assert parse_mesh_spec("data=8") == {"data": 8}
    with pytest.raises(ValueError):
        parse_mesh_spec("data=2,tensor=4")
    with pytest.raises(ValueError):
        parse_mesh_spec("8x2")


def test_mesh_from_spec_builds_the_mesh_and_reports_a_shortage():
    assert required_devices({"data": 2, "fsdp": 4}) == 8
    mesh = mesh_from_spec("data=2,fsdp=2")
    assert dict(mesh.shape) == {"data": 2, "fsdp": 2, "tensor": 1, "seq": 1}
    assert list(mesh.devices.flat) == jax.devices()[:4]
    with pytest.raises(ValueError, match="needs 8 devices, have 2"):
        mesh_from_spec("data=2,fsdp=4", devices=jax.devices()[:2])


def test_shard_batch_reports_indivisible_leaf():
    mesh = make_mesh(data=2, fsdp=2, devices=jax.devices()[:4])
    with pytest.raises(ValueError, match=r"\['labels'\].*leading dim 6.*4 shards"):
        shard_batch({"x": np.zeros((8, 4)), "labels": np.zeros((6,))}, mesh)


# --------------------------------------------------- step equivalence (toy)


@pytest.mark.parametrize("shape", MESHES, ids=str)
@pytest.mark.parametrize("microbatch", [1, 2])
def test_gspmd_toy_step_matches_unsharded(shape, microbatch):
    params = toy_params()
    batch = toy_batch()
    mesh = make_mesh(devices=jax.devices()[:8], **shape)

    ref_state, ref_m = make_train_step(toy_loss, donate=False, microbatch=microbatch)(
        toy_state(params), batch
    )
    state, m = make_train_step(toy_loss, donate=False, microbatch=microbatch)(
        shard_train_state(toy_state(params), mesh, min_weight_size=MIN_WEIGHT_SIZE),
        shard_batch(dict(batch), mesh),
    )

    np.testing.assert_allclose(float(m["loss"]), float(ref_m["loss"]), atol=1e-5)
    assert int(state.step) == int(ref_state.step) == 1
    for name, a, b in zip(
        params, jax.tree.leaves(state.params), jax.tree.leaves(ref_state.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, err_msg=name)

    # the sync math is verifiable analytically: grad of every leaf is the
    # GLOBAL batch mean of per-sample weights (sgd lr 1e-2)
    w = float(jnp.mean(jnp.sum(batch["x"], axis=-1)))
    np.testing.assert_allclose(
        np.asarray(state.params["big"]), np.asarray(params["big"]) - 1e-2 * w, atol=1e-5
    )


# --------------------------------------------- trainer integration + events


def test_trainer_sharded_fit_logs_input_wait(tmp_path):
    """The Trainer fits on a data x fsdp mesh through the GSPMD step, and the
    per-window log rows carry input_wait_ms (the device-side double buffer)."""
    from perceiver_io_tpu.training.metrics import MetricsLogger
    from perceiver_io_tpu.training.trainer import Trainer, TrainerConfig

    mesh = make_mesh(data=2, fsdp=2, devices=jax.devices()[:4])
    logger = MetricsLogger(str(tmp_path), use_tensorboard=False)
    trainer = Trainer(
        toy_loss,
        mesh=mesh,
        logger=logger,
        config=TrainerConfig(
            max_steps=3, log_interval=1, fsdp_min_weight_size=MIN_WEIGHT_SIZE, prefetch_batches=0,
        ),
    )
    batches = [toy_batch(8) for _ in range(3)]
    state = trainer.fit(toy_state(toy_params()), iter(batches))
    logger.close()
    assert int(state.step) == 3

    rows = list(csv.DictReader((tmp_path / "metrics.csv").open()))
    waits = [float(r["input_wait_ms"]) for r in rows if r.get("input_wait_ms")]
    assert waits and all(w >= 0.0 for w in waits)


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
