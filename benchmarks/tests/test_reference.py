"""Each plain float32 reference against the repo's model at a tiny size; the
lower precisions move away from it; the reference's AdamW against optax."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run
from benchmarks.lib import compare
from benchmarks.lib.weights import flat_dict, seed_key, weight_builder
from benchmarks.reference import common

DATA = run.os.path.join(run.HERE, "tests", "data")
TINY = {"perceiver_ar": "tiny-ar", "perceiver_io_image": "tiny-image"}


def tiny_family(name):
    config = run.load_json("configs", TINY[name], DATA)
    return run.importlib.import_module(f"benchmarks.families.{name}").Family(config)


def program_and_reference(family, seed):
    model = family.model()
    params = weight_builder(family.param_shapes(model), family.cfg["init_scale"])(seed_key(seed))
    batch = family.train_batch(seed, 0, 3)
    placed = {k: (None if v is None else jnp.asarray(v)) for k, v in batch.items()}
    (loss, _), grads = jax.value_and_grad(family.train_loss_fn(model), has_aux=True)(
        params, placed, jax.random.PRNGKey(0))
    ref_batch = {k: jnp.asarray(v) for k, v in family.reference_batch(batch).items()}
    return float(loss), flat_dict(grads), flat_dict(params), ref_batch


@pytest.mark.parametrize("name", sorted(TINY))
def test_reference_matches_the_program_in_float32(name):
    family = tiny_family(name)
    loss, grads, weights, batch = program_and_reference(family, seed=2**31 + 7)
    ref_loss, ref_grads = jax.value_and_grad(family.reference_loss("float32"))(weights, batch)
    assert loss == pytest.approx(float(ref_loss), abs=2e-6)
    assert set(grads) == set(ref_grads)
    gap, leaf = compare.worst_leaf_gap(common.leaf_norms(grads), common.leaf_norms(ref_grads))
    assert gap < 1e-4, leaf
    scale = max(float(jnp.abs(g).max()) for g in ref_grads.values())
    for key, g in ref_grads.items():
        np.testing.assert_allclose(np.asarray(grads[key]), np.asarray(g), atol=2e-5 * scale, err_msg=key)


@pytest.mark.parametrize("name", sorted(TINY))
def test_lower_precisions_leave_the_reference_in_order(name):
    family = tiny_family(name)
    _, _, weights, batch = program_and_reference(family, seed=11)
    ref = common.leaf_norms(jax.grad(family.reference_loss("float32"))(weights, batch))
    gaps = {p: compare.worst_leaf_gap(common.leaf_norms(jax.grad(family.reference_loss(p))(weights, batch)), ref)[0]
            for p in ("bfloat16", "fp8")}
    assert 1e-5 < gaps["bfloat16"] < gaps["fp8"], gaps
    assert gaps["fp8"] > 3 * gaps["bfloat16"], gaps


@pytest.mark.parametrize("moment_dtype", [jnp.float32, jnp.bfloat16])
def test_reference_adamw_follows_optax(moment_dtype):
    from perceiver_io_tpu.training import make_optimizer

    rng = np.random.default_rng(0)
    weights = {"a": jnp.asarray(rng.normal(size=(5, 3)), jnp.float32), "b": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}
    tx = make_optimizer(1e-2, gradient_clip=1.0, weight_decay=0.01,
                        moment_dtype=None if moment_dtype == jnp.float32 else "bfloat16")
    opt_state, state, got, want = tx.init(weights), common.adamw_init(weights, moment_dtype), weights, weights
    for i in range(3):
        grads = {k: jnp.asarray(rng.normal(size=v.shape) * (3.0 if i == 0 else 0.1), jnp.float32) for k, v in weights.items()}
        updates, opt_state = tx.update(grads, opt_state, want)
        want = jax.tree.map(jnp.add, want, updates)
        got, state = common.adamw_step(got, grads, state, lr=1e-2, clip=1.0, weight_decay=0.01)
    for k in weights:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]), rtol=1e-5, atol=1e-6)


def test_worst_leaf_gap_measures_against_the_median_leaf():
    ref = {"big": 10.0, "mid": 1.0, "zero": 1e-9}
    gap, leaf = compare.worst_leaf_gap({"big": 10.5, "mid": 1.0, "zero": 0.01}, ref)
    assert leaf == "big" and gap == pytest.approx(0.05)
    gap, leaf = compare.worst_leaf_gap({"big": 10.0, "mid": 1.0, "zero": 0.5}, ref)
    assert leaf == "zero" and gap == pytest.approx(0.5)
    assert compare.worst_leaf_gap({"big": float("nan"), "mid": 1.0, "zero": 0.0}, ref)[1] == "big"
    with pytest.raises(ValueError):
        compare.worst_leaf_gap({"big": 1.0}, ref)
