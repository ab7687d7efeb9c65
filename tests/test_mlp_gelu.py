"""The MLP's exact GELU under its own differentiation rule
(``core.modules.gelu_exact``): the same numbers as ``nn.gelu`` and its
autodiff gradient, to the bit, with ``gelu(h)`` evaluated once a site and
kept behind a barrier (the compile that shows XLA honours it is in
``tests/test_tpu_compile.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from jax import lax

from perceiver_io_tpu.core import modules
from perceiver_io_tpu.core.modules import MLP, gelu_exact, mlp_gelu_plans


def _plain(x):
    return nn.gelu(x, approximate=False)


def _all_bfloat16():
    """Every bfloat16 value: the 65 536 bit patterns, NaNs and infinities included."""
    return jnp.asarray(np.arange(65536, dtype=np.uint16).view(jnp.bfloat16))


def _bits(x):
    """Bit patterns, with every NaN folded onto one."""
    x = np.asarray(x)
    bits = x.view(np.uint16).copy()
    bits[np.isnan(x.astype(np.float32))] = 0x7FC0
    return bits


def _slope(fn, x):
    return jax.vjp(fn, x)[1](jnp.ones_like(x))[0]


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
def test_value_is_nn_gelu_to_the_bit_for_every_bfloat16(jit):
    """The primal, and the forward rule's ``a`` (what ``dense_2`` reads
    under differentiation). Eager and compiled evaluation of ``nn.gelu``
    round differently (XLA keeps ``-x / sqrt 2`` in float32 inside a
    program), so each is compared with its own kind."""
    wrap = jax.jit if jit else (lambda f: f)
    x = _all_bfloat16()
    want = _bits(wrap(_plain)(x))
    np.testing.assert_array_equal(_bits(wrap(gelu_exact)(x)), want)
    np.testing.assert_array_equal(_bits(wrap(lambda x: jax.vjp(gelu_exact, x)[0])(x)), want)


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
def test_slope_is_autodiffs_to_the_bit_for_every_bfloat16(jit):
    """The backward differentiates ``nn.gelu`` at the kept ``h``: the
    derivative autodiff gave before the rule, NaN and infinities as before."""
    wrap = jax.jit if jit else (lambda f: f)
    x = _all_bfloat16()
    got, want = wrap(lambda x: _slope(gelu_exact, x))(x), wrap(lambda x: _slope(_plain, x))(x)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    # and it is the exact GELU's derivative, Phi(x) + x phi(x), within a few bfloat16 roundings (eager evaluation
    # rounds after every operation)
    finite = np.isfinite(np.asarray(x, np.float32)) & (np.abs(np.asarray(x, np.float32)) < 8)
    x64 = np.asarray(x, np.float64)[finite]
    from math import erfc, exp, pi, sqrt
    exact = np.array([0.5 * erfc(-v / sqrt(2)) + v * exp(-v * v / 2) / sqrt(2 * pi) for v in x64])
    np.testing.assert_allclose(np.asarray(got, np.float64)[finite], exact, rtol=2.0**-5, atol=2.0**-7)


def _mlp_grads(dtype, act=None):
    mlp = MLP(num_channels=16, widening_factor=4, dtype=dtype)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16), dtype)
    params = mlp.init(jax.random.PRNGKey(0), x)

    def loss(params, x):
        return (mlp.apply(params, x).astype(jnp.float32) ** 2).sum()

    if act is None:
        return jax.grad(loss, argnums=(0, 1))(params, x)
    original, modules.gelu_exact = modules.gelu_exact, act
    try:
        return jax.grad(loss, argnums=(0, 1))(params, x)
    finally:
        modules.gelu_exact = original


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6), (jnp.bfloat16, 2.0**-7)], ids=["float32", "bfloat16"])
def test_mlp_gradients_match_plain_autodiff(dtype, tol):
    got, want = _mlp_grads(dtype), _mlp_grads(dtype, act=_plain)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype
        scale = float(jnp.abs(w.astype(jnp.float32)).max())
        np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(w, np.float32), rtol=tol, atol=tol * scale)


def test_mlp_parameter_tree_is_unchanged():
    mlp = MLP(num_channels=16, widening_factor=2)
    params = mlp.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 16)))["params"]
    assert sorted(params) == ["LayerNorm_0", "dense_1", "dense_2"]
    assert params["dense_1"]["kernel"].shape == (16, 32) and params["dense_2"]["kernel"].shape == (32, 16)


def _via_vmap(x):
    return jax.vmap(lambda row: _slope(gelu_exact, row))(x)


def _via_scan(x):
    return lax.scan(lambda c, row: (c, _slope(gelu_exact, row)), 0, x)[1]


def _via_checkpoint(x):
    return jax.grad(lambda x: jax.checkpoint(gelu_exact)(x).sum())(x)


def _via_jit(x):
    return jax.jit(lambda x: _slope(gelu_exact, x))(x)


@pytest.mark.parametrize("how", [_via_jit, _via_vmap, _via_scan, _via_checkpoint], ids=["jit", "vmap", "scan", "checkpoint"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6), (jnp.bfloat16, 2.0**-6)], ids=["float32", "bfloat16"])
def test_rule_under_transformations(how, dtype, tol):
    """bfloat16 within its rounding: a compiled program keeps float32 between operations where eager evaluation rounds."""
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 24), jnp.float32).astype(dtype) * 3
    want = _slope(_plain, x)
    np.testing.assert_allclose(np.asarray(how(x), np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_second_derivative_works_as_autodiffs():
    """The backward is ``jax.vjp`` of the plain expression, so the rule
    differentiates twice like the expression itself."""
    x = jnp.linspace(-4.0, 4.0, 41)
    second = jax.vmap(jax.grad(jax.grad(gelu_exact)))(x)
    want = jax.vmap(jax.grad(jax.grad(_plain)))(x)
    np.testing.assert_allclose(np.asarray(second), np.asarray(want), rtol=1e-6, atol=1e-6)
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(gelu_exact, (x,), (jnp.ones_like(x),))  # forward mode through a custom_vjp: jax's own refusal


def test_the_forward_pass_alone_lowers_to_nn_gelu():
    """A program that does not differentiate runs the primal: the lowered
    text holds no barrier and is the plain expression's (behind jax's
    ``custom_vjp_call`` wrapper, which leaves nothing in the StableHLO)."""
    x = jax.ShapeDtypeStruct((4, 32), jnp.bfloat16)
    ours, plain = jax.jit(gelu_exact).lower(x).as_text(), jax.jit(_plain).lower(x).as_text()
    assert "optimization_barrier" not in ours
    strip = lambda text: [line.split(" loc(")[0] for line in text.splitlines() if "erfc" in line or "multiply" in line]  # noqa: E731
    assert strip(ours) == strip(plain) and strip(ours)
    assert "optimization_barrier" in jax.jit(jax.grad(lambda x: gelu_exact(x).sum())).lower(x).as_text()


def test_compile_event_carries_the_mlp_gelu_rows():
    from perceiver_io_tpu.obs.recompile import RecompileTracker

    class Sink:
        rows = []

        def emit(self, kind, **fields):
            self.rows.append((kind, fields))

    mlp = MLP(num_channels=24, widening_factor=2, dtype=jnp.bfloat16)
    x = jnp.ones((3, 5, 24), jnp.bfloat16)
    params = mlp.init(jax.random.PRNGKey(0), x)
    before = modules.mlp_gelu_sites()
    step = jax.jit(jax.grad(lambda p, x: (mlp.apply(p, x) + mlp.apply(p, 2 * x)).astype(jnp.float32).sum()))
    RecompileTracker(events=Sink()).wrap(step, "step")(params, x)
    (kind, fields), = Sink.rows
    assert kind == "compile"
    # the row counts this call's trace alone, whatever the process traced before
    assert fields["mlp_gelu"] == mlp_gelu_plans(since=before) == [{
        "rows": 15, "width": 48, "dtype": "bfloat16", "sites": 2, "residuals": "h+erfc",
        "residual_bytes": 2 * 15 * 48 * 2, "erfc_evals_per_site": 1,
    }]
    jax.jit(lambda p, x: mlp.apply(p, x))(params, x)  # the forward alone is no site
    assert mlp_gelu_plans(since=before)[0]["sites"] == 2
    row = next(r for r in mlp_gelu_plans() if (r["rows"], r["width"], r["dtype"]) == (15, 48, "bfloat16"))
    assert row["sites"] >= 2  # every trace of the process so far


def test_a_train_runs_compile_event_names_the_rule(tmp_path):
    """``Trainer.fit`` on a tiny Perceiver AR model: the ``compile`` row of
    ``events.jsonl`` carries one ``mlp_gelu`` row for the three MLPs (the
    cross-attention's and two self-attention layers') of 4 x 8 latents at
    width 4 x 32: the sites of the step's own trace (the trainer traces the
    step once more for graphlint, and the process may have traced others)."""
    import json
    import os

    from perceiver_io_tpu.models.text import CausalLanguageModel, CausalLanguageModelConfig
    from perceiver_io_tpu.training import MetricsLogger, Trainer, TrainerConfig, TrainState, clm_loss_fn, make_optimizer

    config = CausalLanguageModelConfig(vocab_size=50, max_seq_len=24, max_latents=8, num_channels=32, num_heads=4,
                                       num_self_attention_layers=2, cross_attention_dropout=0.5)
    model = CausalLanguageModel(config)
    tokens = np.random.default_rng(0).integers(0, config.vocab_size, size=(4, config.max_seq_len + 1))
    batch = {"labels": jnp.asarray(tokens[:, 1:]), "input_ids": jnp.asarray(tokens[:, :-1]), "pad_mask": None}
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"], prefix_len=16)
    state = TrainState.create(model.apply, params, make_optimizer(1e-3), jax.random.PRNGKey(1))
    logger = MetricsLogger(str(tmp_path), use_tensorboard=False)
    trainer = Trainer(clm_loss_fn(model.apply, max_latents=config.max_latents), logger=logger,
                      config=TrainerConfig(max_steps=2, log_interval=1, prefetch_batches=0))
    trainer.fit(state, iter([batch] * 2), model_config=config)
    trainer.close()
    logger.close()
    with open(os.path.join(str(tmp_path), "events.jsonl")) as f:
        compiles = [row for row in map(json.loads, f) if row["event"] == "compile" and row["fn"] == "train_step"]
    row, = compiles[0]["mlp_gelu"]
    assert (row["rows"], row["width"], row["residuals"], row["erfc_evals_per_site"]) == (32, 128, "h+erfc", 1)
    assert row["sites"] == 3 and row["residual_bytes"] == 2 * 32 * 128 * 4
