"""``ops.layernorm.LayerNorm`` / ``layer_norm`` vs flax.linen.LayerNorm:
values, gradients, parameter names, and the f32 statistics of a narrow dtype."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from perceiver_io_tpu.ops.layernorm import LayerNorm, layer_norm


@pytest.mark.parametrize("shape", [(4, 32, 128), (2, 24, 256), (96, 128), (3, 8, 96)])
def test_matches_flax_layernorm(rng, shape):
    c = shape[-1]
    x = jnp.asarray(rng.normal(size=shape), jnp.float32) * 3 + 1
    scale = jnp.asarray(rng.normal(size=(c,)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(c,)), jnp.float32)

    ref_mod = nn.LayerNorm(epsilon=1e-5)
    ref = ref_mod.apply({"params": {"scale": scale, "bias": bias}}, x)
    got = layer_norm(x, scale, bias, eps=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_gradients_match_flax_layernorm(rng):
    shape, c = (4, 32, 128), 128
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    scale = jnp.asarray(1 + 0.1 * rng.normal(size=(c,)), jnp.float32)
    bias = jnp.asarray(0.1 * rng.normal(size=(c,)), jnp.float32)
    w = jnp.asarray(rng.normal(size=shape), jnp.float32)

    def loss_fused(x, scale, bias):
        return jnp.sum(layer_norm(x, scale, bias) * w)

    def loss_ref(x, scale, bias):
        ref = nn.LayerNorm(epsilon=1e-5).apply({"params": {"scale": scale, "bias": bias}}, x)
        return jnp.sum(ref * w)

    g_fused = jax.grad(loss_fused, argnums=(0, 1, 2))(x, scale, bias)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(x, scale, bias)
    for name, a, b in zip(("dx", "dscale", "dbias"), g_fused, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4, err_msg=name
        )


def test_module_param_naming_matches_nn_layernorm(rng):
    x = jnp.asarray(rng.normal(size=(2, 16, 128)), jnp.float32)
    params = LayerNorm(epsilon=1e-5).init(jax.random.PRNGKey(0), x)
    assert set(params["params"]) == {"scale", "bias"}
    ref_params = nn.LayerNorm(epsilon=1e-5).init(jax.random.PRNGKey(0), x)
    assert jax.tree.map(lambda a: a.shape, params) == jax.tree.map(lambda a: a.shape, ref_params)


def test_bf16_io_f32_stats(rng):
    x = jnp.asarray(rng.normal(size=(4, 16, 128)), jnp.bfloat16)
    scale = jnp.ones((128,), jnp.float32)
    bias = jnp.zeros((128,), jnp.float32)
    got = layer_norm(x, scale, bias, dtype=jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    ref = nn.LayerNorm(epsilon=1e-5, dtype=jnp.bfloat16).apply(
        {"params": {"scale": scale, "bias": bias}}, x
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32), atol=2e-2, rtol=2e-2
    )


def test_f32_input_bf16_dtype_keeps_f32_stats(rng):
    """A bf16-dtype module receiving f32 activations computes its stats from
    the UNROUNDED input (flax semantics): the f32 result, cast once."""
    x = jnp.asarray(rng.normal(size=(4, 32, 128)), jnp.float32) * 2 + 0.5
    scale = jnp.asarray(1 + 0.1 * rng.normal(size=(128,)), jnp.float32)
    bias = jnp.asarray(0.1 * rng.normal(size=(128,)), jnp.float32)

    got = LayerNorm(epsilon=1e-5, dtype=jnp.bfloat16).apply({"params": {"scale": scale, "bias": bias}}, x)
    ref = nn.LayerNorm(epsilon=1e-5).apply({"params": {"scale": scale, "bias": bias}}, x)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref.astype(jnp.bfloat16)))
