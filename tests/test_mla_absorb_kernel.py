"""The absorbed step's kernel (``ops/mla_absorb.py``) in interpret mode
against what it replaces on the chip: ``LatentCache.append`` then
``core.mla.latent_decode_attention``, which stay the CPU's path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.core.cache import LatentCache, init_latent_cache
from perceiver_io_tpu.core.mla import MultiHeadLatentAttention, latent_decode_attention
from perceiver_io_tpu.ops.flash_attention import default_flash
from perceiver_io_tpu.ops.mla_absorb import mla_absorb, mla_absorb_kernel_name, mla_absorb_supported, row_tile

BATCH, CAPACITY, RANK, ROPE = 3, 48, 32, 8
WIDTH = RANK + ROPE
SM_SCALE = 24 ** -0.5
# the products take the cache's dtype and accumulate in float32 on both sides; they differ by the order of the sums
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def operands(heads: int, dtype, seed: int = 0):
    kq, kn, kr = jax.random.split(jax.random.PRNGKey(seed), 3)
    q_cat = jax.random.normal(kq, (BATCH, heads, WIDTH), jnp.float32)
    new_row = jax.random.normal(kn, (BATCH, 1, WIDTH), jnp.float32)
    rows = jax.random.normal(kr, (BATCH, CAPACITY, WIDTH), jnp.float32).astype(dtype)
    return q_cat, new_row, rows


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("length", [0, 21, CAPACITY - 1], ids=["empty", "mid", "last_slot"])
@pytest.mark.parametrize("heads", [4, 8])
def test_the_kernel_is_append_then_attend(heads, length, dtype):
    q_cat, new_row, rows = operands(heads, dtype)
    n = jnp.asarray(length, jnp.int32)
    want_cache = LatentCache(rows=rows, length=n).append(new_row)
    want = latent_decode_attention(q_cat, want_cache, SM_SCALE)[..., :RANK]
    got_rows, got = mla_absorb(q_cat, new_row, rows, n, sm_scale=SM_SCALE, keep=RANK)
    assert got.shape == (BATCH, heads, RANK) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL[dtype], rtol=0)
    # the new row at its slot, every other slot the input's to the bit
    assert got_rows.dtype == rows.dtype
    np.testing.assert_array_equal(np.asarray(got_rows, np.float32), np.asarray(want_cache.rows, np.float32))
    np.testing.assert_array_equal(np.asarray(got_rows[:, length], np.float32), np.asarray(new_row[:, 0].astype(dtype), np.float32))
    # slots past ``length`` carry no weight: whatever they hold, the result is the same to the bit
    other = rows.at[:, length + 1:].set(jnp.asarray(300.0, dtype))
    np.testing.assert_array_equal(
        np.asarray(mla_absorb(q_cat, new_row, other, n, sm_scale=SM_SCALE, keep=RANK)[1]), np.asarray(got)
    )


def test_the_kernel_returns_the_callers_dtype_and_its_name_says_the_shapes():
    q_cat, new_row, rows = operands(4, jnp.bfloat16)
    n = jnp.asarray(5, jnp.int32)
    f32 = mla_absorb(q_cat, new_row, rows, n, sm_scale=SM_SCALE, keep=RANK)[1]
    bf16 = mla_absorb(q_cat, new_row, rows, n, sm_scale=SM_SCALE, keep=RANK, out_dtype=jnp.bfloat16)[1]
    assert bf16.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(bf16, np.float32), np.asarray(f32.astype(jnp.bfloat16), np.float32))
    text = jax.jit(lambda *a: mla_absorb(*a, sm_scale=SM_SCALE, keep=RANK)).lower(q_cat, new_row, rows, n).as_text(debug_info=True)
    assert mla_absorb_kernel_name(4, CAPACITY, WIDTH) == "mla_absorb_h4_s48_w40" and "mla_absorb_h4_s48_w40" in text


def test_which_caches_the_kernel_takes():
    """Whole sublane tiles of rows (the tile written back) and whole lanes of kept channels: the cells' caches, not a test model's."""
    assert row_tile(jnp.float32) == 8 and row_tile(jnp.bfloat16) == 16
    assert mla_absorb_supported((64, 1280, 576), jnp.bfloat16, 512) and mla_absorb_supported((64, 1536, 576), jnp.bfloat16, 512)
    assert not mla_absorb_supported((64, 1288, 576), jnp.bfloat16, 512)  # half a bfloat16 tile
    assert mla_absorb_supported((64, 1288, 576), jnp.float32, 512)
    assert not mla_absorb_supported((4, 16, 24), jnp.float32, 16)  # ``tests/test_decoder_lm.py``'s rank


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_absorb_runs_the_kernel_where_the_flash_kernels_run(dtype):
    """``absorb`` under ``default_flash(True)`` (the chip's choice; here the
    interpreter) at a rank of whole lanes: the kernel is in the program, the
    cache advances by the one row, and outputs and rows are the XLA path's."""
    from tests.test_decoder_lm import tiny_config

    config = tiny_config(kv_lora_rank=128, mla_scale_kv_lora=True)
    attn = MultiHeadLatentAttention(config, dtype=dtype)
    b, n, width = 2, 5, config.kv_lora_rank + config.qk_rope_head_dim
    kx, kp = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (b, n, config.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(n)[None], (b, n))
    params = attn.init(kp, x, pos, method="expand")

    def steps(flash: bool):
        cache = init_latent_cache(b, 16, width, dtype)
        outs = []
        with default_flash(flash):
            # a function of fresh identity a choice: flax's and JAX's trace caches do not see the context variable
            step = jax.jit(lambda p, x_, c, pos_: attn.apply(p, x_, c, pos_, method="absorb"))
            text = step.lower(params, x[:, :1], cache, pos[:, :1]).as_text(debug_info=True)
            for t in range(n):
                out, cache = step(params, x[:, t:t + 1], cache, pos[:, t:t + 1])
                outs.append(out)
        return jnp.concatenate(outs, axis=1), cache, text

    want, want_cache, xla_text = steps(False)
    got, cache, text = steps(True)
    name = mla_absorb_kernel_name(config.num_attention_heads, 16, width)
    assert name in text and name not in xla_text
    assert "latent_cache_append" in xla_text and "latent_cache_append" not in text
    assert int(cache.length) == int(want_cache.length) == n and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(cache.rows, np.float32), np.asarray(want_cache.rows, np.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), atol=TOL[dtype] * 5, rtol=0)

