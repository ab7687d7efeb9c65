"""``ops/moe_combine.py``: a share-held expert layer's rows summed by token,
in interpret mode on the CPU (as ``grouped_matmul``'s tests run), against
``jax.ops.segment_sum`` in float32 and, bit for bit where a token has at most
two rows, against XLA's scatter-add, which the kernel replaced (PR 50)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.core import moe
from perceiver_io_tpu.ops.moe_combine import kernel_name, moe_combine, token_tile

H = 128
K = 4

# tokens, rows of the pass, row tile, and how many rows each token has (a callable of the seeded generator)
CASES = {
    # one token tile, one row tile: tokens of 0, 1, 2 and K rows
    "tiny": (16, 32, 32, lambda rng, t: np.array([0, 1, 2, K] * (t // 4))),
    # 512 tokens are two tiles of 256: runs that cross row tiles of 16 and the edge between the token tiles
    "ragged": (512, 1280, 16, lambda rng, t: rng.integers(0, K + 1, t)),
    # the second token tile has no row at all: it is not visited and keeps what it had
    "an_empty_token_tile": (768, 640, 64, lambda rng, t: np.where((np.arange(t) >= 256) & (np.arange(t) < 512), 0, rng.integers(0, 3, t))),
    # half the pass is rows past the last pair, filled with NaN
    "dead_rows": (64, 256, 32, lambda rng, t: rng.integers(0, 3, t)),
    # every row is one token's, over several row tiles
    "one_token": (24, 64, 16, lambda rng, t: np.where(np.arange(t) == 13, 50, 0)),
    # no row at all: nothing is visited
    "no_rows": (32, 32, 16, lambda rng, t: np.zeros(t, np.int64)),
    # a row tile that several token tiles share (3 tiles of 8 tokens in one row tile of 64)
    "a_shared_row_tile": (24, 64, 64, lambda rng, t: rng.integers(1, 3, t)),
}


def drawn(case, seed):
    t, r, row_tile, counts = CASES[case]
    rng = np.random.default_rng(seed)
    per_token = counts(rng, t)
    tokens = np.repeat(np.arange(t), per_token)
    n = len(tokens)
    assert n <= r, (n, r)
    tokens = np.concatenate([tokens, np.full(r - n, t)]).astype(np.int32)  # the dead rows' token is past the last
    rows = rng.normal(size=(r, H)).astype(np.float32)
    rows[n:] = np.nan
    weights = rng.uniform(0.05, 0.9, r).astype(np.float32)
    y = rng.normal(size=(t, H)).astype(np.float32)
    return per_token, n, jnp.asarray(y), jnp.asarray(rows, jnp.bfloat16), jnp.asarray(weights), jnp.asarray(tokens), row_tile


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("case", sorted(CASES))
def test_rows_in_token_order_are_summed_into_their_tokens(case, seed):
    per_token, n, y, rows, weights, tokens, row_tile = drawn(case, seed)
    t = y.shape[0]
    got = np.asarray(moe_combine(y, rows, weights, tokens, row_tile=row_tile))
    weighed = rows[:n].astype(jnp.float32) * weights[:n, None]
    want = np.asarray(y + jax.ops.segment_sum(weighed, tokens[:n], num_segments=t))
    assert np.isfinite(got).all()  # no dead row was read
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)
    untouched = per_token == 0
    np.testing.assert_array_equal(got[untouched], np.asarray(y)[untouched])
    # the scatter-add's own arithmetic (the product rounded to float32, then float32 sums): its bits at up to two rows a token
    scattered = np.asarray(y.at[tokens[:n]].add(weighed))
    few = per_token <= 2
    np.testing.assert_array_equal(got[few], scattered[few])
    np.testing.assert_allclose(got[~few], scattered[~few], atol=1e-6, rtol=1e-6)


def test_the_token_tile_divides_the_tokens_and_the_kernel_is_named_by_its_shapes():
    assert [token_tile(t) for t in (8192, 4096, 384, 128, 24, 6, 250)] == [256, 256, 192, 128, 24, 6, 250]
    name = kernel_name(8192, 20480, 2560)
    assert name == "moe_combine_t8192_r20480_h2560" and "moe_experts_prefill_" not in name
    per_token, n, y, rows, weights, tokens, row_tile = drawn("tiny", 0)
    assert "moe_combine_t16_r32_h128" in str(jax.make_jaxpr(lambda *a: moe_combine(*a, row_tile=row_tile))(y, rows, weights, tokens))
    with pytest.raises(ValueError, match="not a multiple of the row tile"):
        moe_combine(y, rows, weights, tokens, row_tile=24)


@pytest.mark.parametrize("skew", ["even", "every_pair_here", "one_expert"])
def test_a_skewed_routing_takes_more_passes_and_drops_no_pair(skew):
    """``experts_grouped`` on the share-held side: a pass is sized for an
    even routing, a skewed layer takes as many passes as serve every local
    pair, each accumulating into the tokens' buffer, and the sum is the dense
    path's (every held expert on every token, weighted)."""
    t, g, h, width, routed = 256, 4, H, 64, 16
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    w1, w3 = (jnp.asarray(rng.normal(size=(g, h, width)) * 0.1, jnp.float32) for _ in range(2))
    w2 = jnp.asarray(rng.normal(size=(g, width, h)) * 0.1, jnp.float32)
    if skew == "even":  # K distinct experts of 16 a token: a quarter of the pairs fall here
        chosen = np.argsort(rng.random((t, routed)), axis=1)[:, :K]
    elif skew == "every_pair_here":  # four times what an even routing sends
        chosen = np.argsort(rng.random((t, g)), axis=1)[:, :K]
    else:  # one held expert takes a pair of every token
        chosen = np.concatenate([np.full((t, 1), 2), 4 + np.argsort(rng.random((t, routed - g)), axis=1)[:, :K - 1]], axis=1)
    local = jnp.asarray(np.where(chosen < g, chosen, g), jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 0.5, (t, K)), jnp.float32)
    pass_rows = moe._pass_rows(t * K, g / routed, moe._Cuts(1, 32, 65536))
    assert pass_rows == 320
    y, unserved, passes = jax.jit(lambda *a: moe.experts_grouped(*a, pass_rows, 32, "segment_sum"))(x, local, weights, w1, w3, w2)
    n_local = int((np.asarray(local) < g).sum())
    assert int(unserved) == 0 and int(passes) == -(-n_local // pass_rows) == {"even": 1, "every_pair_here": 4, "one_expert": 1}[skew]
    combine = (jax.nn.one_hot(local, g, dtype=jnp.float32) * weights[:, :, None]).sum(axis=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(moe.experts_dense(x, combine, w1, w3, w2)), atol=2e-5, rtol=0)
