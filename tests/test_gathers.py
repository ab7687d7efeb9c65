"""VJP-rewrite ops (ops/gathers.py): forwards identical to the plain ops and
gradients identical to XLA's scatter-add versions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.ops.gathers import embed_lookup, gather_unique_rows, small_vocab_embed

rng = np.random.default_rng(0)


def test_small_vocab_embed_matches_take():
    table = jnp.asarray(rng.normal(size=(50, 16)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 50, size=(4, 12)))
    np.testing.assert_array_equal(
        np.asarray(small_vocab_embed(table, ids)), np.asarray(jnp.take(table, ids, axis=0))
    )


def test_small_vocab_embed_grad_matches_scatter():
    table = jnp.asarray(rng.normal(size=(50, 16)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 50, size=(4, 12)))
    cot = jnp.asarray(rng.normal(size=(4, 12, 16)), jnp.float32)

    def loss_new(t):
        return jnp.vdot(small_vocab_embed(t, ids), cot)

    def loss_ref(t):
        return jnp.vdot(jnp.take(t, ids, axis=0), cot)

    np.testing.assert_allclose(
        np.asarray(jax.grad(loss_new)(table)), np.asarray(jax.grad(loss_ref)(table)), atol=1e-5
    )


def test_embed_lookup_large_vocab_passthrough():
    table = jnp.asarray(rng.normal(size=(5000, 8)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 5000, size=(3,)))
    np.testing.assert_array_equal(
        np.asarray(embed_lookup(table, ids)), np.asarray(jnp.take(table, ids, axis=0))
    )


def test_gather_unique_rows_matches_take_along_axis():
    x = jnp.asarray(rng.normal(size=(3, 20, 8)), jnp.float32)
    idx = jnp.asarray(np.stack([rng.permutation(20)[:7] for _ in range(3)]))
    idx = jnp.sort(idx, axis=-1)
    np.testing.assert_array_equal(
        np.asarray(gather_unique_rows(x, idx)),
        np.asarray(jnp.take_along_axis(x, idx[..., None], axis=1)),
    )


def test_gather_unique_rows_grad_matches_scatter():
    x = jnp.asarray(rng.normal(size=(3, 20, 8)), jnp.float32)
    idx = jnp.asarray(np.stack([rng.permutation(20)[:7] for _ in range(3)]))
    idx = jnp.sort(idx, axis=-1)
    cot = jnp.asarray(rng.normal(size=(3, 7, 8)), jnp.float32)

    def loss_new(x_):
        return jnp.vdot(gather_unique_rows(x_, idx), cot)

    def loss_ref(x_):
        return jnp.vdot(jnp.take_along_axis(x_, idx[..., None], axis=1), cot)

    np.testing.assert_allclose(
        np.asarray(jax.grad(loss_new)(x)), np.asarray(jax.grad(loss_ref)(x)), atol=1e-6
    )


def test_gather_unique_rows_grad_under_jit_and_vmapped_batch():
    x = jnp.asarray(rng.normal(size=(2, 10, 4)), jnp.float32)
    idx = jnp.asarray(np.stack([rng.permutation(10)[:5] for _ in range(2)]))

    @jax.jit
    def f(x_):
        return jnp.sum(gather_unique_rows(x_, idx) ** 2)

    g = jax.grad(f)(x)
    g_ref = jax.grad(lambda x_: jnp.sum(jnp.take_along_axis(x_, idx[..., None], axis=1) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-6)


def test_gather_sorted_table_rows_matches_take():
    from perceiver_io_tpu.ops.gathers import gather_sorted_table_rows

    table = jnp.asarray(rng.normal(size=(20, 8)), jnp.float32)
    idx = jnp.asarray(np.sort(np.stack([rng.permutation(20)[:7] for _ in range(3)]), axis=-1))
    np.testing.assert_array_equal(
        np.asarray(gather_sorted_table_rows(table, idx)),
        np.asarray(jnp.take(table, idx, axis=0)),
    )


def test_gather_sorted_table_rows_grad_matches_scatter():
    from perceiver_io_tpu.ops.gathers import gather_sorted_table_rows

    table = jnp.asarray(rng.normal(size=(20, 8)), jnp.float32)
    idx = jnp.asarray(np.sort(np.stack([rng.permutation(20)[:7] for _ in range(3)]), axis=-1))
    cot = jnp.asarray(rng.normal(size=(3, 7, 8)), jnp.float32)

    def loss_new(t):
        return jnp.vdot(gather_sorted_table_rows(t, idx), cot)

    def loss_ref(t):
        return jnp.vdot(jnp.take(t, idx, axis=0), cot)

    np.testing.assert_allclose(
        np.asarray(jax.grad(loss_new)(table)), np.asarray(jax.grad(loss_ref)(table)), atol=1e-6
    )


# --- the table gradient as tile-local one-hot products (PR 31) ---------------
#
# keep sets against the tile of T positions (and the blocks of T rows of the
# cotangent): each case is (positions, one sorted unique index row per batch row)

from perceiver_io_tpu.ops.gathers import EMBED_TILE as T  # noqa: E402


def _keep_rows(n, k, b, seed=0):
    r = np.random.default_rng(seed)
    return np.sort(np.stack([r.permutation(n)[:k] for _ in range(b)]), axis=-1)


TABLE_GRAD_CASES = {
    # positions no multiple of the tile, kept no multiple of a block
    "ragged": (2 * T + 44, _keep_rows(2 * T + 44, T + 44, 3)),
    "keep_1": (2 * T + 44, _keep_rows(2 * T + 44, 1, 2)),
    "keep_all": (2 * T, _keep_rows(2 * T, 2 * T, 2)),
    "keep_half_of_4_tiles": (4 * T, _keep_rows(4 * T, 2 * T, 4)),
    "batch_1": (2 * T + 88, _keep_rows(2 * T + 88, T + 44, 1)),
    # every kept index inside one tile (the second of three)
    "one_tile": (3 * T, T + _keep_rows(T, T // 2 + 12, 2)),
    # a full tile whose rows start mid-block: half of tile 0, then all of
    # tile 1 (rows T/2 .. 3T/2 of g: both blocks of its window are needed in
    # full), then a tile that starts mid-block again
    "window_straddles_blocks": (4 * T, np.stack([np.r_[0:T // 2, T:2 * T, 2 * T + 44:2 * T + 44 + T // 2]] * 2)),
    # a tile edge between two neighbouring kept indices
    "tile_edge": (2 * T, np.stack([np.r_[T - 8:T + 8], np.r_[T - 1:T + 15]])),
    "first_and_last_row": (2 * T + 44, np.stack([np.r_[0, 150, 2 * T + 43], np.r_[0, 1, 2 * T + 43]])),
    # nothing kept in the last tiles: their start is K, past the last block
    "empty_tail_tiles": (2 * T + T // 2, _keep_rows(T, T // 2, 2)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(TABLE_GRAD_CASES))
def test_table_grad_tiles_match_scatter_add(case, dtype):
    """The kernel route's table gradient (interpret mode here) against the
    plain ``jnp.take`` scatter-add's, under ``jit``. A one-hot product is
    exact, so float32 cotangents agree to the order of the float32 batch sum
    and bfloat16 ones to the one rounding of the result."""
    from perceiver_io_tpu.ops.gathers import gather_table_rows

    n, idx = TABLE_GRAD_CASES[case]
    idx = jnp.asarray(idx, jnp.int32)
    r = np.random.default_rng(1)
    table = jnp.asarray(r.normal(size=(n, 128)), dtype)
    cot = jnp.asarray(r.normal(size=idx.shape + (128,)), dtype)

    def grad_of(lookup):
        return jax.jit(jax.grad(lambda t: jnp.vdot(lookup(t, idx).astype(jnp.float32), cot.astype(jnp.float32))))

    got = grad_of(gather_table_rows)(table)
    want = grad_of(lambda t, i: jnp.take(t, i, axis=0))(table)
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(np.asarray(gather_table_rows(table, idx)), np.asarray(jnp.take(table, idx, axis=0)))
    # the float32 sum of bfloat16 cotangents, rounded once, against XLA's
    # bfloat16 scatter-add, which rounds after every addition
    atol = 1e-5 if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol)
    if dtype == jnp.bfloat16:
        exact = jnp.zeros((n, 128), jnp.float32).at[idx.reshape(-1)].add(cot.reshape(-1, 128).astype(jnp.float32))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exact.astype(jnp.bfloat16)))


def _pallas_names(jaxpr, into):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            into.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_names(sub, into)
    return into


@pytest.mark.parametrize("route", ["tiles", "plain_gathers", "channels_off_the_lanes"])
def test_table_grad_route_follows_the_shapes(route):
    """128-lane channels outside ``plain_gathers()`` take the named kernel;
    inside it, or at a channel count off the lanes, the gradient is XLA's
    scatter-add, and the plan row says which it was."""
    import contextlib

    from perceiver_io_tpu.ops.gathers import embed_tile_plan, embed_tile_plans, gather_table_rows, plain_gathers

    n, k, b = 300, 150, 3
    c = 96 if route == "channels_off_the_lanes" else 128
    idx = jnp.asarray(_keep_rows(n, k, b), jnp.int32)
    r = np.random.default_rng(2)
    table = jnp.asarray(r.normal(size=(n, c)), jnp.float32)
    cot = jnp.asarray(r.normal(size=(b, k, c)), jnp.float32)

    def grad(t):
        with plain_gathers() if route == "plain_gathers" else contextlib.nullcontext():
            return jax.grad(lambda t_: jnp.vdot(gather_table_rows(t_, idx), cot))(t)

    names = _pallas_names(jax.make_jaxpr(grad)(table).jaxpr, [])
    plan = embed_tile_plan(n, k, b, c, plain=route == "plain_gathers")
    assert plan in embed_tile_plans()
    if route == "tiles":
        assert names == ["embed_pos_grad_n300_k150"]
        assert plan == {"positions": 300, "kept": 150, "batch": 3, "tile": 256, "tiles": 2, "grid_steps": 6,
                        "onehot_flops": 6 * 2 * 2 * 256 * 256 * 128, "route": "tiles"}
    else:
        assert names == [] and plan["route"] == "plain" and plan["grid_steps"] == plan["onehot_flops"] == 0
    want = jax.grad(lambda t_: jnp.vdot(jnp.take(t_, idx, axis=0), cot))(table)
    np.testing.assert_allclose(np.asarray(grad(table)), np.asarray(want), atol=1e-5)


def test_embed_tile_plan_of_the_16k_step():
    """The row the benchmark's ``ar16k-train-b32`` step records (docs/observability.md)."""
    from perceiver_io_tpu.ops.gathers import embed_grad_kernel_name, embed_tile_plan

    plan = embed_tile_plan(15360, 7680, 32, 512)
    assert (plan["tiles"], plan["grid_steps"], plan["onehot_flops"], plan["route"]) == (60, 1920, 257698037760, "tiles")
    assert embed_grad_kernel_name(15360, 7680) == "embed_pos_grad_n15360_k7680"


def test_compile_event_carries_the_embed_tiles():
    from perceiver_io_tpu.obs.recompile import RecompileTracker
    from perceiver_io_tpu.ops.gathers import gather_table_rows

    class Sink:
        rows = []

        def emit(self, kind, **fields):
            self.rows.append((kind, fields))

    idx = jnp.asarray(_keep_rows(200, 40, 2), jnp.int32)
    step = jax.jit(jax.grad(lambda t: gather_table_rows(t, idx).sum()))
    RecompileTracker(events=Sink()).wrap(step, "step")(jnp.zeros((200, 128), jnp.float32))
    (kind, fields), = Sink.rows
    row = next(r for r in fields["embed_tiles"] if (r["positions"], r["kept"], r["batch"]) == (200, 40, 2))
    assert kind == "compile" and row["route"] == "tiles" and row["tiles"] == 1 and row["grid_steps"] == 2


def test_table_grad_on_batch_shards_sums_the_partial_tables():
    """Under ``kernel_mesh`` the kernel runs per batch shard inside a
    shard_map and the float32 partial tables are summed over the batch axes."""
    from jax.sharding import Mesh

    from perceiver_io_tpu.ops.flash_attention import kernel_mesh
    from perceiver_io_tpu.ops.gathers import gather_table_rows

    devices = np.asarray(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devices, ("data", "fsdp"))
    n, k, b = 300, 150, 8
    idx = jnp.asarray(_keep_rows(n, k, b), jnp.int32)
    r = np.random.default_rng(3)
    table = jnp.asarray(r.normal(size=(n, 128)), jnp.bfloat16)
    cot = jnp.asarray(r.normal(size=(b, k, 128)), jnp.bfloat16)

    def grad(t, i, g):
        return jax.grad(lambda t_: jnp.vdot(gather_table_rows(t_, i).astype(jnp.float32), g.astype(jnp.float32)))(t)

    def sharded(t, i, g):
        with kernel_mesh(mesh, ("data", "fsdp")):
            return grad(t, i, g)

    text = jax.jit(sharded).lower(table, idx, cot).as_text()
    assert "shard_map" in text or "manual" in text
    np.testing.assert_array_equal(np.asarray(jax.jit(sharded)(table, idx, cot)), np.asarray(jax.jit(grad)(table, idx, cot)))


def test_embed_compact_table_grad_matches_the_full_length_route():
    """``embed_compact`` (selection before embedding, kernel-route table
    gradient at 128 channels) against embedding the full length and
    gathering the kept rows: the same rows, and the same gradients of both
    tables."""
    from perceiver_io_tpu.core.adapter import TokenInputAdapterWithRotarySupport
    from perceiver_io_tpu.ops.gathers import gather_rows

    b, n, prefix_len, keep = 2, 200, 160, 80
    adapter = TokenInputAdapterWithRotarySupport(
        vocab_size=50, max_seq_len=n, num_input_channels=128, rotated_channels_per_head=8
    )
    r = np.random.default_rng(4)
    x = jnp.asarray(r.integers(0, 50, size=(b, n)))
    keep_idx = jnp.asarray(_keep_rows(prefix_len, keep, b, seed=5), jnp.int32)
    params = adapter.init(jax.random.PRNGKey(0), x)
    cot = jnp.asarray(r.normal(size=(b, keep + n - prefix_len, 128)), jnp.float32)

    def compact(p):
        emb, _ = adapter.apply(p, x, keep_idx, prefix_len, method=adapter.embed_compact)
        return emb

    def full(p):
        emb, _ = adapter.apply(p, x)
        rows = jnp.concatenate([keep_idx, jnp.broadcast_to(jnp.arange(prefix_len, n), (b, n - prefix_len))], axis=1)
        return gather_rows(emb, rows)

    np.testing.assert_array_equal(np.asarray(compact(params)), np.asarray(full(params)))
    got = jax.jit(jax.grad(lambda p: jnp.vdot(compact(p), cot)))(params)
    want = jax.jit(jax.grad(lambda p: jnp.vdot(full(p), cot)))(params)
    for name in ("pos_embedding", "txt_embedding"):
        np.testing.assert_allclose(
            np.asarray(got["params"][name]["embedding"]), np.asarray(want["params"][name]["embedding"]), atol=1e-5
        )


def test_gather_table_rows_plain_mode_passthrough():
    from perceiver_io_tpu.ops.gathers import gather_table_rows, plain_gathers

    table = jnp.asarray(rng.normal(size=(12, 4)), jnp.float32)
    idx = jnp.asarray(np.sort(np.stack([rng.permutation(12)[:5] for _ in range(2)]), axis=-1))
    with plain_gathers():
        out = gather_table_rows(table, idx)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(jnp.take(table, idx, axis=0)))


def test_debug_unique_indices_catches_duplicates_and_unsorted():
    """The opt-in debug check (ADVICE r5): host-supplied index sets with a
    duplicated row entry silently corrupt the scatter-free VJPs' gradients
    (the inverted map credits only one copy) — under
    ``debug_unique_indices()`` they must raise instead."""
    from perceiver_io_tpu.ops.gathers import (
        debug_unique_indices,
        gather_rows,
        gather_table_rows,
    )

    x = jnp.asarray(rng.normal(size=(2, 10, 4)), jnp.float32)
    table = jnp.asarray(rng.normal(size=(10, 4)), jnp.float32)
    good = jnp.asarray(np.sort(np.stack([rng.permutation(10)[:5] for _ in range(2)]), axis=-1))
    dup = good.at[0, 1].set(good[0, 0])
    unsorted = good[:, ::-1]

    # off by default: duplicates pass through unchecked (trusted input)
    gather_rows(x, dup)

    with debug_unique_indices():
        gather_rows(x, good)
        gather_table_rows(table, good)
        with pytest.raises(ValueError, match="duplicates"):
            gather_rows(x, dup)
        with pytest.raises(ValueError, match="duplicates"):
            gather_table_rows(table, dup)
        with pytest.raises(ValueError, match="sorted"):
            gather_table_rows(table, unsorted)
        # unsortedness is allowed for the batch-row gather (only uniqueness
        # is load-bearing there)
        gather_rows(x, unsorted)
