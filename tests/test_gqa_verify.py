"""The speculative step's attention kernel (``ops/gqa_verify.py``) in interpret
mode against what it replaces on the chip: ``RaggedKVCache.write`` /
``RaggedWindowKVCache.write`` (``core/cache.py::_row_scatter``) then
``core.gqa.cached_verify_attention`` under the classes' ``visible``, which stay
the CPU's path and the path of every cache the kernel's rule refuses."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.core.cache import RaggedKVCache, RaggedWindowKVCache, init_ragged_kv_cache, init_ragged_window_kv_cache
from perceiver_io_tpu.core.gqa import GroupedQueryAttention, cached_verify_attention
from perceiver_io_tpu.ops.flash_attention import default_flash
from perceiver_io_tpu.ops.gqa_verify import gqa_verify, gqa_verify_kernel_name, gqa_verify_plans, gqa_verify_supported
from perceiver_io_tpu.ops.mla_absorb import row_tile

HEADS, D, SLOTS, WINDOW = 2, 128, 48, 24  # a ring of 48 slots: a window of 24 and 24 of slack, whole tiles of both dtypes
SM_SCALE = D ** -0.5
# the products take the cache's dtype and accumulate in float32 on both sides; they differ by the order of the sums
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}
DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
KINDS = pytest.mark.parametrize("kind", ["ring", "growing"])


def lengths_of(kind: str, n: int, dtype) -> list:
    """A length a row, each another case of the write-back."""
    tile = row_tile(dtype)
    if kind == "growing":
        # empty; the step straddles two tiles; mid-tile; the row's last slots
        return [0, tile - 1, 21, SLOTS - n]
    # not yet full (``p >= 0``), twice; two tiles; the ring wraps inside the step; wrapped many times; a lap's first slot
    return [0, 5, tile - 1, SLOTS - 1, 1000, 2 * SLOTS]


def make_cache(kind: str, k, v, length):
    if kind == "growing":
        return RaggedKVCache(k=k, v=v, length=length)
    return RaggedWindowKVCache(k=k, v=v, length=length, window=WINDOW)


def operands(kind: str, n: int, group: int, dtype, lengths, seed: int = 0):
    rows = len(lengths) * HEADS
    kq, kk, kv, ck, cv = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(kq, (rows, n * group, D), jnp.float32)
    k_new, v_new = jax.random.normal(kk, (rows, n, D), jnp.float32), jax.random.normal(kv, (rows, n, D), jnp.float32)
    cache = make_cache(kind, jax.random.normal(ck, (rows, SLOTS, D), jnp.float32).astype(dtype),
                       jax.random.normal(cv, (rows, SLOTS, D), jnp.float32).astype(dtype), jnp.asarray(lengths, jnp.int32))
    return q, k_new, v_new, cache


def xla(q, k_new, v_new, cache, group: int):
    n = k_new.shape[1]
    written = cache.write(k_new, v_new)
    return written, cached_verify_attention(q, written, written.visible(n, group), SM_SCALE)


def kernel(q, k_new, v_new, cache):
    window = cache.window if isinstance(cache, RaggedWindowKVCache) else None
    k, v, o = gqa_verify(q, k_new, v_new, cache.k, cache.v, cache.length, heads=HEADS, window=window, sm_scale=SM_SCALE)
    return cache.replace(k=k, v=v), o


def bits(x):
    return np.asarray(x, np.float32)


@DTYPES
@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize("n", [1, 2])
@KINDS
def test_the_kernel_is_write_then_attend(kind, n, group, dtype):
    lengths = lengths_of(kind, n, dtype)
    q, k_new, v_new, cache = operands(kind, n, group, dtype, lengths)
    want_cache, want = xla(q, k_new, v_new, cache, group)
    got_cache, got = kernel(q, k_new, v_new, cache)
    assert got.shape == q.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL[dtype], rtol=0)
    # the caches bit for bit: the new rows at their slots, cast to the cache's dtype; every other slot the input's
    for name in ("k", "v"):
        after, before = getattr(got_cache, name), getattr(cache, name)
        assert after.dtype == dtype
        np.testing.assert_array_equal(bits(after), bits(getattr(want_cache, name)))
        written = np.zeros((len(lengths) * HEADS, SLOTS), bool)
        for r, length in enumerate(lengths):
            for i in range(n):
                slot = (length + i) % SLOTS if kind == "ring" else length + i
                written[r * HEADS:(r + 1) * HEADS, slot] = True
                new = (k_new if name == "k" else v_new)[r * HEADS:(r + 1) * HEADS, i].astype(dtype)
                np.testing.assert_array_equal(bits(after[r * HEADS:(r + 1) * HEADS, slot]), bits(new))
        np.testing.assert_array_equal(bits(after)[~written], bits(before)[~written])
    np.testing.assert_array_equal(np.asarray(got_cache.length), np.asarray(cache.length))  # written, not kept


@DTYPES
@KINDS
def test_a_slot_no_query_sees_carries_no_weight(kind, dtype):
    """Whatever the slots outside a query's mask hold (the growing cache's
    tail, a ring's slack and its not yet written slots), the result is the
    same to the bit."""
    n, group = 2, 4
    lengths = lengths_of(kind, n, dtype)
    q, k_new, v_new, cache = operands(kind, n, group, dtype, lengths)
    seen = np.asarray(cache.write(k_new, v_new).visible(n, group)).any(axis=1)  # (rows, slots): seen by some query of the step
    assert not seen.all()
    unseen = ~jnp.asarray(seen)[:, :, None]
    poison = jnp.asarray(300.0, dtype)
    got = kernel(q, k_new, v_new, cache)[1]
    poisoned = kernel(q, k_new, v_new, cache.replace(k=jnp.where(unseen, poison, cache.k), v=jnp.where(unseen, poison, cache.v)))[1]
    np.testing.assert_array_equal(np.asarray(poisoned), np.asarray(got))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("keeps", [(0, 1, 2), (2, 0, 1), (1, 2, 0), (2, 2, 2)], ids=lambda k: "keep" + "".join(map(str, k)))
@KINDS
def test_three_steps_with_rejected_drafts(kind, keeps, dtype):
    """Three steps in a row, every row keeping another count of its two
    positions a step (``keeps`` turned by a row): a rejected draft's slot is
    written over by the next step and no query sees it in between. The
    kernel's caches stay XLA's bit for bit, its outputs within tolerance, and
    the lengths advance alike."""
    n, group = 2, 8
    tile = row_tile(dtype)
    lengths = [tile - 2, SLOTS - 3, 7] if kind == "ring" else [tile - 2, SLOTS - 7, 7]  # tiles are crossed, the ring wraps
    q, k_new, v_new, cache = operands(kind, n, group, dtype, lengths)
    want_cache = got_cache = cache
    for step in range(3):
        q, k_new, v_new, _ = operands(kind, n, group, dtype, lengths, seed=step + 1)
        want_cache, want = xla(q, k_new, v_new, want_cache, group)
        got_cache, got = kernel(q, k_new, v_new, got_cache)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL[dtype], rtol=0)
        m = jnp.asarray([keeps[(step + r) % 3] for r in range(len(lengths))], jnp.int32)
        want_cache, got_cache = want_cache.keep(m), got_cache.keep(m)
        np.testing.assert_array_equal(bits(got_cache.k), bits(want_cache.k))
        np.testing.assert_array_equal(bits(got_cache.v), bits(want_cache.v))
        np.testing.assert_array_equal(np.asarray(got_cache.length), np.asarray(want_cache.length))
    assert np.asarray(got_cache.length).tolist() == [length + sum(keeps[(step + r) % 3] for step in range(3)) for r, length in enumerate(lengths)]


@pytest.mark.parametrize("shape,dtype,heads,n,group,window,takes", [
    ((512, 1552, 128), jnp.bfloat16, 8, 2, 8, None, True),  # the cell's growing caches
    ((512, 144, 128), jnp.bfloat16, 8, 2, 8, 128, True),  # the cell's rings
    ((512, 1537, 128), jnp.bfloat16, 8, 2, 8, None, False),  # a capacity that is no whole tiles (the parent's)
    ((512, 129, 128), jnp.bfloat16, 8, 2, 8, 128, False),
    ((512, 136, 128), jnp.bfloat16, 8, 2, 8, 128, False),  # whole float32 tiles, half a bfloat16 one
    ((512, 136, 128), jnp.float32, 8, 2, 8, 128, True),
    ((8, 16, 16), jnp.float32, 2, 2, 4, None, False),  # ``tests/test_kexaone.py``'s head width: not whole lanes
    ((512, 128, 128), jnp.bfloat16, 8, 2, 8, 128, False),  # a ring with no slack for the second position
    ((512, 1552, 128), jnp.bfloat16, 8, 17, 8, None, False),  # more positions than a tile: three tiles to write
    ((512, 65536, 128), jnp.bfloat16, 8, 2, 8, None, False),  # a step's blocks past the VMEM limit
    ((510, 1552, 128), jnp.bfloat16, 8, 2, 8, None, False),  # rows that are no whole batch rows of heads
], ids=["cell_full", "cell_ring", "odd_capacity", "odd_ring", "half_tile", "f32_tile", "narrow_head", "no_slack", "many_positions",
        "too_long", "ragged_rows"])
def test_which_caches_the_kernel_takes(shape, dtype, heads, n, group, window, takes):
    assert gqa_verify_supported(shape, dtype, heads, n, group, window) is takes


def test_the_kernels_name_and_plan_say_the_shapes():
    q, k_new, v_new, cache = operands("ring", 2, 8, jnp.bfloat16, [3, 40])
    text = jax.jit(lambda *a: gqa_verify(*a, heads=HEADS, window=WINDOW, sm_scale=SM_SCALE)).lower(
        q, k_new, v_new, cache.k, cache.v, cache.length).as_text(debug_info=True)
    name = gqa_verify_kernel_name(True, 4, 16, SLOTS, D)
    assert name == "gqa_verify_ring_r4_q16_s48_d128" and name in text
    assert gqa_verify_kernel_name(False, 512, 16, 1552, 128) == "gqa_verify_full_r512_q16_s1552_d128"
    plan = next(p for p in gqa_verify_plans() if p["kernel"] == name)
    assert (plan["kind"], plan["rows"], plan["queries"], plan["slots"], plan["head_dim"]) == ("ring", 4, 16, SLOTS, D)
    assert plan["heads_a_step"] == HEADS and plan["grid_steps"] == 2 and plan["vmem_bytes"] > 2 * 2 * HEADS * SLOTS * D * 2


def test_the_compile_row_names_the_path_and_how_the_kernel_cuts_each_cache_kind():
    """The decoder's ``compile`` row (it rides the prompt pass, before a step
    is traced): ``verify``'s own rule a cache kind, and the plans from the shapes."""
    from perceiver_io_tpu.models.text.decoder_lm import DecoderLanguageModel
    from tests.test_kexaone import tiny_config

    decoder = DecoderLanguageModel(tiny_config(head_dim=D, sliding_window=WINDOW)).generation_decoder()  # 8 query heads on 2
    with default_flash(True):
        row = decoder.compile_row(4, 9, 6, jnp.bfloat16)
    assert row["verify_attention"] == {"full": "kernel", "window": "kernel"} and row["kv_cache_window_slack_rows"] == 8
    assert [(p["kernel"], p["kind"], p["grid_steps"], p["heads_a_step"]) for p in row["gqa_verify"]] == [
        ("gqa_verify_full_r8_q8_s16_d128", "full", 4, 2), ("gqa_verify_ring_r8_q8_s32_d128", "ring", 4, 2)]
    off = decoder.compile_row(4, 9, 6, jnp.bfloat16)  # flash off (the CPU's choice): XLA's path, nothing to cut
    assert off["verify_attention"] == {"full": "xla", "window": "xla"} and off["gqa_verify"] == []
    narrow = DecoderLanguageModel(tiny_config()).generation_decoder()  # a head of 16 channels
    with default_flash(True):
        assert narrow.compile_row(4, 9, 6, jnp.bfloat16)["verify_attention"] == {"full": "xla", "window": "xla"}


# ------------------------------------------------------------ through ``verify``


def attention(window: bool, dtype=jnp.float32):
    from tests.test_kexaone import tiny_config

    config = tiny_config(head_dim=D, num_attention_heads=4, num_key_value_heads=HEADS, sliding_window=WINDOW)
    return config, GroupedQueryAttention(config, window=window, dtype=dtype)


def verify_steps(kind: str, slots: int, flash: bool, dtype=jnp.float32, steps: int = 3):
    """``steps`` speculative steps of one layer from an empty cache, rows keeping 2, 1 and 0 of their positions in turn."""
    config, attn = attention(kind == "ring", dtype)
    b, n = 3, 2
    kx, kp = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (steps, b, n, config.hidden_size))
    if kind == "ring":
        cache = init_ragged_window_kv_cache(b, HEADS, WINDOW, slots - WINDOW, D, D, dtype)
    else:
        cache = init_ragged_kv_cache(b, HEADS, slots, D, D, dtype)
    cache = cache.replace(length=jnp.asarray([0, 6, 13], jnp.int32))
    pos = lambda c: c.length[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]  # noqa: E731
    params = attn.init(kp, x[0], cache, pos(cache), method="verify")
    outs = []
    with default_flash(flash):
        # a function of fresh identity a choice: flax's and JAX's trace caches do not see the context variable
        step = jax.jit(lambda p, x_, c, pos_: attn.apply(p, x_, c, pos_, method="verify"))
        text = step.lower(params, x[0], cache, pos(cache)).as_text(debug_info=True)
        for t in range(steps):
            out, cache = step(params, x[t], cache, pos(cache))
            outs.append(out)
            cache = cache.keep(jnp.asarray([(t + r) % 3 for r in (2, 1, 0)], jnp.int32))
    return jnp.stack(outs), cache, text


@DTYPES
@KINDS
def test_verify_runs_the_kernel_where_the_flash_kernels_run(kind, dtype):
    """``verify`` under ``default_flash(True)`` (the chip's choice; here the
    interpreter) at a head of whole lanes and a capacity of whole tiles: the
    kernel is in the program, XLA's write is not, and outputs and caches are
    the XLA path's."""
    want, want_cache, xla_text = verify_steps(kind, SLOTS, False, dtype)
    got, cache, text = verify_steps(kind, SLOTS, True, dtype)
    name = gqa_verify_kernel_name(kind == "ring", 3 * HEADS, 2 * 2, SLOTS, D)
    assert name in text and not re.search(r"gqa_verify_(ring|full)_r\d+", xla_text)
    assert "kv_cache_write" in xla_text and "kv_cache_write" not in text
    np.testing.assert_array_equal(np.asarray(cache.length), np.asarray(want_cache.length))
    np.testing.assert_array_equal(bits(cache.k), bits(want_cache.k))
    np.testing.assert_array_equal(bits(cache.v), bits(want_cache.v))
    np.testing.assert_allclose(bits(got), bits(want), atol=TOL[dtype] * 5, rtol=0)


@KINDS
def test_verify_keeps_xlas_path_for_a_cache_the_rule_refuses(kind):
    """A capacity that is no whole tiles: flash on or off, the same program but for the switch."""
    slots = SLOTS - 3
    assert not gqa_verify_supported((3 * HEADS, slots, D), jnp.float32, HEADS, 2, 2, WINDOW if kind == "ring" else None)
    want, want_cache, xla_text = verify_steps(kind, slots, False)
    got, cache, text = verify_steps(kind, slots, True)
    assert not re.search(r"gqa_verify_(ring|full)_r\d+", text) and "kv_cache_write" in text
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(cache.k), np.asarray(want_cache.k))


def test_a_ring_without_slack_still_refuses_a_second_position():
    config, attn = attention(True)
    cache = init_ragged_window_kv_cache(2, HEADS, WINDOW, 0, D, D)  # 24 slots: whole float32 tiles, no slack
    x, pos = jnp.zeros((2, 2, config.hidden_size)), jnp.zeros((2, 2), jnp.int32)
    with default_flash(True), pytest.raises(ValueError, match="slots of slack"):
        attn.init(jax.random.PRNGKey(0), x, cache, pos, method="verify")
