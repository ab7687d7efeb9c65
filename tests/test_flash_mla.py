"""The expanded latent attention on token-major operands
(``ops/flash_attention.py::flash_attention_mla`` behind
``core/mla.py::expand``) against the path it stands beside, the heads-major
attention on concatenated operands: interpret mode on the CPU.

The rotation's bits are compared in a child process held to an instruction
set without FMA (``python tests/test_flash_mla.py``), as
``tests/test_rotary_kernel.py`` does and for its reason."""

import importlib
import json
import os
import re
import subprocess
import sys
import traceback

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.core import mla
from perceiver_io_tpu.core.mla import MultiHeadLatentAttention
from perceiver_io_tpu.core.position import apply_rotary_interleaved
from tests.test_decoder_lm import tiny_config

fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}  # the flash tests' (``tests/test_flash_attention.py``)
WIDTHS = dict(qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)  # the published ones: the kernel's lane blocks
YARN_POSITIONS = (0, 1, 1023, 163839)  # the ends of a prompt and of YaRN's range (40 x 4096)


def layer(dtype, rows: int, n: int, **kw):
    config = tiny_config(**{**WIDTHS, **kw})
    attn = MultiHeadLatentAttention(config, dtype=dtype)
    kx, kp, kn = jax.random.split(jax.random.PRNGKey(rows + n), 3)
    x = jax.random.normal(kx, (rows, n, config.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None], (rows, n))
    params = attn.init(kp, x, pos, method="expand")
    leaves, tree = jax.tree.flatten(params)  # the norms' scales off 1
    params = jax.tree.unflatten(tree, [p + 0.1 * jax.random.normal(k, p.shape) for p, k in zip(leaves, jax.random.split(kn, len(leaves)))])
    return attn, params, x, pos


def kernels(text: str) -> set:
    """The names of the flash and rotary kernels in a lowered program's text."""
    return set(re.findall(r"\b((?:flash|rotary)_(?:mla_)?fwd_[qn]\d+_\w+?)\b", text))


def expand(attn, params, x, pos, flash: bool):
    """``expand``'s output, cache rows and lowered text with the kernels on or off (ops run one by one: the same
    code gives the same bits whichever program it stands in)."""
    with fa.default_flash(flash):
        out, rows = attn.apply(params, x, pos, method="expand")
        text = jax.jit(lambda p, x_, pos_: attn.apply(p, x_, pos_, method="expand")).lower(params, x, pos).as_text(debug_info=True)
    return out, rows, text


@pytest.mark.parametrize("scaled", [False, True], ids=["plain", "scaled_latents"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("rows", [1, 3])
@pytest.mark.parametrize("n", [256, 512])
@pytest.mark.parametrize("heads", [2, 4])
def test_expand_through_the_kernel_is_the_xla_branch(heads, n, rows, dtype, scaled):
    attn, params, x, pos = layer(dtype, rows, n, num_attention_heads=heads, mla_scale_q_lora=scaled, mla_scale_kv_lora=scaled)
    want, want_rows, xla_text = expand(attn, params, x, pos, False)
    got, got_rows, text = expand(attn, params, x, pos, True)
    name = f"flash_mla_fwd_q{n}_kv{n}_h{heads}"
    assert fa.mla_kernel_name(n, heads) == name and kernels(text) == {name, f"rotary_fwd_n{n}_c{heads * 64}"}
    assert kernels(xla_text) == set()
    assert got.dtype == want.dtype == dtype and got_rows.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got_rows, np.float32), np.asarray(want_rows, np.float32))
    scale = float(np.abs(np.asarray(want, np.float32)).max())
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), atol=TOL[dtype] * max(scale, 1.0), rtol=0)


def test_the_views_taken_in_front_of_a_loop_are_the_weights_columns():
    attn, params, x, pos = layer(jnp.float32, 2, 256, num_attention_heads=2)
    views = mla.expand_views({"layer_0": {"attn": params["params"], "ffn": {"w": jnp.zeros(3)}}}, attn.config, jnp.float32)
    assert sorted(views) == ["layer_0"] and sorted(views["layer_0"]["attn"]) == ["w_uq_nope", "w_uq_rope"]
    w = np.asarray(params["params"]["w_uq"]).reshape(attn.config.q_lora_rank, 2, 192)
    np.testing.assert_array_equal(np.asarray(views["layer_0"]["attn"]["w_uq_nope"]), w[..., :128].reshape(-1, 256))
    np.testing.assert_array_equal(np.asarray(views["layer_0"]["attn"]["w_uq_rope"]), w[..., 128:].reshape(-1, 128))
    with fa.default_flash(True):
        alone = attn.apply(params, x, pos, method="expand")
        handed = attn.apply({**params, mla.VIEWS: views["layer_0"]["attn"]}, x, pos, method="expand")
    for a, b in zip(alone, handed):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def dense(q_nope, q_rope, kv, k_rope, heads: int, sm_scale: float):
    b, n, _ = q_nope.shape
    kv = kv.reshape(b, n, heads, 256)
    s = jnp.einsum("bihc,bjhc->bhij", q_nope.reshape(b, n, heads, 128), kv[..., :128])
    s = (s + jnp.einsum("bihc,bjc->bhij", q_rope.reshape(b, n, heads, 64), k_rope)) * sm_scale
    p = jax.nn.softmax(jnp.where(jnp.arange(n)[None, :] <= jnp.arange(n)[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhij,bjhc->bihc", p, kv[..., 128:]).reshape(b, n, heads * 128)


@pytest.mark.parametrize("block,run,masked,bands", [(128, 10, 4, 0), (256, 12, 8, 0), (512, 12, 12, 256)])
def test_the_kernel_over_several_blocks_against_a_dense_masked_softmax(block, run, masked, bands):
    """Blocks of 128 (the diagonal tile whole, every tile before it unmasked), of 256 (its diagonal tiles whole) and of 512 (the one
    tile in two bands of 256 rows): what the window kernel's plan gives without a window."""
    n, heads = 512, 2
    keys = jax.random.split(jax.random.PRNGKey(block), 4)
    operands = [jax.random.normal(k, (2, n, w)) for k, w in zip(keys, (heads * 128, heads * 64, heads * 256, 64))]
    got = fa.flash_attention_mla(*operands, heads, sm_scale=0.07, block=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense(*operands, heads, 0.07)), atol=2e-6, rtol=0)
    plan = {row["geometry"]: row for row in fa.tile_plans()}[f"q{n}_kv{n}_h{heads}"]
    assert (plan["block_q"], plan["tiles_run"], plan["tiles_masked"], plan["band_rows"]) == (block, run, masked, bands)
    assert plan["backward"] == "none" and plan["tiles_run"] + plan["tiles_skipped"] == 16


def test_the_kernel_has_no_backward_and_says_which_operands_fit():
    ones = lambda *shape: jnp.ones(shape)  # noqa: E731
    q_nope, q_rope, kv, k_rope = ones(1, 128, 256), ones(1, 128, 128), ones(1, 128, 512), ones(1, 128, 64)
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda q: fa.flash_attention_mla(q, q_rope, kv, k_rope, 2).sum())(q_nope)
    with pytest.raises(ValueError, match="do not fit"):
        fa.flash_attention_mla(q_nope, q_rope, kv, k_rope, 4)
    with pytest.raises(ValueError, match="do not fit"):  # the rotary halves of two heads share a lane block
        fa.flash_attention_mla(ones(1, 128, 384), ones(1, 128, 192), ones(1, 128, 768), k_rope, 3)
    with pytest.raises(ValueError, match="whole blocks"):
        fa.flash_attention_mla(ones(1, 192, 256), ones(1, 192, 128), ones(1, 192, 512), ones(1, 192, 64), 2)


@pytest.mark.parametrize("why,n,kw", [
    ("rope_32", 256, dict(qk_rope_head_dim=32)),
    ("nope_64", 256, dict(qk_nope_head_dim=64)),
    ("three_heads", 256, dict(num_attention_heads=3)),
    ("rows_not_blocks", 192, {}),
    ("test_widths", 256, dict(qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=6)),
])
def test_shapes_that_do_not_qualify_run_the_heads_major_path(why, n, kw):
    attn, params, x, pos = layer(jnp.float32, 2, n, **{"num_attention_heads": 2, **kw})
    c = attn.config
    assert not fa.mla_flash_supported(n, c.num_attention_heads, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim)
    want, want_rows, _ = expand(attn, params, x, pos, False)
    got, got_rows, text = expand(attn, params, x, pos, True)
    assert kernels(text) == {f"flash_fwd_q{n}_kv{n}"}
    np.testing.assert_array_equal(np.asarray(got_rows), np.asarray(want_rows))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5 * float(np.abs(np.asarray(want)).max()), rtol=0)


def test_the_cells_shapes_qualify():
    assert fa.mla_flash_supported(1024, 128, 128, 64, 128) and fa.mla_flash_supported(1024, 64, 128, 64, 128)


# ------------------------------------------------------------ the rotation alone, to the bit (the child process)


def bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def rotation_case(dtype):
    """Two heads of 64 rotary channels at 16 positions a row, the ends of YaRN's range among them."""
    inv_freq = MultiHeadLatentAttention(tiny_config(**WIDTHS))._inv_freq()
    pos = jnp.asarray([list(YARN_POSITIONS) + list(range(2, 14)), list(range(40000, 40016))], jnp.int32)
    t = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 2 * 64), jnp.float32).astype(dtype)
    return t, pos, inv_freq


def check_rotation(dtype):
    t, pos, inv_freq = rotation_case(dtype)
    want = apply_rotary_interleaved(t.reshape(2, 16, 2, 64), pos[:, :, None], inv_freq).reshape(t.shape)
    got = mla.rotate_interleaved_packed(t, pos, inv_freq, 2)
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(bits(got), bits(want))
    assert np.abs(np.asarray(got, np.float32) - np.asarray(t, np.float32))[0, 1:].max() > 0.1  # it turns


CHECKS = {"rotation_f32": (check_rotation, (jnp.float32,)), "rotation_bf16": (check_rotation, (jnp.bfloat16,))}


@pytest.fixture(scope="module")
def child_results():
    """Every check of ``CHECKS`` run by one child process without FMA: ``{name: "ok" or a traceback}``."""
    flags = f"{os.environ.get('XLA_FLAGS', '')} --xla_cpu_max_isa=AVX".strip()
    env = dict(os.environ, XLA_FLAGS=flags, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join([root, env.get("PYTHONPATH", "")])
    done = subprocess.run([sys.executable, os.path.abspath(__file__)], env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.splitlines()[-1])


@pytest.mark.parametrize("name", sorted(CHECKS))
def test_the_lane_roll_rotation_is_apply_rotary_interleaved_to_the_bit(name, child_results):
    assert child_results[name] == "ok", child_results[name]


def test_the_rotation_within_rounding_where_products_may_be_fused():
    """This process's instruction set: the same values up to the last bit of a fused multiply-add."""
    t, pos, inv_freq = rotation_case(jnp.float32)
    want = apply_rotary_interleaved(t.reshape(2, 16, 2, 64), pos[:, :, None], inv_freq).reshape(t.shape)
    np.testing.assert_allclose(np.asarray(mla.rotate_interleaved_packed(t, pos, inv_freq, 2)), np.asarray(want), atol=1e-6, rtol=0)


if __name__ == "__main__":
    results = {}
    for name, (f, args) in CHECKS.items():
        try:
            f(*args)
            results[name] = "ok"
        except Exception:  # reported to the parent's case of that name
            results[name] = traceback.format_exc()[-3000:]
    print(json.dumps(results))
