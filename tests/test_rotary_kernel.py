"""The lane-rotating rotary kernel (ops/rotary.py) against the path it
replaced at the packed call sites, ``core.position.apply_rotary_pos_emb`` on
the (B, N, H, d) view: interpret mode on CPU.

Bits are compared in a child process held to an instruction set without FMA
(``python tests/test_rotary_kernel.py``, once a module): XLA's CPU backend
contracts ``a * b + c`` where its vectoriser pleases, so two programs of the
same arithmetic differ in the last bit by how their loops were cut; without
the instruction both round every product, as written. ``CHECKS`` names what
the child runs, and ``test_bits`` has one case for each."""

import importlib
import json
import os
import subprocess
import sys
import traceback

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.core import attention
from perceiver_io_tpu.core.attention import MultiHeadAttention, init_kv_cache, prefill_mode, rotate_slots_major
from perceiver_io_tpu.core.position import apply_rotary_pos_emb, frequency_position_encoding, positions
from perceiver_io_tpu.ops import rotary

DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}
# rows: under one block and no multiple of the chunk; several blocks and a part of one; R < d and R == d
GEOMETRIES = [(2, 300, 8, 64, 32), (2, 300, 2, 64, 64), (1, 1040, 8, 64, 32), (2, 48, 1, 128, 128)]
CHECKS = {}  # name -> (function, arguments): what the child process runs


def check(name, *argsets):
    def register(f):
        for args in argsets or [()]:
            CHECKS[name.format(*args)] = (f, args)
        return f

    return register


def bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def same_bits(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_array_equal(bits(x), bits(y))


def case(b, n, h, d, r, dtype, seed=0):
    """A (B, N, H, d) tensor, a cotangent, and angles at per-row shifted positions."""
    kt, kg = jax.random.split(jax.random.PRNGKey(seed))
    t = jax.random.normal(kt, (b, n, h, d), jnp.float32).astype(dtype)
    g = jax.random.normal(kg, (b, n, h, d), jnp.float32).astype(dtype)
    pos = positions(b, n, shift=7 * jnp.arange(b, dtype=jnp.int32)[:, None])
    return t, g, frequency_position_encoding(pos, r)


@jax.jit
def present(t, pe):
    return apply_rotary_pos_emb(t, pe[:, :, None, :])


@jax.jit
def kernel(t, pe):
    return rotate_slots_major(t, pe, True)


def grad_of(rotate):
    return jax.jit(lambda t, g, pe: jax.vjp(lambda x: rotate(x, pe), t)[1](g)[0])


def pallas_calls(jaxpr):
    """Names of the ``pallas_call`` equations under ``jaxpr``, nested calls included."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += pallas_calls(sub)
    return names


@check("forward-{0}-{1}", *[(g, dt) for g in GEOMETRIES for dt in DTYPES])
def forward_is_the_present_path(geometry, dtype):
    t, _, pe = case(*geometry, DTYPES[dtype])
    assert rotary.rotary_supported(t.shape, pe.shape)
    out = kernel(t, pe)
    assert out.dtype == t.dtype and out.shape == t.shape
    same_bits(out, present(t, pe))


@check("gradient-{0}-{1}", *[(g, dt) for g in GEOMETRIES[:3] for dt in DTYPES])
def gradient_is_the_kernel_with_sin_negated_and_the_present_paths(geometry, dtype):
    b, n, h, d, r = geometry
    t, g, pe = case(*geometry, DTYPES[dtype], seed=1)
    cs = rotary.rotary_table(rotary.rotary_angles(pe), d)
    negated = jnp.concatenate([cs[..., :d], -cs[..., d:]], axis=-1)
    got = grad_of(kernel)(t, g, pe)
    same_bits(got, rotary._rotary_call(g.reshape(b, n, h * d), negated, heads=h, rotate_dim=r, transpose=False).reshape(g.shape))
    want = np.asarray(grad_of(present)(t, g, pe), np.float32)
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - (7 if dtype == "bf16" else 23))
    assert (np.abs(np.asarray(got, np.float32) - want) <= ulp).all()


@check("inf-in-pass-through-channel-{0}", (32,), (33,), (63,))
def inf_in_a_pass_through_channel_stays_and_makes_no_nan(channel):
    t, _, pe = case(2, 64, 2, 64, 32, jnp.bfloat16)
    t = t.at[:, :, :, channel].set(jnp.inf)
    out = kernel(t, pe)
    assert np.isposinf(np.asarray(out, np.float32)[..., channel]).all()
    assert np.isfinite(np.delete(np.asarray(out, np.float32), channel, axis=-1)).all()
    same_bits(out, present(t, pe))


ENGAGES = [
    ((2, 64, 2, 64, 32), True),
    ((2, 64, 2, 32, 16), False),  # 64 channels: off the 128 lanes (the tests' micro geometry)
    ((2, 64, 3, 64, 32), False),  # 192 channels
    ((2, 1, 2, 64, 32), False),  # a decode step's one row
    ((2, 8, 2, 64, 32), False),  # a speculative span: under a tile of rows
]


@check("fallback-agrees-{0}", *[(g,) for g, _ in ENGAGES])
def the_fallback_agrees(geometry):
    t, _, pe = case(*geometry, jnp.bfloat16)
    same_bits(kernel(t, pe), present(t, pe))


@check("angles-of-one-row-for-the-batch")
def angles_of_another_batch_shape_take_the_fallback_and_agree():
    t, _, pe = case(2, 64, 2, 64, 32, jnp.bfloat16)
    same_bits(kernel(t, jnp.broadcast_to(pe[:1], pe.shape)), kernel(t, pe[:1]))


def attention_case(path, dtype):
    """``MultiHeadAttention`` through its packed flash route, cache-free
    (``packed``) and as the prompt pass that fills an empty cache
    (``prefill``): a function of fresh identity a call, so nothing traced
    before a patch is reused after it, and its arguments."""
    b, n_q, n_kv, h, c, r = 2, 128, 256, 2, 128, 32
    dtype = DTYPES[dtype]
    mha = MultiHeadAttention(
        num_heads=h, num_q_input_channels=c, num_kv_input_channels=c, causal_attention=True, dtype=dtype, use_flash=True
    )
    kq, kkv = jax.random.split(jax.random.PRNGKey(2))
    x_q = jax.random.normal(kq, (b, n_q, c), jnp.float32).astype(dtype)
    x_kv = jax.random.normal(kkv, (b, n_kv, c), jnp.float32).astype(dtype)
    rope_k = frequency_position_encoding(positions(b, n_kv, shift=jnp.asarray([[0], [5]], jnp.int32)), r)
    params = mha.init(jax.random.PRNGKey(3), x_q, x_kv)

    def run(params, x_q, x_kv, rope_k):
        rope_q = rope_k[:, -n_q:]
        if path == "packed":
            out = mha.apply(params, x_q, x_kv, rope_q=rope_q, rope_k=rope_k)
            return out.last_hidden_state, ()
        with prefill_mode():
            cache = init_kv_cache(b, n_kv + 64, c, c, dtype)
            out = mha.apply(params, x_q, x_kv, rope_q=rope_q, rope_k=rope_k, kv_cache=cache)
        return out.last_hidden_state, (out.kv_cache.k, out.kv_cache.v)

    return run, (params, x_q, x_kv, rope_k)


def without_the_kernel(f, *args):
    supported = attention.rotary_supported
    attention.rotary_supported = lambda *_: False
    try:
        return f(*args)
    finally:
        attention.rotary_supported = supported


@check("attention-{0}-{1}", *[(p, dt) for p in ("packed", "prefill") for dt in DTYPES])
def attention_is_the_same_with_and_without_the_kernel(path, dtype):
    run, args = attention_case(path, dtype)
    with_kernel = jax.jit(run)(*args)
    run, args = attention_case(path, dtype)
    same_bits(with_kernel, without_the_kernel(jax.jit(run), *args))


@check("kernel-mesh")
def under_a_kernel_mesh_the_kernel_runs_per_batch_shard():
    """As the flash kernels do (GSPMD cannot partition a Mosaic call): a
    shard_map over the batch, values and gradient the single-device ones."""
    from jax.sharding import Mesh

    fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")
    t, g, pe = case(4, 64, 2, 64, 32, jnp.bfloat16)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("data", "fsdp"))

    def both(t, g, pe):
        out, vjp = jax.vjp(lambda x: rotate_slots_major(x, pe, True), t)
        return out, vjp(g)[0]

    def sharded(t, g, pe):
        with fa.kernel_mesh(mesh, ("data", "fsdp")):
            return both(t, g, pe)

    assert "shard_map" in str(jax.make_jaxpr(sharded)(t, g, pe))
    same_bits(jax.jit(sharded)(t, g, pe), jax.jit(both)(t, g, pe))


@pytest.fixture(scope="module")
def child_results():
    """Every check of ``CHECKS`` run by one child process without FMA: ``{name: "ok" or a traceback}``."""
    flags = f"{os.environ.get('XLA_FLAGS', '')} --xla_cpu_max_isa=AVX".strip()
    env = dict(os.environ, XLA_FLAGS=flags, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join([root, env.get("PYTHONPATH", "")])
    done = subprocess.run([sys.executable, os.path.abspath(__file__)], env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.splitlines()[-1])


@pytest.mark.parametrize("name", sorted(CHECKS))
def test_bits(name, child_results):
    assert child_results[name] == "ok", child_results[name]


def test_no_gradient_reaches_the_angles():
    t, g, pe = case(2, 64, 2, 64, 32, jnp.float32)
    d_pe = jax.grad(lambda pe: (rotate_slots_major(t, pe, True) * g).sum())(pe)
    assert not np.asarray(d_pe).any()


@pytest.mark.parametrize("geometry,engages", ENGAGES, ids=lambda v: str(v))
def test_the_kernel_engages_by_shape(geometry, engages):
    b, n, h, d, r = geometry
    t, _, pe = case(*geometry, jnp.bfloat16)
    assert rotary.rotary_supported(t.shape, pe.shape) == engages
    names = pallas_calls(jax.make_jaxpr(lambda t, pe: rotate_slots_major(t, pe, True))(t, pe).jaxpr)
    assert names == ([rotary.rotary_kernel_name("fwd", n, h * d)] if engages else [])
    # where the caller's kernels may not run (flash off: the CPU, a test's choice), the present path
    assert not pallas_calls(jax.make_jaxpr(lambda t, pe: rotate_slots_major(t, pe, False))(t, pe).jaxpr)
    assert not rotary.rotary_supported(t.shape, pe[:1].shape)


@pytest.mark.parametrize("path", ["packed", "prefill"])
def test_attention_rotates_queries_and_keys_by_the_kernel(path):
    run, args = attention_case(path, "bf16")
    calls = pallas_calls(jax.make_jaxpr(run)(*args).jaxpr)
    assert sorted(n for n in calls if n.startswith("rotary")) == ["rotary_fwd_n128_c128", "rotary_fwd_n256_c128"]
    run, args = attention_case(path, "bf16")
    calls = without_the_kernel(lambda: pallas_calls(jax.make_jaxpr(run)(*args).jaxpr))
    assert calls and not [n for n in calls if n.startswith("rotary")]


if __name__ == "__main__":
    results = {}
    for name, (f, args) in CHECKS.items():
        try:
            f(*args)
            results[name] = "ok"
        except Exception:  # reported to the parent's case of that name
            results[name] = traceback.format_exc()[-3000:]
    print(json.dumps(results))
