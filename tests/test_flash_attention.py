"""Flash-attention kernel vs plain einsum attention (values and grads).

The kernels run in Pallas interpret mode on CPU; the contract they must meet
is the reference attention math (reference: perceiver/model/core/
modules.py:90-170) with the right-aligned causal mask of modules.py:135-140.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import importlib

from perceiver_io_tpu.ops.flash_attention import MASK_VALUE, flash_attention

# the package re-exports a function under the module's name
fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")


def einsum_attention(q, k, v, pad_mask=None, causal=False, sm_scale=1.0):
    """Plain attention with the same mask semantics (f32 softmax)."""
    nq, nkv = q.shape[2], k.shape[2]
    s = jnp.einsum("bhic,bhjc->bhij", q, k).astype(jnp.float32) * sm_scale
    masked = jnp.zeros((1, 1, 1, nkv), bool)
    if pad_mask is not None:
        masked = masked | pad_mask[:, None, None, :]
    if causal:
        i = jnp.arange(nq)[:, None]
        j = jnp.arange(nkv)[None, :]
        masked = masked | (j > i + (nkv - nq))[None, None]
    s = jnp.where(masked, -0.7 * jnp.finfo(jnp.float32).max, s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhij,bhjc->bhic", p.astype(v.dtype), v)


CASES = [
    # (nq, nkv, causal, padded)
    (256, 256, True, False),  # square causal self-attention
    (256, 640, True, False),  # AR cross-attention (prefix + latents)
    (256, 640, True, True),  # ... with pad mask
    (256, 512, False, True),  # encoder cross-attention, padded input
    (200, 300, True, False),  # non-block-multiple lengths
]


@pytest.mark.parametrize("nq,nkv,causal,padded", CASES)
def test_forward_matches_einsum(rng, nq, nkv, causal, padded):
    b, h, d = 2, 2, 16
    q = jnp.asarray(rng.normal(size=(b, h, nq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, nkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, nkv, d)), jnp.float32)
    pad = jnp.asarray(rng.random((b, nkv)) < 0.2) if padded else None

    out = flash_attention(q, k, v, pad_mask=pad, causal=causal, sm_scale=d**-0.5,
                          block_q=128, block_kv=128)
    ref = einsum_attention(q, k, v, pad_mask=pad, causal=causal, sm_scale=d**-0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("nq,nkv,causal,padded", CASES[:3])
def test_gradients_match_einsum(rng, nq, nkv, causal, padded):
    b, h, d = 1, 2, 16
    q = jnp.asarray(rng.normal(size=(b, h, nq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, nkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, nkv, d)), jnp.float32)
    pad = jnp.asarray(rng.random((b, nkv)) < 0.2) if padded else None
    w = jnp.asarray(rng.normal(size=(b, h, nq, d)), jnp.float32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, pad_mask=pad, causal=causal, sm_scale=d**-0.5,
                            block_q=128, block_kv=128)
        return jnp.sum(o * w)

    def loss_ref(q, k, v):
        return jnp.sum(einsum_attention(q, k, v, pad_mask=pad, causal=causal, sm_scale=d**-0.5) * w)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-5, rtol=5e-5)


def test_bfloat16_forward(rng):
    b, h, nq, nkv, d = 1, 2, 256, 512, 32
    q = jnp.asarray(rng.normal(size=(b, h, nq, d)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(b, h, nkv, d)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(b, h, nkv, d)), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, sm_scale=d**-0.5, block_q=128, block_kv=128)
    ref = einsum_attention(q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
                           causal=True, sm_scale=d**-0.5)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=3e-2, rtol=3e-2
    )


def test_odd_head_dims_match_einsum(rng):
    """Head widths that are not multiples of 8 are zero-padded inside the
    wrapper (the vision classifier's qk width 261 — pixels + Fourier bands —
    takes this path); values and gradients must match the dense reference."""
    b, h, nq, nkv, d_qk, d_v = 1, 2, 256, 384, 37, 21
    q = jnp.asarray(rng.normal(size=(b, h, nq, d_qk)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, nkv, d_qk)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, nkv, d_v)), jnp.float32)

    out = flash_attention(q, k, v, causal=True, sm_scale=d_qk**-0.5,
                          block_q=128, block_kv=128)
    ref = einsum_attention(q, k, v, causal=True, sm_scale=d_qk**-0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def f(fn):
        def loss(q, k, v):
            o = fn(q, k, v)
            return (o.astype(jnp.float32) ** 2).sum()
        return jax.grad(loss, argnums=(0, 1, 2))

    g_flash = f(lambda q, k, v: flash_attention(
        q, k, v, causal=True, sm_scale=d_qk**-0.5, block_q=128, block_kv=128))(q, k, v)
    g_ref = f(lambda q, k, v: einsum_attention(q, k, v, causal=True, sm_scale=d_qk**-0.5))(q, k, v)
    for a, r in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), atol=5e-5, rtol=5e-5)


def test_fast_kernel_flags_context_scoped():
    """Feature flags are contextvars: scoped by the context manager, reset on
    exit, invisible to other threads — no mutable module global reaches
    trace time (VERDICT r3)."""
    import threading

    from perceiver_io_tpu.ops.flash_attention import (
        ALL_FEATURES,
        fast_features,
        fast_kernels,
        set_fast_kernels,
    )

    assert fast_features() == frozenset()
    with fast_kernels(["paged"]):
        assert fast_features() == {"paged"}
        seen = {}
        t = threading.Thread(target=lambda: seen.setdefault("f", fast_features()))
        t.start()
        t.join()
        assert seen["f"] == frozenset()  # fresh thread, fresh context
        with fast_kernels(False):
            assert fast_features() == frozenset()
        with fast_kernels(True):
            assert fast_features() == ALL_FEATURES == {"paged"}
        assert fast_features() == {"paged"}
    assert fast_features() == frozenset()

    import pytest as _pytest

    with _pytest.raises(ValueError, match="unknown kernel features"):
        set_fast_kernels(["warp_speed"])


@pytest.mark.parametrize(
    "nq,nkv,d,causal,padded",
    [(256, 640, 16, True, False), (128, 600, 16, False, True), (128, 384, 24, False, False), (256, 256, 16, True, True)],
    ids=["causal-offset-256x640", "padded-128x600", "wide-128x384", "causal-square-padded"],
)
def test_one_kernel_backward_equals_the_split_pair(rng, nq, nkv, d, causal, padded):
    """Heads-major calls whose queries are one block: the one backward kernel
    returns the dkv + dq pair's gradients on the same residuals."""
    b, h, block_kv = 2, 2, 128
    q, k, v, w = (jnp.asarray(rng.normal(size=(b * h, n, d)), jnp.float32) for n in (nq, nkv, nkv, nq))
    kf, vf = (fa._pad_to(x, 1, block_kv) for x in (k, v))
    bias = jnp.zeros((b, kf.shape[1]), jnp.float32).at[:, nkv:].set(MASK_VALUE)
    if padded:
        bias = bias.at[:, :3].set(MASK_VALUE)
    statics = (causal, nkv - nq, d**-0.5, nq, block_kv, h, f"q{nq}_kv{nkv}")
    _, residuals = fa._flash_fwd(q, kf, vf, bias[:, None, :], *statics)
    one = fa._flash_bwd_one(*statics, residuals, w)
    split = fa._flash_bwd_split(*statics, residuals, w)
    for name, x, y in zip(("dq", "dk", "dv"), one, split):
        assert float(jnp.max(jnp.abs(y))) > 0.1, name
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6, rtol=1e-6, err_msg=name)


def test_one_q_block_gradients_match_einsum(rng):
    """The public call at one q block (what the image model's cross-attention
    runs) takes the one-kernel backward and matches plain attention."""
    b, h, nq, nkv, d = 1, 2, 128, 384, 16
    q, k, v = (jnp.asarray(rng.normal(size=(b, h, n, d)), jnp.float32) for n in (nq, nkv, nkv))
    pad = jnp.asarray(rng.random((b, nkv)) < 0.2)

    def loss(attn, **blocks):
        return lambda q_, k_, v_: jnp.sum(attn(q_, k_, v_, pad_mask=pad, sm_scale=d**-0.5, **blocks) ** 2)

    flash = loss(flash_attention, block_q=128, block_kv=128)

    def pallas_names(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from pallas_names(sub)

    names = sorted(pallas_names(jax.make_jaxpr(jax.grad(flash, argnums=(0, 1, 2)))(q, k, v).jaxpr))
    assert names == ["flash_bwd_q128_kv384", "flash_fwd_q128_kv384"]
    got = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(einsum_attention), argnums=(0, 1, 2))(q, k, v)
    for x, y in zip(got, want):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=5e-4, rtol=1e-4)
