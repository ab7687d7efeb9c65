"""Packed (slots-major) flash kernels: parity with the heads-major path and
the dense reference, values and gradients (interpret mode on CPU)."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.ops.flash_attention import (
    MASK_VALUE,
    flash_attention,
    flash_attention_packed,
    set_default_flash,
    tile_plan,
)

# the package re-exports a function under the module's name
fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")

B, H, DQK, DV = 2, 4, 16, 16


@pytest.fixture(autouse=True)
def _force_flash():
    set_default_flash(True)
    yield
    set_default_flash(None)


def _data(nq, nkv, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, nq, H * DQK)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, nkv, H * DQK)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, nkv, H * DV)), jnp.float32)
    return q, k, v


def _to_heads(x, d):
    b, n, _ = x.shape
    return x.reshape(b, n, H, d).transpose(0, 2, 1, 3)


def _from_heads(x):
    b, h, n, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, n, h * d)


@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("nq,nkv", [(256, 256), (128, 384), (256, 640)])
def test_packed_matches_heads_major(causal, nq, nkv):
    q, k, v = _data(nq, nkv)
    pad = jnp.zeros((B, nkv), bool).at[:, :3].set(True)
    ref = flash_attention(
        _to_heads(q, DQK), _to_heads(k, DQK), _to_heads(v, DV),
        pad_mask=pad, causal=causal, block_q=128, block_kv=128,
    )
    got = flash_attention_packed(
        q, k, v, num_heads=H, pad_mask=pad, causal=causal, block_q=128, block_kv=128
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(_from_heads(ref)), atol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
def test_packed_grads_match_heads_major(causal):
    nq, nkv = 128, 384
    q, k, v = _data(nq, nkv, seed=1)
    pad = jnp.zeros((B, nkv), bool).at[:, :2].set(True)

    def loss_packed(q_, k_, v_):
        o = flash_attention_packed(
            q_, k_, v_, num_heads=H, pad_mask=pad, causal=causal, block_q=128, block_kv=128
        )
        return jnp.sum(o**2)

    def loss_ref(q_, k_, v_):
        o = flash_attention(
            _to_heads(q_, DQK), _to_heads(k_, DQK), _to_heads(v_, DV),
            pad_mask=pad, causal=causal, block_q=128, block_kv=128,
        )
        return jnp.sum(o**2)

    g_p = jax.grad(loss_packed, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_p, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=1e-4)


@pytest.mark.slow
def test_packed_single_head_wide():
    # 1-head configs (vision-style) with d multiple of 8
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, 128, 136)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 256, 136)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 256, 136)), jnp.float32)
    got = flash_attention_packed(q, k, v, num_heads=1, block_q=128, block_kv=128)
    ref = flash_attention(q.reshape(1, 1, 128, 136),
                          k.reshape(1, 1, 256, 136), v.reshape(1, 1, 256, 136),
                          block_q=128, block_kv=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref[0].transpose(1, 0, 2).reshape(1, 128, 136)), atol=2e-5)


# --- the tile plan (PR 27): what the wrapper chooses from the lengths alone --


def _einsum_packed(q, k, v, h, causal, sm_scale, pad_mask=None):
    """Plain attention on packed operands, the right-aligned causal mask."""
    b, nq, _ = q.shape
    nkv = k.shape[1]
    q4, k4, v4 = (x.reshape(b, x.shape[1], h, -1) for x in (q, k, v))
    s = jnp.einsum("bihc,bjhc->bhij", q4, k4) * sm_scale
    if pad_mask is not None:
        s = jnp.where(pad_mask[:, None, None, :], MASK_VALUE, s)
    if causal:
        hidden = jnp.arange(nkv)[None, :] > jnp.arange(nq)[:, None] + (nkv - nq)
        s = jnp.where(hidden[None, None], MASK_VALUE, s)
    o = jnp.einsum("bhij,bjhc->bihc", jax.nn.softmax(s, axis=-1), v4)
    return o.reshape(b, nq, -1)


@pytest.mark.parametrize(
    "nq,nkv,causal",
    [(1024, 1024, True), (768, 768, True), (1024, 2176, True), (512, 512, False)],
    ids=["causal-1024x1024", "causal-768x768", "right-aligned-1024x2176", "plain-512x512"],
)
def test_default_plan_matches_einsum(nq, nkv, causal):
    """Forward and gradients at the blocks the wrapper picks itself (no
    explicit ``block_q``/``block_kv``): the geometries whose plan PR 27
    changed, and one it must leave alone."""
    h, d = 2, 8
    rng = np.random.default_rng(nq + nkv)
    q, k, v, w = (
        jnp.asarray(rng.normal(size=(1, n, h * d)), jnp.float32) for n in (nq, nkv, nkv, nq)
    )

    def loss(attn):
        return lambda q_, k_, v_: jnp.sum(attn(q_, k_, v_) * w)

    def flash(q_, k_, v_):
        return flash_attention_packed(q_, k_, v_, num_heads=h, causal=causal, sm_scale=d**-0.5)

    def plain(q_, k_, v_):
        return _einsum_packed(q_, k_, v_, h, causal, d**-0.5)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)), np.asarray(plain(q, k, v)), atol=2e-5, rtol=2e-5)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)


# --- the Perceiver AR cross-attention: latents over [kept prefix; latents] ---
#
# The kv window is a kept prefix of any length followed by the latents. 70 and
# 200 end inside a 128-wide kv block (K/V are padded and the bias row masks the
# tail), 1 is the shortest prefix, 128 and 384 are whole blocks.

PREFIXES = [1, 70, 128, 200, 384]
LATENTS = 128


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "plain"])
@pytest.mark.parametrize("prefix", PREFIXES)
def test_right_aligned_prefix_matches_einsum(prefix, causal, dtype):
    q, k, v = (x.astype(dtype) for x in _data(LATENTS, prefix + LATENTS, seed=prefix))
    got = flash_attention_packed(q, k, v, num_heads=H, causal=causal, sm_scale=DQK**-0.5, block_q=128, block_kv=128)
    assert got.dtype == dtype
    want = _einsum_packed(*(x.astype(jnp.float32) for x in (q, k, v)), H, causal, DQK**-0.5)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=tol, rtol=tol)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "plain"])
def test_right_aligned_prefix_gradients_match_einsum(causal):
    prefix = 200
    q, k, v = _data(LATENTS, prefix + LATENTS, seed=9)
    pad = jnp.zeros((B, prefix + LATENTS), bool).at[:, :5].set(True)

    def loss(attn):
        return lambda q_, k_, v_: jnp.sum(attn(q_, k_, v_) ** 2)

    def flash(q_, k_, v_):
        return flash_attention_packed(
            q_, k_, v_, num_heads=H, pad_mask=pad, causal=causal, sm_scale=DQK**-0.5, block_q=128, block_kv=128
        )

    def plain(q_, k_, v_):
        return _einsum_packed(q_, k_, v_, H, causal, DQK**-0.5, pad_mask=pad)

    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5, err_msg=name)


def _today(n_q, n_kv, block_q=None, block_kv=None):
    """The blocks the wrapper chose before PR 27."""
    from perceiver_io_tpu.ops.flash_attention import _choose_block

    return (
        _choose_block(n_q, 1024 if block_q is None else block_q, exact=block_q is not None),
        _choose_block(n_kv, 2048 if block_kv is None else block_kv, exact=block_kv is not None),
    )


@pytest.mark.parametrize(
    "n_q,n_kv,causal,blocks,max_share",
    [
        (1024, 1024, True, (None, None), 0.75),  # latent self-attention of the 16k step
        (768, 768, True, (None, None), 0.75),  # the generator's prompt pass
        (2048, 2048, True, (None, None), 0.75),
        (1024, 8704, True, (None, None), None),  # 16k cross-attention: today's blocks, run whole
        (1024, 2176, True, (None, None), None),
        (512, 512, False, (None, None), None),  # image model: no mask, nothing to skip
        (512, 50176, False, (None, None), None),
        (1024, 1024, True, (256, 256), 0.75),  # explicit blocks stay an upper bound
        (1024, 1024, True, (512, 1024), 0.75),
        (1024, 1024, False, (512, 512), None),
    ],
)
def test_tile_plan(n_q, n_kv, causal, blocks, max_share):
    plan = tile_plan(n_q, n_kv, causal, *blocks)
    assert plan == tile_plan(n_q, n_kv, causal, *blocks)  # a pure function of its arguments
    total = -(-n_q // plan.block_q) * plan.block_q * (-(-n_kv // plan.block_kv) * plan.block_kv) // 128**2
    assert plan.tiles_run + plan.tiles_skipped == total and plan.tiles_masked <= plan.tiles_run
    for got, asked in zip((plan.block_q, plan.block_kv), blocks):
        assert asked is None or got <= asked
    # the grid blocks are the ones of before PR 27: the program around the kernels does not change
    assert (plan.block_q, plan.block_kv) == _today(n_q, n_kv, *blocks)
    # the forward and the bias operand follow from the lengths and the blocks alone (PR 48)
    assert plan.forward == ("plain" if -(-n_kv // plan.block_kv) == 1 else "online")
    assert plan.bias == (n_kv % plan.block_kv != 0) and tile_plan(n_q, n_kv, causal, *blocks, pad_mask=True).bias
    if max_share is None:
        # not worth cutting (or nothing to cut): every tile runs whole
        assert plan.band_rows == 0 and plan.tiles_skipped == 0 and (causal or plan.tiles_masked == 0)
    else:
        # the visible scores of a square causal call are half of all: at most 3/4 are computed
        assert plan.run_share <= max_share
        visible = n_q * (n_q + 1) // 2
        assert plan.tiles_run * 128**2 >= visible


def test_compile_event_carries_the_tile_plans():
    """The plan is fixed at trace time, so it is recorded once per geometry
    where trace-time facts go: the ``compile`` event row."""
    from perceiver_io_tpu.obs.recompile import RecompileTracker

    class Sink:
        rows = []

        def emit(self, kind, **fields):
            self.rows.append((kind, fields))

    @jax.jit
    def step(q, k, v):
        return flash_attention_packed(q, k, v, num_heads=1, causal=True, block_q=256, block_kv=256)

    x = jnp.zeros((1, 512, 8), jnp.float32)
    tracker = RecompileTracker(events=Sink())
    tracker.wrap(step, "step")(x, x, x)
    (kind, fields), = Sink.rows
    row = next(r for r in fields["flash_tiles"] if r["geometry"] == "q512_kv512" and r["causal"])
    assert kind == "compile" and row["block_q"] == 256 and row["tiles_skipped"] == 4 and row["run_share"] == 0.75
    # two kv blocks of 256 and no pad mask: the online forward, no bias operand (PR 48)
    assert (row["forward"], row["bias"], row["backward"]) == ("online", False, "split")


# --- the backward (PR 29): one kernel where the queries are one block --------


def _pallas_eqns(jaxpr, into):
    """The ``pallas_call`` equations under ``jaxpr``, nested calls included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            into.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_eqns(sub, into)
    return into


def _pallas_names(jaxpr, into):
    """Names of the ``pallas_call`` equations under ``jaxpr``, nested calls included."""
    return into + [eqn.params["name"] for eqn in _pallas_eqns(jaxpr, [])]


@pytest.mark.parametrize(
    "nq,nkv,h,d,causal,blocks,padded",
    [
        (512, 512, 2, 16, True, (None, None), False),  # square causal: its one tile is cut into bands
        (256, 640, 2, 16, True, (256, 128), False),  # causal with an offset over five kv blocks
        (128, 384, 2, 16, False, (128, 128), False),
        (256, 600, 2, 16, False, (256, 128), True),  # a pad mask, and kv padded to 640
        (256, 600, 2, 16, True, (256, 128), True),
        (128, 256, 1, 136, False, (128, 128), False),  # one wide head
    ],
    ids=["causal-banded-512x512", "causal-offset-256x640", "plain-128x384", "padded-256x600", "causal-padded-256x600",
         "one-wide-head"],
)
def test_one_kernel_backward_equals_the_split_pair(nq, nkv, h, d, causal, blocks, padded):
    """Same residuals and cotangent through both private backwards: the one
    kernel forms each score band once and must return the pair's dq, dk and
    dv (the same f32 sums in the same order)."""
    rng = np.random.default_rng(nq + nkv + d)
    q, k, v, w = (jnp.asarray(rng.normal(size=(B, n, h * d)), jnp.float32) for n in (nq, nkv, nkv, nq))
    plan = tile_plan(nq, nkv, causal, *blocks)
    assert plan.block_q == nq and plan.backward == "one"
    kf, vf = (fa._pad_to(x, 1, plan.block_kv) for x in (k, v))
    bias = jnp.zeros((B, kf.shape[1]), jnp.float32).at[:, nkv:].set(MASK_VALUE)
    if padded:
        bias = bias.at[:, :3].set(MASK_VALUE)
    statics = (causal, nkv - nq, d**-0.5, plan.block_q, plan.block_kv, h, d, d, f"q{nq}_kv{nkv}")
    _, residuals = fa._flash_packed_fwd(q, kf, vf, bias[:, None, :], *statics)
    one = fa._flash_packed_bwd_one(*statics, residuals, w)
    split = fa._flash_packed_bwd_split(*statics, residuals, w)
    for name, a, b in zip(("dq", "dk", "dv"), one, split):
        assert float(jnp.max(jnp.abs(b))) > 0.1, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-6, err_msg=name)


def test_backward_is_chosen_by_the_number_of_q_blocks():
    """One q block runs ``flash_bwd_*``; two keep ``flash_dq_*`` and
    ``flash_dkv_*``; the plan rows say which. Nothing but the shapes decides."""
    x = jnp.zeros((1, 256, 16), jnp.float32)

    def names(block_q):
        def loss(q, k, v):
            return jnp.sum(flash_attention_packed(q, k, v, num_heads=2, causal=True, block_q=block_q, block_kv=128))

        return sorted(_pallas_names(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x).jaxpr, []))

    def row():
        return next(r for r in fa.tile_plans() if r["geometry"] == "q256_kv256" and r["causal"])

    assert names(256) == ["flash_bwd_q256_kv256", "flash_fwd_q256_kv256"]
    assert (row()["block_q"], row()["backward"]) == (256, "one")
    assert names(128) == ["flash_dkv_q256_kv256", "flash_dq_q256_kv256", "flash_fwd_q256_kv256"]
    assert (row()["block_q"], row()["backward"]) == (128, "split")
    assert {p.backward for p in (tile_plan(1024, 8704, True), tile_plan(1024, 1024, True), tile_plan(512, 512, False))} == {"one"}
    assert tile_plan(2048, 2048, True).backward == "split"


# --- the forward (PR 48): a plain softmax where the keys are one block, a bias operand only where the call has one ---


def _forward_case(nq, nkv, h, d, causal, block_q, pad, dtype):
    """Operands and statics of a call whose (padded) keys are one block, as the wrapper hands them to the
    private forwards: ``pad`` adds a pad mask over the first three keys; keys short of a lane tile are padded
    and masked by the bias row, which such a call keeps."""
    rng = np.random.default_rng(nq + nkv + d)
    q, k, v, w = (jnp.asarray(rng.normal(size=(B, n, h * d)), dtype) for n in (nq, nkv, nkv, nq))
    block_kv = -(-nkv // 128) * 128
    kf, vf = (fa._pad_to(x, 1, block_kv) for x in (k, v))
    bias = None
    if pad or block_kv != nkv:
        bias = jnp.zeros((B, 1, block_kv), jnp.float32).at[:, :, nkv:].set(MASK_VALUE)
        if pad:
            bias = bias.at[:, :, :3].set(MASK_VALUE)
    statics = (causal, nkv - nq, d**-0.5, block_q, block_kv, h, d, d, f"q{nq}_kv{nkv}")
    return (q, kf, vf, bias), statics, w


FORWARD_CASES = {
    # the latent self-attention of the 16k step: one tile of 1024 x 1024 cut into four bands
    "causal-banded-1024x1024": (1024, 1024, 2, 16, True, 1024, False),
    # the image model's latent self-attention: no mask, heads of 128
    "plain-512x512-d128": (512, 512, 2, 128, False, 512, False),
    "padded-into-one-block-256x600": (256, 600, 2, 16, False, 256, True),
    "causal-padded-256x600": (256, 600, 2, 16, True, 256, False),
    "one-wide-head": (128, 256, 1, 136, False, 128, False),
    "two-q-blocks-256x128": (256, 128, 2, 16, False, 128, False),
    # more queries than keys: the first q blocks see no key at all
    "causal-hidden-q-blocks-512x128": (512, 128, 2, 16, True, 128, False),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(FORWARD_CASES))
def test_plain_forward_equals_the_online_forward(case, dtype):
    """Same operands through both private forwards: with one kv block the
    online form's running maximum starts at -inf and its sums at zero, so the
    plain form's ``o`` and ``lse`` are the online form's exactly, and the
    backward of either's residuals is the other's."""
    operands, statics, w = _forward_case(*FORWARD_CASES[case], dtype)
    h = statics[5]
    plain = fa._flash_packed_fwd_plain(*operands, *statics)
    online = fa._flash_packed_fwd_online(*operands, *statics)
    for name, a, b in zip(("o", "lse"), plain, online):
        assert a.dtype == b.dtype and a.shape == b.shape
        if "hidden" not in case:
            assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32)))), name
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32), err_msg=name)
    grads = []
    for out, lse in (plain, online):
        grads.append(fa._flash_packed_bwd(*statics, (*operands, out, fa._slim_lse(lse, h)), w))
    for name, a, b in zip(("dq", "dk", "dv"), *grads):
        if "hidden" not in case:
            assert float(jnp.max(jnp.abs(b.astype(jnp.float32)))) > 0.05, name
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32), err_msg=name)
    assert (grads[0][3] is None) == (operands[3] is None)


@pytest.mark.parametrize(
    "nq,nkv,causal,blocks,forward,backward",
    [
        (256, 256, True, (256, 256), "plain", "one"),
        (256, 256, True, (128, 256), "plain", "split"),
        (256, 512, True, (256, 256), "online", "one"),
        (256, 512, False, (128, 256), "online", "split"),
    ],
    ids=["plain-one", "plain-split", "online-one", "online-split"],
)
def test_kernels_without_a_bias_equal_the_same_call_under_an_all_false_pad_mask(nq, nkv, causal, blocks, forward, backward):
    """A call without a pad mask and with whole key blocks has no bias
    operand in any of its kernels; ``s + 0`` is ``s``, so it returns what the
    same call returns under a pad mask that hides nothing."""
    h, d = 2, 16
    rng = np.random.default_rng(nq + nkv)
    q, k, v, w = (jnp.asarray(rng.normal(size=(B, n, h * d)), jnp.float32) for n in (nq, nkv, nkv, nq))
    plan = tile_plan(nq, nkv, causal, *blocks)
    assert (plan.forward, plan.backward, plan.bias) == (forward, backward, False)

    def run(pad_mask):
        def attn(q_, k_, v_):
            return flash_attention_packed(
                q_, k_, v_, num_heads=h, pad_mask=pad_mask, causal=causal, sm_scale=d**-0.5, block_q=blocks[0], block_kv=blocks[1]
            )

        out, vjp = jax.vjp(attn, q, k, v)
        return (out,) + vjp(w)

    bare, masked = run(None), run(jnp.zeros((B, nkv), bool))
    for name, a, b in zip(("o", "dq", "dk", "dv"), bare, masked):
        assert float(jnp.max(jnp.abs(b))) > 0.05, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


def test_forward_is_chosen_by_the_number_of_kv_blocks():
    """One kv block runs the plain forward (no scratch), two keep the online
    one (m, l and acc); the bias row is an operand only under a pad mask or
    padded keys. Nothing but the shapes and the operands decides; the plan
    rows say which."""
    assert (fa._forward(1), fa._forward(2), fa._forward(7)) == ("plain", "online", "online")
    x = jnp.zeros((1, 256, 16), jnp.float32)

    def fwd(block_kv, pad_mask=None, n_kv=256):
        kv = jnp.zeros((1, n_kv, 16), jnp.float32)
        def attn(q, k, v):
            return flash_attention_packed(q, k, v, num_heads=2, pad_mask=pad_mask, causal=True, block_q=256, block_kv=block_kv)

        (eqn,) = _pallas_eqns(jax.make_jaxpr(attn)(x, kv, kv).jaxpr, [])  # the forward alone: nothing is differentiated
        return eqn.params["grid_mapping"].num_scratch_operands, len(eqn.invars)

    def row(geometry="q256_kv256"):
        rows = [r for r in fa.tile_plans() if r["geometry"] == geometry and r["causal"]]
        return [(r["forward"], r["bias"]) for r in rows]

    fa._TILE_PLANS.clear()
    assert fwd(256) == (0, 3) and row() == [("plain", False)]
    assert fwd(128) == (3, 3) and row() == [("online", False)]
    mask = jnp.zeros((1, 256), bool)
    assert fwd(256, mask) == (0, 4) and fwd(128, mask) == (3, 4)
    # a call with a pad mask and one without are two calls: two rows
    assert row() == [("online", False), ("online", True)]
    # 200 keys are padded to two blocks of 128: online, and the bias row masks the padding
    assert fwd(256, n_kv=200) == (3, 4) and row("q256_kv200") == [("online", True)]
    assert {(p.forward, p.bias) for p in (tile_plan(1024, 1024, True), tile_plan(512, 512, False))} == {("plain", False)}
    assert (tile_plan(1024, 8704, True).forward, tile_plan(768, 768, True).forward) == ("online", "online")
    assert tile_plan(768, 768, True).bias and not tile_plan(768, 16128, True).bias and tile_plan(768, 16128, True, pad_mask=True).bias
