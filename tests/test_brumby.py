"""The decoder-only model under its sixth configuration (the Brumby family:
power retention layers alone on the grouped-query skeleton, a dense SwiGLU in
every layer, an untied head) against its plain reference, at tiny widths that
keep the published shape: hidden 64, 4 query heads on 2 key-value heads of 16
(a feature map of 9 tiles of 16: 144 rows for 136 distinct features), q/k
norms, a rotary at theta 1e6, 3 layers.

Float32 products at "highest" precision on both sides, so the program (the
recurrent or the chunked form over a state) and ``benchmarks/reference/brumby.py``
(the attention form over whole rows) differ in the order of float32 sums, and
in one thing more: the state form computes a weight ``(q . k)^2`` twice, once
through ``S`` and once through ``z``, each time as a sum of 144 signed products of
magnitude up to ``|q|^2 |k|^2``, so numerator and denominator carry different
roundings of it (the attention form divides a weight by itself). Where a row
has many keys that is the float32 rounding, 5e-4 absolute on logits of
magnitude up to about 10 (``TOL``); at a row's first ``FIRST`` positions the
quotient is of one to four such squares and carries their cancellation,
``|q|^2 |k|^2 / (q . k)^2`` times the rounding, so those are compared at a
hundred times that. The reference with bfloat16 products moves the logits by a
hundred tolerances, each fault of the mechanism by more, and tests say so. The
gates' biases are seeded as the benchmark's family seeds them
(``families/brumby.py::remembering``, here forgetting 1e-3 to 1e-1 a token), so
the state remembers the whole of these sequences and a token's gate still
depends on the token."""

import dataclasses
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families.brumby import remembering
from benchmarks.lib import brumby_cost
from benchmarks.lib.weights import flat_dict
from benchmarks.reference import brumby as reference
from perceiver_io_tpu import generation
from perceiver_io_tpu.core import retention
from perceiver_io_tpu.core.cache import RecurrentState, RetentionState, init_retention_state
from perceiver_io_tpu.generation import GenerationConfig, make_generate_fn
from perceiver_io_tpu.models.text import decoder_lm
from perceiver_io_tpu.models.text.decoder_lm import DecoderLanguageModel, DecoderLanguageModelConfig
from perceiver_io_tpu.obs import xplane
from perceiver_io_tpu.ops import power_retention as pr

fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")  # the package exports a function of that name

TOL = 5e-4
FIRST = 4  # a row's first positions: few keys under each quotient (the module docstring)
VOCAB = 96
FORGET = (1e-3, 1e-1)
SCOPES = ("ret/proj", "ret/gate", "ret/chunk", "ret/update", "ret/out")


def tiny_config(**kw) -> DecoderLanguageModelConfig:
    depth = kw.pop("num_hidden_layers", 3)
    base = dict(
        vocab_size=VOCAB, hidden_size=64, num_hidden_layers=depth, first_k_dense_replace=depth, intermediate_size=96,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16, layer_types=("power_retention",) * depth, qk_norm=True,
        rope_scaling=None, rope_theta=1e6, init_scale=0.3, max_position_embeddings=512,
    )
    base.update(kw)
    return DecoderLanguageModelConfig(**base)


def reference_cfg(config: DecoderLanguageModelConfig) -> dict:
    return dataclasses.asdict(config)


def seeded(config, seed: int, batch: int = 2, n: int = 13):
    """The model, its weights drawn from ``seed`` with the gates' biases as the family hands them on, and prompts."""
    model = DecoderLanguageModel(config)
    k_ids, k_init = jax.random.split(jax.random.PRNGKey(seed))
    ids = jax.random.randint(k_ids, (batch, n), 0, config.vocab_size)
    params = model.init(k_init, ids)
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: remembering(getattr(path[-1], "key", ""), leaf, config.init_scale, *FORGET), params)
    return model, params, ids


def served_logits(model, params, ids, new_tokens: int, cache_dtype=jnp.float32):
    """Greedy decoding through the generator's own decoder (prompt pass, then
    one-token steps over the states): the logits the tokens were read from, (B,
    new_tokens, V), the tokens, and the states at the end."""
    decoder = generation._decoder_of(model)
    prefill = jax.jit(lambda p, i: decoder.prefill(p, i, None, 1, new_tokens, cache_dtype))
    step = jax.jit(lambda p, w, t: decoder.step(p, w, (), t))
    logits, window, consts = prefill(params, ids)
    assert consts == ()
    out, tokens = [logits[:, -1]], []
    for _ in range(new_tokens - 1):
        tokens.append(jnp.argmax(out[-1], axis=-1))
        logits, window = step(params, window, tokens[-1])
        out.append(logits[:, -1])
    tokens.append(jnp.argmax(out[-1], axis=-1))
    return np.stack([np.asarray(o) for o in out], axis=1), np.stack([np.asarray(t) for t in tokens], axis=1), window[0]


def served_gap(model, params, ids, config, new_tokens: int = 6) -> float:
    """The widest difference between the served logits and the reference's full forward over the same tokens."""
    got, tokens, _ = served_logits(model, params, ids, new_tokens)
    full = np.concatenate([np.asarray(ids), tokens[:, :-1]], axis=1)
    want = np.asarray(reference.logits(flat_dict(params), jnp.asarray(full), reference_cfg(config), last=new_tokens))
    return float(np.abs(got - want).max())


def short_chunks(monkeypatch, chunk: int = 16):
    """The mixer's prompt pass in chunks of ``chunk`` tokens, so that these short rows cross chunk boundaries."""
    monkeypatch.setattr(retention, "power_retention", lambda q, k, v, g, heads: pr.power_retention(q, k, v, g, heads, chunk))


def retention_args(rows, length, heads, kv_heads, d, seed=0):
    """``q``, ``k``, ``v`` of unit scale and log-gates that forget 2% to 10% a token."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (rows, length, heads, d)), jax.random.normal(ks[1], (rows, length, kv_heads, d)),
            jax.random.normal(ks[2], (rows, length, kv_heads, d)), jax.nn.log_sigmoid(3.0 + jax.random.normal(ks[3], (rows, length, kv_heads))))


def attention_form(q, k, v, log_gamma, eps=pr.EPS):
    """``y_t = sum_j A_tj v_j / (sum_j A_tj + eps)`` with ``A_tj = (q_t . k_j)^2 exp(Lambda_t - Lambda_j)``: a masked (T, T) matrix a head."""
    t, group = q.shape[1], q.shape[2] // k.shape[2]
    lam = jnp.cumsum(log_gamma, axis=1)
    k, v, lam = (jnp.repeat(a, group, axis=2) for a in (k, v, lam))
    lam = lam.transpose(0, 2, 1)
    scores = jnp.einsum("bihd,bjhd->bhij", q, k, precision="highest")
    seen = jnp.tril(jnp.ones((t, t), bool))
    a = jnp.where(seen, scores * scores * jnp.exp(jnp.where(seen, lam[:, :, :, None] - lam[:, :, None, :], 0.0)), 0.0)
    return jnp.einsum("bhij,bjhd->bihd", a, v, precision="highest") / (a.sum(-1).transpose(0, 2, 1)[..., None] + eps)


# ------------------------------------------------------------ the whole model


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("n", [6, 21], ids=["short", "longer"])
def test_full_forward_matches_the_reference(seed, n):
    config = tiny_config()
    model, params, ids = seeded(config, seed, n=n)
    got = np.asarray(model.apply(params, ids))
    want = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config)))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got[:, FIRST:], want[:, FIRST:], atol=TOL, rtol=0)
    np.testing.assert_allclose(got[:, :FIRST], want[:, :FIRST], atol=100 * TOL, rtol=0)


@pytest.mark.parametrize("kernel", [False, True], ids=["lax_scan", "kernels"])
@pytest.mark.parametrize("n", [5, 13, 53], ids=lambda n: f"prompt{n}")
def test_prompt_pass_then_decode_through_the_state_matches_the_references_full_forward(n, kernel, monkeypatch):
    """Every served position: the prompt pass (with the kernels, the chunked
    form in chunks of 16 tokens: a prompt of 53 crosses three boundaries and is
    padded to 64) hands each layer's ``S`` and ``z`` to 11 one-token steps (with
    the kernels, the step's own, over the state in place), and the served
    logits equal the reference's attention form over the whole row. The state
    is float32, of one size whatever the prompt, and carries the length the
    steps read their rotary position off."""
    new = 12
    config = tiny_config()
    model, params, ids = seeded(config, 3, n=n)
    short_chunks(monkeypatch)
    with fa.default_flash(kernel):
        got, tokens, state = served_logits(model, params, ids, new)
    full = np.concatenate([np.asarray(ids), tokens[:, :-1]], axis=1)
    want = np.asarray(reference.logits(flat_dict(params), jnp.asarray(full), reference_cfg(config), last=new))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert [type(c) for c in state] == [RetentionState] * 3
    assert state[0].s.shape == (2, 2, 144, 16) and state[0].z.shape == (2, 2, 9, 16)
    assert state[0].s.dtype == state[0].z.dtype == jnp.float32 and int(state[0].length) == n + new - 1


def test_the_generator_serves_the_same_tokens_and_a_bfloat16_cache_keeps_the_state_float32():
    config = tiny_config()
    model, params, ids = seeded(config, 5, n=9)
    _, tokens, _ = served_logits(model, params, ids, 8)
    out = make_generate_fn(model, config=GenerationConfig(max_new_tokens=8))(params, ids)
    np.testing.assert_array_equal(np.asarray(out[:, 9:]), tokens)
    _, _, state = served_logits(model, params, ids, 3, cache_dtype=jnp.bfloat16)
    assert state[0].s.dtype == jnp.float32 and state[0].z.dtype == jnp.float32


def test_bfloat16_products_are_not_the_model():
    """What the tolerance is for: the reference itself with bfloat16 operands in its products lies a hundred tolerances off."""
    config = tiny_config()
    model, params, ids = seeded(config, 0, n=21)
    want = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config), last=4))
    lower = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config), "bfloat16", last=4))
    assert np.abs(lower - want).max() > 100 * TOL


def _two_halves_without_a_carry(q, k, v, g, heads):
    half = (q.shape[1] // 32) * 16
    if half == 0:
        return pr.power_retention(q, k, v, g, heads, 16)
    y0, _, _ = pr.power_retention(q[:, :half], k[:, :half], v[:, :half], g[:, :half], heads, 16)
    y1, s, z = pr.power_retention(q[:, half:], k[:, half:], v[:, half:], g[:, half:], heads, 16)  # from an empty state
    return jnp.concatenate([y0, y1], axis=1), s, z


def _heads_on_the_next_state(real):
    def update(q, k, v, gamma, s, z, eps=pr.EPS):
        group = q.shape[1] // k.shape[1]
        y, s, z = real(jnp.roll(q, group, axis=1), k, v, gamma, s, z, eps)  # every query head against its neighbour's state
        return jnp.roll(y, -group, axis=1), s, z

    return update


@pytest.mark.parametrize("fault", [
    "a_carry_dropped_at_a_chunk_boundary", "a_state_zeroed_at_the_hand_off", "a_gate_held_at_one", "no_sqrt2_on_the_pairs",
    "position_0_at_every_step", "query_heads_on_the_wrong_state",
])
def test_each_fault_of_the_mechanism_shows(fault, monkeypatch):
    """The program with one thing wrong, against the same reference and tolerance
    as the sound program: a hundred tolerances off or more, each of them."""
    config = tiny_config()
    model, params, ids = seeded(config, 3, n=53)
    kernels = fault == "a_carry_dropped_at_a_chunk_boundary"
    if kernels:  # the chunked form run as two halves, the second from an empty state
        monkeypatch.setattr(retention, "power_retention", _two_halves_without_a_carry)
    elif fault == "a_state_zeroed_at_the_hand_off":  # the prompt pass hands the steps an empty state of the right length
        monkeypatch.setattr(decoder_lm, "RetentionState", lambda s, z, length: RetentionState(jnp.zeros_like(s), jnp.zeros_like(z), length))
    elif fault == "a_gate_held_at_one":  # nothing is forgotten
        monkeypatch.setattr(retention.PowerRetention, "_log_gate", lambda self, x: jnp.zeros(x.shape[:2] + (2,), jnp.float32))
    elif fault == "no_sqrt2_on_the_pairs":  # every tile of the feature map at weight 1
        monkeypatch.setattr(pr, "_coefficients", lambda d: (1.0,) * (d // 2 + 1))
    elif fault == "position_0_at_every_step":  # the rule of before this configuration: a state has no length to read
        monkeypatch.setattr(decoder_lm, "RecurrentState", (RecurrentState, RetentionState))
    else:
        wrong = _heads_on_the_next_state(pr.retention_update)
        monkeypatch.setattr(pr, "retention_update", wrong)
        monkeypatch.setattr(retention, "retention_update", wrong)
    with fa.default_flash(kernels):
        gap = served_gap(model, params, ids, config)
    assert gap > 100 * TOL, gap


def test_a_sound_program_passes_where_each_fault_fails(monkeypatch):
    config = tiny_config()
    model, params, ids = seeded(config, 3, n=53)
    assert served_gap(model, params, ids, config) < TOL
    short_chunks(monkeypatch)
    with fa.default_flash(True):
        assert served_gap(model, params, ids, config) < TOL


def test_a_rotary_stack_of_states_alone_decodes_at_the_right_positions():
    """No cache of this stack grows, so a step's position is the state's own
    length: the prompt's, then one more a step; and a step at another position is another function."""
    config = tiny_config(num_hidden_layers=1)
    model, params, ids = seeded(config, 7, n=9)
    decoder = generation._decoder_of(model)
    _, window, _ = decoder.prefill(params, ids, None, 1, 4, jnp.float32)
    assert int(window[0][0].length) == 9
    logits, (stepped,) = decoder.step(params, window, (), ids[:, 0])
    assert int(stepped[0].length) == 10
    elsewhere = (dataclasses.replace(window[0][0], length=jnp.asarray(3, jnp.int32)),)
    moved, _ = decoder.step(params, (elsewhere,), (), ids[:, 0])
    assert np.abs(np.asarray(moved) - np.asarray(logits)).max() > 100 * TOL
    full = jnp.concatenate([ids, ids[:, :1]], axis=1)
    want = np.asarray(reference.logits(flat_dict(params), full, reference_cfg(config), last=1))
    np.testing.assert_allclose(np.asarray(logits), want, atol=TOL, rtol=0)


def test_what_is_not_built_is_refused():
    with pytest.raises(ValueError, match="even width"):
        tiny_config(head_dim=15)
    with pytest.raises(ValueError, match="num_key_value_heads and head_dim"):
        tiny_config(head_dim=None)
    with pytest.raises(ValueError, match="multi-token-prediction"):
        tiny_config(num_nextn_predict_layers=1)
    config = tiny_config()
    model, params, ids = seeded(config, 0, n=5)
    with pytest.raises(ValueError, match="pad_mask"):
        generation._decoder_of(model).prefill(params, ids, jnp.zeros(ids.shape, bool), 1, 2, jnp.float32)


# ------------------------------------------------------------ the three forms


def test_the_feature_map_squares_the_product_at_128():
    """``phi(q) . phi(k) = (q . k)^2``: 65 tiles of 128 lanes, the squares once,
    every other pair at sqrt 2 once, the 64 antipodal pairs twice at weight 1."""
    q, k = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 128))
    fq, fk = pr.phi(q), pr.phi(k)
    assert fq.shape == (5, 65, 128) and pr.feature_rows(128) == 8320 and 128 * 129 // 2 == 8256
    np.testing.assert_allclose(np.asarray(jnp.sum(fq * fk, axis=(-2, -1))), np.asarray(jnp.sum(q * k, -1) ** 2), rtol=2e-5)
    x = np.asarray(q[0])
    np.testing.assert_allclose(np.asarray(fq[0, 0]), x * x, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(fq[0, 3]), math.sqrt(2) * x * np.roll(x, -3), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(fq[0, 64]), x * np.roll(x, -64), rtol=1e-6)
    with pytest.raises(ValueError, match="even width"):
        pr.phi(jnp.zeros((3, 15)))


@pytest.mark.parametrize("rows,length,heads,kv_heads,d,chunk", [
    (2, 70, 4, 2, 16, 16), (1, 64, 6, 2, 32, 16), (2, 9, 2, 2, 16, 16), (1, 40, 4, 1, 16, 512),
], ids=["five_chunks_the_last_one_padded", "four_whole_chunks", "shorter_than_a_chunk", "one_chunk"])
def test_the_three_forms_agree(rows, length, heads, kv_heads, d, chunk):
    """The attention form (a masked matrix a head), the recurrent form (a
    ``lax.scan`` of a token a step over ``S`` and ``z``) and the chunked form (the
    kernel, interpret mode) on the same inputs: ``y`` at every token and, of
    the last two, the rows' final state. A length that is no multiple of the
    chunk is padded with tokens that add nothing and forget nothing. Float32 on
    every side: the sums run in three orders, 5e-5 of the largest value."""
    q, k, v, g = retention_args(rows, length, heads, kv_heads, d)
    want = np.asarray(attention_form(q, k, v, g))
    y_r, (s_r, z_r) = pr.power_retention_reference(q, k, v, g)
    flat = lambda t: t.reshape(rows, length, -1)  # noqa: E731
    y_c, s_c, z_c = pr.power_retention(flat(q), flat(k), flat(v), g, heads, chunk)
    assert np.abs(want).max() > 0.5
    tol = 5e-5 * np.abs(want).max()
    for got in (np.asarray(y_r), np.asarray(y_c).reshape(want.shape)):
        np.testing.assert_allclose(got[:, FIRST:], want[:, FIRST:], atol=tol, rtol=0)
        np.testing.assert_allclose(got[:, :FIRST], want[:, :FIRST], atol=200 * tol, rtol=0)  # few keys under the quotient
    assert s_c.shape == (rows, kv_heads, pr.feature_rows(d), d) and z_c.shape == (rows, kv_heads, d // 2 + 1, d) and s_c.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_r), atol=2e-5 * float(jnp.abs(s_r).max()), rtol=0)
    np.testing.assert_allclose(np.asarray(z_c), np.asarray(z_r), atol=2e-5 * float(jnp.abs(z_r).max()), rtol=0)
    plan = next(p for p in pr.power_retention_plans() if (p["length"], p["heads"], p["head_dim"]) == (length, heads, d))
    assert plan["chunk"] == min(chunk, -(-length // 16) * 16) and plan["grid_steps"] == kv_heads * -(-length // plan["chunk"])
    assert plan["feature_rows"] == pr.feature_rows(d)
    assert pr.power_ret_kernel_name(4096, 512, 40, 128) == "power_ret_chunk_l4096_c512_h40_d128"


def test_the_state_carries_across_chunks():
    """A row of 70 tokens in chunks of 16: its final state is the state of the
    first 64 tokens carried through the last 6, not the last chunk's alone."""
    q, k, v, g = retention_args(1, 70, 4, 2, 16)
    flat = lambda t: t.reshape(1, t.shape[1], -1)  # noqa: E731
    _, s, z = pr.power_retention(flat(q), flat(k), flat(v), g, 4, 16)
    _, head = pr.power_retention_reference(q[:, :64], k[:, :64], v[:, :64], g[:, :64])
    _, (want, _) = pr.power_retention_reference(q[:, 64:], k[:, 64:], v[:, 64:], g[:, 64:], state=head)
    _, (alone, _) = pr.power_retention_reference(q[:, 64:], k[:, 64:], v[:, 64:], g[:, 64:])
    np.testing.assert_allclose(np.asarray(s), np.asarray(want), atol=1e-4, rtol=0)
    assert np.abs(np.asarray(alone) - np.asarray(want)).max() > 1.0


def test_the_steps_kernel_is_the_update():
    """``power_retention_step`` (interpret mode) against ``retention_update`` from a state that holds 30 tokens."""
    q, k, v, g = retention_args(2, 31, 4, 2, 16, seed=2)
    _, (s, z) = pr.power_retention_reference(q[:, :30], k[:, :30], v[:, :30], g[:, :30])
    last = (q[:, 30], k[:, 30], v[:, 30], jnp.exp(g[:, 30]))
    want = pr.retention_update(*last, s, z)
    got = pr.power_retention_step(*last, s, z)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5 * float(jnp.abs(b).max()), rtol=0)
    assert pr.power_ret_step_kernel_name(32, 40, 128) == "power_ret_step_b32_h40_d128"


def test_differentiation_through_the_kernel_raises():
    q, k, v, g = retention_args(1, 16, 2, 2, 16)
    flat = lambda t: t.reshape(1, 16, -1)  # noqa: E731
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda x: pr.power_retention(x, flat(k), flat(v), g, 2)[0].sum())(flat(q))


# ------------------------------------------------------------------ the mixer


@pytest.mark.parametrize("kernel", [False, True], ids=["lax_scan", "kernels"])
def test_expand_hands_its_state_to_step(kernel, monkeypatch):
    """``expand`` over 20 tokens then ``step`` on the 21st equals ``expand`` over
    all 21, in the output and in the state; from an empty state one step equals a row of one token."""
    config = tiny_config()
    mixer = retention.PowerRetention(config)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 21, config.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(21)[None], (2, 21))
    params = mixer.init(jax.random.PRNGKey(1), u, pos, method="expand")
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: remembering(getattr(path[-1], "key", ""), leaf, config.init_scale, *FORGET), params)
    short_chunks(monkeypatch)
    with fa.default_flash(kernel):
        whole, (s_end, z_end) = mixer.apply(params, u, pos, method="expand")
        head, (s, z) = mixer.apply(params, u[:, :20], pos[:, :20], method="expand")
        last, stepped = mixer.apply(params, u[:, 20:], RetentionState(s, z, jnp.asarray(20, jnp.int32)), pos[:, 20:], method="step")
        empty = init_retention_state(2, 2, 144, 16)
        first, _ = mixer.apply(params, u[:, :1], empty, pos[:, :1], method="step")
    tol = 1e-5 * float(jnp.abs(whole).max())
    np.testing.assert_allclose(np.asarray(head), np.asarray(whole[:, :20]), atol=tol, rtol=0)
    np.testing.assert_allclose(np.asarray(last), np.asarray(whole[:, 20:]), atol=tol, rtol=0)
    np.testing.assert_allclose(np.asarray(stepped.s), np.asarray(s_end), atol=1e-5 * float(jnp.abs(s_end).max()), rtol=0)
    np.testing.assert_allclose(np.asarray(stepped.z), np.asarray(z_end), atol=1e-5 * float(jnp.abs(z_end).max()), rtol=0)
    np.testing.assert_allclose(np.asarray(first), np.asarray(whole[:, :1]), atol=tol, rtol=0)
    assert int(stepped.length) == 21 and (empty.s.shape, empty.z.shape, empty.s.dtype, int(empty.length)) == ((2, 2, 144, 16), (2, 2, 9, 16), jnp.float32, 0)
    # the projections, the norms and the rotary are the grouped-query layer's own, not a copy
    assert retention.PowerRetention._project is decoder_lm.GroupedQueryAttention._project
    assert set(params["params"]) == {"w_q", "w_k", "w_v", "w_o", "q_norm", "k_norm", "w_g", "b_g"}


def test_the_seeded_gate_remembers():
    """``b_g = logit(1 - r)`` with ``r`` log-uniform over the file's range, read off the seeded leaf; other leaves as drawn."""
    noise = 0.02 * jax.random.normal(jax.random.PRNGKey(0), (4096,))
    forget = 1 - jax.nn.sigmoid(remembering("b_g", noise, 0.02, 1e-4, 1e-2))
    assert 1e-4 <= float(forget.min()) < 1.2e-4 and 0.8e-2 < float(forget.max()) <= 1e-2
    quartiles = np.quantile(np.log(np.asarray(forget)), [0.25, 0.5, 0.75])  # log-uniform: the quartiles of the logarithm lie evenly
    assert np.allclose(quartiles, np.log(1e-4) + np.array([0.25, 0.5, 0.75]) * np.log(100), atol=0.15)
    assert remembering("w_g", noise, 0.02, 1e-4, 1e-2) is noise
    # as drawn every state halves a token; made to remember, a fifth of the heads keep a third of a state over 4096 tokens
    assert float(jnp.mean((1 - forget) ** 4096 > 0.3)) > 0.2 and float(jax.nn.sigmoid(noise).max()) < 0.53


# ----------------------------------------------- scopes, taps, the compile row


def test_the_scopes_are_in_the_vocabulary_and_in_the_programs():
    assert set(SCOPES) <= xplane.LAYER_SCOPES and set(SCOPES) <= xplane.CLOSED_LAYERS
    config = tiny_config()
    model, params, ids = seeded(config, 0, n=9)
    decoder = generation._decoder_of(model)
    prompt_pass = jax.jit(lambda p, i: decoder.prefill(p, i, None, 1, 4, jnp.float32)).lower(params, ids).as_text(debug_info=True)
    _, window, _ = decoder.prefill(params, ids, None, 1, 4, jnp.float32)
    step = jax.jit(lambda p, w, t: decoder.step(p, w, (), t)).lower(params, window, ids[:, 0]).as_text(debug_info=True)
    for scope in ("ret/proj", "ret/gate", "ret/chunk", "ret/out"):
        assert f"prefill/DecoderLanguageModel.attend_layer/layer_0.attend/attn.expand/{scope}" in prompt_pass, scope
    assert "ret/update" not in prompt_pass and "ret/chunk" not in step
    for scope in ("ret/proj", "ret/gate", "ret/update", "ret/out"):
        assert f"attn.step/{scope}" in step, scope


def test_the_instrumented_generator_taps_the_state(tmp_path):
    import json

    from perceiver_io_tpu.obs.events import EventLog

    config = tiny_config()
    model, params, ids = seeded(config, 2, n=9)
    events = EventLog(str(tmp_path))
    fn = generation.make_instrumented_generate_fn(model, config=GenerationConfig(max_new_tokens=4), events=events, probes=True)
    out, stats = fn(params, ids)
    assert out.shape == (2, 13) and stats.outcome == "ok"
    snapshot = fn.registry.snapshot()
    snap = {**snapshot["counters"], **snapshot["gauges"]}
    assert snap["ret_state_abs_max"] > 0 and snap["ret_state_nonfinite_total"] == 0 and "ssm_state_abs_max" not in snap
    rows = [json.loads(line) for line in open(tmp_path / "events.jsonl")]
    request = next(r for r in rows if r.get("event") == "request")
    assert request["ret_state_abs_max"] == pytest.approx(snap["ret_state_abs_max"]) and request["ret_state_nonfinite"] == 0
    assert request["kv_cache_frac"] == 0 and "moe_local_share" not in request  # nothing fills; no expert layer
    compile_row = next(r for r in rows if r.get("event") == "compile" and "ret_layers" in r)
    assert compile_row["ret_layers"] == 3 and compile_row["ret_state_dtype"] == "float32" and "kv_cache_full_layers" not in compile_row
    assert compile_row["ret_feature_dim"] == 136 and compile_row["ret_state_rows"] == 144 and compile_row["ret_chunk"] == 16
    assert compile_row["ret_state_bytes"] == 3 * 2 * 2 * (144 * 16 + 144) * 4
    assert isinstance(compile_row["power_retention"], list)  # the kernels' plans traced so far (none where the kernels are off)


def test_every_configuration_taps_only_what_it_has():
    taps = lambda **kw: generation._decoder_of(DecoderLanguageModel(DecoderLanguageModelConfig(**kw))).tap_scopes  # noqa: E731
    small = dict(vocab_size=VOCAB, hidden_size=64, intermediate_size=96, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 n_routed_experts=8, num_experts_per_tok=2, n_group=1, topk_group=1, moe_intermediate_size=32, rope_scaling=None)
    assert taps(**small, num_hidden_layers=2, first_k_dense_replace=2, layer_types=("power_retention",) * 2) == ("spec.*", "ret.*")
    assert taps(**small, num_hidden_layers=2, first_k_dense_replace=2, layer_types=("mamba", "full_attention")) == ("spec.*", "ssm.*")
    assert taps(**small, num_hidden_layers=2, first_k_dense_replace=0, layer_types=("full_attention",) * 2) == ("moe.*", "spec.*")
    assert taps(**small, num_hidden_layers=2, first_k_dense_replace=1, q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8,
                qk_rope_head_dim=8, v_head_dim=8) == ("moe.*", "spec.*")


# ------------------------------------------------------- the published widths


def test_the_published_stage_counts_3_207_594_280_parameters():
    """``jax.eval_shape`` of the program under the benchmark's configuration, against the hand count of ``lib/brumby_cost.py``."""
    from benchmarks import run

    config = run.load_json("configs", "brumby-14b-pp8")
    family = importlib.import_module("benchmarks.families.brumby").Family(config)
    shapes = family.param_shapes(family.model())
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n == brumby_cost.held_params(family.cfg) == 3_207_594_280
    mixer = shapes["params"]["layer_4"]["attn"]
    assert mixer["w_q"].shape == (5120, 5120) and mixer["w_k"].shape == (5120, 1024) and mixer["w_g"].shape == (5120, 8)
    assert mixer["b_g"].shape == (8,) and mixer["q_norm"]["scale"].shape == (128,) and "layer_5" not in shapes["params"]
    assert shapes["params"]["head"].shape == (5120, 151936) and shapes["params"]["embedding"].shape == (151936, 5120)
    row = generation._decoder_of(family.model()).compile_row(32, 4096, 256, jnp.bfloat16)
    assert row["ret_state_rows"] == 8320 and row["ret_feature_dim"] == 8256 and row["ret_chunk"] == pr.CHUNK
    assert row["ret_state_bytes"] == 5 * 32 * 8 * (8320 * 128 + 8320) * 4
