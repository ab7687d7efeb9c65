"""The decoder-only model under its seventh configuration (the Ling 3.0 family:
Kimi delta attention layers beside latent attention as a layer kind, a leading
dense layer, then a share of sigmoid-routed experts with a shared expert, an
untied head) against its plain reference, at tiny widths that keep the
published shape: hidden 64, 4 heads of 16 on q, k and v, a latent of 16 with 16
+ 8 query-key channels, 8 of 16 experts held in 4 groups, 4 layers of which the
third is latent attention.

Float32 products at "highest" precision on both sides, so the program (the
chunked form or the token scan over a transposed state, the absorbed attention
over a cache, the experts by their pairs) and ``benchmarks/reference/ling.py``
(the recurrence token by token over whole rows, expanded attention, the experts
one at a time) differ in the order of float32 sums: ``TOL`` on logits of
magnitude up to about 10. The reference with bfloat16 products moves the logits
by a hundred tolerances, each fault of the mechanism by more, and tests say so.
The gates are seeded as the benchmark's family seeds them
(``families/ling.py::remembering``, here forgetting 1e-3 to 1e-1 a token a
channel), so the state remembers the whole of these sequences and a token's gate
still depends on the token."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families.ling import remembering
from benchmarks.lib import ling_cost
from benchmarks.lib.weights import flat_dict
from benchmarks.reference import deepseek_v3 as dsv3_reference
from benchmarks.reference import ling as reference
from perceiver_io_tpu import generation
from perceiver_io_tpu.core import kda as kda_core
from perceiver_io_tpu.core import mla, moe, ssm
from perceiver_io_tpu.core.cache import DeltaState, LatentCache, init_delta_state
from perceiver_io_tpu.generation import GenerationConfig, make_generate_fn
from perceiver_io_tpu.models.text import decoder_lm
from perceiver_io_tpu.models.text.decoder_lm import DecoderLanguageModel, DecoderLanguageModelConfig
from perceiver_io_tpu.obs import xplane
from perceiver_io_tpu.ops import kda

fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")  # the package exports a function of that name

TOL = 5e-4
VOCAB = 96
FORGET = (1e-3, 1e-1)
KINDS = ("kda", "kda", "latent_attention", "kda")
SCOPES = ("kda/proj", "kda/conv", "kda/gate", "kda/chunk", "kda/update", "kda/out")


def tiny_config(**kw) -> DecoderLanguageModelConfig:
    base = dict(
        vocab_size=VOCAB, hidden_size=64, num_hidden_layers=4, first_k_dense_replace=1, intermediate_size=96, moe_intermediate_size=32,
        num_attention_heads=4, head_dim=16, q_lora_rank=None, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        mla_head_gate=True, n_routed_experts=16, n_held_experts=8, held_experts_start=0, n_shared_experts=1, num_experts_per_tok=4,
        n_group=4, topk_group=2, routed_scaling_factor=2.5, rope_scaling=None, rope_theta=6e6, init_scale=0.3,
        max_position_embeddings=512, layer_types=KINDS,
    )
    base.update(kw)
    return DecoderLanguageModelConfig(**base)


def reference_cfg(config: DecoderLanguageModelConfig) -> dict:
    return dataclasses.asdict(config)


def handed_on(params, config):
    """The tree as the benchmark's family hands it on: the gates made to remember, the router's bias as drawn."""
    flat = remembering(flat_dict(params), config.init_scale, *FORGET, lower_bound=config.kda_lower_bound, bias_scale=1.0)
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(params), list(flat.values()))


def seeded(config, seed: int, batch: int = 2, n: int = 13):
    """The model, its weights drawn from ``seed`` with the gates as the family hands them on, and prompts."""
    model = DecoderLanguageModel(config)
    k_ids, k_init = jax.random.split(jax.random.PRNGKey(seed))
    ids = jax.random.randint(k_ids, (batch, n), 0, config.vocab_size)
    return model, handed_on(model.init(k_init, ids), config), ids


def served_logits(model, params, ids, new_tokens: int, cache_dtype=jnp.float32):
    """Greedy decoding through the generator's own decoder (prompt pass, then
    one-token steps over the states and the cache): the logits the tokens were
    read from, (B, new_tokens, V), the tokens, and the states at the end."""
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


def short_chunks(monkeypatch, chunk: int = 32, sub: int = 16):
    """The mixer's prompt pass in chunks of ``chunk`` tokens, so that these short rows cross chunk boundaries."""
    monkeypatch.setattr(kda_core, "kda_chunked", lambda q, k, v, g, b, heads, taps=None: kda.kda_chunked(q, k, v, g, b, heads, chunk, sub, 2, taps))


def delta_args(rows, length, heads, d, seed=0, at_the_bound=False):
    """Unit ``q`` (scaled) and ``k``, ``v`` of unit scale, log-decays a channel between -5 and 0 (``at_the_bound``: within
    a thousandth of -5, where a chunk's factored decays reach ``exp(75)``), steps around a half."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    shape = (rows, length, heads, d)
    pre = 9.0 + 0.2 * jax.random.normal(ks[3], shape) if at_the_bound else -2.0 + 2.0 * jax.random.normal(ks[3], shape)
    return (unit(jax.random.normal(ks[0], shape)) * d ** -0.5, unit(jax.random.normal(ks[1], shape)), jax.random.normal(ks[2], shape),
            -5.0 * jax.nn.sigmoid(pre), jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3])))


def flat(t):
    return t.reshape(t.shape[0], t.shape[1], -1)


# ------------------------------------------------------------ the whole model


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("n", [6, 21], ids=["short", "longer"])
def test_full_forward_matches_the_reference(seed, n):
    config = tiny_config()
    model, params, ids = seeded(config, seed, n=n)
    got = np.asarray(model.apply(params, ids))
    want = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config)))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("n,kernel", [(53, False), (5, True), (32, True), (53, True)],
                         ids=["prompt53-lax_scan", "prompt5-kernels", "prompt32-kernels", "prompt53-kernels"])
def test_prompt_pass_then_decode_through_the_states_and_the_cache_matches_the_references_full_forward(n, kernel, monkeypatch):
    """Every served position: the prompt pass (with the kernels, the chunked
    form in chunks of 32 tokens and sub-chunks of 16: a prompt of 53 crosses a
    chunk boundary and is padded to 64, one of 32 is one whole chunk, one of 5
    less than a sub-chunk) hands each delta layer's ``S`` and windows and the
    latent layer's rows to 11 one-token steps (with the kernels, the step's
    own, over the state in place), and the served logits equal the reference's
    recurrence over the whole row. The states are float32, transposed, of one
    size whatever the prompt, with no length; the cache carries it."""
    new = 12
    config = tiny_config()
    model, params, ids = seeded(config, 3, n=n)
    short_chunks(monkeypatch)
    with fa.default_flash(kernel):
        got, tokens, state = served_logits(model, params, ids, new)
    full = np.concatenate([np.asarray(ids), tokens[:, :-1]], axis=1)
    want = np.asarray(reference.logits(flat_dict(params), jnp.asarray(full), reference_cfg(config), last=new))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert [type(c) for c in state] == [DeltaState, DeltaState, LatentCache, DeltaState]
    assert state[0].s.shape == (2, 4, 16, 16) and state[0].s.dtype == jnp.float32 and not hasattr(state[0], "length")
    assert state[0].conv_q.shape == state[0].conv_k.shape == state[0].conv_v.shape == (2, 3, 64)
    assert state[2].rows.shape == (2, n + new, 24) and int(state[2].length) == n + new - 1


def test_the_generator_serves_the_same_tokens_and_a_bfloat16_cache_keeps_the_state_float32():
    config = tiny_config()
    model, params, ids = seeded(config, 5, n=9)
    _, tokens, _ = served_logits(model, params, ids, 8)
    out = make_generate_fn(model, config=GenerationConfig(max_new_tokens=8))(params, ids)
    np.testing.assert_array_equal(np.asarray(out[:, 9:]), tokens)
    _, _, state = served_logits(model, params, ids, 3, cache_dtype=jnp.bfloat16)
    assert state[0].s.dtype == jnp.float32 and state[0].conv_k.dtype == jnp.bfloat16 and state[2].rows.dtype == jnp.bfloat16


def test_bfloat16_products_are_not_the_model():
    """What the tolerance is for: the reference itself with bfloat16 operands in its products lies a hundred tolerances off."""
    config = tiny_config()
    model, params, ids = seeded(config, 0, n=21)
    want = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config), last=4))
    lower = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config), "bfloat16", last=4))
    assert np.abs(lower - want).max() > 100 * TOL


def _two_halves_without_a_carry(q, k, v, g, b, heads, taps=None):
    half = (q.shape[1] // 32) * 16
    if half == 0:
        return kda.kda_chunked(q, k, v, g, b, heads, 16, 16, 2, taps)
    o0, _ = kda.kda_chunked(q[:, :half], k[:, :half], v[:, :half], g[:, :half], b[:, :half], heads, 16, 16, 2, taps)
    o1, s = kda.kda_chunked(q[:, half:], k[:, half:], v[:, half:], g[:, half:], b[:, half:], heads, 16, 16, 2, taps)  # from an empty state
    return jnp.concatenate([o0, o1], axis=1), s


def _without_the_correction(q, k, v, g, beta, s):
    """Gated linear attention in the delta rule's place: ``b k k^T`` left out of the update."""
    s = s * jnp.exp(g)[:, :, None, :]
    s = s + (beta[..., None] * v)[..., :, None] * k[..., None, :]
    return jnp.einsum("bhvc,bhc->bhv", s, q, precision="highest"), s


def _hand_on(monkeypatch, wrong):
    """The generator's hand-off with every delta state passed through ``wrong``."""
    real = decoder_lm._Decoder._caches
    monkeypatch.setattr(decoder_lm._Decoder, "_caches", lambda self, *args: tuple(
        wrong(cache) if isinstance(cache, DeltaState) else cache for cache in real(self, *args)))


@pytest.mark.parametrize("fault", [
    "a_carry_dropped_at_a_chunk_boundary", "a_state_zeroed_at_the_hand_off", "the_windows_zeroed_at_the_hand_off",
    "the_correction_term_left_out", "a_decay_held_at_one", "the_held_experts_offset_by_one_group", "no_gate_on_the_attention",
])
def test_each_fault_of_the_mechanism_shows(fault, monkeypatch):
    """The program with one thing wrong, against the same reference and tolerance
    as the sound program: a hundred tolerances off or more, each of them."""
    config = tiny_config()
    model, params, ids = seeded(config, 3, n=53)
    kernels = fault == "a_carry_dropped_at_a_chunk_boundary"
    if kernels:  # the chunked form run as two halves, the second from an empty state
        monkeypatch.setattr(kda_core, "kda_chunked", _two_halves_without_a_carry)
    elif fault == "a_state_zeroed_at_the_hand_off":  # the prompt pass hands the steps an empty state
        _hand_on(monkeypatch, lambda state: state.replace(s=jnp.zeros_like(state.s)))
    elif fault == "the_windows_zeroed_at_the_hand_off":  # the steps' first convolutions see zeros where the prompt's last tokens were
        _hand_on(monkeypatch, lambda state: init_delta_state(2, 4, 16, 4).replace(s=state.s))
    elif fault == "the_correction_term_left_out":
        monkeypatch.setattr(kda, "kda_update", _without_the_correction)
        monkeypatch.setattr(kda_core, "kda_update", _without_the_correction)
    elif fault == "a_decay_held_at_one":  # nothing is forgotten
        real = kda_core.KimiDeltaAttention._gates
        monkeypatch.setattr(kda_core.KimiDeltaAttention, "_gates", lambda self, x: (jnp.zeros_like(real(self, x)[0]), real(self, x)[1]))
    elif fault == "the_held_experts_offset_by_one_group":  # the weights of experts 0 to 7 answer for experts 4 to 11
        model = DecoderLanguageModel(dataclasses.replace(config, held_experts_start=4))
    else:
        monkeypatch.setattr(mla.MultiHeadLatentAttention, "_project_out", lambda self, o, x: self._mm(o, self.w_o))
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


def test_the_leaves_as_drawn_forget_and_a_dropped_carry_goes_unseen_there():
    """Why the family seeds the gate: as ``lib/weights.py`` draws the leaves a channel's log-decay is about -2.5 a
    token, and the first half of a row of 53 tokens moves the last served logits by less than the tolerance."""
    config = tiny_config()
    model = DecoderLanguageModel(config)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 53), 0, VOCAB)
    params = model.init(jax.random.PRNGKey(1), ids)
    mixer = kda_core.KimiDeltaAttention(config)
    layer = lambda p: mixer.apply({"params": p["params"]["layer_0"]["mixer"]}, jnp.ones((1, 4, 64)), method="_gates")[0]  # noqa: E731
    assert -3.5 < float(jnp.mean(layer(params))) < -1.5
    assert -0.2 < float(jnp.mean(layer(handed_on(params, config)))) < -1e-3


def test_layer_types_mix_the_two_kinds_and_what_is_not_built_is_refused():
    for kinds in (("latent_attention", "kda", "kda", "kda"), ("kda", "latent_attention", "latent_attention", "kda")):
        config = tiny_config(layer_types=kinds)
        model, params, ids = seeded(config, 1, n=7)
        assert served_gap(model, params, ids, config, new_tokens=3) < TOL, kinds
    # latent attention in every layer, by its name or by ``layer_types`` ``None``: the same function of the same weights
    config = tiny_config(layer_types=("latent_attention",) * 4)
    model, params, ids = seeded(config, 1, n=7)
    unnamed = DecoderLanguageModel(tiny_config(layer_types=None))
    np.testing.assert_array_equal(np.asarray(model.apply(params, ids)), np.asarray(unnamed.apply(params, ids)))
    with pytest.raises(ValueError, match="beside at least one latent_attention"):
        tiny_config(layer_types=("kda",) * 4)
    with pytest.raises(ValueError, match="mix with each other alone"):
        tiny_config(layer_types=("kda", "latent_attention", "full_attention", "kda"), num_key_value_heads=2)
    with pytest.raises(ValueError, match="a kda layer needs head_dim"):
        tiny_config(head_dim=None)
    with pytest.raises(ValueError, match="a kda layer needs head_dim"):
        tiny_config(kda_lower_bound=0.0)
    with pytest.raises(ValueError, match="multi-token-prediction"):
        tiny_config(num_nextn_predict_layers=1)
    with pytest.raises(ValueError, match="one of"):
        tiny_config(layer_types=("kda", "gated_delta", "latent_attention", "kda"))
    with pytest.raises(ValueError, match="overflow float32"):
        seeded(tiny_config(kda_lower_bound=-9.0), 0)
    model, params, ids = seeded(tiny_config(), 0, n=5)
    with pytest.raises(ValueError, match="pad_mask"):
        generation._decoder_of(model).prefill(params, ids, jnp.zeros(ids.shape, bool), 1, 2, jnp.float32)


# ------------------------------------------------------------ the three forms


@pytest.mark.parametrize("at_the_bound", [False, True], ids=["decays_spread", "decays_at_the_lower_bound"])
@pytest.mark.parametrize("rows,length,heads,d,chunk,sub,block", [
    (2, 70, 4, 16, 32, 16, 2), (1, 64, 6, 32, 32, 16, 3), (2, 9, 2, 16, 32, 16, 1), (1, 40, 4, 16, 128, 16, 4), (1, 52, 2, 16, 64, 8, 2),
    (1, 128, 2, 16, 128, 16, 2),
], ids=["three_chunks_the_last_one_padded", "two_whole_chunks", "shorter_than_a_sub_chunk", "one_chunk_cut_to_three_sub_chunks",
        "sub_chunks_of_8_the_last_one_padded", "one_chunk_of_128"])
def test_the_chunked_form_is_the_recurrence(rows, length, heads, d, chunk, sub, block, at_the_bound):
    """The recurrent form (a ``lax.scan`` of a token a step over ``S``) and the
    chunked form (the kernel, interpret mode) on the same inputs: ``o`` at every
    token and the rows' final state, at lengths that are and are not whole
    chunks and sub-chunks (a length that is no multiple is padded with tokens
    that write nothing and forget nothing), with the log-decays spread over (-5,
    0) and within a thousandth of the lower bound, where a sub-chunk's factored
    decays reach ``exp(75)``. Float32 on both sides: 1e-5 of the largest value."""
    q, k, v, g, b = delta_args(rows, length, heads, d, at_the_bound=at_the_bound)
    want_o, want_s = kda.kda_reference(q, k, v, g, b)
    o, s = kda.kda_chunked(flat(q), flat(k), flat(v), flat(g), b, heads, chunk, sub, block)
    assert (float(g.max()) < -4.99) == at_the_bound and float(g.min()) > -5.0
    assert o.shape == (rows, length, heads * d) and s.shape == (rows, heads, d, d) and s.dtype == jnp.float32
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    np.testing.assert_allclose(np.asarray(o).reshape(want_o.shape), np.asarray(want_o), atol=1e-5 * float(jnp.abs(want_o).max()), rtol=0)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=1e-5 * float(jnp.abs(want_s).max()), rtol=0)
    plan = next(p for p in kda.kda_plans() if (p["length"], p["heads"], p["head_dim"], p["sub_chunk"], p["conv_taps"]) == (length, heads, d, sub, 0))
    assert plan["chunk"] == min(chunk, -(-length // sub) * sub) and plan["grid_steps"] == heads // block * -(-length // plan["chunk"])
    assert kda.kda_chunk_kernel_name(2048, 128, 32, 128) == "kda_chunk_l2048_c128_h32_d128"


def test_the_recurrence_is_the_delta_rule_and_the_state_is_stored_transposed():
    """``kda_update`` against the equation written out with ``S`` ``d_k x d_v``: ``S_t = (I - b k k^T) Diag(exp(g)) S + b k v^T``, ``o = S_t^T q``."""
    q, k, v, g, b = (t[:, 0] for t in delta_args(2, 1, 3, 16, seed=4))
    s0 = jax.random.normal(jax.random.PRNGKey(5), (2, 3, 16, 16))  # (B, H, d_k, d_v)
    eye = jnp.eye(16)
    decayed = jnp.exp(g)[..., None] * s0
    want_s = jnp.einsum("bhij,bhjv->bhiv", eye - b[..., None, None] * k[..., :, None] * k[..., None, :], decayed, precision="highest") \
        + b[..., None, None] * k[..., :, None] * v[..., None, :]
    want_o = jnp.einsum("bhkv,bhk->bhv", want_s, q, precision="highest")
    o, s = kda.kda_update(q, k, v, g, b, jnp.swapaxes(s0, 2, 3))
    np.testing.assert_allclose(np.asarray(jnp.swapaxes(s, 2, 3)), np.asarray(want_s), atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=1e-5, rtol=0)


def test_the_state_carries_across_chunks():
    """A row of 70 tokens in chunks of 32: its final state is the state of the
    first 64 tokens carried through the last 6, not the last chunk's alone."""
    q, k, v, g, b = delta_args(1, 70, 4, 16)
    g = g * 0.02  # a state that remembers the row
    _, s = kda.kda_chunked(flat(q), flat(k), flat(v), flat(g), b, 4, 32, 16, 2)
    _, head = kda.kda_reference(q[:, :64], k[:, :64], v[:, :64], g[:, :64], b[:, :64])
    _, want = kda.kda_reference(q[:, 64:], k[:, 64:], v[:, 64:], g[:, 64:], b[:, 64:], state=head)
    _, alone = kda.kda_reference(q[:, 64:], k[:, 64:], v[:, 64:], g[:, 64:], b[:, 64:])
    np.testing.assert_allclose(np.asarray(s), np.asarray(want), atol=1e-5, rtol=0)
    assert np.abs(np.asarray(alone) - np.asarray(want)).max() > 0.3


def _raw_args(rows, length, config, seed=0):
    """A mixer of ``config``'s shape with its weights, and what its prompt pass hands the recurrence's entry: the three
    projections' raw outputs (B, T, H * D), the gates, and the tap tables as the mixer holds them."""
    mixer = kda_core.KimiDeltaAttention(config)
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, length, config.hidden_size))
    params = handed_on(mixer.init(jax.random.PRNGKey(seed + 1), x, method="expand"), config)
    leaves = params["params"]
    raw = [x @ leaves[name] for name in ("w_q", "w_k", "w_v")]
    g, b = mixer.apply(params, x, method="_gates")
    return mixer, params, x, raw, g, b, tuple(leaves[name] for name in ("conv_q", "conv_k", "conv_v"))


@pytest.mark.parametrize("rows,length,chunk,block", [(2, 64, 32, 4), (2, 70, 32, 1), (2, 2, 32, 4), (2, 133, 32, 1), (1, 128, 128, 4)],
                         ids=["whole_chunks", "a_last_chunk_padded", "a_row_shorter_than_the_taps", "five_chunks_of_two_rows", "one_chunk_of_128"])
def test_the_kernel_shapes_q_k_and_v_on_its_tiles_as_the_mixer_does_in_xla(rows, length, chunk, block):
    """The chunk kernel **with taps** (interpret mode) on the projections' raw
    outputs against the mixer's own ``_shape`` (XLA: ``causal_conv``, silu, l2
    norms, q's scale) followed by the token scan: ``o`` at every token and the
    rows' final state to the 1e-5 the recurrence's entry is held to. Whole
    chunks; a last chunk padded, where the first three pad tokens see real
    tokens through their taps and so have a key and a value, and still write
    nothing and forget nothing (their step and log-decay are zero); a row
    shorter than the taps; several chunks of two rows, so that the ``K - 1``
    raw rows carried from a grid step to the next are the row's own and start
    from zeros (row 1 does not see row 0's last tokens); a head and four heads
    a grid step. The plan says which entry a call took (0 taps where
    ``test_the_chunked_form_is_the_recurrence`` hands the kernel shaped inputs)."""
    config = tiny_config()
    mixer, params, _, raw, g, b, taps = _raw_args(rows, length, config)
    heads, d, n_taps = config.num_attention_heads, config.head_dim, config.short_conv_kernel_size
    q, k, v = mixer.apply(params, [ssm.rows_window(t, n_taps) for t in raw], method="_shape")
    in_heads = lambda t: t.reshape(rows, length, heads, d)  # noqa: E731
    want_o, want_s = kda.kda_reference(in_heads(q), in_heads(k), in_heads(v), in_heads(g), b)
    o, s = kda.kda_chunked(*raw, g, b, heads, chunk, 16, block, taps)
    assert o.shape == (rows, length, heads * d) and s.shape == (rows, heads, d, d) and s.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(o).reshape(want_o.shape), np.asarray(want_o), atol=1e-5 * float(jnp.abs(want_o).max()), rtol=0)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=1e-5 * float(jnp.abs(want_s).max()), rtol=0)
    plans = [p for p in kda.kda_plans() if (p["length"], p["heads"], p["head_dim"], p["heads_block"]) == (length, heads, d, block)]
    assert n_taps in {p["conv_taps"] for p in plans}


def test_rows_under_a_pad_tokens_taps_do_not_reach_the_state():
    """A row of 70 tokens in chunks of 32 is padded by 26: the state after it
    is the state of its 70 tokens, whatever the raw rows after the end would
    be (the kernel pads with zeros; here the same row cut out of a longer one,
    whose tokens 70 to 95 are real, gives a state that differs)."""
    config = tiny_config()
    _, _, _, raw, g, b, taps = _raw_args(1, 96, config, seed=3)
    cut = lambda t: t[:, :70]  # noqa: E731
    _, s = kda.kda_chunked(*(cut(t) for t in raw), cut(g), cut(b), 4, 32, 16, 2, taps)
    _, longer = kda.kda_chunked(*raw, g, b, 4, 32, 16, 2, taps)
    assert np.abs(np.asarray(s) - np.asarray(longer)).max() > 1e-3
    # zero steps and log-decays after the row's end, as the wrapper pads them, and real raw rows there: the same state
    zero_after = lambda t: t.at[:, 70:].set(0.0)  # noqa: E731
    _, s_real_rows = kda.kda_chunked(*raw, zero_after(g), zero_after(b), 4, 32, 16, 2, taps)
    np.testing.assert_allclose(np.asarray(s_real_rows), np.asarray(s), atol=1e-6 * float(jnp.abs(s).max()), rtol=0)


@pytest.mark.parametrize("length", [2, 70], ids=["shorter_than_the_taps", "two_chunks_and_a_padded_one"])
def test_expand_on_the_kernel_path_runs_no_shaping_in_xla_and_keeps_the_windows_to_the_bit(length, monkeypatch):
    """``expand`` where the kernels run hands the chunk kernel the raw
    projections and the mixer's three tap tables: ``_shape`` is not called (it
    raises here), the plan carries the taps, the three kept windows are bit for
    bit what the scan path keeps (``window_tail`` of the raw rows, zeros before
    a short row), and output and state agree with the scan path's, which still
    runs ``_shape``, as does a step on either path."""
    config = tiny_config()
    mixer, params, x, _, _, _, _ = _raw_args(2, length, config, seed=5)
    want, want_state = mixer.apply(params, x, method="expand")
    short_chunks(monkeypatch)
    real = kda_core.KimiDeltaAttention._shape
    calls = []

    def counted(self, windows):
        calls.append(windows[0].shape)
        return real(self, windows)

    monkeypatch.setattr(kda_core.KimiDeltaAttention, "_shape", counted)
    with fa.default_flash(True):
        got, state = mixer.apply(params, x, method="expand")
        assert calls == []
        _, stepped = mixer.apply(params, x[:, :1], state, method="step")
        assert calls == [(2, 4, 64)]
    mixer.apply(params, x, method="expand")
    assert calls[1:] == [(2, length + 3, 64)]
    for name in ("conv_q", "conv_k", "conv_v"):
        assert getattr(state, name).shape == (2, 3, 64) and np.array_equal(np.asarray(getattr(state, name)), np.asarray(getattr(want_state, name)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5 * float(jnp.abs(want).max()), rtol=0)
    np.testing.assert_allclose(np.asarray(state.s), np.asarray(want_state.s), atol=1e-5 * float(jnp.abs(want_state.s).max()), rtol=0)
    assert any(p["conv_taps"] == 4 and p["length"] == length for p in kda.kda_plans())
    assert kda_core._L2_EPS == kda.L2_EPS == reference.L2_EPS


def test_the_steps_kernel_is_the_update_in_place():
    """``kda_step`` (interpret mode) against ``kda_update`` from a state that holds 30 tokens; the kernel's state goes out through the array it came in by."""
    q, k, v, g, b = delta_args(2, 31, 4, 16, seed=2)
    _, s = kda.kda_reference(q[:, :30], k[:, :30], v[:, :30], g[:, :30], b[:, :30])
    last = (q[:, 30], k[:, 30], v[:, 30], g[:, 30], b[:, 30])
    want = kda.kda_update(*last, s)
    got = kda.kda_step(*last, s)
    for a, w in zip(got, want):
        assert a.shape == w.shape and a.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), atol=1e-5 * float(jnp.abs(w).max()), rtol=0)
    jaxpr = str(jax.make_jaxpr(kda.kda_step)(*last, s))
    assert "input_output_aliases=((5, 1),)" in jaxpr and jaxpr.count("pallas_call") == 1
    assert kda.kda_step_kernel_name(128, 32, 128) == "kda_step_b128_h32_d128"
    assert kda.sub_chunk_safe(-5.0) and not kda.sub_chunk_safe(-5.0, 32) and not kda.sub_chunk_safe(-6.0) and kda.sub_chunk_safe(-10.0, 8)


def test_differentiation_through_the_kernel_raises():
    q, k, v, g, b = delta_args(1, 16, 2, 16)
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda x: kda.kda_chunked(x, flat(k), flat(v), flat(g), b, 2)[0].sum())(flat(q))
    with pytest.raises(ValueError, match="whole sub-chunks"):
        kda.kda_chunked(flat(q), flat(k), flat(v), flat(g), b, 2, 24, 16)


# ------------------------------------------------------------------ the mixers


@pytest.mark.parametrize("kernel", [False, True], ids=["lax_scan", "kernels"])
def test_expand_hands_its_state_to_step(kernel, monkeypatch):
    """``expand`` over 40 tokens then ``step`` on the 41st equals ``expand`` over
    all 41, in the output, in the state and in the windows; from an empty state one step equals a row of one token."""
    config = tiny_config()
    mixer = kda_core.KimiDeltaAttention(config)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 41, config.hidden_size))
    params = handed_on(mixer.init(jax.random.PRNGKey(1), u, method="expand"), config)
    short_chunks(monkeypatch)
    with fa.default_flash(kernel):
        whole, end = mixer.apply(params, u, method="expand")
        head, state = mixer.apply(params, u[:, :40], method="expand")
        last, stepped = mixer.apply(params, u[:, 40:], state, method="step")
        empty = init_delta_state(2, 4, 16, 4)
        first, _ = mixer.apply(params, u[:, :1], empty, method="step")
    tol = 1e-5 * float(jnp.abs(whole).max())
    np.testing.assert_allclose(np.asarray(head), np.asarray(whole[:, :40]), atol=tol, rtol=0)
    np.testing.assert_allclose(np.asarray(last), np.asarray(whole[:, 40:]), atol=tol, rtol=0)
    np.testing.assert_allclose(np.asarray(first), np.asarray(whole[:, :1]), atol=tol, rtol=0)
    for name in ("s", "conv_q", "conv_k", "conv_v"):
        np.testing.assert_allclose(np.asarray(getattr(stepped, name)), np.asarray(getattr(end, name)), atol=1e-5 * float(jnp.abs(end.s).max()), rtol=0)
    assert (empty.s.shape, empty.s.dtype, empty.conv_v.shape) == ((2, 4, 16, 16), jnp.float32, (2, 3, 64))
    assert set(params["params"]) == {"w_q", "w_k", "w_v", "conv_q", "conv_k", "conv_v", "w_f", "dt_bias", "a_log", "w_b", "w_g", "o_norm", "w_o"}
    # one causal convolution in the code base: the state-space mixer's, its windows too
    assert kda_core.causal_conv is ssm.causal_conv and kda_core.rows_window is ssm.rows_window and kda_core.step_window is ssm.step_window
    assert kda_core.window_tail is ssm.window_tail


def test_latent_attention_without_a_query_latent_and_with_the_head_wise_gate():
    """``q_lora_rank`` ``None``: no ``w_dq`` and no ``q_norm``, ``w_uq`` from the hidden state; ``mla_head_gate``: one
    gate a head before ``W_o``. The expanded pass equals the reference's layer, the absorbed step the expanded pass."""
    config = tiny_config()
    attn = mla.MultiHeadLatentAttention(config)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 11, 64))
    pos = jnp.broadcast_to(jnp.arange(11)[None], (2, 11))
    params = attn.init(jax.random.PRNGKey(1), x, pos, method="expand")
    assert set(params["params"]) == {"w_uq", "w_dkv", "kv_norm", "w_ukv", "w_gate", "w_o"}
    assert params["params"]["w_uq"].shape == (64, 4 * 24) and params["params"]["w_gate"].shape == (64, 4) and mla.query_rank(config) == 64
    whole, rows = attn.apply(params, x, pos, method="expand")
    want = reference.latent_attention(x, {"a/" + k: v for k, v in flat_dict(params["params"]).items()}, "a", reference_cfg(config), "float32")
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), atol=1e-5, rtol=0)
    cache = LatentCache(rows=jnp.zeros((2, 16, 24)), length=jnp.zeros((), jnp.int32)).append(rows[:, :10])
    last, cache = attn.apply(params, x[:, 10:], cache, pos[:, 10:], method="absorb")
    np.testing.assert_allclose(np.asarray(last), np.asarray(whole[:, 10:]), atol=1e-5, rtol=0)
    ungated = mla.MultiHeadLatentAttention(dataclasses.replace(config, mla_head_gate=False))
    bare = {"params": {k: v for k, v in params["params"].items() if k != "w_gate"}}
    assert np.abs(np.asarray(ungated.apply(bare, x, pos, method="expand")[0]) - np.asarray(whole)).max() > 100 * TOL
    # the query latent stays where a configuration has one
    with_latent = mla.MultiHeadLatentAttention(dataclasses.replace(config, q_lora_rank=8, mla_head_gate=False))
    assert {"w_dq", "q_norm"} <= set(with_latent.init(jax.random.PRNGKey(1), x, pos, method="expand")["params"])


def test_the_four_shares_parts_add_up_to_the_uncut_layer():
    """One chip of four computes its experts' part of the expert layer and the
    shared expert whole. The four chips' parts, the shared expert counted once,
    are the uncut layer: the reference's with every expert held."""
    config = tiny_config(n_held_experts=4)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 64))
    whole = moe.MoELayer(dataclasses.replace(config, n_held_experts=16)).init(jax.random.PRNGKey(1), x)["params"]
    whole["gate_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    flat_w = {"f/" + k: v for k, v in flat_dict(whole).items()}
    shared = dsv3_reference.swiglu(x.reshape(-1, 64), flat_w["f/shared/w1"], flat_w["f/shared/w3"], flat_w["f/shared/w2"], "float32").reshape(x.shape)
    total, parts = shared, []
    for start in (0, 4, 8, 12):
        share = {k: (v[start:start + 4] if k.startswith("experts_") else v) for k, v in whole.items()}
        y = moe.MoELayer(dataclasses.replace(config, held_experts_start=start)).apply({"params": share}, x)
        parts.append(y - shared)
        total = total + parts[-1]
    want = dsv3_reference.experts(x, flat_w, "f", {**reference_cfg(config), "n_held_experts": 16, "held_experts_start": 0}, "float32")
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=1e-5 * float(jnp.abs(want).max()), rtol=0)
    assert all(float(jnp.abs(part).max()) > 0.01 for part in parts)  # every share has pairs of its own
    assert float(jnp.abs(shared).max()) > 0.01  # and the shared expert is no small part of a share's output


def test_a_layer_of_many_small_experts_takes_the_third_sets_cuts_and_no_other_changes():
    """The nearest measured geometry, by expert size and by the number held: each measured one its own set, the tiny
    test models the small one, and what lies between them the nearer."""
    assert moe._cuts(7168, 2048, 16) == moe._cuts(6144, 2048, 16) == moe._WIDE_EXPERTS  # DeepSeek-V3, K-EXAONE, LongCat
    assert moe._cuts(2304, 896, 64) == moe._SMALL_EXPERTS
    assert all(moe._cuts(64, 32, held) == moe._SMALL_EXPERTS for held in (1, 2, 4, 8, 16, 64))
    many = moe._cuts(2560, 768, 128)
    assert many == moe._MANY_EXPERTS and many.pass_rows % many.row_tile == 0 and many.row_tile % 16 == 0
    assert moe._cuts(2560, 768, 64) == moe._SMALL_EXPERTS  # by the held experts where the experts' sizes are near
    assert moe._cuts(7168, 2048, 65) == moe._WIDE_EXPERTS  # and by the expert's size where the counts are


def test_the_seeded_gate_remembers():
    """``dt_bias = logit(r / 5) / exp(A_log)`` with ``r`` log-uniform over the file's range, read off the seeded leaf; the
    router's bias scaled; other leaves as drawn."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    leaves = {"l/mixer/dt_bias": 0.02 * jax.random.normal(keys[0], (8 * 512,)), "l/mixer/a_log": 0.02 * jax.random.normal(keys[1], (8,)),
              "l/mixer/w_f": jnp.ones((3, 3)), "l/ffn/gate_bias": 0.02 * jax.random.normal(keys[2], (16,))}
    out = remembering(leaves, 0.02, 1e-4, 1e-2, lower_bound=-5.0, bias_scale=0.1)
    rate = jnp.repeat(jnp.exp(leaves["l/mixer/a_log"]), 512)
    forget = 5.0 * jax.nn.sigmoid(rate * out["l/mixer/dt_bias"])  # the log-decay a token at a zero projection
    assert 1e-4 <= float(forget.min()) < 1.2e-4 and 0.8e-2 < float(forget.max()) <= 1.0001e-2
    quartiles = np.quantile(np.log(np.asarray(forget)), [0.25, 0.5, 0.75])  # log-uniform: the quartiles of the logarithm lie evenly
    assert np.allclose(quartiles, np.log(1e-4) + np.array([0.25, 0.5, 0.75]) * np.log(100), atol=0.15)
    assert out["l/mixer/w_f"] is leaves["l/mixer/w_f"] and out["l/mixer/a_log"] is leaves["l/mixer/a_log"] and list(out) == list(leaves)
    np.testing.assert_allclose(np.asarray(out["l/ffn/gate_bias"]), 0.1 * np.asarray(leaves["l/ffn/gate_bias"]))
    # as drawn a channel forgets 2.5 nats a token; made to remember, a fifth of the channels keep a third of a state over 2048 tokens
    assert float(jnp.mean(jnp.exp(-forget * 2048) > 0.3)) > 0.2


# ----------------------------------------------- scopes, taps, the compile row


def test_the_scopes_are_in_the_vocabulary_and_in_the_programs():
    assert set(SCOPES) <= xplane.LAYER_SCOPES and set(SCOPES) <= xplane.CLOSED_LAYERS
    config = tiny_config()
    model, params, ids = seeded(config, 0, n=9)
    decoder = generation._decoder_of(model)
    prompt_pass = jax.jit(lambda p, i: decoder.prefill(p, i, None, 1, 4, jnp.float32)).lower(params, ids).as_text(debug_info=True)
    _, window, _ = decoder.prefill(params, ids, None, 1, 4, jnp.float32)
    step = jax.jit(lambda p, w, t: decoder.step(p, w, (), t)).lower(params, window, ids[:, 0]).as_text(debug_info=True)
    for scope in ("kda/proj", "kda/conv", "kda/gate", "kda/chunk", "kda/out"):
        assert f"prefill/DecoderLanguageModel.attend_layer/layer_0.attend/mixer.expand/{scope}" in prompt_pass, scope
    assert "layer_2.attend/attn.expand/mla/expand" in prompt_pass and "kda/update" not in prompt_pass and "kda/chunk" not in step
    for scope in ("kda/proj", "kda/conv", "kda/gate", "kda/update", "kda/out"):
        assert f"mixer.step/{scope}" in step, scope
    assert "attn.absorb/mla/absorb" in step and "moe/experts" in step


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
    assert snap["kda_state_abs_max"] > 0 and snap["kda_state_nonfinite_total"] == 0 and "ret_state_abs_max" not in snap
    assert 0.8 < snap["kda_decay_mean"] < 1.0 and 0.2 < snap["kda_beta_mean"] < 0.8 and snap["moe_pairs_routed_total"] > 0
    rows = [json.loads(line) for line in open(tmp_path / "events.jsonl")]
    request = next(r for r in rows if r.get("event") == "request")
    assert request["kda_state_abs_max"] == pytest.approx(snap["kda_state_abs_max"]) and request["kda_state_nonfinite"] == 0
    assert 0 < request["kv_cache_frac"] <= 1 and 0 < request["moe_local_share"] < 1  # the latent cache fills; a share of the pairs is here
    compile_row = next(r for r in rows if r.get("event") == "compile" and "kda_layers" in r)
    assert compile_row["kda_layers"] == 3 and compile_row["kda_state_dtype"] == "float32" and compile_row["latent_cache_layers"] == 1
    assert compile_row["kda_state_bytes"] == 3 * 2 * 4 * 16 * 16 * 4 and compile_row["kda_conv_bytes"] == 3 * 2 * 3 * 3 * 64 * 4
    assert compile_row["kda_chunk"] == 16 and compile_row["moe_combine"] == "segment_sum" and isinstance(compile_row["kda"], list)
    assert compile_row["latent_cache_bytes"] == 2 * 13 * 24 * 4


def test_every_configuration_taps_only_what_it_has():
    taps = lambda config: generation._decoder_of(DecoderLanguageModel(config)).tap_scopes  # noqa: E731
    assert taps(tiny_config()) == ("moe.*", "spec.*", "kda.*")
    assert taps(tiny_config(layer_types=("latent_attention",) * 4)) == ("moe.*", "spec.*")
    assert taps(tiny_config(layer_types=None, first_k_dense_replace=4)) == ("spec.*",)


# ------------------------------------------------------- the published widths


def test_the_published_share_counts_5_231_790_016_parameters():
    """``jax.eval_shape`` of the program under the benchmark's configuration, against the hand count of ``lib/ling_cost.py``."""
    from benchmarks import run

    config = run.load_json("configs", "ling3-flash-ep4")
    family = importlib.import_module("benchmarks.families.ling").Family(config)
    shapes = family.param_shapes(family.model())
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n == ling_cost.held_params(family.cfg) == 5_231_790_016
    mixer = shapes["params"]["layer_6"]["mixer"]
    assert mixer["w_q"].shape == mixer["w_f"].shape == mixer["w_g"].shape == (2560, 4096) and mixer["w_o"].shape == (4096, 2560)
    assert mixer["conv_k"].shape == (4, 4096) and mixer["a_log"].shape == (32,) and mixer["w_b"].shape == (2560, 32)
    assert mixer["o_norm"]["scale"].shape == (128,) and mixer["dt_bias"].shape == (4096,)
    attn = shapes["params"]["layer_4"]["attn"]
    assert attn["w_uq"].shape == (2560, 32 * 192) and attn["w_gate"].shape == (2560, 32) and "w_dq" not in attn and "mixer" not in shapes["params"]["layer_4"]
    ffn = shapes["params"]["layer_3"]["ffn"]
    assert ffn["experts_w1"].shape == (128, 2560, 768) and ffn["gate"].shape == (2560, 512) and ffn["shared"]["w2"].shape == (768, 2560)
    assert shapes["params"]["layer_0"]["ffn"]["w1"].shape == (2560, 6144) and "layer_7" not in shapes["params"]
    assert shapes["params"]["head"].shape == (2560, 39296) and shapes["params"]["embedding"].shape == (39296, 2560)
    row = generation._decoder_of(family.model()).compile_row(128, 2048, 256, jnp.bfloat16)
    assert row["kda_layers"] == 6 and row["kda_state_bytes"] == 6 * 128 * 2_097_152 and row["kda_chunk"] == kda.CHUNK
    assert row["latent_cache_layers"] == 1 and row["latent_cache_bytes"] == 128 * 2304 * 1152 and row["moe_combine"] == "segment_sum"
