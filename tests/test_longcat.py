"""The decoder-only model's fourth configuration (the LongCat-Flash family:
the shortcut-connected block) against its plain reference, at tiny widths that
keep the published ratios: hidden 64, 4 heads, ranks 16 / 8 (so the two scale
factors are 2 and 2.83, not 1), nope 16 + rope 8, dense width 128, two double
layers, 16 experts of width 32 of which 4 are held, 8 identity experts after
them (24 router outputs), 3 a token, times 6, plain rotary at theta 1e7.

Tolerances are ``tests/test_decoder_lm.py``'s: float32 products at "highest"
precision differ from ``benchmarks/reference/longcat_flash.py`` only in the
order of float32 sums, 2e-4 absolute on logits of magnitude up to about 10
(observed under 5e-5); bfloat16 in the reference's place moves them by 5e-2
and more, and a test says so. Routing is discrete (3 of 24 outputs on
``p + b``), so a seed with a near-tie would fail loudly, not flakily: none of
the seeds used has one. A "wrong equation" test asks for 50 times the
tolerance: a variant that differs by less would hide inside bfloat16."""

import dataclasses
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib.weights import flat_dict
from benchmarks.reference import longcat_flash as reference
from perceiver_io_tpu import generation
from perceiver_io_tpu.core import moe
from perceiver_io_tpu.core.mla import MultiHeadLatentAttention
from perceiver_io_tpu.generation import GenerationConfig, make_decode_fns, make_generate_fn, make_instrumented_generate_fn
from perceiver_io_tpu.models.text import decoder_lm
from perceiver_io_tpu.models.text.decoder_lm import DecoderLanguageModel, DecoderLanguageModelConfig, ShortcutBlock
from perceiver_io_tpu.obs import probes

TOL = 2e-4
VOCAB = 96
REAL, ZERO, TOP_K = 16, 8, 3
CUTS = moe._cuts(64, 32, 4)


def tiny_config(**kw) -> DecoderLanguageModelConfig:
    base = dict(
        vocab_size=VOCAB, hidden_size=64, num_hidden_layers=2, first_k_dense_replace=0, intermediate_size=128,
        moe_intermediate_size=32, num_attention_heads=4, q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=REAL, n_held_experts=4, held_experts_start=4,
        zero_expert_num=ZERO, num_experts_per_tok=TOP_K, n_shared_experts=0, n_group=1, topk_group=1,
        scoring_func="softmax_biased", routed_scaling_factor=6.0, rms_norm_eps=1e-5, rope_theta=1e7, rope_scaling=None,
        block="shortcut", mla_scale_q_lora=True, mla_scale_kv_lora=True, init_scale=0.3, max_position_embeddings=64,
    )
    base.update(kw)
    return DecoderLanguageModelConfig(**base)


def reference_cfg(config: DecoderLanguageModelConfig) -> dict:
    return dataclasses.asdict(config)


def noisy(params, key, scale=0.1):
    """Every leaf moved off its initial value: the norms' scales off 1, the router's bias off 0."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [p + scale * jax.random.normal(k, p.shape) for p, k in zip(leaves, keys)])


def seeded(config, seed: int, batch: int = 4, n: int = 8):
    model = DecoderLanguageModel(config)
    k_ids, k_init, k_noise = jax.random.split(jax.random.PRNGKey(seed), 3)
    ids = jax.random.randint(k_ids, (batch, n), 0, config.vocab_size)
    return model, noisy(model.init(k_init, ids), k_noise), ids


# --------------------------------------------------- the whole model, no cache


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("start,held", [(0, 16), (4, 4), (12, 4)], ids=lambda v: str(v))
def test_full_forward_matches_the_reference(seed, start, held):
    config = tiny_config(held_experts_start=start, n_held_experts=held)
    model, params, ids = seeded(config, seed)
    got = np.asarray(model.apply(params, ids))
    want = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config)))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_bfloat16_in_the_references_place_fails_the_float32_tolerance():
    config = tiny_config()
    _, params, ids = seeded(config, 0)
    want = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config)))
    lower = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config), precision="bfloat16"))
    assert np.abs(lower - want).max() > 50 * TOL


def layer_whose_branch_reads(what: str):
    """The reference's layer with the expert branch fed something else than ``u``."""

    def layer(h, w, prefix, cfg, precision):
        eps = cfg["rms_norm_eps"]
        dense = lambda x, name: reference.swiglu(x, *(w[f"{prefix}/{name}/{m}"] for m in ("w1", "w3", "w2")), precision)  # noqa: E731
        a0 = h + reference.mla(reference.rms_norm(h, w[prefix + "/attn0_norm/scale"], eps), w, prefix + "/attn0", cfg, precision)
        u = reference.rms_norm(a0, w[prefix + "/ffn0_norm/scale"], eps)
        read = {"h": reference.rms_norm(h, w[prefix + "/ffn0_norm/scale"], eps), "a0": a0, "u": u}[what]
        s = reference.experts(read, w, prefix + "/moe", cfg, precision)
        b0 = a0 + dense(u, "ffn0")
        a1 = b0 + reference.mla(reference.rms_norm(b0, w[prefix + "/attn1_norm/scale"], eps), w, prefix + "/attn1", cfg, precision)
        return a1 + dense(reference.rms_norm(a1, w[prefix + "/ffn1_norm/scale"], eps), "ffn1") + s

    return layer


@pytest.mark.parametrize("what", ["h", "a0"], ids=["the_layers_input", "the_unnormed_state"])
def test_the_shortcut_reads_the_first_sublayers_normed_state(what):
    """``s = MoE(u)`` with ``u = RMS(a0)``: a layer whose branch reads the
    layer's (normed) input ``h``, or ``a0`` without its norm, is another
    function by far more than the tolerance; the same harness fed ``u`` is the
    reference itself."""
    config = tiny_config()
    model, params, ids = seeded(config, 0)
    w, cfg = flat_dict(params), reference_cfg(config)
    got = np.asarray(model.apply(params, ids))
    same = np.asarray(reference.logits(w, ids, cfg, layer_fn=layer_whose_branch_reads("u")))
    np.testing.assert_allclose(got, same, atol=TOL, rtol=0)
    wrong = np.asarray(reference.logits(w, ids, cfg, layer_fn=layer_whose_branch_reads(what)))
    assert np.abs(got - wrong).max() > 50 * TOL


@pytest.mark.parametrize("off", ["mla_scale_q_lora", "mla_scale_kv_lora"])
def test_each_scale_factor_is_held_by_the_comparison(off):
    """At ranks 16 and 8 of hidden 64 the factors are 2 and 2.83: a program without one is not the reference's function."""
    config = tiny_config()
    model, params, ids = seeded(config, 0)
    got = np.asarray(DecoderLanguageModel(dataclasses.replace(config, **{off: False})).apply(params, ids))
    want = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config)))
    assert np.abs(got - want).max() > 50 * TOL


def test_the_caches_rows_hold_the_scaled_latent_and_the_unscaled_rotary_key():
    config = tiny_config()
    attn = MultiHeadLatentAttention(config)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 64))
    pos = jnp.broadcast_to(jnp.arange(5)[None], (2, 5))
    params = attn.init(jax.random.PRNGKey(1), x, pos, method="expand")  # the norms' scales are 1
    _, rows = attn.apply(params, x, pos, method="expand")
    plain = MultiHeadLatentAttention(dataclasses.replace(config, mla_scale_kv_lora=False))
    _, unscaled = plain.apply(params, x, pos, method="expand")
    rms = np.sqrt(np.mean(np.square(np.asarray(rows[..., :8])), axis=-1))
    np.testing.assert_allclose(rms, (64 / 8) ** 0.5, rtol=1e-3)  # an RMSNorm's output has mean square 1
    np.testing.assert_allclose(np.asarray(rows[..., :8]), np.asarray(unscaled[..., :8]) * (64 / 8) ** 0.5, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(rows[..., 8:]), np.asarray(unscaled[..., 8:]))  # k_rope: rotated, not scaled


# --------------------------------------- prompt pass, then steps through the caches


def served_logits(model, params, ids, new_tokens: int, cache_dtype=jnp.float32):
    """Greedy decoding through the generator's own decoder (prompt pass, then
    one-token steps over the layers' pairs of latent caches): the logits the
    tokens were read from, (B, new_tokens, V), and the tokens."""
    decoder = generation._decoder_of(model)
    prefill = jax.jit(lambda p, i: decoder.prefill(p, i, None, 1, new_tokens, cache_dtype))
    step = jax.jit(lambda p, w, t: decoder.step(p, w, (), t))
    logits, window, consts = prefill(params, ids)
    assert consts == () and len(window[0]) == 2 * model.config.num_hidden_layers
    out, tokens = [logits[:, -1]], []
    for _ in range(new_tokens - 1):
        tokens.append(jnp.argmax(out[-1], axis=-1))
        logits, window = step(params, window, tokens[-1])
        out.append(logits[:, -1])
    tokens.append(jnp.argmax(out[-1], axis=-1))
    assert all(int(c.length) == ids.shape[1] + new_tokens - 1 for c in window[0])
    return np.stack([np.asarray(o) for o in out], axis=1), np.stack([np.asarray(t) for t in tokens], axis=1)


@pytest.mark.parametrize("scaled", [True, False], ids=["scaled_latents", "plain"])
def test_prompt_pass_through_the_token_major_kernel_then_cached_decode_matches_the_reference(scaled):
    """The published head widths, where both attentions of a layer run
    ``flash_attention_mla`` on what their up-projections write (interpret
    mode here), the latents' two scale factors on and off: every served
    position against the float32 reference."""
    import importlib

    fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")
    new, n = 3, 128
    config = tiny_config(num_attention_heads=2, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, num_hidden_layers=1,
                         max_position_embeddings=256, mla_scale_q_lora=scaled, mla_scale_kv_lora=scaled)
    model, params, ids = seeded(config, 5, batch=2, n=n)
    decoder = generation._decoder_of(model)
    with fa.default_flash(True):
        lowered = jax.jit(lambda p, i: decoder.prefill(p, i, None, 1, new, jnp.float32)).lower(params, ids).as_text(debug_info=True)
        got, tokens = served_logits(model, params, ids, new)
    assert lowered.count(f"flash_mla_fwd_q{n}_kv{n}_h2") >= 2 and not re.search(r"flash_fwd_q\d", lowered)
    full = np.concatenate([np.asarray(ids), tokens[:, :-1]], axis=1)
    want = np.asarray(reference.logits(flat_dict(params), jnp.asarray(full), reference_cfg(config), last=new))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_prompt_pass_then_cached_decode_matches_the_references_full_forward(seed):
    """Every served position: the logits of prefill + decoding through the
    four caches (two a layer, holding the scaled latent) against one plain
    forward over the prompt with the served tokens; and the fused generator
    and the host-driven pair serve exactly those tokens."""
    new = 6
    config = tiny_config()
    model, params, ids = seeded(config, seed, batch=4, n=8)
    got, tokens = served_logits(model, params, ids, new)
    full = np.concatenate([np.asarray(ids), tokens[:, :-1]], axis=1)
    want = np.asarray(reference.logits(flat_dict(params), jnp.asarray(full), reference_cfg(config), last=new))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)

    gen_cfg = GenerationConfig(max_new_tokens=new)
    fused = np.asarray(make_generate_fn(model, num_latents=1, config=gen_cfg)(params, ids))
    np.testing.assert_array_equal(fused[:, :8], np.asarray(ids))
    np.testing.assert_array_equal(fused[:, 8:], tokens)
    prefill_fn, step_fn = make_decode_fns(model, 1, gen_cfg)
    token, state = prefill_fn(params, ids)
    stream = [np.asarray(token)]
    for _ in range(new - 1):
        state, token = step_fn(state)
        stream.append(np.asarray(token))
    np.testing.assert_array_equal(np.stack(stream, axis=1), tokens)


@pytest.mark.parametrize("seed", [0, 1])
def test_a_prompt_pass_cut_into_chunks_is_the_uncut_forward(seed):
    """Enough tokens (64 rows of 256) to cross the shipped cut: four chunks of
    16 whole rows, each through a whole layer (both attentions, both dense
    feed-forwards, the experts on their grouped path, the identity add), the
    taps carried out of the loop. Rows are independent, so the reference's
    forward over two of them holds the whole batch to account."""
    b, n = 64, 256
    assert b * n > decoder_lm._PREFILL_ATTENTION_TOKENS >= CUTS.grouped_min_tokens
    config = tiny_config(max_position_embeddings=n + 1)
    model, params, ids = seeded(config, seed, batch=b, n=n)

    def chunked(params, ids):
        with probes.collecting(probes.ProbeConfig(scopes=("moe.*",), activations=False)) as col:
            logits, (caches,), _ = generation._decoder_of(model).prefill(params, ids, None, 1, 1, jnp.float32)
        return logits[:, -1], caches, col.stats

    logits, caches, stats = jax.jit(chunked)(params, ids)
    assert len(stats) == 2  # one tap site a layer, summed over the chunks
    for load in stats.values():
        assert int(load["pairs_routed"]) == TOP_K * b * n and int(load["pairs_dropped"]) == 0
        assert 0 < int(load["pairs_gathered"]) == int(load["pairs_local"]) < TOP_K * b * n and 0 < int(load["passes"])
        assert 0 < int(load["pairs_zero"]) < TOP_K * b * n and int(load["real_experts_per_token_max"]) == TOP_K
    rows = np.array([0, b - 1])
    want = np.asarray(reference.logits(flat_dict(params), ids[rows], reference_cfg(config), last=1))[:, 0]
    np.testing.assert_allclose(np.asarray(logits)[rows], want, atol=TOL, rtol=0)
    assert len(caches) == 4 and all(int(c.length) == n and c.rows.shape == (b, n + 1, 16) for c in caches)


# -------------------------------------------------- the router's third rule


def test_the_bias_moves_the_choice_and_not_the_weight_and_nothing_is_renormalised():
    logits = jax.random.normal(jax.random.PRNGKey(0), (32, REAL + ZERO)) * 1.5
    p = np.asarray(jax.nn.softmax(logits, axis=-1))
    chosen, w = (np.asarray(a) for a in moe.choose_experts_softmax_biased(logits, jnp.zeros(REAL + ZERO), TOP_K, 6.0))
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(np.argsort(-p, -1)[:, :TOP_K], -1))
    np.testing.assert_allclose(w, 6.0 * np.take_along_axis(p, chosen, 1), rtol=1e-6)
    sums = w.sum(-1)
    assert sums.std() > 0.05 and (np.abs(sums - 6.0) > 0.5).all()  # 6 p, not 6 p / sum(p): a token's weights sum to what its picks hold
    # a bias of 1 on output 5 (every probability is below 1): chosen by every token, weighed by its own unbiased p
    chosen_b, w_b = (np.asarray(a) for a in moe.choose_experts_softmax_biased(
        logits, jnp.zeros(REAL + ZERO).at[5].set(1.0), TOP_K, 6.0))
    assert (chosen_b == 5).any(-1).all() and not (chosen == 5).any(-1).all()
    np.testing.assert_allclose(w_b, 6.0 * np.take_along_axis(p, chosen_b, 1), rtol=1e-6)


def moe_layer_and_weights(seed, tokens=48, **kw):
    config = tiny_config(**kw)
    layer = moe.MoELayer(config)
    x = jax.random.normal(jax.random.PRNGKey(seed), (tokens, config.hidden_size))
    params = noisy(layer.init(jax.random.PRNGKey(seed + 1), x), jax.random.PRNGKey(seed + 2), 0.2)
    return config, x, params["params"]


def tapped(config, p, x):
    with probes.collecting(probes.ProbeConfig(scopes=("moe.*",))) as col:
        y = moe.MoELayer(config).apply({"params": p}, x)
    (load,) = col.stats.values()
    return np.asarray(y), {k: int(v) for k, v in load.items()}


@pytest.mark.parametrize("path", ["grouped", "dense"])
@pytest.mark.parametrize("picks", ["all_identity", "no_identity", "as_routed"])
def test_identity_pairs_are_one_weight_a_token_and_never_a_row(picks, path):
    """A token whose picks are all identity experts gets ``(sum of its
    weights) * x`` and sends the held experts nothing; one with none gets the
    held experts' rows alone; the books count the identity pairs apart from
    the local ones and from the dropped (none)."""
    tokens = CUTS.grouped_min_tokens if path == "grouped" else 48
    config, x, p = moe_layer_and_weights(3, tokens)
    bias = {"all_identity": jnp.zeros(REAL + ZERO).at[REAL:].set(1.0), "no_identity": jnp.zeros(REAL + ZERO).at[4:8].set(1.0),
            "as_routed": p["gate_bias"]}[picks]
    p = {**p, "gate_bias": bias}
    y, load = tapped(config, p, x)
    w = {"l/" + k: v for k, v in flat_dict(p).items()}
    np.testing.assert_allclose(y, np.asarray(reference.experts(x, w, "l", reference_cfg(config), "float32")), atol=TOL, rtol=0)
    chosen, weight = (np.asarray(a) for a in reference.route(x, w, "l", reference_cfg(config)))
    assert load["pairs_routed"] == TOP_K * tokens and load["pairs_dropped"] == 0
    assert load["pairs_gathered"] == (load["pairs_local"] if path == "grouped" else 0)  # the rows the segment sum read back
    assert load["pairs_zero"] == int((chosen >= REAL).sum())
    assert load["real_experts_per_token_max"] == int((chosen < REAL).sum(-1).max())
    if picks == "all_identity":
        assert load["pairs_zero"] == TOP_K * tokens and load["pairs_local"] == 0 and load["real_experts_per_token_max"] == 0
        np.testing.assert_allclose(y, weight.sum(-1, keepdims=True) * np.asarray(x), atol=TOL, rtol=0)
    elif picks == "no_identity":  # the held experts 4 to 7 take every pair
        assert load["pairs_zero"] == 0 and load["pairs_local"] == TOP_K * tokens and load["real_experts_per_token_max"] == TOP_K
    else:
        assert 0 < load["pairs_zero"] < TOP_K * tokens and 0 < load["pairs_local"] < TOP_K * tokens
        real = (chosen < REAL).sum(-1)
        assert real.min() < real.max()  # the experts with weights a token runs vary from token to token


# -------------------------------------------------- the expert layer's share


@pytest.mark.parametrize("path", ["grouped", "dense"])
@pytest.mark.parametrize("seed", [0, 1])
def test_the_shares_add_up_to_the_uncut_layer(seed, path):
    """Four chips share a layer, each with four of the sixteen experts that
    have weights: what each chip's whole block adds beyond what every chip
    computes alike (both attentions, both dense feed-forwards and the identity
    experts: the reference's layer with no expert held, counted once) sums to
    the uncut reference's layer. The held share is taken of the 16 experts
    with weights, not of the router's 24 outputs. The weights are drawn
    smaller than elsewhere in this file so that a block's output stays of the
    logits' magnitude, where the 2e-4 absolute is float32's sums and the
    difference ``y - alike`` loses nothing."""
    close = dict(atol=TOL, rtol=0)
    b, n = (6, 64) if path == "grouped" else (4, 12)
    assert (b * n >= CUTS.grouped_min_tokens) == (path == "grouped")
    config = tiny_config(n_held_experts=REAL, held_experts_start=0, init_scale=0.1)
    block = ShortcutBlock(config)
    h = jax.random.normal(jax.random.PRNGKey(seed), (b, n, 64))
    pos = jnp.broadcast_to(jnp.arange(n)[None], (b, n))
    params = noisy(block.init(jax.random.PRNGKey(seed + 1), h, pos), jax.random.PRNGKey(seed + 2), 0.05)["params"]
    w = {"l/" + k: v for k, v in flat_dict(params).items()}
    whole = np.asarray(reference.layer(h, w, "l", reference_cfg(config), "float32"))
    none_held = dataclasses.replace(config, n_held_experts=0)
    alike = np.asarray(reference.layer(h, w, "l", reference_cfg(none_held), "float32"))
    total = alike.copy()
    for start in range(0, REAL, 4):
        share = dataclasses.replace(config, n_held_experts=4, held_experts_start=start)
        p = {**params, "moe": {k: v[start:start + 4] if k.startswith("experts_") else v for k, v in params["moe"].items()}}
        y, _ = ShortcutBlock(share).apply({"params": p}, h, pos)
        ws = {"l/" + k: v for k, v in flat_dict(p).items()}
        np.testing.assert_allclose(np.asarray(y), np.asarray(reference.layer(h, ws, "l", reference_cfg(share), "float32")), **close)
        total += np.asarray(y) - alike
    np.testing.assert_allclose(total, whole, **close)
    assert np.abs(whole).max() < 20 and np.abs(whole - alike).max() > 0.1  # the experts with weights matter
    with pytest.raises(ValueError, match="reach past"):
        tiny_config(n_held_experts=4, held_experts_start=REAL)  # outputs 16 to 23 have no weights to hold


# --------------------------------------------------- configuration, scopes, taps


def test_the_block_is_chosen_by_the_configuration_and_refuses_what_it_is_not():
    with pytest.raises(ValueError, match="block"):
        tiny_config(block="parallel")
    with pytest.raises(ValueError, match="shortcut-connected"):
        tiny_config(first_k_dense_replace=1)
    with pytest.raises(ValueError, match="shortcut-connected"):
        tiny_config(layer_types=("full_attention",) * 2, num_key_value_heads=2, head_dim=16, sliding_window=4)
    with pytest.raises(ValueError, match="scoring_func"):
        x = jnp.zeros((4, 64))
        moe.MoELayer(tiny_config(scoring_func="tanh")).init(jax.random.PRNGKey(0), x)
    shapes = jax.eval_shape(lambda: DecoderLanguageModel(tiny_config()).init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)))
    layer = shapes["params"]["layer_0"]
    assert sorted(layer) == ["attn0", "attn0_norm", "attn1", "attn1_norm", "ffn0", "ffn0_norm", "ffn1", "ffn1_norm", "moe"]
    assert layer["moe"]["gate"].shape == (64, REAL + ZERO) and layer["moe"]["gate_bias"].shape == (REAL + ZERO,)
    assert layer["moe"]["gate_bias"].dtype == jnp.float32 and layer["moe"]["experts_w1"].shape == (4, 64, 32)
    assert moe.grouped_combine(REAL, REAL + ZERO) == "segment_sum"  # every expert with weights held: an identity pair still has no row


def test_scopes_and_taps_reach_the_compiled_programs_and_the_registry(tmp_path):
    config = tiny_config()
    model, params, ids = seeded(config, 0)
    gen_cfg = GenerationConfig(max_new_tokens=3)
    text = make_generate_fn(model, config=gen_cfg).lower(params, ids).as_text(debug_info=True)
    for outer, inner in (("prefill", "mla/expand"), ("prefill", "moe/zero"), ("prefill", "moe/experts"), ("prefill", "dense_mlp"),
                         ("decode", "mla/absorb"), ("decode", "moe/zero"), ("decode", "moe/route"), ("decode", "dense_mlp")):
        assert re.search(rf'"{outer}/[^"]*{inner}', text), (outer, inner)
    assert "moe.load" not in text and "moe/shared" not in text

    from perceiver_io_tpu.obs.events import EventLog
    from perceiver_io_tpu.obs.xplane import op_scope

    assert op_scope("jit(f)/decode/while/body/layer_1.step/moe/moe/zero/mul").layer == "moe/zero"
    events = EventLog(str(tmp_path))
    fn = make_instrumented_generate_fn(model, config=gen_cfg, events=events, probes=True)
    out, stats = fn(params, ids)
    assert stats.outcome == "ok" and out.shape == (4, 8 + 3)
    snapshot = fn.registry.snapshot()
    snap = {**snapshot["counters"], **snapshot["gauges"]}
    routed = 2 * TOP_K * (32 + 4 + 4)  # 2 layers, 4 rows: 32 prompt tokens then 2 steps of 4 tokens, 3 outputs a token
    assert snap["moe_pairs_routed_total"] == routed and snap["moe_pairs_dropped_total"] == 0
    assert 0 < snap["moe_pairs_zero_total"] < routed and 0 < snap["moe_pairs_local_total"] < routed
    assert snap["moe_pairs_zero_total"] + snap["moe_pairs_local_total"] < routed  # the rest went to experts held elsewhere
    assert 1 <= snap["moe_real_experts_per_token"] <= TOP_K
    rows = [json.loads(line) for line in open(tmp_path / "events.jsonl")]
    compiled = next(r for r in rows if r.get("event") == "compile" and "latent_cache_row_bytes" in r)
    assert (compiled["block"], compiled["latent_cache_layers"], compiled["moe_router_width"], compiled["moe_zero_experts"]) \
        == ("shortcut", 4, REAL + ZERO, ZERO)
    assert compiled["moe_combine"] == "segment_sum" and compiled["latent_cache_bytes"] == 4 * 11 * 16 * 4 * 4
    request = [r for r in rows if r.get("event") == "request"][-1]
    assert request["moe_zero_share"] == pytest.approx(snap["moe_pairs_zero_total"] / routed, abs=1e-5)


def test_a_serial_configurations_taps_and_compile_row_keep_their_keys():
    """The configurations before this one: no ``pairs_zero`` in the tap, no ``block`` in the row (their probed programs are the parent's)."""
    from tests.test_decoder_lm import tiny_config as dsv3_config

    config = dsv3_config()
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    layer = moe.MoELayer(config)
    params = layer.init(jax.random.PRNGKey(1), x)
    with probes.collecting(probes.ProbeConfig(scopes=("moe.*",))) as col:
        layer.apply(params, x)
    (load,) = col.stats.values()
    assert sorted(load) == ["expert_load_max", "expert_visits", "expert_weight_blocks", "expert_weight_fetches",
                            "pairs_dropped", "pairs_gathered", "pairs_local", "pairs_routed", "passes"]
    row = generation._decoder_of(DecoderLanguageModel(config)).compile_row(4, 8, 3, jnp.float32)
    assert sorted(row) == ["latent_cache_bytes", "latent_cache_capacity", "latent_cache_layers", "latent_cache_row_bytes", "moe_combine"]
    assert row["latent_cache_layers"] == config.num_hidden_layers
