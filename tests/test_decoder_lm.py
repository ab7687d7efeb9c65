"""The decoder-only (DeepSeek-V3 family) language model against its plain
reference, at tiny widths that keep every ratio of the published model:
hidden 64, 4 heads, ranks 24/16, nope 16 + rope 8, 16 experts in 4 groups of
which 2 stay and 2 experts a token, 3 layers of which 1 dense.

Tolerances. The suite runs float32 products at "highest" precision, so the
program and ``benchmarks/reference/deepseek_v3.py`` differ only in the order
of float32 sums: logits of magnitude up to about 10 agree to 2e-4 absolute
(observed 2e-5 to 6e-5). bfloat16 products in the reference's place move the
same logits by 5e-2 and more, 250 times the tolerance, and a test says so.
Routing is discrete: a token whose eighth and ninth scores lie within
rounding of each other could take another expert in the two programs, and
the logits would then differ by far more than 2e-4; the seeds used have no
such tie (the tests would say so loudly, not flakily: same seeds, same
machine arithmetic).

How the work is cut (chunks of the prompt pass, the token count from which
the experts take the grouped path, its rows a pass and row tile) is module
constants, not options: most tests here are too small to reach them (32
tokens: one chunk, the dense expert path), and the ones that test the cuts
run enough tokens at these tiny widths to cross the shipped values."""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib.weights import flat_dict
from benchmarks.reference import deepseek_v3 as reference
from perceiver_io_tpu import generation
from perceiver_io_tpu.core import moe
from perceiver_io_tpu.core.cache import init_latent_cache
from perceiver_io_tpu.core.mla import MultiHeadLatentAttention
from perceiver_io_tpu.core.position import apply_rotary_interleaved, yarn_inv_freq, yarn_mscale
from perceiver_io_tpu.generation import GenerationConfig, make_decode_fns, make_generate_fn
from perceiver_io_tpu.models.text import decoder_lm
from perceiver_io_tpu.models.text.decoder_lm import DecoderLanguageModel, DecoderLanguageModelConfig, YarnConfig
from perceiver_io_tpu.obs import probes
from perceiver_io_tpu.ops import grouped_matmul as gm
from perceiver_io_tpu.ops.grouped_matmul import grouped_matmul, visit_plan
from perceiver_io_tpu.ops.layernorm import rms_norm

TOL = 2e-4  # float32 against float32, see the module docstring
VOCAB = 96
CUTS = moe._cuts(64, 32, 4)  # how the expert layer cuts its work at these tiny widths (hidden 64, width 32, 4 experts held)


def tiny_config(**kw) -> DecoderLanguageModelConfig:
    base = dict(
        vocab_size=VOCAB, hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1, intermediate_size=160,
        moe_intermediate_size=32, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16, n_held_experts=4, held_experts_start=4,
        num_experts_per_tok=2, n_group=4, topk_group=2, init_scale=0.3, max_position_embeddings=64,
    )
    base.update(kw)
    return DecoderLanguageModelConfig(**base)


def reference_cfg(config: DecoderLanguageModelConfig) -> dict:
    return dataclasses.asdict(config)


def seeded(config, seed: int, batch: int = 4, n: int = 8):
    """A model, weights drawn from the seed (every leaf noisy, the norms'
    scales 1 + noise, the router's bias too), and prompt ids."""
    model = DecoderLanguageModel(config)
    k_ids, k_init, k_noise = jax.random.split(jax.random.PRNGKey(seed), 3)
    ids = jax.random.randint(k_ids, (batch, n), 0, config.vocab_size)
    params = model.init(k_init, ids)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(k_noise, len(leaves))
    params = jax.tree.unflatten(tree, [p + 0.1 * jax.random.normal(k, p.shape) for p, k in zip(leaves, keys)])
    return model, params, ids


SHARES = [(0, 16), (4, 4), (12, 4)]


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("start,held", SHARES, ids=lambda v: str(v))
def test_full_forward_matches_the_reference(seed, start, held):
    config = tiny_config(held_experts_start=start, n_held_experts=held)
    model, params, ids = seeded(config, seed)
    got = np.asarray(model.apply(params, ids))
    want = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config)))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_bfloat16_in_the_references_place_fails_the_float32_tolerance():
    config = tiny_config()
    model, params, ids = seeded(config, 0)
    want = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config)))
    lower = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config), precision="bfloat16"))
    assert np.abs(lower - want).max() > 50 * TOL


def served_logits(model, params, ids, new_tokens: int, cache_dtype=jnp.float32):
    """Greedy decoding through the generator's own decoder (prompt pass, then
    one-token steps over the latent caches): the logits the tokens were read
    from, (B, new_tokens, V), and the tokens."""
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
    return np.stack([np.asarray(o) for o in out], axis=1), np.stack([np.asarray(t) for t in tokens], axis=1)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_prompt_pass_then_cached_decode_matches_the_references_full_forward(seed):
    """Every served position: the logits of prefill + decoding through the
    latent cache against one plain forward over the prompt with the served
    tokens; and the fused generator and the host-driven pair serve exactly
    those tokens."""
    new = 6
    config = tiny_config()
    model, params, ids = seeded(config, seed, batch=4, n=8)
    got, tokens = served_logits(model, params, ids, new)
    full = np.concatenate([np.asarray(ids), tokens[:, :-1]], axis=1)
    want = np.asarray(reference.logits(flat_dict(params), jnp.asarray(full), reference_cfg(config), last=new))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)

    gen_cfg = GenerationConfig(max_new_tokens=new)
    fused = np.asarray(make_generate_fn(model, num_latents=8, config=gen_cfg)(params, ids))
    np.testing.assert_array_equal(fused[:, :8], np.asarray(ids))
    np.testing.assert_array_equal(fused[:, 8:], tokens)
    prefill_fn, step_fn = make_decode_fns(model, 8, gen_cfg)
    token, state = prefill_fn(params, ids)
    stream = [np.asarray(token)]
    for _ in range(new - 1):
        state, token = step_fn(state)
        stream.append(np.asarray(token))
    np.testing.assert_array_equal(np.stack(stream, axis=1), tokens)
    assert int(state["cache"][0].length) == 8 + new - 1
    # where the reference's best leads its second by more than the tolerance, it is the served token
    top2 = np.sort(want, axis=-1)[..., -2:]
    clear = (top2[..., 1] - top2[..., 0]) > 2 * TOL
    np.testing.assert_array_equal(want.argmax(-1)[clear], tokens[clear])


@pytest.mark.parametrize("seed", [0, 1])
def test_prompt_pass_through_the_token_major_kernel_then_cached_decode_matches_the_reference(seed):
    """The published head widths (128 + 64 rotary, 128 value channels), where
    the expanded pass runs ``flash_attention_mla`` on what its up-projections
    write (here in interpret mode, ``default_flash(True)``), a prompt of two
    attention chunks: every served position against the float32 reference,
    and the caches the steps read hold the rows of the XLA path to the bit."""
    import importlib

    fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")
    new, n = 3, 128
    config = tiny_config(num_attention_heads=2, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, num_hidden_layers=2,
                         max_position_embeddings=256)
    model, params, ids = seeded(config, seed, batch=2, n=n)
    decoder = generation._decoder_of(model)
    with fa.default_flash(True):
        lowered = jax.jit(lambda p, i: decoder.prefill(p, i, None, 1, new, jnp.float32)).lower(params, ids).as_text(debug_info=True)
        got, tokens = served_logits(model, params, ids, new)
    assert f"flash_mla_fwd_q{n}_kv{n}_h2" in lowered and not re.search(r"flash_fwd_q\d", lowered)
    full = np.concatenate([np.asarray(ids), tokens[:, :-1]], axis=1)
    want = np.asarray(reference.logits(flat_dict(params), jnp.asarray(full), reference_cfg(config), last=new))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    with fa.default_flash(True):
        _, with_kernel = decoder_lm.prefill(model, params, ids)
    with fa.default_flash(False):
        _, without = decoder_lm.prefill(model, params, ids)
    for a, b in zip(with_kernel, without):
        np.testing.assert_array_equal(np.asarray(a[:, 0]), np.asarray(b[:, 0]))  # position 0: no layer before it read the kernel's output
    np.testing.assert_array_equal(np.asarray(with_kernel[0]), np.asarray(without[0]))  # the first layer's rows, every position


def test_bfloat16_cache_serves_within_the_caches_rounding():
    config = tiny_config()
    model, params, ids = seeded(config, 3)
    exact, _ = served_logits(model, params, ids, 4)
    rounded, _ = served_logits(model, params, ids, 4, cache_dtype=jnp.bfloat16)
    np.testing.assert_array_equal(exact[:, 0], rounded[:, 0])  # the prompt pass reads no cache
    assert 0 < np.abs(exact - rounded).max() < 0.5


def test_generator_refuses_what_the_decoder_only_model_cannot_do():
    config = tiny_config(max_position_embeddings=12)
    model, params, ids = seeded(config, 0)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        make_generate_fn(model, config=GenerationConfig(max_new_tokens=8))(params, ids)
    with pytest.raises(ValueError, match="pad_mask"):
        make_generate_fn(model, config=GenerationConfig(max_new_tokens=2))(params, ids, jnp.zeros(ids.shape, bool))
    # int8 weights key on the Perceiver models' `kernel` leaves: here they would quantize nothing, silently
    with pytest.raises(ValueError, match="weight_dtype"):
        make_generate_fn(model, config=GenerationConfig(max_new_tokens=2), weight_dtype=jnp.int8)(params, ids)


@pytest.mark.parametrize("seed", [0, 1])
def test_a_prompt_pass_cut_into_chunks_is_the_uncut_forward(seed):
    """Enough tokens (64 rows of 256) to cross the shipped cuts: four chunks
    of rows through attention, two chunks of tokens through the feed-forward,
    the experts on their grouped path, the taps carried out of the loops.
    Rows are independent, so the reference's forward over two of them holds
    the whole batch's last-position logits and cache rows to account."""
    b, n = 64, 256
    assert b * n > decoder_lm._PREFILL_FFN_TOKENS > decoder_lm._PREFILL_ATTENTION_TOKENS >= CUTS.grouped_min_tokens
    config = tiny_config(max_position_embeddings=n + 1)
    model, params, ids = seeded(config, seed, batch=b, n=n)

    def chunked(params, ids):
        with probes.collecting(probes.ProbeConfig(scopes=("moe.*",), activations=False)) as col:
            logits, (caches,), _ = generation._decoder_of(model).prefill(params, ids, None, n, 1, jnp.float32)
        return logits[:, -1], caches, col.stats

    logits, caches, stats = jax.jit(chunked)(params, ids)
    assert len(stats) == 2  # one tap site an expert layer, summed over the chunks
    for load in stats.values():
        assert int(load["pairs_routed"]) == 2 * b * n and int(load["pairs_dropped"]) == 0
        assert 0 < int(load["pairs_local"]) < 2 * b * n and int(load["expert_load_max"]) >= b * n // 16
        assert int(load["pairs_gathered"]) == int(load["pairs_local"])  # a share of the experts: every local pair's row read back by the segment sum
    rows = np.array([0, b - 1])
    want = np.asarray(reference.logits(flat_dict(params), ids[rows], reference_cfg(config), last=1))[:, 0]
    np.testing.assert_allclose(np.asarray(logits)[rows], want, atol=TOL, rtol=0)
    whole = np.asarray(jax.jit(model.apply)(params, ids[rows]))[:, -1]  # the uncut forward of those rows
    np.testing.assert_allclose(np.asarray(logits)[rows], whole, atol=TOL, rtol=0)
    assert all(int(c.length) == n and c.rows.shape == (b, n + 1, 24) for c in caches)


# ------------------------------------------------------------------- MLA


@pytest.mark.parametrize("seed", [0, 1])
def test_absorbed_attention_is_the_expanded_attention(seed):
    """One set of weights, two ways: token by token through the latent cache
    (queries carried into the latent space) against all tokens at once with
    per-head keys and values."""
    config = tiny_config()
    attn = MultiHeadLatentAttention(config)
    b, n = 3, 10
    kx, kp = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (b, n, config.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(n)[None], (b, n))
    params = attn.init(kp, x, pos, method="expand")
    params = jax.tree.map(lambda p: p + 0.1 * jax.random.normal(kx, p.shape), params)
    want, rows = attn.apply(params, x, pos, method="expand")
    cache = init_latent_cache(b, n + 2, config.kv_lora_rank + config.qk_rope_head_dim)
    for t in range(n):
        got, cache = attn.apply(params, x[:, t:t + 1], cache, pos[:, t:t + 1], method="absorb")
        np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want[:, t]), atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(cache.rows[:, :n]), np.asarray(rows), atol=1e-6, rtol=0)
    assert int(cache.length) == n and cache.row_bytes == 24 * 4


def test_yarn_frequencies_and_mscale_against_hand_computed_values():
    """DeepSeek-V3's numbers: 64 rope channels, theta 10000, factor 40, beta
    32 and 1, original context 4096. The correction range is the pair index
    at which a frequency turns 32 times over 4096 positions, floor(10.47) =
    10, to the one that turns once, ceil(22.51) = 23: pairs below 10 keep
    their frequency, pairs from 23 are divided by 40, a linear ramp between."""
    f = yarn_inv_freq(64, 10000.0, 40.0, 32.0, 1.0, 4096)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64.0)
    assert f.shape == (32,)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], plain[23:] / 40.0, rtol=1e-6)
    np.testing.assert_allclose(f[16], 0.01 * ((6 / 13) / 40.0 + (7 / 13)), rtol=1e-5)  # plain[16] = 0.01
    assert yarn_mscale(40.0, 1.0) == pytest.approx(1.3688879454)
    assert yarn_mscale(1.0) == 1.0
    attn = MultiHeadLatentAttention(tiny_config(rope_scaling=YarnConfig()))
    assert attn.sm_scale == pytest.approx(24 ** -0.5 * 1.3688879454 ** 2)
    assert MultiHeadLatentAttention(tiny_config(rope_scaling=None)).sm_scale == pytest.approx(24 ** -0.5)


def test_rotary_pairs_adjacent_channels_as_complex_numbers():
    t = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 8))
    pos = jnp.arange(5)[None].repeat(2, 0)
    inv_freq = np.array([1.0, 0.5, 0.1, 0.01], np.float32)
    got = np.asarray(apply_rotary_interleaved(t, pos, inv_freq))
    z = (np.asarray(t)[..., 0::2] + 1j * np.asarray(t)[..., 1::2]) * np.exp(1j * np.asarray(pos)[..., None] * inv_freq)
    want = np.stack([z.real, z.imag], axis=-1).reshape(t.shape)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_rms_norm_is_the_formula_in_float32():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 32)) * 3
    scale = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (32,))
    want = np.asarray(x) / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-6) * np.asarray(scale)
    np.testing.assert_allclose(np.asarray(rms_norm(x, scale)), want, rtol=1e-6)
    assert rms_norm(x.astype(jnp.bfloat16), scale).dtype == jnp.bfloat16


# ---------------------------------------------------------------- routing


def route_by_loop(scores, bias, n_group, topk_group, top_k, scale):
    """The router written out in numpy, a token at a time."""
    t, e = scores.shape
    per = e // n_group
    chosen, weights = np.zeros((t, top_k), np.int64), np.zeros((t, top_k))
    for i in range(t):
        biased = scores[i] + bias
        group_score = [np.sort(biased[g * per:(g + 1) * per])[-2:].sum() for g in range(n_group)]
        kept = np.argsort(group_score)[::-1][:topk_group]
        candidates = [j for j in range(e) if j // per in kept]
        best = sorted(candidates, key=lambda j: -biased[j])[:top_k]
        chosen[i] = best
        w = scores[i][best]  # the weights are the scores without the bias
        weights[i] = w / w.sum() * scale
    return chosen, weights


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("bias_scale", [0.0, 0.3], ids=["no_bias", "bias"])
def test_router_against_a_loop(seed, bias_scale):
    rng = np.random.default_rng(seed)
    scores = 1 / (1 + np.exp(-rng.normal(size=(40, 32)))).astype(np.float32)
    bias = (bias_scale * rng.normal(size=32)).astype(np.float32)
    kw = dict(n_group=8, topk_group=3, top_k=4, scale=2.5)
    chosen, weights = moe.choose_experts(jnp.asarray(scores), jnp.asarray(bias), **kw)
    want_chosen, want_weights = route_by_loop(scores.astype(np.float64), bias.astype(np.float64), 8, 3, 4, 2.5)
    np.testing.assert_array_equal(np.asarray(chosen), want_chosen)
    np.testing.assert_allclose(np.asarray(weights), want_weights, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-5)
    if bias_scale:
        # the bias moves the choice (it must be used) and never the weights (it must not be)
        unbiased, _ = moe.choose_experts(jnp.asarray(scores), jnp.zeros(32), **kw)
        assert (np.asarray(unbiased) != want_chosen).any()


# -------------------------------------------------- the expert layer's share


# (chips that share a layer, groups of the router, groups kept): DeepSeek-V3's share of PR 28 in small, and K-EXAONE's
# (PR 34): eight chips with two experts each of sixteen, one group, so no group limit
SHARE_GEOMETRIES = {"four_shares_of_four_groups": (4, 4, 2), "eight_shares_of_one_group": (8, 1, 1)}


def moe_layer_and_weights(seed, tokens=48, **kw):
    config = tiny_config(n_held_experts=16, held_experts_start=0, **kw)
    layer = moe.MoELayer(config)
    x = jax.random.normal(jax.random.PRNGKey(seed), (tokens, config.hidden_size))
    params = layer.init(jax.random.PRNGKey(seed + 1), x)
    params = jax.tree.map(lambda p: p + 0.2 * jax.random.normal(jax.random.PRNGKey(seed + 2), p.shape), params)
    return config, x, params


@pytest.mark.parametrize("geometry", sorted(SHARE_GEOMETRIES))
@pytest.mark.parametrize("path", ["grouped", "dense"])
@pytest.mark.parametrize("seed", [0, 1])
def test_the_shares_add_up_to_the_uncut_layer(seed, path, geometry):
    """The chips that share a layer, each with its experts: what each share
    adds beyond the shared expert (which every chip computes alike, counted
    once) sums to the uncut reference's layer."""
    shares, n_group, topk_group = SHARE_GEOMETRIES[geometry]
    held = 16 // shares
    config, x, params = moe_layer_and_weights(seed, CUTS.grouped_min_tokens if path == "grouped" else 48,
                                              n_group=n_group, topk_group=topk_group)
    w = {"l/" + k: v for k, v in flat_dict(params["params"]).items()}
    whole = np.asarray(reference.experts(x, w, "l", reference_cfg(config), "float32"))
    shared = np.asarray(reference.swiglu(x, w["l/shared/w1"], w["l/shared/w3"], w["l/shared/w2"], "float32"))
    total = shared.copy()
    for start in range(0, 16, held):
        share = dataclasses.replace(config, n_held_experts=held, held_experts_start=start)
        p = dict(params["params"])
        for name in ("experts_w1", "experts_w3", "experts_w2"):
            p[name] = params["params"][name][start:start + held]
        y = np.asarray(moe.MoELayer(share).apply({"params": p}, x))
        # the same share in the reference
        ws = {**w, **{"l/" + name: p[name] for name in ("experts_w1", "experts_w3", "experts_w2")}}
        np.testing.assert_allclose(y, np.asarray(reference.experts(x, ws, "l", reference_cfg(share), "float32")), atol=TOL)
        total += y - shared
    np.testing.assert_allclose(total, whole, atol=TOL, rtol=0)
    assert np.abs(whole - shared).max() > 0.1  # the routed experts matter


@pytest.mark.parametrize("held", [(0, 4), (4, 4)], ids=["hot_share", "cold_share"])
def test_no_pair_is_dropped_under_a_skewed_routing(held):
    """A router whose bias sends every token to experts 0 and 1: the share
    that holds them gets every pair of the batch, four times what an even
    routing would send it and so more than two of the grouped path's passes,
    and serves them all; the share that holds neither gets none. Both agree
    with the reference."""
    tokens = 2 * CUTS.grouped_min_tokens
    assert 2 * moe._pass_rows(2 * tokens, 4 / 16, CUTS) < 2 * tokens  # two pairs a token, all of them here
    config, x, params = moe_layer_and_weights(0, tokens)
    start, n = held
    share = dataclasses.replace(config, n_held_experts=n, held_experts_start=start)
    p = dict(params["params"])
    p["gate_bias"] = jnp.zeros(16).at[:2].set(10.0)
    for name in ("experts_w1", "experts_w3", "experts_w2"):
        p[name] = params["params"][name][start:start + n]
    def tapped(p, x):
        with probes.collecting(probes.ProbeConfig(scopes=("moe.*",))) as col:
            return moe.MoELayer(share).apply({"params": p}, x), col.stats

    grouped, stats = jax.jit(tapped)(p, x)
    (load,) = stats.values()
    assert int(load["pairs_routed"]) == 2 * tokens and int(load["pairs_dropped"]) == 0
    assert int(load["pairs_local"]) == (2 * tokens if start == 0 else 0)
    assert int(load["expert_load_max"]) == (tokens if start == 0 else 0)
    w = {"l/" + k: v for k, v in flat_dict(p).items()}
    want = reference.experts(x, w, "l", reference_cfg(share), "float32")
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(want), atol=TOL, rtol=0)


def test_a_share_of_the_experts_sums_its_rows_by_token_without_a_scatter_add():
    """The rule between the grouped path's two combines is the
    configuration's own fact: a layer that holds 4 of 16 experts has 0 to 2
    local pairs a token, moves only those, brought into token order, and sums
    them a token in ``ops/moe_combine.py``'s kernel (``pairs_gathered`` the
    local pairs, **no** float32 scatter-add of rows in its jaxpr, one pass at
    an even routing, ``moe_combine`` ``"segment_sum"`` in the ``compile`` row);
    the whole layer, every expert held, gathers all pairs and has no such
    scatter or kernel either."""
    tokens = CUTS.grouped_min_tokens
    config, x, params = moe_layer_and_weights(0, tokens)
    jaxpr = lambda c, p: str(jax.make_jaxpr(moe.MoELayer(c).apply)({"params": p}, x))  # noqa: E731
    adds_rows = lambda c, p: bool(re.search(rf"f32\[{tokens},64\] = scatter-add", jaxpr(c, p)))  # noqa: E731

    def tapped(c, p):
        with probes.collecting(probes.ProbeConfig(scopes=("moe.*",))) as col:
            moe.MoELayer(c).apply({"params": p}, x)
            (load,) = col.stats.values()
            return load

    share = dataclasses.replace(config, n_held_experts=4, held_experts_start=4)
    p = {k: v[4:8] if k.startswith("experts_") else v for k, v in params["params"].items()}
    load = tapped(share, p)
    assert 0 < int(load["pairs_gathered"]) == int(load["pairs_local"]) < int(load["pairs_routed"]) == 2 * tokens
    assert int(load["passes"]) == 1 and int(load["pairs_dropped"]) == 0
    assert not adds_rows(share, p) and "moe_combine_t" in jaxpr(share, p) and moe.grouped_combine(4, 16) == "segment_sum"
    load = tapped(config, params["params"])
    assert int(load["pairs_gathered"]) == int(load["pairs_local"]) == int(load["pairs_routed"]) == 2 * tokens
    assert not adds_rows(config, params["params"]) and "moe_combine_t" not in jaxpr(config, params["params"])
    assert moe.grouped_combine(16, 16) == "gather"
    row = lambda c: generation._decoder_of(DecoderLanguageModel(c)).compile_row(4, 8, 3, jnp.float32)  # noqa: E731
    assert row(tiny_config())["moe_combine"] == "segment_sum"
    assert row(tiny_config(n_held_experts=16, held_experts_start=0))["moe_combine"] == "gather"


GROUP_SIZES = [[3, 0, 9, 1, 0, 7, 0, 0], [8, 8, 8, 8, 0, 0, 0, 0], [0, 0, 0, 0, 0, 0, 0, 32], [1, 1, 1, 1, 1, 1, 1, 1],
               [0, 0, 0, 0, 0, 0, 0, 0], [5, 11, 0, 0, 2, 0, 14, 0]]


# (K, N, dtype): one lane tile, and contractions of several 128-lane tiles that the kernel holds whole
PRODUCT_SHAPES = [(16, 128, "float32"), (384, 256, "float32"), (640, 256, "float32"), (384, 256, "bfloat16"), (640, 256, "bfloat16")]


@pytest.mark.parametrize("k_n_dtype", PRODUCT_SHAPES, ids=lambda s: f"k{s[0]}_n{s[1]}_{s[2]}")
@pytest.mark.parametrize("sizes", GROUP_SIZES, ids=lambda s: "-".join(map(str, s)))
def test_grouped_matmul_and_its_visit_plan(sizes, k_n_dtype):
    m, tm = 32, 8
    offsets, group_ids, m_tile_ids, visits = (np.asarray(a) for a in visit_plan(jnp.asarray(sizes, jnp.int32), m, tm))
    ends = np.cumsum(sizes)
    np.testing.assert_array_equal(offsets, np.concatenate([[0], ends]))
    want = [(tile, g) for tile in range(m // tm) for g in range(len(sizes))
            if sizes[g] and max(offsets[g], tile * tm) < min(offsets[g + 1], (tile + 1) * tm)]
    assert int(visits) == len(want)
    assert list(zip(m_tile_ids[:visits], group_ids[:visits])) == want

    width, n, dtype = k_n_dtype
    k = jax.random.split(jax.random.PRNGKey(sum(sizes)), 2)
    lhs, rhs = jax.random.normal(k[0], (m, width)).astype(dtype), jax.random.normal(k[1], (len(sizes), width, n)).astype(dtype)
    assert gm.block_plan(m, width, n, tm, lhs.dtype.itemsize)["tiles_k"] == 1  # the whole contraction is what runs
    out = grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32), tm=tm)
    assert out.dtype == lhs.dtype
    lhs, rhs, out = (np.asarray(a, np.float32) for a in (lhs, rhs, out))
    for g in range(len(sizes)):
        rows = slice(offsets[g], offsets[g + 1])
        # float32: summation order alone; bfloat16: one rounding of a float32 sum to the output's 8 bits
        tol = dict(atol=1e-4, rtol=0) if dtype == "float32" else dict(atol=2 ** -8 * 4 * width ** 0.5, rtol=2 ** -8)
        np.testing.assert_allclose(out[rows], lhs[rows] @ rhs[g], **tol)


# the grouped products the five cells with expert layers run: (hidden, width, row tile) in bfloat16
CELL_EXPERTS = {"dsv3": (7168, 2048, 256), "kexaone": (6144, 2048, 256), "longcat": (6144, 2048, 256), "mellum": (2304, 896, 256),
                "ling": (2560, 768, 128)}


@pytest.mark.parametrize("direction", ["up", "down"])
@pytest.mark.parametrize("cell", sorted(CELL_EXPERTS))
def test_the_block_rule_holds_the_contraction_whole_at_every_cells_products(cell, direction):
    """An expert's weight block keeps its index across the expert's visits (``tiles_k`` 1), inside the kernel's VMEM."""
    hidden, width, tile = CELL_EXPERTS[cell]
    k, n = (hidden, width) if direction == "up" else (width, hidden)
    assert moe._cuts(hidden, width, {"mellum": 64, "ling": 128}.get(cell, 16)).row_tile == tile
    plan = gm.block_plan(4096, k, n, tile, 2)
    assert (plan["tk"], plan["tiles_k"], plan["weights_resident"]) == (k, 1, True)
    # the column whole too at these sizes: an expert's whole matrix a block, each ``lhs`` tile read once
    assert (plan["tn"], plan["tiles_n"], plan["rhs_block_bytes"]) == (n, 1, k * n * 2)
    assert plan["vmem_bytes"] == 2 * 2 * (tile * k + k * n + tile * n) + 4 * tile * n <= gm._VMEM_LIMIT


def test_a_contraction_too_large_for_vmem_is_cut_by_the_same_rule(monkeypatch):
    """A made-up K whose whole block does not fit falls back to the largest
    divisor in whole lane tiles that does, and the cut kernel (partial sums in
    a float32 scratch) gives the whole one's result."""
    plan = gm.block_plan(4096, 262144, 2048, 256, 2)
    assert not plan["weights_resident"] and plan["tk"] % 128 == 0 and plan["tk"] * plan["tiles_k"] == 262144
    assert plan["tn"] == 1024 and plan["vmem_bytes"] <= gm._VMEM_LIMIT < gm._vmem_bytes(256, 2 * plan["tk"], plan["tn"], 2)
    # a contraction that fits beside a narrower column keeps the weights resident and narrows the column
    plan = gm.block_plan(4096, 20480, 4096, 256, 2)
    assert plan["weights_resident"] and plan["tn"] == 512 and gm._vmem_bytes(256, 20480, 1024, 2) > gm._VMEM_LIMIT >= plan["vmem_bytes"]
    sizes = jnp.asarray(GROUP_SIZES[0], jnp.int32)
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    lhs, rhs = jax.random.normal(k[0], (32, 640)), jax.random.normal(k[1], (8, 640, 256))
    whole = gm.grouped_matmul.__wrapped__(lhs, rhs, sizes, tm=8)
    monkeypatch.setattr(gm, "_VMEM_LIMIT", gm._vmem_bytes(8, 128, 256, 4))  # room for one lane tile of K
    assert gm.block_plan(32, 640, 256, 8, 4)["tiles_k"] == 5
    cut = gm.grouped_matmul.__wrapped__(lhs, rhs, sizes, tm=8)
    live = int(sizes.sum())
    np.testing.assert_allclose(np.asarray(cut)[:live], np.asarray(whole)[:live], atol=1e-4, rtol=0)


def test_the_tap_counts_a_weight_block_once_an_expert_where_the_contraction_is_whole(monkeypatch):
    """``moe.grouped_fetches`` on a made-up routing: 3 held experts of 256 x
    128 in passes of 64 rows at a row tile of 16. With the contraction whole a
    block is fetched when the visit's expert changes; cut in two (the parent's
    kind of cut) every visit fetches every block."""
    sizes = jnp.asarray([40, 40, 8], jnp.int32)  # the held experts' shares of 96 routed pairs; 8 went to an expert held elsewhere
    count = lambda: tuple(int(c) for c in moe.grouped_fetches(sizes, 96, 256, 128, 4, 64, 16))  # noqa: E731
    # pass 0: expert 0 on tiles 0 to 2, expert 1 on tiles 2 and 3; pass 1: expert 1 on tile 0, expert 2 on tile 1.
    # Seven visits of four (pass, expert) pairs; a column tile a product
    assert count() == (7, 4 * 3, 4 * 3)
    monkeypatch.setattr(gm, "_blocks", lambda k, n, *_: (128, n))  # the up-projections' 256 in two; the down-projection's 128 stays whole
    assert count() == (7, 7 * (2 + 2) + 4, 4 * (2 + 2 + 1))


# --------------------------------------------------- spans, taps, counters


def test_scopes_and_taps_reach_the_compiled_programs_and_the_registry(tmp_path):
    config = tiny_config()
    model, params, ids = seeded(config, 0)
    gen_cfg = GenerationConfig(max_new_tokens=3)
    text = make_generate_fn(model, config=gen_cfg).lower(params, ids).as_text(debug_info=True)
    for scope in ("mla/expand", "mla/absorb", "moe/route", "moe/experts", "moe/shared", "latent_cache_append"):
        assert scope in text, scope
    # inside the generator's own scopes, the prompt pass's through its chunk loops too
    for outer, inner in (("prefill", "mla/expand"), ("prefill", "moe/experts"), ("decode", "mla/absorb"),
                         ("decode", "moe/experts")):
        assert re.search(rf'"{outer}/[^"]*{inner}', text), (outer, inner)
    assert "moe.load" not in text  # no tap without a collector

    from perceiver_io_tpu.generation import make_instrumented_generate_fn
    from perceiver_io_tpu.obs.events import EventLog

    events = EventLog(str(tmp_path))
    fn = make_instrumented_generate_fn(model, config=gen_cfg, events=events, probes=True)
    out, stats = fn(params, ids)
    assert stats.outcome == "ok" and out.shape == (4, 8 + 3)
    snapshot = fn.registry.snapshot()
    snap = {**snapshot["counters"], **snapshot["gauges"]}
    # 2 expert layers, 4 rows: 32 prompt tokens then 2 steps of 4 tokens, 2 experts a token
    assert snap["moe_pairs_routed_total"] == 2 * 2 * (32 + 4 + 4)
    assert 0 < snap["moe_pairs_local_total"] < snap["moe_pairs_routed_total"]
    assert snap["moe_pairs_dropped_total"] == 0 and snap["moe_expert_load_max"] >= 1
    assert snap["moe_pairs_gathered_total"] == 0  # 32 prompt tokens and steps of 4: every call under the grouped path's cut
    import json

    rows = [json.loads(line) for line in open(tmp_path / "events.jsonl")]
    compiles = [r for r in rows if r.get("event") == "compile" and "latent_cache_row_bytes" in r]
    assert compiles and compiles[0]["latent_cache_row_bytes"] == 24 * 4 and compiles[0]["latent_cache_capacity"] == 11
    assert compiles[0]["moe_combine"] == "segment_sum"
    request = [r for r in rows if r.get("event") == "request"][-1]
    assert request["moe_pairs_dropped"] == 0 and request["moe_local_share"] == pytest.approx(
        snap["moe_pairs_local_total"] / snap["moe_pairs_routed_total"], abs=1e-6)


# ------------------------------------ the Perceiver AR generator is untouched

# sha256 of the lowered text (locations stripped) of the tiny Perceiver AR
# generator and decode pair below, taken on the parent commit (PR 27). The
# decoder-only model came in by moving Perceiver AR's side of the loop behind
# an interface; the program the loop traces for it must stay the parent's to
# the character (PERF.md section 7: the caches' layout hangs on details). A PR
# that means to change the AR generator's program updates these.
AR_GOLDEN = {
    "generate": "fa59f6e53a5a1b05759cf7ebcbc35097a652359d6649d3bcb236cfbf0224e790",
    "prefill": "4d6026c477591bad5b29b25b843fd2739121d441872575133848c77c71b8bece",
    "step": "6f1f9be0f5a10eafb60bf0dfdfd9e49641f0982603e7c8d84f7c8978c5685001",
}


def ar_lowered_texts():
    from perceiver_io_tpu.models.text import CausalLanguageModel, CausalLanguageModelConfig

    config = CausalLanguageModelConfig(
        vocab_size=40, max_seq_len=24, max_latents=8, num_channels=32, num_heads=4, num_self_attention_layers=2,
        num_self_attention_rotary_layers=1, cross_attention_dropout=0.5,
    )
    model = CausalLanguageModel(config)
    ids = jnp.zeros((4, 12), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids, prefix_len=4))
    gen_cfg = GenerationConfig(max_new_tokens=16, eos_token_id=3)
    texts = {"generate": make_generate_fn(model, num_latents=4, config=gen_cfg, cache_dtype=jnp.bfloat16)
             .lower(params, ids).as_text()}
    prefill, step = make_decode_fns(model, 4, gen_cfg, probes=True)
    texts["prefill"] = prefill.lower(params, ids).as_text()
    texts["step"] = step.lower(jax.eval_shape(prefill, params, ids)[1]).as_text()
    return {k: hashlib.sha256(re.sub(r"loc\(.*", "", t).encode()).hexdigest() for k, t in texts.items()}


@pytest.mark.parametrize("program", sorted(AR_GOLDEN))
def test_the_perceiver_ar_generators_lowered_program_is_the_parents(program):
    assert ar_lowered_texts()[program] == AR_GOLDEN[program]


# ---------------------- the decoder-only generators of PR 28 and PR 32 are untouched

# The same pin for the decoder-only model's two older configurations at tiny
# sizes, taken on the parent commit of PR 34 (which gave the class a third
# configuration, caches with a length a row and a speculative generator
# beside these): the one-token generator, prompt pass and step that the
# latent-attention and the window/full grouped-query configurations trace are
# the parent's to the character. A PR that means to change them updates these:
# the probed ``_prefill`` and ``_step`` are PR 54's (the ``moe.load`` tap gained
# ``expert_visits``, ``expert_weight_fetches`` and ``expert_weight_blocks``); the
# ``_generate`` programs, which hold no tap, stand.
DECODER_GOLDEN = {
    "dsv3_generate": "cc2078bfe6bb6924fca1cbf90373efac1e20b8d647febe0c165013bcb1139976",
    "dsv3_prefill": "0b9e0c5172dd67cc6034a0b11799fec1c245cffa4162d9da97f78384034dbeca",
    "dsv3_step": "1147aeb68ce2bc7a4b1aafe8448668fd580f0c63677df6a119e8acb1668822fb",
    "mellum_generate": "67b1747f7062e960de892be626600397e8c99b4ccda74c499b1c2b96bbe91302",
    "mellum_prefill": "221f9e3af8503510a35594f24ceed59e4925aa85a75b04d7df6212fd627628d5",
    "mellum_step": "775ed358b58eccc388974a4c87d7d8aeb1f8eee49a63ce2d40985fdfb24aeb34",
}


def decoder_lowered_texts():
    mellum = DecoderLanguageModelConfig(
        vocab_size=VOCAB, hidden_size=64, num_hidden_layers=4, first_k_dense_replace=0, moe_intermediate_size=32,
        num_attention_heads=8, num_key_value_heads=2, head_dim=16,
        layer_types=("sliding_attention", "sliding_attention", "sliding_attention", "full_attention"), sliding_window=8,
        n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=0, n_group=1, topk_group=1, scoring_func="softmax",
        rope_theta=500000.0, init_scale=0.3, max_position_embeddings=512,
        rope_scaling=YarnConfig(factor=4.0, beta_fast=32.0, beta_slow=1.0, original_max_position_embeddings=8, attention_factor=1.1386),
    )
    texts = {}
    for name, config in (("dsv3", tiny_config()), ("mellum", mellum)):
        model = DecoderLanguageModel(config)
        ids = jnp.zeros((4, 12), jnp.int32)
        params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids))
        gen_cfg = GenerationConfig(max_new_tokens=6, eos_token_id=3)
        texts[name + "_generate"] = make_generate_fn(model, config=gen_cfg, cache_dtype=jnp.bfloat16).lower(params, ids).as_text()
        prefill, step = make_decode_fns(model, 1, gen_cfg, probes=True)
        texts[name + "_prefill"] = prefill.lower(params, ids).as_text()
        texts[name + "_step"] = step.lower(jax.eval_shape(prefill, params, ids)[1]).as_text()
    return {k: hashlib.sha256(re.sub(r"loc\(.*", "", t).encode()).hexdigest() for k, t in texts.items()}


@pytest.mark.parametrize("program", sorted(DECODER_GOLDEN))
def test_the_decoder_only_generators_lowered_program_is_the_parents(program):
    assert decoder_lowered_texts()[program] == DECODER_GOLDEN[program]
