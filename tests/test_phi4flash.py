"""The decoder-only model under its eighth configuration (the SambaY family,
Phi-4-mini-flash-reasoning: a decoder-hybrid-decoder stack of Mamba-1 and
differential window attention below, one differential full attention whose
keys and values are **the shared cache**, gated memory units and differential
cross-attentions above it, LayerNorms, a tied head) against its plain
reference, at tiny widths that keep the published shape: 12 layers by the
``phi4flash`` rule (Mamba at 0 .. 6 even, windows of 8 at 1 .. 5 odd, the full
layer at 7, GMUs and cross layers above), hidden 64, ``d_inner`` 128, 4 states,
a ``dt`` rank of 4, 8 query heads on 4 key-value heads of 8.

Tolerances as in ``tests/test_decoder_lm.py``: float32 products at "highest"
precision on both sides, so the program and ``benchmarks/reference/phi4flash.py``
differ in the order of float32 sums alone (the flash kernel's online softmax,
the scan kernel's state order), 3e-4 absolute on logits of magnitude up to
about 10: the subnorm divides a difference of two softmax maps by its rms, which
carries the sums' rounding on. A wrong model (a stale cache, ``lam`` = 0, the
memory after the gate) moves the same logits by hundreds of times that, and
tests say so. With a bfloat16 cache (windows, rings and the shared cache rounded
to 8 bits; the subnorm divides the rounding of a difference by that
difference's rms) the served logits read 0.29 and 0.68 from the float32
reference's over two seeds at these sharp tiny-width softmaxes: 1.5 absolute,
which ``lam`` = 0 and the memory after the gate still fail by four times (6.6 to
9.4); a cache one row stale moves a logit by 0.04 and is seen by the float32
tolerance alone. Products in bfloat16 are not run here: XLA's CPU backend
refuses the step's bfloat16 x bfloat16 = float32 products under ``jit``. The
weights are seeded as the benchmark's family seeds them
(``families/jamba.py::remembering``)."""

import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families.jamba import remembering
from benchmarks.families.phi4flash import layer_types
from benchmarks.lib import phi4flash_cost
from benchmarks.lib.weights import flat_dict
from benchmarks.reference import phi4flash as reference
from perceiver_io_tpu import generation
from perceiver_io_tpu.core.cache import KVCache, RecurrentState, WindowKVCache
from perceiver_io_tpu.models.text import decoder_lm
from perceiver_io_tpu.models.text.decoder_lm import DecoderLanguageModel, DecoderLanguageModelConfig

fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")  # the package exports a function of that name

TOL = 3e-4
BF16_TOL = 1.5
VOCAB = 96
DEPTH, WINDOW = 12, 8


def tiny_config(**kw) -> DecoderLanguageModelConfig:
    base = dict(
        vocab_size=VOCAB, hidden_size=64, num_hidden_layers=DEPTH, first_k_dense_replace=DEPTH, intermediate_size=96,
        num_attention_heads=8, num_key_value_heads=4, head_dim=8, sliding_window=WINDOW,
        layer_types=layer_types(dict(num_hidden_layers=DEPTH, mb_per_layer=2)), rope_scaling=None, tie_word_embeddings=True,
        layer_norm_eps=1e-5, differential_attention=True, mamba_inner_norms=False, mamba_expand=2, mamba_d_state=4,
        mamba_dt_rank=4, mamba_d_conv=4, init_scale=0.3, max_position_embeddings=512,
    )
    base.update(kw)
    return DecoderLanguageModelConfig(**base)


def reference_cfg(config: DecoderLanguageModelConfig) -> dict:
    return dict(dataclasses.asdict(config), mb_per_layer=2)


def seeded(config, seed: int, batch: int = 2, n: int = 21, dtype=jnp.float32):
    """The model, its weights drawn from ``seed`` with the recurrences' leaves as the family hands them on, and prompts."""
    model = DecoderLanguageModel(config, dtype=dtype)
    k_ids, k_init = jax.random.split(jax.random.PRNGKey(seed))
    ids = jax.random.randint(k_ids, (batch, n), 0, config.vocab_size)
    params = model.init(k_init, ids)
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: remembering(getattr(path[-1], "key", ""), leaf, config.init_scale, 1e-3, 1e-1), params)
    # biases and norm offsets are zeros at init: seed them, as the benchmark does, so that a missing one shows
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), len(jax.tree.leaves(params))))
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf + 0.1 * jax.random.normal(next(keys), leaf.shape)
        if getattr(path[-1], "key", "") in ("bias", "scale") else leaf, params)
    return model, params, ids


def served_logits(model, params, ids, new_tokens: int, cache_dtype=jnp.float32):
    """Greedy decoding through the generator's own decoder (the cut prompt
    pass, then one-token steps over states, rings and the shared cache): the
    logits the tokens were read from (B, new_tokens, V), the tokens, and the state at the end."""
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


def reference_at_served(params, ids, tokens, config, **kw):
    """The reference's logits at the served positions: one full forward over the prompt with its served tokens."""
    n = tokens.shape[1]
    both = jnp.concatenate([ids, jnp.asarray(tokens[:, :-1])], axis=1)
    return np.asarray(reference.logits(flat_dict(params), both, reference_cfg(config), last=n, **kw))


@functools.lru_cache(maxsize=None)
def served(cache_dtype=jnp.float32):
    """Seed 3 served once a cache dtype for the tests that read it: the model's side, what it served, and the reference's logits there."""
    config = tiny_config()
    model, params, ids = seeded(config, 3)
    got, tokens, caches = served_logits(model, params, ids, 24, cache_dtype)
    return config, params, ids, got, tokens, caches, reference_at_served(params, ids, tokens, config)


# ------------------------------------------------------------ the configuration


def test_the_layer_rule_and_the_layers_that_are_read():
    kinds = layer_types(dict(num_hidden_layers=32, mb_per_layer=2))
    assert [kinds.count(k) for k in ("mamba", "sliding_attention", "full_attention", "gmu", "cross_attention")] == [9, 8, 1, 7, 7]
    assert kinds[16:20] == ("mamba", "full_attention", "gmu", "cross_attention") and kinds.index("full_attention") == 17
    config = tiny_config()
    assert (config.memory_layer, config.shared_cache_layer, config.prompt_layers) == (6, 7, 7)
    assert reference.layer_kinds(reference_cfg(config)) == tuple(
        {"sliding_attention": "window", "full_attention": "full", "cross_attention": "cross"}.get(k, k) for k in config.layer_types)
    plain = DecoderLanguageModelConfig()
    assert (plain.memory_layer, plain.shared_cache_layer, plain.prompt_layers) == (None, None, plain.num_hidden_layers)


@pytest.mark.parametrize("kinds, why", [
    (("sliding_attention", "full_attention", "gmu", "cross_attention"), "a mamba layer below"),
    (("mamba", "sliding_attention", "gmu", "cross_attention"), "a full_attention layer below"),
    (("mamba", "full_attention", "gmu", "gmu"), "shared cache"),
    (("mamba", "full_attention", "cross_attention", "mamba"), "shared cache"),
])
def test_a_reader_with_nothing_to_read_is_refused(kinds, why):
    with pytest.raises(ValueError, match=why):
        tiny_config(num_hidden_layers=4, first_k_dense_replace=4, layer_types=kinds)


def test_the_parameters_are_the_cost_librarys_count():
    config = tiny_config()
    model = DecoderLanguageModel(config)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == phi4flash_cost.held_params(dataclasses.asdict(config))
    layer = shapes["params"]["layer_9"]  # a cross layer: a query projection and an output, no key, no value
    assert set(layer["attn"]) == {"w_q", "b_q", "w_o", "b_o", "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "subln"}
    assert set(shapes["params"]["layer_8"]["mixer"]) == {"w_in", "w_out"}  # a gated memory unit
    assert "dt_norm" not in shapes["params"]["layer_0"]["mixer"] and set(shapes["params"]["layer_0"]["attn_norm"]) == {"scale", "bias"}


# the seven configurations of the class that were there: family, leaves, and the first 12 digits of the sha256 of
# the sorted (name, shape, dtype) of every leaf, taken on the parent commit (8225bb7) by the lines of the test
OTHER_FAMILIES = {
    "deepseek-v3-ep16": ("deepseek_v3", 83, "2554b9fc1d96"), "mellum2-12b-pp4": ("mellum", 83, "0c205b56946b"),
    "k-exaone-236b-ep8": ("exaone_moe", 98, "c834d3ae734b"), "longcat-flash-ep32": ("longcat_flash", 119, "54f9390c5f21"),
    "jamba2-3b": ("jamba", 462, "1e9ee13a9aac"), "brumby-14b-pp8": ("brumby", 68, "ffdf4a68449c"),
    "ling3-flash-ep4": ("ling", 152, "fd654f720d61"),
}


@pytest.mark.parametrize("name", sorted(OTHER_FAMILIES))
def test_the_other_families_build_the_trees_they_built(name):
    import hashlib

    from benchmarks import run

    family, leaves, golden = OTHER_FAMILIES[name]
    fam = importlib.import_module(f"benchmarks.families.{family}").Family(run.load_json("configs", name))
    shapes = flat_dict(fam.param_shapes(fam.model()))
    assert len(shapes) == leaves
    digest = hashlib.sha256(repr(sorted((k, v.shape, str(v.dtype)) for k, v in shapes.items())).encode()).hexdigest()
    assert digest[:12] == golden


# ------------------------------------------------------------ the whole model


@pytest.mark.parametrize("n", [5, 21], ids=["inside_the_window", "past_the_window"])
def test_full_forward_matches_the_reference(n):
    config = tiny_config()
    model, params, ids = seeded(config, 0, n=n)
    got = np.asarray(model.apply(params, ids))
    want = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config)))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_the_flash_kernel_path_matches_the_reference():
    """The prompt pass through ``ops/diff_attention.py`` (interpret mode) and the scan kernel: rows of 128, a window of 40."""
    config = tiny_config(sliding_window=40, num_hidden_layers=4, first_k_dense_replace=4,
                         layer_types=("mamba", "sliding_attention", "mamba", "full_attention"))
    model, params, ids = seeded(config, 1, batch=1, n=160)
    with fa.default_flash(True):
        got = np.asarray(jax.jit(model.apply)(params, ids))
    want = np.asarray(reference.logits(flat_dict(params), ids, dict(dataclasses.asdict(config), mb_per_layer=2, num_hidden_layers=4)))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_the_cut_prompt_pass_gives_the_full_forwards_last_position():
    config = tiny_config()
    model, params, ids = seeded(config, 2)
    logits, rows = jax.jit(lambda p, i: decoder_lm.prefill(model, p, i))(params, ids)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(model.apply(params, ids))[:, -1], atol=TOL, rtol=0)
    assert len(rows) == 8  # 4 states, 3 rings' rows, the shared cache's rows: the readers hand on nothing
    k, v = rows[-1]
    assert k.shape == v.shape == (2 * 2, 21, 16)  # a pair of key heads is a row: (B * Hkv / 2, N, 2d)


@pytest.mark.parametrize("cache_dtype, tol", [(jnp.float32, TOL), (jnp.bfloat16, BF16_TOL)], ids=["float32", "bfloat16_cache"])
def test_prefill_then_decode_matches_the_references_full_forward(cache_dtype, tol):
    """The cut prompt pass, then 24 steps through states, rings (which wrap: the window is 8) and the shared cache."""
    _, _, _, got, _, caches, want = served(cache_dtype)
    np.testing.assert_allclose(got, want, atol=tol, rtol=0)
    # 4 states, 3 rings, 1 cache: the generator's state has an entry a layer that owns one, and no copy a reader
    assert [type(c) for c in caches] == [RecurrentState, WindowKVCache] * 3 + [RecurrentState, KVCache]
    assert int(caches[-1].length) == 21 + 23 and caches[-1].k.shape == (2 * 2, 21 + 24, 16) and caches[1].k.shape == (2 * 2, WINDOW, 16)
    # a narrow cache narrows the windows, the rings and the shared cache; the states stay float32
    assert all(c.ssm.dtype == jnp.float32 for c in caches[::2]) and caches[-1].k.dtype == cache_dtype and caches[0].conv.dtype == cache_dtype


@pytest.mark.parametrize("wrong, tol, times", [("stale_cache", TOL, 100), ("lam0", BF16_TOL, 4), ("memory_after_gate", BF16_TOL, 4)])
def test_a_wrong_model_fails_the_tolerance(wrong, tol, times):
    """The reference's own wrong variants against the reference: each moves a served logit by ``times`` the tolerance that sees it."""
    config, params, ids, _, tokens, _, right = served()
    assert np.abs(reference_at_served(params, ids, tokens, config, wrong=wrong) - right).max() > times * tol


def test_the_cross_layers_read_what_the_owning_layer_wrote_in_the_same_step():
    """One ``decode_step`` after the prompt pass is the reference's logits at that position, where every cross layer
    sees the position's own key and value; the reference with the cross layers one row behind (the owning layer's
    write of the step left out) lies five tolerances and more away."""
    config = tiny_config()
    model, params, ids = seeded(config, 4)
    decoder = generation._decoder_of(model)
    logits, (caches,), _ = jax.jit(lambda p, i: decoder.prefill(p, i, None, 1, 4, jnp.float32))(params, ids)
    token = jnp.argmax(logits[:, -1], axis=-1)
    right, new = jax.jit(lambda p, t, c: model.apply(p, t, c, method="decode_step"))(params, token, caches)
    # the one cache advanced by one row, in the owning layer's entry, and every other length with it
    assert int(new[-1].length) == int(caches[-1].length) + 1
    want = reference_at_served(params, ids, np.concatenate([np.asarray(token)[:, None]] * 2, axis=1), config)[:, 1]
    np.testing.assert_allclose(np.asarray(right), want, atol=TOL, rtol=0)
    stale = reference_at_served(params, ids, np.concatenate([np.asarray(token)[:, None]] * 2, axis=1), config, wrong="stale_cache")[:, 1]
    assert np.abs(np.asarray(right) - stale).max() > 5 * TOL


def test_the_probes_read_the_shared_cache_the_memory_and_lambda():
    config = tiny_config()
    model, params, ids = seeded(config, 5)
    decoder = generation._decoder_of(model)
    assert {"yoco.*", "gmu.*", "ssm.*"} <= set(decoder.tap_scopes)
    (_, taps) = generation._with_taps(decoder.tap_scopes, lambda: decoder.prefill(params, ids, None, 1, 4, jnp.float32))
    assert int(taps["yoco_reads"]) == 2 and int(taps["yoco_cache_length_max"]) == 21  # the tiny stack's two cross layers
    assert int(taps["gmu_sites"]) == 2 and float(taps["gmu_memory_rms_sum"]) > 0
    lam0 = [0.8 - 0.6 * np.exp(-0.3 * i) for i in (1, 3, 5, 7, 9, 11)]  # the six attentions; the seeded vectors move lam by little
    assert float(taps["diff_lam_sum"]) / int(taps["diff_lam_sites"]) == pytest.approx(np.mean(lam0), abs=0.3)
    row = decoder.compile_row(2, 21, 4, jnp.bfloat16)
    assert row["shared_cache_layer"] == 7 and row["shared_cache_readers"] == 3 and row["prompt_layers"] == 7
    assert row["shared_cache_bytes"] == 2 * 25 * 2 * 4 * 8 * 2 and row["shared_cache_bytes_unshared"] == 3 * row["shared_cache_bytes"]
