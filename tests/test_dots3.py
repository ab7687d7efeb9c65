"""The decoder-only model under its ninth configuration (the dots3-note family:
latent attention over the keys a lightning indexer selects on the full layers, a
second latent attention of other sizes behind a window on the rest, a leading
dense layer, then a share of sigmoid-routed experts with a shared expert, an
untied head) against its plain reference, at tiny widths that keep the published
shape: hidden 64, full layers of 4 heads of 16 + 8 query-key channels on a
latent of 16, an indexer of 4 heads of 16 that keeps 8 keys, window layers of 2
heads of 24 + 8 on a latent of 32 behind 5 positions, 8 of 16 experts held, the
five layers ``full, sliding, sliding, sliding, full``.

Float32 products at "highest" precision on both sides, so the program (the
selection as a threshold, the expanded attention under its mask or the absorbed
one over gathered rows, a ring of latent rows, the experts by their pairs) and
``benchmarks/reference/dots3.py`` (``lax.top_k`` of whole score blocks, a masked
softmax, the window as a mask, the experts one at a time) differ in the order of
float32 sums: ``TOL`` on logits of magnitude up to about 10. A tiny
``index_topk`` and window make the ring wrap and the context pass ``index_topk``
within a few dozen tokens."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmarks.lib.weights import flat_dict
from benchmarks.reference import deepseek_v3 as dsv3_reference
from benchmarks.reference import dots3 as reference
from perceiver_io_tpu import generation
from perceiver_io_tpu.core import dsa
from perceiver_io_tpu.core.cache import IndexedLatentCache, LatentCache, LatentRingCache, init_latent_ring_cache
from perceiver_io_tpu.generation import GenerationConfig, make_generate_fn
from perceiver_io_tpu.models.text.decoder_lm import DecoderLanguageModel, DecoderLanguageModelConfig
from perceiver_io_tpu.obs import xplane
from perceiver_io_tpu.ops import dsa as kernels

fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")  # the package exports a function of that name

TOL = 5e-4
VOCAB = 96
KINDS = ("full_attention", "sliding_attention", "sliding_attention", "sliding_attention", "full_attention")
SCOPES = ("dsa/index", "dsa/score", "dsa/select", "dsa/attend", "dsa/step_score", "dsa/step_select", "dsa/step_gather",
          "dsa/step_attend", "mla/window", "mla/window_step")


def tiny_config(**kw) -> DecoderLanguageModelConfig:
    base = dict(
        vocab_size=VOCAB, hidden_size=64, num_hidden_layers=5, first_k_dense_replace=1, intermediate_size=96, moe_intermediate_size=32,
        num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        mla_head_gate=True, mla_scale_q_lora=True, mla_scale_kv_lora=True,
        n_routed_experts=16, n_held_experts=8, held_experts_start=0, n_shared_experts=1, num_experts_per_tok=4,
        n_group=1, topk_group=1, routed_scaling_factor=1.0, rope_scaling=None, rope_theta=8e7, init_scale=0.3,
        max_position_embeddings=512, layer_types=KINDS, index_n_heads=4, index_head_dim=16, index_topk=8,
        swa_q_lora_rank=24, swa_kv_lora_rank=32, swa_num_attention_heads=2, swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8,
        swa_v_head_dim=16, swa_rope_theta=50000.0, sliding_window_size=5,
    )
    base.update(kw)
    return DecoderLanguageModelConfig(**base)


def reference_cfg(config: DecoderLanguageModelConfig) -> dict:
    return dataclasses.asdict(config)


def seeded(config, seed: int, batch: int = 2, n: int = 13):
    model = DecoderLanguageModel(config)
    k_ids, k_init = jax.random.split(jax.random.PRNGKey(seed))
    ids = jax.random.randint(k_ids, (batch, n), 0, config.vocab_size)
    return model, model.init(k_init, ids), ids


def served_logits(model, params, ids, new_tokens: int, cache_dtype=jnp.float32):
    """Greedy decoding through the generator's own decoder (prompt pass, then one-token steps over the three cache
    kinds): the logits the tokens were read from, (B, new_tokens, V), the tokens, and the caches at the end."""
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


def served_gap(model, params, ids, config, new_tokens: int, wrong=None) -> float:
    """The widest difference between the served logits and the reference's full forward over the same tokens."""
    got, tokens, _ = served_logits(model, params, ids, new_tokens)
    full = np.concatenate([np.asarray(ids), tokens[:, :-1]], axis=1)
    want = np.asarray(reference.logits(flat_dict(params), jnp.asarray(full), reference_cfg(config), last=new_tokens, wrong=wrong))
    return float(np.abs(got - want).max())


# ------------------------------------------------------------ the whole model


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("n", [6, 21, 40], ids=["under_topk", "past_topk", "long"])
def test_full_forward_matches_the_reference(seed, n):
    config = tiny_config()
    model, params, ids = seeded(config, seed, n=n)
    got = np.asarray(model.apply(params, ids))
    want = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config)))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("n", [6, 21], ids=["prompt_under_topk", "prompt_past_topk"])
def test_prompt_pass_then_steps_match_one_forward_past_the_wrap_and_past_topk(n):
    """40 steps over a ring of 32 slots behind a window of 5 and an indexer that keeps 8: the ring wraps, and the
    context passes ``index_topk`` in the steps (first case) or already in the prompt."""
    config = tiny_config()
    model, params, ids = seeded(config, 3, n=n)
    got, tokens, caches = served_logits(model, params, ids, 40)
    full = np.concatenate([np.asarray(ids), tokens[:, :-1]], axis=1)
    want = np.asarray(reference.logits(flat_dict(params), jnp.asarray(full), reference_cfg(config), last=40))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert [type(c) for c in caches] == [IndexedLatentCache, LatentRingCache, LatentRingCache, LatentRingCache, IndexedLatentCache]
    assert int(caches[0].length) == int(caches[1].length) == n + 39 > caches[1].capacity == config.latent_ring_slots == 32
    assert caches[0].latent.rows.shape == (2, n + 40, 24) and caches[0].index.rows.shape == (2, n + 40, 16)
    assert caches[1].rows.shape == (2, 32, 40)


def test_caches_in_bfloat16_stay_near_the_reference():
    config = tiny_config()
    model, params, ids = seeded(config, 4, n=21)
    got, tokens, caches = served_logits(model, params, ids, 12, cache_dtype=jnp.bfloat16)
    full = np.concatenate([np.asarray(ids), tokens[:, :-1]], axis=1)
    want = np.asarray(reference.logits(flat_dict(params), jnp.asarray(full), reference_cfg(config), last=12))
    assert caches[0].index.rows.dtype == caches[1].rows.dtype == jnp.bfloat16
    assert TOL < np.abs(got - want).max() < 2.0  # a rounded index key flips a selection here and there


def test_a_prompt_shorter_than_topk_is_plain_latent_attention():
    """Everything is selected: the full layers' output is the parent class's own, to the bit, and the reference's
    ``every_key`` model is the same function."""
    config = tiny_config(index_topk=64)
    model, params, ids = seeded(config, 5, n=21)
    plain = dataclasses.replace(config, index_topk=None, index_n_heads=None, index_head_dim=None)
    kept = jax.tree.map(lambda x: x, params)
    for layer in ("layer_0", "layer_4"):
        kept["params"][layer]["attn"] = {k: v for k, v in params["params"][layer]["attn"].items()
                                         if k not in ("w_iq", "w_ik", "w_iw", "index_k_norm")}
    np.testing.assert_array_equal(np.asarray(model.apply(params, ids)), np.asarray(DecoderLanguageModel(plain).apply(kept, ids)))
    want = reference.logits(flat_dict(params), ids, reference_cfg(config), wrong="every_key")
    np.testing.assert_allclose(np.asarray(model.apply(params, ids)), np.asarray(want), atol=TOL, rtol=0)


def test_the_generator_emits_the_decoders_tokens():
    config = tiny_config()
    model, params, ids = seeded(config, 6, n=21)
    _, tokens, _ = served_logits(model, params, ids, 10)
    out = make_generate_fn(model, num_latents=1, config=GenerationConfig(max_new_tokens=10))(params, ids)
    np.testing.assert_array_equal(np.asarray(out[:, 21:]), tokens)


# ------------------------------------------------------------ the selection


@pytest.mark.parametrize("k", [1, 8, 33, 200])
def test_topk_mask_is_lax_top_k_with_ties_and_hidden_slots(k):
    scores = jax.random.normal(jax.random.PRNGKey(k), (3, 50, 128))
    scores = jnp.round(scores * 4) / 4  # many exact ties, some at the threshold
    scores = scores.at[0, 0].set(0.0).at[1, 1, 5:].set(-0.0)
    scores = dsa.causal_scores(scores, 60)  # query i of the chunk sees keys 0 .. 60 + i
    got = np.asarray(dsa.topk_mask(scores, k))
    _, chosen = lax.top_k(scores, min(k, 128))
    want = np.zeros(scores.shape, bool)
    np.put_along_axis(want, np.asarray(chosen), True, axis=-1)
    want &= np.isfinite(np.asarray(scores))
    np.testing.assert_array_equal(got, want)
    assert (got.sum(-1) == np.minimum(k, np.isfinite(np.asarray(scores)).sum(-1))).all()


def program_selection(model, params, ids, layer: int):
    """The mask (B, N, N) the program's full layer ``layer`` forms over ``ids``, and the index scores it was read from."""
    def inside(m, ids):
        b, n = ids.shape
        pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None], (b, n))
        x = m.embed(ids)
        for i in range(layer):
            x, _ = m.layers[i](x, pos)
        attn = m.layers[layer].attn
        h = m.layers[layer].attn_norm(x)
        mask, keys = attn.selection(h, pos)
        q, w = attn._index_queries(attn._c_q(h), h, pos)
        return mask, dsa.causal_scores(dsa.index_scores(q, keys, w), 0)

    import flax.linen as nn

    return nn.apply(inside, model)(params, ids)


@pytest.mark.parametrize("layer", [0, 4])
def test_the_programs_selected_set_is_the_references(layer):
    """Where a query's scores are not within rounding of its threshold (the 8th against the 9th largest), the set is
    the reference's, key for key."""
    config = tiny_config()
    model, params, ids = seeded(config, 7, n=40)
    mask, scores = program_selection(model, params, ids, layer)
    w, cfg = flat_dict(params), reference_cfg(config)
    x = dsv3_reference.f32(w["params/embedding"][ids])
    for i in range(layer):  # the reference's own layers below the one looked at
        sub = dict(cfg, layer_types=cfg["layer_types"][i:i + 1])
        shifted = {k.replace(f"layer_{i}/", "layer_0/"): v for k, v in w.items() if f"layer_{i}/" in k}
        first_dense = 1 if i < cfg["first_k_dense_replace"] else 0
        x = reference_layer(x, shifted, dict(sub, first_k_dense_replace=first_dense))
    prefix = f"params/layer_{layer}/attn"
    h = dsv3_reference.rms_norm(x, w[f"params/layer_{layer}/attn_norm/scale"], cfg["rms_norm_eps"])
    checked = 0
    for row in range(ids.shape[0]):
        c_q = dsv3_reference.rms_norm(reference.c.mm(h[row], dsv3_reference.f32(w[prefix + "/w_dq"]), "float32"),
                                      w[prefix + "/q_norm/scale"], cfg["rms_norm_eps"]) * (cfg["hidden_size"] / cfg["q_lora_rank"]) ** 0.5
        want = np.asarray(reference.selection(h[row], c_q, w, prefix, cfg, "float32", None))[:ids.shape[1]]
        ranked = np.sort(np.asarray(scores[row]), axis=-1)[:, ::-1]
        clear = (ranked[:, 7] - ranked[:, 8] > 1e-4) | ~np.isfinite(ranked[:, 8])
        assert clear.sum() > 30
        np.testing.assert_array_equal(np.asarray(mask[row]).astype(bool)[clear], want[clear])
        checked += int(clear.sum())
    assert checked > 60


def reference_layer(x, w, cfg):
    """One layer of the reference over ``x`` (B, N, h), its weights named ``layer_0``."""
    eps = cfg["rms_norm_eps"]
    h = dsv3_reference.rms_norm(x, w["params/layer_0/attn_norm/scale"], eps)
    x = x + jnp.stack([reference.latent_attention(h[r], w, "params/layer_0/attn", cfg, cfg["layer_types"][0], "float32") for r in range(x.shape[0])])
    h = dsv3_reference.rms_norm(x, w["params/layer_0/ffn_norm/scale"], eps)
    if cfg["first_k_dense_replace"]:
        return x + dsv3_reference.swiglu(h, *(dsv3_reference.f32(w[f"params/layer_0/ffn/{n}"]) for n in ("w1", "w3", "w2")), "float32")
    return x + dsv3_reference.experts(h, w, "params/layer_0/ffn", cfg, "float32")


# ------------------------------------------------------------ each wrong model shows


@pytest.mark.parametrize("wrong", reference.WRONG)
def test_each_wrong_model_of_the_reference_fails_by_the_logits(wrong):
    """The sound program against the reference with one fault planted: far outside the tolerance that the sound
    reference is within, on the full forward and on the served logits."""
    config = tiny_config()
    model, params, ids = seeded(config, 8, n=30)
    got = np.asarray(model.apply(params, ids))
    sound = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config)))
    faulty = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config), wrong=wrong))
    assert np.abs(got - sound).max() < TOL
    assert np.abs(got - faulty).max() > 100 * TOL
    assert served_gap(model, params, ids, config, 12, wrong=wrong) > 100 * TOL > TOL > served_gap(model, params, ids, config, 12)


def test_an_index_cache_one_row_stale_in_a_step_fails_by_the_logits(monkeypatch):
    """A step that scores the index cache as it stood before its own key was written: the newest key is never a candidate."""
    config = tiny_config()
    model, params, ids = seeded(config, 9, n=21)
    assert served_gap(model, params, ids, config, 12) < TOL
    monkeypatch.setattr(dsa.SparseLatentAttention, "_candidates", staticmethod(
        lambda cache: jnp.arange(cache.capacity, dtype=jnp.int32) < cache.length - 1))
    assert served_gap(model, params, ids, config, 12) > 100 * TOL


@pytest.mark.parametrize("fault", ["ring_one_slot_short", "ring_never_wraps", "step_sees_every_slot"])
def test_each_fault_of_the_ring_shows(monkeypatch, fault):
    config = tiny_config()
    model, params, ids = seeded(config, 10, n=21)
    assert served_gap(model, params, ids, config, 40) < TOL
    if fault == "ring_one_slot_short":
        monkeypatch.setattr(LatentRingCache, "visible", lambda self: _visible(self, self.window - 1))
    elif fault == "ring_never_wraps":
        monkeypatch.setattr(LatentRingCache, "append", lambda self, row: self.replace(
            rows=lax.dynamic_update_slice(self.rows, row.astype(self.rows.dtype), (0, jnp.minimum(self.length, self.capacity - 1), 0)),
            length=self.length + 1))
    else:
        monkeypatch.setattr(LatentRingCache, "visible", lambda self: jnp.ones((self.capacity,), bool))
    assert served_gap(model, params, ids, config, 40) > 100 * TOL


def _visible(ring, window):
    t = ring.length - 1
    age = (t - jnp.arange(ring.capacity, dtype=jnp.int32)) % ring.capacity
    return (age < window) & (age <= t)


@pytest.mark.parametrize("n", [3, 32, 45])
def test_a_ring_keeps_each_position_in_its_slot(n):
    slots, window = 32, 5
    rows = jnp.arange(n, dtype=jnp.float32)[None, :, None] * jnp.ones((2, 1, 3))
    ring = init_latent_ring_cache(2, window, slots, 3).fill(rows[:, -slots:], n)
    for t in range(n, n + 40):
        ring = ring.append(jnp.full((2, 1, 3), float(t)))
        held = np.asarray(ring.rows[0, :, 0])
        seen = np.asarray(ring.visible())
        assert sorted(held[seen]) == list(range(max(t - window + 1, 0), t + 1))


# ------------------------------------------------------------ the kernels, in interpret mode


def kernel_config(**kw):
    return tiny_config(qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, num_attention_heads=2, index_head_dim=128,
                       index_topk=32, swa_qk_nope_head_dim=192, swa_qk_rope_head_dim=64, swa_v_head_dim=128, sliding_window_size=70,
                       max_position_embeddings=1024, **kw)


def test_the_kernels_path_matches_the_reference_and_the_xla_path():
    """Under ``default_flash(True)`` at the published head widths the pass runs the four kernels (interpret mode)."""
    config = kernel_config()
    model, params, ids = seeded(config, 11, batch=1, n=256)
    want = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config)))
    plain = np.asarray(model.apply(params, ids))
    with fa.default_flash(True):
        text = jax.jit(lambda p, i: model.apply(p, i)).lower(params, ids).as_text(debug_info=True)
        got = np.asarray(model.apply(params, ids))
    for name in (kernels.index_scores_kernel_name(256, 256, 4), kernels.select_kernel_name(256, 256, 32),
                 kernels.masked_flash_kernel_name(256, 2), kernels.window_flash_kernel_name(256, 2, 70)):
        assert name in text, name
    np.testing.assert_allclose(plain, want, atol=TOL, rtol=0)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("first", [0, 256, 384])
def test_the_index_score_kernel_against_xla(first):
    ks = jax.random.split(jax.random.PRNGKey(first), 3)
    b, nq, n, heads, d = 2, 128, 512, 4, 128
    q, k, w = jax.random.normal(ks[0], (b, nq, heads, d)), jax.random.normal(ks[1], (b, n, d)), jax.random.normal(ks[2], (b, nq, heads))
    want = np.asarray(dsa.causal_scores(dsa.index_scores(q, k, w), first))
    with fa.default_flash(True):
        got = np.asarray(kernels.index_scores(q.reshape(b, nq, -1), k, w, heads, jnp.int32(first)))
    np.testing.assert_array_equal(np.isfinite(got), np.isfinite(want))
    np.testing.assert_allclose(np.where(np.isfinite(want), got, 0), np.where(np.isfinite(want), want, 0), atol=1e-4, rtol=0)


@pytest.mark.parametrize("k", [16, 100])
def test_the_selection_kernel_is_topk_mask_and_writes_its_rows_alone(k):
    scores = jnp.round(jax.random.normal(jax.random.PRNGKey(k), (2, 64, 256)) * 8) / 8
    scores = dsa.causal_scores(scores, 128)
    want = np.asarray(dsa.topk_mask(scores, k))
    with fa.default_flash(True):
        got = np.asarray(kernels.select_mask(scores, k))
        into = np.asarray(kernels.select_mask_into(jnp.full((2, 192, 256), 7, jnp.int8), scores, k, jnp.int32(64)))
    np.testing.assert_array_equal(got.astype(bool), want)
    np.testing.assert_array_equal(into[:, 64:128], got)
    assert (into[:, :64] == 7).all() and (into[:, 128:] == 7).all()


# ------------------------------------------------------------ what is refused, what is named


@pytest.mark.parametrize("kw", [
    dict(swa_kv_lora_rank=None), dict(sliding_window_size=None), dict(layer_types=None), dict(num_nextn_predict_layers=1),
    dict(differential_attention=True), dict(layer_types=("full_attention", "mamba", "sliding_attention", "sliding_attention", "full_attention")),
    dict(index_topk=None), dict(q_lora_rank=None), dict(index_head_dim=4), dict(sliding_window_size=0),
    dict(swa_kv_lora_rank=None, swa_q_lora_rank=None, swa_num_attention_heads=None, swa_qk_nope_head_dim=None, swa_qk_rope_head_dim=None,
         swa_v_head_dim=None, swa_rope_theta=None, sliding_window_size=None, num_key_value_heads=4, head_dim=16, sliding_window=5),
], ids=["a_swa_size_missing", "no_window", "no_layer_types", "a_drafting_module", "differential", "a_mamba_layer", "half_an_indexer",
        "no_query_latent", "a_rotary_wider_than_the_index_head", "a_window_of_nothing", "an_indexer_over_grouped_query_layers"])
def test_post_init_refuses_what_is_not_built(kw):
    with pytest.raises((ValueError, TypeError)):  # a grouped-query check may meet a size that is None first
        tiny_config(**kw)


def test_full_layers_without_an_indexer_are_plain_latent_attention_behind_growing_caches():
    config = tiny_config(index_topk=None, index_n_heads=None, index_head_dim=None)
    model, params, ids = seeded(config, 12, n=21)
    got, _, caches = served_logits(model, params, ids, 12)
    assert [type(c) for c in caches] == [LatentCache, LatentRingCache, LatentRingCache, LatentRingCache, LatentCache]
    full = np.asarray(model.apply(params, jnp.concatenate([ids, jnp.asarray(np.argmax(got, -1))[:, :-1]], axis=1)))[:, -12:]
    np.testing.assert_allclose(got, full, atol=TOL, rtol=0)


def test_scopes_are_layers_and_the_compile_row_counts_three_cache_kinds():
    assert set(SCOPES) <= xplane.LAYER_SCOPES and set(SCOPES) <= xplane.CLOSED_LAYERS
    config = tiny_config()
    model, params, ids = seeded(config, 13, n=21)
    decoder = generation._decoder_of(model)
    text = jax.jit(lambda p, i: decoder.prefill(p, i, None, 1, 4, jnp.float32)).lower(params, ids).as_text(debug_info=True)
    for scope in ("dsa/index", "dsa/score", "dsa/select", "dsa/attend", "mla/window", "mla/expand"):
        assert scope in text, scope
    _, window, _ = decoder.prefill(params, ids, None, 1, 4, jnp.float32)
    text = jax.jit(lambda p, w, t: decoder.step(p, w, (), t)).lower(params, window, ids[:, 0]).as_text(debug_info=True)
    for scope in ("dsa/index", "dsa/step_score", "dsa/step_select", "dsa/step_gather", "dsa/step_attend", "mla/window_step", "mla/absorb"):
        assert scope in text, scope
    assert "dsa.*" in decoder.tap_scopes
    row = decoder.compile_row(2, 21, 4, jnp.bfloat16)
    assert row["latent_cache_layers"] == 2 and row["latent_ring_layers"] == 3 and row["latent_ring_slots"] == 32
    assert row["latent_cache_bytes"] == 2 * 25 * 24 * 2 * 2 and row["index_cache_bytes"] == 2 * 25 * 16 * 2 * 2
    assert row["latent_ring_bytes"] == 2 * 32 * 40 * 2 * 3 and row["index_topk"] == 8


def test_the_instrumented_generator_reports_the_selection():
    from perceiver_io_tpu.generation import make_instrumented_generate_fn
    from perceiver_io_tpu.obs.metrics import MetricsRegistry

    config = tiny_config()
    model, params, ids = seeded(config, 14, n=21)
    registry = MetricsRegistry()
    fn = make_instrumented_generate_fn(model, num_latents=1, config=GenerationConfig(max_new_tokens=6), registry=registry, probes=True)
    out, _ = fn(params, ids)
    plain = make_generate_fn(model, num_latents=1, config=GenerationConfig(max_new_tokens=6))(params, ids)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))
    snapshot = registry.snapshot()
    flat = str(snapshot)
    assert "dsa_selected_keys_mean" in flat and "dsa_recent_share" in flat


# ------------------------------------------------------------ the share and the whole


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Each chip's held experts' part of the result, the shared expert counted once, against the uncut reference."""
    config = tiny_config(n_held_experts=2)
    model, params, _ = seeded(config, 15)
    w = flat_dict(params)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, config.hidden_size))
    whole_cfg = dict(reference_cfg(config), n_held_experts=16, held_experts_start=0)
    draw = jax.random.normal(jax.random.PRNGKey(2), (3, 16, config.hidden_size, config.moe_intermediate_size)) * 0.3
    whole_w = dict(w, **{"params/layer_1/ffn/experts_w1": draw[0], "params/layer_1/ffn/experts_w3": draw[1],
                         "params/layer_1/ffn/experts_w2": jnp.swapaxes(draw[2], 1, 2)})
    want = dsv3_reference.experts(x, whole_w, "params/layer_1/ffn", whole_cfg, "float32")
    shared = dsv3_reference.swiglu(x, w["params/layer_1/ffn/shared/w1"], w["params/layer_1/ffn/shared/w3"], w["params/layer_1/ffn/shared/w2"], "float32")
    total = shared
    for chip in range(8):
        share = dataclasses.replace(config, n_held_experts=2, held_experts_start=2 * chip)
        layer_params = jax.tree.map(lambda v: v, params["params"]["layer_1"]["ffn"])
        for name in ("experts_w1", "experts_w3", "experts_w2"):
            layer_params[name] = whole_w[f"params/layer_1/ffn/{name}"][2 * chip:2 * chip + 2]
        from perceiver_io_tpu.core.moe import MoELayer
        got = MoELayer(share).apply({"params": layer_params}, x)
        total = total + (got - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=TOL, rtol=0)
