"""The decoder-only model under its second configuration (the Mellum 2
family: grouped-query attention, window and full layers side by side, softmax
routing over experts that are all held) against its plain reference, at tiny
widths that keep the published ratios: hidden 64, 8 query heads on 2 key-value
heads of 16, a window of 8, three window layers then a full one, 8 experts of
width 32 of which 2 a token, YaRN by 4 over 8 original positions.

Tolerances as in ``tests/test_decoder_lm.py``: float32 products at "highest"
precision on both sides, so the program and ``benchmarks/reference/mellum.py``
differ in the order of float32 sums alone, 2e-4 absolute on logits of
magnitude up to about 10. A window layer run as a full one moves the same
logits by hundreds of times that, and a test says so. The sequences are longer
than the window, so the mask bites, the prompt pass turns its last rows into
the ring's slots, and the decode steps wrap the ring."""

import dataclasses
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib.weights import flat_dict
from benchmarks.reference import mellum as reference
from perceiver_io_tpu import generation
from perceiver_io_tpu.core import moe
from perceiver_io_tpu.core.cache import KVCache, WindowKVCache, init_window_kv_cache
from perceiver_io_tpu.core.position import apply_rotary_half, yarn_inv_freq
from perceiver_io_tpu.generation import GenerationConfig, make_generate_fn
from perceiver_io_tpu.models.text.decoder_lm import DecoderLanguageModel, DecoderLanguageModelConfig, YarnConfig
from perceiver_io_tpu.obs import probes

fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")  # the package exports a function of that name

TOL = 2e-4
VOCAB = 96
WINDOW = 8
LAYER_TYPES = ("sliding_attention", "sliding_attention", "sliding_attention", "full_attention")
YARN = YarnConfig(factor=4.0, beta_fast=32.0, beta_slow=1.0, original_max_position_embeddings=8, attention_factor=1.1386)


def tiny_config(**kw) -> DecoderLanguageModelConfig:
    base = dict(
        vocab_size=VOCAB, hidden_size=64, num_hidden_layers=4, first_k_dense_replace=0, moe_intermediate_size=32,
        num_attention_heads=8, num_key_value_heads=2, head_dim=16, layer_types=LAYER_TYPES, sliding_window=WINDOW,
        n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=0, n_group=1, topk_group=1, scoring_func="softmax",
        rope_theta=500000.0, rope_scaling=YARN, init_scale=0.3, max_position_embeddings=512,
    )
    base.update(kw)
    return DecoderLanguageModelConfig(**base)


def reference_cfg(config: DecoderLanguageModelConfig) -> dict:
    return dataclasses.asdict(config)


def seeded(config, seed: int, batch: int = 2, n: int = 13):
    model = DecoderLanguageModel(config)
    k_ids, k_init, k_noise = jax.random.split(jax.random.PRNGKey(seed), 3)
    ids = jax.random.randint(k_ids, (batch, n), 0, config.vocab_size)
    params = model.init(k_init, ids)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(k_noise, len(leaves))
    params = jax.tree.unflatten(tree, [p + 0.1 * jax.random.normal(k, p.shape) for p, k in zip(leaves, keys)])
    return model, params, ids


def served_logits(model, params, ids, new_tokens: int, cache_dtype=jnp.float32):
    """Greedy decoding through the generator's own decoder (prompt pass, then
    one-token steps over both kinds of cache): the logits the tokens were read
    from, (B, new_tokens, V), the tokens, and the caches at the end."""
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


# ------------------------------------------------------------ the whole model


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("n", [5, WINDOW, 21], ids=["shorter_than_the_window", "the_window", "longer"])
def test_full_forward_matches_the_reference(seed, n):
    config = tiny_config()
    model, params, ids = seeded(config, seed, n=n)
    got = np.asarray(model.apply(params, ids))
    want = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config)))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("n", [5, WINDOW, 13, 16], ids=lambda n: f"prompt{n}")
def test_prompt_pass_then_decode_through_both_caches_matches_the_references_full_forward(seed, n):
    """Every served position, for prompts shorter than the window, of its
    length, longer by a part of it (the prompt pass turns its last rows by 5
    slots) and by a whole one; 12 steps wrap the ring at least once."""
    new = 12
    config = tiny_config()
    model, params, ids = seeded(config, seed, n=n)
    got, tokens, caches = served_logits(model, params, ids, new)
    full = np.concatenate([np.asarray(ids), tokens[:, :-1]], axis=1)
    want = np.asarray(reference.logits(flat_dict(params), jnp.asarray(full), reference_cfg(config), last=new))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    # two kinds of cache in one state: rings of the window's size, and a cache that grew
    kinds = [type(c) for c in caches]
    assert kinds == [WindowKVCache] * 3 + [KVCache]
    assert all(c.k.shape == (2 * 2, WINDOW, 16) for c in caches[:3]) and caches[3].k.shape == (2 * 2, n + new, 16)
    assert all(int(c.length) == n + new - 1 for c in caches)
    assert n + new - 1 > 2 * WINDOW or n < WINDOW  # the longer prompts wrapped the ring


@pytest.mark.parametrize("what", ["full_forward", "served"])
def test_a_window_layer_run_as_a_full_one_fails(what):
    """The program with its window layers seeing everything before them (a
    window no sequence here reaches: no mask bites, no ring wraps), against
    the reference of the configuration as it is."""
    config = tiny_config()
    wrong = tiny_config(sliding_window=4096)
    _, params, ids = seeded(config, 0, n=21)
    if what == "full_forward":
        got = np.asarray(DecoderLanguageModel(wrong).apply(params, ids))
        want = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config)))
        assert np.abs(got[:, :WINDOW] - want[:, :WINDOW]).max() < TOL  # nothing has left a window yet
    else:
        got, tokens, _ = served_logits(DecoderLanguageModel(wrong), params, ids, 4)
        full = np.concatenate([np.asarray(ids), tokens[:, :-1]], axis=1)
        want = np.asarray(reference.logits(flat_dict(params), jnp.asarray(full), reference_cfg(config), last=4))
    assert np.abs(got - want).max() > 100 * TOL


def test_the_generator_runs_it_through_the_same_interface_as_the_other_decoder(tmp_path):
    """``make_generate_fn`` end to end: greedy tokens equal the host-driven
    pair's, the scopes of the issue sit inside ``prefill`` / ``decode``, the
    ``compile`` row names both kinds of cache, and the expert taps count every
    routed pair as local (all experts are held)."""
    import json
    import re

    from perceiver_io_tpu.generation import make_instrumented_generate_fn
    from perceiver_io_tpu.obs.events import EventLog

    config = tiny_config()
    model, params, ids = seeded(config, 2, batch=4, n=13)
    gen_cfg = GenerationConfig(max_new_tokens=6)
    fn = make_generate_fn(model, config=gen_cfg)
    out = np.asarray(fn(params, ids))
    _, tokens, _ = served_logits(model, params, ids, 6)
    np.testing.assert_array_equal(out[:, 13:], tokens)
    text = fn.lower(params, ids).as_text(debug_info=True)
    for outer, inner in (("prefill", "attn/window"), ("prefill", "attn/full"), ("prefill", "moe/route"),
                         ("prefill", "moe/experts"), ("decode", "attn/window"), ("decode", "attn/full"),
                         ("decode", "moe/route"), ("decode", "moe/experts")):
        assert re.search(rf'"{outer}/[^"]*{inner}', text), (outer, inner)
    assert "moe/shared" not in text and "mla/" not in text and "kv_cache_append" in text

    events = EventLog(str(tmp_path))
    probed = make_instrumented_generate_fn(model, config=gen_cfg, events=events, probes=True)
    _, stats = probed(params, ids)
    assert stats.outcome == "ok"
    snapshot = probed.registry.snapshot()
    snap = {**snapshot["counters"], **snapshot["gauges"]}
    # 4 expert layers, 4 rows: 52 prompt tokens then 5 steps of 4 tokens, 2 experts a token
    assert snap["moe_pairs_routed_total"] == 4 * 2 * (52 + 5 * 4)
    assert snap["moe_pairs_local_total"] == snap["moe_pairs_routed_total"] and snap["moe_pairs_dropped_total"] == 0
    rows = [json.loads(line) for line in open(tmp_path / "events.jsonl")]
    (compiled,) = [r for r in rows if r.get("event") == "compile" and "kv_cache_window_rows" in r][:1]
    row_bytes = 2 * 2 * 16 * 4  # keys and values of 2 heads of 16, float32
    assert compiled["kv_cache_full_layers"] == 1 and compiled["kv_cache_window_layers"] == 3
    assert compiled["kv_cache_window_rows"] == WINDOW
    assert compiled["kv_cache_full_bytes"] == 4 * (13 + 6) * row_bytes
    assert compiled["kv_cache_window_bytes"] == 4 * WINDOW * row_bytes * 3
    # 52 prompt tokens are under the 384 of the grouped path: nothing was gathered, whatever a longer prompt would take
    assert compiled["moe_combine"] == "gather" and snap["moe_pairs_gathered_total"] == 0


def test_the_gather_combine_is_counted_where_it_ran(tmp_path):
    """32 rows of 13 tokens are 416 prompt tokens, over the 384 from which the
    experts take the grouped path, and a step's 32 are under it: every pair of
    the prompt pass comes back through the gather (``moe_pairs_gathered_total``
    over the prompt's share of ``moe_pairs_local_total`` is 1.0), no pair of a
    step does, and the ``compile`` row says which combine the configuration takes."""
    import json

    from perceiver_io_tpu.generation import make_instrumented_generate_fn
    from perceiver_io_tpu.obs.events import EventLog

    model, params, ids = seeded(tiny_config(), 4, batch=32, n=13)
    probed = make_instrumented_generate_fn(model, config=GenerationConfig(max_new_tokens=3), events=EventLog(str(tmp_path)), probes=True)
    _, stats = probed(params, ids)
    assert stats.outcome == "ok"
    counters = probed.registry.snapshot()["counters"]
    prompt_pairs, step_pairs = 4 * 2 * 32 * 13, 4 * 2 * 32 * 2  # 4 expert layers, 2 experts a token, 2 steps
    assert counters["moe_pairs_gathered_total"] == prompt_pairs
    assert counters["moe_pairs_local_total"] == counters["moe_pairs_routed_total"] == prompt_pairs + step_pairs
    rows = [json.loads(line) for line in open(tmp_path / "events.jsonl")]
    assert [r["moe_combine"] for r in rows if r.get("event") == "compile" and "moe_combine" in r][:1] == ["gather"]


def test_the_grouped_kernels_fetch_an_experts_weights_once_and_the_compile_row_says_so(tmp_path):
    """The same 416 prompt tokens through the instrumented generator: every
    grouped product of its ``compile`` rows (``moe_tiles``) holds the
    contraction whole, and the ``moe.load`` tap counts one fetch of a weight
    block for each expert a pass hit and each column tile of the three
    products (one each at these widths), however many row tiles the expert's
    rows straddle; the steps' dense path visits and fetches nothing."""
    import json

    from perceiver_io_tpu.generation import make_instrumented_generate_fn
    from perceiver_io_tpu.obs.events import EventLog
    from perceiver_io_tpu.ops.grouped_matmul import block_plan

    model, params, ids = seeded(tiny_config(), 4, batch=32, n=13)
    probed = make_instrumented_generate_fn(model, config=GenerationConfig(max_new_tokens=3), events=EventLog(str(tmp_path)), probes=True)
    _, stats = probed(params, ids)
    assert stats.outcome == "ok"
    rows = [json.loads(line) for line in open(tmp_path / "events.jsonl")]
    tiles = [r["moe_tiles"] for r in rows if r.get("event") == "compile" and "moe_tiles" in r][0]
    pass_rows = moe._pass_rows(2 * 416, 1.0, moe._cuts(64, 32, 8))
    ours = [t for t in tiles if t["m"] == pass_rows and {t["k"], t["n"]} == {64, 32}]
    assert sorted((t["k"], t["n"]) for t in ours) == [(32, 64), (64, 32)] and all(t["weights_resident"] for t in tiles)
    assert ours[0] == block_plan(pass_rows, ours[0]["k"], ours[0]["n"], ours[0]["tm"], 4) and ours[0]["tiles_k"] == ours[0]["tiles_n"] == 1
    counters = probed.registry.snapshot()["counters"]
    # 4 expert layers of 8 experts, 104 pairs an expert in the mean: every expert is hit, in the one pass a layer takes
    hit, column_tiles = 4 * 8, 3
    assert counters["moe_expert_weight_fetches_total"] == counters["moe_expert_weight_blocks_total"] == hit * column_tiles
    assert counters["moe_expert_visits_total"] >= hit


def test_the_benchmarks_two_configurations_sit_on_either_side_of_the_rule():
    """``mellum2-12b-pp4`` holds its 64 experts and gathers; ``deepseek-v3-ep16`` holds 16 of 256 and sums its rows by token (``ops/moe_combine.py``)."""
    from benchmarks import run

    def combine(name):
        config = run.load_json("configs", name)
        model = importlib.import_module(f"benchmarks.families.{config['family']}").Family(config).model()
        return generation._decoder_of(model).compile_row(32, 8192, 256, jnp.bfloat16)["moe_combine"]

    assert combine("mellum2-12b-pp4") == "gather" and combine("deepseek-v3-ep16") == "segment_sum"


def test_the_prompt_pass_through_the_flash_kernels_matches_the_reference():
    """Flash on (interpret mode here): 160 positions in blocks of 128, a
    window of 48, four query heads a key-value head; also the plan rows."""
    config = tiny_config(sliding_window=48, max_position_embeddings=256)
    model, params, ids = seeded(config, 3, batch=1, n=160)
    fa._TILE_PLANS.clear()
    with fa.default_flash(True):
        got = np.asarray(jax.jit(model.apply)(params, ids))
    want = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config)))
    np.testing.assert_allclose(got, want, atol=2 * TOL, rtol=0)
    plans = {row["geometry"]: row for row in fa.tile_plans()}
    assert set(plans) == {"q160_kv160", "q160_kv160_w48"}
    assert plans["q160_kv160_w48"]["window"] == 48 and plans["q160_kv160"]["window"] is None
    assert plans["q160_kv160_w48"]["tiles_run"] + plans["q160_kv160_w48"]["tiles_skipped"] == 4


# -------------------------------------------------------------- the pieces


def test_softmax_routing_by_hand():
    """Four experts, two a token: softmax of the logits, the two largest, renormalised over the two."""
    logits = jnp.log(jnp.asarray([[0.1, 0.2, 0.3, 0.4], [0.7, 0.1, 0.1, 0.1], [0.25, 0.35, 0.05, 0.35]]))
    chosen, weights = moe.choose_experts_softmax(logits, 2)
    np.testing.assert_array_equal(np.asarray(chosen)[0], [3, 2])
    np.testing.assert_allclose(np.asarray(weights)[0], [0.4 / 0.7, 0.3 / 0.7], rtol=1e-6)
    assert int(chosen[1, 0]) == 0 and float(weights[1, 0]) == pytest.approx(0.7 / 0.8, rel=1e-6)
    assert sorted(np.asarray(chosen)[2].tolist()) == [1, 3]
    np.testing.assert_allclose(np.asarray(weights)[2], [0.5, 0.5], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)


def test_a_softmax_routed_layer_has_no_bias_and_no_shared_expert():
    config = tiny_config()
    x = jax.random.normal(jax.random.PRNGKey(0), (24, 64))
    params = moe.MoELayer(config).init(jax.random.PRNGKey(1), x)
    assert sorted(params["params"]) == ["experts_w1", "experts_w2", "experts_w3", "gate"]
    w = {"l/" + k: v for k, v in flat_dict(params["params"]).items()}
    want = np.asarray(reference.experts(x, w, "l", reference_cfg(config), "float32"))
    np.testing.assert_allclose(np.asarray(moe.MoELayer(config).apply(params, x)), want, atol=TOL, rtol=0)
    with pytest.raises(ValueError, match="scoring_func"):
        moe.MoELayer(tiny_config(scoring_func="tanh")).init(jax.random.PRNGKey(1), x)


# (tokens, rows a pass or None for the shipped cut, every token to experts 0 and 1): 768 tokens send 1536 pairs, six row
# tiles of 256 in one pass of 2048 rows; 777 send 1554, so the seventh tile holds 18 pairs and 238 dead rows; passes of
# 512 rows cut them into three (768 tokens) or four, the last one nearly empty (777); under the skew two experts get 768
# rows each, across the passes' edges
EXPERT_PATH_CASES = {
    "dense_path": (48, None, False),
    "grouped_path": (768, None, False),
    "dead_rows_in_the_last_tile": (777, None, False),
    "three_passes": (768, 512, False),
    "four_passes_the_last_nearly_empty": (777, 512, False),
    "skewed_routing": (768, None, True),
    "skewed_routing_in_three_passes": (768, 512, True),
}


@pytest.mark.parametrize("case", sorted(EXPERT_PATH_CASES))
def test_both_expert_paths_serve_every_pair_when_all_experts_are_held(case, monkeypatch):
    """Every expert held: the grouped path brings its rows back by the inverse
    of the sort and a sum over a token's pairs (``pairs_gathered`` counts them
    all), the dense path has no such step (0)."""
    tokens, pass_rows, skewed = EXPERT_PATH_CASES[case]
    config = tiny_config()
    cuts = moe._cuts(64, 32, config.n_held_experts)
    grouped = tokens >= cuts.grouped_min_tokens
    assert grouped == (case != "dense_path")
    if pass_rows:
        monkeypatch.setattr(moe, "_cuts", lambda hidden, width, held: cuts._replace(pass_rows=pass_rows))
    x = jax.random.normal(jax.random.PRNGKey(5), (tokens, 64))
    params = moe.MoELayer(config).init(jax.random.PRNGKey(6), x)
    if skewed:  # one channel the same in every token, and a router that reads it for experts 0 and 1
        x = x.at[:, 0].set(5.0)
        params = {"params": {**params["params"], "gate": params["params"]["gate"].at[0, :2].set(10.0)}}

    def tapped(p, x):
        with probes.collecting(probes.ProbeConfig(scopes=("moe.*",))) as col:
            return moe.MoELayer(config).apply(p, x), col.stats

    y, stats = jax.jit(tapped)(params, x)
    (load,) = stats.values()
    assert int(load["pairs_local"]) == int(load["pairs_routed"]) == 2 * tokens and int(load["pairs_dropped"]) == 0
    assert int(load["pairs_gathered"]) == (2 * tokens if grouped else 0)
    assert int(load["expert_load_max"]) == tokens or not skewed
    # a weight block a (pass, expert hit) and product, whatever the visits (the contraction is whole); none on the dense path
    assert int(load["expert_weight_fetches"]) == int(load["expert_weight_blocks"]) <= 3 * int(load["expert_visits"])
    assert (int(load["expert_visits"]) > 0) == grouped
    if skewed:  # experts 0 and 1 take 768 rows each: three row tiles of 256 each in one pass, four (pass, expert) pairs in passes of 512
        assert (int(load["expert_visits"]), int(load["expert_weight_blocks"])) == ((6, 3 * 4) if pass_rows else (6, 3 * 2))
    if grouped:
        assert int(load["passes"]) == -(-2 * tokens // (pass_rows or 2048))
        # no row is added into the tokens' buffer (the integer scatter-adds left count group sizes)
        assert not re.search(r"f32\[\d+,64\] = scatter-add", str(jax.make_jaxpr(moe.MoELayer(config).apply)(params, x)))
    w = {"l/" + k: v for k, v in flat_dict(params["params"]).items()}
    np.testing.assert_allclose(np.asarray(y), np.asarray(reference.experts(x, w, "l", reference_cfg(config), "float32")),
                               atol=TOL, rtol=0)


def test_the_cuts_follow_the_geometry_they_were_measured_at():
    wide = moe._cuts(7168, 2048, 16)
    assert wide == (384, 256, 65536)  # DeepSeek-V3's share keeps PR 28's crossing and tile; no cap on a pass since PR 50
    assert moe._cuts(6144, 2048, 16) == wide  # K-EXAONE's share: its own readings came out the same (PR 34)
    # a share-held pass is what an even routing sends here and a quarter more, in whole row tiles: a prompt chunk's 8192 tokens
    assert moe._pass_rows(8192 * 8, 16 / 256, wide) == 5120 and moe._pass_rows(8192 * 8, 16 / 128, wide) == 10240
    small = moe._cuts(2304, 896, 64)
    assert small == moe._cuts(64, 32, 8) and small.pass_rows % small.row_tile == 0 and small.row_tile % 128 == 0


def test_half_split_rotary_with_yarn_against_complex_numbers():
    """Channel i and channel i + D/2 are one complex number turned by
    pos * f_i, the whole times the attention factor; YaRN keeps the fast
    frequencies, divides the slow ones by the factor and blends between."""
    d = 16
    inv_freq = yarn_inv_freq(d, 500000.0, 4.0, 32.0, 1.0, 8)
    plain = 1.0 / (500000.0 ** (np.arange(0, d, 2) / d))
    np.testing.assert_allclose(inv_freq, np.asarray(reference.rotary_tables(
        {"head_dim": d, "rope_theta": 500000.0, "rope_scaling": dataclasses.asdict(YARN)}, "full_attention")[0]), rtol=1e-6)
    assert inv_freq[0] == pytest.approx(plain[0]) and inv_freq[-1] == pytest.approx(plain[-1] / 4.0, rel=1e-5)
    assert np.all(inv_freq <= plain * (1 + 1e-6)) and np.all(inv_freq >= plain / 4.0 * (1 - 1e-6))
    t = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, d)))
    pos = np.asarray([[0, 1, 2, 30, 700], [3, 4, 5, 6, 7]])
    got = np.asarray(apply_rotary_half(jnp.asarray(t), jnp.asarray(pos)[:, :, None], inv_freq, 1.25))
    z = (t[..., : d // 2] + 1j * t[..., d // 2:]) * np.exp(1j * pos[:, :, None, None] * inv_freq) * 1.25
    np.testing.assert_allclose(got, np.concatenate([z.real, z.imag], axis=-1), atol=1e-5)


@pytest.mark.parametrize("n", [3, 8, 13, 16, 21])
def test_the_ring_holds_the_last_window_each_position_in_its_slot(n):
    """After a prompt of n positions and then single appends, slot p % 8 holds position p for the last 8 positions."""
    w = 8
    rows = jnp.arange(1, 40, dtype=jnp.float32)[None, :, None] * jnp.ones((2, 1, 4))  # row p holds p + 1
    kept = rows[:, max(n - w, 0):n]
    cache = init_window_kv_cache(2, w, 4, 4).fill(kept, -kept, n)
    for length in range(n, n + 11):
        assert int(cache.length) == length
        k = np.asarray(cache.k)[0, :, 0]
        for p in range(max(length - w, 0), length):
            assert k[p % w] == p + 1, (length, p)
        assert np.all(k[min(length, w):] == 0)
        np.testing.assert_array_equal(np.asarray(cache.v), -np.asarray(cache.k))
        cache = cache.append(rows[:, length:length + 1], -rows[:, length:length + 1])
    with pytest.raises(ValueError, match="one token"):
        cache.append(rows[:, :2], rows[:, :2])
    with pytest.raises(ValueError, match="fills a ring"):
        init_window_kv_cache(2, w, 4, 4).fill(rows[:, :n + 1], rows[:, :n + 1], n)


# ------------------------------------------------ the windowed flash forward


def dense_attention(q, k, v, heads, window, scale):
    b, n, _ = q.shape
    kv_heads, d = k.shape[1], k.shape[3]
    qh = q.reshape(b, n, heads, d).transpose(0, 2, 1, 3)
    kh, vh = jnp.repeat(k, heads // kv_heads, axis=1), jnp.repeat(v, heads // kv_heads, axis=1)
    s = jnp.einsum("bhid,bhjd->bhij", qh, kh) * scale
    i, j = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    visible = (j <= i) if window is None else (j <= i) & (j > i - window)
    p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhij,bhjd->bihd", p, vh).reshape(b, n, heads * d)


@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize("window", [48, 128, 300, 1, None], ids=lambda w: f"w{w}")
def test_windowed_flash_forward_against_a_dense_masked_softmax(window, group):
    """Blocks of 128: windows smaller than a block, equal to it, larger
    (three kv blocks a q block), of one position, and none; 500 positions
    (padded to 512); every query head its own key-value head, or 8 sharing one."""
    n, heads, d = 500, 8, 16
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(11 + group), 3)
    q = jax.random.normal(kq, (2, n, heads * d))
    k = jax.random.normal(kk, (2, heads // group, n, d))
    v = jax.random.normal(kv, (2, heads // group, n, d))
    got = fa.flash_attention_gqa(q, k, v, heads, window=window, sm_scale=0.25, block=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense_attention(q, k, v, heads, window, 0.25)), atol=2e-6, rtol=0)


def test_windowed_flash_forward_cut_into_bands():
    """Blocks of 512 against a window of 512: both tiles a q block sees are triangles and run as bands of 256 rows."""
    n, heads, d = 1024, 2, 16
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kq, (1, n, heads * d)), jax.random.normal(kk, (1, 1, n, d)),
               jax.random.normal(kv, (1, 1, n, d)))
    assert fa.tile_plan(n, n, True, 512, 512, window=512).band_rows == 256
    for window in (512, 700):
        got = fa.flash_attention_gqa(q, k, v, heads, window=window, sm_scale=0.25, block=512)
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense_attention(q, k, v, heads, window, 0.25)), atol=2e-6)


def test_the_windowed_flash_forward_has_no_backward():
    q = jnp.ones((1, 128, 32))
    k = v = jnp.ones((1, 1, 128, 16))
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda q: fa.flash_attention_gqa(q, k, v, 2, window=32).sum())(q)
    with pytest.raises(ValueError, match="do not fit"):
        fa.flash_attention_gqa(q, k, v, 3)
    with pytest.raises(ValueError, match="causal self-attention"):
        fa.tile_plan(128, 256, True, window=32)


@pytest.mark.parametrize("n,block,window,run,masked,bands", [
    # the cell's window layers: 8 diagonal tiles and 7 before them, each a triangle in four bands of
    # 2 x (2 + 4 + 6 + 8) score tiles; all of them masked somewhere
    (8192, None, 1024, 15 * 40, 15 * 40, 256),
    # the cell's full layers: 8 diagonal tiles in bands (40), 28 whole tiles of 64 before them, unmasked
    (8192, None, 8192, 8 * 40 + 28 * 64, 8 * 40, 256),
    # blocks of 128 (one score tile), window 128: the diagonal tile and the one before it, both masked
    (512, 128, 128, 4 + 3, 4 + 3, 0),
    # window 300 at blocks of 128: tiles 0 to 3 before the diagonal are seen (300 + 127 reach into the
    # fourth); the first and the last two are masked, the second is whole
    (512, 128, 300, 4 + 3 + 2 + 1, 4 + 2 + 1, 0),
    # one position: the diagonal tile alone
    (512, 128, 1, 4, 4, 0),
    # 500 positions are padded to 512
    (500, 128, 128, 7, 7, 0),
    # K-EXAONE's window layers (PR 34): a row of 1024 is one block of 1024, so one tile, the diagonal one, in four bands
    # of 256 rows; a band sees the 256 slots under it and the 128 before them (the first band none before): 2 x 2 score
    # tiles, then 3 times 2 x 3, 22 of the 64, every one of them masked; visible are 1024 x 128 scores less the corner, 7.5 tiles' worth
    (1024, None, 128, 4 + 3 * 6, 4 + 3 * 6, 256),
    # the same row in blocks of 128 (what a window as narrow as a score tile would like): 8 diagonal tiles and 7 before them
    (1024, 128, 128, 8 + 7, 8 + 7, 0),
])
def test_tile_plan_with_a_window_counted_by_hand(n, block, window, run, masked, bands):
    plan = fa.tile_plan(n, n, True, block, block, window=window)
    padded = -(-n // plan.block_q) * plan.block_q
    assert plan.block_q == plan.block_kv == (block or 1024) and plan.backward == "none"
    assert (plan.tiles_run, plan.tiles_masked, plan.band_rows) == (run, masked, bands)
    assert plan.tiles_run + plan.tiles_skipped == (padded // 128) ** 2
    # without a window the plan is the packed path's, as before
    assert fa.tile_plan(1024, 1024, True) == fa.tile_plan(1024, 1024, True, window=None)
    assert fa.tile_plan(1024, 1024, True).backward == "one"
