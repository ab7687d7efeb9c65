"""``lib/dsv3_cost.py`` against hand counts at DeepSeek-V3's published widths
(the figures of ISSUE 28: 187.1M a layer of MLA, 44.04M an expert, 937.6M an
expert layer with 16 experts held, 1 152 bytes of cache a token a layer)."""

import pytest

from benchmarks import run
from benchmarks.lib import dsv3_cost as cost


@pytest.fixture(scope="module")
def cfg():
    config = run.load_json("configs", "deepseek-v3-ep16")
    return run.importlib.import_module("benchmarks.families.deepseek_v3").Family(config).cfg


def test_parameter_counts(cfg):
    assert cost.mla_params(cfg) == 7168 * 1536 + 1536 * 24576 + 7168 * 576 + 512 * 32768 + 16384 * 7168 == 187_105_280
    assert cost.expert_params(cfg) == 3 * 7168 * 2048 == 44_040_192
    assert cost.dense_mlp_params(cfg) == 3 * 7168 * 18432 == 396_361_728
    assert cost.router_params(cfg) == 7168 * 256 == 1_835_008
    assert cost.expert_layer_params(cfg) == 187_105_280 + 44_040_192 + 1_835_008 + 16 * 44_040_192 == 937_623_552
    assert cost.dense_layer_params(cfg) == 583_467_008
    assert cost.vocab_params(cfg) == 2 * 16160 * 7168 == 231_669_760
    assert cost.held_params(cfg) == 583_467_008 + 4 * 937_623_552 + 231_669_760 == 4_565_630_976  # 9.13 GB in bfloat16
    # the whole layer, as published: 256 experts
    assert cost.expert_layer_params(cfg, 256) == 187_105_280 + 44_040_192 + 1_835_008 + 256 * 44_040_192


def test_cache_and_routing(cfg):
    assert cost.latent_row_bytes(cfg) == (512 + 64) * 2 == 1152
    assert 5 * cost.latent_row_bytes(cfg) == 5760  # a token over the five layers
    assert cost.local_pairs_per_token(cfg) == 0.5  # 8 of 256, 16 held
    assert cost.experts_hit(cfg, 64) == pytest.approx(16 * (1 - (31 / 32) ** 64))  # 87% at batch 64
    assert cost.experts_hit(cfg, 64) / 16 == pytest.approx(0.869, abs=1e-3)


def test_decode_step_bytes(cfg):
    """Attention and dense weights 2.66 GB, shared experts and routers 0.37
    GB, of the 64 held experts (5.64 GB) the 86.9% a batch of 64 hits (4.90
    GB: what the arithmetic needs, whichever path the program takes), the
    head 0.23 GB, the cache 0.38 GB at 1025 tokens: 8.54 GB a step."""
    attn_dense = 2 * (5 * 187_105_280 + 396_361_728)
    shared_routers = 2 * 4 * (44_040_192 + 1_835_008)
    experts = 2 * 4 * 16 * (1 - (31 / 32) ** 64) * 44_040_192
    head = 2 * 16160 * 7168
    cache = 64 * 1025 * 1152 * 5
    assert attn_dense == pytest.approx(2.66e9, rel=5e-3) and shared_routers == pytest.approx(0.37e9, rel=1e-2)
    assert experts == pytest.approx(0.869 * 5.64e9, rel=1e-3) and cache == pytest.approx(0.378e9, rel=1e-2)
    want = attn_dense + shared_routers + experts + head + 2 * 64 * 7168 + cache
    assert cost.decode_step_bytes(cfg, 64, 1025) == pytest.approx(want, rel=1e-12) and want == pytest.approx(8.54e9, rel=1e-3)
    # a batch that hits every held expert reads them all: the deployment's 16 x 64 tokens a step
    assert cost.decode_step_bytes(cfg, 1024, 1025) - 1024 * (1025 * 1152 * 5 + 2 * 7168) == pytest.approx(
        attn_dense + shared_routers + 2 * 64 * 44_040_192 + head, rel=1e-6)
    scan = cost.decode_scan_bytes(cfg, 64, 1024, 256)
    assert scan == pytest.approx(255 * (want - cache) + 64 * 1152 * 5 * sum(range(1025, 1280)))
    assert scan / 819e9 == pytest.approx(2.67, abs=0.02)  # seconds at the HBM peak


def test_prompt_pass_operations(cfg):
    per_token = 2 * (583_467_008 + 4 * (187_105_280 + 44_040_192 + 1_835_008 + 0.5 * 44_040_192))
    assert cost.token_product_flops(cfg) == per_token == pytest.approx(3.21e9, rel=5e-3)
    attn_row = 2 * 128 * (1024 * 1025 / 2) * (192 + 128)
    assert cost.attention_flops(cfg, 1024) == attn_row
    want = 65536 * per_token + 64 * 5 * attn_row + 2 * 64 * 7168 * 16160
    assert cost.prefill_flops(cfg, 64, 1024) == want == pytest.approx(0.224e15, rel=1e-2)
    assert cost.train_flops(cfg, 1, 1280) > 3 * 1280 * per_token


def test_absorbed_attention_sits_on_the_ridge(cfg):
    a = cost.absorbed_attention_cost(cfg, 1, 1)
    assert a["flops"] == 2 * 128 * (576 + 512) and a["bytes"] == 1152
    assert a["flops"] / a["bytes"] == pytest.approx(241.8, abs=0.1)  # the v5e's ridge is 197e12 / 819e9 = 240.5
