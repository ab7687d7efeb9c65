"""``lib/mellum_cost.py`` against hand counts at Mellum 2's published widths
(the figures of ISSUE 32: attention 21.23M a layer, router 0.15M, an expert
6.193M, 64 of them 396.4M, a layer 417.7M, 8 layers 3.342B, embedding and head
2 x 226.5M, 3.795B parameters; 2048 bytes of cache a token a layer)."""

import pytest

from benchmarks import run
from benchmarks.lib import mellum_cost as cost


@pytest.fixture(scope="module")
def cfg():
    config = run.load_json("configs", "mellum2-12b-pp4")
    return run.importlib.import_module("benchmarks.families.mellum").Family(config).cfg


def test_parameter_counts(cfg):
    assert cost.attention_params(cfg) == 2 * 2304 * 4096 + 2 * 2304 * 512 == 21_233_664
    assert cost.router_params(cfg) == 2304 * 64 == 147_456
    assert cost.expert_params(cfg) == 3 * 2304 * 896 == 6_193_152
    assert 64 * cost.expert_params(cfg) == 396_361_728
    assert cost.layer_params(cfg) == 21_233_664 + 147_456 + 396_361_728 == 417_742_848
    assert 8 * cost.layer_params(cfg) == 3_341_942_784
    assert cost.vocab_params(cfg) == 2 * 98304 * 2304 == 2 * 226_492_416
    assert cost.held_params(cfg) == 3_341_942_784 + 452_984_832 == 3_794_927_616  # 7.59 GB in bfloat16
    assert 12 * cost.layer_params(cfg) + cost.vocab_params(cfg) == 5_465_899_008  # 12 layers: 10.9 GB


def test_caches_and_routing(cfg):
    assert cost.kv_row_bytes(cfg) == 2 * 4 * 128 * 2 == 2048
    assert (cost.window_layers(cfg), cost.full_layers(cfg)) == (6, 2)
    assert 32 * 8448 * cost.kv_row_bytes(cfg) == 553_648_128  # a full layer's cache at the cell's sizes: 554 MB
    assert 32 * 1024 * cost.kv_row_bytes(cfg) == 67_108_864  # a window layer's ring: 67 MB
    assert cost.experts_hit(cfg, 32) == pytest.approx(64 * (1 - (7 / 8) ** 32))
    assert cost.experts_hit(cfg, 32) / 64 == pytest.approx(0.986, abs=1e-3)  # 32 tokens x 8 pairs hit 98.6%


def test_decode_step_bytes(cfg):
    """Weights: 8 x (attention + router + 63.1 experts hit) + the head + 32
    embedding rows, 7.05 GB (7.14 with every expert read, the issue's figure); caches: 2 full layers at the context and 6 rings
    of 1024, 1.5 GB at 8448 tokens (4.4 GB if every layer were full)."""
    weights = 8 * (21_233_664 + 147_456 + cost.experts_hit(cfg, 32) * 6_193_152) + 98304 * 2304 + 32 * 2304
    caches = 32 * (2 * 8448 + 6 * 1024) * 2048
    assert cost.decode_step_bytes(cfg, 32, 8448) == pytest.approx(2 * weights + caches)
    assert 2 * weights == pytest.approx(7.05e9, rel=5e-3) and caches == pytest.approx(1.51e9, rel=5e-3)
    assert 32 * 8 * 8448 * 2048 == pytest.approx(4.43e9, rel=5e-3)
    # a context shorter than the window: the rings are read as far as they are filled
    assert cost.decode_step_bytes(cfg, 32, 100) == pytest.approx(2 * weights + 32 * 8 * 100 * 2048)
    scan = cost.decode_scan_bytes(cfg, 32, 8192, 256)
    assert scan == pytest.approx(sum(cost.decode_step_bytes(cfg, 32, 8192 + j) for j in range(1, 256)))
    assert scan / 255 / 819e9 == pytest.approx(10.5e-3, rel=2e-2)  # 10.5 ms a step at the HBM peak


def test_prompt_pass_operations(cfg):
    assert cost.visible_pairs(8192) == 8192 * 8193 // 2
    # a window of 1024: the first 1024 positions see 1 to 1024, the other 7168 see 1024 each
    assert cost.visible_pairs(8192, 1024) == 1024 * 1025 // 2 + 7168 * 1024 == 7_864_832
    assert cost.visible_pairs(8192, 1024) / cost.visible_pairs(8192) == pytest.approx(0.234, abs=1e-3)  # a quarter
    assert cost.visible_pairs(5, 8) == 15 and cost.visible_pairs(8, 8) == 36 and cost.visible_pairs(9, 8) == 44
    assert cost.attention_flops(cfg, 8192, 1024) == 4 * 32 * 128 * 7_864_832
    # a token's products a layer: attention 21.23M + router 0.15M + 8 experts 49.55M = 70.9M parameters, 142 MFLOP
    per_layer = 21_233_664 + 147_456 + 8 * 6_193_152
    assert cost.token_product_flops(cfg) == 2.0 * 8 * per_layer
    assert 2 * per_layer == pytest.approx(141.9e6, rel=1e-3) and 2 * 8 * 6_193_152 == pytest.approx(99.1e6, rel=1e-3)
    total = cost.prefill_flops(cfg, 32, 8192)
    attention = 32 * (2 * cost.attention_flops(cfg, 8192) + 6 * cost.attention_flops(cfg, 8192, 1024))
    assert total == pytest.approx(262144 * 16 * per_layer + attention + 2.0 * 32 * 2304 * 98304)
    assert total == pytest.approx(0.36e15, rel=2e-2)  # 0.36 PFLOP
    assert attention / total == pytest.approx(0.17, abs=0.01)
    assert 262144 * 16 * 8 * 6_193_152 / (total - attention) == pytest.approx(0.70, abs=0.01)  # experts: 70% of the products


def test_kernel_costs(cfg):
    flash = cost.window_flash_cost(cfg, 32, 8192)
    assert flash["flops"] == 32 * 4 * 32 * 128 * 7_864_832 and flash["bytes"] == 32 * 8192 * 128 * (64 + 8) * 2
    assert flash["flops"] / 197e12 > flash["bytes"] / 819e9  # bound by its operations: 20.9 ms against 5.9
    experts = cost.expert_kernel_cost(cfg, 262144)
    assert experts["flops"] == 2.0 * 262144 * 8 * 6_193_152
    assert experts["bytes"] == 2 * (396_361_728 + 262144 * 8 * (2 * 2304 + 3 * 896))
    assert experts["flops"] / 197e12 == pytest.approx(0.1318, rel=1e-2)  # 132 ms a layer at the peak
