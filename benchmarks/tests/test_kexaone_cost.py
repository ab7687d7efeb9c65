"""``lib/kexaone_cost.py`` against hand counts at K-EXAONE's published widths
(the figures of ISSUE 34: attention 113.25M, an expert 37.75M, a sparse layer
with 16 held 755.8M, the dense layer 453.0M, embedding and head 2 x 117.96M,
the module 75.5M and a sparse block, 4.54B parameters, 9.09 GB; 4096 bytes of
cache a token a layer)."""

import pytest

from benchmarks import run
from benchmarks.lib import kexaone_cost as cost


@pytest.fixture(scope="module")
def cfg():
    config = run.load_json("configs", "k-exaone-236b-ep8")
    return run.importlib.import_module("benchmarks.families.exaone_moe").Family(config).cfg


def test_parameter_counts(cfg):
    assert cost.attention_params(cfg) == 2 * 6144 * 8192 + 2 * 6144 * 1024 == 113_246_208
    assert cost.expert_params(cfg) == 3 * 6144 * 2048 == 37_748_736
    assert cost.router_params(cfg) == 6144 * 128 == 786_432
    assert 16 * cost.expert_params(cfg) == 603_979_776
    assert cost.sparse_layer_params(cfg) == 113_246_208 + 786_432 + 37_748_736 + 603_979_776 == 755_761_152
    assert cost.dense_layer_params(cfg) == 113_246_208 + 3 * 6144 * 18432 == 452_984_832
    assert cost.vocab_params(cfg) == 2 * 19200 * 6144 == 2 * 117_964_800
    assert cost.module_params(cfg) == 2 * 6144 * 6144 + 755_761_152 == 831_258_624
    assert cost.stack_params(cfg) == 452_984_832 + 4 * 755_761_152 == 3_476_029_440
    assert cost.held_params(cfg) == 3_476_029_440 + 831_258_624 + 235_929_600 == 4_543_217_664  # 9.09 GB in bfloat16
    assert cost.held_params(cfg) + cost.sparse_layer_params(cfg) == 5_298_978_816  # a fifth sparse layer: 10.6 GB


def test_caches_and_routing(cfg):
    assert cost.kv_row_bytes(cfg) == 2 * 8 * 128 * 2 == 4096
    assert cost.cache_layers(cfg) == (4, 2)  # four rings; the stack's full layer and the module's grow
    assert 2 * 64 * 1537 * cost.kv_row_bytes(cfg) == 805_830_656  # the two growing caches of the cell: 0.81 GB
    assert 4 * 64 * 129 * cost.kv_row_bytes(cfg) == 135_266_304  # the four rings with their slot of slack: 0.14 GB
    assert cost.local_pairs_per_token(cfg) == 1.0  # 8 pairs a token, an eighth of the experts here
    assert cost.experts_hit(cfg, 128) == pytest.approx(16 * (1 - (15 / 16) ** 128))
    assert cost.experts_hit(cfg, 128) / 16 == pytest.approx(0.9997, abs=1e-4)  # a step's 128 positions hit every held expert
    assert cost.experts_hit(cfg, 64) / 16 == pytest.approx(0.984, abs=1e-3)  # 64 would hit 98.4%, the issue's figure


def test_speculative_step_bytes(cfg):
    """Weights: the dense layer, 4 sparse layers and the module's block with
    the experts 128 positions hit, the projection, the head once, 256
    embedding rows: 8.85 GB; caches: two growing at the context, four rings of 128."""
    hit = cost.experts_hit(cfg, 128)
    sparse = 113_246_208 + 786_432 + (1 + hit) * 37_748_736
    weights = 452_984_832 + 5 * sparse + 2 * 6144 * 6144 + 19200 * 6144 + 256 * 6144
    caches = 64 * (2 * 1280 + 4 * 128) * 4096
    assert cost.spec_step_bytes(cfg, 64, 1280) == pytest.approx(2 * weights + caches)
    assert 2 * weights == pytest.approx(8.85e9, rel=5e-3) and caches == pytest.approx(0.805e9, rel=5e-3)
    # a context shorter than the window: the rings are read as far as they are filled
    assert cost.spec_step_bytes(cfg, 64, 100) == pytest.approx(2 * weights + 64 * 6 * 100 * 4096)
    scan = cost.spec_scan_bytes(cfg, 64, 1024, 512)
    assert scan == pytest.approx(sum(cost.spec_step_bytes(cfg, 64, 1024 + j) for j in range(1, 512)))
    assert scan / 511 / 819e9 == pytest.approx(11.8e-3, rel=1e-2)  # 11.8 ms a step at the HBM peak


def test_prompt_pass_operations(cfg):
    assert cost.visible_pairs(1024, 128) == 128 * 129 // 2 + 896 * 128 == 122_944
    assert cost.visible_pairs(1024, 128) / cost.visible_pairs(1024) == pytest.approx(0.234, abs=1e-3)
    assert cost.attention_flops(cfg, 1024, 128) == 4 * 64 * 128 * 122_944
    # a token's products: the dense layer, then five sparse blocks with the shared expert and one local pair, and the projection
    sparse = 113_246_208 + 786_432 + 2 * 37_748_736
    per_token = 452_984_832 + 5 * sparse + 2 * 6144 * 6144
    assert cost.token_product_flops(cfg) == 2.0 * per_token
    assert 2 * per_token == pytest.approx(2.95e9, rel=5e-3)  # 3.0 GFLOP a token
    total = cost.prefill_flops(cfg, 64, 1024)
    attention = 64 * (2 * cost.attention_flops(cfg, 1024) + 4 * cost.attention_flops(cfg, 1024, 128))
    assert total == pytest.approx(65536 * 2 * per_token + attention + 2 * 2.0 * 64 * 6144 * 19200)
    assert total == pytest.approx(0.197e15, rel=1e-2)  # 0.197 PFLOP
    assert attention / total < 0.02  # short prompts: attention is under 2% of the pass
    assert cost.train_flops(cfg, 1, 1024) > 3 * total / 64


def test_kernel_costs(cfg):
    flash = cost.window_flash_cost(cfg, 64, 1024)
    assert flash["flops"] == 64 * 4 * 64 * 128 * 122_944 and flash["bytes"] == 64 * 1024 * 128 * (128 + 16) * 2
    assert flash["flops"] / 197e12 < flash["bytes"] / 819e9  # a window of 128 is bound by its bytes: 1.3 ms against 2.9
    experts = cost.expert_kernel_cost(cfg, 65536)
    assert experts["flops"] == 2.0 * 65536 * 37_748_736
    assert experts["bytes"] == 2 * (603_979_776 + 65536 * (2 * 6144 + 3 * 2048))
    assert experts["flops"] / 197e12 == pytest.approx(0.0251, rel=1e-2)  # 25 ms a layer at the peak
    assert cost.sparse_blocks(cfg) == 5
