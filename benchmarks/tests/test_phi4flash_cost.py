"""``lib/phi4flash_cost.py`` against hand counts at Phi-4-mini-flash-reasoning's
published widths (the figures of ISSUE 53 with the attention biases in: a Mamba
mixer 41 241 600, a self differential attention 19 668 864, a cross one
13 112 704, a gated memory unit 26 214 400, a SwiGLU 78 643 200, two LayerNorms
a layer 10 240; the tied table 512 163 840: 3 852 562 944 parameters, 7.71 GB;
5120 bytes of shared cache a token a row, held once and read eight times a step)."""

import pytest

from benchmarks import run
from benchmarks.lib import phi4flash_cost as cost


@pytest.fixture(scope="module")
def cfg():
    config = run.load_json("configs", "phi4-mini-flash")
    return run.importlib.import_module("benchmarks.families.phi4flash").Family(config).cfg


def test_parameter_counts(cfg):
    assert cost.d_inner(cfg) == 5120 and cfg["head_dim"] == 64
    products = 2560 * 10240 + 5120 * (160 + 16 + 16) + 160 * 5120 + 5120 * 2560
    # the convolution's four taps and its bias, W_dt's bias, A_log, D; no inner norm
    assert cost.mamba_params(cfg) == products + 4 * 5120 + 5120 + 5120 + 16 * 5120 + 5120 == 41_241_600
    assert cost.gmu_params(cfg) == 2 * 2560 * 5120 == 26_214_400
    assert cost.self_attention_products(cfg) == 2560 * 5120 + 2560 * 2560 == 19_660_800
    assert cost.self_attention_params(cfg) == 19_660_800 + 5120 + 2560 + 4 * 64 + 128 == 19_668_864
    assert cost.cross_attention_params(cfg) == 2 * 2560 * 2560 + 2560 + 2560 + 384 == 13_112_704
    assert cost.mlp_params(cfg) == 3 * 2560 * 10240 == 78_643_200
    assert [cost.layer_params(cfg, k) for k in ("mamba", "sliding_attention", "full_attention", "gmu", "cross_attention")] == [
        119_895_040, 98_322_304, 98_322_304, 104_867_840, 91_766_144]
    assert [cost.n_layers(cfg, k) for k in ("mamba", "sliding_attention", "full_attention", "gmu", "cross_attention")] == [9, 8, 1, 7, 7]
    assert cost.table_params(cfg) == 200064 * 2560 == 512_163_840  # once: the head is the table
    total = 9 * 119_895_040 + 9 * 98_322_304 + 7 * 104_867_840 + 7 * 91_766_144 + 512_163_840 + 5120
    assert cost.held_params(cfg) == total == 3_852_562_944 and 2 * total == pytest.approx(7.705e9, rel=1e-4)


def test_the_shared_cache_is_held_once_and_read_eight_times(cfg):
    assert (cost.shared_cache_layer(cfg), cost.prompt_layers(cfg), cost.layers_skipped(cfg), cost.shared_cache_readers(cfg)) == (17, 17, 15, 8)
    assert cost.kv_row_bytes(cfg) == 2 * 20 * 64 * 2 == 5120
    assert cost.shared_cache_bytes(cfg, 32, 8448) == 32 * 8448 * 5120 == 1_384_120_320  # 1.38 GB; eight private caches would be 11.1 GB
    assert cost.ring_bytes(cfg, 32) == 8 * 32 * 512 * 5120 == 671_088_640
    assert cost.ssm_state_bytes(cfg, 32) == 9 * 32 * 16 * 5120 * 4 == 94_371_840 and cost.conv_window_bytes(cfg, 32) == 9 * 32 * 3 * 5120 * 2
    weights, state = 2 * 3_852_562_944, 2 * (94_371_840 + 8_847_360)
    step = cost.decode_step_bytes(cfg, 32, 8320)
    assert step == weights + state + 671_088_640 + 8 * 32 * 8320 * 5120 == pytest.approx(19.49e9, rel=1e-3)
    assert 8 * 32 * 8320 * 5120 / step == pytest.approx(0.56, abs=0.01)  # the shared reads: over half of a step
    assert step / 819e9 == pytest.approx(23.8e-3, rel=1e-2)
    assert cost.decode_step_bytes(cfg, 32, 100) - cost.decode_step_bytes(cfg, 32, 99) == 8 * 32 * 5120 + 8 * 32 * 5120  # a ring not yet full grows too
    scan = cost.decode_scan_bytes(cfg, 32, 8192, 256)
    assert scan == pytest.approx(sum(cost.decode_step_bytes(cfg, 32, 8192 + j) for j in range(1, 256)))


def test_the_cut_prompt_pass(cfg):
    assert cost.visible_pairs(8192) == 8192 * 8193 // 2 and cost.visible_pairs(8192, 512) == 512 * 513 // 2 + 7680 * 512
    assert cost.attention_flops(cfg, 1) == 2 * 20 * (2 * 64 + 2 * 128)  # a pair: two dots of 64, two axpys of 128, a query pair
    below = 9 * (41_123_840 + 78_643_200) + 8 * (19_660_800 + 78_643_200)
    assert cost.token_product_flops(cfg, cfg["layer_types"][:17]) == 2.0 * below
    above = 19_660_800 + 78_643_200 + 7 * (26_214_400 + 78_643_200) + 7 * (13_107_200 + 78_643_200)
    cut = 32 * (8192 * 2.0 * below + 8 * cost.attention_flops(cfg, cost.visible_pairs(8192, 512)) + 8192 * 2.0 * 2560 * 2560
                + 2.0 * above + 8 * cost.attention_flops(cfg, 8192) + 2.0 * 2560 * 200064)
    assert cost.prefill_flops(cfg, 32, 8192) == pytest.approx(cut, rel=1e-12) and cut == pytest.approx(0.997e15, rel=2e-3)
    whole = cost.prefill_flops(cfg, 32, 8192, cut=False)
    assert whole == pytest.approx(1.899e15, rel=2e-3) and whole / cut == pytest.approx(1.90, abs=0.01)  # what the cut saves
    assert cost.train_flops(cfg, 1, 4096) > 3 * cost.prefill_flops(cfg, 1, 4096, cut=False)


def test_the_window_flash_kernels_cost(cfg):
    one = cost.diff_flash_cost(cfg, 32, 8192)
    assert one["flops"] == 32 * cost.attention_flops(cfg, cost.visible_pairs(8192, 512)) == pytest.approx(2.0e12, rel=2e-3)
    assert one["bytes"] == 32 * 8192 * (2 * 2560 + 2 * 1280) * 2 == 4_026_531_840
    # the operations bind: 10.1 ms a layer at the bf16 peak against 4.9 ms to move the bytes
    assert one["flops"] / 197e12 == pytest.approx(10.1e-3, rel=1e-2) and one["bytes"] / 819e9 == pytest.approx(4.9e-3, rel=1e-2)
