"""``lib/jamba_cost.py`` against hand counts at Jamba2-3B's published widths
(the figures of ISSUE 41: a Mamba mixer 41 241 792, an attention 13 762 560, a
SwiGLU 62 914 560, two norms a layer 5 120; 26 Mamba layers of 104 161 472, 2
attention layers of 76 682 240, the tied table 167 772 160, the last norm
2 560: 3 029 337 472 parameters, 6.06 GB; 2.18 GB of float32 state at batch
256, read and written every step; 512 bytes of cache a token an attention)."""

import pytest

from benchmarks import run
from benchmarks.lib import jamba_cost as cost


@pytest.fixture(scope="module")
def cfg():
    config = run.load_json("configs", "jamba2-3b")
    return run.importlib.import_module("benchmarks.families.jamba").Family(config).cfg


def test_parameter_counts(cfg):
    assert cost.d_inner(cfg) == 2 * 2560 == 5120
    products = 2560 * 10240 + 5120 * (160 + 16 + 16) + 160 * 5120 + 5120 * 2560
    assert cost.mamba_products(cfg) == products == 26_214_400 + 983_040 + 819_200 + 13_107_200 == 41_123_840
    # the convolution's four taps and its bias, W_dt's bias, A_log, D, the three inner norms (160 + 16 + 16)
    assert cost.mamba_params(cfg) == products + 4 * 5120 + 5120 + 5120 + 16 * 5120 + 5120 + 192 == 41_241_792
    assert cost.attention_params(cfg) == 2560 * 2560 + 2 * 2560 * 128 + 2560 * 2560 == 13_762_560
    assert cost.mlp_params(cfg) == 3 * 2560 * 8192 == 62_914_560
    assert cost.layer_params(cfg, "mamba") == 41_241_792 + 62_914_560 + 5120 == 104_161_472
    assert cost.layer_params(cfg, "full_attention") == 13_762_560 + 62_914_560 + 5120 == 76_682_240
    assert cost.table_params(cfg) == 65536 * 2560 == 167_772_160  # once: the head is the table
    assert (cost.n_layers(cfg, "mamba"), cost.n_layers(cfg, "full_attention")) == (26, 2)
    assert cost.held_params(cfg) == 26 * 104_161_472 + 2 * 76_682_240 + 167_772_160 + 2560 == 3_029_337_472
    assert 2 * cost.held_params(cfg) == pytest.approx(6.06e9, rel=1e-3)
    # the training cut the issue rules out: one period of the pattern with an eighth of the vocabulary, 16 bytes a parameter
    period = 13 * 104_161_472 + 76_682_240 + 8192 * 2560 + 2560
    assert period == pytest.approx(1.45e9, rel=1e-2) and 16 * period == pytest.approx(23.2e9, rel=1e-2)


def test_the_state_the_windows_and_the_caches(cfg):
    assert cost.ssm_state_bytes(cfg, 256) == 26 * 256 * 16 * 5120 * 4 == 2_181_038_080  # 2.18 GB, float32
    assert cost.ssm_state_bytes(cfg, 1) == 26 * 327_680  # 320 KB a row a layer: one size whatever the context
    assert cost.conv_window_bytes(cfg, 256) == 26 * 256 * 3 * 5120 * 2 == 204_472_320
    assert cost.kv_row_bytes(cfg) == 2 * 1 * 128 * 2 == 512
    assert 2 * 256 * 640 * 512 == 167_772_160  # the cell's two caches at their end: 0.17 GB beside 2.4 GB of state


def test_a_steps_bytes(cfg):
    """Every weight once, the state and the windows read and written, the two caches at the context."""
    weights = 2 * 3_029_337_472
    state = 2 * (2_181_038_080 + 204_472_320)
    assert cost.decode_step_bytes(cfg, 256, 640) == weights + state + 2 * 256 * 640 * 512 == 10_997_467_904
    assert state == pytest.approx(4.77e9, rel=1e-3) and state / cost.decode_step_bytes(cfg, 256, 640) == pytest.approx(0.434, abs=2e-3)
    scan = cost.decode_scan_bytes(cfg, 256, 256, 384)
    assert scan == pytest.approx(sum(cost.decode_step_bytes(cfg, 256, 256 + j) for j in range(1, 384)))
    assert scan / 383 == pytest.approx(10.95e9, rel=1e-3) and scan / 383 / 819e9 == pytest.approx(13.37e-3, rel=1e-3)  # 13.4 ms a step
    # the Mamba mixers' weights and state: over 60% of a step's bytes (what the cell is for)
    mixers = 2 * 26 * 41_241_792 + state
    assert mixers / (scan / 383) == pytest.approx(0.63, abs=0.01)
    # a float32 cache doubles the windows' and the caches' bytes and leaves the state's alone
    wider = cost.decode_step_bytes(cfg, 256, 640, cache_itemsize=4) - cost.decode_step_bytes(cfg, 256, 640)
    assert wider == 2 * 204_472_320 + 167_772_160


def test_prompt_pass_operations(cfg):
    per_token = 26 * (41_123_840 + 62_914_560) + 2 * (13_762_560 + 62_914_560)
    assert cost.token_product_flops(cfg) == 2.0 * per_token == pytest.approx(5.717e9, rel=1e-3)  # 5.7 GFLOP a token
    attention = 2.0 * 2.0 * 20 * 128 * (256 * 257 / 2)
    assert cost.attention_flops(cfg, 256) == attention
    total = cost.prefill_flops(cfg, 256, 256)
    assert total == pytest.approx(65536 * 2.0 * per_token + 256 * 2 * attention + 2.0 * 256 * 2560 * 65536)
    assert total == pytest.approx(375e12, rel=2e-3) and total / 197e12 == pytest.approx(1.90, abs=0.01)  # 375 TFLOP, 1.9 s at the peak
    assert 256 * 2 * attention / total < 1e-3  # two one-head layers over 256 positions: nothing
    assert cost.train_flops(cfg, 1, 256) > 3 * total / 256


def test_kernel_costs(cfg):
    scan = cost.scan_cost(cfg, 256, 256)
    assert scan["flops"] == 65536 * 5120 * (7 * 16 + 1)  # 113 elementwise operations a channel a token: the recurrence alone
    # x, the step size and y a channel a token, B and C a state a token, A once, the final state
    assert scan["bytes"] == 4 * (65536 * (3 * 5120 + 32) + 16 * 5120 + 256 * 16 * 5120)
    assert scan["bytes"] / 819e9 == pytest.approx(5.03e-3, rel=1e-2)  # 5.0 ms a layer at the HBM peak
    assert scan["flops"] / 197e12 < 0.05 * scan["bytes"] / 819e9  # no matrix-unit form: against that peak the bytes bind
    assert 26 * 65536 * 5120 * 16 == pytest.approx(140e9, rel=1e-2)  # state elements a prompt pass updates
    # a step's update of one layer: the state read and written once, 205 us at the HBM peak (no kernel reads it: XLA's fusion is at that floor)
    assert 2 * cost.ssm_state_bytes(cfg, 256) // 26 == 2 * 256 * 16 * 5120 * 4 == 167_772_160
