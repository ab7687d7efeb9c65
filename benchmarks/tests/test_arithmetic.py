"""FLOPs and bytes against hand-worked values, the peaks table."""

import json

import pytest

from benchmarks.lib import flops
from benchmarks.lib.peaks import load_peaks


def test_attention_pairs():
    assert flops.attention_pairs(4, 10, causal=False) == 40
    # right-aligned: rows see 7, 8, 9, 10 keys
    assert flops.attention_pairs(4, 10, causal=True) == 34
    assert flops.attention_pairs(3, 3, causal=True) == 6
    with pytest.raises(ValueError):
        flops.attention_pairs(4, 3, causal=True)


def test_flash_attention_cost_by_hand():
    call = {"batch": 2, "heads": 3, "n_q": 4, "n_kv": 10, "d_qk": 8, "d_v": 16, "causal": True}
    fwd = flops.flash_attention_cost(call, backward=False)
    # 34 pairs x 2 x (8 + 16) = 1632 per head; 6 heads
    assert fwd["flops"] == 1632 * 6
    # q 32 + k 80 + v 160 + o 64 elements x 2 bytes + 4 x 4 bytes of row statistics
    assert fwd["bytes"] == (2 * (32 + 80 + 160 + 64) + 16) * 6
    bwd = flops.flash_attention_cost(call, backward=True)
    assert bwd["flops"] == 2 * fwd["flops"]
    assert bwd["bytes"] == (2 * (32 + 80 + 160 + 64 + 64) + 32 + 2 * (32 + 80 + 160)) * 6


def test_roofline_takes_the_larger_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 1.0}
    call = {"batch": 1, "heads": 1, "n_q": 2, "n_kv": 2, "d_qk": 1, "d_v": 1, "causal": False}
    got = flops.roofline_seconds([call], peaks, training=False)
    assert got["bound"] == "bytes" and got["seconds"] == pytest.approx(2 * 8 + 8)
    peaks = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1e9}
    got = flops.roofline_seconds([call], peaks, training=True)
    assert got["bound"] == "flops" and got["seconds"] == pytest.approx(16 + 32)


def test_perceiver_ar_train_flops_by_hand():
    cfg = {"max_latents": 2, "num_channels": 4, "num_self_attention_layers": 1, "max_seq_len": 6,
           "cross_attention_dropout": 0.5, "self_attention_widening_factor": 4,
           "cross_attention_widening_factor": 4, "vocab_size": 10}
    # prefix 4, kept 2, kv 4
    ca = 2 * 2 * 64 + 2 * 2 * 32 + 2 * 2 * 2 * 4 * 4 + 2 * 2 * 2 * 4 * 16
    sa = 2 * 2 * 64 + 2 * 2 * 2 * 2 * 4 + 2 * 2 * 2 * 4 * 16
    logits = 2 * 2 * 4 * 10
    assert flops.perceiver_ar_train_flops(cfg, 3) == 3.0 * (ca + sa + logits) * 3


def test_perceiver_io_image_train_flops_by_hand():
    cfg = {"image_shape": [2, 2, 1], "num_latents": 2, "num_latent_channels": 4, "num_frequency_bands": 1,
           "cross_attention_widening_factor": 1, "self_attention_widening_factor": 1,
           "num_self_attention_layers_per_block": 1, "num_self_attention_blocks": 2}
    m, in_ch = 4, 1 + 2 * 3
    ca = 2 * 2 * 4 * in_ch + 2 * m * in_ch * in_ch * 2 + 2 * 2 * 2 * m * in_ch + 2 * 2 * in_ch * 4 + 2 * 2 * 2 * 16
    sa = 2 * (2 * 2 * 4 * 16 + 2 * 2 * 2 * 2 * 4 + 2 * 2 * 2 * 16)
    assert flops.perceiver_io_image_train_flops(cfg, 5) == 3.0 * (ca + sa) * 5


def test_real_configs_flops_magnitudes():
    from benchmarks import run

    ar = run.load_json("configs", "perceiver-ar-small-16k")
    assert flops.perceiver_ar_train_flops(ar, 32) == pytest.approx(9.767e12, rel=1e-3)
    image = run.load_json("configs", "perceiver-io-imagenet-fourier")
    assert flops.perceiver_io_image_train_flops(image, 16) == pytest.approx(1.939e13, rel=1e-3)


def test_peaks_are_keyed_by_device_kind_and_unknown_raises():
    v5e = load_peaks("TPU v5 lite")
    assert v5e == {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    with open(__import__("benchmarks.lib.peaks", fromlist=["x"]).PEAKS_FILE) as f:
        assert json.load(f)["source"] == "Google Cloud documentation, TPU v5e"
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(KeyError):
            load_peaks(kind)
