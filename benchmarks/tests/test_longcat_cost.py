"""``lib/longcat_cost.py`` against hand counts at LongCat-Flash's published
widths (the figures of ISSUE 39: one latent attention 90.57M, a dense SwiGLU
226.49M, the router 4.72M, 638.8M a layer outside the experts, an expert
37.75M, a layer with 16 held 1 242.8M, embedding and head 2 x 100.66M, 5.173B
parameters, 10.35 GB; 1152 bytes of cache a token an attention, eight caches)."""

import pytest

from benchmarks import run
from benchmarks.lib import dsv3_cost
from benchmarks.lib import longcat_cost as cost


@pytest.fixture(scope="module")
def cfg():
    config = run.load_json("configs", "longcat-flash-ep32")
    return run.importlib.import_module("benchmarks.families.longcat_flash").Family(config).cfg


def test_parameter_counts(cfg):
    mla = 6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256 + 64 * 128 * 6144
    assert dsv3_cost.mla_params(cfg) == mla == 9_437_184 + 18_874_368 + 3_538_944 + 8_388_608 + 50_331_648 == 90_570_752
    assert cost.dense_mlp_params(cfg) == 3 * 6144 * 12288 == 226_492_416
    assert cost.router_width(cfg) == 512 + 256 == 768 and cost.router_params(cfg) == 6144 * 768 == 4_718_592
    assert cost.layer_params(cfg, 0) == 2 * 90_570_752 + 2 * 226_492_416 + 4_718_592 == 638_844_928  # outside the experts
    assert dsv3_cost.expert_params(cfg) == 3 * 6144 * 2048 == 37_748_736 and 16 * 37_748_736 == 603_979_776
    assert cost.layer_params(cfg) == 638_844_928 + 603_979_776 == 1_242_824_704
    assert cost.vocab_params(cfg) == 2 * 16384 * 6144 == 2 * 100_663_296
    assert cost.held_params(cfg) == 4 * 1_242_824_704 + 201_326_592 == 5_172_625_408  # 5.173B: 10.35 GB in bfloat16
    assert 2 * cost.held_params(cfg) == pytest.approx(10.345e9, rel=1e-4)
    assert 2 * (cost.held_params(cfg) + cost.layer_params(cfg)) == pytest.approx(12.83e9, rel=1e-3)  # a fifth layer: 12.8 GB
    # the training cut the issue rules out: one layer at the floor of 8 experts held, 16 bytes a parameter
    assert cost.layer_params(cfg, 8) == 940_834_816 and 16 * cost.layer_params(cfg, 8) == pytest.approx(15.05e9, rel=1e-3)


def test_caches_and_routing(cfg):
    assert dsv3_cost.latent_row_bytes(cfg) == (512 + 64) * 2 == 1152 and cost.cache_count(cfg) == 8
    assert 8 * 1152 == 9216 and 64 * 1536 * 9216 == 905_969_664  # 9216 bytes a token; the cell's eight caches: 0.91 GB
    assert cost.local_pairs_per_token(cfg) == 12 * 16 / 768 == 0.25
    assert cost.real_experts_per_token(cfg) == 12 * 512 / 768 == 8.0  # 0 to 12 a token, 8 in the mean
    assert cost.experts_hit_share(cfg, 64) == pytest.approx(1 - (1 - 12 / 768) ** 64)
    assert cost.experts_hit_share(cfg, 64) == pytest.approx(0.635, abs=5e-4)  # 64 tokens hit 63.5% of the held experts
    assert cost.experts_hit_share(cfg, 2048) > 0.9999  # the pool's step of 32 pairs an expert hits every one


def test_a_steps_bytes(cfg):
    """Weights: four layers outside their experts, 63.5% of the 16 held
    experts a layer, the head once, 64 embedding rows; caches: eight at the
    context. Every held expert read (the dense path) would be 10.14 GB."""
    hit = 16 * cost.experts_hit_share(cfg, 64)
    weights = 4 * (638_844_928 + hit * 37_748_736) + 16384 * 6144 + 64 * 6144
    caches = 64 * 1280 * 9216
    assert cost.decode_step_bytes(cfg, 64, 1280) == pytest.approx(2 * weights + caches)
    assert 2 * weights == pytest.approx(8.38e9, rel=2e-3) and caches == pytest.approx(0.755e9, rel=2e-3)
    every = 2 * (4 * cost.layer_params(cfg) + 16384 * 6144)
    assert every == pytest.approx(10.14e9, rel=1e-3) and every / 819e9 == pytest.approx(12.4e-3, rel=5e-3)
    assert 2 * 4 * 603_979_776 / every == pytest.approx(0.476, abs=2e-3)  # the shortcut branch's experts: 47% of those bytes
    assert 2 * 8 * 226_492_416 == pytest.approx(3.62e9, rel=2e-3) and 2 * 8 * 90_570_752 == pytest.approx(1.45e9, rel=2e-3)
    scan = cost.decode_scan_bytes(cfg, 64, 1024, 512)
    assert scan == pytest.approx(sum(cost.decode_step_bytes(cfg, 64, 1024 + j) for j in range(1, 512)))
    assert scan / 511 / 819e9 == pytest.approx(11.16e-3, rel=2e-3)  # 11.2 ms a step at the HBM peak
    assert cost.decode_step_bytes(cfg, 64, 1280, cache_itemsize=4) - cost.decode_step_bytes(cfg, 64, 1280) == caches


def test_prompt_pass_operations(cfg):
    per_token = 4 * (638_844_928 + 0.25 * 37_748_736)
    assert cost.token_product_flops(cfg) == 2.0 * per_token == pytest.approx(5.19e9, rel=2e-3)  # 5.2 GFLOP a token
    assert 4 * 0.25 * 37_748_736 / per_token == pytest.approx(0.0146, abs=2e-4)  # the held experts: 1.4 to 1.5% of the products
    attention = 2.0 * 64 * (1024 * 1025 / 2) * (192 + 128)
    assert dsv3_cost.attention_flops(cfg, 1024) == attention
    total = cost.prefill_flops(cfg, 64, 1024)
    assert total == pytest.approx(65536 * 2 * per_token + 64 * 8 * attention + 2.0 * 64 * 6144 * 16384)
    assert total == pytest.approx(351e12, rel=2e-3)  # 351 TFLOP
    assert 64 * 8 * attention / total == pytest.approx(0.031, abs=1e-3)  # short prompts: attention's scores and values are 3%
    assert cost.train_flops(cfg, 1, 1024) > 3 * total / 64


def test_kernel_costs(cfg):
    experts = cost.expert_kernel_cost(cfg, 65536)
    assert experts["flops"] == 2.0 * 16384 * 37_748_736
    assert experts["bytes"] == 2 * (603_979_776 + 16384 * (2 * 6144 + 3 * 2048))
    assert experts["flops"] / 197e12 == pytest.approx(6.28e-3, rel=1e-2)  # 6.3 ms a layer at the peak
    assert experts["bytes"] / 819e9 < experts["flops"] / 197e12  # read once a layer the weights are 2.2 ms: the products bound it
