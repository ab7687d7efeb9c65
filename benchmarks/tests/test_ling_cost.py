"""``lib/ling_cost.py`` against hand counts at Ling-3.0-flash's published
widths, one chip of four and one stage of seven (the figures of ISSUE 49: a KDA
mixer 63.05 M, a latent attention 31.97 M, an expert 5.898 M (128 held: 755.0
M), the dense SwiGLU 47.19 M, a quarter of both tables 201.2 M: 5.23 B
parameters, 10.46 GB; 2 097 152 bytes of float32 state a row a delta layer, 1.61
GB at batch 128 over six; a step 13.9 GB with every held expert read, 12.7 with
those a step hits; a prompt pass 310 TFLOP)."""

import jax
import numpy as np
import pytest

from benchmarks import run
from benchmarks.lib import ling_cost as cost


@pytest.fixture(scope="module")
def family():
    config = run.load_json("configs", "ling3-flash-ep4")
    return run.importlib.import_module("benchmarks.families.ling").Family(config)


@pytest.fixture(scope="module")
def cfg(family):
    return family.cfg


def test_parameter_counts(cfg):
    projections = 6 * 2560 * 4096  # W_q, W_k, W_v, W_f, W_g, W_o
    assert cost.width(cfg) == 32 * 128 == 4096
    assert cost.kda_params(cfg) == projections + 3 * 4 * 4096 + 4096 + 32 + 2560 * 32 + 128 == 63_049_888
    assert cost.mla_params(cfg) == 2560 * 32 * 192 + 2560 * 576 + 512 + 512 * 32 * 256 + 2560 * 32 + 4096 * 2560 == 31_965_696
    assert cost.expert_params(cfg) == 3 * 2560 * 768 == 5_898_240 and 128 * 5_898_240 == 754_974_720
    assert cost.dense_mlp_params(cfg) == 3 * 2560 * 6144 == 47_185_920
    assert cost.router_params(cfg) == 2560 * 512 + 512 == 1_311_232
    assert cost.sparse_ffn_params(cfg, 128) == 1_311_232 + 129 * 5_898_240 == 762_184_192
    assert cost.table_params(cfg) == 39296 * 2560 == 100_597_760
    layers = 6 * 63_049_888 + 31_965_696 + 7 * 2 * 2560 + 47_185_920 + 6 * 762_184_192
    assert cost.held_params(cfg) == layers + 2560 + 2 * 100_597_760 == 5_231_790_016
    assert 2 * cost.held_params(cfg) == pytest.approx(10.46e9, rel=1e-3)
    assert cost.sparse_layers(cfg) == 6 and cost.kda_layers(cfg) == 6 and cost.latent_layers(cfg) == 1


def test_the_count_is_the_programs(family, cfg):
    """``jax.eval_shape`` of the program, leaf for leaf: every mixer, every feed-forward, the tables and the norms."""
    shapes = family.param_shapes(family.model())["params"]
    size = lambda tree: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))  # noqa: E731
    assert size(shapes) == cost.held_params(cfg)
    assert size(shapes["layer_0"]["mixer"]) == cost.kda_params(cfg) and size(shapes["layer_4"]["attn"]) == cost.mla_params(cfg)
    assert size(shapes["layer_0"]["ffn"]) == cost.dense_mlp_params(cfg) and size(shapes["layer_1"]["ffn"]) == cost.sparse_ffn_params(cfg, 128)
    assert size(shapes["embedding"]) == size(shapes["head"]) == cost.table_params(cfg)
    assert [cost.mixer_params(cfg, kind) for kind in cfg["layer_types"]] == [size(shapes[f"layer_{i}"].get("mixer") or shapes[f"layer_{i}"]["attn"]) for i in range(7)]


def test_the_state_and_the_cache(cfg):
    assert cost.state_row_bytes(cfg) == 32 * 128 * 128 * 4 == 2_097_152  # 2 MB a row a delta layer, float32
    assert cost.window_row_bytes(cfg) == 3 * 3 * 4096 * 2 == 73_728  # 74 KB of windows a row a layer
    assert cost.state_bytes(cfg, 128) == 6 * 128 * 2_097_152 == 1_610_612_736  # 1.61 GB
    assert 6 * 2_097_152 == pytest.approx(12.6e6, rel=2e-3)  # 12.6 MB a row over the six, whatever the context
    assert cost.latent_row_bytes(cfg) == 576 * 2 == 1152 and 128 * 2304 * 1152 == pytest.approx(0.34e9, rel=1e-2)
    assert 2_097_152 / 1152 == pytest.approx(1820, rel=1e-3)  # a delta layer's state is what 1820 tokens of the latent cache take
    held = 10.46e9 + 1.61e9 + 0.34e9 + 6 * 128 * 73_728
    assert held == pytest.approx(12.5e9, rel=1e-2) and held / 16e9 > 0.75


def test_a_steps_bytes(cfg):
    """The experts a step's tokens hit, every other weight and the head, the states both ways, the cache once."""
    hit = cost.experts_hit(cfg, 128)
    assert cost.local_pairs_per_token(cfg) == 2.0 and hit == pytest.approx(128 * (1 - (1 - 8 / 512) ** 128)) and 110 < hit < 112
    parts = cost.decode_step_parts(cfg, 128, 2176, hit)
    other = 6 * 63_049_888 + 31_965_696 + 7 * 2 * 2560 + 2560 + 47_185_920 + 6 * (1_311_232 + 5_898_240) + 100_597_760 + 128 * 2560
    assert parts["other_weights"] == 2 * other and parts["experts"] == pytest.approx(6 * hit * 5_898_240 * 2)
    assert parts["state"] == 2 * (1_610_612_736 + 6 * 128 * 73_728) and parts["cache"] == 128 * 2176 * 1152
    assert cost.decode_step_bytes(cfg, 128, 2176) == pytest.approx(sum(parts.values())) == pytest.approx(12.71e9, rel=1e-3)
    held = sum(cost.decode_step_parts(cfg, 128, 2176, 128).values())
    assert held == pytest.approx(13.92e9, rel=1e-3) and held / 819e9 == pytest.approx(17.0e-3, rel=1e-2)  # every held expert read: 17.0 ms
    assert 6 * 754_974_720 * 2 / held == pytest.approx(0.65, abs=0.005) and parts["state"] / held == pytest.approx(0.24, abs=0.005)
    assert parts["cache"] / held == pytest.approx(0.023, abs=0.002)
    assert cost.decode_scan_bytes(cfg, 128, 2048, 256) == pytest.approx(sum(cost.decode_step_bytes(cfg, 128, 2048 + j) for j in range(1, 256)))
    assert cost.step_state_bytes(cfg, 128) == 2 * 1_610_612_736 and cost.step_state_bytes(cfg, 128) / 819e9 == pytest.approx(3.93e-3, rel=1e-2)


def test_prompt_pass_operations(cfg):
    assert cost.kda_token_flops(cfg) == 6.0 * 32 * 128 * 128 == 3_145_728
    kda = 6 * 2560 * 4096 + 2560 * 32
    mla = 2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 2560 * 32 + 4096 * 2560
    sparse = 2560 * 512 + (1 + 2) * 5_898_240
    assert cost.token_product_flops(cfg) == 2.0 * (6 * kda + mla + 47_185_920 + 6 * sparse) == 1_142_325_248
    assert cost.attention_flops(cfg, 2048) == 2.0 * 32 * (2048 * 2049 / 2) * (192 + 128)
    total = cost.prefill_flops(cfg, 128, 2048)
    chunked = cost.chunk_cost(cfg, 128, 2048)["flops"]
    assert total == pytest.approx(262144 * 1_142_325_248 + 6 * chunked + 128 * cost.attention_flops(cfg, 2048) + 2.0 * 128 * 2560 * 39296)
    assert total == pytest.approx(310e12, rel=2e-3) and total / 197e12 == pytest.approx(1.573, abs=0.005)  # 310 TFLOP, 1.57 s at the peak
    assert 6 * chunked / total == pytest.approx(0.016, abs=0.001) and 128 * cost.attention_flops(cfg, 2048) / total == pytest.approx(0.018, abs=0.001)
    assert cost.train_flops(cfg, 1, 2048) > 3 * total / 128


def test_kernel_costs(cfg):
    chunk = cost.chunk_cost(cfg, 128, 2048)
    tokens = 128 * 2048
    assert chunk["flops"] == tokens * 3_145_728  # the recurrence: three products of 128 x 128 a head a token
    # q, k, v and y at 4096 channels, bfloat16; a float32 log-decay a channel and a step a head; the rows' final state
    assert chunk["bytes"] == tokens * (4 * 4096 * 2 + 4096 * 4 + 32 * 4) + 128 * 2_097_152
    assert chunk["bytes"] / 819e9 == pytest.approx(16.1e-3, rel=1e-2) and chunk["flops"] / 197e12 == pytest.approx(4.19e-3, rel=1e-2)  # the bytes bind
    experts = cost.expert_kernel_cost(cfg, tokens)
    assert experts["flops"] == 2.0 * tokens * 2 * 5_898_240 and experts["bytes"] == 2 * (754_974_720 + tokens * 2 * (2 * 2560 + 3 * 768))
    assert experts["flops"] / 197e12 == pytest.approx(31.4e-3, rel=1e-2) and experts["bytes"] / 819e9 < 0.4 * experts["flops"] / 197e12


def test_the_floor_does_not_follow_a_programs_chunk(cfg):
    """A chunk's pairwise products and its triangular solve are the program's choice of shape: counted, a longer
    chunk would read as more useful work for no speed-up."""
    import inspect

    assert "chunk" not in inspect.signature(cost.chunk_cost).parameters and "chunk" not in inspect.signature(cost.prefill_flops).parameters
    assert cost.chunk_cost(cfg, 1, 2048)["flops"] == 2048 * 3_145_728
    in_chunk = lambda c: 32 * (2 * 2.0 * 128 * (c + 1) / 2 + 2.0 * 2 * 128 * c)  # noqa: E731  A and B over the visible pairs, T and B against U
    assert in_chunk(128) > cost.kda_token_flops(cfg) / 3  # at the program's chunk the in-chunk products are of the recurrence's own size
