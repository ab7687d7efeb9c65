"""``lib/brumby_cost.py`` against hand counts at Brumby-14B-Base's published
widths, one pipeline stage of eight (the figures of ISSUE 46: a layer's
projections 62 914 560, its gate 40 968, its q and k norms 256, its SwiGLU
267 386 880, its two norms 10 240: 330 352 904 a layer; the embedding and the
head 777 912 320 each; the last norm 5 120: 3 207 594 280 parameters, 6.42 GB;
34.08 MB of float32 state a row a layer, 5.45 GB at batch 32 over five layers;
a step 15.77 GB, 19.2 ms at the HBM peak; a prompt pass 0.50 PFLOP)."""

import pytest

from benchmarks import run
from benchmarks.lib import brumby_cost as cost


@pytest.fixture(scope="module")
def cfg():
    config = run.load_json("configs", "brumby-14b-pp8")
    return run.importlib.import_module("benchmarks.families.brumby").Family(config).cfg


def test_parameter_counts(cfg):
    assert cost.projection_params(cfg) == 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 5120 == 62_914_560
    assert cost.gate_params(cfg) == 5120 * 8 + 8 == 40_968
    assert cost.mixer_params(cfg) == 62_914_560 + 40_968 + 2 * 128 == 62_955_784
    assert cost.mlp_params(cfg) == 3 * 5120 * 17408 == 267_386_880
    assert cost.layer_params(cfg) == 62_955_784 + 267_386_880 + 2 * 5120 == 330_352_904
    assert cost.table_params(cfg) == 151936 * 5120 == 777_912_320
    assert cost.held_params(cfg) == 5 * 330_352_904 + 2 * 777_912_320 + 5120 == 3_207_594_280
    assert 2 * cost.held_params(cfg) == pytest.approx(6.42e9, rel=1e-3)
    # the model whole: 40 such layers, 28 GB in bfloat16; the training cut the issue rules out: four layers and an eighth of the vocabulary
    assert 2 * (40 * 330_352_904 + 2 * 777_912_320 + 5120) == pytest.approx(29.5e9, rel=1e-2)
    assert 16 * (4 * 330_352_904 + 2 * 777_912_320 // 8) == pytest.approx(24.3e9, rel=1e-2)


def test_the_state(cfg):
    assert cost.feature_dim(cfg) == 128 * 129 // 2 == 8256  # the squares and every pair once
    assert cost.state_row_bytes(cfg) == (8 * 8256 * 128 + 8 * 8256) * 4 == 34_080_768  # S and z, float32: 34.08 MB a row a layer
    assert cost.state_bytes(cfg, 32) == 5 * 32 * 34_080_768 == 5_452_922_880  # 5.45 GB
    assert 40 * 34_080_768 == pytest.approx(1.36e9, rel=3e-3)  # the model whole: 1.36 GB a row whatever the context
    assert 34_080_768 / (2 * 8 * 128 * 2) == pytest.approx(8320.5)  # as much as 8.3k tokens of the key-value cache this skeleton would keep
    assert 6.42e9 + 5.45e9 == pytest.approx(0.74 * 16e9, rel=1e-2)


def test_a_steps_bytes(cfg):
    """Five layers' weights and the head once, the state read and written; nothing grows with the context."""
    weights = 2 * (3_207_594_280 - 777_912_320)
    assert weights == pytest.approx(4.86e9, rel=1e-3)
    assert cost.decode_step_bytes(cfg, 32) == weights + 2 * 5_452_922_880 == 15_765_209_680
    assert cost.decode_step_bytes(cfg, 32) / 819e9 == pytest.approx(19.25e-3, rel=1e-3)  # 19.2 ms a step at the HBM peak
    assert 2 * 5_452_922_880 / cost.decode_step_bytes(cfg, 32) == pytest.approx(0.69, abs=0.005)  # 69% of a step's bytes are the state
    assert cost.decode_scan_bytes(cfg, 32, 256) == 255 * 15_765_209_680
    assert 2 * 5_452_922_880 / 5 / 819e9 == pytest.approx(2.66e-3, rel=1e-2)  # a layer's state once each way: 2.7 ms


def test_prompt_pass_operations(cfg):
    per_layer = 62_914_560 + 5120 * 8 + 267_386_880
    assert cost.token_product_flops(cfg) == 2.0 * 5 * per_layer and 2.0 * per_layer == pytest.approx(660.7e6, rel=1e-4)
    per_token = cost.retention_token_flops(cfg)
    assert per_token == {"query": 2.0 * 40 * 8256 * 128, "key": 2.0 * 8 * 8256 * 128}
    assert per_token["query"] == pytest.approx(84.5e6, rel=1e-3) and per_token["key"] == pytest.approx(16.9e6, rel=1e-3)
    # in-chunk form 20.5 kFLOP a key a token, the state form 101 MFLOP a token flat: they cross near 5000 keys
    assert 2.0 * 2.0 * 40 * 128 == 20_480 and (per_token["query"] + per_token["key"]) / 20_480 == pytest.approx(4953, rel=1e-3)
    total = cost.prefill_flops(cfg, 32, 4096)
    chunked = cost.chunk_cost(cfg, 32, 4096)["flops"]
    assert total == pytest.approx(131072 * 2.0 * 5 * per_layer + 5 * chunked + 2.0 * 32 * 5120 * 151936)
    assert total == pytest.approx(0.4995e15, rel=2e-3) and total / 197e12 == pytest.approx(2.54, abs=0.01)  # 0.50 PFLOP, 2.54 s at the peak
    assert 5 * chunked / total == pytest.approx(0.133, abs=0.003)  # the retention is a seventh of the prompt pass's operations
    assert cost.train_flops(cfg, 1, 4096) > 3 * total / 32


def test_kernel_costs(cfg):
    chunk = cost.chunk_cost(cfg, 32, 4096)
    tokens = 32 * 4096
    assert chunk["flops"] == tokens * (2.0 * 40 * 8256 * 128 + 2.0 * 8 * 8256 * 128)  # the state form: a token's query and key sides
    # q and y 40 heads, k and v 8, bfloat16; a float32 gate a key-value head; the rows' final state
    assert chunk["bytes"] == tokens * ((2 * 40 + 2 * 8) * 128 * 2 + 8 * 4) + 32 * 34_080_768
    assert chunk["flops"] / 197e12 == pytest.approx(67.5e-3, rel=1e-2)  # 67.5 ms a layer at the bf16 peak
    assert chunk["bytes"] / 819e9 < 0.08 * chunk["flops"] / 197e12  # the operations bind, by far
    # what phi(Q) as an array in HBM would be: 660 KB a token a layer, 87 GB a layer at the cell's 131 072 prompt tokens
    assert 40 * 8256 * 2 == 660_480 and 131072 * 660_480 == pytest.approx(86.6e9, rel=1e-3)


def test_the_floor_does_not_follow_a_programs_chunk(cfg):
    """A chunk's scores and values are the program's choice of shape (2.6 MFLOP a token at 256, 10.5 at 1024, on the
    state form's 101.4): counted, a longer chunk would read as more useful work for no speed-up. And the state form is
    the floor only because the attention form under the switch-over is not built."""
    import inspect

    assert "chunk" not in inspect.signature(cost.chunk_cost).parameters and "chunk" not in inspect.signature(cost.prefill_flops).parameters
    per_token = cost.retention_token_flops(cfg)
    state_form = per_token["query"] + per_token["key"]
    assert cost.chunk_cost(cfg, 1, 4096)["flops"] == 4096 * state_form and state_form == pytest.approx(101.4e6, rel=1e-3)
    in_chunk = lambda c: 20_480 * (c + 1) / 2  # noqa: E731
    assert in_chunk(256) == pytest.approx(2.63e6, rel=1e-2) and in_chunk(1024) == pytest.approx(10.5e6, rel=1e-2)
    assert in_chunk(4096) + per_token["key"] == pytest.approx(58.9e6, rel=1e-2)  # the attention form over 4096 keys, with the hand-off's key side
