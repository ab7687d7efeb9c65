"""``lib/dots3_cost.py`` against hand counts at the published widths: the
parameters a chip holds, the caches, and the mechanism counted by its
definition, whatever a program's tiling."""

import pytest

from benchmarks import run
from benchmarks.lib import dots3_cost as cost


@pytest.fixture(scope="module")
def cfg():
    config = run.load_json("configs", "dots3-note-ep8")
    return run.importlib.import_module("benchmarks.families.dots3").Family(config).cfg


def test_the_attentions_by_hand(cfg):
    full = 5120 * 1024 + 1024 + 1024 * 128 * 192 + 5120 * 576 + 512 + 512 * 128 * 256 + 5120 * 128 + 128 * 128 * 5120
    indexer = 1024 * 64 * 128 + 5120 * 128 + 2 * 128 + 5120 * 64
    window = 5120 * 1024 + 1024 + 1024 * 64 * 256 + 5120 * 1088 + 1024 + 1024 * 64 * 320 + 5120 * 64 + 64 * 128 * 5120
    assert cost.latent_params(cfg, "full_attention") == full and cost.indexer_params(cfg) == indexer
    assert cost.attention_params(cfg, "full_attention") == full + indexer and 144.0e6 < full + indexer < 144.2e6
    assert cost.attention_params(cfg, "sliding_attention") == window and 90.7e6 < window < 90.9e6


def test_the_share_a_chip_holds_by_hand(cfg):
    expert = 3 * 5120 * 1536
    assert cost.expert_params(cfg) == expert == 23_592_960
    sparse = 5120 * 256 + 256 + 33 * expert
    stack = 2 * cost.attention_params(cfg, "full_attention") + 3 * cost.attention_params(cfg, "sliding_attention") + 3 * 5120 * 13824 + 4 * sparse + 5 * 2 * 5120 + 5120
    assert cost.stack_params(cfg, 32) == stack
    assert cost.held_params(cfg) == stack + 2 * 19008 * 5120 == 4_087_154_176


def test_the_three_cache_kinds_by_hand(cfg):
    assert cost.latent_row_bytes(cfg) == 1152 and cost.index_key_bytes(cfg) == 256
    assert cost.latent_row_bytes(cfg, "sliding_attention") == 2176
    assert cost.cache_bytes(cfg, 4, 33024, 544) == 2 * 4 * 33024 * 1408 + 3 * 4 * 544 * 2176


def test_the_mechanism_is_counted_by_its_definition(cfg):
    n = 32768
    assert cost.causal_pairs(n) == n * (n + 1) / 2
    assert cost.kept_pairs(n, 2048) == 2048 * 2049 / 2 + (n - 2048) * 2048
    assert cost.kept_pairs(100, 2048) == cost.causal_pairs(100)  # a short row keeps every key
    assert cost.index_score_cost(cfg, 4, n)["flops"] == 2 * 64 * 128 * 4 * cost.causal_pairs(n)
    assert cost.sparse_attend_cost(cfg, 4, n)["flops"] == 2 * 128 * 320 * 4 * cost.kept_pairs(n, 2048)
    assert cost.window_attend_cost(cfg, 4, n)["flops"] == 2 * 64 * 384 * 4 * cost.kept_pairs(n, 513)
    assert cost.selections(cfg, 4, n) == 4 * (n - 2048) and cost.selections(cfg, 4, 100) == 0
    # about half of the pass's operations are the mechanism's by the issue's count; by the definition a third
    whole = cost.prefill_flops(cfg, 4, n)
    mechanism = 2 * (cost.index_score_cost(cfg, 4, n)["flops"] + cost.sparse_attend_cost(cfg, 4, n)["flops"])
    assert 0.25 < mechanism / whole < 0.35 and 3.5e14 < whole < 4.0e14


def test_a_step_reads_the_index_keys_and_the_selected_rows(cfg):
    assert cost.dsa_step_bytes(cfg, 4, 32768) == 4 * (32768 * 256 + 2048 * 1152)
    assert cost.dsa_step_bytes(cfg, 1, 1000) == 1000 * 256 + 1000 * 1152  # a short context: every row
    assert cost.ring_step_bytes(cfg, 4, 32768) == 4 * 513 * 2176
    parts = cost.decode_step_parts(cfg, 4, 32768, cost.experts_hit(cfg, 4))
    assert set(parts) == {"experts", "other_weights", "index_and_selected", "rings"}
    assert parts["index_and_selected"] == 2 * cost.dsa_step_bytes(cfg, 4, 32768) and parts["rings"] == 3 * cost.ring_step_bytes(cfg, 4, 32768)
    assert cost.local_pairs_per_token(cfg) == 1.0 and 3.7 < cost.experts_hit(cfg, 4) < 4.0
    assert cost.decode_scan_bytes(cfg, 4, 32768, 256) == sum(cost.decode_step_bytes(cfg, 4, 32768 + j) for j in range(1, 256))


def test_the_expert_kernels_and_the_training_count(cfg):
    k = cost.expert_kernel_cost(cfg, 4 * 32768)
    assert k["flops"] == 2 * 131072 * 23_592_960 and k["bytes"] == 2 * (32 * 23_592_960 + 131072 * (2 * 5120 + 3 * 1536))
    assert cost.train_flops(cfg, 1, 4096) > 3 * cost.prefill_flops(cfg, 1, 4096)
