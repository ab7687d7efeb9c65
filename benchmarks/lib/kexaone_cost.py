"""Parameters, bytes and operations of the K-EXAONE share a chip holds (one
of eight that share each layer: a dense layer, sparse layers with a share of
the experts and a shared one, window and full grouped-query attention, and the
multi-token-prediction module), from the configuration's numbers alone: what
the algorithm needs, not what a program happens to execute. Imports nothing of
the program.

``cfg`` is the family's ``cfg`` dict (``families/exaone_moe.py``): the
published keys under the program's names (``n_routed_experts`` the router's
width, ``n_held_experts`` of them here). A product of (m, k) by (k, n) is
``2 m k n`` operations. Parameters are counted without the norms' scales and
the router's bias (under a thousandth of a percent). The module is one more
sparse block of its own layer type behind a ``2h -> h`` projection; it shares
the embedding and the head. The trace helpers are ``lib/dsv3_cost.py``'s."""

from __future__ import annotations

from typing import Dict, Tuple

from benchmarks.lib.mellum_cost import visible_pairs

SPEC_POSITIONS = 2  # a speculative step verifies a row's last emitted token and one draft


def attention_params(cfg: Dict) -> int:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return 2 * h * cfg["num_attention_heads"] * d + 2 * h * cfg["num_key_value_heads"] * d


def expert_params(cfg: Dict) -> int:
    """One routed expert; the shared expert is ``n_shared_experts`` of them wide."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: Dict) -> int:
    return cfg["hidden_size"] * cfg["n_routed_experts"]


def dense_layer_params(cfg: Dict) -> int:
    return attention_params(cfg) + 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def sparse_layer_params(cfg: Dict, experts: float = None) -> float:
    """A sparse layer with ``experts`` routed experts (default: those held)."""
    n = cfg["n_held_experts"] if experts is None else experts
    return attention_params(cfg) + router_params(cfg) + (cfg["n_shared_experts"] + n) * expert_params(cfg)


def module_params(cfg: Dict, experts: float = None) -> float:
    """The multi-token-prediction module: the ``2h -> h`` projection and one sparse block."""
    return cfg["num_nextn_predict_layers"] * (2 * cfg["hidden_size"] ** 2 + sparse_layer_params(cfg, experts))


def vocab_params(cfg: Dict) -> int:
    """Embedding and head over the rows held."""
    return 2 * cfg["vocab_size"] * cfg["hidden_size"]


def stack_params(cfg: Dict, experts: float = None) -> float:
    dense = cfg["first_k_dense_replace"]
    return dense * dense_layer_params(cfg) + (cfg["num_hidden_layers"] - dense) * sparse_layer_params(cfg, experts)


def held_params(cfg: Dict) -> float:
    return stack_params(cfg) + module_params(cfg) + vocab_params(cfg)


def kv_row_bytes(cfg: Dict, itemsize: int = 2) -> int:
    """A token's keys and values in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def cache_layers(cfg: Dict) -> Tuple[int, int]:
    """``(window, full)``: the stack's layers and the module's block, each with a cache of its kind."""
    kinds = tuple(cfg["layer_types"]) + tuple(cfg["mtp_layer_types"]) * cfg["num_nextn_predict_layers"]
    window = sum(t == "sliding_attention" for t in kinds)
    return window, len(kinds) - window


def local_pairs_per_token(cfg: Dict) -> float:
    """Routed pairs a token sends to the held experts, under even routing."""
    return cfg["num_experts_per_tok"] * cfg["n_held_experts"] / cfg["n_routed_experts"]


def experts_hit(cfg: Dict, tokens: int) -> float:
    """Held experts of a layer that at least one of ``tokens`` tokens is routed to, under even routing."""
    miss = (1.0 - cfg["num_experts_per_tok"] / cfg["n_routed_experts"]) ** tokens
    return cfg["n_held_experts"] * (1.0 - miss)


# ------------------------------------------------------------------ decoding


def spec_step_bytes(cfg: Dict, batch: int, context: int, weight_itemsize: int = 2, cache_itemsize: int = 2) -> float:
    """The bytes one speculative step of ``batch`` rows has to read, each row
    with ``context`` cached tokens, whatever program runs it: two positions a
    row through the stack and through the module. Every weight the step's
    arithmetic needs once (of a layer's held experts those that at least one
    of the ``2 * batch`` positions is routed to under even routing; the head
    once for both the stack's logits and the module's; an embedding row for
    each token read), a full layer's cache at ``context`` tokens and a window
    layer's at ``min(context, window)``."""
    positions = SPEC_POSITIONS * batch
    hit = experts_hit(cfg, positions)
    embedded = positions * (1 + cfg["num_nextn_predict_layers"])  # the stack's tokens, and the module's next tokens
    weights = (stack_params(cfg, hit) + module_params(cfg, hit) + cfg["vocab_size"] * cfg["hidden_size"]
               + embedded * cfg["hidden_size"])
    window, full = cache_layers(cfg)
    cached = full * context + window * min(context, cfg["sliding_window"])
    return weights * weight_itemsize + batch * cached * kv_row_bytes(cfg, cache_itemsize)


def spec_scan_bytes(cfg: Dict, batch: int, prompt_len: int, new_tokens: int, **kw) -> float:
    """The bytes of the ``new_tokens - 1`` steps a call takes when no draft is
    accepted (each then yields one token): step ``j`` (1-based) finds
    ``prompt_len + j`` tokens in a growing cache. A call whose drafts are
    accepted takes fewer steps and reads less."""
    return sum(spec_step_bytes(cfg, batch, prompt_len + j, **kw) for j in range(1, new_tokens))


# -------------------------------------------------------------- prompt pass


def attention_flops(cfg: Dict, n: int, window: int = None) -> float:
    """Scores and values of one row in one layer over the visible pairs."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * visible_pairs(n, window)


def token_product_flops(cfg: Dict) -> float:
    """The matrix products one token passes on its way through the stack and
    the module (without attention's scores and values and without the head):
    the held experts count for the pairs routed to them, not for every token."""
    local = local_pairs_per_token(cfg)
    return 2.0 * (stack_params(cfg, local) + module_params(cfg, local))


def prefill_flops(cfg: Dict, batch: int, prompt_len: int) -> float:
    """Useful operations of one prompt pass, the module's included: every
    token through the products, attention over the visible pairs (a window
    layer's band only), and the head at the last position of each row, once
    for the first token and once for the first draft."""
    window, full = cache_layers(cfg)
    attention = full * attention_flops(cfg, prompt_len) + window * attention_flops(cfg, prompt_len, cfg["sliding_window"])
    heads = (1 + cfg["num_nextn_predict_layers"]) * 2.0 * batch * cfg["hidden_size"] * cfg["vocab_size"]
    return batch * prompt_len * token_product_flops(cfg) + batch * attention + heads


def train_flops(cfg: Dict, batch: int, seq_len: int) -> float:
    """Forward and backward (3x forward) of the held share with logits at
    every position. No cell trains this configuration; the harness asks every
    family for the count."""
    window, full = cache_layers(cfg)
    attention = full * attention_flops(cfg, seq_len) + window * attention_flops(cfg, seq_len, cfg["sliding_window"])
    heads = (1 + cfg["num_nextn_predict_layers"]) * 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return 3.0 * (batch * seq_len * (token_product_flops(cfg) + heads) + batch * attention)


# ----------------------------------------------------------------- kernels


def sparse_blocks(cfg: Dict) -> int:
    """Blocks with an expert layer: the stack's sparse layers and the module's."""
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] + cfg["num_nextn_predict_layers"]


def window_flash_cost(cfg: Dict, batch: int, n: int, itemsize: int = 2) -> Dict[str, float]:
    """One window layer's flash forward over ``batch`` rows of ``n`` tokens:
    the operations of the visible band alone, and the bytes of queries and
    output once and of each key-value head's keys and values once."""
    d = cfg["head_dim"]
    moved = batch * n * d * (2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]) * itemsize
    return {"flops": batch * attention_flops(cfg, n, cfg["sliding_window"]), "bytes": float(moved)}


def expert_kernel_cost(cfg: Dict, tokens: int, itemsize: int = 2) -> Dict[str, float]:
    """One layer's three grouped products on the pairs ``tokens`` tokens send
    to the held experts under even routing: operations, and the bytes of every
    held expert's weights once with the rows in and out of each product."""
    pairs = tokens * local_pairs_per_token(cfg)
    moved = cfg["n_held_experts"] * expert_params(cfg) + pairs * (2 * cfg["hidden_size"] + 3 * cfg["moe_intermediate_size"])
    return {"flops": 2.0 * pairs * expert_params(cfg), "bytes": float(moved * itemsize)}
