"""Parameters, bytes and operations of the Mellum 2 stage a chip holds, from
the configuration's numbers alone: what the algorithm needs, not what a
program happens to execute. Imports nothing of the program.

``cfg`` is the family's ``cfg`` dict (``families/mellum.py``): the published
keys under the program's names (``n_routed_experts`` the router's width, all
of them held). A product of (m, k) by (k, n) is ``2 m k n`` operations.
Parameters are counted without the norms' scales (under a thousandth of a
percent). The trace helpers are ``lib/dsv3_cost.py``'s."""

from __future__ import annotations

from typing import Dict

LANES = 128


def attention_params(cfg: Dict) -> int:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return 2 * h * cfg["num_attention_heads"] * d + 2 * h * cfg["num_key_value_heads"] * d


def expert_params(cfg: Dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: Dict) -> int:
    return cfg["hidden_size"] * cfg["n_routed_experts"]


def layer_params(cfg: Dict, experts: float = None) -> float:
    """A layer with ``experts`` experts (default: all of them)."""
    n = cfg["n_routed_experts"] if experts is None else experts
    return attention_params(cfg) + router_params(cfg) + n * expert_params(cfg)


def vocab_params(cfg: Dict) -> int:
    return 2 * cfg["vocab_size"] * cfg["hidden_size"]


def held_params(cfg: Dict) -> int:
    return cfg["num_hidden_layers"] * layer_params(cfg) + vocab_params(cfg)


def kv_row_bytes(cfg: Dict, itemsize: int = 2) -> int:
    """A token's keys and values in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def window_layers(cfg: Dict) -> int:
    return sum(t == "sliding_attention" for t in cfg["layer_types"])


def full_layers(cfg: Dict) -> int:
    return cfg["num_hidden_layers"] - window_layers(cfg)


def experts_hit(cfg: Dict, tokens: int) -> float:
    """Experts of a layer that at least one of ``tokens`` tokens is routed to, under even routing."""
    miss = (1.0 - cfg["num_experts_per_tok"] / cfg["n_routed_experts"]) ** tokens
    return cfg["n_routed_experts"] * (1.0 - miss)


# ------------------------------------------------------------------ decoding


def decode_step_bytes(cfg: Dict, batch: int, context: int, weight_itemsize: int = 2, cache_itemsize: int = 2) -> float:
    """The bytes one decode step of ``batch`` rows has to read, each row with
    ``context`` cached tokens, whatever program runs it: every weight the
    step's arithmetic needs (of a layer's experts those that at least one of
    the ``batch`` tokens is routed to under even routing; the embedding
    contributes ``batch`` rows, the head all of its own), a full layer's cache
    at ``context`` tokens and a window layer's at ``min(context, window)``."""
    per_layer = layer_params(cfg, experts_hit(cfg, batch))
    weights = cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * cfg["hidden_size"] + batch * cfg["hidden_size"]
    cached = full_layers(cfg) * context + window_layers(cfg) * min(context, cfg["sliding_window"])
    return weights * weight_itemsize + batch * cached * kv_row_bytes(cfg, cache_itemsize)


def decode_scan_bytes(cfg: Dict, batch: int, prompt_len: int, new_tokens: int, **kw) -> float:
    """The bytes the ``new_tokens - 1`` steps of one call read: step ``j``
    (1-based) finds ``prompt_len + j`` tokens in a growing cache."""
    return sum(decode_step_bytes(cfg, batch, prompt_len + j, **kw) for j in range(1, new_tokens))


# -------------------------------------------------------------- prompt pass


def visible_pairs(n: int, window: int = None) -> int:
    """(query, key) pairs of one row of ``n`` tokens: i sees ``j <= i`` and ``j > i - window``."""
    if window is None or window >= n:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


def attention_flops(cfg: Dict, n: int, window: int = None) -> float:
    """Scores and values of one row in one layer over the visible pairs."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * visible_pairs(n, window)


def token_product_flops(cfg: Dict) -> float:
    """The matrix products one token passes on its way through the stack
    (without attention's scores and values and without the head): the experts
    count for the ``num_experts_per_tok`` pairs a token is routed to."""
    return 2.0 * cfg["num_hidden_layers"] * layer_params(cfg, cfg["num_experts_per_tok"])


def prefill_flops(cfg: Dict, batch: int, prompt_len: int) -> float:
    """Useful operations of one prompt pass: every token through the stack,
    attention over the visible pairs (a window layer's band only), and the
    head at the last position of each row."""
    attention = (full_layers(cfg) * attention_flops(cfg, prompt_len)
                 + window_layers(cfg) * attention_flops(cfg, prompt_len, cfg["sliding_window"]))
    return (batch * prompt_len * token_product_flops(cfg) + batch * attention
            + 2.0 * batch * cfg["hidden_size"] * cfg["vocab_size"])


def train_flops(cfg: Dict, batch: int, seq_len: int) -> float:
    """Forward and backward (3x forward) with logits at every position. No
    cell trains this configuration; the harness asks every family for the count."""
    attention = (full_layers(cfg) * attention_flops(cfg, seq_len)
                 + window_layers(cfg) * attention_flops(cfg, seq_len, cfg["sliding_window"]))
    fwd = batch * seq_len * (token_product_flops(cfg) + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]) + batch * attention
    return 3.0 * fwd


# ----------------------------------------------------------------- kernels


def window_flash_cost(cfg: Dict, batch: int, n: int, itemsize: int = 2) -> Dict[str, float]:
    """One window layer's flash forward over ``batch`` rows of ``n`` tokens:
    the operations of the visible band alone, and the bytes of queries and
    output once and of each key-value head's keys and values once."""
    d = cfg["head_dim"]
    moved = batch * n * d * (2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]) * itemsize
    return {"flops": batch * attention_flops(cfg, n, cfg["sliding_window"]), "bytes": float(moved)}


def expert_kernel_cost(cfg: Dict, tokens: int, itemsize: int = 2) -> Dict[str, float]:
    """One layer's three grouped products on the ``num_experts_per_tok`` pairs
    a token: operations, and the bytes of every expert's weights once with the
    rows in and out of each product."""
    pairs = tokens * cfg["num_experts_per_tok"]
    moved = cfg["n_routed_experts"] * expert_params(cfg) + pairs * (2 * cfg["hidden_size"] + 3 * cfg["moe_intermediate_size"])
    return {"flops": 2.0 * pairs * expert_params(cfg), "bytes": float(moved * itemsize)}
