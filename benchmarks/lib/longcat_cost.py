"""Parameters, bytes and operations of the LongCat-Flash share a chip holds
(one of 32 that share each layer: two latent attentions, two dense
feed-forwards, the router and the identity experts whole, 16 of the 512 experts
that have weights), from the configuration's numbers alone: what the algorithm
needs, not what a program happens to execute. Imports nothing of the program.

``cfg`` is the family's ``cfg`` dict (``families/longcat_flash.py``): the
published sizes under the program's names, ``n_routed_experts`` the experts
that have weights, ``zero_expert_num`` the identity experts after them in the
router's outputs, ``n_held_experts`` the experts here. A product of (m, k) by
(k, n) is ``2 m k n`` operations. Parameters are counted without the norms'
scales and the router's bias (under a thousandth of a percent); an identity
pair costs no product and no weight, and its ``w * x`` is not counted. The
latent attention's counts and the trace helpers are ``lib/dsv3_cost.py``'s."""

from __future__ import annotations

from typing import Dict

from benchmarks.lib.dsv3_cost import attention_flops, expert_params, latent_row_bytes, mla_params

ATTENTIONS = DENSE_MLPS = 2  # a layer's sublayers in series, beside its one expert branch


def router_width(cfg: Dict) -> int:
    """The router's outputs: the experts with weights, then the identity experts."""
    return cfg["n_routed_experts"] + cfg["zero_expert_num"]


def dense_mlp_params(cfg: Dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg: Dict) -> int:
    return cfg["hidden_size"] * router_width(cfg)


def layer_params(cfg: Dict, experts: float = None) -> float:
    """A shortcut-connected layer with ``experts`` routed experts (default: those held)."""
    n = cfg["n_held_experts"] if experts is None else experts
    return ATTENTIONS * mla_params(cfg) + DENSE_MLPS * dense_mlp_params(cfg) + router_params(cfg) + n * expert_params(cfg)


def vocab_params(cfg: Dict) -> int:
    """Embedding and head over the rows held."""
    return 2 * cfg["vocab_size"] * cfg["hidden_size"]


def held_params(cfg: Dict) -> float:
    return cfg["num_hidden_layers"] * layer_params(cfg) + vocab_params(cfg)


def cache_count(cfg: Dict) -> int:
    """Latent caches in the generator's state: one an attention."""
    return ATTENTIONS * cfg["num_hidden_layers"]


def local_pairs_per_token(cfg: Dict) -> float:
    """Routed pairs a token sends to the held experts, under even routing over every output."""
    return cfg["num_experts_per_tok"] * cfg["n_held_experts"] / router_width(cfg)


def real_experts_per_token(cfg: Dict) -> float:
    """Experts with weights a token runs in the whole pool, under even routing (0 to ``num_experts_per_tok``)."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / router_width(cfg)


def experts_hit_share(cfg: Dict, tokens: int) -> float:
    """The share of a layer's held experts that at least one of ``tokens`` tokens is routed to, under even routing."""
    return 1.0 - (1.0 - cfg["num_experts_per_tok"] / router_width(cfg)) ** tokens


# ------------------------------------------------------------------ decoding


def decode_step_bytes(cfg: Dict, batch: int, context: int, weight_itemsize: int = 2, cache_itemsize: int = 2) -> float:
    """The bytes one decode step of ``batch`` rows has to read, each row with
    ``context`` cached tokens, whatever program runs it: every weight the
    step's arithmetic needs once (of a layer's held experts those that at
    least one of the ``batch`` tokens is routed to under even routing, not all
    of them; the head whole, an embedding row a token) and each of the caches
    once. A program that pushes every token through every held expert reads
    more than this and is held to the same count."""
    hit = cfg["n_held_experts"] * experts_hit_share(cfg, batch)
    weights = cfg["num_hidden_layers"] * layer_params(cfg, hit) + cfg["vocab_size"] * cfg["hidden_size"] + batch * cfg["hidden_size"]
    cache = batch * context * latent_row_bytes(cfg, cache_itemsize) * cache_count(cfg)
    return weights * weight_itemsize + cache


def decode_scan_bytes(cfg: Dict, batch: int, prompt_len: int, new_tokens: int, **kw) -> float:
    """The bytes the ``new_tokens - 1`` steps of one call read: step ``j`` (1-based) finds ``prompt_len + j`` tokens in each cache."""
    return sum(decode_step_bytes(cfg, batch, prompt_len + j, **kw) for j in range(1, new_tokens))


# -------------------------------------------------------------- prompt pass


def token_product_flops(cfg: Dict) -> float:
    """The matrix products one token passes on its way through the stack
    (without attention's scores and values and without the head): the held
    experts count for the pairs routed to them, not for every token."""
    return 2.0 * cfg["num_hidden_layers"] * layer_params(cfg, local_pairs_per_token(cfg))


def prefill_flops(cfg: Dict, batch: int, prompt_len: int) -> float:
    """Useful operations of one prompt pass: every token through the stack,
    both attentions of every layer over the visible pairs, and the head at the
    last position of each row (the only logits the generator reads)."""
    return (batch * prompt_len * token_product_flops(cfg) + batch * cache_count(cfg) * attention_flops(cfg, prompt_len)
            + 2.0 * batch * cfg["hidden_size"] * cfg["vocab_size"])


def train_flops(cfg: Dict, batch: int, seq_len: int) -> float:
    """Forward and backward (3x forward) of the held share with logits at
    every position. No cell trains this configuration; the harness asks every
    family for the count."""
    fwd = (batch * seq_len * (token_product_flops(cfg) + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
           + batch * cache_count(cfg) * attention_flops(cfg, seq_len))
    return 3.0 * fwd


# ----------------------------------------------------------------- kernels


def expert_kernel_cost(cfg: Dict, tokens: int, itemsize: int = 2) -> Dict[str, float]:
    """One layer's three grouped products on the pairs ``tokens`` tokens send
    to the held experts under even routing: operations, and the bytes of every
    held expert's weights once with the rows in and out of each product."""
    pairs = tokens * local_pairs_per_token(cfg)
    moved = cfg["n_held_experts"] * expert_params(cfg) + pairs * (2 * cfg["hidden_size"] + 3 * cfg["moe_intermediate_size"])
    return {"flops": 2.0 * pairs * expert_params(cfg), "bytes": float(moved * itemsize)}
