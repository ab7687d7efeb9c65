"""Parameters, bytes and operations of the dots3-note share a chip holds, from
the configuration's numbers alone: what the algorithm needs **by its
definition**, not what a program happens to execute, its tiling or which
implementation runs: the indexer's scores are ``2 * index_n_heads *
index_head_dim`` operations a causal pair, the attention of a full layer is
over ``min(t + 1, index_topk)`` keys a query (a program that runs the dense
rectangle under a mask does more and is held to this count), a window layer's
over ``min(t + 1, sliding_window_size)``. Imports nothing of the program.

``cfg`` is the family's ``cfg`` dict (``families/dots3.py``): the published
sizes under the program's names, ``layer_types`` an entry a held layer
(``"full_attention"`` or ``"sliding_attention"``), ``n_routed_experts`` the
router's width and ``n_held_experts`` the experts held here. A product of (m,
k) by (k, n) is ``2 m k n`` operations. The parameters are counted whole, norms
and biases too: they reproduce ``jax.eval_shape`` of the program to the last
one. The trace helpers are ``lib/dsv3_cost.py``'s."""

from __future__ import annotations

from typing import Dict

NORMS_A_LAYER = 2  # before the attention, before the feed-forward


def sizes(cfg: Dict, kind: str) -> Dict:
    """The latent attention's sizes of a layer of ``kind``."""
    pre = "swa_" if kind == "sliding_attention" else ""
    keys = ("q_lora_rank", "kv_lora_rank", "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")
    return {key: cfg[pre + key] for key in keys}


def latent_params(cfg: Dict, kind: str) -> int:
    """A latent attention whole: ``W_dq``, the query latent's norm, ``W_uq``, ``W_dkv``, the key-value latent's norm,
    ``W_ukv``, the head-wise gate, ``W_o``."""
    s, h = sizes(cfg, kind), cfg["hidden_size"]
    heads, nope, rope, dv = s["num_attention_heads"], s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    q_rank, rank = s["q_lora_rank"], s["kv_lora_rank"]
    return (h * q_rank + q_rank + q_rank * heads * (nope + rope) + h * (rank + rope) + rank + rank * heads * (nope + dv)
            + h * heads + heads * dv * h)


def indexer_params(cfg: Dict) -> int:
    """A full layer's indexer: ``W^I_q`` off the query latent, ``W^I_k`` with its LayerNorm's scale and bias, ``W^I_w``."""
    h, heads, d = cfg["hidden_size"], cfg["index_n_heads"], cfg["index_head_dim"]
    return cfg["q_lora_rank"] * heads * d + h * d + 2 * d + h * heads


def attention_params(cfg: Dict, kind: str) -> int:
    return latent_params(cfg, kind) + (indexer_params(cfg) if kind == "full_attention" else 0)


def expert_params(cfg: Dict) -> int:
    """One routed expert; the shared expert is ``n_shared_experts`` of them wide."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_mlp_params(cfg: Dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg: Dict) -> int:
    """The router at its published width, with its bias."""
    return cfg["hidden_size"] * cfg["n_routed_experts"] + cfg["n_routed_experts"]


def sparse_ffn_params(cfg: Dict, experts: float) -> float:
    return router_params(cfg) + (cfg["n_shared_experts"] + experts) * expert_params(cfg)


def table_params(cfg: Dict) -> int:
    """The embedding over the rows held, and as much again for the untied head."""
    return cfg["vocab_size"] * cfg["hidden_size"]


def sparse_layers(cfg: Dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def full_layers(cfg: Dict) -> int:
    return list(cfg["layer_types"]).count("full_attention")


def window_layers(cfg: Dict) -> int:
    return list(cfg["layer_types"]).count("sliding_attention")


def stack_params(cfg: Dict, experts: float) -> float:
    """Every layer (attention, two norms, feed-forward with ``experts`` routed experts a sparse layer) and the last norm."""
    attentions = sum(attention_params(cfg, kind) for kind in cfg["layer_types"])
    ffn = cfg["first_k_dense_replace"] * dense_mlp_params(cfg) + sparse_layers(cfg) * sparse_ffn_params(cfg, experts)
    return attentions + ffn + cfg["num_hidden_layers"] * NORMS_A_LAYER * cfg["hidden_size"] + cfg["hidden_size"]


def held_params(cfg: Dict) -> int:
    return int(stack_params(cfg, cfg["n_held_experts"])) + 2 * table_params(cfg)


# ------------------------------------------------------------------ the caches


def latent_row_bytes(cfg: Dict, kind: str = "full_attention", itemsize: int = 2) -> int:
    """A joint row: the key-value latent with the rotary key."""
    s = sizes(cfg, kind)
    return (s["kv_lora_rank"] + s["qk_rope_head_dim"]) * itemsize


def index_key_bytes(cfg: Dict, itemsize: int = 2) -> int:
    return cfg["index_head_dim"] * itemsize


def cache_bytes(cfg: Dict, batch: int, capacity: int, ring_slots: int, itemsize: int = 2) -> int:
    """The generator's three cache kinds: latent rows and index keys that grow, rings of ``ring_slots`` latent rows."""
    grows = full_layers(cfg) * batch * capacity * (latent_row_bytes(cfg, itemsize=itemsize) + index_key_bytes(cfg, itemsize))
    return grows + window_layers(cfg) * batch * ring_slots * latent_row_bytes(cfg, "sliding_attention", itemsize)


# ------------------------------------------------------------------ the mechanism, by its definition


def causal_pairs(n: int) -> float:
    return n * (n + 1) / 2


def kept_pairs(n: int, keep: int) -> float:
    """``sum_t min(t + 1, keep)`` over a row of ``n`` queries."""
    full = min(n, keep)
    return full * (full + 1) / 2 + (n - full) * keep


def index_score_cost(cfg: Dict, rows: int, n: int, itemsize: int = 2) -> Dict[str, float]:
    """One full layer's index scores over ``rows`` rows of ``n`` tokens: ``2 * heads * head_dim`` operations a causal
    pair; the indexer's queries and keys and the head weights (float32) read once."""
    heads, d = cfg["index_n_heads"], cfg["index_head_dim"]
    return {"flops": 2.0 * heads * d * rows * causal_pairs(n),
            "bytes": float(rows * n * (heads * d * itemsize + d * itemsize + heads * 4))}


def selections(cfg: Dict, rows: int, n: int) -> int:
    """Top-``index_topk`` selections of one full layer's pass: the queries with more than ``index_topk`` keys before them."""
    return rows * max(n - cfg["index_topk"], 0)


def sparse_attend_cost(cfg: Dict, rows: int, n: int, itemsize: int = 2) -> Dict[str, float]:
    """One full layer's expanded attention over the selected keys: scores and values of ``min(t + 1, index_topk)`` keys
    a query a head; the expanded queries, keys and values read and the output written once."""
    s = sizes(cfg, "full_attention")
    heads, nope, rope, dv = s["num_attention_heads"], s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    moved = rows * n * (heads * (nope + rope) + heads * (nope + dv) + rope + heads * dv) * itemsize
    return {"flops": 2.0 * heads * (nope + rope + dv) * rows * kept_pairs(n, cfg["index_topk"]), "bytes": float(moved)}


def window_attend_cost(cfg: Dict, rows: int, n: int, itemsize: int = 2) -> Dict[str, float]:
    """One window layer's expanded attention: ``min(t + 1, sliding_window_size)`` keys a query a head."""
    s = sizes(cfg, "sliding_attention")
    heads, nope, rope, dv = s["num_attention_heads"], s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    moved = rows * n * (heads * (nope + rope) + heads * (nope + dv) + rope + heads * dv) * itemsize
    return {"flops": 2.0 * heads * (nope + rope + dv) * rows * kept_pairs(n, cfg["sliding_window_size"]), "bytes": float(moved)}


def dsa_step_bytes(cfg: Dict, batch: int, context: int, itemsize: int = 2) -> float:
    """What a step of one full layer must read of its caches: every index key of the context, and the latent rows of the
    ``min(context, index_topk)`` keys it selects."""
    return float(batch * (context * index_key_bytes(cfg, itemsize) + min(context, cfg["index_topk"]) * latent_row_bytes(cfg, itemsize=itemsize)))


def ring_step_bytes(cfg: Dict, batch: int, context: int, itemsize: int = 2) -> float:
    """What a step of one window layer must read of its ring: the rows its window keeps."""
    return float(batch * min(context, cfg["sliding_window_size"]) * latent_row_bytes(cfg, "sliding_attention", itemsize))


# ------------------------------------------------------------------ decoding


def local_pairs_per_token(cfg: Dict) -> float:
    """Routed pairs a token sends to the held experts, under even routing."""
    return cfg["num_experts_per_tok"] * cfg["n_held_experts"] / cfg["n_routed_experts"]


def experts_hit(cfg: Dict, tokens: int) -> float:
    """Held experts of a layer that at least one of ``tokens`` tokens is routed to, under even routing."""
    miss = (1.0 - cfg["num_experts_per_tok"] / cfg["n_routed_experts"]) ** tokens
    return cfg["n_held_experts"] * (1.0 - miss)


def decode_step_parts(cfg: Dict, batch: int, context: int, experts: float, weight_itemsize: int = 2, cache_itemsize: int = 2) -> Dict[str, float]:
    """The bytes one decode step of ``batch`` rows moves with ``experts`` routed experts read a sparse layer, by part:
    the experts' weights, every other weight of the stack with the head (of the embedding a step reads ``batch``
    rows), a full layer's index keys and selected latent rows, a window layer's ring."""
    routed = sparse_layers(cfg) * experts * expert_params(cfg) * weight_itemsize
    other = (stack_params(cfg, 0) + table_params(cfg) + batch * cfg["hidden_size"]) * weight_itemsize
    return {"experts": float(routed), "other_weights": float(other),
            "index_and_selected": full_layers(cfg) * dsa_step_bytes(cfg, batch, context, cache_itemsize),
            "rings": window_layers(cfg) * ring_step_bytes(cfg, batch, context, cache_itemsize)}


def decode_step_bytes(cfg: Dict, batch: int, context: int, **kw) -> float:
    """The bytes one decode step has to move, whatever program runs it: of a layer's held experts those that at least
    one of the ``batch`` tokens is routed to (:func:`experts_hit` under even routing), not all of them."""
    return sum(decode_step_parts(cfg, batch, context, experts_hit(cfg, batch), **kw).values())


def decode_scan_bytes(cfg: Dict, batch: int, prompt_len: int, new_tokens: int, **kw) -> float:
    """The bytes the ``new_tokens - 1`` steps of one call move: step ``j`` (1-based) finds ``prompt_len + j`` tokens in the caches."""
    return sum(decode_step_bytes(cfg, batch, prompt_len + j, **kw) for j in range(1, new_tokens))


# -------------------------------------------------------------- prompt pass


def token_product_flops(cfg: Dict) -> float:
    """The dense matrix products one token passes on its way through the stack (without the index scores, attention's
    scores and values, and the head): the held experts count for the pairs routed to them, not for every token. The
    norms' scales and biases are no products and are left out."""
    h = cfg["hidden_size"]

    def latent(kind):
        s = sizes(cfg, kind)
        heads, nope, rope, dv = s["num_attention_heads"], s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
        return (h * s["q_lora_rank"] + s["q_lora_rank"] * heads * (nope + rope) + h * (s["kv_lora_rank"] + rope)
                + s["kv_lora_rank"] * heads * (nope + dv) + h * heads + heads * dv * h)

    indexer = cfg["q_lora_rank"] * cfg["index_n_heads"] * cfg["index_head_dim"] + h * cfg["index_head_dim"] + h * cfg["index_n_heads"]
    attentions = sum(latent(kind) + (indexer if kind == "full_attention" else 0) for kind in cfg["layer_types"])
    sparse = h * cfg["n_routed_experts"] + (cfg["n_shared_experts"] + local_pairs_per_token(cfg)) * expert_params(cfg)
    ffn = cfg["first_k_dense_replace"] * dense_mlp_params(cfg) + sparse_layers(cfg) * sparse
    return 2.0 * (attentions + ffn)


def prefill_flops(cfg: Dict, batch: int, prompt_len: int) -> float:
    """Useful matrix-unit operations of one prompt pass: every token through the stack's dense products, every full
    layer's index scores over the causal pairs and its attention over the selected keys, every window layer's
    attention over its window, and the head at the last position of each row (the only logits the generator reads)."""
    return (batch * prompt_len * token_product_flops(cfg)
            + full_layers(cfg) * (index_score_cost(cfg, batch, prompt_len)["flops"] + sparse_attend_cost(cfg, batch, prompt_len)["flops"])
            + window_layers(cfg) * window_attend_cost(cfg, batch, prompt_len)["flops"]
            + 2.0 * batch * cfg["hidden_size"] * cfg["vocab_size"])


def expert_kernel_cost(cfg: Dict, tokens: int, itemsize: int = 2) -> Dict[str, float]:
    """One sparse layer's three grouped products on the pairs ``tokens`` tokens send to the held experts: operations,
    and the bytes of every held expert's weights once with the rows in and out of each product
    (``lib/mellum_cost.py::expert_kernel_cost``, for a share of the experts)."""
    pairs = tokens * local_pairs_per_token(cfg)
    moved = cfg["n_held_experts"] * expert_params(cfg) + pairs * (2 * cfg["hidden_size"] + 3 * cfg["moe_intermediate_size"])
    return {"flops": 2.0 * pairs * expert_params(cfg), "bytes": float(moved * itemsize)}


def train_flops(cfg: Dict, batch: int, seq_len: int) -> float:
    """Forward and backward (3x forward) with logits at every position. No cell trains this configuration; the harness
    asks every family for the count."""
    fwd = prefill_flops(cfg, batch, seq_len) + 2.0 * batch * (seq_len - 1) * cfg["hidden_size"] * cfg["vocab_size"]
    return 3.0 * fwd
