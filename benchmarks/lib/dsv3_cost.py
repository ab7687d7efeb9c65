"""Parameters, bytes and operations of the DeepSeek-V3 share a chip holds,
from the configuration's numbers alone: what the algorithm needs, not what a
program happens to execute. Imports nothing of the program.

``cfg`` is the family's ``cfg`` dict (``families/deepseek_v3.py``): the
published keys, with ``n_routed_experts`` the router's width and
``n_held_experts`` the experts held here. A product of (m, k) by (k, n) is
``2 m k n`` operations. Parameters are counted without the norms' scales and
the router's bias (under a thousandth of a percent)."""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmarks.lib import trace

def mla_params(cfg: Dict) -> int:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return (h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * heads * (nope + rope) + h * (cfg["kv_lora_rank"] + rope)
            + cfg["kv_lora_rank"] * heads * (nope + dv) + heads * dv * h)


def expert_params(cfg: Dict) -> int:
    """One routed expert; a shared expert is ``n_shared_experts`` of them wide."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_mlp_params(cfg: Dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg: Dict) -> int:
    return cfg["hidden_size"] * cfg["n_routed_experts"]


def dense_layer_params(cfg: Dict) -> int:
    return mla_params(cfg) + dense_mlp_params(cfg)


def expert_layer_params(cfg: Dict, experts: Optional[int] = None) -> int:
    """An expert layer with ``experts`` routed experts (default: those held)."""
    n = cfg["n_held_experts"] if experts is None else experts
    return mla_params(cfg) + cfg["n_shared_experts"] * expert_params(cfg) + router_params(cfg) + n * expert_params(cfg)


def vocab_params(cfg: Dict) -> int:
    """Embedding and head over the rows held."""
    return 2 * cfg["vocab_size"] * cfg["hidden_size"]


def held_params(cfg: Dict) -> int:
    dense = cfg["first_k_dense_replace"]
    return dense * dense_layer_params(cfg) + (cfg["num_hidden_layers"] - dense) * expert_layer_params(cfg) + vocab_params(cfg)


def latent_row_bytes(cfg: Dict, itemsize: int = 2) -> int:
    """The cache's bytes a token a layer."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def local_pairs_per_token(cfg: Dict) -> float:
    """Routed pairs a token sends to the held experts, under even routing."""
    return cfg["num_experts_per_tok"] * cfg["n_held_experts"] / cfg["n_routed_experts"]


def experts_hit(cfg: Dict, tokens: int) -> float:
    """Held experts of a layer that at least one of ``tokens`` tokens is routed to, under even routing."""
    miss = (1.0 - cfg["num_experts_per_tok"] / cfg["n_routed_experts"]) ** tokens
    return cfg["n_held_experts"] * (1.0 - miss)


# ------------------------------------------------------------------ decoding


def decode_step_bytes(cfg: Dict, batch: int, context: int, weight_itemsize: int = 2, cache_itemsize: int = 2) -> float:
    """The bytes one decode step of ``batch`` rows has to read, each row with
    ``context`` cached tokens, whatever program runs it: every weight the
    step's arithmetic needs (of a layer's held experts those that at least
    one of the ``batch`` tokens is routed to, ``experts_hit`` under even
    routing, not all of them; the embedding contributes ``batch`` rows, the
    head all of its own), and each layer's cache once. A program that pushes
    every token through every held expert reads more than this and is held
    to the same count."""
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    per_expert_layer = expert_layer_params(cfg, 0) + experts_hit(cfg, batch) * expert_params(cfg)
    weights = dense * dense_layer_params(cfg) + (layers - dense) * per_expert_layer
    weights += cfg["vocab_size"] * cfg["hidden_size"] + batch * cfg["hidden_size"]
    cache = batch * context * latent_row_bytes(cfg, cache_itemsize) * layers
    return weights * weight_itemsize + cache


def decode_scan_bytes(cfg: Dict, batch: int, prompt_len: int, new_tokens: int, **kw) -> float:
    """The bytes the ``new_tokens - 1`` steps of one call read: step ``j``
    (1-based) finds ``prompt_len + j`` tokens in the cache."""
    return sum(decode_step_bytes(cfg, batch, prompt_len + j, **kw) for j in range(1, new_tokens))


def absorbed_attention_cost(cfg: Dict, batch: int, context: int, cache_itemsize: int = 2) -> Dict[str, float]:
    """One layer's absorbed attention of one step over the cache alone
    (scores and values; the projections around it are weights): operations
    and the bytes of the rows read once."""
    heads, width, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"], cfg["kv_lora_rank"]
    return {"flops": 2.0 * batch * heads * context * (width + rank),
            "bytes": float(batch * context * width * cache_itemsize)}


# -------------------------------------------------------------- prompt pass


def attention_flops(cfg: Dict, n: int) -> float:
    """Causal expanded attention of one row of ``n`` tokens in one layer:
    scores and values over the visible pairs (i sees 0..i)."""
    pairs = n * (n + 1) / 2
    return 2.0 * cfg["num_attention_heads"] * pairs * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])


def token_product_flops(cfg: Dict) -> float:
    """The matrix products one token passes on its way through the stack
    (without attention's scores and values and without the head): the held
    experts count for the pairs routed to them, not for every token."""
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    expert_layer = expert_layer_params(cfg, 0) + local_pairs_per_token(cfg) * expert_params(cfg)
    return 2.0 * (dense * dense_layer_params(cfg) + (layers - dense) * expert_layer)


def prefill_flops(cfg: Dict, batch: int, prompt_len: int) -> float:
    """Useful operations of one prompt pass: every token through the stack,
    attention over the visible pairs, and the head at the last position of
    each row (the only logits the generator reads)."""
    tokens = batch * prompt_len
    return (tokens * token_product_flops(cfg) + batch * cfg["num_hidden_layers"] * attention_flops(cfg, prompt_len)
            + 2.0 * batch * cfg["hidden_size"] * cfg["vocab_size"])


def train_flops(cfg: Dict, batch: int, seq_len: int) -> float:
    """Forward and backward (3x forward) of the held share on ``batch`` rows
    of ``seq_len`` tokens, logits at every position. No cell trains this
    configuration (12 bytes a parameter do not fit the chip); the harness
    asks every family for the count."""
    tokens = batch * seq_len
    fwd = (tokens * (token_product_flops(cfg) + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
           + batch * cfg["num_hidden_layers"] * attention_flops(cfg, seq_len))
    return 3.0 * fwd


# ------------------------------------------------------- reading the trace


def first_plane(run: Dict) -> List:
    plane = sorted(run["trace"]["devices"])[0]
    return trace.clip(run["trace"]["devices"][plane], run["trace_window"])


def decode_while_ns(events: List) -> Optional[float]:
    """Device time of the decode scan: of the ``while`` instructions in the
    window, the one with the largest total time. The prompt pass runs loops
    of its own (a ``lax.map`` a layer over chunks), each a fraction of the
    scan's 255 steps; ``None`` where the window holds no ``while``."""
    totals = trace.totals_by_name([e for e in events if e[0].split(".")[0] == "while"])
    return max(totals.values()) if totals else None


def kernel_ns(events: List, name_holds: str) -> float:
    return sum(dur for name, _, dur in events if name_holds in name)


def roofline_seconds(flops: float, bytes_: float, peaks: Dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"], bytes_ / peaks["hbm_bytes_per_s"])
