"""Parameters, bytes and operations of the Ling-3.0-flash share a chip holds,
from the configuration's numbers alone: what the algorithm needs, not what a
program happens to execute. Imports nothing of the program.

``cfg`` is the family's ``cfg`` dict (``families/ling.py``): the published
sizes under the program's names, ``layer_types`` an entry a layer (``"kda"`` or
``"latent_attention"``), ``n_routed_experts`` the router's width and
``n_held_experts`` the experts held here. A product of (m, k) by (k, n) is ``2
m k n`` operations. The parameters are counted whole, norms and biases too:
they reproduce ``jax.eval_shape`` of the program to the last one. The trace
helpers are ``lib/dsv3_cost.py``'s."""

from __future__ import annotations

from typing import Dict

NORMS_A_LAYER = 2  # before the mixer, before the feed-forward
STATE_ITEMSIZE = 4  # S is float32 whatever the cache's dtype (the configuration's ``dtypes``)
CONVS = 3  # q, k and v each pass a causal convolution


def width(cfg: Dict) -> int:
    """The channels of q, of k and of v in a delta layer: every head's ``head_dim`` side by side."""
    return cfg["num_attention_heads"] * cfg["head_dim"]


def kda_params(cfg: Dict) -> int:
    """A delta layer's mixer whole: ``W_q``, ``W_k``, ``W_v``, ``W_f``, ``W_g``
    and ``W_o`` (each hidden x width), the three convolutions' taps, ``dt_bias``,
    ``A_log`` (one a head), ``W_b`` (hidden x heads) and the head norm's scale."""
    h, w, heads = cfg["hidden_size"], width(cfg), cfg["num_attention_heads"]
    return 6 * h * w + CONVS * cfg["short_conv_kernel_size"] * w + w + heads + h * heads + cfg["head_dim"]


def mla_params(cfg: Dict) -> int:
    """The latent attention without a query latent: ``W_uq`` from the hidden
    state, ``W_dkv``, the latent's norm, ``W_ukv``, the head-wise gate, ``W_o``."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv, rank = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    return h * heads * (nope + rope) + h * (rank + rope) + rank + rank * heads * (nope + dv) + h * heads + heads * dv * h


def mixer_params(cfg: Dict, kind: str) -> int:
    return kda_params(cfg) if kind == "kda" else mla_params(cfg)


def expert_params(cfg: Dict) -> int:
    """One routed expert; the shared expert is ``n_shared_experts`` of them wide."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_mlp_params(cfg: Dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg: Dict) -> int:
    """The router at its published width, with its bias."""
    return cfg["hidden_size"] * cfg["n_routed_experts"] + cfg["n_routed_experts"]


def sparse_ffn_params(cfg: Dict, experts: float) -> float:
    """An expert layer's feed-forward with ``experts`` routed experts: the router, the shared expert, the experts."""
    return router_params(cfg) + (cfg["n_shared_experts"] + experts) * expert_params(cfg)


def table_params(cfg: Dict) -> int:
    """The embedding over the rows held, and as much again for the untied head."""
    return cfg["vocab_size"] * cfg["hidden_size"]


def sparse_layers(cfg: Dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def stack_params(cfg: Dict, experts: float) -> float:
    """Every layer (mixer, two norms, feed-forward with ``experts`` routed experts a sparse layer) and the last norm."""
    mixers = sum(mixer_params(cfg, kind) for kind in cfg["layer_types"])
    ffn = cfg["first_k_dense_replace"] * dense_mlp_params(cfg) + sparse_layers(cfg) * sparse_ffn_params(cfg, experts)
    return mixers + ffn + cfg["num_hidden_layers"] * NORMS_A_LAYER * cfg["hidden_size"] + cfg["hidden_size"]


def held_params(cfg: Dict) -> int:
    return int(stack_params(cfg, cfg["n_held_experts"])) + 2 * table_params(cfg)


# ------------------------------------------------------------------ the state


def kda_layers(cfg: Dict) -> int:
    return list(cfg["layer_types"]).count("kda")


def latent_layers(cfg: Dict) -> int:
    return list(cfg["layer_types"]).count("latent_attention")


def state_row_bytes(cfg: Dict) -> int:
    """One row's ``S`` in one delta layer: ``head_dim x head_dim`` a head, float32."""
    return cfg["num_attention_heads"] * cfg["head_dim"] ** 2 * STATE_ITEMSIZE


def window_row_bytes(cfg: Dict, itemsize: int = 2) -> int:
    """One row's three convolution windows in one delta layer."""
    return CONVS * (cfg["short_conv_kernel_size"] - 1) * width(cfg) * itemsize


def state_bytes(cfg: Dict, batch: int) -> int:
    return kda_layers(cfg) * batch * state_row_bytes(cfg)


def latent_row_bytes(cfg: Dict, itemsize: int = 2) -> int:
    """The latent cache's bytes a token a layer."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


# ------------------------------------------------------------------ decoding


def local_pairs_per_token(cfg: Dict) -> float:
    """Routed pairs a token sends to the held experts, under even routing."""
    return cfg["num_experts_per_tok"] * cfg["n_held_experts"] / cfg["n_routed_experts"]


def experts_hit(cfg: Dict, tokens: int) -> float:
    """Held experts of a layer that at least one of ``tokens`` tokens is routed to, under even routing."""
    miss = (1.0 - cfg["num_experts_per_tok"] / cfg["n_routed_experts"]) ** tokens
    return cfg["n_held_experts"] * (1.0 - miss)


def decode_step_parts(cfg: Dict, batch: int, context: int, experts: float, weight_itemsize: int = 2, cache_itemsize: int = 2) -> Dict[str, float]:
    """The bytes one decode step of ``batch`` rows moves with ``experts``
    routed experts read a sparse layer, by part: the experts' weights, every
    other weight of the stack with the head (of the embedding a step reads
    ``batch`` rows), every delta layer's float32 state read **and written**
    once with its windows, and each latent layer's cache of ``context`` tokens
    read once."""
    routed = sparse_layers(cfg) * experts * expert_params(cfg) * weight_itemsize
    other = (stack_params(cfg, 0) + table_params(cfg) + batch * cfg["hidden_size"]) * weight_itemsize
    state = 2 * (state_bytes(cfg, batch) + kda_layers(cfg) * batch * window_row_bytes(cfg, cache_itemsize))
    cache = latent_layers(cfg) * batch * context * latent_row_bytes(cfg, cache_itemsize)
    return {"experts": float(routed), "other_weights": float(other), "state": float(state), "cache": float(cache)}


def decode_step_bytes(cfg: Dict, batch: int, context: int, **kw) -> float:
    """The bytes one decode step has to move, whatever program runs it: of a
    layer's held experts those that at least one of the ``batch`` tokens is
    routed to (:func:`experts_hit` under even routing), not all of them. A
    program that pushes every token through every held expert reads more than
    this and is held to the same count."""
    return sum(decode_step_parts(cfg, batch, context, experts_hit(cfg, batch), **kw).values())


def decode_scan_bytes(cfg: Dict, batch: int, prompt_len: int, new_tokens: int, **kw) -> float:
    """The bytes the ``new_tokens - 1`` steps of one call move: step ``j`` (1-based) finds ``prompt_len + j`` tokens in the latent cache."""
    return sum(decode_step_bytes(cfg, batch, prompt_len + j, **kw) for j in range(1, new_tokens))


# -------------------------------------------------------------- prompt pass


def kda_token_flops(cfg: Dict) -> float:
    """One token's matrix work in one delta layer's recurrence: what the state
    predicts for ``k`` (``S^T k``), the rank-one correction (``k u^T``) and the
    read (``S^T q``), ``2 D^2`` each a head: what a chunk of one token costs."""
    return 6.0 * cfg["num_attention_heads"] * cfg["head_dim"] ** 2


def chunk_cost(cfg: Dict, rows: int, length: int, itemsize: int = 2) -> Dict[str, float]:
    """The floor of one delta layer's prompt pass over ``rows`` rows of
    ``length`` tokens, **whatever chunk a program cuts them into**: the
    recurrence's matrix work (:func:`kda_token_flops` a token; the pairwise
    products and the triangular solve a program computes within a chunk are its
    own choice of shape and are not counted, so a longer chunk cannot read as
    more useful work), and the bytes of one read of q, k, v (at ``itemsize``),
    the log-decays and the steps (float32) and one write of ``y`` and of the
    rows' final state."""
    tokens = rows * length
    moved = tokens * (4 * width(cfg) * itemsize + (width(cfg) + cfg["num_attention_heads"]) * 4) + rows * state_row_bytes(cfg)
    return {"flops": tokens * kda_token_flops(cfg), "bytes": float(moved)}


def step_state_bytes(cfg: Dict, batch: int) -> float:
    """What the step's kernels have to move a step: every delta layer's state read and written once."""
    return 2.0 * state_bytes(cfg, batch)


def attention_flops(cfg: Dict, n: int) -> float:
    """Causal expanded latent attention of one row of ``n`` tokens in one layer: scores and values over the visible pairs."""
    pairs = n * (n + 1) / 2
    return 2.0 * cfg["num_attention_heads"] * pairs * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])


def token_product_flops(cfg: Dict) -> float:
    """The dense matrix products one token passes on its way through the stack
    (without the recurrence, attention's scores and values, and the head): the
    held experts count for the pairs routed to them, not for every token. The
    norms' scales, biases, taps and ``A_log`` are no products and are left out."""
    h, w, heads = cfg["hidden_size"], width(cfg), cfg["num_attention_heads"]
    nope, rope, dv, rank = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    kda = 6 * h * w + h * heads
    mla = h * heads * (nope + rope) + h * (rank + rope) + rank * heads * (nope + dv) + h * heads + heads * dv * h
    sparse = h * cfg["n_routed_experts"] + (cfg["n_shared_experts"] + local_pairs_per_token(cfg)) * expert_params(cfg)
    ffn = cfg["first_k_dense_replace"] * dense_mlp_params(cfg) + sparse_layers(cfg) * sparse
    return 2.0 * (kda_layers(cfg) * kda + latent_layers(cfg) * mla + ffn)


def prefill_flops(cfg: Dict, batch: int, prompt_len: int) -> float:
    """Useful matrix-unit operations of one prompt pass: every token through
    the stack's dense products, every delta layer's recurrence
    (:func:`chunk_cost`), the latent layers' attention over the visible pairs,
    and the head at the last position of each row (the only logits the
    generator reads)."""
    return (batch * prompt_len * token_product_flops(cfg)
            + kda_layers(cfg) * chunk_cost(cfg, batch, prompt_len)["flops"]
            + latent_layers(cfg) * batch * attention_flops(cfg, prompt_len)
            + 2.0 * batch * cfg["hidden_size"] * cfg["vocab_size"])


def expert_kernel_cost(cfg: Dict, tokens: int, itemsize: int = 2) -> Dict[str, float]:
    """One sparse layer's three grouped products on the pairs ``tokens`` tokens
    send to the held experts: operations, and the bytes of every held expert's
    weights once with the rows in and out of each product
    (``lib/mellum_cost.py::expert_kernel_cost``, for a share of the experts)."""
    pairs = tokens * local_pairs_per_token(cfg)
    moved = cfg["n_held_experts"] * expert_params(cfg) + pairs * (2 * cfg["hidden_size"] + 3 * cfg["moe_intermediate_size"])
    return {"flops": 2.0 * pairs * expert_params(cfg), "bytes": float(moved * itemsize)}


def train_flops(cfg: Dict, batch: int, seq_len: int) -> float:
    """Forward and backward (3x forward) with logits at every position. No
    cell trains this configuration; the harness asks every family for the count."""
    fwd = (batch * seq_len * (token_product_flops(cfg) + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
                              + kda_layers(cfg) * kda_token_flops(cfg))
           + latent_layers(cfg) * batch * attention_flops(cfg, seq_len))
    return 3.0 * fwd
