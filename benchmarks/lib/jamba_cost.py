"""Parameters, bytes and operations of Jamba2-3B held whole on one chip, from
the configuration's numbers alone: what the algorithm needs, not what a
program happens to execute. Imports nothing of the program.

``cfg`` is the family's ``cfg`` dict (``families/jamba.py``): the published
sizes under the program's names, ``layer_types`` an entry a layer (``"mamba"``
or ``"full_attention"``). A product of (m, k) by (k, n) is ``2 m k n``
operations. The parameters are counted whole, norms and biases too: they
reproduce ``jax.eval_shape`` of the program to the last one. The trace helpers
are ``lib/dsv3_cost.py``'s."""

from __future__ import annotations

from typing import Dict

NORMS_A_LAYER = 2  # before the mixer, before the feed-forward
STATE_ITEMSIZE = 4  # the SSM state is float32 whatever the cache's dtype (the configuration's ``dtypes``)


def d_inner(cfg: Dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def mamba_products(cfg: Dict) -> int:
    """The weights of a mixer's four matrix products: ``W_in``, ``W_x``, ``W_dt``, ``W_out``."""
    h, d, n, r = cfg["hidden_size"], d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    return h * 2 * d + d * (r + 2 * n) + r * d + d * h


def mamba_params(cfg: Dict) -> int:
    """A mixer whole: its products, the convolution with its bias, ``W_dt``'s
    bias, ``A_log``, ``D`` and the three inner norms."""
    d, n, r, k = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    return mamba_products(cfg) + k * d + d + d + n * d + d + (r + 2 * n)


def attention_params(cfg: Dict) -> int:
    h, heads, kv, hd = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return h * heads * hd + 2 * h * kv * hd + heads * hd * h


def mlp_params(cfg: Dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_params(cfg: Dict, kind: str) -> int:
    mixer = mamba_params(cfg) if kind == "mamba" else attention_params(cfg)
    return mixer + mlp_params(cfg) + NORMS_A_LAYER * cfg["hidden_size"]


def table_params(cfg: Dict) -> int:
    """The embedding table, which is the head too (``tie_word_embeddings``)."""
    return cfg["vocab_size"] * cfg["hidden_size"]


def held_params(cfg: Dict) -> int:
    return sum(layer_params(cfg, kind) for kind in cfg["layer_types"]) + table_params(cfg) + cfg["hidden_size"]


def n_layers(cfg: Dict, kind: str) -> int:
    return sum(1 for k in cfg["layer_types"] if k == kind)


# ------------------------------------------------------------------ the state


def ssm_state_bytes(cfg: Dict, batch: int) -> int:
    """The recurrent states of every Mamba layer, float32."""
    return n_layers(cfg, "mamba") * batch * cfg["mamba_d_state"] * d_inner(cfg) * STATE_ITEMSIZE


def conv_window_bytes(cfg: Dict, batch: int, itemsize: int = 2) -> int:
    return n_layers(cfg, "mamba") * batch * (cfg["mamba_d_conv"] - 1) * d_inner(cfg) * itemsize


def kv_row_bytes(cfg: Dict, itemsize: int = 2) -> int:
    """A token's keys and values in one attention layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


# ------------------------------------------------------------------ decoding


def decode_step_bytes(cfg: Dict, batch: int, context: int, weight_itemsize: int = 2, cache_itemsize: int = 2) -> float:
    """The bytes one decode step of ``batch`` rows has to move, each row with
    ``context`` tokens behind it, whatever program runs it: every weight once
    (the tied table once: the head reads it whole, and the rows a step embeds
    are among those), every Mamba layer's convolution window and state read
    **and written** once at the configuration's dtypes (a step replaces
    both), and the attention layers' two caches read once at the length the
    step finds (the row it appends is not counted)."""
    weights = held_params(cfg) * weight_itemsize
    state = 2 * (ssm_state_bytes(cfg, batch) + conv_window_bytes(cfg, batch, cache_itemsize))
    cache = n_layers(cfg, "full_attention") * batch * context * kv_row_bytes(cfg, cache_itemsize)
    return float(weights + state + cache)


def decode_scan_bytes(cfg: Dict, batch: int, prompt_len: int, new_tokens: int, **kw) -> float:
    """The bytes the ``new_tokens - 1`` steps of one call move: step ``j`` (1-based) finds ``prompt_len + j`` tokens in each cache."""
    return sum(decode_step_bytes(cfg, batch, prompt_len + j, **kw) for j in range(1, new_tokens))


# -------------------------------------------------------------- prompt pass


def attention_flops(cfg: Dict, n: int) -> float:
    """Scores and values of one row's causal attention in one layer: each of
    the ``n (n + 1) / 2`` visible pairs costs a dot product and an axpy of ``head_dim`` a query head."""
    return 2.0 * 2.0 * cfg["num_attention_heads"] * cfg["head_dim"] * n * (n + 1) / 2


def token_product_flops(cfg: Dict) -> float:
    """The matrix products one token passes on its way through the stack
    (without attention's scores and values, the scan and the head)."""
    per_kind = {"mamba": mamba_products(cfg), "full_attention": attention_params(cfg)}
    return 2.0 * sum(per_kind[kind] + mlp_params(cfg) for kind in cfg["layer_types"])


def prefill_flops(cfg: Dict, batch: int, prompt_len: int) -> float:
    """Useful **product** operations of one prompt pass: every token through
    the stack's products, the attention layers over the visible pairs, and the
    head at the last position of each row (the only logits the generator
    reads). The scans' elementwise work (:func:`scan_cost`) is not a product
    and is not counted here."""
    return (batch * prompt_len * token_product_flops(cfg)
            + batch * n_layers(cfg, "full_attention") * attention_flops(cfg, prompt_len)
            + 2.0 * batch * cfg["hidden_size"] * cfg["vocab_size"])


def train_flops(cfg: Dict, batch: int, seq_len: int) -> float:
    """Forward and backward (3x forward) with logits at every position, the
    scans' elementwise operations with the products'. No cell trains this
    configuration; the harness asks every family for the count."""
    fwd = (batch * seq_len * (token_product_flops(cfg) + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
           + batch * n_layers(cfg, "full_attention") * attention_flops(cfg, seq_len)
           + n_layers(cfg, "mamba") * scan_cost(cfg, batch, seq_len)["flops"])
    return 3.0 * fwd


# ----------------------------------------------------------------- kernels

SCAN_OPS_A_STATE = 7  # dt*A, exp, *h, dtx*B, +, h*C, + : a state element a token
SCAN_OPS_A_CHANNEL = 1  # dt*x


def scan_cost(cfg: Dict, rows: int, length: int, itemsize: int = 4) -> Dict[str, float]:
    """One layer's selective scan over ``rows`` rows of ``length`` tokens, the
    recurrence alone (the step size's softplus before it, the skip and the
    gate after it are no part of it): its elementwise operations
    (``SCAN_OPS_A_STATE`` a state element a token, ``SCAN_OPS_A_CHANNEL`` a
    channel a token; a transcendental counts one), and the bytes of one read
    of its inputs (``x`` and the step size a channel a token, ``B`` and ``C`` a
    state a token, ``A`` once) and one write of ``y`` and of the rows' final
    state, at ``itemsize`` (the kernel's streams are float32)."""
    d, n = d_inner(cfg), cfg["mamba_d_state"]
    tokens = rows * length
    flops = tokens * d * (SCAN_OPS_A_STATE * n + SCAN_OPS_A_CHANNEL)
    moved = tokens * (3 * d + 2 * n) + n * d + rows * n * d
    return {"flops": float(flops), "bytes": float(moved * itemsize)}

