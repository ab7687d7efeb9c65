"""Parameters, bytes and operations of one pipeline stage of Brumby-14B-Base,
from the configuration's numbers alone: what the algorithm needs, not what a
program happens to execute. Imports nothing of the program.

``cfg`` is the family's ``cfg`` dict (``families/brumby.py``): the published
sizes under the program's names, ``layer_types`` an entry a layer (all
``"power_retention"``). A product of (m, k) by (k, n) is ``2 m k n`` operations.
The parameters are counted whole, norms and biases too: they reproduce
``jax.eval_shape`` of the program to the last one. The feature map of degree 2
over a head of ``D`` has ``D (D + 1) / 2`` distinct features (8256 at 128): the
state is counted at that width whatever a program pads it to, and the prompt
pass's retention in the state form whatever chunk a program cuts a row into. The
trace helpers are ``lib/dsv3_cost.py``'s."""

from __future__ import annotations

from typing import Dict

NORMS_A_LAYER = 2  # before the mixer, before the feed-forward
STATE_ITEMSIZE = 4  # S and z are float32 whatever the cache's dtype (the configuration's ``dtypes``)


def feature_dim(cfg: Dict) -> int:
    """``D (D + 1) / 2``: the distinct products of a head's channels."""
    return cfg["head_dim"] * (cfg["head_dim"] + 1) // 2


def projection_params(cfg: Dict) -> int:
    """``W_q``, ``W_k``, ``W_v``, ``W_o``."""
    h, heads, kv, hd = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return h * heads * hd + 2 * h * kv * hd + heads * hd * h


def gate_params(cfg: Dict) -> int:
    """``W_g`` with its bias: one gate a key-value head."""
    return cfg["hidden_size"] * cfg["num_key_value_heads"] + cfg["num_key_value_heads"]


def mixer_params(cfg: Dict) -> int:
    """A retention layer whole: the projections, the gate, the q and k norms."""
    return projection_params(cfg) + gate_params(cfg) + 2 * cfg["head_dim"]


def mlp_params(cfg: Dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_params(cfg: Dict) -> int:
    return mixer_params(cfg) + mlp_params(cfg) + NORMS_A_LAYER * cfg["hidden_size"]


def table_params(cfg: Dict) -> int:
    """The embedding, and as much again for the untied head."""
    return cfg["vocab_size"] * cfg["hidden_size"]


def held_params(cfg: Dict) -> int:
    return cfg["num_hidden_layers"] * layer_params(cfg) + 2 * table_params(cfg) + cfg["hidden_size"]


# ------------------------------------------------------------------ the state


def state_row_bytes(cfg: Dict) -> int:
    """One row's ``S`` and ``z`` in one layer: a key-value head's ``F x D`` and ``F``, float32."""
    return cfg["num_key_value_heads"] * feature_dim(cfg) * (cfg["head_dim"] + 1) * STATE_ITEMSIZE


def state_bytes(cfg: Dict, batch: int) -> int:
    return cfg["num_hidden_layers"] * batch * state_row_bytes(cfg)


# ------------------------------------------------------------------ decoding


def decode_step_bytes(cfg: Dict, batch: int, weight_itemsize: int = 2) -> float:
    """The bytes one decode step of ``batch`` rows has to move, whatever
    program runs it and whatever the context: every layer's weights and the
    head once (of the embedding a step reads ``batch`` rows, not counted), and
    every layer's state read **and written** once (a step replaces it)."""
    weights = (held_params(cfg) - table_params(cfg)) * weight_itemsize
    return float(weights + 2 * state_bytes(cfg, batch))


def decode_scan_bytes(cfg: Dict, batch: int, new_tokens: int, **kw) -> float:
    """The bytes the ``new_tokens - 1`` steps of one call move: every step the same."""
    return (new_tokens - 1) * decode_step_bytes(cfg, batch, **kw)


# -------------------------------------------------------------- prompt pass


def token_product_flops(cfg: Dict) -> float:
    """The dense matrix products one token passes on its way through the stack (without the retention and the head)."""
    return 2.0 * cfg["num_hidden_layers"] * (projection_params(cfg) + cfg["hidden_size"] * cfg["num_key_value_heads"]
                                             + mlp_params(cfg))


def retention_token_flops(cfg: Dict) -> Dict[str, float]:
    """One token's matrix-unit work in one layer's state form: the query side
    ``phi(q)^T S`` (every query head against its key-value head's state) and the
    key side ``phi(k) v^T`` (a key-value head each)."""
    f, d = feature_dim(cfg), cfg["head_dim"]
    return {"query": 2.0 * cfg["num_attention_heads"] * f * d, "key": 2.0 * cfg["num_key_value_heads"] * f * d}


def chunk_cost(cfg: Dict, rows: int, length: int, itemsize: int = 2) -> Dict[str, float]:
    """The floor of one layer's prompt pass over ``rows`` rows of ``length``
    tokens, **whatever chunk a program cuts them into**: the state form's
    matrix-unit operations, a token's query and key sides (what a chunk of one
    token costs; the scores and values a program computes within a longer chunk,
    20.5 kFLOP a visible pair, are its own choice of shape and are not counted,
    so a longer chunk cannot read as more useful work), and the bytes of one
    read of q, k, v (at ``itemsize``) and the gates (float32) and one write of
    ``y`` and of the rows' final state. The state form is the configuration's
    (``assumed.state_form``); under about 5000 keys the attention form with the
    key side for the hand-off would need fewer operations (59 MFLOP a token at
    4096 against 101), a mechanism that is not built (``PERF.md`` 7)."""
    tokens = rows * length
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    per_token = retention_token_flops(cfg)
    flops = tokens * (per_token["query"] + per_token["key"])
    moved = tokens * ((2 * heads + 2 * kv) * d * itemsize + kv * 4) + rows * state_row_bytes(cfg)
    return {"flops": float(flops), "bytes": float(moved)}


def prefill_flops(cfg: Dict, batch: int, prompt_len: int) -> float:
    """Useful matrix-unit operations of one prompt pass: every token through
    the stack's dense products, every layer's retention in the state form
    (:func:`chunk_cost`), and the head at the last position of each row (the
    only logits the generator reads)."""
    return (batch * prompt_len * token_product_flops(cfg)
            + cfg["num_hidden_layers"] * chunk_cost(cfg, batch, prompt_len)["flops"]
            + 2.0 * batch * cfg["hidden_size"] * cfg["vocab_size"])


def train_flops(cfg: Dict, batch: int, seq_len: int) -> float:
    """Forward and backward (3x forward) with logits at every position, the
    retention in its state form. No cell trains this configuration; the harness
    asks every family for the count."""
    per_token = retention_token_flops(cfg)
    fwd = batch * seq_len * (token_product_flops(cfg) + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
                             + cfg["num_hidden_layers"] * (per_token["query"] + per_token["key"]))
    return 3.0 * fwd
