"""Parameters, bytes and operations of Phi-4-mini-flash-reasoning held whole on
one chip, from the configuration's numbers alone: what the algorithm needs, not
what a program happens to execute. Imports nothing of the program.

``cfg`` is the family's ``cfg`` dict (``families/phi4flash.py``): the published
sizes under the program's names, ``layer_types`` an entry a layer (``"mamba"``,
``"sliding_attention"``, ``"full_attention"``, ``"gmu"``, ``"cross_attention"``).
A product of (m, k) by (k, n) is ``2 m k n`` operations. The parameters are
counted whole, norms and biases too: they reproduce ``jax.eval_shape`` of the
program to the last one. The Mamba mixer's counts are ``lib/jamba_cost.py``'s
without the three inner norms; the trace helpers are ``lib/dsv3_cost.py``'s."""

from __future__ import annotations

from typing import Dict

from benchmarks.lib import jamba_cost

NORM_PARAMS = 2  # a LayerNorm's scale and bias, a channel each
NORMS_A_LAYER = 2  # before the mixer, before the feed-forward

d_inner = jamba_cost.d_inner
mamba_products = jamba_cost.mamba_products
n_layers = jamba_cost.n_layers
ssm_state_bytes = jamba_cost.ssm_state_bytes
conv_window_bytes = jamba_cost.conv_window_bytes
scan_cost = jamba_cost.scan_cost


def mamba_params(cfg: Dict) -> int:
    """A mixer whole: its four products, the convolution with its bias, ``W_dt``'s bias, ``A_log`` and ``D``; no inner norm."""
    d, n, k = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_d_conv"]
    return mamba_products(cfg) + k * d + d + d + n * d + d


def gmu_params(cfg: Dict) -> int:
    return 2 * cfg["hidden_size"] * d_inner(cfg)


def _widths(cfg: Dict):
    return cfg["num_attention_heads"] * cfg["head_dim"], cfg["num_key_value_heads"] * cfg["head_dim"]


def lambda_params(cfg: Dict) -> int:
    """Four vectors of ``head_dim`` and the subnorm's scale of ``2 * head_dim``."""
    return 4 * cfg["head_dim"] + 2 * cfg["head_dim"]


def self_attention_products(cfg: Dict) -> int:
    q, kv = _widths(cfg)
    return cfg["hidden_size"] * (q + 2 * kv) + q * cfg["hidden_size"]


def cross_attention_products(cfg: Dict) -> int:
    q, _ = _widths(cfg)
    return 2 * cfg["hidden_size"] * q


def self_attention_params(cfg: Dict) -> int:
    """``W_qkv`` and ``W_o`` with their biases, the four lambda vectors, the subnorm."""
    q, kv = _widths(cfg)
    return self_attention_products(cfg) + (q + 2 * kv) + cfg["hidden_size"] + lambda_params(cfg)


def cross_attention_params(cfg: Dict) -> int:
    q, _ = _widths(cfg)
    return cross_attention_products(cfg) + q + cfg["hidden_size"] + lambda_params(cfg)


def mlp_params(cfg: Dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


MIXER_PARAMS = {"mamba": mamba_params, "gmu": gmu_params, "sliding_attention": self_attention_params,
                "full_attention": self_attention_params, "cross_attention": cross_attention_params}
MIXER_PRODUCTS = {"mamba": mamba_products, "gmu": gmu_params, "sliding_attention": self_attention_products,
                  "full_attention": self_attention_products, "cross_attention": cross_attention_products}


def layer_params(cfg: Dict, kind: str) -> int:
    return MIXER_PARAMS[kind](cfg) + mlp_params(cfg) + NORMS_A_LAYER * NORM_PARAMS * cfg["hidden_size"]


def table_params(cfg: Dict) -> int:
    """The embedding table, which is the head too (``tie_word_embeddings``)."""
    return cfg["vocab_size"] * cfg["hidden_size"]


def held_params(cfg: Dict) -> int:
    return sum(layer_params(cfg, kind) for kind in cfg["layer_types"]) + table_params(cfg) + NORM_PARAMS * cfg["hidden_size"]


# ------------------------------------------------------ the stack's two halves


def shared_cache_layer(cfg: Dict) -> int:
    """The layer whose keys and values the cross layers read: the last full attention below the first of them."""
    kinds = cfg["layer_types"]
    return max(i for i in range(kinds.index("cross_attention")) if kinds[i] == "full_attention")


def prompt_layers(cfg: Dict) -> int:
    """The layers a prompt pass has to run over every position: those below the one that owns the shared cache."""
    return shared_cache_layer(cfg)


def layers_skipped(cfg: Dict) -> int:
    """The layers whose work at a prompt position nothing reads, but at the last: the owning layer's query side and all above it."""
    return cfg["num_hidden_layers"] - prompt_layers(cfg)


def shared_cache_readers(cfg: Dict) -> int:
    """Reads of the shared cache a step: the owning layer's own and one a cross layer."""
    return 1 + n_layers(cfg, "cross_attention")


# ------------------------------------------------------------------ the state


def kv_row_bytes(cfg: Dict, itemsize: int = 2) -> int:
    """A token's keys and values in one attention layer that owns a cache."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def ring_bytes(cfg: Dict, batch: int, itemsize: int = 2) -> int:
    """The window layers' rings, each ``sliding_window`` slots a row."""
    return n_layers(cfg, "sliding_attention") * batch * cfg["sliding_window"] * kv_row_bytes(cfg, itemsize)


def shared_cache_bytes(cfg: Dict, batch: int, context: int, itemsize: int = 2) -> int:
    """The one cache at ``context`` tokens a row: held once, whatever the number of layers that read it."""
    return batch * context * kv_row_bytes(cfg, itemsize)


# ------------------------------------------------------------------ decoding


def decode_step_bytes(cfg: Dict, batch: int, context: int, weight_itemsize: int = 2, cache_itemsize: int = 2) -> float:
    """The bytes one decode step of ``batch`` rows has to move, each row with
    ``context`` tokens behind it, whatever program runs it: every weight once
    (the tied table once), every Mamba layer's convolution window and float32
    state read **and written** once, every ring read once at what it holds
    (``min(context, sliding_window)`` slots), and the shared cache read **once a
    reading layer** at the length the step finds: the layers run one after the
    other, each on the one before, so no read serves two of them (the row a
    step appends is not counted)."""
    weights = held_params(cfg) * weight_itemsize
    state = 2 * (ssm_state_bytes(cfg, batch) + conv_window_bytes(cfg, batch, cache_itemsize))
    rings = ring_bytes(cfg, batch, cache_itemsize) * min(context, cfg["sliding_window"]) // cfg["sliding_window"]
    shared = shared_cache_readers(cfg) * shared_cache_bytes(cfg, batch, context, cache_itemsize)
    return float(weights + state + rings + shared)


def decode_scan_bytes(cfg: Dict, batch: int, prompt_len: int, new_tokens: int, **kw) -> float:
    """The bytes the ``new_tokens - 1`` steps of one call move: step ``j`` (1-based) finds ``prompt_len + j`` tokens in the shared cache."""
    return sum(decode_step_bytes(cfg, batch, prompt_len + j, **kw) for j in range(1, new_tokens))


# -------------------------------------------------------------- prompt pass


def visible_pairs(n: int, window=None) -> int:
    """The (query, key) pairs of one row's causal attention: ``j <= i``, and ``j > i - window`` under a window."""
    if window is None or window >= n:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


def attention_flops(cfg: Dict, pairs: int) -> float:
    """Scores and values of differential attention over ``pairs`` visible
    pairs in one layer: each costs, a query pair, two dot products of ``head_dim``
    (the two maps) and two axpys of ``2 * head_dim`` (each map over the pair's value)."""
    d, q_pairs = cfg["head_dim"], cfg["num_attention_heads"] // 2
    return 2.0 * q_pairs * pairs * (2 * d + 2 * 2 * d)


def token_product_flops(cfg: Dict, kinds) -> float:
    """The matrix products one token passes through the layers ``kinds`` (without attention's scores and values, the scan and the head)."""
    return 2.0 * sum(MIXER_PRODUCTS[kind](cfg) + mlp_params(cfg) for kind in kinds)


def shared_kv_flops(cfg: Dict) -> float:
    """The owning layer's key and value projections of one token."""
    return 2.0 * cfg["hidden_size"] * 2 * _widths(cfg)[1]


def prefill_flops(cfg: Dict, batch: int, prompt_len: int, cut: bool = True) -> float:
    """Useful **product** operations of one prompt pass. ``cut`` (what the
    algorithm needs): every token through the products of the layers below the
    owning one, their window attentions over the visible pairs, the owning
    layer's key and value projections over the prompt, and at the **last
    position of each row alone** the owning layer's query side with its
    attention over the prompt, every layer above it and the head. ``cut=False``
    is a pass that runs all layers over all positions (what the cut saves is
    the difference). The scans' elementwise work is no product and is not counted."""
    kinds, stop = cfg["layer_types"], prompt_layers(cfg)
    window_pairs = visible_pairs(prompt_len, cfg["sliding_window"])
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    if not cut:
        full = shared_cache_readers(cfg) * attention_flops(cfg, visible_pairs(prompt_len))
        return batch * (prompt_len * token_product_flops(cfg, kinds) + n_layers(cfg, "sliding_attention") * attention_flops(cfg, window_pairs)
                        + full + head)
    below = prompt_len * token_product_flops(cfg, kinds[:stop]) + n_layers(cfg, "sliding_attention") * attention_flops(cfg, window_pairs)
    last = token_product_flops(cfg, kinds[stop:]) + shared_cache_readers(cfg) * attention_flops(cfg, prompt_len) + head
    return batch * (below + prompt_len * shared_kv_flops(cfg) + last)


def train_flops(cfg: Dict, batch: int, seq_len: int) -> float:
    """Forward and backward (3x forward) with logits at every position, the
    scans' elementwise operations with the products'. No cell trains this
    configuration; the harness asks every family for the count."""
    fwd = (prefill_flops(cfg, batch, seq_len, cut=False) + batch * (seq_len - 1) * 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
           + n_layers(cfg, "mamba") * scan_cost(cfg, batch, seq_len)["flops"])
    return 3.0 * fwd


# ----------------------------------------------------------------- kernels


def diff_flash_cost(cfg: Dict, rows: int, length: int, itemsize: int = 2) -> Dict[str, float]:
    """One window layer's differential flash forward over ``rows`` rows of
    ``length`` tokens: the operations of the visible band alone
    (:func:`attention_flops`), and the bytes of one read of the query pairs and
    one write of the output (``H * head_dim`` a token each) and one read of each
    key-value pair's keys and values (``Hkv * head_dim`` a token each)."""
    q, kv = _widths(cfg)
    flops = rows * attention_flops(cfg, visible_pairs(length, cfg["sliding_window"]))
    moved = rows * length * (2 * q + 2 * kv) * itemsize
    return {"flops": float(flops), "bytes": float(moved)}
