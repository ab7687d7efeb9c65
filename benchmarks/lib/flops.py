"""Operations and bytes from shapes: what the algorithm needs, not what a
program happens to execute. Recomputed work (rematerialisation, the score
matrix a flash backward builds again) is not counted, so a share of a peak
computed from these cannot pass 100%.

``perceiver_ar_train_flops`` is copied from
``perceiver_io_tpu/utils/flops.py::train_step_flops`` and
``perceiver_io_image_train_flops`` from the arithmetic inside
``bench.image_bench`` (PR 25); both count multiply-adds of the matrix
products twice, forward once and backward twice (3x forward)."""

from __future__ import annotations

from typing import Dict, List


def perceiver_ar_train_flops(cfg: Dict, batch_size: int) -> float:
    """One optimizer step of the Perceiver AR CLM on ``batch_size`` windows
    of ``max_seq_len`` tokens. The prefix the cross-attention sees is
    discounted by the prefix dropout the configuration trains with."""
    lat, c, layers = cfg["max_latents"], cfg["num_channels"], cfg["num_self_attention_layers"]
    prefix_len = cfg["max_seq_len"] - lat
    prefix = prefix_len - int(prefix_len * cfg["cross_attention_dropout"])
    kv = prefix + lat
    wf_sa, wf_ca = cfg["self_attention_widening_factor"], cfg["cross_attention_widening_factor"]
    ca_proj = 2 * lat * (4 * c * c) + 2 * prefix * (2 * c * c)  # q,k,v,o over latents; k,v over the prefix
    ca_attn = 2 * 2 * lat * kv * c
    ca_mlp = 2 * lat * 2 * wf_ca * c * c
    sa_proj = layers * 2 * lat * 4 * c * c
    sa_attn = layers * 2 * 2 * lat * lat * c
    sa_mlp = layers * 2 * lat * 2 * wf_sa * c * c
    logits = 2 * lat * c * cfg["vocab_size"]
    fwd = ca_proj + ca_attn + ca_mlp + sa_proj + sa_attn + sa_mlp + logits
    return 3.0 * fwd * batch_size


def perceiver_io_image_train_flops(cfg: Dict, batch_size: int) -> float:
    """One optimizer step of the Perceiver IO image classifier: the encoder
    cross-attention over the pixel array and the weight-shared latent stack.
    The decoder (one query) is left out, as in ``bench.image_bench``."""
    image_shape = cfg["image_shape"]
    lat, lc = cfg["num_latents"], cfg["num_latent_channels"]
    m = 1
    for s in image_shape[:-1]:
        m *= s
    in_ch = image_shape[-1] + len(image_shape[:-1]) * (2 * cfg["num_frequency_bands"] + 1)
    qk = in_ch  # qk and v channels default to the adapter width
    ca = (
        2 * lat * lc * qk  # q projection
        + 2 * m * in_ch * qk * 2  # k, v projections
        + 2 * 2 * lat * m * qk  # scores and values
        + 2 * lat * qk * lc  # output projection
        + 2 * lat * 2 * cfg["cross_attention_widening_factor"] * lc * lc  # mlp
    )
    layers = cfg["num_self_attention_layers_per_block"] * cfg["num_self_attention_blocks"]
    sa = layers * (
        2 * lat * 4 * lc * lc
        + 2 * 2 * lat * lat * lc
        + 2 * lat * 2 * cfg["self_attention_widening_factor"] * lc * lc
    )
    return 3.0 * (ca + sa) * batch_size


def attention_pairs(n_q: int, n_kv: int, causal: bool) -> int:
    """Query-key pairs an attention call scores. Causal calls are
    right-aligned: query i sees keys 0..n_kv-n_q+i."""
    if not causal:
        return n_q * n_kv
    if n_kv < n_q:
        raise ValueError(f"right-aligned causal attention needs n_kv >= n_q, got {n_kv} < {n_q}")
    return n_q * n_kv - n_q * (n_q - 1) // 2


def flash_attention_cost(call: Dict, backward: bool, dtype_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes one attention call needs, forward or backward,
    over all its heads and batch rows.

    ``call``: ``{"batch", "heads", "n_q", "n_kv", "d_qk", "d_v", "causal"}``.
    Forward: QK^T and PV. Backward: dV, dP, dQ, dK (the scores a flash
    backward computes again are recomputation and are left out). Bytes:
    every operand read once and every result written once in
    ``dtype_bytes``, the row statistics in float32."""
    b, h = call["batch"], call["heads"]
    n_q, n_kv, d_qk, d_v = call["n_q"], call["n_kv"], call["d_qk"], call["d_v"]
    pairs = attention_pairs(n_q, n_kv, call["causal"])
    q, k, v, o = n_q * d_qk, n_kv * d_qk, n_kv * d_v, n_q * d_v
    stats = 4 * n_q
    if not backward:
        flops = 2 * pairs * (d_qk + d_v)
        nbytes = dtype_bytes * (q + k + v + o) + stats
    else:
        flops = 2 * pairs * (2 * d_qk + 2 * d_v)
        nbytes = dtype_bytes * (q + k + v + o + o) + 2 * stats + dtype_bytes * (q + k + v)
    return {"flops": float(flops * b * h), "bytes": float(nbytes * b * h)}


def roofline_seconds(calls: List[Dict], peaks: Dict, training: bool) -> Dict[str, float]:
    """The least time the chip could take over ``calls``: for each call the
    larger of FLOPs over the bf16 peak and bytes over the HBM peak, forward
    and (``training``) backward. Also which of the two bounds most of it."""
    total = by_flops = 0.0
    for call in calls:
        for backward in ((False, True) if training else (False,)):
            cost = flash_attention_cost(call, backward)
            t_f = cost["flops"] / peaks["bf16_flops_per_s"]
            t_b = cost["bytes"] / peaks["hbm_bytes_per_s"]
            total += max(t_f, t_b)
            by_flops += t_f if t_f >= t_b else 0.0
    return {"seconds": total, "bound": "flops" if by_flops >= total / 2 else "bytes"}
