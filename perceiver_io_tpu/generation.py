"""Autoregressive generation with KV caches and a sliding window.

Behavioral parity with the reference's HF generation integration
(reference: perceiver/model/core/huggingface.py:89-230):

- A prompt of length S with ``num_latents`` initial latents sets
  ``prefix_len = S - num_latents``; the first forward populates the caches.
- Each new token appends to the caches; the number of latents grows until
  ``max_latents``, then the prefix grows until ``max_prefix_len``.
- When the self-attention caches are full they are truncated to
  ``max_latents - 1`` (huggingface.py:152-156); when the total window reaches
  ``max_seq_len`` the cross-attention cache is truncated to
  ``max_seq_len - 1`` (huggingface.py:146-150), emulating unbounded
  generation.

TPU-first: caches are fixed-capacity buffers with ``max_new_tokens`` slack,
so "truncate the oldest" is marking the expired slot in a pad mask — the
buffers never physically shift (a per-step roll breaks XLA's in-place
aliasing and costs ~60% of a decode step at 16k, measured) — and the whole
decode loop is ONE compiled ``lax.scan`` with no per-step retracing at any
fill level. Sampling covers greedy, temperature, top-k and top-p (the
reference's exercised strategies, SURVEY §7.3); ``beam_search`` keeps the
roll-based slide (its window never exceeds ``max_seq_len``).
"""

from __future__ import annotations

import contextlib
import contextvars
import time
from dataclasses import asdict, dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perceiver_io_tpu.core.attention import KVCache, prefill_mode
from perceiver_io_tpu.utils.arrays import concrete_or_none


@dataclass
class GenerationConfig:
    max_new_tokens: int = 64
    do_sample: bool = False
    temperature: float = 1.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_token_id: Optional[int] = None
    pad_token_id: int = 0


class GenerationAborted(RuntimeError):
    """Raise from an ``on_token`` callback to stop a request mid-decode.

    The cancellation seam of :func:`make_instrumented_generate_fn`: the
    wrapper classifies the abort by :attr:`outcome` instead of ``"error"``,
    so the ``request`` event (and ``GenerationStats``) carries the honest
    terminal outcome with the partial TTFT/TPOT already measured. The
    serving front end (``perceiver_io_tpu.serving``) raises the
    :class:`GenerationDeadlineExceeded` subclass when a request's deadline
    expires mid-decode and this base class for explicit cancellation.
    """

    outcome = "cancelled"


class GenerationDeadlineExceeded(GenerationAborted):
    """Mid-decode deadline expiry — stamped as a ``timeout`` outcome."""

    outcome = "timeout"


def _maybe_quantize_weights(model, params, weight_dtype):
    """``(decode_params, compute_dtype)`` — int8-quantized kernels and the
    dtype to dequantize to inside the decode loop, or ``(params, None)``
    passthrough (the None sentinel keeps the default path's tree untouched,
    bit-for-bit)."""
    if weight_dtype is None:
        return params, None
    if jnp.dtype(weight_dtype) != jnp.dtype(jnp.int8):
        raise ValueError(f"weight_dtype must be None or jnp.int8, got {weight_dtype}")
    if hasattr(model, "generation_decoder"):
        raise ValueError("weight_dtype: int8 weights are defined for the Perceiver AR models' `kernel` leaves only")
    from perceiver_io_tpu.ops.quant import quantize_weights

    return quantize_weights(params), getattr(model, "dtype", jnp.float32)


def _maybe_dequantize_weights(decode_params, compute_dtype):
    if compute_dtype is None:
        return decode_params
    from perceiver_io_tpu.ops.quant import dequantize_weights

    return dequantize_weights(decode_params, compute_dtype)


# LayerNorm scale/bias, projection biases, int8 scale planes — everything at
# or under this element count rides the packed buffer
_PACK_MAX_SIZE = 4096

# the pack stages leaves through ONE f32 buffer, so only dtypes whose
# f32 round-trip is exact may ride it: f32 itself, and the sub-f32 floats
# f32 embeds losslessly (bf16/f16). Anything else (f64 under x64, float8
# variants, future dtypes) is left unpacked — correct, just not
# consolidated — rather than silently rounded through f32 (ADVICE r5).
_PACK_EXACT_DTYPES = frozenset(
    jnp.dtype(d) for d in (jnp.float32, jnp.bfloat16, jnp.float16)
)

# trace-time lever (A/B tool since deleted): None = auto — pack at batch >= 4,
# where the scan's schedule-spread dominates (measured bf16 A/B: +12.5%
# tok/s at b=8, +2.5% at b=4, -30% at b=2, -8% at b=1 — below the boundary
# the loop is latency-bound and the barrier serializes staging that
# previously prefetched concurrently). True/False force.
_PACK_SMALL = contextvars.ContextVar("generation_pack_small", default=None)
_PACK_MIN_BATCH = 4


@contextlib.contextmanager
def pack_small_params(mode: Optional[bool]):
    """Scoped toggle for the decode scan's small-parameter packing
    (None = batch-size auto).

    Read at **trace time** (the same contract as
    ops.flash_attention.default_flash): a function already compiled by
    ``make_generate_fn``/``jax.jit`` keeps whatever mode it was traced
    with, and calling it inside this context has no effect. Build AND
    first-call the generate fn inside the block (as a ``default_flash``
    block is used)."""
    token = _PACK_SMALL.set(mode)
    try:
        yield
    finally:
        _PACK_SMALL.reset(token)


def _pack_enabled(batch_size: int) -> bool:
    mode = _PACK_SMALL.get()
    return batch_size >= _PACK_MIN_BATCH if mode is None else mode


def _pack_small_params(params, max_size: int = _PACK_MAX_SIZE):
    """Consolidate the tree's small float leaves into ONE flat f32 buffer
    (only dtypes whose f32 round-trip is exact — see ``_PACK_EXACT_DTYPES``;
    other float leaves stay unpacked).

    The decode scan body reads dozens of tiny loop-invariant parameter
    buffers (LayerNorm scales/biases, projection biases — f32[512], 2 KB
    each); each one costs the scheduler a separate VMEM staging copy every
    iteration (profiled: the dominant slice of the b=8 bf16 decode's ~12%
    gap to its bandwidth floor, docs/performance.md). Packing them into one
    buffer turns N copy-starts into one; the body re-slices views out of
    the staged buffer (VMEM-cheap).

    Returns ``(packed, unpack)`` with ``unpack(packed)`` rebuilding the full
    tree (the large leaves ride in ``unpack``'s closure unchanged), or
    ``(None, None)`` when nothing qualifies. ``unpack`` pins the buffer
    behind an ``optimization_barrier`` so LICM cannot hoist the slices back
    out of the loop into N separate buffers (which would undo the
    consolidation).
    """
    flat, treedef = jax.tree_util.tree_flatten(params)
    meta = []  # (flat index, shape, dtype, offset, size)
    offset = 0
    for i, x in enumerate(flat):
        if (
            hasattr(x, "dtype")
            and jnp.issubdtype(x.dtype, jnp.floating)
            and jnp.dtype(x.dtype) in _PACK_EXACT_DTYPES
            and x.size <= max_size
        ):
            meta.append((i, x.shape, x.dtype, offset, x.size))
            offset += x.size
    if not meta:
        return None, None
    packed = jnp.concatenate([flat[i].astype(jnp.float32).reshape(-1) for i, *_ in meta])

    def unpack(packed):
        packed = lax.optimization_barrier(packed)
        new = list(flat)
        for i, shape, dtype, off, size in meta:
            new[i] = packed[off : off + size].reshape(shape).astype(dtype)
        return jax.tree_util.tree_unflatten(treedef, new)

    return packed, unpack


def _shift_left_if_full(cache: KVCache) -> KVCache:
    """Drop the oldest slot when the cache is full (the fixed-capacity analog
    of the reference's ``[:, -max_len+1:]`` truncation)."""

    def shift(c):
        # map_slots keeps the int8 scale planes aligned with their slots
        return c.map_slots(lambda a: jnp.roll(a, -1, axis=1), length=c.length - 1)

    full = cache.length >= cache.capacity
    return lax.cond(full, shift, lambda c: c, cache)


def _filtered_logits(logits: jnp.ndarray, config: GenerationConfig) -> jnp.ndarray:
    """The f32 temperature/top-k/top-p-filtered logits :func:`_sample` draws
    from, factored out so the speculative accept/residual math (rejection
    sampling needs the REAL sampling distributions p and q, filters
    included) can never drift from the sampling path. Rank-generic over
    leading axes; op-for-op the filtering `_sample` has always traced."""
    logits = logits.astype(jnp.float32) / jnp.maximum(config.temperature, 1e-6)

    if config.top_k is not None:
        top_k = min(config.top_k, logits.shape[-1])
        kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)

    if config.top_p is not None:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # number of tokens needed to reach top_p mass (at least 1)
        cutoff_idx = jnp.sum(cum < config.top_p, axis=-1, keepdims=True)
        cutoff_logit = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff_logit, -jnp.inf, logits)

    return logits


@jax.named_scope("sample")
def _sample(logits: jnp.ndarray, rng: jax.Array, config: GenerationConfig) -> jnp.ndarray:
    """Sample next-token ids from (B, V) logits."""
    if not config.do_sample:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(rng, _filtered_logits(logits, config), axis=-1)


def _require_pads_in_prefix(pad_mask, prefix_len: int) -> None:
    """Left padding must not reach into the latent region: the latent
    self-attention stack carries no pad mask (reference semantics — pads are
    masked in the cross-attention only), so a pad token that becomes a latent
    would be attended. Checked eagerly on concrete masks; under jit the
    contract is documented, not checked."""
    pad_mask = concrete_or_none(pad_mask)
    if pad_mask is None:
        return
    max_pads = int(np.max(np.sum(pad_mask, axis=1)))
    if max_pads > prefix_len:
        raise ValueError(
            f"left padding ({max_pads} tokens) reaches into the latent region "
            f"(prefix_len={prefix_len}); lower num_latents or shorten the padding"
        )


def _validate_window(mcfg, seq_len: int, num_latents: int) -> int:
    """Shared window validation (reference error contract,
    reference: core/huggingface.py:187-230). Returns the prefix length."""
    if not 0 < seq_len <= mcfg.max_seq_len:
        raise ValueError(f"Input sequence length out of valid range [1..{mcfg.max_seq_len}]")
    if not 0 < num_latents <= mcfg.max_latents:
        raise ValueError(f"num_latents={num_latents} out of valid range [1..{mcfg.max_latents}]")
    num_latents = min(seq_len, num_latents)
    prefix_len = seq_len - num_latents
    max_prefix_len = mcfg.max_seq_len - mcfg.max_latents
    if prefix_len > max_prefix_len:
        num_latents_min = num_latents + prefix_len - max_prefix_len
        raise ValueError(
            f"For given sequence of length={seq_len}, num_latents must "
            f"be in range [{num_latents_min}..{mcfg.max_latents}]"
        )
    return prefix_len


def beam_search(
    model,
    params,
    input_ids: jnp.ndarray,
    num_latents: int = 1,
    num_beams: int = 4,
    max_new_tokens: int = 64,
    length_penalty: float = 1.0,
    eos_token_id: Optional[int] = None,
    pad_token_id: int = 0,
    pad_mask: Optional[jnp.ndarray] = None,
    cache_dtype=jnp.float32,
    weight_dtype=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Beam-search decoding over the fixed-capacity KV caches.

    The reference delegates beam search to HF ``GenerationMixin`` and only
    supplies cache reordering (reference: core/huggingface.py:140-144
    ``_reorder_cache``). Here the whole search is one compiled ``lax.scan``:
    beams live as extra batch rows (B*num_beams), and the reorder is a
    ``take`` over the cache batch axis each step — static shapes throughout.

    Sequence length must satisfy ``seq_len + max_new_tokens <= max_seq_len``
    (no sliding window during search; beams must share absolute positions).

    :param pad_mask: boolean (B, S), True at (left) padding — mixed-length
        prompts batched with left padding; positions are shifted per row so a
        padded row decodes exactly like its unpadded equivalent.
    :return: ``(sequences (B, S + max_new_tokens), scores (B,))`` — the best
        beam per batch element and its length-penalized log-probability.
    """
    mcfg = model.config
    b, seq_len = input_ids.shape
    if num_beams < 1:
        raise ValueError("num_beams must be >= 1")
    if seq_len + max_new_tokens > mcfg.max_seq_len:
        raise ValueError(
            f"seq_len + max_new_tokens ({seq_len + max_new_tokens}) exceeds "
            f"max_seq_len ({mcfg.max_seq_len}) — beam search does not slide the window"
        )
    prefix_len = _validate_window(mcfg, seq_len, num_latents)
    _require_pads_in_prefix(pad_mask, prefix_len)

    from perceiver_io_tpu.core.modules import CausalSequenceModel

    bb = b * num_beams
    # prompt pass on B rows, then tile caches/logits to B*num_beams rows
    small_cache = CausalSequenceModel.init_cache(mcfg, b, dtype=cache_dtype)
    with jax.named_scope("prefill"), prefill_mode():
        out = model.apply(
            params, input_ids, prefix_len=prefix_len, pad_mask=pad_mask, kv_cache=small_cache
        )

    def tile(x):
        return jnp.repeat(x, num_beams, axis=0)

    cache = tuple(c.map_slots(tile) for c in out.kv_cache)

    # left-pad handling for decode steps: padded prompt slots stay masked in
    # the CA window forever (slot-aligned mask over the cache capacity), and
    # positions shift down by the per-row pad count — the same contract as
    # generate()'s decode loop
    if pad_mask is not None:
        ca_capacity = cache[0].capacity
        pos_shift = tile(pad_mask.sum(axis=1, keepdims=True).astype(jnp.int32))
        pad_slots = jnp.zeros((bb, ca_capacity), bool).at[:, :seq_len].set(tile(pad_mask))
    else:
        pos_shift = None
        pad_slots = None
    logprobs0 = jax.nn.log_softmax(out.logits[:, -1].astype(jnp.float32))  # (B, V)
    vocab = logprobs0.shape[-1]

    # first step: top beams per batch element
    top0, tok0 = lax.top_k(logprobs0, num_beams)  # (B, beams)
    beam_scores = top0.reshape(bb)
    token = tok0.reshape(bb)
    seqs = jnp.zeros((bb, max_new_tokens), jnp.int32).at[:, 0].set(token)
    done = jnp.zeros((bb,), bool)
    if eos_token_id is not None:
        done = token == eos_token_id

    batch_base = jnp.repeat(jnp.arange(b) * num_beams, num_beams)  # (bb,)

    decode_params, compute_dtype = _maybe_quantize_weights(model, params, weight_dtype)
    if _pack_enabled(b * num_beams):
        packed_small, unpack_small = _pack_small_params(decode_params)
    else:
        packed_small = unpack_small = None

    @jax.named_scope("decode")
    def step(carry, t):
        cache, seqs, beam_scores, token, done = carry
        dp = decode_params if unpack_small is None else unpack_small(packed_small)
        step_params = _maybe_dequantize_weights(dp, compute_dtype)
        # slide the self-attention windows when full, exactly as generate()
        # does (the CA cache cannot fill — validated above); positions keep
        # counting from the CA length, so beams stay aligned
        cache = (cache[0],) + tuple(_shift_left_if_full(c) for c in cache[1:])
        out = model.apply(
            step_params,
            token[:, None],
            prefix_len=0,
            pad_mask=pad_slots,
            kv_cache=cache,
            decode=True,
            pos_shift=pos_shift,
        )
        logprobs = jax.nn.log_softmax(out.logits[:, -1].astype(jnp.float32))  # (bb, V)

        if eos_token_id is not None:
            # finished beams: only PAD continues, at no cost
            frozen = jnp.full((vocab,), -jnp.inf).at[pad_token_id].set(0.0)
            logprobs = jnp.where(done[:, None], frozen[None, :], logprobs)

        cand = beam_scores[:, None] + logprobs  # (bb, V)
        cand = cand.reshape(b, num_beams * vocab)
        new_scores, flat_idx = lax.top_k(cand, num_beams)  # (B, beams)
        beam_idx = flat_idx // vocab  # source beam within the batch element
        new_token = (flat_idx % vocab).reshape(bb)

        gather_rows = (batch_base.reshape(b, num_beams) + beam_idx).reshape(bb)
        new_cache = tuple(
            c.map_slots(lambda a: jnp.take(a, gather_rows, axis=0)) for c in out.kv_cache
        )
        seqs = jnp.take(seqs, gather_rows, axis=0).at[:, t].set(new_token)
        done = jnp.take(done, gather_rows, axis=0)
        if eos_token_id is not None:
            done = done | (new_token == eos_token_id)
        return (new_cache, seqs, new_scores.reshape(bb), new_token, done), ()

    carry = (cache, seqs, beam_scores, token, done)
    if max_new_tokens > 1:
        carry, _ = lax.scan(step, carry, jnp.arange(1, max_new_tokens))
    _, seqs, beam_scores, _, done = carry

    # length penalty on the final scores (HF convention: score / len**penalty)
    if eos_token_id is not None:
        lengths = jnp.where(
            (seqs == eos_token_id).any(axis=1),
            (seqs == eos_token_id).argmax(axis=1) + 1,
            max_new_tokens,
        )
    else:
        lengths = jnp.full((bb,), max_new_tokens)
    final = beam_scores / (lengths.astype(jnp.float32) ** length_penalty)

    final = final.reshape(b, num_beams)
    best = jnp.argmax(final, axis=1)  # (B,)
    best_rows = jnp.arange(b) * num_beams + best
    best_seqs = jnp.take(seqs, best_rows, axis=0)
    best_scores = jnp.take(final.reshape(bb), best_rows, axis=0)
    prompt_tiled = input_ids
    return jnp.concatenate([prompt_tiled, best_seqs], axis=1), best_scores


class _PerceiverARDecoder:
    """Perceiver AR's side of the decode loop: what :func:`generate` and
    :func:`make_decode_fns` ask of a model. The loop itself (sampling, the
    scan, EOS freezing, parameter packing and dequantization) is shared; the
    model supplies the prompt pass, the one-token step and the state the two
    hand each other:

    - ``prefill(params, input_ids, pad_mask, num_latents, max_new_tokens,
      cache_dtype) -> (logits (B, N, V), window, consts)``, of which the loop
      reads the last position: validation,
      cache allocation, the prompt pass. ``window`` is a tuple of pytrees the
      step advances (it rides the scan's carry, in this order); ``consts`` a
      tuple of arrays the step only reads.
    - ``step(step_params, window, consts, token) -> (logits (B, 1, V), window)``.
    - ``health(logits, window)``: the Probeline decode gauges of a step.
    - ``window_names`` / ``const_names``: the keys the parts take in the
      host-driven pair's state dict.

    A model that is not a ``CausalSequenceModel`` brings its own through a
    ``generation_decoder()`` method (``models/text/decoder_lm.py``: latent
    caches, no window to slide).

    Here the window is ``(cache, ca_start, sa_start)``: the fixed-capacity
    caches (cross-attention first, then one a self-attention layer) and the
    start counters of the two sliding windows; the consts are the slot-aligned
    pad mask and the per-row position shift."""

    window_names = ("cache", "ca_start", "sa_start")
    const_names = ("pad_slots", "pos_shift")
    # Probeline tap sites (``probes.tap``) whose counts the probed decode pair
    # carries beside the health gauges; Perceiver AR has none
    tap_scopes = ()

    def __init__(self, model):
        self.model, self.mcfg = model, model.config

    def prefill(self, params, input_ids, pad_mask, num_latents, max_new_tokens, cache_dtype):
        mcfg = self.mcfg
        b, seq_len = input_ids.shape
        prefix_len = _validate_window(mcfg, seq_len, num_latents)
        _require_pads_in_prefix(pad_mask, prefix_len)

        from perceiver_io_tpu.core.modules import CausalSequenceModel

        # Roll-free sliding window: allocate `max_new_tokens` slack so the caches
        # never physically shift (the per-step roll + its aliasing-breaking copies
        # cost ~60% of a decode step at 16k, measured on v5e). "Truncate the
        # oldest" becomes marking the expired slot in the pad masks; slot index
        # stays the token's absolute position, and RoPE only depends on position
        # differences, so logits are identical to the rolling scheme.
        ca_capacity = seq_len + max_new_tokens
        sa_capacity = num_latents + max_new_tokens
        with jax.named_scope("prefill"):
            with jax.named_scope("cache_fill"):  # the empty caches and the masks over their slots
                cache = CausalSequenceModel.init_cache(
                    mcfg, b, ca_capacity=ca_capacity, sa_capacity=sa_capacity, dtype=cache_dtype
                )

                if pad_mask is None:
                    pad_mask = jnp.zeros((b, seq_len), bool)
                # left-pad count for position shifts — pad_slots below can't double as
                # this once expired slots are also marked
                pos_shift = pad_mask.sum(axis=1, keepdims=True).astype(jnp.int32)

                # slot-aligned pad mask over the cross-attention window (original
                # left-pads only; expired slots are derived from the start counters)
                pad_slots = jnp.zeros((b, ca_capacity), bool).at[:, :seq_len].set(pad_mask)

            # prompt pass (populates caches); prefill_mode routes its attention
            # through the flash kernels over the fresh k/v (see core/attention.py)
            with prefill_mode():
                out = self.model.apply(params, input_ids, prefix_len=prefix_len, pad_mask=pad_mask, kv_cache=cache)
            zero = jnp.zeros((), jnp.int32)
        return out.logits, (out.kv_cache, zero, zero), (pad_slots, pos_shift)

    def step(self, step_params, window, consts, token):
        """Slide the windows when full (expired slots derived from the start
        counters, the roll-free analog of the reference's truncation), apply
        the model on the last token."""
        mcfg = self.mcfg
        cache, ca_start, sa_start = window
        pad_slots, pos_shift = consts
        ca_cache, sa_caches = cache[0], cache[1:]
        ca_idx = jnp.arange(ca_cache.capacity, dtype=jnp.int32)[None, :]
        sa_idx = jnp.arange(sa_caches[0].capacity, dtype=jnp.int32)[None, :]

        ca_full = (ca_cache.length - ca_start) >= mcfg.max_seq_len
        ca_start = ca_start + ca_full.astype(jnp.int32)
        sa_full = (sa_caches[0].length - sa_start) >= mcfg.max_latents
        sa_start = sa_start + sa_full.astype(jnp.int32)

        out = self.model.apply(
            step_params,
            token[:, None],
            prefix_len=0,
            pad_mask=pad_slots | (ca_idx < ca_start),
            kv_cache=cache,
            decode=True,
            sa_pad_mask=sa_idx < sa_start,
            pos_shift=pos_shift,
        )
        return out.logits, (out.kv_cache, ca_start, sa_start)

    def health(self, logits, window):
        from perceiver_io_tpu.obs.probes import decode_health

        return decode_health(logits, window[0][0], window[1])

    def compile_row(self, batch, prompt_len, max_new_tokens, cache_dtype) -> dict:
        return {}


def _with_taps(scopes, thunk):
    """``thunk()`` traced under a collector for the tap sites ``scopes``:
    ``(result, totals)``, the taps reduced over their sites (one an expert
    layer: counts summed, ``*_max`` maxed). With no scopes ``totals`` is
    empty and nothing is traced that was not before."""
    if not scopes:
        return thunk(), {}
    from perceiver_io_tpu.obs import probes

    with probes.collecting(probes.ProbeConfig(scopes=scopes, activations=False)) as col:
        out = thunk()
    totals = {}
    for entry in col.stats.values():
        for name, v in entry.items():
            reduce = jnp.maximum if name.endswith("_max") else jnp.add
            totals[name] = v if name not in totals else reduce(totals[name], v)
    return out, totals


def _decoder_of(model):
    """The model's side of the decode loop (see :class:`_PerceiverARDecoder`)."""
    bring = getattr(model, "generation_decoder", None)
    return bring() if bring is not None else _PerceiverARDecoder(model)


def _decode_step_body(decoder, config, step_params, carry, consts, health=False):
    """One decode step — the SHARED body of :func:`generate`'s compiled scan
    and the host-driven step fn (:func:`make_decode_fns`), so the two paths
    cannot drift: the model's one-token step over its window (``decoder``),
    sample, handle EOS freezing. ``carry`` is ``(*window, token, rng,
    done)``. Callers own parameter unpacking/dequantization and the
    ``decode`` named scope.

    ``health=True`` (trace-time static — the Probeline decode gauges,
    obs/probes.py) additionally returns a third element: the in-graph
    decode-health dict (KV-cache occupancy fraction, mean logit entropy,
    non-finite logit fraction) computed from this step's logits and the
    post-append cache. The default ``False`` returns the historical
    2-tuple and traces zero extra ops, keeping :func:`generate`'s fused
    scan bitwise identical."""
    *window, token, rng, done = carry
    logits, window = decoder.step(step_params, tuple(window), consts, token)
    with jax.named_scope("sample"):  # the key chain and the EOS freeze are the sampling's
        rng, step_rng = jax.random.split(rng)
        sampled = _sample(logits[:, -1], step_rng, config)
        if config.eos_token_id is not None:
            sampled = jnp.where(done, config.pad_token_id, sampled)
            done = done | (sampled == config.eos_token_id)
    carry_out = (*window, sampled, rng, done)
    if not health:
        return carry_out, sampled
    return carry_out, sampled, decoder.health(logits[:, -1], window)


def advance_rng_chain(rng: jax.Array, n_tokens: int) -> jax.Array:
    """The sequential rng chain's state after ``n_tokens`` emitted tokens.

    Every decode path advances the chain exactly ONE split per emitted
    token — ``rng, step_key = jax.random.split(rng)`` in the prefill's
    first sample, :func:`generate`'s fused scan, the host-driven
    :func:`make_decode_fns` step, the paged engine's per-slot chains and
    the speculative accept — so the chain position IS the emitted-token
    count. That alignment is what makes preempted requests resumable
    token-exactly: replaying a prefill over ``prompt + emitted_prefix``
    with ``advance_rng_chain(PRNGKey(seed), len(emitted_prefix))`` hands
    the prefill's internal split exactly the key the uninterrupted run
    would have drawn for the next token (``serving.engine`` eviction
    resume and journal recovery ride this seam —
    docs/robustness.md#engine-eviction-and-recovery)."""
    for _ in range(int(n_tokens)):
        rng, _ = jax.random.split(rng)
    return rng


def _sample_per_slot(logits: jnp.ndarray, rngs: jnp.ndarray, config: GenerationConfig) -> jnp.ndarray:
    """Per-slot sampling with per-slot key chains: each decode slot draws
    exactly what a batch-1 :func:`_sample` call with its key would draw —
    the property that makes the batched engine token-exact (rng chain
    included) against the sequential path. ``logits`` (S, V), ``rngs``
    (S,) keys; greedy short-circuits (argmax is row-local already)."""
    if not config.do_sample:
        return jnp.argmax(logits, axis=-1)
    return jax.vmap(lambda row, key: _sample(row[None, :], key, config)[0])(logits, rngs)


def _paged_decode_step_body(model, mcfg, config, step_params, state):
    """One BATCHED decode step over paged caches — the engine analog of
    :func:`_decode_step_body` with every window counter, length, rng chain
    and done flag per-slot: slide each slot's window when full (expired
    slots masked via the per-slot start counters, exactly the sequential
    discipline), apply the model on each slot's last token, sample per slot
    with that slot's key. The compiled step is total over all slots —
    inactive slots decode garbage into their scratch page and their samples
    are discarded by the host scheduler (no per-slot control flow, one
    compiled program at every fill level).

    ``state`` keys: ``cache`` (tuple: paged CA + per-layer paged SA),
    ``ca_start``/``sa_start`` (S,), ``token`` (S,), ``rng`` (S,) keys,
    ``done`` (S,) bool, ``pad_slots`` (S, ca_capacity), ``pos_shift``
    (S, 1). Returns ``(new_state, sampled_tokens)``."""
    cache = state["cache"]
    ca_cache, sa_caches = cache[0], cache[1:]
    ca_start, sa_start = state["ca_start"], state["sa_start"]
    token, rng, done = state["token"], state["rng"], state["done"]
    ca_idx = jnp.arange(ca_cache.capacity, dtype=jnp.int32)[None, :]
    sa_idx = jnp.arange(sa_caches[0].capacity, dtype=jnp.int32)[None, :]

    ca_full = (ca_cache.length - ca_start) >= mcfg.max_seq_len
    ca_start = ca_start + ca_full.astype(jnp.int32)
    sa_full = (sa_caches[0].length - sa_start) >= mcfg.max_latents
    sa_start = sa_start + sa_full.astype(jnp.int32)

    out = model.apply(
        step_params,
        token[:, None],
        prefix_len=0,
        pad_mask=state["pad_slots"] | (ca_idx < ca_start[:, None]),
        kv_cache=cache,
        decode=True,
        sa_pad_mask=sa_idx < sa_start[:, None],
        pos_shift=state["pos_shift"],
    )
    rng, step_rng = jax.vmap(jax.random.split, out_axes=1)(rng)
    sampled = _sample_per_slot(out.logits[:, -1], step_rng, config)
    if config.eos_token_id is not None:
        sampled = jnp.where(done, config.pad_token_id, sampled)
        done = done | (sampled == config.eos_token_id)
    new_state = dict(
        state, cache=out.kv_cache, ca_start=ca_start, sa_start=sa_start,
        token=sampled, rng=rng, done=done,
    )
    return new_state, sampled


def make_paged_step_fn(model, config: Optional[GenerationConfig] = None, weight_dtype=None):
    """The batched engine's jitted decode step: ``fn(params, state) ->
    (state, tokens)`` over a paged-cache state pytree (see
    :func:`_paged_decode_step_body`). The STATE is donated — the page pools
    update in place on TPU, so a step moves O(tokens-this-step) bytes of
    cache writes, never O(pool); the (possibly int8) decode params ride as
    a separate, never-donated argument. ``serving.engine`` owns building
    the state and the join/retire host loop; ``analysis.flagship`` builds
    the same fn as the ``decode_paged`` graphcheck program."""
    config = config or GenerationConfig()
    mcfg = model.config
    compute_dtype = None if weight_dtype is None else getattr(model, "dtype", jnp.float32)

    def step(params, state):
        with jax.named_scope("decode_paged"):
            step_params = _maybe_dequantize_weights(params, compute_dtype)
            return _paged_decode_step_body(model, mcfg, config, step_params, state)

    return jax.jit(step, donate_argnums=1)


def make_generate_fn(
    model,
    num_latents: int = 1,
    config: Optional[GenerationConfig] = None,
    cache_dtype=jnp.float32,
    weight_dtype=None,
):
    """Jit-compiled ``fn(params, input_ids, pad_mask, rng) -> tokens``.

    Always prefer this over calling :func:`generate` eagerly on TPU: the
    eager path re-dispatches the prompt pass and decode-loop setup per call
    (measured ~20x slower per token at 16k context). One compilation serves
    all prompts of the same shape."""
    config = config or GenerationConfig()

    @jax.jit
    def fn(params, input_ids, pad_mask=None, rng=None):
        return generate(
            model,
            params,
            input_ids,
            num_latents=num_latents,
            pad_mask=pad_mask,
            config=config,
            rng=rng,
            cache_dtype=cache_dtype,
            weight_dtype=weight_dtype,
        )

    return fn


def generate(
    model,
    params,
    input_ids: jnp.ndarray,
    num_latents: int = 1,
    pad_mask: Optional[jnp.ndarray] = None,
    config: Optional[GenerationConfig] = None,
    rng: Optional[jax.Array] = None,
    cache_dtype=jnp.float32,
    weight_dtype=None,
) -> jnp.ndarray:
    """Generate ``config.max_new_tokens`` continuation tokens.

    :param model: a ``CausalSequenceModel`` (or subclass), or a model that
        brings its own side of the loop (``generation_decoder()``, see
        :class:`_PerceiverARDecoder`).
    :param input_ids: left-padded prompt (B, S).
    :param num_latents: initial number of latent positions at the end of the
        prompt (reference: huggingface.py:187-230).
    :param pad_mask: boolean (B, S), True at (left) padding.
    :param weight_dtype: ``jnp.int8`` stores the matmul kernels int8
        (per-output-channel scales, ops/quant.py) for the DECODE loop,
        halving its per-token weight read; the prompt pass stays full
        precision (it is compute-bound). Dequantization happens inside the
        scan body so the loop's HBM reads stay int8 (see ops/quant.py on
        why XLA does not hoist it). ``None`` (default) = model precision.
    :return: (B, S + max_new_tokens) sequence including the prompt.
    """
    config = config or GenerationConfig()
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    b = input_ids.shape[0]

    if config.max_new_tokens <= 0:
        return input_ids

    decoder = _decoder_of(model)
    if getattr(decoder, "speculative", False):
        return _generate_speculative(decoder, model, params, input_ids, pad_mask, config, cache_dtype, weight_dtype)
    # every operation of the program lies under one of two phases: what is made once a call (the prompt pass, which
    # opens the scope itself; the first token; the decode loop's weights) under ``prefill``, the loop and what it
    # hands back under ``decode``
    logits, window, consts = decoder.prefill(
        params, input_ids, pad_mask, num_latents, config.max_new_tokens, cache_dtype
    )
    with jax.named_scope("prefill"):
        with jax.named_scope("sample"):
            rng, first_rng = jax.random.split(rng)
            next_token = _sample(logits[:, -1], first_rng, config)

        with jax.named_scope("loop_io"):  # what is laid out for the decode loop once a call
            decode_params, compute_dtype = _maybe_quantize_weights(model, params, weight_dtype)
            if _pack_enabled(b):
                packed_small, unpack_small = _pack_small_params(decode_params)
            else:
                packed_small = unpack_small = None

    def step(carry, _):
        with jax.named_scope("decode"):
            dp = decode_params if unpack_small is None else unpack_small(packed_small)
            step_params = _maybe_dequantize_weights(dp, compute_dtype)
            return _decode_step_body(decoder, config, step_params, carry, consts)

    with jax.named_scope("prefill"), jax.named_scope("sample"):
        done0 = jnp.zeros((b,), bool)
        if config.eos_token_id is not None:
            done0 = next_token == config.eos_token_id

    # ``loop_io``: the loop's own carry and stacked tokens, and their assembly behind the prompt
    with jax.named_scope("decode"), jax.named_scope("loop_io"):
        if config.max_new_tokens > 1:
            carry = (*window, next_token, rng, done0)
            _, tokens = lax.scan(step, carry, None, length=config.max_new_tokens - 1)
            tokens = jnp.concatenate([next_token[:, None], tokens.T], axis=1)
        else:
            tokens = next_token[:, None]

        return jnp.concatenate([input_ids, tokens], axis=1)


# ---------------------------------------------------------------------------
# a model that drafts for itself: a step that yields one or two tokens a row
# ---------------------------------------------------------------------------
#
# Where the model's decoder is ``speculative`` (a multi-token-prediction
# module: ``models/text/decoder_lm.py``) the generator drafts and verifies
# greedily; nothing selects this but the configuration having the module. A
# row's state is its last emitted token ``t_l`` (not yet in a cache), the
# module's draft ``d_{l+1}`` of the token after it, and caches that hold
# positions ``0 .. l - 1``. A step runs the stack on both (``spec/verify``),
# emits ``g = argmax`` at ``l`` and, where ``g == d``, also the argmax at
# ``l + 1`` (``spec/accept``: :func:`_speculative_accept`'s greedy rule with
# one draft), runs the module on the same two positions with the tokens that
# followed them and takes the draft for the next step from the last position
# kept (``mtp/*``), and every cache keeps position ``l`` and, where the draft
# was accepted, ``l + 1`` (``spec/rollback``: a length a row). The emitted
# stream is the stack's own greedy stream token for token, whatever the module
# drafts: a draft only decides whether a step yields one token or two.


def _refuse_sampled_speculation(config: GenerationConfig):
    if config.do_sample:
        raise ValueError(
            "a model with a multi-token-prediction module generates greedily: drafting and verifying with a "
            "temperature (the rejection rule of _speculative_accept) is not wired to the module's draft"
        )


def _spec_first(decoder, params, input_ids, pad_mask, config, cache_dtype):
    """The speculative prompt pass: the first token, the first draft, the window, the rows done, and the first token's logits."""
    token, logits, draft_logits, window = decoder.spec_prefill(
        params, input_ids, pad_mask, config.max_new_tokens, cache_dtype, lambda logits: jnp.argmax(logits, axis=-1).astype(jnp.int32))
    with jax.named_scope("prefill"), jax.named_scope("mtp/draft"):
        draft = jnp.argmax(draft_logits, axis=-1).astype(jnp.int32)
    with jax.named_scope("prefill"), jax.named_scope("sample"):
        done = jnp.zeros(token.shape, bool)
        if config.eos_token_id is not None:
            done = token == config.eos_token_id
    return token, draft, window, done, logits


def _spec_step_body(decoder, config, step_params, window, token, draft, done, budget):
    """One speculative step, shared by the compiled loop and the host-driven
    pair. ``budget`` (B,) is how many tokens each row may still emit (0: the
    row is finished and keeps nothing). Returns the window, the step's tokens
    (B, 2), how many of them each row emits ``m`` (B,) in 0..2, the next
    ``token`` and ``draft``, ``done``, and the stack's logits at the row's
    first position (B, V) for the health gauges. Under a probe collector the
    step taps ``spec.step``: the drafts verified (one a live row), those
    accepted, the tokens emitted and the rows still live."""
    from perceiver_io_tpu.obs import probes

    b = token.shape[0]
    with jax.named_scope("spec/verify"):
        p_logits, hidden, window = decoder.spec_verify(step_params, window, jnp.stack([token, draft], axis=1))
    with jax.named_scope("spec/accept"):
        # the greedy rule needs no keys; the chain it threads is dead code here
        tokens, m, new_token, _, new_done = _speculative_accept(
            config, draft[:, None], None, p_logits, jnp.zeros((b, 2), jnp.uint32), done)
        accepted = (m == 2) & (budget > 0)
        m = jnp.minimum(m, budget)
        live = m > 0
        if probes.active():
            n_live = live.sum().astype(jnp.int32)  # one draft is verified a live row
            probes.tap("spec.step", {"drafts": n_live, "accepted": accepted.sum().astype(jnp.int32),
                                     "tokens_out": m.sum().astype(jnp.int32), "rows_live": n_live})
        token = jnp.where(live, new_token, token)
        done = jnp.where(live, new_done, done)
    # the module reads the token that followed each position: the two the stack put there
    m_logits, window = decoder.spec_draft(step_params, window, hidden, tokens)
    with jax.named_scope("mtp/draft"):
        kept_last = jnp.take_along_axis(m_logits, jnp.maximum(m - 1, 0)[:, None, None], axis=1)[:, 0]
        draft = jnp.where(live, jnp.argmax(kept_last, axis=-1).astype(jnp.int32), draft)
    with jax.named_scope("spec/rollback"):
        window = decoder.spec_keep(window, m)
    return window, tokens, m, token, draft, done, p_logits[:, 0]


def _generate_speculative(decoder, model, params, input_ids, pad_mask, config, cache_dtype, weight_dtype):
    """:func:`generate` for a model that drafts for itself: the prompt pass,
    then speculative steps in one compiled ``while`` until every row has its
    ``max_new_tokens``. A row that accepts drafts finishes in fewer steps and
    waits, finished, for the others."""
    _refuse_sampled_speculation(config)
    b = input_ids.shape[0]
    n_new = config.max_new_tokens
    token, draft, window, done, _ = _spec_first(decoder, params, input_ids, pad_mask, config, cache_dtype)
    with jax.named_scope("prefill"), jax.named_scope("loop_io"):  # the two phases of :func:`generate`
        decode_params, compute_dtype = _maybe_quantize_weights(model, params, weight_dtype)
        out = jnp.full((b, n_new), config.pad_token_id, jnp.int32).at[:, 0].set(token)
        rows = jnp.arange(b)[:, None]

    def step(carry):
        with jax.named_scope("decode"):
            window, out, count, token, draft, done = carry
            step_params = _maybe_dequantize_weights(decode_params, compute_dtype)
            window, tokens, m, token, draft, done, _ = _spec_step_body(
                decoder, config, step_params, window, token, draft, done, n_new - count)
            # a row writes its ``m`` tokens from column ``count`` on; what is not emitted goes past the edge and is dropped
            with jax.named_scope("loop_io"):
                j = jnp.arange(tokens.shape[1])[None, :]
                cols = jnp.where(j < m[:, None], count[:, None] + j, n_new)
                out = out.at[rows, cols].set(tokens, mode="drop")
                return window, out, count + m, token, draft, done

    with jax.named_scope("decode"), jax.named_scope("loop_io"):
        if n_new > 1:
            carry = (window, out, jnp.ones((b,), jnp.int32), token, draft, done)
            out = lax.while_loop(lambda carry: jnp.any(carry[2] < n_new), step, carry)[1]
        return jnp.concatenate([input_ids, out.astype(input_ids.dtype)], axis=1)


def make_decode_fns(
    model,
    num_latents: int = 1,
    config: Optional[GenerationConfig] = None,
    cache_dtype=jnp.float32,
    weight_dtype=None,
    probes: bool = False,
):
    """The host-driven decode pair: ``(prefill_fn, step_fn)``.

    - ``prefill_fn(params, input_ids, pad_mask=None, rng=None) ->
      (first_token, state)`` — validation, cache allocation (same
      ``max_new_tokens``-slack roll-free windows as :func:`generate`),
      prompt pass, first sample, and weight quantization; ``state`` is a
      dict pytree carrying the (possibly int8) decode params, caches,
      window counters, rng and the slot masks.
    - ``step_fn(state) -> (state, token)`` — exactly one scan-body
      iteration (:func:`_decode_step_body` — literally the same code
      :func:`generate`'s compiled scan runs, so the streams are token-exact
      equal, rng chain included).

    Both are jit-compiled; the per-token host dispatch costs more than the
    fused scan, so this is the *serving-shaped* path: the instrumented
    wrapper times every token through it (TTFT + a real TPOT distribution,
    not a mean), and a continuous-batching scheduler steps requests through
    ``step_fn`` between admissions (ROADMAP item 1).

    ``probes=True`` (trace-time static — the Probeline decode gauges,
    obs/probes.py, docs/observability.md#probes) adds a ``"probe"`` entry to
    the state dict: the in-graph decode-health stats (KV-cache occupancy
    fraction, mean logit entropy, non-finite logit fraction) computed by the
    SAME compiled step, read by the instrumented wrapper into the metrics
    registry and the per-request ``request`` event. Off (default) the
    compiled pair is bitwise today's.
    """
    config = config or GenerationConfig()
    if config.max_new_tokens < 1:
        raise ValueError("decode fns require max_new_tokens >= 1")
    decoder = _decoder_of(model)
    if getattr(decoder, "speculative", False):
        return _make_self_drafting_decode_fns(decoder, model, config, cache_dtype, weight_dtype, probes)
    names = decoder.window_names + decoder.const_names
    compute_dtype = None if weight_dtype is None else getattr(model, "dtype", jnp.float32)

    def prefill(params, input_ids, pad_mask=None, rng=None):
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        b = input_ids.shape[0]
        (logits, window, consts), taps = _with_taps(
            decoder.tap_scopes if probes else (),
            lambda: decoder.prefill(params, input_ids, pad_mask, num_latents, config.max_new_tokens, cache_dtype),
        )
        rng, first_rng = jax.random.split(rng)
        next_token = _sample(logits[:, -1], first_rng, config)
        done = jnp.zeros((b,), bool)
        if config.eos_token_id is not None:
            done = next_token == config.eos_token_id

        decode_params, _ = _maybe_quantize_weights(model, params, weight_dtype)
        state = {
            "params": decode_params,
            "token": next_token,
            "rng": rng,
            "done": done,
            **dict(zip(names, window + consts)),
        }
        if probes:
            # the prompt pass's health (token 0): same gauges, same scopes,
            # so the state pytree is uniform across prefill and every step
            state["probe"] = {**decoder.health(logits[:, -1], window), **taps}
        return next_token, state

    def step(state):
        with jax.named_scope("decode"):
            step_params = _maybe_dequantize_weights(state["params"], compute_dtype)
            carry = (
                *(state[k] for k in decoder.window_names),
                state["token"], state["rng"], state["done"],
            )
            consts = tuple(state[k] for k in decoder.const_names)
            stepped, taps = _with_taps(
                decoder.tap_scopes if probes else (),
                lambda: _decode_step_body(decoder, config, step_params, carry, consts, health=probes),
            )
            carry, token = stepped[0], stepped[1]
            n = len(decoder.window_names)
            new_state = dict(
                state, **dict(zip(decoder.window_names, carry[:n])),
                token=carry[n], rng=carry[n + 1], done=carry[n + 2],
            )
            if probes:
                new_state["probe"] = {**stepped[2], **taps}
            return new_state, token

    return jax.jit(prefill), jax.jit(step)


def _make_self_drafting_decode_fns(decoder, model, config, cache_dtype, weight_dtype, probes):
    """:func:`make_decode_fns` for a model that drafts for itself (a
    ``speculative`` decoder): the same prompt pass and step as
    :func:`_generate_speculative`'s compiled loop, driven from the host.

    - ``prefill_fn(params, input_ids, pad_mask=None, rng=None) ->
      (first_token, state)``; ``state`` also carries the first ``draft`` and
      ``count`` (B,), the tokens each row has emitted (1).
    - ``step_fn(state) -> (state, tokens (B, 2))``: one speculative step. Row
      ``r`` emits ``tokens[r, :state["emitted"][r]]``, 0 to 2 tokens: none once
      it has its ``max_new_tokens``, and the caller stops when every
      ``count`` has reached that. ``rng`` is taken and unused: the pair is greedy.
    """
    _refuse_sampled_speculation(config)
    compute_dtype = None if weight_dtype is None else getattr(model, "dtype", jnp.float32)
    scopes = decoder.tap_scopes if probes else ()
    # The weights stay out of the compiled programs' results: a state that carried them through ``jit`` would hold
    # them twice (an output is a buffer of its own), and this model's are most of a chip. They ride the state dict
    # from outside, as the arguments of both programs.
    if weight_dtype is None:
        decode_weights = lambda params: params  # noqa: E731
    else:
        decode_weights = jax.jit(lambda params: _maybe_quantize_weights(model, params, weight_dtype)[0])

    @jax.jit
    def first(params, input_ids, pad_mask):
        (token, draft, window, done, logits), taps = _with_taps(
            scopes, lambda: _spec_first(decoder, params, input_ids, pad_mask, config, cache_dtype))
        state = {
            "token": token, "draft": draft, "done": done,
            "count": jnp.ones(token.shape, jnp.int32), "emitted": jnp.ones(token.shape, jnp.int32),
            **dict(zip(decoder.window_names, window)),
        }
        if probes:
            # the prompt pass ran no speculative step: its books read zero, so the state is one pytree throughout
            zeros = {k: jnp.zeros((), jnp.int32) for k in ("drafts", "accepted", "tokens_out", "rows_live")}
            state["probe"] = {**decoder.health(logits, window), **zeros, **taps}
        return token, state

    @jax.jit
    def advance(decode_params, state):
        with jax.named_scope("decode"):
            step_params = _maybe_dequantize_weights(decode_params, compute_dtype)
            window = tuple(state[k] for k in decoder.window_names)
            stepped, taps = _with_taps(scopes, lambda: _spec_step_body(
                decoder, config, step_params, window, state["token"], state["draft"], state["done"],
                config.max_new_tokens - state["count"]))
            window, tokens, m, token, draft, done, logits = stepped
            new_state = dict(state, **dict(zip(decoder.window_names, window)), token=token, draft=draft, done=done,
                             count=state["count"] + m, emitted=m)
            if probes:
                new_state["probe"] = {**decoder.health(logits, window), **taps}
            return new_state, tokens

    def prefill(params, input_ids, pad_mask=None, rng=None):
        del rng
        token, state = first(params, input_ids, pad_mask)
        return token, dict(state, params=decode_weights(params))

    def step(state):
        new_state, tokens = advance(state["params"], {k: v for k, v in state.items() if k != "params"})
        return dict(new_state, params=state["params"]), tokens

    # what ``obs.recompile.RecompileTracker`` asks a jitted callable for
    prefill._cache_size, step._cache_size = first._cache_size, advance._cache_size
    return prefill, step


def make_shared_prefill_fn(
    model,
    num_latents: int,
    skip_tokens: int,
    seq_len: int,
    config: Optional[GenerationConfig] = None,
    cache_dtype=jnp.float32,
    probes: bool = False,
):
    """Prefill that SKIPS the first ``skip_tokens`` prompt tokens because
    their cross-attention KV rows are already resident in shared pool pages
    (Shareline, the radix prefix match): the rows are gathered from the pages
    into the contiguous cache, and the model forward runs over the unshared
    SUFFIX alone — prefill compute and TTFT collapse to the suffix.

    Exactness conditions (the caller — ``serving/engine.py`` — enforces both
    and falls back to the unshared prefill otherwise, so sharing is always a
    no-op rather than an approximation):

    - ``skip_tokens`` is a whole number of pages lying entirely inside the
      request's CONTEXT region (``skip_tokens <= seq_len - num_latents``):
      context rows are per-token functions of (token id, absolute position)
      under rotate-at-write RoPE, so byte-identical across requests with the
      same prefix — latent-region rows are not (they pass through ``q_norm``
      and the SA stack), so a match never reaches into them;
    - the suffix carries ALL ``num_latents`` latents, making the latent set
      (and therefore the logits) identical to the full-prompt prefill's.

    With byte-identical resident rows the suffix forward's attend inputs are
    bitwise the full prefill's on the einsum attend route (the CPU tier-1
    route — ``flash_enabled`` is TPU-only), so the sampled stream is
    token-exact equal to the unshared one, rng chain included (pinned by
    tests/test_pages.py ``decode_shared``).

    Returns ``shared_prefill(params, suffix_ids, pool_k, pool_v, page_ids,
    rng) -> (first_token, state)`` — jitted; ``state`` carries the same
    cache/rng/done/slot-mask fields the unshared prefill's state does (the
    engine's join seam reads exactly those; the decode params the unshared
    state also carries are the ENGINE's to hold, so this state omits them —
    no per-join params copy out of the compiled program). ``pool_k``/
    ``pool_v`` are the paged CA pools ``(num_pages, page_size, C)`` and
    ``page_ids`` the matched run ``(skip_tokens / page_size,)`` int32 —
    page ids are traced, so one trace serves every match of this geometry.
    """
    config = config or GenerationConfig()
    if config.max_new_tokens < 1:
        raise ValueError("decode fns require max_new_tokens >= 1")
    mcfg = model.config
    suffix_len = seq_len - skip_tokens
    if skip_tokens < 1:
        raise ValueError(f"skip_tokens must be >= 1, got {skip_tokens}")
    if suffix_len < num_latents:
        raise ValueError(
            f"matched run ({skip_tokens} tokens) reaches into the latent "
            f"region of a {seq_len}-token prompt with {num_latents} latents: "
            f"latent rows are not shareable"
        )
    _validate_window(mcfg, seq_len, num_latents)

    from perceiver_io_tpu.core.modules import CausalSequenceModel

    def shared_prefill(params, suffix_ids, pool_k, pool_v, page_ids, rng=None):
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        b, m = suffix_ids.shape
        if m != suffix_len:
            raise ValueError(f"suffix is {m} tokens; this fn skips "
                             f"{skip_tokens} of {seq_len}")
        page_size = pool_k.shape[1]
        if page_ids.shape[0] * page_size != skip_tokens:
            raise ValueError(
                f"{page_ids.shape[0]} pages of {page_size} do not cover "
                f"{skip_tokens} skipped tokens (whole pages only)"
            )
        ca_capacity = seq_len + config.max_new_tokens
        sa_capacity = num_latents + config.max_new_tokens
        cache = CausalSequenceModel.init_cache(
            mcfg, b, ca_capacity=ca_capacity, sa_capacity=sa_capacity, dtype=cache_dtype
        )
        ca = cache[0]
        if ca.quantized:
            raise NotImplementedError(
                "shared prefill over an int8 cache needs the scale-plane "
                "gather; the engine gates sharing off for cache_dtype=int8"
            )

        # the resident prefix rows, pool pages -> contiguous slots [0, skip)
        with jax.named_scope("shared_prefix_gather"):
            rows_k = pool_k[page_ids].reshape(skip_tokens, -1)
            rows_v = pool_v[page_ids].reshape(skip_tokens, -1)
            seeded = KVCache(
                k=ca.k.at[:, :skip_tokens].set(
                    jnp.broadcast_to(rows_k[None], (b,) + rows_k.shape).astype(ca.k.dtype)
                ),
                v=ca.v.at[:, :skip_tokens].set(
                    jnp.broadcast_to(rows_v[None], (b,) + rows_v.shape).astype(ca.v.dtype)
                ),
                length=jnp.full((), skip_tokens, jnp.int32),
                k_scale=None,
                v_scale=None,
            )
        cache = (seeded,) + tuple(cache[1:])

        # suffix forward: NOT prefill_mode (the CA cache enters non-empty) —
        # the generic cache-attend route appends the suffix rows at the fill
        # level and right-aligns the causal mask, exactly the full prefill's
        # einsum attend over the same bytes
        with jax.named_scope("shared_prefill"):
            out = model.apply(
                params,
                suffix_ids,
                prefix_len=suffix_len - num_latents,
                pad_mask=None,
                kv_cache=cache,
                pos_offset=skip_tokens,
            )
        rng, first_rng = jax.random.split(rng)
        next_token = _sample(out.logits[:, -1], first_rng, config)
        done = jnp.zeros((b,), bool)
        if config.eos_token_id is not None:
            done = next_token == config.eos_token_id

        state = {
            "cache": out.kv_cache,
            "token": next_token,
            "rng": rng,
            "done": done,
            "pad_slots": jnp.zeros((b, ca_capacity), bool),
            "pos_shift": jnp.zeros((b, 1), jnp.int32),
        }
        if probes:
            from perceiver_io_tpu.obs.probes import decode_health

            state["probe"] = decode_health(
                out.logits[:, -1], out.kv_cache[0], jnp.zeros((), jnp.int32)
            )
        return next_token, state

    return jax.jit(shared_prefill)


# ---------------------------------------------------------------------------
# Specline — speculative self-drafting decode (draft k cheap tokens, verify
# them in ONE flagship forward; arXiv:2603.09555 for the drafter-state
# design, the PR-13 paged substrate for the ragged verify geometry)
# ---------------------------------------------------------------------------

# keeps drafter proposal keys off the sequential rng chain: the chain itself
# advances one split per EMITTED token (the alignment that makes seeds
# reproduce across the speculative and sequential paths)
_DRAFT_SALT = 0x5BEC


def make_drafter(model, draft_depth: int):
    """The truncated-depth SELF-drafter: the same model class over a config
    whose latent self-attention stack keeps only the FIRST ``draft_depth``
    layers — no separate training, the drafter runs the flagship's own
    weights (:func:`drafter_decode_params` carves the matching subtree).
    Because layer i's input is layer i-1's output, the drafter's forward is
    the flagship's forward truncated after layer ``draft_depth - 1`` (plus
    the shared out-norm / tied-logits readout), so its prefill caches are
    literally a PREFIX of the flagship's (CA + SA layers 0..draft_depth-1)
    — the speculative prefill reuses them without a second prompt pass."""
    import dataclasses as _dc

    mcfg = model.config
    n_layers = mcfg.num_self_attention_layers
    if not 1 <= draft_depth < n_layers:
        raise ValueError(
            f"draft_depth must be in [1..{n_layers - 1}] "
            f"(a {n_layers}-layer flagship), got {draft_depth}"
        )
    rotary = mcfg.num_self_attention_rotary_layers
    cfg = _dc.replace(
        mcfg,
        num_self_attention_layers=draft_depth,
        num_self_attention_rotary_layers=(
            rotary if rotary == -1 else min(rotary, draft_depth)
        ),
    )
    return type(model)(config=cfg, dtype=getattr(model, "dtype", jnp.float32))


def drafter_decode_params(params, draft_depth: int):
    """The drafter's parameter tree: the flagship tree with the latent SA
    stack truncated to its first ``draft_depth`` layers (embedding,
    cross-attention, out-norm and the tied readout ride unchanged). Pure
    restructuring — identical on the raw tree and on the int8-quantized
    decode tree (ops/quant.py preserves module structure), and free under
    jit (no bytes move)."""
    col = params["params"]
    pa = col["perceiver_ar"]
    sa = pa["self_attention"]
    kept = {f"layer_{i}": sa[f"layer_{i}"] for i in range(draft_depth)}
    return {
        **params,
        "params": {**col, "perceiver_ar": {**pa, "self_attention": kept}},
    }


def _speculative_accept(config: GenerationConfig, drafts, q_logits, p_logits, rng, done):
    """The draft/verify acceptance core shared by the contiguous pair and
    the engine's paged slot mode — everything is per ROW, so ragged batches
    (per-slot accepted-prefix lengths) fall out naturally.

    Greedy: accept while the flagship argmax agrees with the draft; the
    first disagreement (or the bonus position after k accepts) emits the
    flagship argmax — token-for-token the sequential greedy stream.
    Sampling: standard speculative rejection sampling over the REAL
    sampling distributions (temperature/top-k/top-p filters included, via
    the shared :func:`_filtered_logits`): accept ``d_i`` with probability
    ``min(1, p_i(d_i) / q_i(d_i))``, resample the first rejection from the
    residual ``norm(max(p_i - q_i, 0))``, and the bonus position samples
    ``p_{k+1}`` — the emitted marginals are exactly the sequential path's.

    The rng chain advances ONE split per EMITTED token (the sequential
    discipline), so after m emitted tokens the returned key equals the
    sequential path's chain state after m tokens: seeds reproduce, and a
    speculative→sequential handoff continues the same stream.

    :param drafts: (B, k) drafter proposals.
    :param q_logits: (B, k, V) drafter logits the proposals were drawn from.
    :param p_logits: (B, k+1, V) flagship verify logits (one forward).
    :param rng: (B, 2) per-row chain keys; ``done`` (B,) EOS flags.
    :return: ``(tokens (B, k+1), m (B,), new_token (B,), rng_new (B, 2),
        done_new (B,))`` — rows emit ``tokens[:m]``; ``new_token`` is the
        pending carry (== ``tokens[m-1]``).
    """
    b, k = drafts.shape
    # the chain the sequential path would thread: chain[j] is the rng state
    # BEFORE emitting token j, step_keys[j] is token j's per-step key
    chain = [rng]
    step_keys = []
    for _ in range(k + 1):
        nxt, step = jax.vmap(jax.random.split, out_axes=1)(chain[-1])
        chain.append(nxt)
        step_keys.append(step)
    chain_stack = jnp.stack(chain, axis=1)  # (B, k+2, 2)

    if config.do_sample:
        pf = jax.nn.softmax(_filtered_logits(p_logits, config), axis=-1)  # (B, k+1, V)
        qf = jax.nn.softmax(_filtered_logits(q_logits, config), axis=-1)  # (B, k, V)
        p_d = jnp.take_along_axis(pf[:, :k], drafts[..., None], axis=-1)[..., 0]
        q_d = jnp.take_along_axis(qf, drafts[..., None], axis=-1)[..., 0]
        u = jnp.stack(
            [
                jax.vmap(lambda key: jax.random.uniform(jax.random.fold_in(key, 1)))(
                    step_keys[j]
                )
                for j in range(k)
            ],
            axis=1,
        )  # (B, k)
        # accept with prob min(1, p/q) — multiplied form, so q == 0 (cannot
        # happen for a drafter-sampled token, but stays total) never divides
        accept = u * q_d <= p_d
        residual = jnp.maximum(pf[:, :k] - qf, 0.0)
        rsum = residual.sum(axis=-1, keepdims=True)
        # degenerate residual (p == q exactly): fall back to sampling p
        resid = jnp.where(rsum > 0, residual / jnp.maximum(rsum, 1e-20), pf[:, :k])
        fix = []
        for j in range(k + 1):
            dist = resid[:, j] if j < k else pf[:, k]
            logd = jnp.where(dist > 0, jnp.log(jnp.maximum(dist, 1e-38)), -jnp.inf)
            keys = jax.vmap(lambda key: jax.random.fold_in(key, 2))(step_keys[j])
            fix.append(
                jax.vmap(lambda row, key: jax.random.categorical(key, row))(logd, keys)
            )
    else:
        flag = jnp.argmax(p_logits, axis=-1)  # (B, k+1)
        accept = flag[:, :k] == drafts
        fix = [flag[:, j] for j in range(k + 1)]

    cum = jnp.cumprod(accept.astype(jnp.int32), axis=1)  # (B, k)
    n_acc = cum.sum(axis=1)  # (B,) leading accepts
    m = n_acc + 1  # emitted tokens this span, in [1, k+1]

    pad = jnp.int32(config.pad_token_id)
    toks = []
    d_carry = done
    for j in range(k + 1):
        drafted = drafts[:, j] if j < k else jnp.zeros_like(fix[j])
        raw = jnp.where(j < n_acc, drafted, jnp.where(j == n_acc, fix[j], pad))
        emitted = j < m
        if config.eos_token_id is not None:
            # the sequential EOS discipline per emitted token: pad after
            # done, done latches on the emitted token — positions beyond m
            # never advance the flag
            raw = jnp.where(d_carry, pad, raw)
            d_carry = jnp.where(emitted, d_carry | (raw == config.eos_token_id), d_carry)
        toks.append(jnp.where(emitted, raw, pad).astype(jnp.int32))
    tokens = jnp.stack(toks, axis=1)  # (B, k+1)

    new_token = jnp.take_along_axis(tokens, n_acc[:, None], axis=1)[:, 0]
    rng_new = jnp.take_along_axis(chain_stack, m[:, None, None], axis=1)[:, 0]
    return tokens, m, new_token, rng_new, d_carry


def _validate_no_slide(mcfg, seq_len: int, num_latents: int, config: GenerationConfig):
    """Speculative decode scores k+1 query positions against the caches in
    one forward; a window that slides MID-SPAN would need a different
    expiry mask per query position, which the single slot-aligned pad mask
    cannot express — so, exactly like :func:`beam_search`, the speculative
    paths require geometry where the windows never fill during decode and
    fail loudly otherwise."""
    n_lat = min(seq_len, num_latents)
    if (
        seq_len + config.max_new_tokens > mcfg.max_seq_len
        or n_lat + config.max_new_tokens > mcfg.max_latents
    ):
        raise ValueError(
            "speculative decode does not slide the window: need "
            f"seq_len + max_new_tokens <= max_seq_len ({seq_len} + "
            f"{config.max_new_tokens} vs {mcfg.max_seq_len}) and "
            f"num_latents + max_new_tokens <= max_latents ({n_lat} + "
            f"{config.max_new_tokens} vs {mcfg.max_latents})"
        )


def make_speculative_decode_fns(
    model,
    num_latents: int = 1,
    config: Optional[GenerationConfig] = None,
    *,
    k: int = 4,
    draft_depth: int = 1,
    cache_dtype=jnp.float32,
    weight_dtype=None,
):
    """The speculative host-driven pair: ``(prefill_fn, spec_step_fn)``.

    - ``prefill_fn(params, input_ids, pad_mask=None, rng=None) ->
      (first_token, state)`` — the :func:`make_decode_fns` prefill contract
      (batch 1: this pair's Perceiver AR caches keep one length for the
      batch; batched speculative decode of Perceiver AR is the engine's
      paged slot mode, :func:`make_speculative_paged_step_fn`, and a model
      that drafts for itself runs batched over caches with a length a row,
      :func:`_generate_speculative`) plus the drafter wiring: the
      drafter's caches are the flagship prefill caches' PREFIX (CA + first
      ``draft_depth`` SA layers — shared weights make them identical, see
      :func:`make_drafter`), so there is no second prompt pass. Caches get
      ``k + 1`` slots of slack for the transient pre-rollback span.
    - ``spec_step_fn(state) -> (state, tokens (1, k+1), m (1,))`` — ONE
      draft/verify span: the drafter proposes k tokens autoregressively
      (k+1 single-token drafter steps in a compiled scan — the last append
      keeps the drafter cache current through an all-accept span), the
      flagship scores all k+1 positions in ONE batched forward against its
      KV cache (the prefill geometry with tiny q — no per-token flagship
      loop), and :func:`_speculative_accept` emits ``m ∈ [1, k+1]`` tokens.
      The caller streams ``tokens[:, :m]`` and calls again while budget
      remains. Rollback of the rejected span suffix is a LENGTH-COUNTER
      adjustment on every cache (static shapes, no concat/gather — the
      ``decode_spec`` graphcheck contract pins this).

    Greedy output is token-exact to the sequential pair (pinned by
    tests/test_speculative.py); temperature sampling is distribution-faithful
    with the rng chain advanced one split per emitted token, so seeds
    reproduce and the chain state matches the sequential path at every
    emitted-token count.
    """
    config = config or GenerationConfig()
    if config.max_new_tokens < 1:
        raise ValueError("speculative decode fns require max_new_tokens >= 1")
    if k < 1:
        raise ValueError(f"k (draft tokens per span) must be >= 1, got {k}")
    mcfg = model.config
    drafter = make_drafter(model, draft_depth)
    compute_dtype = None if weight_dtype is None else getattr(model, "dtype", jnp.float32)

    def prefill(params, input_ids, pad_mask=None, rng=None):
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        b, seq_len = input_ids.shape
        if b != 1:
            raise ValueError(
                "the speculative host-driven pair serves batch 1: its Perceiver AR caches "
                "(core/cache.py::KVCache) keep one length for the batch, and ragged "
                "accepted-prefix lengths need one a row (core/cache.py::RaggedKVCache holds "
                "one, for the decoder-only model's own drafting) — batched speculative "
                "decode of Perceiver AR is the engine's paged slot mode"
            )
        prefix_len = _validate_window(mcfg, seq_len, num_latents)
        _require_pads_in_prefix(pad_mask, prefix_len)
        _validate_no_slide(mcfg, seq_len, num_latents, config)

        from perceiver_io_tpu.core.modules import CausalSequenceModel

        # + k + 1 slack: a verify span transiently appends k+1 tokens
        # before rollback trims the rejected suffix
        ca_capacity = seq_len + config.max_new_tokens + k + 1
        sa_capacity = num_latents + config.max_new_tokens + k + 1
        cache = CausalSequenceModel.init_cache(
            mcfg, b, ca_capacity=ca_capacity, sa_capacity=sa_capacity, dtype=cache_dtype
        )
        if pad_mask is None:
            pad_mask = jnp.zeros((b, seq_len), bool)
        pos_shift = pad_mask.sum(axis=1, keepdims=True).astype(jnp.int32)
        pad_slots = jnp.zeros((b, ca_capacity), bool).at[:, :seq_len].set(pad_mask)

        with jax.named_scope("prefill"), prefill_mode():
            out = model.apply(
                params, input_ids, prefix_len=prefix_len, pad_mask=pad_mask, kv_cache=cache
            )
        rng, first_rng = jax.random.split(rng)
        next_token = _sample(out.logits[:, -1], first_rng, config)
        done = jnp.zeros((b,), bool)
        if config.eos_token_id is not None:
            done = next_token == config.eos_token_id

        decode_params, _ = _maybe_quantize_weights(model, params, weight_dtype)
        state = {
            "params": decode_params,
            "cache": out.kv_cache,
            # the drafter's caches ARE the flagship prefill caches' prefix
            # (shared trunk weights — see make_drafter); functional updates
            # keep the two streams independent from here on
            "draft_cache": (out.kv_cache[0],) + tuple(out.kv_cache[1 : 1 + draft_depth]),
            "token": next_token,
            "rng": rng,
            "done": done,
            "pad_slots": pad_slots,
            "pos_shift": pos_shift,
        }
        return next_token, state

    def step(state):
        with jax.named_scope("decode_spec"):
            cache, dcache = state["cache"], state["draft_cache"]
            token, rng, done = state["token"], state["rng"], state["done"]
            pad_slots, pos_shift = state["pad_slots"], state["pos_shift"]
            step_params = _maybe_dequantize_weights(state["params"], compute_dtype)
            dparams = drafter_decode_params(state["params"], draft_depth)

            with jax.named_scope("draft"):
                draft_base = jax.random.fold_in(rng, _DRAFT_SALT)

                def body(carry, i):
                    dc, cur = carry
                    dp = _maybe_dequantize_weights(dparams, compute_dtype)
                    out = drafter.apply(
                        dp, cur[:, None], prefix_len=0, pad_mask=pad_slots,
                        kv_cache=dc, decode=True, pos_shift=pos_shift,
                    )
                    logits = out.logits[:, -1]
                    if config.do_sample:
                        nxt = jax.random.categorical(
                            jax.random.fold_in(draft_base, i),
                            _filtered_logits(logits, config),
                            axis=-1,
                        )
                    else:
                        nxt = jnp.argmax(logits, axis=-1)
                    return (out.kv_cache, nxt), (nxt, logits)

                # k+1 drafter steps: k proposals + one catch-up append so the
                # drafter cache holds d_{k-1}'s kv through an all-accept span
                (dcache_full, _), (draft_seq, q_seq) = lax.scan(
                    body, (dcache, token), jnp.arange(k + 1)
                )
                drafts = draft_seq[:k].T  # (1, k)
                q_logits = jnp.moveaxis(q_seq[:k], 0, 1)  # (1, k, V)

            with jax.named_scope("verify"):
                # ONE flagship forward scores all k+1 positions against the
                # cache — the prefill geometry with tiny q; appends ride the
                # same dynamic_update_slice discipline (no kv-axis concat)
                inputs = jnp.concatenate([token[:, None], drafts], axis=1)
                out = model.apply(
                    step_params, inputs, prefix_len=0, pad_mask=pad_slots,
                    kv_cache=cache, decode=True, pos_shift=pos_shift,
                )
                cache_full, p_logits = out.kv_cache, out.logits

            with jax.named_scope("accept"):
                tokens, m, new_token, rng_rows, done = _speculative_accept(
                    config, drafts, q_logits, p_logits, rng[None], done
                )

            with jax.named_scope("rollback"):
                # static-shape rollback: both spans appended k+1 slots; the
                # accepted prefix is a length-counter adjustment — rejected
                # slots are dead until the next span overwrites them
                m0 = m[0]

                def roll(c):
                    return c.replace(length=c.length - (k + 1) + m0)

                cache_new = tuple(roll(c) for c in cache_full)
                dcache_new = tuple(roll(c) for c in dcache_full)

            new_state = dict(
                state, cache=cache_new, draft_cache=dcache_new,
                token=new_token, rng=rng_rows[0], done=done,
            )
            return new_state, tokens, m

    return jax.jit(prefill), jax.jit(step)


def make_speculative_paged_step_fn(
    model,
    config: Optional[GenerationConfig] = None,
    *,
    k: int = 4,
    draft_depth: int = 1,
    weight_dtype=None,
):
    """The engine's SPECULATIVE batched step: ``fn(params, state) ->
    (state, tokens (S, k+1), m (S,))`` over the paged state pytree of
    :func:`make_paged_step_fn` extended with ``draft_cache`` (a paged CA
    pool + the first ``draft_depth`` SA pools, mirroring the flagship
    pools' geometry and page ids — ``serving.engine`` owns the mirrored
    ``commit_prefill``/``release_slot`` bookkeeping).

    One drafter span (k+1 single-token paged steps in a compiled scan) +
    ONE flagship verify forward over all k+1 positions per engine step;
    per-slot acceptance, rng chains, done flags and length rollbacks —
    ragged accepted-prefix lengths are NATIVE to the paged discipline's
    per-slot length counters (rollback subtracts per slot; no bytes move).
    Inactive slots draft/verify garbage into their scratch page exactly as
    the non-speculative step does — the compiled program is total over all
    slots at every fill level. Requires no-slide geometry (the engine
    validates at construction). State is donated like the plain step."""
    config = config or GenerationConfig()
    if k < 1:
        raise ValueError(f"k (draft tokens per span) must be >= 1, got {k}")
    drafter = make_drafter(model, draft_depth)
    compute_dtype = None if weight_dtype is None else getattr(model, "dtype", jnp.float32)

    def step(params, state):
        with jax.named_scope("decode_spec"):
            cache, dcache = state["cache"], state["draft_cache"]
            token, rng, done = state["token"], state["rng"], state["done"]
            pos_shift = state["pos_shift"]
            ca_idx = jnp.arange(cache[0].capacity, dtype=jnp.int32)[None, :]
            pad_rows = state["pad_slots"] | (ca_idx < state["ca_start"][:, None])
            step_params = _maybe_dequantize_weights(params, compute_dtype)
            dparams = drafter_decode_params(params, draft_depth)

            with jax.named_scope("draft"):
                draft_base = jax.vmap(
                    lambda key: jax.random.fold_in(key, _DRAFT_SALT)
                )(rng)

                def body(carry, i):
                    dc, cur = carry
                    dp = _maybe_dequantize_weights(dparams, compute_dtype)
                    out = drafter.apply(
                        dp, cur[:, None], prefix_len=0, pad_mask=pad_rows,
                        kv_cache=dc, decode=True, pos_shift=pos_shift,
                    )
                    logits = out.logits[:, -1]
                    if config.do_sample:
                        keys = jax.vmap(lambda key: jax.random.fold_in(key, i))(draft_base)
                        fl = _filtered_logits(logits, config)
                        nxt = jax.vmap(
                            lambda row, key: jax.random.categorical(key, row)
                        )(fl, keys)
                    else:
                        nxt = jnp.argmax(logits, axis=-1)
                    return (out.kv_cache, nxt), (nxt, logits)

                (dcache_full, _), (draft_seq, q_seq) = lax.scan(
                    body, (dcache, token), jnp.arange(k + 1)
                )
                drafts = draft_seq[:k].T  # (S, k)
                q_logits = jnp.moveaxis(q_seq[:k], 0, 1)  # (S, k, V)

            with jax.named_scope("verify"):
                inputs = jnp.concatenate([token[:, None], drafts], axis=1)
                out = model.apply(
                    step_params, inputs, prefix_len=0, pad_mask=pad_rows,
                    kv_cache=cache, decode=True, pos_shift=pos_shift,
                )
                cache_full, p_logits = out.kv_cache, out.logits

            with jax.named_scope("accept"):
                tokens, m, new_token, rng_new, done = _speculative_accept(
                    config, drafts, q_logits, p_logits, rng, done
                )

            with jax.named_scope("rollback"):
                # per-slot rollback: lengths are (S,) int32 — the ragged
                # accepted prefixes land as a counter subtraction per slot
                def roll(c):
                    return c.replace(length=c.length - (k + 1) + m)

                cache_new = tuple(roll(c) for c in cache_full)
                dcache_new = tuple(roll(c) for c in dcache_full)

            new_state = dict(
                state, cache=cache_new, draft_cache=dcache_new,
                token=new_token, rng=rng_new, done=done,
            )
            return new_state, tokens, m

    return jax.jit(step, donate_argnums=1)


@dataclass
class GenerationStats:
    """Host-measured serving telemetry for one generate request (the
    per-request numbers TPU serving comparisons gate on)."""

    batch: int
    prompt_len: int
    new_tokens: int  # requested
    prefill_s: float  # TTFT: prompt pass + first token on the host clock
    decode_s: float  # wall time for the remaining tokens
    per_token_s: float  # MEAN TPOT — the percentiles live in the event/fields below
    tokens_per_sec: float  # batch * tokens_out / (prefill_s + decode_s)
    compiled: bool  # True when THIS call paid a compile (timings include it)
    # --- Spanline (PR 8) per-request SLO fields -------------------------
    ttft_s: float = 0.0  # == prefill_s (serving-literature name)
    tokens_out: int = 0  # tokens actually produced (== new_tokens unless aborted)
    # terminal outcome of THIS call: "ok" | "error" | "timeout" | "cancelled"
    # ("shed" never reaches this wrapper — a shed request is rejected at
    # admission by the serving front end and never decodes)
    outcome: str = "ok"
    tpot_p50_s: Optional[float] = None  # histogram-derived decode percentiles
    tpot_p90_s: Optional[float] = None
    tpot_p99_s: Optional[float] = None
    # --- Loadline (PR 11) admission telemetry ---------------------------
    # time the request sat queued before the worker picked it up (measured
    # by the caller — obs/loadgen.py — and handed in per call); None when
    # the caller did no admission accounting
    queue_wait_s: Optional[float] = None
    # --- Shedline (PR 12) serving-hardening fields ----------------------
    # worst per-token non-finite-logit fraction (probes=True only): the
    # sentinel signal the front end's circuit breaker feeds on
    nonfinite_logit_frac: Optional[float] = None


def make_instrumented_generate_fn(
    model,
    num_latents: int = 1,
    config: Optional[GenerationConfig] = None,
    cache_dtype=jnp.float32,
    weight_dtype=None,
    events=None,
    registry=None,
    on_token=None,
    snapshot_interval_s: float = 30.0,
    probes: bool = False,
):
    """``fn(params, input_ids, pad_mask, rng) -> (tokens, GenerationStats)``
    — the serving measurement wrapper: host-driven decode
    (:func:`make_decode_fns`) with EVERY token individually host-timed.

    Per call it records TTFT (prompt pass + first token) and a real
    per-token decode-latency distribution — each token's wall time lands in
    a log-bucketed ``obs.metrics.Histogram``, and the ``request`` event
    emitted per call carries TTFT, TPOT p50/p90/p99 **from that histogram**
    (not means), tokens in/out, the cache geometry, the sparse bucket
    counts (``obs.slo`` merges them into run-level percentiles) and the
    outcome. A request that dies mid-decode still emits its event with
    ``outcome="error"`` and the partial TPOT data before the exception
    re-raises (the same except-and-reraise guarantee ``fit_end`` makes);
    an ``on_token`` callback raising :class:`GenerationAborted` /
    :class:`GenerationDeadlineExceeded` instead classifies the event as
    ``cancelled`` / ``timeout`` — the mid-decode cancellation seam the
    serving front end (``perceiver_io_tpu.serving``) enforces deadlines
    through. Either way the exception re-raises with the partial
    ``GenerationStats`` attached as ``e.generation_stats``.

    The per-token host dispatch costs more than :func:`make_generate_fn`'s
    fused scan — this is the measurement wrapper for serving telemetry and
    A/Bs, not the peak-throughput path. Compiles are tracked (surfaced as
    ``compile`` events, attributed to the request's span): a call that
    compiled reports wall times including the compile and says so in
    ``stats.compiled``.

    Admission telemetry (the Loadline seam, obs/loadgen.py): callers that
    do their own queueing pass ``fn(..., queue_wait_s=..., arrival_ts=...)``
    per request — queue wait lands on the ``request`` event, the request
    span and the ``generate_queue_wait_s`` registry histogram, so the
    per-request tail breakdown (``obs.slo.request_breakdowns``) can
    attribute a slow request to queueing vs prefill vs decode vs compile.

    ``registry`` (an ``obs.metrics.MetricsRegistry``; fresh one per fn when
    None) accumulates cross-request counters/histograms and snapshots into
    ``metrics`` event rows at most every ``snapshot_interval_s``.
    ``on_token(i, token_array)`` observes each decoded token — the seam a
    streaming consumer (or an abort-injection test) hangs off.

    ``probes=True`` compiles the Probeline decode-health gauges into the
    step (``make_decode_fns(probes=True)``): KV-cache occupancy and logit
    entropy are published into the registry (``generate_kv_cache_frac``
    gauge, ``generate_logit_entropy`` histogram — the admission/SLO inputs
    the ROADMAP-1 scheduler reads) and onto each ``request`` event
    (``kv_cache_frac``, ``logit_entropy_mean``/``_last``,
    ``nonfinite_logit_frac``). Health arrays are collected per token but
    host-fetched ONCE per request, after the decode loop.
    """
    config = config or GenerationConfig()
    if config.max_new_tokens < 1:
        raise ValueError("instrumented generation requires max_new_tokens >= 1")
    from perceiver_io_tpu.obs import trace as obs_trace
    from perceiver_io_tpu.obs.metrics import Histogram, MetricsRegistry
    from perceiver_io_tpu.obs.recompile import RecompileTracker

    tracker = RecompileTracker(events=events)
    prefill_raw, step_raw = make_decode_fns(
        model, num_latents, config, cache_dtype, weight_dtype, probes=probes
    )
    decoder = _decoder_of(model)
    # the cache's geometry rides the prompt pass's ``compile`` row (nothing for Perceiver AR, whose
    # request rows carry ca_capacity/sa_capacity)
    prefill_fn = tracker.wrap(
        prefill_raw, "generate_prefill",
        extra=lambda args, kwargs: decoder.compile_row(*args[1].shape, config.max_new_tokens, cache_dtype),
    )
    step_fn = tracker.wrap(step_raw, "generate_decode_step")
    registry = registry if registry is not None else MetricsRegistry()
    m_requests = registry.counter("generate_requests_total")
    m_cold = registry.counter("generate_cold_requests_total")
    m_errors = registry.counter("generate_request_errors_total")
    m_timeouts = registry.counter("generate_request_timeouts_total")
    m_cancelled = registry.counter("generate_request_cancelled_total")
    m_tokens = registry.counter("generate_tokens_out_total")
    # WARM samples only: the cross-request histograms feed dashboards
    # (Prometheus export / metrics snapshots) that never reset, so one
    # compile-inflated sample would poison their tails forever. The
    # per-request event still reports what THAT request experienced,
    # compile included, flagged by `compiled` — consumers exclude it.
    m_ttft = registry.histogram("generate_ttft_s")
    m_tpot = registry.histogram("generate_tpot_s")
    # queue wait is admission telemetry, not compute latency: recorded for
    # every request that carries one (a compile stall upstream genuinely
    # grows the queue — excluding cold requests would hide real backlog)
    m_queue = registry.histogram("generate_queue_wait_s")
    m_entropy = registry.histogram("generate_logit_entropy") if probes else None
    m_kv_frac = registry.gauge("generate_kv_cache_frac") if probes else None
    # an expert layer's routed-pair books (``core/moe.py`` taps), where the model has them
    moe_taps = probes and "moe.*" in decoder.tap_scopes
    m_moe_routed = registry.counter("moe_pairs_routed_total") if moe_taps else None
    m_moe_local = registry.counter("moe_pairs_local_total") if moe_taps else None
    m_moe_gathered = registry.counter("moe_pairs_gathered_total") if moe_taps else None
    m_moe_dropped = registry.counter("moe_pairs_dropped_total") if moe_taps else None
    m_moe_load = registry.gauge("moe_expert_load_max") if moe_taps else None
    # the grouped kernels' visits and weight-block fetches beside the blocks of the experts hit (fetches / blocks is 1.0
    # where an expert's weights stay in VMEM across its visits)
    moe_fetch_keys = ("expert_visits", "expert_weight_fetches", "expert_weight_blocks")
    m_moe_fetch = [registry.counter(f"moe_{k}_total") for k in moe_fetch_keys] if moe_taps else None
    # a state-space layer's recurrent state (``core/ssm.py`` taps ``ssm.state``): its largest element, its non-finite ones
    ssm_taps = probes and "ssm.*" in decoder.tap_scopes
    m_ssm_abs_max = registry.gauge("ssm_state_abs_max") if ssm_taps else None
    m_ssm_nonfinite = registry.counter("ssm_state_nonfinite_total") if ssm_taps else None
    # a retention layer's state (``core/retention.py`` taps ``ret.state``): the same two readings of ``S``
    ret_taps = probes and "ret.*" in decoder.tap_scopes
    m_ret_abs_max = registry.gauge("ret_state_abs_max") if ret_taps else None
    m_ret_nonfinite = registry.counter("ret_state_nonfinite_total") if ret_taps else None
    # a delta layer's state (``core/kda.py`` taps ``kda.state``): the same two readings of ``S``, its mean decay and step
    kda_taps = probes and "kda.*" in decoder.tap_scopes
    m_kda_abs_max = registry.gauge("kda_state_abs_max") if kda_taps else None
    m_kda_nonfinite = registry.counter("kda_state_nonfinite_total") if kda_taps else None
    m_kda_decay = registry.gauge("kda_decay_mean") if kda_taps else None
    m_kda_beta = registry.gauge("kda_beta_mean") if kda_taps else None
    # a decoder-hybrid-decoder stack (``core/diff_attention.py`` taps ``yoco.cache`` a reading layer and ``yoco.lam`` an
    # attention, ``core/ssm.py`` ``gmu.memory`` a unit): the one cache's length and bytes, its reads, the memory's rms
    yoco_taps = probes and "yoco.*" in decoder.tap_scopes
    m_yoco_length = registry.gauge("yoco_cache_length") if yoco_taps else None
    m_yoco_bytes = registry.gauge("yoco_cache_bytes") if yoco_taps else None
    m_yoco_reads = registry.counter("yoco_cache_reads_total") if yoco_taps else None
    m_lam = registry.gauge("diff_lam_mean") if yoco_taps else None
    gmu_taps = probes and "gmu.*" in decoder.tap_scopes
    m_gmu_rms = registry.gauge("gmu_memory_rms") if gmu_taps else None
    # latent attention that chooses its keys (``core/dsa.py`` taps ``dsa.select`` a full layer, in the pass and in a step):
    # the keys a query keeps, and the share of them that lies within the window layers' window
    dsa_taps = probes and "dsa.*" in decoder.tap_scopes
    m_dsa_selected = registry.gauge("dsa_selected_keys_mean") if dsa_taps else None
    m_dsa_recent = registry.gauge("dsa_recent_share") if dsa_taps else None
    # a model that drafts for itself (a ``speculative`` decoder): a step yields 0 to 2 tokens a row, every
    # step is host-timed as one TPOT sample, and the ``spec.step`` taps keep the drafting's books
    self_drafting = getattr(decoder, "speculative", False)
    spec_taps = probes and self_drafting
    m_spec_steps = registry.counter("spec_steps_total") if self_drafting else None
    m_spec_drafts = registry.counter("spec_drafts_total") if spec_taps else None
    m_spec_accepted = registry.counter("spec_accepted_total") if spec_taps else None
    m_spec_rate = registry.gauge("spec_accept_rate") if spec_taps else None
    tracer = obs_trace.Tracer(events, flush_every=64) if events is not None else None

    def fn(params, input_ids, pad_mask=None, rng=None, queue_wait_s=None, arrival_ts=None,
           tenant=None):
        b, prompt_len = input_ids.shape
        compiles_before = tracker.total_compiles
        request_id = obs_trace.new_span_id()
        hist = Histogram("tpot_s")  # THIS request's decode latencies
        toks = []
        spans = []  # a self-drafting model's steps: (tokens (B, 2), how many of them each row emitted)
        healths = []  # device-array health dicts; fetched once, after the loop
        outcome, err = "ok", None
        ttft = 0.0
        if queue_wait_s is not None:
            queue_wait_s = float(queue_wait_s)
            m_queue.record(queue_wait_s)
        span_cm = (
            tracer.span("request", request_id=request_id)
            if tracer is not None
            else contextlib.nullcontext(None)
        )
        t_all0 = time.perf_counter()
        with span_cm as sp:
            try:
                # timings end in a host fetch of the sampled token: the
                # time to first token is the time until the host HAS it
                c0 = tracker.total_compiles
                t0 = time.perf_counter()
                token, state = prefill_fn(params, input_ids, pad_mask, rng)
                float(token[0])
                ttft = time.perf_counter() - t0
                if tracker.total_compiles == c0:
                    m_ttft.record(ttft)
                toks.append(token)
                if probes:
                    healths.append(state["probe"])
                if on_token is not None:
                    on_token(0, token)
                def timed_step(state, fetch):
                    """One host-timed step: the time ends when ``fetch(state, out)`` has the step's tokens on the host."""
                    c0 = tracker.total_compiles
                    t1 = time.perf_counter()
                    state, out = step_fn(state)
                    held = fetch(state, out)
                    dt = time.perf_counter() - t1
                    hist.record(dt)
                    if tracker.total_compiles == c0:
                        m_tpot.record(dt)
                    if probes:
                        healths.append(state["probe"])
                    return state, out, held

                if self_drafting:
                    while int(state["count"].min()) < config.max_new_tokens:
                        state, span, emitted = timed_step(state, lambda s, _: np.asarray(s["emitted"]))
                        spans.append((np.asarray(span), emitted))
                        m_spec_steps.inc()
                        if on_token is not None:
                            on_token(len(spans), span)
                else:
                    for i in range(1, config.max_new_tokens):
                        state, token, _ = timed_step(state, lambda _, t: float(t[0]))
                        toks.append(token)
                        if on_token is not None:
                            on_token(i, token)
            except BaseException as e:  # noqa: BLE001 — event out, then reraise
                # the cancellation seam: an on_token callback raising
                # GenerationAborted (deadline expiry, explicit cancel)
                # classifies by its declared outcome, not as an error
                outcome = e.outcome if isinstance(e, GenerationAborted) else "error"
                err = e
            if sp is not None:
                sp.set("outcome", outcome)
                sp.set("tokens_out", len(toks))
                if queue_wait_s is not None:
                    sp.set("queue_wait_s", round(queue_wait_s, 6))
                if tenant is not None:
                    sp.set("tenant", str(tenant))
        elapsed = time.perf_counter() - t_all0
        decode_s = max(elapsed - ttft, 0.0)
        # tokens every row has: a self-drafting model's rows emit 0 to 2 a step
        tokens_out = len(toks) + (int(sum(e for _, e in spans).min()) if spans else 0)
        compiled = tracker.total_compiles > compiles_before
        health_row = None
        if probes and healths:
            # one host fetch for the whole request's health arrays — the
            # per-token loop never blocked on them. Guarded: on an aborted
            # request these arrays came from the computation that FAILED and
            # the fetch may re-raise — the outcome="error" request event must
            # still go out (the same guarantee fit_end makes), with health
            # merely missing, and the ORIGINAL exception must stay the one
            # surfaced.
            try:
                hh = jax.device_get(healths)
                ents = [float(h["logit_entropy"]) for h in hh]
                kv_frac = float(hh[-1]["kv_cache_frac"])
                for e in ents:
                    m_entropy.record(e)
                m_kv_frac.set(kv_frac)
                health_row = {
                    "kv_cache_frac": round(kv_frac, 6),
                    "logit_entropy_mean": round(sum(ents) / len(ents), 6),
                    "logit_entropy_last": round(ents[-1], 6),
                    "nonfinite_logit_frac": round(
                        max(float(h["nonfinite_logit_frac"]) for h in hh), 6
                    ),
                }
                if moe_taps:
                    routed, local, gathered, dropped = (
                        sum(int(h[k]) for h in hh)
                        for k in ("pairs_routed", "pairs_local", "pairs_gathered", "pairs_dropped")
                    )
                    m_moe_routed.inc(routed)
                    m_moe_local.inc(local)
                    m_moe_gathered.inc(gathered)
                    m_moe_dropped.inc(dropped)
                    m_moe_load.set(max(int(h["expert_load_max"]) for h in hh))
                    for counter, k in zip(m_moe_fetch, moe_fetch_keys):
                        counter.inc(sum(int(h[k]) for h in hh))
                    health_row["moe_local_share"] = round(local / max(routed, 1), 6)
                    health_row["moe_pairs_dropped"] = dropped
                    if "pairs_zero" in hh[0]:  # experts without weights: the pairs they took, the most real experts a token ran
                        zero = sum(int(h["pairs_zero"]) for h in hh)
                        registry.counter("moe_pairs_zero_total").inc(zero)
                        registry.gauge("moe_real_experts_per_token").set(max(int(h["real_experts_per_token_max"]) for h in hh))
                        health_row["moe_zero_share"] = round(zero / max(routed, 1), 6)
                if ssm_taps:
                    health_row["ssm_state_abs_max"] = round(max(float(h["state_abs_max"]) for h in hh), 6)
                    health_row["ssm_state_nonfinite"] = sum(int(h["state_nonfinite"]) for h in hh)
                    m_ssm_abs_max.set(health_row["ssm_state_abs_max"])
                    m_ssm_nonfinite.inc(health_row["ssm_state_nonfinite"])
                if ret_taps:
                    health_row["ret_state_abs_max"] = round(max(float(h["ret_state_abs_max"]) for h in hh), 6)
                    health_row["ret_state_nonfinite"] = sum(int(h["ret_state_nonfinite"]) for h in hh)
                    m_ret_abs_max.set(health_row["ret_state_abs_max"])
                    m_ret_nonfinite.inc(health_row["ret_state_nonfinite"])
                if kda_taps:
                    health_row["kda_state_abs_max"] = round(max(float(h["kda_state_abs_max"]) for h in hh), 6)
                    health_row["kda_state_nonfinite"] = sum(int(h["kda_state_nonfinite"]) for h in hh)
                    sites = max(sum(int(h["kda_sites"]) for h in hh), 1)  # every layer of every call taps once
                    health_row["kda_decay_mean"] = round(sum(float(h["kda_decay_sum"]) for h in hh) / sites, 6)
                    health_row["kda_beta_mean"] = round(sum(float(h["kda_beta_sum"]) for h in hh) / sites, 6)
                    m_kda_abs_max.set(health_row["kda_state_abs_max"])
                    m_kda_nonfinite.inc(health_row["kda_state_nonfinite"])
                    m_kda_decay.set(health_row["kda_decay_mean"])
                    m_kda_beta.set(health_row["kda_beta_mean"])
                if yoco_taps:
                    health_row["yoco_cache_length"] = max(int(h["yoco_cache_length_max"]) for h in hh)
                    health_row["yoco_cache_bytes"] = int(max(float(h["yoco_cache_bytes_max"]) for h in hh))
                    health_row["yoco_cache_reads"] = sum(int(h["yoco_reads"]) for h in hh)
                    health_row["diff_lam_mean"] = round(sum(float(h["diff_lam_sum"]) for h in hh) / max(sum(int(h["diff_lam_sites"]) for h in hh), 1), 6)
                    health_row["diff_lam_max"] = round(max(float(h["diff_lam_max"]) for h in hh), 6)
                    m_yoco_length.set(health_row["yoco_cache_length"])
                    m_yoco_bytes.set(health_row["yoco_cache_bytes"])
                    m_yoco_reads.inc(health_row["yoco_cache_reads"])
                    m_lam.set(health_row["diff_lam_mean"])
                if gmu_taps:
                    health_row["gmu_memory_rms"] = round(sum(float(h["gmu_memory_rms_sum"]) for h in hh) / max(sum(int(h["gmu_sites"]) for h in hh), 1), 6)
                    m_gmu_rms.set(health_row["gmu_memory_rms"])
                if dsa_taps:
                    sites = max(sum(int(h["dsa_sites"]) for h in hh), 1)  # every full layer of the pass and of every step taps once
                    health_row["dsa_selected_mean"] = round(sum(float(h["dsa_selected_sum"]) for h in hh) / sites, 3)
                    health_row["dsa_selected_max"] = max(int(h["dsa_selected_max"]) for h in hh)
                    health_row["dsa_recent_share"] = round(sum(float(h["dsa_recent_share_sum"]) for h in hh) / sites, 6)
                    m_dsa_selected.set(health_row["dsa_selected_mean"])
                    m_dsa_recent.set(health_row["dsa_recent_share"])
                if spec_taps:
                    drafts, accepted = (sum(int(h[k]) for h in hh) for k in ("drafts", "accepted"))
                    m_spec_drafts.inc(drafts)
                    m_spec_accepted.inc(accepted)
                    m_spec_rate.set(accepted / max(drafts, 1))
                    health_row["spec_drafts"] = drafts
                    health_row["spec_accept_rate"] = round(accepted / max(drafts, 1), 6)
            except Exception:  # noqa: BLE001 — health is telemetry, never fatal
                health_row = None
        stats = GenerationStats(
            batch=b,
            prompt_len=prompt_len,
            new_tokens=config.max_new_tokens,
            prefill_s=round(ttft, 6),
            decode_s=round(decode_s, 6),
            per_token_s=round(decode_s / max(tokens_out - 1, 1), 6),
            tokens_per_sec=round(b * tokens_out / max(elapsed, 1e-9), 3),
            compiled=compiled,
            ttft_s=round(ttft, 6),
            tokens_out=tokens_out,
            outcome=outcome,
            tpot_p50_s=hist.percentile(50),
            tpot_p90_s=hist.percentile(90),
            tpot_p99_s=hist.percentile(99),
            queue_wait_s=None if queue_wait_s is None else round(queue_wait_s, 6),
            nonfinite_logit_frac=(
                None if health_row is None else health_row["nonfinite_logit_frac"]
            ),
        )
        m_requests.inc()
        m_tokens.inc(tokens_out * b)
        if compiled:
            m_cold.inc()
        if outcome == "error":
            m_errors.inc()
        elif outcome == "timeout":
            m_timeouts.inc()
        elif outcome == "cancelled":
            m_cancelled.inc()
        if events is not None:
            row = asdict(stats)
            row.update(
                request_id=request_id,
                span_id=None if tracer is None else sp.span_id,
                # cache geometry: the fixed-capacity windows this request
                # decoded against (the admission-relevant footprint)
                ca_capacity=prompt_len + config.max_new_tokens,
                sa_capacity=num_latents + config.max_new_tokens,
                num_latents=num_latents,
                tpot_hist=dict(sorted((str(k), v) for k, v in hist.counts.items())),
            )
            if health_row is not None:
                row.update(health_row)
            if health_row is None:
                row.pop("nonfinite_logit_frac", None)  # probes off / fetch failed
            if queue_wait_s is None:
                row.pop("queue_wait_s", None)  # no admission accounting upstream
            elif arrival_ts is not None:
                row["arrival_ts"] = round(float(arrival_ts), 6)
            if tenant is not None:
                # multi-tenant identity (Simline, docs/serving.md#multi-
                # tenant-telemetry): optional validated string field
                row["tenant"] = str(tenant)
            if hist.n and hist.n < 5:
                row["tpot_low_n"] = True
            if err is not None:
                row["error"] = repr(err)
            if row.get("span_id") is None:
                row.pop("span_id", None)  # let the ambient span stamp it
            # spans BEFORE the request row: a flight recorder triggering on
            # this request dumps its ring synchronously, and the ring must
            # already hold THIS request's span — the one the dump names
            if tracer is not None:
                tracer.flush()
            events.emit("request", **row)
            registry.maybe_emit(events, min_interval_s=snapshot_interval_s)
        if err is not None:
            # the caller sees the exception, not the return value — carry the
            # partial stats along so a serving front end can keep honest
            # books (tokens produced, partial TTFT/TPOT) for the dead request
            try:
                err.generation_stats = stats
            except Exception:  # noqa: BLE001 — slotted/frozen exception types
                pass
            raise err
        out = jnp.concatenate([input_ids] + [t[:, None] for t in toks], axis=1)
        if spans:  # each row's emitted tokens, in order: exactly max_new_tokens of them with the first
            rest = [np.concatenate([span[r, :e[r]] for span, e in spans]) for r in range(b)]
            out = jnp.concatenate([out, jnp.asarray(np.stack(rest), out.dtype)], axis=1)
        return out, stats

    fn.registry = registry  # exporter access (to_prometheus / snapshot)
    return fn
