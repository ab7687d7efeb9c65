"""A decoder-only language model: token embedding, blocks
``x + Attn(RMSNorm(x))`` then ``x + FFN(RMSNorm(x))``, a final RMSNorm and an
untied head (``docs/decoder-lm.md``). One class, two published families, told
apart by the configuration:

- **DeepSeek-V3** (``layer_types`` None): multi-head latent attention
  (``core/mla.py``) in every block, ``first_k_dense_replace`` blocks with a
  dense SwiGLU, then sigmoid-routed experts with a shared expert. The cache is
  one :class:`LatentCache` a layer; a prompt pass fills it through the
  expanded attention and a decode step reads it through the absorbed one.
- **Mellum 2** (``layer_types`` a tuple of ``"sliding_attention"`` and
  ``"full_attention"``): grouped-query attention (``core/gqa.py``) with the
  rotary of the layer's kind, softmax-routed experts in every block, no shared
  expert. A full layer's cache is a :class:`KVCache` that grows with the
  context, a window layer's a :class:`WindowKVCache` ring of
  ``sliding_window`` slots, both kinds side by side in one generator state.

Unlike the Perceiver models every position passes the whole stack, so there
is no latent window. The model meets :mod:`perceiver_io_tpu.generation`
through :meth:`DecoderLanguageModel.generation_decoder`.

The multi-token-prediction modules of the published models are not part of
serving (DeepSeek-V3's report section 2.2; Mellum's config has no key for
one) and are not here.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from perceiver_io_tpu.core.cache import (
    KVCache, LatentCache, WindowKVCache, init_kv_cache, init_latent_cache, init_window_kv_cache,
)
from perceiver_io_tpu.core.gqa import GroupedQueryAttention
from perceiver_io_tpu.core.mla import MultiHeadLatentAttention
from perceiver_io_tpu.core.moe import MoELayer, SwiGLU, grouped_combine
from perceiver_io_tpu.obs import probes
from perceiver_io_tpu.ops.layernorm import RMSNorm


_LAYER_TYPES = ("sliding_attention", "full_attention")


@dataclass(frozen=True)
class YarnConfig:
    factor: float = 40.0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    original_max_position_embeddings: int = 4096
    # grouped-query layers carry YaRN's temperature on cos and sin (Hugging Face's ``attention_factor``)
    attention_factor: float = 1.0


@dataclass(frozen=True)
class DecoderLanguageModelConfig:
    """Key names follow the published ``config.json`` of DeepSeek-V3.
    ``n_routed_experts`` is the router's width; ``n_held_experts`` of them,
    from ``held_experts_start``, live here (``None``: all of them).
    ``vocab_size`` is the number of rows held of the embedding and the head.

    ``layer_types`` (one entry a layer, ``"sliding_attention"`` or
    ``"full_attention"``) selects grouped-query attention with
    ``num_key_value_heads``, ``head_dim`` and ``sliding_window``, and
    ``rope_scaling`` then applies to the full layers only; ``None`` selects
    latent attention. ``scoring_func`` is the router's rule (``core/moe.py``)."""

    vocab_size: int = 129280
    hidden_size: int = 7168
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_held_experts: Optional[int] = None
    held_experts_start: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[YarnConfig] = YarnConfig()
    max_position_embeddings: int = 163840
    init_scale: float = 0.02
    scoring_func: str = "sigmoid"
    layer_types: Optional[Tuple[str, ...]] = None
    num_key_value_heads: Optional[int] = None
    head_dim: Optional[int] = None
    sliding_window: Optional[int] = None

    def __post_init__(self):
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            if len(self.layer_types) != self.num_hidden_layers or set(self.layer_types) - set(_LAYER_TYPES):
                raise ValueError(f"layer_types: one of {_LAYER_TYPES} for each of the {self.num_hidden_layers} layers")
            if not (self.num_key_value_heads and self.head_dim and self.sliding_window):
                raise ValueError("layer_types needs num_key_value_heads, head_dim and sliding_window")
            if self.num_attention_heads % self.num_key_value_heads:
                raise ValueError("num_key_value_heads must divide num_attention_heads")
        if self.n_held_experts is None:
            object.__setattr__(self, "n_held_experts", self.n_routed_experts)
        if self.held_experts_start + self.n_held_experts > self.n_routed_experts:
            raise ValueError("the held experts reach past the router's width")
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_group must divide n_routed_experts")


# How a prompt pass is cut, not what it computes: the tokens that go through
# attention at once (whole rows: a row's expanded queries, keys and values are
# 40 times its hidden state) and through the feed-forward at once. At the
# published widths, 65 536 prompt tokens and 9 GB of weights these keep the
# whole generator at 13.1 GB (compiled for a described v5e; PERF.md 6, PR 28).
_PREFILL_ATTENTION_TOKENS = 4096
_PREFILL_FFN_TOKENS = 8192


def _chunks(n: int, want: int) -> int:
    """The largest divisor of ``n`` that is at most ``want`` (at least 1)."""
    return max(d for d in range(1, n + 1) if n % d == 0 and d <= max(want, 1))


class DecoderBlock(nn.Module):
    config: DecoderLanguageModelConfig
    sparse: bool
    layer_type: Optional[str] = None  # None: latent attention
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        c = self.config
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        self.attn_norm = RMSNorm(epsilon=c.rms_norm_eps, **kw)
        if self.layer_type is None:
            self.attn = MultiHeadLatentAttention(c, **kw)
        else:
            self.attn = GroupedQueryAttention(c, window=self.layer_type == "sliding_attention", **kw)
        self.ffn_norm = RMSNorm(epsilon=c.rms_norm_eps, **kw)
        if self.sparse:
            self.ffn = MoELayer(c, **kw)
        else:
            self.ffn = SwiGLU(c.hidden_size, c.intermediate_size, c.init_scale, **kw)

    def attend(self, x, pos):
        """``x + Attn(RMSNorm(x))`` over whole rows, expanded; also the cache
        rows (latent attention: one array; grouped-query: rotated keys and
        values, of which a window layer hands on its last ``sliding_window``)."""
        a, rows = self.attn.expand(self.attn_norm(x), pos)
        if self.layer_type == "sliding_attention":
            rows = tuple(r[:, :, -self.config.sliding_window:] for r in rows)
        return x + a, rows

    def feed_forward(self, x):
        return x + self.ffn(self.ffn_norm(x))

    def step(self, x, cache, pos):
        one_token = self.attn.absorb if self.layer_type is None else self.attn.step
        a, cache = one_token(self.attn_norm(x), cache, pos)
        return self.feed_forward(x + a), cache


def _over_chunks(fn, x):
    """Apply ``fn`` to each ``x[i]`` of ``x`` (n, ...) and write the result
    over ``x[i]``: a loop that updates the one buffer in place (a ``lax.map``
    would hold the input and the stacked output both, the whole batch's
    hidden state twice). ``fn`` returns ``(chunk, aux)``; the ``aux`` of every
    chunk is stacked into (n, ...) buffers. Probe taps inside ``fn`` are
    carried out of the loop: counts summed over the chunks, ``*_max`` maxed."""
    n = x.shape[0]
    tapping = probes.active()

    def call(chunk):
        if not tapping:
            return fn(chunk), {}
        with probes.collecting(probes.current_config()) as col:
            out = fn(chunk)
        return out, col.stats

    (_, aux_shape), stats_shape = jax.eval_shape(call, x[0])
    aux = jax.tree.map(lambda s: jnp.zeros((n,) + s.shape, s.dtype), aux_shape)
    stats = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), stats_shape)

    def body(i, carry):
        x, aux, stats = carry
        (chunk, a), found = call(lax.dynamic_index_in_dim(x, i, 0, keepdims=False))
        x = lax.dynamic_update_index_in_dim(x, chunk, i, 0)
        aux = jax.tree.map(lambda buf, v: lax.dynamic_update_index_in_dim(buf, v, i, 0), aux, a)
        stats = {
            key: {name: (jnp.maximum if name.endswith("_max") else jnp.add)(stats[key][name], v)
                  for name, v in entry.items()}
            for key, entry in found.items()
        }
        return x, aux, stats

    x, aux, stats = lax.fori_loop(0, n, body, (x, aux, stats))
    for key, entry in stats.items():
        probes.tap(probes.scope_of(key), entry)
    return x, aux


class DecoderLanguageModel(nn.Module):
    config: DecoderLanguageModelConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        c = self.config
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        self.embedding = self.param(
            "embedding", nn.initializers.normal(c.init_scale), (c.vocab_size, c.hidden_size), self.param_dtype
        )
        self.layers = [
            DecoderBlock(c, sparse=i >= c.first_k_dense_replace, layer_type=c.layer_types and c.layer_types[i],
                         name=f"layer_{i}", **kw)
            for i in range(c.num_hidden_layers)
        ]
        self.out_norm = RMSNorm(epsilon=c.rms_norm_eps, **kw)
        self.head = self.param(
            "head", nn.initializers.normal(c.init_scale), (c.hidden_size, c.vocab_size), self.param_dtype
        )

    # the two ends and one layer's halves, as methods ``apply`` can reach: the
    # prompt pass (:func:`prefill`) loops them over chunks from outside the
    # module (a jax loop may not wrap a bound submodule's call)

    def embed(self, input_ids):
        return self.embedding[input_ids].astype(self.dtype)

    def logits(self, x):
        return jnp.dot(self.out_norm(x), self.head.astype(self.dtype), preferred_element_type=jnp.float32)

    def attend_layer(self, x, pos, i: int):
        return self.layers[i].attend(x, pos)

    def ffn_layer(self, x, i: int):
        return self.layers[i].feed_forward(x)

    def __call__(self, input_ids):
        """Logits (B, N, V) float32 of a full causal forward, no cache."""
        b, n = input_ids.shape
        pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None], (b, n))
        x = self.embed(input_ids)
        for layer in self.layers:
            x, _ = layer.attend(x, pos)
            x = layer.feed_forward(x)
        return self.logits(x)

    def decode_step(self, token, caches: Tuple[Union[LatentCache, KVCache, WindowKVCache], ...]):
        """One new token a row against the caches: logits (B, V) and the advanced caches."""
        b = token.shape[0]
        pos = jnp.broadcast_to(caches[0].length, (b, 1)).astype(jnp.int32)
        x = self.embed(token)[:, None]
        new = []
        for layer, cache in zip(self.layers, caches):
            x, cache = layer.step(x, cache, pos)
            new.append(cache)
        return self.logits(x[:, 0]), tuple(new)

    # ------------------------------------------------ the generator's side

    def generation_decoder(self):
        return _Decoder(self)


def prefill(model: DecoderLanguageModel, params, input_ids) -> Tuple[jnp.ndarray, tuple]:
    """The prompt pass: last-position logits (B, V) and, a layer, the cache
    rows of the prompt: (B, N, width) of a latent layer; of a grouped-query
    layer the keys and the values, each (B * Hkv, N, D), a window layer's
    last ``sliding_window`` positions only. The hidden state of the whole batch
    stays in memory between layers (B * N * h); within a layer the attention
    runs over chunks of whole rows and the feed-forward over chunks of tokens
    (``_PREFILL_ATTENTION_TOKENS``, ``_PREFILL_FFN_TOKENS``), inside the one
    program."""
    c = model.config
    b, n = input_ids.shape
    h = c.hidden_size
    rows_a = _chunks(b, _PREFILL_ATTENTION_TOKENS // n)
    tokens_f = _chunks(b * n, _PREFILL_FFN_TOKENS)
    pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None], (rows_a, n))

    def scoped(method, *args):
        # a loop's body does not inherit the scope around the loop: it is opened again inside
        with jax.named_scope("prefill"):
            return model.apply(params, *args, method=method)

    x = scoped("embed", input_ids)
    cache_rows = []
    for i in range(c.num_hidden_layers):
        x, rows = _over_chunks(lambda xc, i=i: scoped("attend_layer", xc, pos, i), x.reshape(b // rows_a, rows_a, n, h))
        if c.layer_types is None:
            cache_rows.append(rows.reshape(b, n, rows.shape[-1]))
        else:  # (chunks, rows a chunk, Hkv, positions, D): a key-value head is a row of the cache
            cache_rows.append(tuple(r.reshape(b * r.shape[2], *r.shape[3:]) for r in rows))
        x, _ = _over_chunks(lambda xc, i=i: (scoped("ffn_layer", xc, i), ()), x.reshape(b * n // tokens_f, tokens_f, h))
        x = x.reshape(b, n, h)
    return scoped("logits", x[:, -1]), tuple(cache_rows)


class _Decoder:
    """What :mod:`perceiver_io_tpu.generation` asks of a model (see
    ``generation._PerceiverARDecoder`` for the other one): the prompt pass,
    the one-token step, and the state they hand each other. The window is the
    tuple of the layers' caches, each of its layer's kind (latent; or, under
    grouped-query attention, a growing :class:`KVCache` for a full layer and a
    :class:`WindowKVCache` ring for a window layer, side by side). Nothing the
    generator owns slides: a growing cache's capacity is the prompt plus the
    new tokens, which must fit ``max_position_embeddings``, and a ring
    overwrites the position that left its window."""

    window_names = ("cache",)
    const_names = ()
    tap_scopes = ("moe.*",)

    def __init__(self, model: DecoderLanguageModel):
        self.model = model

    def _caches(self, rows, batch: int, n: int, max_new_tokens: int, cache_dtype):
        c = self.model.config
        if c.layer_types is None:
            return tuple(init_latent_cache(batch, n + max_new_tokens, r.shape[-1], cache_dtype).append(r) for r in rows)
        slots, d = batch * c.num_key_value_heads, c.head_dim
        return tuple(
            init_window_kv_cache(slots, c.sliding_window, d, d, cache_dtype).fill(k, v, n)
            if kind == "sliding_attention" else init_kv_cache(slots, n + max_new_tokens, d, d, cache_dtype).append(k, v)
            for kind, (k, v) in zip(c.layer_types, rows)
        )

    def prefill(self, params, input_ids, pad_mask, num_latents, max_new_tokens, cache_dtype):
        del num_latents  # no latent window: every position passes the whole stack
        c = self.model.config
        b, n = input_ids.shape
        if pad_mask is not None:
            raise ValueError("the decoder-only model takes no pad_mask: batch prompts of one length")
        if n + max_new_tokens > c.max_position_embeddings:
            raise ValueError(
                f"prompt ({n}) + max_new_tokens ({max_new_tokens}) exceeds max_position_embeddings "
                f"({c.max_position_embeddings})"
            )
        logits, rows = prefill(self.model, params, input_ids)
        caches = self._caches(rows, b, n, max_new_tokens, cache_dtype)
        return logits[:, None], (caches,), ()

    def step(self, step_params, window, consts, token):
        del consts
        logits, caches = self.model.apply(step_params, token, window[0], method="decode_step")
        return logits[:, None], (caches,)

    def health(self, logits, window):
        # the occupancy gauge reads a cache that grows: a ring is full from its window on
        grows = next((cache for cache in window[0] if not isinstance(cache, WindowKVCache)), window[0][0])
        return probes.decode_health(logits, grows, jnp.zeros((), jnp.int32))

    def compile_row(self, batch: int, prompt_len: int, max_new_tokens: int, cache_dtype) -> dict:
        """The caches' geometry for a ``compile`` event row."""
        c = self.model.config
        itemsize = jnp.dtype(cache_dtype).itemsize
        # how a prompt chunk's expert rows get back to their tokens (a step of few tokens takes the dense path)
        moe = {"moe_combine": grouped_combine(c.n_held_experts, c.n_routed_experts)}
        if c.layer_types is not None:
            row_bytes = 2 * c.num_key_value_heads * c.head_dim * itemsize  # a token's keys and values in one layer
            n_window = c.layer_types.count("sliding_attention")
            n_full = c.num_hidden_layers - n_window
            return {
                "kv_cache_full_layers": n_full,
                "kv_cache_window_layers": n_window,
                "kv_cache_full_bytes": batch * (prompt_len + max_new_tokens) * row_bytes * n_full,
                "kv_cache_window_bytes": batch * c.sliding_window * row_bytes * n_window,
                "kv_cache_window_rows": c.sliding_window,
                **moe,
            }
        row_bytes = (c.kv_lora_rank + c.qk_rope_head_dim) * itemsize
        return {
            "latent_cache_row_bytes": row_bytes,
            "latent_cache_capacity": prompt_len + max_new_tokens,
            "latent_cache_layers": c.num_hidden_layers,
            "latent_cache_bytes": batch * (prompt_len + max_new_tokens) * row_bytes * c.num_hidden_layers,
            **moe,
        }


__all__ = ["DecoderLanguageModel", "DecoderLanguageModelConfig", "YarnConfig"]
