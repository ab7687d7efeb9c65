"""A decoder-only language model: token embedding, blocks
``x + Mix(Norm(x))`` then ``x + FFN(Norm(x))``, a final norm and a head, untied
or the embedding table itself (``docs/decoder-lm.md``); the norm is an RMSNorm
or, where the configuration gives ``layer_norm_eps``, a LayerNorm with scale and
bias. One class, nine published families, told apart by the configuration (a third, K-EXAONE, puts the first's expert layer
on the second's skeleton and adds the drafting module below; a fourth,
LongCat-Flash, changes the block itself: **the shortcut-connected block**,
further down):

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

- **Jamba** and **Brumby** (``layer_types`` with ``"mamba"`` or
  ``"power_retention"`` entries): a layer whose mixer keeps a state of one size
  whatever the context in attention's place, a state-space layer's
  :class:`RecurrentState` (``core/ssm.py``) beside grouped-query layers, or a
  power retention layer's :class:`RetentionState` (``core/retention.py``) in
  every layer, which also carries the length a step's rotary position is read
  off; a dense SwiGLU in every block.

- **Ling 3.0** (``layer_types`` of ``"kda"`` and ``"latent_attention"``): Kimi
  delta attention layers (``core/kda.py``; arXiv:2510.26692), whose
  :class:`DeltaState` is a float32 ``S`` of ``[batch, heads, 128, 128]`` a
  layer, stored transposed, with three convolution windows and no length,
  beside latent attention **as a layer kind** (the first family's attention,
  here without a query latent and with a head-wise output gate), whose
  :class:`LatentCache` carries the length a step's position is read off;
  leading dense layers, then sigmoid-routed experts with a shared expert.

- **Phi-4-mini-flash** (SambaY; ``layer_types`` with ``"gmu"`` and
  ``"cross_attention"`` entries, ``differential_attention``): a
  decoder-hybrid-decoder stack. Below, state-space layers without Jamba's inner
  norms and differential window attention (``core/diff_attention.py``: the
  difference of two softmax maps a head pair under a learned scalar, an RMSNorm
  over the pair); one differential full attention whose :class:`KVCache` is
  **the shared cache**; above it gated memory units (``core/ssm.py``), which
  gate the last state-space layer's scan output with their own input and keep
  no state, and cross-attentions that project a query only and read the shared
  cache. **A layer that reads owns no entry of the generator's state**, which is
  one entry a layer that owns a cache or a state; the cache is written once a
  step and read by every layer above the one that owns it, never copied. The
  prompt pass runs the layers below the owning one over every position, that
  layer's key and value projections over the prompt, and everything above at
  the last position alone (``config.prompt_layers``, :func:`prefill`).

- **dots3-note** (``layer_types`` of ``"full_attention"`` and
  ``"sliding_attention"`` **with the ``swa_*`` sizes given**, which makes both
  entries *latent* attentions; ``core/dsa.py``): a full layer is the first
  family's latent attention **over the keys a lightning indexer selects**
  (``index_n_heads`` heads of ``index_head_dim`` score every earlier token,
  ``I = sum_j w_j relu(q_j . k)``, and the softmax runs over the
  ``index_topk`` best, found exactly; the indexer's one key a token is cached
  beside the latent row: an :class:`IndexedLatentCache`, two arrays that grow), a
  sliding layer a **second latent attention of other sizes** (rank, heads, head
  widths and rotary base its own) behind ``sliding_window_size`` positions, its
  cache a :class:`LatentRingCache` of latent rows: **three cache kinds in one
  generator state**. The prompt pass runs the expanded attention under the
  selection's mask, a step the absorbed attention over the gathered rows; both
  attentions rescale their normed latents and gate their heads; a leading dense
  layer, then sigmoid-routed experts with a shared expert.

Unlike the Perceiver models every position passes the whole stack (the
Phi-4-mini-flash family's prompt pass apart), so there is no latent window. The model meets :mod:`perceiver_io_tpu.generation`
through :meth:`DecoderLanguageModel.generation_decoder`.

**The multi-token-prediction module** (``num_nextn_predict_layers`` 1; the
K-EXAONE family, in DeepSeek-V3's form): ``u_i = W_eh [RMSNorm_e(Emb(t_{i+1}));
RMSNorm_h(h_i)]`` with ``h_i`` the last block's output at position i, one
block of its own over ``u`` (``mtp_layer_types[0]``, a sparse feed-forward),
its own final RMSNorm, the model's embedding and head: logits for
``t_{i+2}``. Where a configuration has it the generator drafts with it and a
step verifies two positions a row (``_Decoder``'s ``spec_*`` methods;
``generation._generate_speculative``): the caches then keep a length a row
(``core/cache.py``'s ragged classes). DeepSeek-V3's own published module is
still not built (its configuration here keeps ``num_nextn_predict_layers`` 0),
and Mellum's config has no key for one.

**The shortcut-connected block** (``block="shortcut"``; the LongCat-Flash
family, arXiv:2509.01322 section 2.2, the topology of arXiv:2404.05019): a
layer is two latent attentions and two dense SwiGLUs in series, and the expert
layer is a branch that reads the first sublayer's normed state and joins the
residual at the layer's end::

    a0 = h  + MLA_0(RMS(h))
    u  = RMS(a0)
    s  = MoE(u)                      # the shortcut: reads u, joins at the end
    b0 = a0 + FFN_0(u)
    a1 = b0 + MLA_1(RMS(b0))
    h' = a1 + FFN_1(RMS(a1)) + s

Every layer is such a block (no leading dense layers), a layer owns two
:class:`LatentCache` (the generator's state holds ``2 * num_hidden_layers``,
a layer's pair side by side), and the prompt pass runs a whole layer over a
chunk of whole rows, so the branch's output lives a chunk long. The expert
layer routes by the third rule of ``core/moe.py`` over experts of which
``zero_expert_num`` have no weights, and the attentions scale their normed
latents (``mla_scale_q_lora``, ``mla_scale_kv_lora``: ``core/mla.py``).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from perceiver_io_tpu.core.cache import (
    DeltaState, IndexedLatentCache, KVCache, LatentCache, LatentRingCache, RaggedKVCache, RaggedWindowKVCache, RecurrentState,
    RetentionState, WindowKVCache, init_indexed_latent_cache, init_kv_cache, init_latent_cache, init_latent_ring_cache,
    init_ragged_kv_cache, init_ragged_window_kv_cache, init_window_kv_cache,
)
from perceiver_io_tpu.core.diff_attention import DifferentialAttention
from perceiver_io_tpu.core.dsa import SparseLatentAttention, WindowLatentAttention, window_sizes
from perceiver_io_tpu.core.gqa import GroupedQueryAttention, verify_fused
from perceiver_io_tpu.core.kda import KimiDeltaAttention
from perceiver_io_tpu.core.mla import VIEWS, MultiHeadLatentAttention, expand_views
from perceiver_io_tpu.core.moe import MoELayer, SwiGLU, grouped_combine
from perceiver_io_tpu.core.retention import PowerRetention
from perceiver_io_tpu.core.ssm import GatedMemoryUnit, MambaMixer
from perceiver_io_tpu.obs import probes
from perceiver_io_tpu.ops.gqa_verify import verify_plan
from perceiver_io_tpu.ops.kda import chunk_of as kda_chunk_of, kda_plans
from perceiver_io_tpu.ops.layernorm import LayerNorm, RMSNorm
from perceiver_io_tpu.ops.mla_absorb import row_tile
from perceiver_io_tpu.ops.power_retention import chunk_of, feature_rows, power_retention_plans
from perceiver_io_tpu.ops.selective_scan import ssm_scan_plans


_ATTENTION_TYPES = ("sliding_attention", "full_attention")
_RETENTION = "power_retention"
_KDA = "kda"
_LATENT = "latent_attention"
_GMU = "gmu"
_CROSS = "cross_attention"
_READERS = (_GMU, _CROSS)  # layers that own no cache and no state: they read what a layer below handed on or wrote
_LAYER_TYPES = _ATTENTION_TYPES + ("mamba", _RETENTION, _KDA, _LATENT) + _READERS
_STATEFUL = ("mamba", _RETENTION, _KDA)  # a mixer whose past is a state of one size, handed on as it leaves the prompt pass
_POSITIONLESS = ("mamba", _KDA)  # of those, the mixers that read no position (``self.mixer``, not ``self.attn``)


def _latent(kind: Optional[str]) -> bool:
    """Whether a layer of ``kind`` attends through the latent cache: every layer where ``layer_types`` is ``None``."""
    return kind is None or kind == _LATENT
_BLOCKS = ("serial", "shortcut")


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
    latent attention. ``scoring_func`` is the router's rule (``core/moe.py``).
    ``qk_norm`` and ``full_attention_rotary`` are the grouped-query layers'
    (``core/gqa.py``). ``num_nextn_predict_layers`` 1 builds the
    multi-token-prediction module, a block of ``mtp_layer_types[0]``; the
    generator then drafts with it (no option selects that).

    A ``"mamba"`` entry of ``layer_types`` makes that layer's mixer a
    state-space layer (``core/ssm.py``) of ``mamba_expand``, ``mamba_d_state``,
    ``mamba_dt_rank`` and ``mamba_d_conv`` (the published keys of the Jamba
    family); ``first_k_dense_replace`` at the depth gives every layer the dense
    SwiGLU. ``tie_word_embeddings`` reads the logits off the embedding table
    (no ``head``).

    A ``"power_retention"`` entry makes that layer's mixer a power retention
    layer (``core/retention.py``) on the grouped-query sizes and rotary, at
    degree 2 (the power of the query-key product the kernels are written for).

    A ``"kda"`` entry makes that layer's mixer a Kimi delta attention layer
    (``core/kda.py``) of ``num_attention_heads`` heads of ``head_dim`` on q, k
    and v, with ``short_conv_kernel_size`` and ``kda_lower_bound`` (the
    published keys of the Ling 3.0 family); a ``"latent_attention"`` entry is
    the latent attention that ``layer_types`` ``None`` gives every layer.
    ``q_lora_rank`` ``None`` and ``mla_head_gate`` are that attention's
    (``core/mla.py``).

    With the ``swa_*`` sizes and ``sliding_window_size`` given (the published
    keys of the dots3-note family) ``"full_attention"`` and
    ``"sliding_attention"`` entries are latent attentions (``core/dsa.py``): a
    full layer of the configuration's own sizes, a sliding layer of the ``swa_*``
    ones behind the window (position ``t`` sees ``t - sliding_window_size < s <=
    t``); ``index_n_heads``, ``index_head_dim`` and ``index_topk`` (DeepSeek-V3.2's
    keys) give every full layer an indexer and a top-``index_topk`` selection."""

    vocab_size: int = 129280
    hidden_size: int = 7168
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_attention_heads: int = 128
    q_lora_rank: Optional[int] = 1536
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
    qk_norm: bool = False
    full_attention_rotary: bool = True
    num_nextn_predict_layers: int = 0
    mtp_layer_types: Tuple[str, ...] = ("full_attention",)
    block: str = "serial"
    zero_expert_num: int = 0
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_dt_rank: int = 160
    mamba_d_conv: int = 4
    tie_word_embeddings: bool = False
    mla_head_gate: bool = False
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    layer_norm_eps: Optional[float] = None
    differential_attention: bool = False
    mamba_inner_norms: bool = True
    # the dots3-note family (``core/dsa.py``): with the ``swa_*`` sizes given, ``layer_types`` of ``"full_attention"`` and
    # ``"sliding_attention"`` select *latent* attention, a full layer the configuration's own sizes and a sliding layer
    # the ``swa_*`` ones behind ``sliding_window_size`` positions; with ``index_topk`` a full layer chooses its keys
    index_n_heads: Optional[int] = None
    index_head_dim: Optional[int] = None
    index_topk: Optional[int] = None
    swa_q_lora_rank: Optional[int] = None
    swa_kv_lora_rank: Optional[int] = None
    swa_num_attention_heads: Optional[int] = None
    swa_qk_nope_head_dim: Optional[int] = None
    swa_qk_rope_head_dim: Optional[int] = None
    swa_v_head_dim: Optional[int] = None
    swa_rope_theta: Optional[float] = None
    sliding_window_size: Optional[int] = None

    @property
    def windowed_latent(self) -> bool:
        """Whether ``"full_attention"`` and ``"sliding_attention"`` entries of ``layer_types`` are latent attentions (the ``swa_*`` sizes say so)."""
        return self.swa_kv_lora_rank is not None

    def latent_kind(self, kind: Optional[str]) -> bool:
        """Whether a layer of ``kind`` is a latent attention (``absorb`` is its step), of whichever cache."""
        return _latent(kind) or (self.windowed_latent and kind in _ATTENTION_TYPES)

    @property
    def latent_ring_slots(self) -> int:
        """The slots of a window layer's ring of latent rows: the window, up to whole sublane tiles of any cache dtype."""
        return -(-self.sliding_window_size // 32) * 32

    @property
    def memory_layer(self) -> Optional[int]:
        """The state-space layer whose scan output the ``gmu`` layers read: the last ``mamba`` layer below the first of them."""
        kinds = self.layer_types or ()
        return max(i for i in range(kinds.index(_GMU)) if kinds[i] == "mamba") if _GMU in kinds else None

    @property
    def shared_cache_layer(self) -> Optional[int]:
        """The layer whose keys and values the ``cross_attention`` layers read: the last ``full_attention`` layer below the first of them."""
        kinds = self.layer_types or ()
        return max(i for i in range(kinds.index(_CROSS)) if kinds[i] == "full_attention") if _CROSS in kinds else None

    @property
    def prompt_layers(self) -> int:
        """How many layers a prompt pass runs over every position: readers
        alone stand above the layer that owns the shared cache, so no layer
        reads what it or they would compute at a position but the last."""
        shared = self.shared_cache_layer
        return self.num_hidden_layers if shared is None else shared

    def __post_init__(self):
        if self.block not in _BLOCKS:
            raise ValueError(f"block {self.block!r}: one of {_BLOCKS}")
        if self.block == "shortcut" and (self.layer_types is not None or self.first_k_dense_replace
                                         or self.num_nextn_predict_layers):
            raise ValueError("the shortcut-connected block: latent attention, an expert branch in every layer, no module")
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            if len(self.layer_types) != self.num_hidden_layers or set(self.layer_types) - set(_LAYER_TYPES):
                raise ValueError(f"layer_types: one of {_LAYER_TYPES} for each of the {self.num_hidden_layers} layers")
            attends = set() if self.windowed_latent else set(self.layer_types) & (set(_ATTENTION_TYPES) | {_RETENTION})
            if attends and not (self.num_key_value_heads and self.head_dim):
                raise ValueError("attention and retention entries of layer_types need num_key_value_heads and head_dim")
            if _RETENTION in attends and self.head_dim % 2:
                raise ValueError("a power_retention layer: the symmetric square over a head of even width is what is built")
            if "sliding_attention" in attends and not self.sliding_window:
                raise ValueError("a sliding_attention layer needs sliding_window")
            if _KDA in self.layer_types and not (self.head_dim and self.short_conv_kernel_size > 1 and self.kda_lower_bound < 0):
                raise ValueError("a kda layer needs head_dim, a convolution of at least 2 taps and a negative kda_lower_bound")
            if _KDA in self.layer_types and _LATENT not in self.layer_types:
                # a delta state has no length: a step reads its position off the latent cache beside it
                raise ValueError("kda layers stand beside at least one latent_attention layer")
            if {_KDA, _LATENT} & set(self.layer_types) and set(self.layer_types) - {_KDA, _LATENT}:
                raise ValueError("kda and latent_attention entries mix with each other alone")
            if attends and self.num_attention_heads % self.num_key_value_heads:
                raise ValueError("num_key_value_heads must divide num_attention_heads")
            kinds = self.layer_types
            if _GMU in kinds and "mamba" not in kinds[:kinds.index(_GMU)]:
                raise ValueError("a gmu layer reads the memory of a mamba layer below it")
            if _CROSS in kinds and "full_attention" not in kinds[:kinds.index(_CROSS)]:
                raise ValueError("a cross_attention layer reads the cache of a full_attention layer below it")
            if _CROSS in kinds and not self.differential_attention:
                raise ValueError("a cross_attention layer is built in the differential form alone")
            if set(kinds) & set(_READERS) and (_CROSS not in kinds or set(kinds[self.shared_cache_layer + 1:]) - set(_READERS)):
                # what the cut prompt pass rests on: nothing above the owning layer is read at a position but the last
                raise ValueError("gmu and cross_attention layers, and they alone, stand above the layer that owns the shared cache")
            if self.differential_attention and self.num_key_value_heads % 2:
                raise ValueError("differential attention pairs the heads: an even number of key-value heads")
        swa = ("swa_q_lora_rank", "swa_kv_lora_rank", "swa_num_attention_heads", "swa_qk_nope_head_dim", "swa_qk_rope_head_dim",
               "swa_v_head_dim", "swa_rope_theta", "sliding_window_size")
        if any(getattr(self, key) is not None for key in swa):
            if (any(getattr(self, key) is None for key in swa) or self.layer_types is None or set(self.layer_types) - set(_ATTENTION_TYPES)
                    or self.differential_attention or self.num_nextn_predict_layers or self.sliding_window_size < 1):
                raise ValueError("window latent attention: every swa_* size and sliding_window_size, under layer_types of "
                                 "full_attention and sliding_attention alone, with no differential form and no drafting module")
        if any(getattr(self, key) is not None for key in ("index_n_heads", "index_head_dim", "index_topk")):
            if (not (self.index_n_heads and self.index_head_dim and self.index_topk) or not self.windowed_latent
                    or self.q_lora_rank is None or self.qk_rope_head_dim > self.index_head_dim or self.qk_rope_head_dim % 2):
                raise ValueError("an indexer (index_n_heads, index_head_dim, index_topk, all three): built for the full layers of a "
                                 "stack whose layer_types select latent attention, its queries read off a query latent, its rotary "
                                 "on an even number of channels no wider than its head")
        if self.differential_attention and (self.layer_types is None or set(self.layer_types) & {_RETENTION, _KDA, _LATENT}
                                            or self.num_nextn_predict_layers):
            raise ValueError("differential attention: the grouped-query layer types' form, with no drafting module")
        if self.num_nextn_predict_layers:
            object.__setattr__(self, "mtp_layer_types", tuple(self.mtp_layer_types))
            if self.layer_types is None or self.num_nextn_predict_layers != 1 or len(self.mtp_layer_types) != 1 \
                    or set(self.mtp_layer_types + self.layer_types) - set(_ATTENTION_TYPES) or self.tie_word_embeddings:
                raise ValueError("a multi-token-prediction module: one block of a grouped-query layer type, under "
                                 "layer_types of attention alone, with a head of its own")
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


@jax.named_scope("residual")
def _residual(x, y):
    """A block's skip connection: a layer of its own in the scope vocabulary (``obs/xplane.py``)."""
    return x + y


def _norm(c: DecoderLanguageModelConfig, **kw):
    """A block's norm: RMSNorm, or where the configuration gives ``layer_norm_eps`` a LayerNorm with scale and bias."""
    if c.layer_norm_eps is not None:
        return LayerNorm(epsilon=c.layer_norm_eps, **kw)
    return RMSNorm(epsilon=c.rms_norm_eps, **kw)


class DecoderBlock(nn.Module):
    config: DecoderLanguageModelConfig
    sparse: bool
    layer_type: Optional[str] = None  # None: latent attention, as ``"latent_attention"``
    index: int = 0  # the layer's depth: differential attention's ``lam0`` and the memory layer are read off it
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        c = self.config
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        self.attn_norm = _norm(c, **kw)
        if _latent(self.layer_type):
            self.attn = MultiHeadLatentAttention(c, **kw)
        elif c.windowed_latent and self.layer_type == "sliding_attention":  # a second latent attention, of the ``swa_*`` sizes
            self.attn = WindowLatentAttention(window_sizes(c), window=c.sliding_window_size, **kw)
        elif c.windowed_latent:  # a full layer: the configuration's own sizes, over the keys an indexer selects where it has one
            self.attn = (SparseLatentAttention if c.index_topk else MultiHeadLatentAttention)(c, **kw)
        elif self.layer_type == "mamba":
            self.mixer = MambaMixer(c, memory=self.hands_on_memory, **kw)
        elif self.layer_type == _GMU:
            self.mixer = GatedMemoryUnit(c, **kw)
        elif self.layer_type == _KDA:
            self.mixer = KimiDeltaAttention(c, **kw)
        elif self.layer_type == _RETENTION:
            self.attn = PowerRetention(c, **kw)
        elif c.differential_attention:
            self.attn = DifferentialAttention(c, kind=self.layer_type, index=self.index, **kw)
        else:
            self.attn = GroupedQueryAttention(c, window=self.layer_type == "sliding_attention", **kw)
        self.ffn_norm = _norm(c, **kw)
        if self.sparse:
            self.ffn = MoELayer(c, **kw)
        else:
            self.ffn = SwiGLU(c.hidden_size, c.intermediate_size, c.init_scale, **kw)

    @property
    def hands_on_memory(self) -> bool:
        return self.index == self.config.memory_layer

    def attend(self, x, pos, source=None):
        """``x + Attn(RMSNorm(x))`` over whole rows, expanded; also the cache
        rows (latent attention: one array; grouped-query: rotated keys and
        values, of which a window layer hands on its last ``sliding_window``;
        a state-space layer: the rows' :class:`RecurrentState`, the memory
        layer's the pair of it and the memory ``m``; a delta layer:
        the rows' :class:`DeltaState`; a retention layer: the rows' final ``(S, z)``).
        A layer that owns nothing reads ``source`` (a ``gmu`` layer the memory
        at the same positions, a ``cross_attention`` layer the owning layer's
        rows) and hands on ``()``."""
        if self.layer_type == _GMU:
            return _residual(x, self.mixer(self.attn_norm(x), source)), ()
        if self.layer_type == _CROSS:
            return _residual(x, self.attn.expand(self.attn_norm(x), pos, kv=source)[0]), ()
        if self.layer_type in _POSITIONLESS:
            a, state = self.mixer.expand(self.attn_norm(x))
            if self.hands_on_memory:  # the mixer's output is the pair of it and the memory
                return _residual(x, a[0]), (state, a[1])
            return _residual(x, a), state
        a, rows = self.attn.expand(self.attn_norm(x), pos)
        if self.layer_type == "sliding_attention":
            with jax.named_scope("chunk_io"):  # what a window layer hands on to its cache
                if self.config.windowed_latent:  # latent rows (B, N, width): the last positions a ring holds
                    rows = rows[:, -self.config.latent_ring_slots:]
                else:
                    rows = tuple(r[:, :, -self.config.sliding_window:] for r in rows)
        return _residual(x, a), rows

    def feed_forward(self, x):
        if self.sparse:  # the expert layer opens its own scopes (``moe/*``)
            return _residual(x, self.ffn(self.ffn_norm(x)))
        with jax.named_scope("dense_mlp"):
            return x + self.ffn(self.ffn_norm(x))

    def read(self, x, source):
        """A step, or a prompt pass's last position, of a layer that writes
        nothing: ``x`` (B, 1, h) against ``source``, a ``gmu`` layer the memory
        of the same position, an attention the cache as it lies (a
        ``cross_attention`` layer's whole step; the owning layer's own last position)."""
        if self.layer_type == _GMU:
            return self.feed_forward(_residual(x, self.mixer(self.attn_norm(x), source)))
        return self.feed_forward(_residual(x, self.attn.read(self.attn_norm(x), source)))

    def step(self, x, cache, pos):
        if self.layer_type in _POSITIONLESS:  # the state has no positions
            a, cache = self.mixer.step(self.attn_norm(x), cache)
            if self.hands_on_memory:  # the advanced state and, beside it, what the layers above read this step
                return self.feed_forward(_residual(x, a[0])), (cache, a[1])
            return self.feed_forward(_residual(x, a)), cache
        one_token = self.attn.absorb if self.config.latent_kind(self.layer_type) else self.attn.step
        a, cache = one_token(self.attn_norm(x), cache, pos)
        return self.feed_forward(_residual(x, a)), cache

    def __call__(self, x, pos, source=None):
        """Whole rows ``x`` (B, N, h): the block's output and its cache rows."""
        x, rows = self.attend(x, pos, source)
        return self.feed_forward(x), rows

    def shared_kv(self, x):
        """The owning layer's cache rows of whole rows ``x`` (B, N, h), its norm and its two projections alone."""
        return self.attn.kv(self.attn_norm(x))

    def verify(self, x, cache, pos):
        """A speculative step's positions, each row at its own length: written to ``cache``, not yet kept."""
        a, cache = self.attn.verify(self.attn_norm(x), cache, pos)
        return self.feed_forward(_residual(x, a)), cache


class ShortcutBlock(nn.Module):
    """The shortcut-connected block (the module docstring's equations): two
    latent attentions and two dense SwiGLUs in series, the expert layer a
    branch from the first sublayer's normed state to the layer's end."""

    config: DecoderLanguageModelConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        c = self.config
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        norm = functools.partial(RMSNorm, epsilon=c.rms_norm_eps, **kw)
        dense = functools.partial(SwiGLU, c.hidden_size, c.intermediate_size, c.init_scale, **kw)
        self.attn0_norm, self.attn0 = norm(), MultiHeadLatentAttention(c, **kw)
        self.ffn0_norm, self.ffn0, self.moe = norm(), dense(), MoELayer(c, **kw)
        self.attn1_norm, self.attn1 = norm(), MultiHeadLatentAttention(c, **kw)
        self.ffn1_norm, self.ffn1 = norm(), dense()

    def _layer(self, x, attend0, attend1):
        """The block around its two attentions, ``attend_j(normed x) -> (output, what the path keeps)``."""
        a, kept0 = attend0(self.attn0_norm(x))
        x = _residual(x, a)
        u = self.ffn0_norm(x)
        shortcut = self.moe(u)  # opens its own scopes (``moe/*``)
        with jax.named_scope("dense_mlp"):
            x = x + self.ffn0(u)
        a, kept1 = attend1(self.attn1_norm(x))
        x = _residual(x, a)
        with jax.named_scope("dense_mlp"):
            x = x + self.ffn1(self.ffn1_norm(x))
        return _residual(x, shortcut), (kept0, kept1)

    def __call__(self, x, pos):
        """Whole rows ``x`` (B, N, h), expanded: the layer's output and its two caches' rows."""
        return self._layer(x, lambda y: self.attn0.expand(y, pos), lambda y: self.attn1.expand(y, pos))

    def step(self, x, caches: Tuple[LatentCache, LatentCache], pos):
        """One new token a row against the layer's two caches: the output and the advanced pair."""
        return self._layer(x, lambda y: self.attn0.absorb(y, caches[0], pos), lambda y: self.attn1.absorb(y, caches[1], pos))


class MTPModule(nn.Module):
    """The multi-token-prediction module's own weights (the module
    docstring): two norms, the ``2h -> h`` projection, one block and a final
    norm. The embedding and the head are the model's."""

    config: DecoderLanguageModelConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        c = self.config
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        self.embed_norm = RMSNorm(epsilon=c.rms_norm_eps, **kw)
        self.hidden_norm = RMSNorm(epsilon=c.rms_norm_eps, **kw)
        self.w_eh = self.param("w_eh", nn.initializers.normal(c.init_scale), (2 * c.hidden_size, c.hidden_size), self.param_dtype)
        self.block = DecoderBlock(c, sparse=True, layer_type=c.mtp_layer_types[0], **kw)
        self.out_norm = RMSNorm(epsilon=c.rms_norm_eps, **kw)

    def project(self, embedded, hidden):
        """``W_eh [RMSNorm_e(Emb(t_{i+1})); RMSNorm_h(h_i)]``: both (..., h) -> (..., h)."""
        with jax.named_scope("mtp/project"):
            both = jnp.concatenate([self.embed_norm(embedded), self.hidden_norm(hidden)], axis=-1)
            return jnp.dot(both.astype(self.dtype), self.w_eh.astype(self.dtype))


def _over_chunks(fn, x, *beside):
    """Apply ``fn`` to each ``x[i]`` of ``x`` (n, ...) and write the result
    over ``x[i]``: a loop that updates the one buffer in place (a ``lax.map``
    would hold the input and the stacked output both, the whole batch's
    hidden state twice). ``fn`` returns ``(chunk, aux)``; the ``aux`` of every
    chunk is stacked into (n, ...) buffers. Arrays ``beside`` (n, ...) are
    read chunk by chunk with ``x`` and handed to ``fn`` after it. Probe taps
    inside ``fn`` are carried out of the loop: counts summed over the chunks,
    ``*_max`` maxed."""
    n = x.shape[0]
    tapping = probes.active()

    def call(chunk, *more):
        if not tapping:
            return fn(chunk, *more), {}
        with probes.collecting(probes.current_config()) as col:
            out = fn(chunk, *more)
        return out, col.stats

    (_, aux_shape), stats_shape = jax.eval_shape(call, x[0], *(a[0] for a in beside))
    aux = jax.tree.map(lambda s: jnp.zeros((n,) + s.shape, s.dtype), aux_shape)
    stats = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), stats_shape)

    def body(i, carry):
        x, aux, stats = carry
        (chunk, a), found = call(*(lax.dynamic_index_in_dim(arr, i, 0, keepdims=False) for arr in (x, *beside)))
        x = lax.dynamic_update_index_in_dim(x, chunk, i, 0)
        aux = jax.tree.map(lambda buf, v: lax.dynamic_update_index_in_dim(buf, v, i, 0), aux, a)
        stats = {
            key: {name: (jnp.maximum if name.endswith("_max") else jnp.add)(stats[key][name], v)
                  for name, v in entry.items()}
            for key, entry in found.items()
        }
        return x, aux, stats

    # the loop's own reads and writes of the one buffer, beside the layers that ``fn`` names
    with jax.named_scope("chunk_io"):
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
        if c.block == "shortcut":
            self.layers = [ShortcutBlock(c, name=f"layer_{i}", **kw) for i in range(c.num_hidden_layers)]
        else:
            self.layers = [
                DecoderBlock(c, sparse=i >= c.first_k_dense_replace, layer_type=c.layer_types and c.layer_types[i],
                             index=i, name=f"layer_{i}", **kw)
                for i in range(c.num_hidden_layers)
            ]
        self.out_norm = _norm(c, **kw)
        if not c.tie_word_embeddings:
            self.head = self.param(
                "head", nn.initializers.normal(c.init_scale), (c.hidden_size, c.vocab_size), self.param_dtype
            )
        if c.num_nextn_predict_layers:
            self.mtp = MTPModule(c, **kw)

    # the two ends and one layer's halves, as methods ``apply`` can reach: the
    # prompt pass (:func:`prefill`) loops them over chunks from outside the
    # module (a jax loop may not wrap a bound submodule's call)

    def embed(self, input_ids):
        with jax.named_scope("embed"):
            return self.embedding[input_ids].astype(self.dtype)

    def logits(self, x):
        with jax.named_scope("logits"):
            if self.config.tie_word_embeddings:  # ``h E^T``: the table is read as it lies, rows on the contraction's other side
                return lax.dot_general(self.out_norm(x), self.embedding.astype(self.dtype),
                                       (((x.ndim - 1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            return jnp.dot(self.out_norm(x), self.head.astype(self.dtype), preferred_element_type=jnp.float32)

    def attend_layer(self, x, pos, i: int):
        return self.layers[i].attend(x, pos)

    def ffn_layer(self, x, i: int):
        return self.layers[i].feed_forward(x)

    def whole_layer(self, x, pos, i: int):
        return self.layers[i](x, pos)

    def shared_kv(self, x, i: int):
        return self.layers[i].shared_kv(x)

    def last_position(self, x, memory, rows):
        """The prompt pass above the self-decoder, at the last position alone:
        ``x`` (B, 1, h) as layer ``prompt_layers - 1`` left it, the memory
        (B, 1, d_inner) of that position and the owning layer's ``rows`` of the
        whole prompt, key pairs and values (B * Hkv/2, N, 2d), every slot live.
        The owning layer's query side, then the readers: logits (B, V)."""
        c = self.config
        with jax.named_scope("prefill/last"):
            shared = KVCache(k=rows[0], v=rows[1], length=jnp.asarray(rows[0].shape[1], jnp.int32))
            x = self.layers[c.prompt_layers].read(x, shared)
            for i in range(c.prompt_layers + 1, c.num_hidden_layers):
                x = self.layers[i].read(x, memory if c.layer_types[i] == _GMU else shared)
        return self.logits(x[:, 0])

    # the module's parts, for the prompt pass's chunk loops in the same way

    def mtp_project(self, x, next_ids):
        return self.mtp.project(self.embed(next_ids), x)

    def mtp_attend(self, x, pos):
        with jax.named_scope("mtp/block"):
            return self.mtp.block.attend(x, pos)

    def mtp_ffn(self, x):
        with jax.named_scope("mtp/block"):
            return self.mtp.block.feed_forward(x)

    def mtp_logits(self, x):
        with jax.named_scope("mtp/draft"):
            return jnp.dot(self.mtp.out_norm(x), self.head.astype(self.dtype), preferred_element_type=jnp.float32)

    def __call__(self, input_ids, drafts: bool = False):
        """Logits (B, N, V) float32 of a full causal forward, no cache. With
        ``drafts`` also the module's logits (B, N - 1, V): at position i, from
        ``h_i`` and ``t_{i+1}``, its prediction of ``t_{i+2}``."""
        c = self.config
        b, n = input_ids.shape
        pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None], (b, n))
        x = self.embed(input_ids)
        handed = {}  # what the readers read: the memory layer's ``m`` and the owning layer's rows, over every position
        for i, layer in enumerate(self.layers):
            reads = handed.get(getattr(layer, "layer_type", None))
            x, rows = layer(x, pos) if reads is None else layer(x, pos, reads)
            if i == c.memory_layer:
                handed[_GMU] = rows[1]
            if i == c.shared_cache_layer:
                handed[_CROSS] = rows
        if not drafts:
            return self.logits(x)
        u = self.mtp_project(x[:, :-1], input_ids[:, 1:])
        u, _ = self.mtp_attend(u, pos[:, :-1])
        return self.logits(x), self.mtp_logits(self.mtp_ffn(u))

    def decode_step(self, token, caches: Tuple[Union[LatentCache, KVCache, WindowKVCache, RecurrentState, RetentionState, DeltaState], ...]):
        """One new token a row against the caches: logits (B, V) and the advanced caches."""
        b = token.shape[0]
        # the position is the length of whatever carries one: a cache that grows, a ring, or a retention state (a
        # stack with no growing cache still rotates by it). Only a state-space layer's and a delta layer's state have
        # none, and such a layer reads no position: a stack of those alone decodes at 0 and nothing reads it
        length = next((cache.length for cache in caches if not isinstance(cache, (RecurrentState, DeltaState))), 0)
        pos = jnp.broadcast_to(length, (b, 1)).astype(jnp.int32)
        x = self.embed(token)[:, None]
        new = []
        if self.config.block == "shortcut":  # a layer's two caches lie side by side
            for i, layer in enumerate(self.layers):
                x, pair = layer.step(x, caches[2 * i: 2 * i + 2], pos)
                new.extend(pair)
            return self.logits(x[:, 0]), tuple(new)
        owned = iter(caches)  # one entry a layer that owns a cache or a state; the readers own none
        handed = {}  # the memory of this step's position, and the shared cache with this step's row written
        for i, layer in enumerate(self.layers):
            if layer.layer_type in _READERS:  # nothing of its own to advance
                x = layer.read(x, handed[layer.layer_type])
                continue
            x, cache = layer.step(x, next(owned), pos)
            if i == self.config.memory_layer:
                cache, handed[_GMU] = cache
            if i == self.config.shared_cache_layer:
                handed[_CROSS] = cache
            new.append(cache)
        return self.logits(x[:, 0]), tuple(new)

    def verify_step(self, tokens, caches: Tuple[Union[RaggedKVCache, RaggedWindowKVCache], ...]):
        """A speculative step of the stack: ``tokens`` (B, n), a row's last
        emitted token and the drafts after it, at the row's own positions
        ``length .. length + n - 1``. Logits (B, n, V), the last block's
        output (B, n, h) and the caches with the positions written, not kept."""
        n = tokens.shape[1]
        pos = caches[0].length[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
        x = self.embed(tokens)
        new = []
        for layer, cache in zip(self.layers, caches):
            x, cache = layer.verify(x, cache, pos)
            new.append(cache)
        return self.logits(x), x, tuple(new)

    def draft_step(self, next_tokens, hidden, cache: Union[RaggedKVCache, RaggedWindowKVCache]):
        """The module on a step's positions: ``hidden`` (B, n, h) of
        :meth:`verify_step` and the token after each position (B, n). Its
        logits (B, n, V) for the token after that, and its cache written."""
        n = next_tokens.shape[1]
        pos = cache.length[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
        u = self.mtp_project(hidden, next_tokens)
        with jax.named_scope("mtp/block"):
            u, cache = self.mtp.block.verify(u, cache, pos)
        return self.mtp_logits(u), cache

    # ------------------------------------------------ the generator's side

    def generation_decoder(self):
        return _Decoder(self)


def _scoped(model, params, method, *args):
    # a loop's body does not inherit the scope around the loop: it is opened again inside
    with jax.named_scope("prefill"):
        return model.apply(params, *args, method=method)


def _prefill_cuts(b: int, n: int) -> Tuple[int, int]:
    """Rows an attention chunk and tokens a feed-forward chunk of a prompt pass over ``b`` rows of ``n``."""
    return _chunks(b, _PREFILL_ATTENTION_TOKENS // n), _chunks(b * n, _PREFILL_FFN_TOKENS)


def _batch_rows(stacked, b: int, n: int):
    """A chunk loop's stacked latent rows (chunks, rows a chunk, N, width) as
    the batch's (B, N, width). Under ``chunk_io``: the loop leaves them with
    the positions on the lanes and the row-major cache takes them through a
    relayout copy, which then has a layer (0.12 ms a cache a call, PERF.md 6, PR 40)."""
    with jax.named_scope("chunk_io"):
        return stacked.reshape(b, n, stacked.shape[-1])


def prefill(model: DecoderLanguageModel, params, input_ids, keep_hidden: bool = False) -> Tuple[jnp.ndarray, tuple]:
    """The prompt pass: last-position logits (B, V) and, a layer, the cache
    rows of the prompt: (B, N, width) of a latent layer (of a shortcut-connected
    layer two of them, side by side in the tuple); of a grouped-query
    layer the keys and the values, each (B * Hkv, N, D), a window layer's
    last ``sliding_window`` positions only; of a state-space layer the rows'
    :class:`RecurrentState` (such a layer runs over chunks of whole rows like an
    attention: a row's time axis is the scan kernel's to chunk, and a padded
    row would run its padding through the state); of a retention layer the
    rows' final ``(S, z)``, float32; of a delta layer the rows'
    :class:`DeltaState`. A ``gmu`` or ``cross_attention`` layer owns nothing
    and has no entry. **Where such layers alone stand above the layer that owns
    the shared cache** (``config.prompt_layers``; the SambaY family) the pass
    over every position stops below that layer: its keys and values are
    projected over the prompt (the cache rows, (B * Hkv/2, N, 2d) each), and its
    query side and every layer above run **at the last position only**, one row
    a batch row against those rows and the memory layer's ``m`` of that position
    (:meth:`DecoderLanguageModel.last_position`): nothing reads what they would
    compute elsewhere. The hidden state of the whole batch
    stays in memory between layers (B * N * h); within a layer the attention
    runs over chunks of whole rows and the feed-forward over chunks of tokens
    (``_PREFILL_ATTENTION_TOKENS``, ``_PREFILL_FFN_TOKENS``), inside the one
    program. ``keep_hidden`` also returns the last block's output (B, N, h),
    which the multi-token-prediction module's own prompt pass reads
    (:func:`prefill_module`)."""
    c = model.config
    b, n = input_ids.shape
    h = c.hidden_size
    rows_a, tokens_f = _prefill_cuts(b, n)
    pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None], (rows_a, n))

    kinds = c.layer_types or (None,) * c.num_hidden_layers
    if any(_latent(kind) for kind in kinds):  # latent attention: the weight views its expanded pass takes, once and not a chunk
        with jax.named_scope("prefill"):
            params = {**params, VIEWS: expand_views(params["params"], c, model.dtype)}
    elif c.windowed_latent:  # the full layers' alone: a window layer's ``w_uq`` has sizes of its own and is cut as it lies
        with jax.named_scope("prefill"):
            full = (f"layer_{i}" for i, kind in enumerate(kinds) if kind == "full_attention")
            params = {**params, VIEWS: {name: expand_views(params["params"][name], c, model.dtype) for name in full}}
    scoped = functools.partial(_scoped, model, params)

    x = scoped("embed", input_ids)
    cache_rows = []
    stop = c.prompt_layers
    memory = None
    for i in range(stop):
        if c.block == "shortcut":  # the whole layer over a chunk of whole rows: the branch's output lives a chunk long
            x, pair = _over_chunks(lambda xc, i=i: scoped("whole_layer", xc, pos, i), x.reshape(b // rows_a, rows_a, n, h))
            cache_rows.extend(_batch_rows(rows, b, n) for rows in pair)
            x = x.reshape(b, n, h)
            continue
        if i == c.memory_layer:  # of the memory, the last position is all that is read
            def attend(xc, i=i):
                xc, (state, m) = scoped("attend_layer", xc, pos, i)
                return xc, (state, m[:, -1:])
        else:
            attend = lambda xc, i=i: scoped("attend_layer", xc, pos, i)  # noqa: E731
        x, rows = _over_chunks(attend, x.reshape(b // rows_a, rows_a, n, h))
        if i == c.memory_layer:  # (the state, the memory): the second is handed on, not kept
            rows, memory = rows
            memory = memory.reshape(b, *memory.shape[2:])
        if _latent(kinds[i]):
            cache_rows.append(_batch_rows(rows, b, n))
        elif c.latent_kind(kinds[i]):  # latent rows (and, of a layer that selects, its index keys); a window layer's last positions
            cache_rows.append(jax.tree.map(lambda r: _batch_rows(r, b, r.shape[2]), rows))
        elif kinds[i] in _STATEFUL:  # (chunks, rows a chunk, ...): the rows' states, as they leave the kernel
            cache_rows.append(jax.tree.map(lambda r: r.reshape(b, *r.shape[2:]), rows))
        else:  # (chunks, rows a chunk, Hkv, positions, D): a key-value head is a row of the cache
            cache_rows.append(tuple(r.reshape(b * r.shape[2], *r.shape[3:]) for r in rows))
        x, _ = _over_chunks(lambda xc, i=i: (scoped("ffn_layer", xc, i), ()), x.reshape(b * n // tokens_f, tokens_f, h))
        x = x.reshape(b, n, h)
    if stop < c.num_hidden_layers:  # the owning layer's rows over the prompt, then everything above at the last position
        x = x.reshape(b // rows_a, rows_a, n, h)
        _, rows = _over_chunks(lambda xc: (xc, scoped("shared_kv", xc, stop)), x)
        rows = tuple(r.reshape(b * r.shape[2], *r.shape[3:]) for r in rows)
        cache_rows.append(rows)
        return scoped("last_position", x.reshape(b, n, h)[:, -1:], memory, rows), tuple(cache_rows)
    if keep_hidden:
        return scoped("logits", x[:, -1]), tuple(cache_rows), x
    return scoped("logits", x[:, -1]), tuple(cache_rows)


def prefill_module(model: DecoderLanguageModel, params, hidden, next_ids) -> Tuple[jnp.ndarray, tuple]:
    """The multi-token-prediction module's prompt pass, cut as the stack's:
    ``hidden`` (B, N, h) is the last block's output over the prompt (its
    buffer is written over), ``next_ids`` (B, N) the token after each
    position (the prompt from its second token on, then the first token
    sampled). Returns the module's last-position logits (B, V), its draft of
    the token after the sampled one, and its block's cache rows."""
    b, n, h = hidden.shape
    rows_a, tokens_f = _prefill_cuts(b, n)
    pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None], (rows_a, n))

    scoped = functools.partial(_scoped, model, params)

    x, _ = _over_chunks(lambda xc, ids: (scoped("mtp_project", xc, ids), ()),
                        hidden.reshape(b * n // tokens_f, tokens_f, h), next_ids.reshape(b * n // tokens_f, tokens_f))
    x, rows = _over_chunks(lambda xc: scoped("mtp_attend", xc, pos), x.reshape(b // rows_a, rows_a, n, h))
    rows = tuple(r.reshape(b * r.shape[2], *r.shape[3:]) for r in rows)
    x, _ = _over_chunks(lambda xc: (scoped("mtp_ffn", xc), ()), x.reshape(b * n // tokens_f, tokens_f, h))
    return scoped("mtp_logits", x.reshape(b, n, h)[:, -1]), rows


class _Decoder:
    """What :mod:`perceiver_io_tpu.generation` asks of a model (see
    ``generation._PerceiverARDecoder`` for the other one): the prompt pass,
    the one-token step, and the state they hand each other. The window is the
    tuple of the layers' caches, each of its layer's kind (latent; or, under
    grouped-query attention, a growing :class:`KVCache` for a full layer and a
    :class:`WindowKVCache` ring for a window layer, side by side; or, where both
    kinds are latent attentions, an :class:`IndexedLatentCache` for a full layer
    that selects its keys and a :class:`LatentRingCache` for a window layer). Nothing the
    generator owns slides: a growing cache's capacity is the prompt plus the
    new tokens, which must fit ``max_position_embeddings``, and a ring
    overwrites the position that left its window.

    Where the configuration has a multi-token-prediction module the decoder is
    ``speculative`` and the generator runs the ``spec_*`` methods in the
    one-token step's place (``generation._generate_speculative``): the prompt
    pass of the stack and of the module, then steps that verify
    ``spec_positions`` positions a row. The window is then the stack's caches
    and the module's, the last one, all with a length a row."""

    window_names = ("cache",)
    const_names = ()
    # a speculative step verifies a row's last emitted token and one draft after it
    spec_positions = 2

    def __init__(self, model: DecoderLanguageModel):
        self.model = model

    @property
    def speculative(self) -> bool:
        return bool(self.model.config.num_nextn_predict_layers)

    @property
    def tap_scopes(self) -> Tuple[str, ...]:
        """The probe sites this configuration's layers have: an expert layer's books, a state-space, retention or delta layer's state."""
        c = self.model.config
        sparse = c.block == "shortcut" or c.first_k_dense_replace < c.num_hidden_layers
        kinds = c.layer_types or ()
        return ((("moe.*",) if sparse else ()) + ("spec.*",) + (("ssm.*",) if "mamba" in kinds else ())
                + (("ret.*",) if _RETENTION in kinds else ()) + (("kda.*",) if _KDA in kinds else ())
                + (("yoco.*",) if _CROSS in kinds else ()) + (("gmu.*",) if _GMU in kinds else ())
                + (("dsa.*",) if c.index_topk else ()))

    def _caches(self, rows, batch: int, n: int, max_new_tokens: int, cache_dtype):
        c = self.model.config
        def cache_of(kind, kept):
            if _latent(kind):
                return init_latent_cache(batch, n + max_new_tokens, kept.shape[-1], cache_dtype).append(kept)
            if c.windowed_latent and kind == "sliding_attention":  # a ring of latent rows
                ring = init_latent_ring_cache(batch, c.sliding_window_size, c.latent_ring_slots, kept.shape[-1], cache_dtype)
                return ring.fill(kept, n)
            if c.windowed_latent and c.index_topk:  # the latent rows and, beside them, the indexer's keys: both grow
                rows, keys = kept
                return init_indexed_latent_cache(batch, n + max_new_tokens, rows.shape[-1], keys.shape[-1], cache_dtype).append(rows, keys)
            if c.windowed_latent:
                return init_latent_cache(batch, n + max_new_tokens, kept.shape[-1], cache_dtype).append(kept)
            if kind == "mamba":  # the state as the scan left it (float32), the window in the caches' dtype
                return RecurrentState(conv=kept.conv.astype(cache_dtype), ssm=kept.ssm)
            if kind == _KDA:  # the state as the chunk kernel left it (float32), the three windows in the caches' dtype
                return DeltaState(s=kept.s, **{name: getattr(kept, name).astype(cache_dtype) for name in ("conv_q", "conv_k", "conv_v")})
            if kind == _RETENTION:  # float32 whatever the caches' dtype; the state holds the prompt's ``n`` tokens
                return RetentionState(s=kept[0], z=kept[1], length=jnp.asarray(n, jnp.int32))
            slots, d = batch * c.num_key_value_heads, c.head_dim
            if c.differential_attention:  # a pair of key heads, and of value heads, is a row (``core/diff_attention.py``)
                slots, d = slots // 2, 2 * d
            if kind == "sliding_attention":
                return init_window_kv_cache(slots, c.sliding_window, d, d, cache_dtype).fill(*kept, n)
            return init_kv_cache(slots, n + max_new_tokens, d, d, cache_dtype).append(*kept)

        # one entry a layer that owns a cache or a state: a ``gmu`` or ``cross_attention`` layer reads another's
        owners = tuple(kind for kind in c.layer_types or (None,) * len(rows) if kind not in _READERS)
        return tuple(cache_of(kind, kept) for kind, kept in zip(owners, rows))

    def _refuse(self, pad_mask, n: int, max_new_tokens: int):
        c = self.model.config
        if pad_mask is not None:
            raise ValueError("the decoder-only model takes no pad_mask: batch prompts of one length")
        if n + max_new_tokens > c.max_position_embeddings:
            raise ValueError(
                f"prompt ({n}) + max_new_tokens ({max_new_tokens}) exceeds max_position_embeddings "
                f"({c.max_position_embeddings})"
            )

    def prefill(self, params, input_ids, pad_mask, num_latents, max_new_tokens, cache_dtype):
        del num_latents  # no latent window: every position passes the whole stack
        b, n = input_ids.shape
        self._refuse(pad_mask, n, max_new_tokens)
        with jax.named_scope("prefill"):
            logits, rows = prefill(self.model, params, input_ids)
            with jax.named_scope("cache_fill"):
                caches = self._caches(rows, b, n, max_new_tokens, cache_dtype)
        return logits[:, None], (caches,), ()

    def step(self, step_params, window, consts, token):
        del consts
        logits, caches = self.model.apply(step_params, token, window[0], method="decode_step")
        return logits[:, None], (caches,)

    # ---------------------------------------------- the speculative decoder

    def ring_slack(self, cache_dtype) -> int:
        """The slots a speculative ring holds past its window: one for each
        position that may be written and not kept, then up to whole sublane
        tiles of the cache's dtype (``ops/gqa_verify.py`` writes tiles back; a
        slot of slack more is masked by where it lies, like the others)."""
        return self._whole_tiles(self.model.config.sliding_window + self.spec_positions - 1, cache_dtype) - self.model.config.sliding_window

    def full_capacity(self, prompt_len: int, max_new_tokens: int, cache_dtype) -> int:
        """The slots of a speculative growing cache: the last step of a row
        writes its draft one slot past the last token the row is asked for;
        then up to whole sublane tiles, a dead tail every query's mask hides."""
        return self._whole_tiles(prompt_len + max_new_tokens + self.spec_positions - 1, cache_dtype)

    @staticmethod
    def _whole_tiles(slots: int, cache_dtype) -> int:
        tile = row_tile(cache_dtype)
        return -(-slots // tile) * tile

    def _ragged_cache(self, kind: str, k, v, batch: int, n: int, max_new_tokens: int, cache_dtype):
        c = self.model.config
        heads, d = c.num_key_value_heads, c.head_dim
        if kind == "sliding_attention":
            return init_ragged_window_kv_cache(batch, heads, c.sliding_window, self.ring_slack(cache_dtype), d, d, cache_dtype).fill(k, v, n)
        return init_ragged_kv_cache(batch, heads, self.full_capacity(n, max_new_tokens, cache_dtype), d, d, cache_dtype).fill(k, v)

    def spec_prefill(self, params, input_ids, pad_mask, max_new_tokens, cache_dtype, sample):
        """The prompt pass of the stack, the first token by ``sample(logits
        (B, V))``, then the module's prompt pass over the same positions
        (position i takes ``h_i`` and token ``i + 1``, the last one the token
        just sampled). Returns that token (B,), the logits (B, V) it was
        sampled from, the module's logits (B, V) for the token after it, and
        the window: every cache filled."""
        c = self.model.config
        b, n = input_ids.shape
        self._refuse(pad_mask, n, max_new_tokens)
        with jax.named_scope("prefill"):
            logits, rows, hidden = prefill(self.model, params, input_ids, keep_hidden=True)
            with jax.named_scope("sample"):
                token = sample(logits)
            next_ids = jnp.concatenate([input_ids[:, 1:], token[:, None].astype(input_ids.dtype)], axis=1)
            draft_logits, module_rows = prefill_module(self.model, params, hidden, next_ids)
            with jax.named_scope("cache_fill"):
                caches = tuple(
                    self._ragged_cache(kind, k, v, b, n, max_new_tokens, cache_dtype)
                    for kind, (k, v) in zip(c.layer_types + c.mtp_layer_types, rows + (module_rows,))
                )
        return token, logits, draft_logits, (caches,)

    def spec_verify(self, step_params, window, tokens):
        """The stack on ``tokens`` (B, ``spec_positions``): logits (B, n, V),
        the last block's output (B, n, h), and the window with the positions
        written to the stack's caches, none kept yet."""
        caches = window[0]
        logits, hidden, stack = self.model.apply(step_params, tokens, caches[:-1], method="verify_step")
        return logits, hidden, (stack + caches[-1:],)

    def spec_draft(self, step_params, window, hidden, next_tokens):
        """The module on the same positions, given the token that followed each: its logits (B, n, V), its cache written."""
        caches = window[0]
        logits, cache = self.model.apply(step_params, next_tokens, hidden, caches[-1], method="draft_step")
        return logits, (caches[:-1] + (cache,),)

    def spec_keep(self, window, m):
        """Every cache keeps a row's first ``m`` (B,) of the positions last written."""
        return (tuple(cache.keep(m) for cache in window[0]),)

    def health(self, logits, window):
        # the occupancy gauge reads a cache that grows: a ring is full from its window on, a recurrent state has one size
        fixed = (WindowKVCache, RaggedWindowKVCache, RecurrentState, RetentionState, DeltaState, LatentRingCache)
        grows = next((cache for cache in window[0] if not isinstance(cache, fixed)), window[0][0])
        # a stack of retention states alone: nothing fills
        return probes.decode_health(logits, None if isinstance(grows, RetentionState) else grows, jnp.zeros((), jnp.int32))

    def compile_row(self, batch: int, prompt_len: int, max_new_tokens: int, cache_dtype) -> dict:
        """The caches' geometry for a ``compile`` event row."""
        c = self.model.config
        itemsize = jnp.dtype(cache_dtype).itemsize
        # how a prompt chunk's expert rows get back to their tokens (a step of few tokens takes the dense path)
        router_width = c.n_routed_experts + c.zero_expert_num
        moe = {"moe_combine": grouped_combine(c.n_held_experts, router_width)}
        if c.windowed_latent:  # three cache kinds: latent rows and index keys that grow, rings of latent rows
            n_full, n_window = c.layer_types.count("full_attention"), c.layer_types.count("sliding_attention")
            capacity = prompt_len + max_new_tokens
            row_bytes = (c.kv_lora_rank + c.qk_rope_head_dim) * itemsize
            ring_row_bytes = (c.swa_kv_lora_rank + c.swa_qk_rope_head_dim) * itemsize
            row = {
                "latent_cache_row_bytes": row_bytes,
                "latent_cache_capacity": capacity,
                "latent_cache_layers": n_full,
                "latent_cache_bytes": batch * capacity * row_bytes * n_full,
                "latent_ring_layers": n_window,
                "latent_ring_row_bytes": ring_row_bytes,
                "latent_ring_window": c.sliding_window_size,
                "latent_ring_slots": c.latent_ring_slots,
                "latent_ring_bytes": batch * c.latent_ring_slots * ring_row_bytes * n_window,
                **moe,
            }
            if c.index_topk:
                row.update(index_cache_row_bytes=c.index_head_dim * itemsize,
                           index_cache_bytes=batch * capacity * c.index_head_dim * itemsize * n_full,
                           index_topk=c.index_topk, index_n_heads=c.index_n_heads,
                           # what a step reads of a full layer's caches a row: every index key and the chosen latent rows, against every latent row
                           dsa_step_bytes_a_row=capacity * c.index_head_dim * itemsize + min(c.index_topk, capacity) * row_bytes,
                           dense_step_bytes_a_row=capacity * row_bytes)
            return row
        if c.layer_types is not None and not set(c.layer_types) & {_KDA, _LATENT}:
            row_bytes = 2 * (c.num_key_value_heads or 0) * (c.head_dim or 0) * itemsize  # a token's keys and values in one layer
            # the module's block keeps a cache of its own kind beside the stack's
            kinds = c.layer_types + (c.mtp_layer_types if self.speculative else ())
            slack = self.ring_slack(cache_dtype) if self.speculative else 0
            full_slots = self.full_capacity(prompt_len, max_new_tokens, cache_dtype) if self.speculative else prompt_len + max_new_tokens
            n_window = kinds.count("sliding_attention")
            n_full = kinds.count("full_attention")
            if _RETENTION in kinds:  # states alone, of one size whatever the context: no cache has a length to bound
                d, n_ret = c.head_dim, kinds.count(_RETENTION)
                rows = feature_rows(d)
                return {
                    "ret_layers": n_ret,
                    "ret_state_bytes": batch * c.num_key_value_heads * (rows * d + rows) * 4 * n_ret,
                    "ret_state_dtype": "float32",
                    "ret_feature_dim": d * (d + 1) // 2,
                    "ret_state_rows": rows,
                    "ret_chunk": chunk_of(prompt_len),
                    "power_retention": power_retention_plans(),
                }
            if "mamba" in kinds:  # states of one size beside the caches that grow; no expert layer
                d_inner, n_ssm = c.mamba_expand * c.hidden_size, kinds.count("mamba")
                ssm = {
                    "ssm_layers": n_ssm,
                    "ssm_state_bytes": batch * c.mamba_d_state * d_inner * 4 * n_ssm,
                    "ssm_conv_bytes": batch * (c.mamba_d_conv - 1) * d_inner * itemsize * n_ssm,
                    "ssm_state_dtype": "float32",
                    "ssm_scan": ssm_scan_plans(),
                }
                full_bytes = batch * (prompt_len + max_new_tokens) * row_bytes
                if _CROSS not in kinds:  # no ring: the attention layers' caches grow, one a layer
                    return {**ssm, "kv_cache_full_layers": n_full, "kv_cache_full_bytes": full_bytes * n_full}
                readers = 1 + kinds.count(_CROSS)  # a decoder-hybrid-decoder stack: rings, and one cache that every cross layer reads
                return {
                    **ssm,
                    "kv_cache_window_layers": n_window,
                    "kv_cache_window_bytes": batch * c.sliding_window * row_bytes * n_window,
                    "kv_cache_window_rows": c.sliding_window,
                    "shared_cache_layer": c.shared_cache_layer,
                    "shared_cache_bytes": full_bytes,
                    "shared_cache_readers": readers,
                    "shared_cache_bytes_unshared": full_bytes * readers,
                    "memory_layer": c.memory_layer,
                    "gmu_layers": kinds.count(_GMU),
                    "prompt_layers": c.prompt_layers,
                }
            row = {
                "kv_cache_full_layers": n_full,
                "kv_cache_window_layers": n_window,
                "kv_cache_full_bytes": batch * full_slots * row_bytes * n_full,
                "kv_cache_window_bytes": batch * (c.sliding_window + slack) * row_bytes * n_window,
                "kv_cache_window_rows": c.sliding_window,
                "kv_cache_lengths": "row" if self.speculative else "batch",
                **moe,
            }
            if self.speculative:
                # a cache kind's step attention by ``core/gqa.py::verify``'s rule, and how the kernel cuts what it takes
                heads, n = c.num_key_value_heads, self.spec_positions
                group = c.num_attention_heads // heads
                kinds = {"full": ((batch * heads, full_slots, c.head_dim), None),
                         "window": ((batch * heads, c.sliding_window + slack, c.head_dim), c.sliding_window)}
                fused = {kind: verify_fused(shape, cache_dtype, heads, n, group, window) for kind, (shape, window) in kinds.items()}
                row.update(mtp_layers=c.num_nextn_predict_layers, spec_positions_per_step=n,
                           kv_cache_window_slack_rows=slack,
                           verify_attention={kind: "kernel" if fused[kind] else "xla" for kind in kinds},
                           gqa_verify=[verify_plan(shape, cache_dtype, heads, n * group, window)._asdict()
                                       for kind, (shape, window) in kinds.items() if fused[kind]])
            return row
        row_bytes = (c.kv_lora_rank + c.qk_rope_head_dim) * itemsize
        kinds = c.layer_types or (None,) * c.num_hidden_layers
        n_caches = sum(_latent(kind) for kind in kinds)
        if _KDA in kinds:  # delta states of one size beside the latent caches that grow
            n_kda, width = kinds.count(_KDA), c.num_attention_heads * c.head_dim
            moe.update(
                kda_layers=n_kda,
                kda_state_bytes=batch * c.num_attention_heads * c.head_dim * c.head_dim * 4 * n_kda,
                kda_state_dtype="float32",
                kda_conv_bytes=batch * 3 * (c.short_conv_kernel_size - 1) * width * itemsize * n_kda,
                kda_chunk=kda_chunk_of(prompt_len),
                kda=kda_plans(),
            )
        if c.block == "shortcut":  # two attentions a layer, each with a cache; the router's outputs past the experts' weights
            n_caches *= 2
            moe.update(block=c.block, moe_router_width=router_width, moe_zero_experts=c.zero_expert_num)
        return {
            "latent_cache_row_bytes": row_bytes,
            "latent_cache_capacity": prompt_len + max_new_tokens,
            "latent_cache_layers": n_caches,
            "latent_cache_bytes": batch * (prompt_len + max_new_tokens) * row_bytes * n_caches,
            **moe,
        }


__all__ = ["DecoderLanguageModel", "DecoderLanguageModelConfig", "YarnConfig"]
