"""Differential attention (arXiv:2410.05258) as the SambaY family runs it
(arXiv:2507.06607; Hugging Face's ``phi4flash``): every attention is the
difference of two softmax maps, and an attention layer of the cross-decoder
reads **another layer's** keys and values (YOCO, arXiv:2405.05254).

``[q ; k ; v] = W_qkv u + b_qkv`` with ``H`` query heads and ``Hkv`` key and
value heads of ``d`` channels. The query heads make ``H / 2`` pairs (head ``p``
with head ``p + H / 2``), the key heads ``Hkv / 2`` pairs (``g`` with ``g + Hkv /
2``), the value heads likewise, a pair's two values side by side as one head
``V_g`` of ``2d``; query pair ``p`` reads key-value pair ``p // group``. With the
layer's mask ``M`` (``j <= t``, and ``j > t - sliding_window`` on a window layer)
and the scale ``d ** -0.5``::

    A1 = softmax(q_p k_g^T + M)        A2 = softmax(q_{p + H/2} k_{g + Hkv/2}^T + M)
    o_p = (1 - lam0) * RMSNorm_2d((A1 - lam A2) V_g)          a learned scale of 2d, eps 1e-5
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0              four learned vectors of d
    lam0 = 0.8 - 0.6 exp(-0.3 i)                              i the layer's index

and the output is ``W_o [o_0 .. o_{H/2 - 1}] + b_o``. No positional encoding. A
``cross_attention`` layer has ``W_q``, ``b_q`` in ``W_qkv``'s place and projects
no key or value: it reads the cache of the layer that owns one.

**Stored by pairs.** Keys and values are kept ``(B * Hkv / 2, slots, 2d)``, a
key pair ``[k_g | k_{g + Hkv/2}]`` on one row of 128 lanes (a row of 64 would be
padded to 128 in HBM, twice the bytes), in a :class:`WindowKVCache` ring on a
window layer and a growing :class:`KVCache` on a full one. A map's scores are
then the product of a query with zeros on the other half's lanes against the
whole row (``[q | 0] . [k_a | k_b] = q . k_a``: exact), so both maps of the two
query pairs a key pair serves are four queries of one batched product, as a
group's queries are in ``core/gqa.py``.

One set of weights, four ways through them:

``expand`` (the prompt pass, and the full forward of a cross layer)
    whole rows through ``ops/diff_attention.py::flash_attention_diff``, which
    ends in the subtraction and the subnorm; XLA's einsums where it may not run.
``step`` (one new token a row)
    the token's key and value pairs are written, then ``read``.
``read`` (one query a row against a cache, nothing written)
    :func:`~perceiver_io_tpu.core.gqa.cached_decode_attention`'s two batched
    products over the cache as it lies, the difference in float32, the subnorm.
    A layer's own step after its write; a cross layer's whole step, over the
    cache the owning layer wrote **in the same step**; the owning layer's last
    position of a prompt pass, over the prompt's rows.
``kv`` (the owning layer's cache rows of whole rows, no attention)
    what the cut prompt pass runs over the prompt above the self-decoder.

Scores, softmaxes, ``A1 V - lam A2 V`` and the subnorm are float32; products take
``dtype`` operands and accumulate in float32.
"""

from __future__ import annotations

import math
from typing import Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from perceiver_io_tpu.core.cache import KVCache, WindowKVCache
from perceiver_io_tpu.core.gqa import cached_decode_attention
from perceiver_io_tpu.obs import probes
from perceiver_io_tpu.ops.diff_attention import diff_flash_supported, flash_attention_diff
from perceiver_io_tpu.ops.flash_attention import flash_enabled
from perceiver_io_tpu.ops.layernorm import rms_norm

Cache = Union[KVCache, WindowKVCache]
SUBNORM_EPS = 1e-5


def lambda_init(index: int) -> float:
    """``lam0`` of the layer at depth ``index``."""
    return 0.8 - 0.6 * math.exp(-0.3 * index)


class _Scale(nn.Module):
    """The subnorm's one parameter, ``scale`` (2d,): a module of its own so that the leaf is named as every norm's is."""

    width: int
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self):
        return self.param("scale", nn.initializers.ones_init(), (self.width,), self.param_dtype)


class DifferentialAttention(nn.Module):
    """``config`` needs ``hidden_size``, ``num_attention_heads``,
    ``num_key_value_heads``, ``head_dim``, ``sliding_window`` and
    ``init_scale``. ``kind`` is the layer's entry of ``layer_types``, ``index``
    its depth."""

    config: object
    kind: str
    index: int
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        c = self.config
        init = nn.initializers.normal(c.init_scale)
        h, d = c.hidden_size, c.head_dim
        q_width, kv_width = c.num_attention_heads * d, c.num_key_value_heads * d
        if self.kind == "cross_attention":
            self.w_q = self.param("w_q", init, (h, q_width), self.param_dtype)
            self.b_q = self.param("b_q", init, (q_width,), self.param_dtype)
        else:
            self.w_qkv = self.param("w_qkv", init, (h, q_width + 2 * kv_width), self.param_dtype)
            self.b_qkv = self.param("b_qkv", init, (q_width + 2 * kv_width,), self.param_dtype)
        self.w_o = self.param("w_o", init, (q_width, h), self.param_dtype)
        self.b_o = self.param("b_o", init, (h,), self.param_dtype)
        self.lambda_q1, self.lambda_k1, self.lambda_q2, self.lambda_k2 = (
            self.param(name, init, (d,), self.param_dtype) for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
        self.subln = _Scale(2 * d, self.param_dtype)

    # ------------------------------------------------------------ shared

    @property
    def pairs(self) -> Tuple[int, int]:
        """Query pairs and key-value pairs."""
        return self.config.num_attention_heads // 2, self.config.num_key_value_heads // 2

    def _lam(self):
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
        lam = (jnp.exp(jnp.sum(f32(self.lambda_q1) * f32(self.lambda_k1)))
               - jnp.exp(jnp.sum(f32(self.lambda_q2) * f32(self.lambda_k2))) + lambda_init(self.index))
        if probes.active():
            probes.tap("yoco.lam", {"diff_lam_sum": lam, "diff_lam_max": lam, "diff_lam_sites": jnp.ones((), jnp.int32)})
        return lam

    def _gain(self):
        return (1.0 - lambda_init(self.index)) * self.subln().astype(jnp.float32)

    def _linear(self, x, w, b):
        return jnp.dot(x.astype(self.dtype), w.astype(self.dtype)) + b.astype(self.dtype)

    def _pair_queries(self, q):
        """(B, N, H * d) as projected -> (B, N, H/2 pairs, 2d): head p beside head p + H/2."""
        b, n, _ = q.shape
        d = self.config.head_dim
        return q.reshape(b, n, 2, self.pairs[0], d).transpose(0, 1, 3, 2, 4).reshape(b, n, self.pairs[0], 2 * d)

    def _pair_rows(self, x):
        """Keys or values (B, N, Hkv * d) as projected -> heads-major pairs (B, Hkv/2, N, 2d): head g beside head g + Hkv/2."""
        b, n, _ = x.shape
        d = self.config.head_dim
        return x.reshape(b, n, 2, self.pairs[1], d).transpose(0, 3, 1, 2, 4).reshape(b, self.pairs[1], n, 2 * d)

    def _queries(self, x):
        """The layer's query pairs of ``x`` (B, N, h): a cross layer's one projection, the query columns of ``W_qkv`` elsewhere."""
        if self.kind == "cross_attention":
            return self._pair_queries(self._linear(x, self.w_q, self.b_q))
        width = self.config.num_attention_heads * self.config.head_dim
        return self._pair_queries(self._linear(x, self.w_qkv[:, :width], self.b_qkv[:width]))

    def _project(self, x):
        """``x`` (B, N, h) -> query pairs (B, N, H/2, 2d), key pairs and values (B, Hkv/2, N, 2d)."""
        c = self.config
        q_width, kv_width = c.num_attention_heads * c.head_dim, c.num_key_value_heads * c.head_dim
        qkv = self._linear(x, self.w_qkv, self.b_qkv)
        return (self._pair_queries(qkv[..., :q_width]), self._pair_rows(qkv[..., q_width:q_width + kv_width]),
                self._pair_rows(qkv[..., q_width + kv_width:]))

    def _out(self, o):
        return self._linear(o, self.w_o, self.b_o)

    def _subnorm(self, o):
        """``o`` (..., 2d) float32, the maps' difference: the pair's RMSNorm under ``(1 - lam0)``, in ``dtype``."""
        return (rms_norm(o, self.subln(), SUBNORM_EPS, jnp.float32) * (1.0 - lambda_init(self.index))).astype(self.dtype)

    # ------------------------------------------------------ the prompt pass

    def kv(self, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """The cache rows of whole rows ``x`` (B, N, h), key pairs and values (B, Hkv/2, N, 2d), and no attention."""
        c = self.config
        q_width, kv_width = c.num_attention_heads * c.head_dim, c.num_key_value_heads * c.head_dim
        with jax.named_scope("yoco/kv"):
            kv = self._linear(x, self.w_qkv[:, q_width:], self.b_qkv[q_width:])
            return self._pair_rows(kv[..., :kv_width]), self._pair_rows(kv[..., kv_width:])

    def expand(self, x, pos=None, kv=None) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
        """Causal (windowed) differential attention of ``x`` (B, N, h) over
        itself, or, a cross layer, over the owning layer's rows ``kv``. Returns
        the output (B, N, h) and the rows attended over, key pairs and values
        (B, Hkv/2, N, 2d). ``pos`` is not read: nothing here carries a position."""
        del pos
        c = self.config
        b, n, _ = x.shape
        d, (q_pairs, kv_pairs) = c.head_dim, self.pairs
        window = c.sliding_window if self.kind == "sliding_attention" else None
        with jax.named_scope("diff/proj"):
            if self.kind == "cross_attention":
                q, (k, v) = self._queries(x), kv
            else:
                q, k, v = self._project(x)
            lam = self._lam()
        if flash_enabled() and diff_flash_supported(n, 2 * d):
            with jax.named_scope("diff/flash"):
                o = flash_attention_diff(q.reshape(b, n, q_pairs * 2 * d), k, v, lam, self._gain(), q_pairs, window=window,
                                         sm_scale=d ** -0.5, eps=SUBNORM_EPS)
        else:
            with jax.named_scope("diff/flash"):
                qg = q.reshape(b, n, kv_pairs, q_pairs // kv_pairs, 2, d)
                s = jnp.einsum("bigqxd,bgjxd->bgqxij", qg, k.reshape(b, kv_pairs, n, 2, d), preferred_element_type=jnp.float32) * d ** -0.5
                i, j = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
                visible = (j <= i) if window is None else (j <= i) & (j > i - window)
                p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
                av = jnp.einsum("bgqxij,bgje->bigqxe", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            with jax.named_scope("diff/combine"):
                o = self._subnorm(av[..., 0, :] - lam * av[..., 1, :]).reshape(b, n, q_pairs * 2 * d)
        with jax.named_scope("diff/proj"):
            return self._out(o), (k, v)

    # ------------------------------------------------------------- one step

    def _attend(self, q, cache: Cache) -> jnp.ndarray:
        """One query pair set a row, ``q`` (B, 1, H/2, 2d), against ``cache`` as it lies: the output (B, 1, h)."""
        c = self.config
        b = q.shape[0]
        d, (q_pairs, kv_pairs) = c.head_dim, self.pairs
        group = q_pairs // kv_pairs
        cross = self.kind == "cross_attention"
        if cross and probes.active():
            probes.tap("yoco.cache", {"yoco_reads": jnp.ones((), jnp.int32), "yoco_cache_length_max": cache.length,
                                      "yoco_cache_bytes_max": jnp.asarray(cache.k.nbytes + cache.v.nbytes, jnp.float32)})
        with jax.named_scope("yoco/cross" if cross else "diff/step"):
            q = q.reshape(b, kv_pairs, group, 2 * d)
            first = jnp.arange(2 * d) < d
            # a key pair's 2 * group queries: the first map's with zeros on the second key's lanes, then the second map's
            both = jnp.concatenate([jnp.where(first, q, jnp.zeros_like(q)), jnp.where(first, jnp.zeros_like(q), q)], axis=2)
            av = cached_decode_attention(both.reshape(b * kv_pairs, 2 * group, 2 * d), cache, d ** -0.5)
        with jax.named_scope("diff/combine"):
            o = self._subnorm(av[:, :group] - self._lam() * av[:, group:]).reshape(b, 1, q_pairs * 2 * d)
        with jax.named_scope("diff/proj"):
            return self._out(o)

    def read(self, x, cache: Cache) -> jnp.ndarray:
        """One query a row, ``x`` (B, 1, h), against ``cache`` as it lies: the output (B, 1, h). Nothing is written."""
        with jax.named_scope("diff/proj"):
            q = self._queries(x)
        return self._attend(q, cache)

    def step(self, x, cache: Cache, pos=None) -> Tuple[jnp.ndarray, Cache]:
        """One new token a row, ``x`` (B, 1, h), against ``cache``: its key and value pairs are written first."""
        del pos
        b, (_, kv_pairs), width = x.shape[0], self.pairs, 2 * self.config.head_dim
        # the layer that owns the shared cache: its projections and its write are what the layers above wait for
        with jax.named_scope("yoco/kv" if self.kind == "full_attention" else "diff/proj"):
            q, k, v = self._project(x)  # k, v (B, Hkv/2, 1, 2d): a key-value pair is a row of the cache
            with jax.named_scope("kv_cache_append"):
                cache = cache.append(k.reshape(b * kv_pairs, 1, width), v.reshape(b * kv_pairs, 1, width))
        return self._attend(q, cache), cache
