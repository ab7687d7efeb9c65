"""Input/output adapters and query providers — the modality extension seam.

Behavioral parity with the reference adapters
(reference: perceiver/model/core/adapter.py:8-151). A new modality plugs in
one input adapter, one output adapter and one query provider; everything else
is generic (demonstrated by the reference's root-level time-series app).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from flax import linen as nn

from perceiver_io_tpu.core.position import frequency_position_encoding, positions


class TrainableQueryProvider(nn.Module):
    """Learnable cross-attention query array: the latent array in Perceiver IO
    encoders and the output query in most decoders
    (reference: adapter.py:63-83)."""

    num_queries: int
    num_query_channels: int
    init_scale: float = 0.02
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x=None) -> jnp.ndarray:
        query = self.param(
            "query",
            nn.initializers.normal(stddev=self.init_scale),
            (self.num_queries, self.num_query_channels),
        )
        return query.astype(self.dtype)[None, ...]


class TokenInputAdapter(nn.Module):
    """Token embedding + (optional) learned absolute position embedding.

    When the input is shorter than the provided absolute positions the
    right-most position codes are used (reference: adapter.py:105-114 —
    sliding-window decoding).
    """

    vocab_size: int
    max_seq_len: int
    num_input_channels: int
    abs_pos_emb: bool = True
    init_scale: float = 0.02
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.txt_embedding = nn.Embed(
            self.vocab_size,
            self.num_input_channels,
            embedding_init=nn.initializers.normal(stddev=self.init_scale),
            dtype=self.dtype,
            name="txt_embedding",
        )
        if self.abs_pos_emb:
            self.pos_embedding = nn.Embed(
                self.max_seq_len,
                self.num_input_channels,
                embedding_init=nn.initializers.normal(stddev=self.init_scale),
                dtype=self.dtype,
                name="pos_embedding",
            )

    def _tokens(self, x: jnp.ndarray) -> jnp.ndarray:
        # matmul-backward lookup: the scatter-add gradient of a byte-vocab
        # table costs ~1 ms/step at the 16k flagship (profiled); the one-hot
        # contraction is ~5x cheaper (ops/gathers.py)
        from perceiver_io_tpu.ops.gathers import embed_lookup

        table = self.txt_embedding.embedding.astype(self.dtype)
        return embed_lookup(table, x)

    def _pos_slice(self, n: int) -> jnp.ndarray:
        """Position embeddings for ``arange(n)`` as a table *slice* (n, C),
        whose gradient is a pad instead of a scatter-add. The general gather
        path costs ~38% of a 16k-context train step in its backward scatter
        alone (measured on v5e)."""
        table = self.pos_embedding.embedding.astype(self.dtype)
        pos_emb = table[: min(n, self.max_seq_len)]
        if n > self.max_seq_len:
            # clip parity with the gather path: positions past the table
            # end repeat the last row
            tail = jnp.broadcast_to(table[-1], (n - self.max_seq_len, table.shape[1]))
            pos_emb = jnp.concatenate([pos_emb, tail], axis=0)
        return pos_emb

    def embed(self, x: jnp.ndarray, abs_pos: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        if not self.abs_pos_emb:
            return self._tokens(x)
        if abs_pos is None:
            # positions are statically arange(n) — no padding
            return self._tokens(x) + self._pos_slice(x.shape[1])[None]
        if x.shape[1] < abs_pos.shape[1]:
            abs_pos = abs_pos[:, -x.shape[1] :]
        abs_pos = jnp.clip(abs_pos, 0, self.max_seq_len - 1)
        return self._tokens(x) + self.pos_embedding(abs_pos)

    def __call__(self, x: jnp.ndarray, abs_pos: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        return self.embed(x, abs_pos)

    def attend(self, x: jnp.ndarray) -> jnp.ndarray:
        """Logits against the tied token embedding (x @ E^T)."""
        return self.txt_embedding.attend(x)


class TokenInputAdapterWithRotarySupport(TokenInputAdapter):
    """Token adapter that additionally emits the rotary frequency position
    encoding for its absolute positions (reference: adapter.py:22-32,117-135).

    Returns ``(embedded, frq_pos_enc)`` where ``frq_pos_enc`` has
    ``rotated_channels_per_head`` channels. Unlike the reference, the
    frequency encoding follows the *full* ``abs_pos`` even when ``x`` is
    shorter (cached decoding) — callers slice per-query rows by value.
    """

    rotated_channels_per_head: int = 0

    def __call__(self, x: jnp.ndarray, abs_pos: Optional[jnp.ndarray] = None):
        # keep abs_pos=None flowing into embed(): it selects the scatter-free
        # slice path; the frequency encoding is built from the same arange
        embedded = self.embed(x, abs_pos)
        if abs_pos is None:
            abs_pos = positions(x.shape[0], x.shape[1])
        frq = frequency_position_encoding(abs_pos, self.rotated_channels_per_head)
        return embedded, frq

    def embed_compact(self, x: jnp.ndarray, keep_idx: jnp.ndarray, prefix_len: int):
        """Embed the compact ``[kept-prefix; latents]`` sequence directly from
        token ids — the prefix-dropout selection applied *before* embedding.

        ``x`` (B, N) token ids with statically un-padded positions
        (``arange(N)``); ``keep_idx`` (B, K) sorted unique prefix keep set.
        Returns ``(embedded, frq)`` of length ``K + (N - prefix_len)`` —
        bitwise the rows the full-length ``__call__(x, None)`` embedding
        would yield at ``[keep_idx; prefix_len..N)``, because embedding is a
        per-position table lookup and gather-then-add == add-then-gather.

        The point is the backward: the full-length (B, N, C) embedding and
        its dropout row-gather never materialize, so the gather's
        inverse-gather VJP (~0.8 ms/step at the 16k flagship at batch 4,
        August) disappears. What remains is the token one-hot contraction
        over the *compact* row count and the position-table gradient as
        tile-local one-hot products (ops/gathers.gather_table_rows: the
        kernel ``embed_pos_grad_n<prefix_len>_k<K>``, 1.9 ms a step at the
        benchmark's batch 32 where inverting the index map and gathering the
        cotangent's rows took 11.5; PERF.md 6, PR 31). The id gather below
        (2.5 ms a step at batch 32) and the two forward row gathers are what
        XLA makes of them. Semantics: reference modules.py:809-830.
        """
        b, n = x.shape[0], x.shape[1]
        ids_kept = jnp.take_along_axis(x[:, :prefix_len], keep_idx, axis=1)
        ids = jnp.concatenate([ids_kept, x[:, prefix_len:]], axis=1)
        tok = self._tokens(ids)
        if self.abs_pos_emb:
            from perceiver_io_tpu.ops.gathers import gather_table_rows

            pos_full = self._pos_slice(n)  # (N, C), pad-backward slice
            pos_kept = gather_table_rows(pos_full[:prefix_len], keep_idx)
            pos_latent = jnp.broadcast_to(
                pos_full[prefix_len:][None], (b, n - prefix_len, pos_full.shape[1])
            )
            emb = tok + jnp.concatenate([pos_kept, pos_latent], axis=1)
        else:
            emb = tok
        pos_latent_idx = jnp.broadcast_to(
            jnp.arange(prefix_len, n, dtype=keep_idx.dtype)[None], (b, n - prefix_len)
        )
        abs_pos = jnp.concatenate([keep_idx, pos_latent_idx], axis=1)
        frq = frequency_position_encoding(abs_pos, self.rotated_channels_per_head)
        return emb, frq


class ClassificationOutputAdapter(nn.Module):
    """Linear head over decoder output; squeezes a single output query
    (reference: adapter.py:39-49)."""

    num_classes: int
    num_output_query_channels: int
    init_scale: float = 0.02
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = nn.Dense(
            self.num_classes,
            kernel_init=nn.initializers.normal(stddev=self.init_scale),
            dtype=self.dtype,
            name="linear",
        )(x)
        if x.shape[1] == 1:
            x = jnp.squeeze(x, axis=1)
        return x


class TokenOutputAdapter(nn.Module):
    """Independent (untied) linear head to vocab logits."""

    vocab_size: int
    num_output_query_channels: int
    init_scale: float = 0.02
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        return nn.Dense(
            self.vocab_size,
            kernel_init=nn.initializers.normal(stddev=self.init_scale),
            dtype=self.dtype,
            name="linear",
        )(x)


class TiedTokenOutputAdapter(nn.Module):
    """Logits tied to the token embedding: ``x @ E^T (+ bias)``
    (reference: adapter.py:138-150). The embedding table is supplied by the
    caller via an ``attend`` callable to keep parameters owned by the input
    adapter."""

    vocab_size: int
    emb_bias: bool = True
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, attend) -> jnp.ndarray:
        logits = attend(x)
        if self.emb_bias:
            bias = self.param("bias", nn.initializers.zeros, (self.vocab_size,))
            logits = logits + bias.astype(logits.dtype)
        return logits
