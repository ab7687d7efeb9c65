"""Position encodings: absolute positions, rotary (RoPE), frequency and Fourier features.

Behavioral parity with the reference's position utilities
(reference: perceiver/model/core/position.py:9-138), re-expressed as pure
functions so they compose with jit/scan/remat. The TPU-critical difference:
rotary alignment for cached decoding is driven by *position values* (dynamic
values, static shapes) instead of slicing dynamically-shaped encodings, so a
single compiled decode step serves every cache fill level.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def positions(
    batch_size: int,
    seq_len: int,
    shift: Optional[jnp.ndarray] = None,
    offset: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Batched absolute position indices of shape (B, N), clamped at >= 0.

    ``shift`` (B, 1) subtracts the left-pad count per example so that the first
    non-pad token sits at position 0 (reference: position.py:9-17). ``offset``
    (scalar, possibly traced) adds a start position — used for incremental
    decoding where the new token's absolute position is the current sequence
    length (a dynamic value with a static shape).
    """
    pos = jnp.broadcast_to(jnp.arange(seq_len, dtype=jnp.int32)[None, :], (batch_size, seq_len))
    if offset is not None:
        pos = pos + offset
    if shift is not None:
        if shift.shape != (batch_size, 1):
            raise ValueError(f"shift must have shape {(batch_size, 1)} but has shape {shift.shape}")
        pos = pos - shift
    return jnp.maximum(pos, 0)


@jax.named_scope("rotary")
def frequency_position_encoding(abs_pos: jnp.ndarray, dim: int) -> jnp.ndarray:
    """Inverse-frequency rotary position features.

    ``inv_freq_i = 10000**(-2(i-1)/dim)``; each frequency channel is repeated
    twice (adjacent pairs) to match the rotate-half pairing
    (reference: position.py:53-71).

    :param abs_pos: integer absolute positions, shape (..., N).
    :param dim: number of rotary channels (must be even).
    :return: float32 array of shape (..., N, dim).
    """
    if dim % 2 != 0:
        raise ValueError(f"rotary dim must be even but is {dim}")
    inv_freq = 1.0 / (10000 ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    enc = abs_pos.astype(jnp.float32)[..., None] * inv_freq
    return jnp.repeat(enc, 2, axis=-1)


def rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    """[x1, x2, x3, x4, ...] -> [-x2, x1, -x4, x3, ...] over the last axis."""
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    return jnp.stack((-x2, x1), axis=-1).reshape(x.shape)


@jax.named_scope("rotary")
def apply_rotary_pos_emb(t: jnp.ndarray, pos_enc: jnp.ndarray) -> jnp.ndarray:
    """Rotate the first ``pos_enc.shape[-1]`` channels of ``t``.

    :param t: tensor of shape (..., N, C).
    :param pos_enc: per-position frequency encoding broadcastable to
        (..., N, R) with R <= C. Channels beyond R pass through unrotated
        (reference: position.py:30-42).
    """
    rotate_dim = pos_enc.shape[-1]
    t_rot, t_pass = t[..., :rotate_dim], t[..., rotate_dim:]
    pe = pos_enc.astype(jnp.float32)
    t_rot32 = t_rot.astype(jnp.float32)
    rotated = t_rot32 * jnp.cos(pe) + rotate_half(t_rot32) * jnp.sin(pe)
    rotated = rotated.astype(t.dtype)
    if t_pass.shape[-1] == 0:
        return rotated
    return jnp.concatenate([rotated, t_pass], axis=-1)


def yarn_inv_freq(
    dim: int,
    theta: float,
    factor: float,
    beta_fast: float,
    beta_slow: float,
    original_max_position: int,
) -> np.ndarray:
    """YaRN's rotary frequencies (arXiv:2309.00071, as DeepSeek-V3's
    ``inference/model.py`` computes them): ``dim // 2`` inverse frequencies,
    of which those that turn more than ``beta_fast`` times over the original
    context keep their value, those that turn fewer than ``beta_slow`` times
    are divided by ``factor``, and a linear ramp over the pair index blends
    the two between (``factor`` 1: the plain frequencies). float32 on the
    host: a table, not a traced value."""
    inv_freq = (1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim))).astype(np.float32)
    if factor == 1.0:
        return inv_freq

    def correction_dim(rotations: float) -> float:
        return dim * math.log(original_max_position / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0.0, 1.0)
    smooth = 1.0 - ramp
    return (inv_freq / factor * (1.0 - smooth) + inv_freq * smooth).astype(np.float32)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: the softmax scale is multiplied by its
    square (``0.1 * mscale * ln(factor) + 1``, 1 where nothing is scaled)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


@jax.named_scope("rotary")
def apply_rotary_interleaved(t: jnp.ndarray, pos: jnp.ndarray, inv_freq) -> jnp.ndarray:
    """Rotate all channels of ``t`` (..., N, R): adjacent channels
    ``(2i, 2i+1)`` are one complex number turned by ``pos * inv_freq[i]``.
    ``pos`` (..., N) broadcasts against ``t``'s leading axes (pass
    ``pos[:, None]`` for a heads axis). Computed in float32, returned in
    ``t``'s dtype."""
    angles = pos.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    t32 = t.astype(jnp.float32)
    x1, x2 = t32[..., 0::2], t32[..., 1::2]
    out = jnp.stack((x1 * cos - x2 * sin, x1 * sin + x2 * cos), axis=-1)
    return out.reshape(t.shape).astype(t.dtype)


@jax.named_scope("rotary")
def apply_rotary_half(t: jnp.ndarray, pos: jnp.ndarray, inv_freq, attention_factor: float = 1.0) -> jnp.ndarray:
    """Rotate all channels of ``t`` (..., N, R) in the half-split pairing
    (Hugging Face's ``rotate_half``): channel ``i`` of the first half and
    channel ``i`` of the second are one complex number turned by
    ``pos * inv_freq[i]``. ``attention_factor`` multiplies cos and sin (YaRN's
    temperature carried by the rotation, on queries and keys alike). ``pos``
    (..., N) broadcasts against ``t``'s leading axes. Computed in float32,
    returned in ``t``'s dtype."""
    angles = pos.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(angles) * attention_factor, jnp.sin(angles) * attention_factor
    t32 = t.astype(jnp.float32)
    half = t.shape[-1] // 2
    x1, x2 = t32[..., :half], t32[..., half:]
    return jnp.concatenate((x1 * cos - x2 * sin, x2 * cos + x1 * sin), axis=-1).astype(t.dtype)


class RotaryPositionEmbedding:
    """Convenience wrapper bundling a frequency encoding with its alignment.

    ``rotate(t)`` reproduces the reference semantics (position.py:20-42):
    with ``right_align=True`` a tensor of length N is rotated with the *last*
    N rows of the encoding (Perceiver AR: q/k right-aligned at the end of the
    window), otherwise with the first N rows. For fixed-capacity cached
    decoding, build per-slot encodings directly with
    :func:`frequency_position_encoding` instead.
    """

    def __init__(self, frq_pos_enc: jnp.ndarray, right_align: bool = False):
        # (B, N, R) broadcast over heads at application time.
        self.frq_pos_enc = frq_pos_enc
        self.rotate_dim = frq_pos_enc.shape[-1]
        self.right_align = right_align

    def rotate(self, t: jnp.ndarray) -> jnp.ndarray:
        """Rotate ``t`` of shape (B, H, N, C)."""
        seq_len = t.shape[-2]
        if self.right_align:
            pos_enc = self.frq_pos_enc[:, -seq_len:, :]
        else:
            pos_enc = self.frq_pos_enc[:, :seq_len, :]
        return apply_rotary_pos_emb(t, pos_enc[:, None, :, :])


@functools.lru_cache(maxsize=16)
def fourier_position_encodings(
    input_shape: Sequence[int],
    num_frequency_bands: int,
    include_positions: bool = True,
) -> np.ndarray:
    """Fourier features over an N-dimensional grid in [-1, 1].

    Returns a (prod(input_shape), C) float32 array where
    C = len(input_shape) * (2 * num_frequency_bands + include_positions),
    channel order = [raw positions, sin per dim, cos per dim]
    (reference: position.py:74-138). Computed with numpy at trace time and
    memoized per grid geometry; XLA treats it as a constant.
    """
    input_shape = tuple(input_shape)
    coords = [np.linspace(-1.0, 1.0, num=s, dtype=np.float32) for s in input_shape]
    pos = np.stack(np.meshgrid(*coords, indexing="ij"), axis=-1)  # (*shape, ndim)

    frequency_grids = []
    for i, size in enumerate(input_shape):
        freqs = np.linspace(1.0, size / 2.0, num=num_frequency_bands, dtype=np.float32)
        frequency_grids.append(pos[..., i : i + 1] * freqs)

    encodings = [pos] if include_positions else []
    encodings.extend(np.sin(math.pi * g) for g in frequency_grids)
    encodings.extend(np.cos(math.pi * g) for g in frequency_grids)

    enc = np.concatenate(encodings, axis=-1)
    return enc.reshape(-1, enc.shape[-1])


class FourierPositionEncoding:
    """Stateless provider of flattened Fourier position encodings for a grid."""

    def __init__(self, input_shape: Sequence[int], num_frequency_bands: int):
        self.input_shape = tuple(input_shape)
        self.num_frequency_bands = num_frequency_bands

    def num_position_encoding_channels(self, include_positions: bool = True) -> int:
        # analytic — does not build the grid
        return len(self.input_shape) * (2 * self.num_frequency_bands + include_positions)

    def __call__(self, batch_size: int) -> jnp.ndarray:
        enc = jnp.asarray(fourier_position_encodings(self.input_shape, self.num_frequency_bands))
        return jnp.broadcast_to(enc[None], (batch_size,) + enc.shape)
