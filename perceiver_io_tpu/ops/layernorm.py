"""Fused LayerNorm (forward + backward) as Pallas TPU kernels.

The round-3 device profile (docs/performance.md) shows the flagship step
spending ~1.5 ms in 19 XLA ``convert_reduce_fusion`` layernorm-stat fusions
running at ~50 GB/s effective — compute-bound on f32 converts and naive
cross-lane reductions, an order of magnitude under HBM bandwidth. These
kernels do the whole normalization (stats + normalize, and the full backward
including the parameter gradients) in ONE pass over the tile each way.

Numerics follow ``flax.linen.LayerNorm`` with its defaults: stats in f32,
``use_fast_variance`` (var = E[x²] − E[x]², clipped at 0), eps added to var
before rsqrt. The ``FusedLayerNorm`` module stores the same parameters
({scale, bias}, f32) under the same names, so checkpoints are
interchangeable with ``nn.LayerNorm``.

MEASURED AND REJECTED as the training-path default (same-process
interleaved full-step A/B on the 16k flagship, batch 4, v5e): the fused
kernels are ~1% SLOWER end-to-end than XLA's layernorm fusions (22.93 vs
22.71 ms/step) despite their ~1.5 ms exclusive-time footprint — XLA
overlaps the stat fusions with surrounding work, and the pallas_call
boundary breaks the adjacent-op fusions the LN input/output otherwise
joins. The lesson generalizes (see docs/performance.md round-3 notes):
this step is SCHEDULE-bound, and exclusive-time profiles overstate what
removing an op can save. The kernels stay correct, tested, and toggleable
(``set_default_fused_ln(True)``) for shapes/backends where the trade
differs; the default everywhere is the identical-formula jnp fallback.
"""

from __future__ import annotations

import functools
import contextlib
import contextvars
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

STAT_LANES = 8  # residual lanes for per-row mean/rstd (lane 0 carries data)

# None = auto (currently: OFF, see module notes); a contextvar like the
# other trace-time toggles (no mutable module global reaches tracing)
_FUSED_LN_DEFAULT = contextvars.ContextVar("fused_ln_default", default=None)


def set_default_fused_ln(mode: Optional[bool]) -> None:
    """True forces the Pallas path (interpret off-TPU — slow, for tests),
    False disables it, None restores the measured auto default (off).
    Read at trace time; affects the current context only."""
    _FUSED_LN_DEFAULT.set(mode)


@contextlib.contextmanager
def fused_ln(mode: Optional[bool]):
    """Scoped :func:`set_default_fused_ln`."""
    token = _FUSED_LN_DEFAULT.set(mode)
    try:
        yield
    finally:
        _FUSED_LN_DEFAULT.reset(token)


def _fused_enabled() -> bool:
    default = _FUSED_LN_DEFAULT.get()
    if default is not None:
        return default
    # auto = off: the fused path measured ~1% slower on the flagship train
    # step (A/B above); flip with set_default_fused_ln to re-probe
    return False


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _block_rows(n_rows: int, c: int) -> int:
    for b in (1024, 512, 256, 128, 64, 32, 16, 8):
        if n_rows % b == 0 and b * c * 4 <= 2 * 1024 * 1024:
            return b
    return 0  # no clean block: fall back


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, eps: float, want_stats: bool):
    # refs: x (rows, C), gamma (1, C), beta (1, C); outs y (rows, C)
    # [+ mean/rstd (rows, STAT_LANES) when want_stats — the primal-only
    # forward skips them: inference would pay HBM writes for dropped data]
    if want_stats:
        x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref = refs
    else:
        x_ref, g_ref, b_ref, y_ref = refs
    x = x_ref[...].astype(jnp.float32)  # (rows, C)
    c = x.shape[1]
    mean = jnp.sum(x, axis=1, keepdims=True) / c  # (rows, 1)
    mean2 = jnp.sum(x * x, axis=1, keepdims=True) / c
    var = jnp.maximum(mean2 - mean * mean, 0.0)
    rstd = lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    y = xhat * g_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    if want_stats:
        mean_ref[...] = jnp.broadcast_to(mean, (x.shape[0], STAT_LANES))
        rstd_ref[...] = jnp.broadcast_to(rstd, (x.shape[0], STAT_LANES))


def _bwd_kernel(
    x_ref, g_ref, mean_ref, rstd_ref, dy_ref,
    dx_ref, dg_ref, db_ref,
    dg_scr, db_scr,
    *, num_blocks: int,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dg_scr[...] = jnp.zeros_like(dg_scr)
        db_scr[...] = jnp.zeros_like(db_scr)

    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    gamma = g_ref[...].astype(jnp.float32)  # (1, C)
    mean = mean_ref[...][:, :1]
    rstd = rstd_ref[...][:, :1]
    c = x.shape[1]

    xhat = (x - mean) * rstd
    g = dy * gamma
    m1 = jnp.sum(g, axis=1, keepdims=True) / c
    m2 = jnp.sum(g * xhat, axis=1, keepdims=True) / c
    dx = rstd * (g - m1 - xhat * m2)
    dx_ref[...] = dx.astype(dx_ref.dtype)

    dg_scr[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_scr[...] += jnp.sum(dy, axis=0, keepdims=True)

    @pl.when(i == num_blocks - 1)
    def _store():
        dg_ref[...] = dg_scr[...]
        db_ref[...] = db_scr[...]


# ---------------------------------------------------------------------------
# custom-vjp wrapper over 2-D (rows, C) operands
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ln2d(x, scale, bias, eps, block, out_dtype):
    return _ln2d_fwd_impl(x, scale, bias, eps, block, out_dtype, want_stats=False)[0]


def _ln2d_fwd_impl(x, scale, bias, eps, block, out_dtype, want_stats):
    rows, c = x.shape
    grid = (rows // block,)
    out_specs = [pl.BlockSpec((block, c), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((rows, c), out_dtype)]
    if want_stats:
        out_specs += [
            pl.BlockSpec((block, STAT_LANES), lambda i: (i, 0)),
            pl.BlockSpec((block, STAT_LANES), lambda i: (i, 0)),
        ]
        out_shape += [
            jax.ShapeDtypeStruct((rows, STAT_LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, STAT_LANES), jnp.float32),
        ]
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, want_stats=want_stats),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, c), lambda i: (i, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=_interpret_default(),
    )(x, scale[None, :], bias[None, :])
    return outs if want_stats else (outs[0] if isinstance(outs, (list, tuple)) else outs,)


def _ln2d_fwd(x, scale, bias, eps, block, out_dtype):
    y, mean, rstd = _ln2d_fwd_impl(x, scale, bias, eps, block, out_dtype, want_stats=True)
    return y, (x, scale, mean[:, :1], rstd[:, :1])


def _ln2d_bwd(eps, block, out_dtype, residuals, dy):
    x, scale, mean_col, rstd_col = residuals
    rows, c = x.shape
    mean = jnp.broadcast_to(mean_col, (rows, STAT_LANES))
    rstd = jnp.broadcast_to(rstd_col, (rows, STAT_LANES))
    grid = (rows // block,)
    dx, dg, db = pl.pallas_call(
        functools.partial(_bwd_kernel, num_blocks=grid[0]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, c), lambda i: (i, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((block, STAT_LANES), lambda i: (i, 0)),
            pl.BlockSpec((block, STAT_LANES), lambda i: (i, 0)),
            pl.BlockSpec((block, c), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, c), lambda i: (i, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, c), x.dtype),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, c), jnp.float32),
            pltpu.VMEM((1, c), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=_interpret_default(),
    )(x, scale[None, :], mean, rstd, dy)
    return dx, dg[0].astype(scale.dtype), db[0].astype(scale.dtype)


_ln2d.defvjp(_ln2d_fwd, _ln2d_bwd)


# ---------------------------------------------------------------------------
# public functional + module
# ---------------------------------------------------------------------------


def _reference_ln(x, scale, bias, eps, dtype):
    """flax.linen.LayerNorm formula (fast variance, f32 stats).

    Intentional precision deviation from ``nn.LayerNorm(dtype=narrow)``
    (ADVICE r3): flax casts x/mean/var to the narrow dtype BEFORE
    normalizing; here the whole normalize (center, rsqrt, scale/bias) runs
    in f32 and only the final output is cast — strictly tighter numerics
    for bf16 configs, matching the Pallas kernels so the fused/fallback
    paths agree bit-for-bit in their f32 math."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    mean2 = jnp.mean(xf * xf, axis=-1, keepdims=True)
    var = jnp.maximum(mean2 - mean * mean, 0.0)
    y = (xf - mean) * lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5, dtype=None):
    """LayerNorm over the minor axis; fused Pallas kernels on TPU when the
    shape tiles cleanly, flax-formula fallback otherwise."""
    dtype = dtype or x.dtype
    c = x.shape[-1]
    rows = 1
    for d in x.shape[:-1]:
        rows *= d
    block = _block_rows(rows, c) if rows else 0
    if not _fused_enabled() or c % 128 != 0 or block == 0 or x.ndim < 2:
        return _reference_ln(x, scale, bias, eps, dtype)
    # NOTE: x enters the kernel in its ORIGINAL dtype — stats are f32 of the
    # unrounded input, exactly like the fallback/flax; only y is cast
    y = _ln2d(x.reshape(rows, c), scale, bias, eps, block, jnp.dtype(dtype))
    return y.reshape(x.shape)


class FusedLayerNorm(nn.Module):
    """Drop-in for ``nn.LayerNorm`` (same {scale, bias} parameters, same
    defaults) backed by the fused kernels; pass ``name=`` explicitly when
    replacing an auto-named ``nn.LayerNorm`` (e.g. ``LayerNorm_0``) so
    checkpoint naming is preserved.

    Scope deviations from ``nn.LayerNorm`` (intentional, ADVICE r3): with a
    narrow ``dtype`` the normalize stays in f32 end-to-end and only the
    output is cast (flax casts before normalizing — slightly looser
    numerics); the ``use_scale``/``use_bias``/``param_dtype`` knobs are not
    reproduced (no caller in this framework disables scale/bias or narrows
    parameter storage)."""

    epsilon: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones_init(), (c,))
        bias = self.param("bias", nn.initializers.zeros_init(), (c,))
        return layer_norm(x, scale, bias, self.epsilon, self.dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps: float = 1e-6, dtype=None):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the minor axis. The mean
    and the normalize run in float32 whatever ``x`` is stored in, and only
    the output is cast (the contract :func:`_reference_ln` keeps). Plain XLA:
    it fuses into the product that follows, and no cell has shown it on a
    trace yet."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype or x.dtype)


class RMSNorm(nn.Module):
    """RMSNorm with one ``scale`` parameter, stored in ``param_dtype``."""

    epsilon: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones_init(), (x.shape[-1],), self.param_dtype)
        return rms_norm(x, scale, self.epsilon, self.dtype)
