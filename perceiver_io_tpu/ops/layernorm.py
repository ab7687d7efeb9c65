"""LayerNorm and RMSNorm: the normalize in float32, the output cast.

``layer_norm`` follows ``flax.linen.LayerNorm`` with its defaults (stats in
f32, ``use_fast_variance``: var = E[x^2] - E[x]^2 clipped at 0, eps added to
var before rsqrt), and the ``LayerNorm`` module stores the same parameters
({scale, bias}, f32) under the same names, so checkpoints are
interchangeable with ``nn.LayerNorm``. Plain XLA: a Pallas LayerNorm
(forward and backward kernels) was measured on the v5e and lost twice (1%
on the 16k AR step, 5% on the image step: XLA overlaps the stat fusions
with the work around them, and a ``pallas_call`` boundary breaks the fusions
the input and output otherwise join). It was deleted in PR 30
(docs/performance.md, round 3).
"""

from __future__ import annotations

import jax.numpy as jnp
from flax import linen as nn
from jax import lax


def layer_norm(x, scale, bias, eps: float = 1e-5, dtype=None):
    """LayerNorm over the minor axis, flax.linen.LayerNorm's formula (fast
    variance, f32 stats).

    Intentional precision deviation from ``nn.LayerNorm(dtype=narrow)``
    (ADVICE r3): flax casts x/mean/var to the narrow dtype BEFORE
    normalizing; here the whole normalize (center, rsqrt, scale/bias) runs
    in f32 and only the final output is cast — strictly tighter numerics
    for bf16 configs."""
    dtype = dtype or x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    mean2 = jnp.mean(xf * xf, axis=-1, keepdims=True)
    var = jnp.maximum(mean2 - mean * mean, 0.0)
    y = (xf - mean) * lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(dtype)


class LayerNorm(nn.Module):
    """Drop-in for ``nn.LayerNorm`` (same {scale, bias} parameters, same
    defaults); pass ``name=`` explicitly when replacing an auto-named
    ``nn.LayerNorm`` (e.g. ``LayerNorm_0``) so checkpoint naming is
    preserved.

    Scope deviations from ``nn.LayerNorm`` (intentional, ADVICE r3): with a
    narrow ``dtype`` the normalize stays in f32 end-to-end and only the
    output is cast (flax casts before normalizing — slightly looser
    numerics); the ``use_scale``/``use_bias`` knobs are not reproduced (no
    caller in this framework disables scale/bias). ``param_dtype`` is the
    parameters' storage (float32 unless the decoder-only class stores a
    published bfloat16 model's norms as it does every other weight)."""

    epsilon: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones_init(), (c,), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros_init(), (c,), self.param_dtype)
        return layer_norm(x, scale, bias, self.epsilon, self.dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps: float = 1e-6, dtype=None):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the minor axis. The mean
    and the normalize run in float32 whatever ``x`` is stored in, and only
    the output is cast (the contract :func:`layer_norm` keeps). Plain XLA:
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
