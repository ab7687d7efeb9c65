"""The Mamba-1 mixer (arXiv:2312.00752) in the form the Jamba family runs it
(arXiv:2403.19887; Hugging Face's ``jamba``: three RMSNorms inside the mixer):
a selective state-space layer whose state has one size whatever the context.

For a row's inputs ``u_1..u_T`` (``d_inner = mamba_expand * hidden_size``,
``N = mamba_d_state``, ``R = mamba_dt_rank``, ``K = mamba_d_conv``)::

    [x_t ; z_t] = W_in u_t                                       no bias
    x_t = silu(sum_{j<K} w_conv[j] * x_{t-K+1+j} + b_conv)       per channel, causal, zeros before t = 1
    [dt_t ; B_t ; C_t] = W_x x_t                                 R + N + N, no bias
    dt_t, B_t, C_t = RMSNorm_dt(dt_t), RMSNorm_B(B_t), RMSNorm_C(C_t)
    D_t = softplus(W_dt dt_t + b_dt)                             the step size
    h_t = exp(D_t * A) * h_{t-1} + (D_t * x_t) * B_t             A = -exp(a_log), (N, d_inner), h_0 = 0
    y_t = sum_n h_t[n] * C_t[n] + d_skip * x_t
    out_t = W_out (y_t * silu(z_t))                              no bias

One set of weights, two ways through them, as in ``core/gqa.py``:

``expand`` (the prompt pass)
    whole rows: the projections and the width-``K`` convolution (``K`` shifted
    sums) in XLA, the recurrence alone in ``ops/selective_scan.py``'s kernel where it
    may run (``flash_enabled()`` and whole channel tiles) and as a ``lax.scan`` of
    a token a step elsewhere. Also returns what a step needs of the rows' past,
    a :class:`~perceiver_io_tpu.core.cache.RecurrentState`: the last ``K - 1``
    convolution inputs and the final ``h``.

``step`` (one new token a row against the state)
    the window shifts by one row; ``h`` is read, updated and written once, in
    one elementwise pass that XLA fuses (the state's bytes at the HBM peak are
    its floor, and a step has nothing else to do with them).

Stored with the channels on the minor axis: ``a_log`` is (N, d_inner) and
``conv_w`` (K, d_inner), the transposes of the published tensors, so that a
state row, ``A``'s row and a convolution tap are each ``d_inner`` lanes wide.

Products take ``dtype`` operands and accumulate in float32; the three norms,
the step size (its projection's output, bias and softplus), ``exp(D * A)`` and
**the state ``h`` are float32**, as are ``B`` and ``C`` (the recurrence is
elementwise: nothing is gained by rounding them). ``x`` is rounded to ``dtype``
once, after the convolution's silu: ``W_x`` and the recurrence read the same
values, on both paths. ``expand`` hands the convolution window on in ``dtype``;
the generator casts it to its ``cache_dtype`` and ``step`` keeps the dtype it is given.
"""

from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from perceiver_io_tpu.core.cache import RecurrentState
from perceiver_io_tpu.obs import probes
from perceiver_io_tpu.ops.flash_attention import flash_enabled
from perceiver_io_tpu.ops.layernorm import RMSNorm
from perceiver_io_tpu.ops.selective_scan import selective_scan, selective_scan_reference, ssm_scan_supported


def causal_conv(window, w, bias=None):
    """The causal depthwise convolution of ``K`` taps and its silu, shared by
    every mixer that has one (this one; ``core/kda.py``): ``window`` (B, T + K -
    1, d) holds every position's ``K`` inputs, oldest first, ``w`` (K, d) the
    taps and ``bias`` (d,) or ``None``. Returns ``silu(sum_j w[j] * window[:, j:j
    + T] + bias)`` (B, T, d) float32: ``K`` shifted sums in float32."""
    k = w.shape[0]
    t = window.shape[1] - k + 1
    w = w.astype(jnp.float32)
    acc = None if bias is None else bias.astype(jnp.float32)
    for j in range(k):
        term = w[j] * window[:, j:j + t].astype(jnp.float32)
        acc = term if acc is None else acc + term
    return jax.nn.silu(acc)


def rows_window(x_in, k: int):
    """Whole rows ``x_in`` (B, T, d) from an empty past: the convolution's window (B, T + K - 1, d), zeros before the first token."""
    return jnp.concatenate([jnp.zeros((x_in.shape[0], k - 1, x_in.shape[2]), x_in.dtype), x_in], axis=1)


def step_window(kept, x_in):
    """One new input a row, ``x_in`` (B, 1, d), after the ``K - 1`` inputs ``kept``: the token's window (B, K, d), in ``kept``'s dtype."""
    return jnp.concatenate([kept, x_in.astype(kept.dtype)], axis=1)


def window_tail(window, k: int):
    """What a step needs of a window afterwards: its last ``K - 1`` inputs."""
    return window[:, -(k - 1):]


class MambaMixer(nn.Module):
    """``config`` needs ``hidden_size``, ``mamba_expand``, ``mamba_d_state``,
    ``mamba_dt_rank``, ``mamba_d_conv``, ``mamba_inner_norms``, ``rms_norm_eps`` and
    ``init_scale``; ``mamba_inner_norms`` off leaves the three RMSNorms out (the SambaY family's
    mixer is Mamba-1's own). ``memory`` makes this the layer whose scan output
    the gated memory units above read: ``expand`` and ``step`` then also return
    ``m_t = sum_n h_t[n] C_t[n] + d_skip x_t`` (B, T, d_inner) float32, as it
    stands **before** ``silu(z_t)`` gates it."""

    config: object
    memory: bool = False
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        c = self.config
        init = nn.initializers.normal(c.init_scale)
        d, n, r = self.d_inner, c.mamba_d_state, c.mamba_dt_rank
        self.w_in = self.param("w_in", init, (c.hidden_size, 2 * d), self.param_dtype)
        self.conv_w = self.param("conv_w", init, (c.mamba_d_conv, d), self.param_dtype)
        self.conv_b = self.param("conv_b", init, (d,), self.param_dtype)
        self.w_x = self.param("w_x", init, (d, r + 2 * n), self.param_dtype)
        if c.mamba_inner_norms:
            f32 = dict(epsilon=c.rms_norm_eps, dtype=jnp.float32, param_dtype=self.param_dtype)
            self.dt_norm, self.b_norm, self.c_norm = RMSNorm(**f32), RMSNorm(**f32), RMSNorm(**f32)
        self.w_dt = self.param("w_dt", init, (r, d), self.param_dtype)
        self.dt_bias = self.param("dt_bias", init, (d,), self.param_dtype)
        self.a_log = self.param("a_log", init, (n, d), self.param_dtype)
        self.d_skip = self.param("d_skip", init, (d,), self.param_dtype)
        self.w_out = self.param("w_out", init, (d, c.hidden_size), self.param_dtype)

    @property
    def d_inner(self) -> int:
        return self.config.mamba_expand * self.config.hidden_size

    def _mm(self, x, w):
        return jnp.dot(x.astype(self.dtype), w.astype(self.dtype))

    # ------------------------------------------------------------ shared

    def _convolve(self, window):
        """:func:`causal_conv` of ``window`` under the mixer's taps and bias, in ``dtype``."""
        return causal_conv(window, self.conv_w, self.conv_b).astype(self.dtype)

    def _select(self, x):
        """``x`` (B, T, d) -> the step size ``D_t`` (B, T, d) and ``B_t``, ``C_t``
        (B, T, N), all float32 (the bias and the softplus are the epilogue of ``W_dt``'s product)."""
        c = self.config
        n, r = c.mamba_d_state, c.mamba_dt_rank
        sel = self._mm(x, self.w_x).astype(jnp.float32)
        dt, b, cc = sel[..., :r], sel[..., r:r + n], sel[..., r + n:]
        normed = c.mamba_inner_norms
        pre = jnp.dot((self.dt_norm(dt) if normed else dt).astype(self.dtype), self.w_dt.astype(self.dtype), preferred_element_type=jnp.float32)
        return jax.nn.softplus(pre + self.dt_bias.astype(jnp.float32)), self.b_norm(b) if normed else b, self.c_norm(cc) if normed else cc

    def _a(self):
        return -jnp.exp(self.a_log.astype(jnp.float32))

    def _out(self, y, x, z):
        """The skip, the gate and ``W_out``: ``y`` (float32, as the recurrence
        left it), ``x`` the convolved inputs and ``z`` the gate's, all (B, T, d).
        The memory layer's output is the pair ``(out, m)``, ``m`` the skip's sum before the gate."""
        y = y + self.d_skip.astype(jnp.float32) * x.astype(jnp.float32)
        out = self._mm(y * jax.nn.silu(z.astype(jnp.float32)), self.w_out)
        return (out, y) if self.memory else out

    @staticmethod
    def _tap(state):
        if probes.active():
            probes.tap("ssm.state", {"state_abs_max": jnp.max(jnp.abs(state)),
                                     "state_nonfinite": jnp.sum(~jnp.isfinite(state)).astype(jnp.int32)})

    # ------------------------------------------------------ the prompt pass

    def expand(self, u) -> Tuple[jnp.ndarray, RecurrentState]:
        """Whole rows ``u`` (B, T, h) from an empty state: the output (B, T, h)
        (the memory layer: the pair of it and ``m`` (B, T, d_inner) float32) and
        the rows' state after their last token (the window in ``dtype``)."""
        c = self.config
        k, d = c.mamba_d_conv, self.d_inner
        with jax.named_scope("ssm/proj_in"):
            xz = self._mm(u, self.w_in)
            x_in, z = xz[..., :d], xz[..., d:]
        with jax.named_scope("ssm/conv"):
            window = rows_window(x_in, k)
            x = self._convolve(window)
            kept = window_tail(window, k)
        with jax.named_scope("ssm/select"):
            dt, bb, cc = self._select(x)
        with jax.named_scope("ssm/scan"):
            scan = selective_scan if flash_enabled() and ssm_scan_supported(d) else selective_scan_reference
            y, h = scan(x, dt, bb, cc, self._a())
            self._tap(h)
        with jax.named_scope("ssm/out"):
            return self._out(y, x, z), RecurrentState(conv=kept, ssm=h)

    # ------------------------------------------------------------- one step

    def step(self, u, state: RecurrentState) -> Tuple[jnp.ndarray, RecurrentState]:
        """One new token a row, ``u`` (B, 1, h), against ``state``: the output (B, 1, h) (the memory layer: the pair
        of it and ``m`` (B, 1, d_inner)) and the advanced state."""
        d = self.d_inner
        with jax.named_scope("ssm/proj_in"):
            xz = self._mm(u, self.w_in)
            x_in, z = xz[..., :d], xz[..., d:]
        with jax.named_scope("ssm/conv"):
            window = step_window(state.conv, x_in)
            x = self._convolve(window)
            kept = window_tail(window, self.config.mamba_d_conv)
        with jax.named_scope("ssm/select"):
            dt, bb, cc = self._select(x)
        with jax.named_scope("ssm/update"):
            dt0 = dt[:, 0]
            h = jnp.exp(dt0[:, None, :] * self._a()[None]) * state.ssm + (dt0 * x[:, 0].astype(jnp.float32))[:, None, :] * bb[:, 0, :, None]
            y = jnp.sum(h * cc[:, 0, :, None], axis=1)
            self._tap(h)
        with jax.named_scope("ssm/out"):
            return self._out(y[:, None], x, z), RecurrentState(conv=kept, ssm=h)


class GatedMemoryUnit(nn.Module):
    """A gated memory unit (arXiv:2507.06607 section 2): a layer of the
    cross-decoder that mixes no tokens and keeps no state, and gates the
    **memory** ``m`` the last state-space layer below handed on (a
    :class:`MambaMixer` with ``memory``) with the layer's own input::

        out_t = W_out (silu(W_in u_t) * m_t)            W_in: h -> d_inner, W_out: d_inner -> h, no bias

    ``m`` is float32 and the gate is float32; the two products take ``dtype``
    operands. ``config`` needs ``hidden_size``, ``mamba_expand`` and ``init_scale``."""

    config: object
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        c = self.config
        init = nn.initializers.normal(c.init_scale)
        d = c.mamba_expand * c.hidden_size
        self.w_in = self.param("w_in", init, (c.hidden_size, d), self.param_dtype)
        self.w_out = self.param("w_out", init, (d, c.hidden_size), self.param_dtype)

    def __call__(self, u, memory):
        """``u`` (B, T, h) and the memory at the same positions (B, T, d_inner): the output (B, T, h)."""
        if probes.active():
            probes.tap("gmu.memory", {"gmu_memory_rms_sum": jnp.sqrt(jnp.mean(jnp.square(memory))),
                                      "gmu_sites": jnp.ones((), jnp.int32)})
        with jax.named_scope("gmu"):  # one scope: the compiler fuses the gate into the product after it
            gate = jnp.dot(u.astype(self.dtype), self.w_in.astype(self.dtype))
            gated = jax.nn.silu(gate.astype(jnp.float32)) * memory
            return jnp.dot(gated.astype(self.dtype), self.w_out.astype(self.dtype))
