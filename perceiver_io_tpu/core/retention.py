"""Power retention (arXiv:2507.04239; Manifest AI's ``retention`` package) on
the grouped-query skeleton: a gated linear-attention layer whose state has one
size whatever the context, a symmetric power of the query-key product where
attention has the softmax's exponential.

With ``h`` the block's normed input, for token ``t``, key-value head ``g`` and
its ``group`` query heads ``a`` (``D = head_dim``)::

    q_t^a, k_t^g, v_t^g = h W_q, h W_k, h W_v          no bias; RMSNorm over D on q and k (qk_norm); rotary at t
    gamma_t^g = sigmoid(h W_g + b_g)_g                 one gate a key-value head; log gamma in float32
    S_t = gamma_t S_{t-1} + phi(k_t) v_t^T             phi: the symmetric square, phi(x) . phi(y) = (x . y)^2
    z_t = gamma_t z_{t-1} + phi(k_t)
    y_t^a = phi(q_t^a)^T S_t / (phi(q_t^a)^T z_t + eps)
    out_t = concat_a(y_t^a) W_o

(degree 2: every weight ``(q . k)^2`` is non-negative; equivalently ``y_t = sum_j
A_tj v_j / (sum_j A_tj + eps)`` with ``A_tj = (q_t . k_j)^2 prod_{j < l <= t}
gamma_l``, the form the plain reference computes). One state a key-value head,
shared by its query heads. The projections, the q/k norms and the rotary are
:class:`~perceiver_io_tpu.core.gqa.GroupedQueryAttention`'s own (``_project``:
this module is that class with a gate and another way from q, k, v to ``y``).

One set of weights, two ways through them:

``expand`` (the prompt pass)
    whole rows from an empty state: the chunked form in
    ``ops/power_retention.py``'s kernel where it may run (``flash_enabled()``
    and a head of 128) and the recurrent form as a ``lax.scan`` of a token a
    step elsewhere. Also returns the rows' final ``(S, z)``.

``step`` (one new token a row against the state)
    ``S`` and ``z`` read, decayed, updated and read for ``y`` whole: where the
    kernels run, one Pallas call over the state where it lies, updated in place
    (``ops/power_retention.py::power_retention_step``); elsewhere the same
    arithmetic in XLA (``retention_update``). The state's length goes up by
    one. The position is the caller's, which reads it off that length.

``S``, ``z``, ``log gamma`` and the normalisation are float32; products take
``dtype`` operands and accumulate in float32.
"""

from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from perceiver_io_tpu.core.cache import RetentionState
from perceiver_io_tpu.core.gqa import GroupedQueryAttention
from perceiver_io_tpu.obs import probes
from perceiver_io_tpu.ops.flash_attention import flash_enabled
from perceiver_io_tpu.ops.power_retention import (
    EPS, power_retention, power_retention_reference, power_retention_step, power_retention_supported, retention_update,
)


class PowerRetention(GroupedQueryAttention):
    """``config`` needs what :class:`GroupedQueryAttention` needs of a full layer."""

    window: bool = False

    def setup(self):
        super().setup()
        c = self.config
        init = nn.initializers.normal(c.init_scale)
        self.w_g = self.param("w_g", init, (c.hidden_size, c.num_key_value_heads), self.param_dtype)
        self.b_g = self.param("b_g", init, (c.num_key_value_heads,), self.param_dtype)

    def _log_gate(self, x):
        """``x`` (B, N, h) -> ``log gamma`` (B, N, Hkv) float32."""
        logit = jnp.dot(x.astype(self.dtype), self.w_g.astype(self.dtype), preferred_element_type=jnp.float32)
        return jax.nn.log_sigmoid(logit + self.b_g.astype(jnp.float32))

    @staticmethod
    def _tap(s):
        if probes.active():
            probes.tap("ret.state", {"ret_state_abs_max": jnp.max(jnp.abs(s)),
                                     "ret_state_nonfinite": jnp.sum(~jnp.isfinite(s)).astype(jnp.int32)})

    # ------------------------------------------------------ the prompt pass

    def expand(self, x, pos) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
        """Whole rows ``x`` (B, N, h) at ``pos`` (B, N) from an empty state: the
        output (B, N, h) and the rows' state after their last token, ``s`` (B,
        Hkv, R, D) and ``z`` (B, Hkv, R / D, D) float32."""
        c = self.config
        b, n, _ = x.shape
        heads, kv_heads, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        with jax.named_scope("ret/proj"):
            q, k, v = self._project(x, pos)
        with jax.named_scope("ret/gate"):
            log_gate = self._log_gate(x)
        with jax.named_scope("ret/chunk"):
            if flash_enabled() and power_retention_supported(d):
                y, s, z = power_retention(q.reshape(b, n, heads * d), k.reshape(b, n, kv_heads * d),
                                          v.reshape(b, n, kv_heads * d), log_gate, heads)
            else:
                y, (s, z) = power_retention_reference(q, k, v, log_gate)
                y = y.reshape(b, n, heads * d)
            self._tap(s)
        with jax.named_scope("ret/out"):
            return self._mm(y, self.w_o), (s, z)

    # ------------------------------------------------------------- one step

    def step(self, x, state: RetentionState, pos) -> Tuple[jnp.ndarray, RetentionState]:
        """One new token a row, ``x`` (B, 1, h) at positions ``pos`` (B, 1), against ``state``."""
        c = self.config
        b = x.shape[0]
        with jax.named_scope("ret/proj"):
            q, k, v = self._project(x, pos)
        with jax.named_scope("ret/gate"):
            gate = jnp.exp(self._log_gate(x)[:, 0])
        with jax.named_scope("ret/update"):
            update = power_retention_step if flash_enabled() and power_retention_supported(c.head_dim) else retention_update
            y, s, z = update(q[:, 0], k[:, 0], v[:, 0], gate, state.s, state.z, EPS)
            self._tap(s)
        with jax.named_scope("ret/out"):
            out = self._mm(y.reshape(b, 1, c.num_attention_heads * c.head_dim), self.w_o)
            return out, RetentionState(s=s, z=z, length=state.length + 1)
