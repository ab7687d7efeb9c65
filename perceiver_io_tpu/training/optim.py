"""Optimizers and LR schedules.

Schedule semantics match the reference's LambdaLR schedulers
(reference: perceiver/scripts/lrs.py:7-38); optimizers cover the reference's
AdamW + torch_optimizer extras (Lamb) via optax; gradient clipping and
accumulation replace ``--trainer.gradient_clip_val`` /
``--trainer.accumulate_grad_batches`` (SURVEY §2.7 P6).
"""

from __future__ import annotations

import math
from typing import Optional, Union

import optax


def cosine_with_warmup(
    base_lr: float,
    training_steps: int,
    warmup_steps: int = 0,
    num_cycles: float = 0.5,
    min_fraction: float = 0.0,
) -> optax.Schedule:
    """Linear warmup then cosine decay to ``min_fraction * base_lr``
    (reference: lrs.py:7-29)."""

    def schedule(step):
        import jax.numpy as jnp

        step = jnp.asarray(step, jnp.float32)
        warmup = step / max(1, warmup_steps)
        progress = (step - warmup_steps) / max(1, training_steps - warmup_steps)
        cosine = min_fraction + jnp.maximum(
            0.0, 0.5 * (1.0 - min_fraction) * (1.0 + jnp.cos(math.pi * num_cycles * 2.0 * progress))
        )
        return base_lr * jnp.where(step < warmup_steps, warmup, cosine)

    return schedule


def constant_with_warmup(base_lr: float, warmup_steps: int = 0) -> optax.Schedule:
    """Linear warmup then constant (reference: lrs.py:32-38)."""

    def schedule(step):
        import jax.numpy as jnp

        step = jnp.asarray(step, jnp.float32)
        return base_lr * jnp.minimum(1.0, step / max(1, warmup_steps))

    return schedule


def freeze_mask(params, frozen_paths) -> "object":
    """Pytree of bools marking leaves whose key path contains one of the
    ``frozen_paths`` as a contiguous run of whole path segments (so
    ``"encoder"`` freezes ``params/encoder/...`` but not
    ``params/image_encoder/...``) — the parity mechanism for the reference's
    ``encoder.freeze`` (requires_grad=False) option
    (reference: perceiver/model/core/utils.py:46-48, text/common/backend.py:39-40)."""
    import jax

    patterns = [p.split("/") for p in frozen_paths]

    def is_frozen(path) -> bool:
        segments = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        for pat in patterns:
            n = len(pat)
            if any(segments[i : i + n] == pat for i in range(len(segments) - n + 1)):
                return True
        return False

    return jax.tree_util.tree_map_with_path(lambda path, _: is_frozen(path), params)


def scale_by_adam_compact(
    b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8, moment_dtype="bfloat16"
) -> optax.GradientTransformation:
    """Adam whose moment accumulators are *stored* in ``moment_dtype``
    (bfloat16), halving the optimizer state's HBM footprint and traffic.

    Motivation: the flagship train step's optimizer update is pinned at its
    HBM roofline — ~1 GB of f32 param+moment traffic, 1.24 ms/step at the
    37M model (docs/performance.md). The update math runs in f32 (moments
    are upcast, updated, and cast back on store), so only the storage
    precision narrows: bf16 keeps f32's full exponent range (no
    under/overflow of ``nu``) but 8 mantissa bits, i.e. ~0.4% relative noise
    on the moment estimates — measured indistinguishable convergence on the
    offline convergence runs (docs/results/). Parameters stay full f32.
    """
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(moment_dtype)

    def init_fn(params):
        zeros = lambda p: jnp.zeros_like(p, dtype=dtype)  # noqa: E731
        return optax.ScaleByAdamState(
            count=jnp.zeros([], jnp.int32),
            mu=jax.tree.map(zeros, params),
            nu=jax.tree.map(zeros, params),
        )

    def update_fn(updates, state, params=None):
        del params
        count = optax.safe_increment(state.count)
        bc1 = 1.0 - b1 ** count.astype(jnp.float32)
        bc2 = 1.0 - b2 ** count.astype(jnp.float32)

        def moments(g, m, v):
            g32 = g.astype(jnp.float32)
            m32 = b1 * m.astype(jnp.float32) + (1.0 - b1) * g32
            v32 = b2 * v.astype(jnp.float32) + (1.0 - b2) * g32 * g32
            u = (m32 / bc1) / (jnp.sqrt(v32 / bc2) + eps)
            return u.astype(g.dtype), m32.astype(dtype), v32.astype(dtype)

        flat = jax.tree.map(moments, updates, state.mu, state.nu)
        is_triple = lambda x: isinstance(x, tuple) and len(x) == 3  # noqa: E731
        u = jax.tree.map(lambda t: t[0], flat, is_leaf=is_triple)
        mu = jax.tree.map(lambda t: t[1], flat, is_leaf=is_triple)
        nu = jax.tree.map(lambda t: t[2], flat, is_leaf=is_triple)
        return u, optax.ScaleByAdamState(count=count, mu=mu, nu=nu)

    return optax.GradientTransformation(init_fn, update_fn)


def make_optimizer(
    learning_rate: Union[float, optax.Schedule],
    optimizer: str = "adamw",
    weight_decay: float = 0.01,
    beta1: float = 0.9,
    beta2: float = 0.999,
    gradient_clip: Optional[float] = None,
    accumulate_grad_batches: int = 1,
    frozen_mask=None,
    moment_dtype: Optional[str] = None,
) -> optax.GradientTransformation:
    """``moment_dtype``: store Adam moments in a narrower dtype (e.g.
    ``"bfloat16"`` — see :func:`scale_by_adam_compact`). Only meaningful for
    adamw/adam; other optimizers reject it."""
    if moment_dtype is not None and optimizer not in ("adamw", "adam"):
        raise ValueError(f"moment_dtype is only supported for adam/adamw, not {optimizer}")
    if optimizer == "adamw":
        if moment_dtype is not None:
            tx = optax.chain(
                scale_by_adam_compact(b1=beta1, b2=beta2, moment_dtype=moment_dtype),
                optax.add_decayed_weights(weight_decay),
                optax.scale_by_learning_rate(learning_rate),
            )
        else:
            tx = optax.adamw(learning_rate, b1=beta1, b2=beta2, weight_decay=weight_decay)
    elif optimizer == "adam":
        if moment_dtype is not None:
            tx = optax.chain(
                scale_by_adam_compact(b1=beta1, b2=beta2, moment_dtype=moment_dtype),
                optax.scale_by_learning_rate(learning_rate),
            )
        else:
            tx = optax.adam(learning_rate, b1=beta1, b2=beta2)
    elif optimizer == "lamb":
        tx = optax.lamb(learning_rate, b1=beta1, b2=beta2, weight_decay=weight_decay)
    elif optimizer == "sgd":
        tx = optax.sgd(learning_rate)
    else:
        raise ValueError(f"unknown optimizer: {optimizer}")

    parts = []
    if frozen_mask is not None:
        # zero frozen grads FIRST so they neither enter the global clip norm
        # nor advance optimizer moments (requires_grad=False parity)
        parts.append(optax.masked(optax.set_to_zero(), frozen_mask))
    if gradient_clip is not None:
        parts.append(optax.clip_by_global_norm(gradient_clip))
    parts.append(tx)
    if frozen_mask is not None:
        # and zero frozen UPDATES last: adamw weight decay would otherwise
        # still shrink frozen parameters despite zero gradients
        parts.append(optax.masked(optax.set_to_zero(), frozen_mask))
    tx = optax.chain(*parts) if len(parts) > 1 else tx

    if accumulate_grad_batches > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=accumulate_grad_batches)
    return tx
