"""Task loss functions, mirroring the reference Lightning steps.

Each loss_fn has signature ``(apply_fn) -> (params, batch, rng) ->
(loss, metrics)`` so the generic train step can differentiate it.
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

IGNORE_INDEX = -100  # torch CrossEntropyLoss ignore_index parity


@jax.named_scope("loss")
def _cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mean CE over labels != IGNORE_INDEX. Returns (loss, num_valid)."""
    valid = labels != IGNORE_INDEX
    safe_labels = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
    num_valid = valid.sum()
    loss = jnp.where(valid, nll, 0.0).sum() / jnp.maximum(num_valid, 1)
    return loss, num_valid


def classification_loss_fn(apply_fn, deterministic: bool = False) -> Callable:  # noqa: D401
    """CE + accuracy over ``{"x" | "image", "label"}`` batches
    (reference: perceiver/model/core/lightning.py:47-77). ``deterministic``
    builds the eval variant (dropout off, the Lightning ``model.eval()``
    analog)."""

    def loss_fn(params, batch: Dict, rng, deterministic: bool = deterministic) -> Tuple[jnp.ndarray, Dict]:
        x = batch.get("x", batch.get("image", batch.get("input_ids")))
        y = batch["label"]
        pad_mask = batch.get("pad_mask")
        kwargs = {} if pad_mask is None else {"pad_mask": pad_mask}
        if not deterministic:
            kwargs["rngs"] = {"dropout": rng}
        logits = apply_fn(params, x, deterministic=deterministic, **kwargs)
        loss, _ = _cross_entropy(logits, y)
        acc = jnp.mean((jnp.argmax(logits, axis=-1) == y).astype(jnp.float32))
        return loss, {"loss": loss, "acc": acc}

    # per-example mean CE/acc: equal-size chunks carry equal weight, so the
    # microbatch mean-of-means equals the full-batch mean
    loss_fn.uniform_weighting = True
    return loss_fn


def masked_lm_loss_fn(apply_fn, deterministic: bool = False) -> Callable:
    """CE over masked positions only: labels are IGNORE_INDEX except where a
    token was masked (reference: perceiver/model/text/mlm/lightning.py:45-60)."""

    def loss_fn(params, batch: Dict, rng, deterministic: bool = deterministic) -> Tuple[jnp.ndarray, Dict]:
        kwargs = {} if deterministic else {"rngs": {"dropout": rng}}
        logits = apply_fn(
            params,
            batch["input_ids"],
            pad_mask=batch.get("pad_mask"),
            deterministic=deterministic,
            **kwargs,
        )
        loss, num_masked = _cross_entropy(logits, batch["labels"])
        return loss, {"loss": loss, "num_masked": num_masked}

    # normalizes by the per-call masked-token count and emits a count-valued
    # metric — microbatch chunking would reweight tokens and scale the count
    # by 1/k, so make_train_step rejects microbatch > 1 for this loss
    loss_fn.uniform_weighting = False
    return loss_fn


def clm_loss_fn(apply_fn, max_latents: int, deterministic: bool = False) -> Callable:
    """Causal LM loss: pads are ignored, prefix_len = seq_len - max_latents,
    CE over the last ``max_latents`` logits
    (reference: perceiver/model/core/lightning.py:117-133).

    Contract: the data pipeline pre-shifts targets — ``input_ids = t[:, :-1]``
    and ``labels = t[:, 1:]`` for a raw token window ``t``
    (reference: perceiver/data/text/c4.py:161-162); this function does NOT
    shift."""

    def loss_fn(params, batch, rng, deterministic: bool = deterministic) -> Tuple[jnp.ndarray, Dict]:
        labels, x = batch["labels"], batch["input_ids"]
        # the key is required (a pipeline dropping it should fail loudly) but
        # the value may be None: static no-padding knowledge that selects the
        # scatter-free position-embedding path (see adapter.embed)
        pad_mask = batch["pad_mask"]
        seq_len = x.shape[1]
        if seq_len < max_latents:
            raise ValueError(f"Training sequence length must be at least {max_latents} (= max_latents)")
        if pad_mask is not None:
            labels = jnp.where(pad_mask, IGNORE_INDEX, labels)
        kwargs = {} if deterministic else {"rngs": {"dropout": rng}}
        # optional host-sampled prefix-dropout keep set (training.prefix_dropout):
        # moves the subset draw's top_k+sort off the device
        keep_idx = batch.get("prefix_keep_idx")
        if keep_idx is not None and not deterministic:
            kwargs["prefix_keep_idx"] = keep_idx
        out = apply_fn(
            params,
            x,
            prefix_len=seq_len - max_latents,
            pad_mask=pad_mask,
            deterministic=deterministic,
            **kwargs,
        )
        logits = out.logits
        labels = labels[:, -logits.shape[1] :]
        loss, _ = _cross_entropy(logits, labels)
        return loss, {"loss": loss}

    return loss_fn


def mse_loss_fn(apply_fn, deterministic: bool = False) -> Callable:
    """MSE for regression tasks (time-series app, reference: model.py:16-114)."""

    def loss_fn(params, batch: Dict, rng, deterministic: bool = deterministic) -> Tuple[jnp.ndarray, Dict]:
        kwargs = {} if deterministic else {"rngs": {"dropout": rng}}
        pred = apply_fn(params, batch["x"], deterministic=deterministic, **kwargs)
        loss = jnp.mean((pred - batch["y"]) ** 2)
        return loss, {"loss": loss}

    loss_fn.uniform_weighting = True  # plain mean over elements
    return loss_fn
