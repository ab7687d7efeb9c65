"""Long-context sequence parallelism wired into the model: the Perceiver AR
forward with the **prefix sharded** over the ``seq`` mesh axis.

This is the explicit ``shard_map`` counterpart of the GSPMD path validated in
``tests/test_seq_parallel_step.py`` (where XLA partitions the dense forward
from sharding annotations alone). Here the blockwise/online-softmax
decomposition is explicit — per-device prefix partials, one ``pmax`` + two
``psum`` of size O(latents) — so the communication volume is independent of
the context length, and a 16k..1M-token prefix never exists in one device's
HBM (SURVEY §5.7; the reference handles long context on a single device,
perceiver/model/core/modules.py:850-866, and has no sequence parallelism,
SURVEY §2.7 P8).

Usage::

    mesh = make_mesh(seq=8)
    fwd = make_seq_parallel_clm_forward(model, mesh, prefix_len=prefix_len)
    logits = fwd(params, input_ids)                 # (B, L, V) latent logits

    loss = make_seq_parallel_clm_loss(model, mesh, prefix_len=prefix_len)
    l, grads = jax.value_and_grad(loss)(params, input_ids, labels)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from perceiver_io_tpu.parallel.mesh import AXIS_SEQ
from perceiver_io_tpu.utils.arrays import concrete_or_none


def _split_prompt(input_ids, pad_mask, prefix_len: int):
    latent_ids = input_ids[:, prefix_len:]
    prefix_ids = input_ids[:, :prefix_len]
    prefix_pad = None if pad_mask is None else pad_mask[:, :prefix_len]
    # value check only on concrete (eager) masks — under jit/grad the mask is
    # a tracer and the contract (left padding only) is documented, not checked
    concrete_mask = concrete_or_none(pad_mask)
    if concrete_mask is not None and bool(concrete_mask[:, prefix_len:].any()):
        raise ValueError("padding must be confined to the (left-padded) prefix")
    return latent_ids, prefix_ids, prefix_pad


def make_seq_parallel_clm_forward(model, mesh: Mesh, *, prefix_len: int, axis_name: str = AXIS_SEQ):
    """Jitted ``fn(params, input_ids, pad_mask=None) -> latent logits``.

    ``input_ids`` is the full (B, S) prompt; the first ``prefix_len`` columns
    are sharded over ``axis_name`` (must divide ``prefix_len``), the latent
    suffix is replicated. ``pad_mask`` marks left padding (prefix only).
    """
    seq_size = mesh.shape[axis_name]
    if prefix_len < seq_size:
        # prefix_len=0 would pass the divisibility check below but give every
        # device an empty prefix block, which crashes in block_attention with
        # an obscure zero-size-axis reduction error during tracing
        raise ValueError(
            f"prefix_len ({prefix_len}) must be at least the '{axis_name}' "
            f"axis size ({seq_size}) so every device gets a non-empty prefix "
            f"block; use the dense forward for prefix-free inputs"
        )
    if prefix_len % seq_size != 0:
        raise ValueError(f"prefix_len ({prefix_len}) must be divisible by the "
                         f"'{axis_name}' axis size ({seq_size})")

    def per_device(params, latent_ids, prefix_local, prefix_pad_local, dropout_rng):
        rngs = None if dropout_rng is None else {"dropout": dropout_rng}
        return model.apply(
            params,
            latent_ids,
            prefix_local,
            axis_name=axis_name,
            prefix_pad_local=prefix_pad_local,
            deterministic=dropout_rng is None,
            rngs=rngs,
            method="seq_parallel_forward",
        )

    shard = P(None, axis_name)
    variants = {}

    def variant(has_mask: bool, has_rng: bool):
        """Jitted shard_map specialization for the optional-arg combination
        (shard_map in_specs must match the positional signature exactly)."""
        key = (has_mask, has_rng)
        if key not in variants:
            specs = [P(), P(), shard] + ([shard] if has_mask else []) + ([P()] if has_rng else [])

            def f(params, latent_ids, prefix_local, *rest):
                pad = rest[0] if has_mask else None
                rng = rest[-1] if has_rng else None
                return per_device(params, latent_ids, prefix_local, pad, rng)

            # Trace with the plain gather/embed ops (ops/gathers.py): the
            # custom-VJP rewrites defeat shard_map's static varying-mesh-axes
            # inference ("possibly varying over {seq}" on replicated grads),
            # and keeping the static check on is worth more here than the
            # single-chip scatter optimization.
            from perceiver_io_tpu.ops.gathers import plain_gathers

            def f_plain(*args, _f=f):
                with plain_gathers():
                    return _f(*args)

            variants[key] = jax.jit(
                jax.shard_map(f_plain, mesh=mesh, in_specs=tuple(specs), out_specs=P())
            )
        return variants[key]

    def fn(params, input_ids, pad_mask=None, dropout_rng=None):
        latent_ids, prefix_ids, prefix_pad = _split_prompt(input_ids, pad_mask, prefix_len)
        args = (params, latent_ids, prefix_ids)
        if prefix_pad is not None:
            args += (prefix_pad,)
        if dropout_rng is not None:
            args += (dropout_rng,)
        return variant(prefix_pad is not None, dropout_rng is not None)(*args)

    return fn


def make_ring_clm_loss(model, mesh: Mesh, *, max_latents: int, axis_name: str = AXIS_SEQ):
    """Trainer-compatible CLM loss over the explicit sequence-parallel path —
    the ``--trainer.strategy=ring`` route (scripts/cli.py): the prefix is
    sharded over ``axis_name`` and its cross-attention partial goes through
    ``parallel.ring_attention.seq_sharded_cross_attention`` (see
    ``PerceiverAR.seq_parallel_forward``), unlike strategy ``seq`` where XLA
    partitions the dense forward from sharding annotations alone.

    Signature parity with ``training.losses.clm_loss_fn``:
    ``loss_fn(params, batch, rng, deterministic=False) -> (loss, metrics)``
    over ``{"labels", "input_ids", "pad_mask"}`` batches; the loss window is
    the last ``max_latents`` positions (reference:
    perceiver/model/core/lightning.py:117-133). ``prefix_len`` is derived
    from each batch's static sequence length.
    """
    inner = {}

    def loss_fn(params, batch, rng, deterministic: bool = False):
        labels, x = batch["labels"], batch["input_ids"]
        pad_mask = batch["pad_mask"]
        prefix_len = x.shape[1] - max_latents
        if prefix_len not in inner:
            inner[prefix_len] = make_seq_parallel_clm_loss(
                model, mesh, prefix_len=prefix_len, axis_name=axis_name
            )
        # the left-pad-only contract is checked by _split_prompt EAGERLY only
        # (under the Trainer's jitted step the mask is a tracer); mask padded
        # latent labels regardless, matching the dense clm_loss_fn (a short
        # document left-padded into the latent window must not contribute
        # pad-token targets to the CE)
        lat_labels = labels[:, -max_latents:]
        if pad_mask is not None:
            lat_labels = jnp.where(pad_mask[:, -max_latents:], -100, lat_labels)
        loss = inner[prefix_len](
            params,
            x,
            lat_labels,
            pad_mask=pad_mask,
            dropout_rng=None if deterministic else rng,
        )
        return loss, {"loss": loss}

    return loss_fn


def make_seq_parallel_clm_loss(model, mesh: Mesh, *, prefix_len: int, axis_name: str = AXIS_SEQ):
    """``loss(params, input_ids, labels) -> scalar`` — mean next-token CE over
    the latent positions (the reference's CLM loss window: loss over the last
    ``max_latents`` logits, perceiver/model/core/lightning.py:117-133), with
    the prefix sharded over ``axis_name``. Differentiable through the
    ``shard_map`` (psum/pmax have transfer rules), so
    ``jax.value_and_grad`` gives sequence-parallel training gradients.

    ``labels``: (B, L) target ids for the latent positions, -100 = ignore.
    ``dropout_rng`` enables training mode: prefix cross-attention dropout as
    the per-device keep-mask (see ``PerceiverAR.seq_parallel_forward``).
    """
    fwd = make_seq_parallel_clm_forward(model, mesh, prefix_len=prefix_len, axis_name=axis_name)

    def loss(params, input_ids, labels, pad_mask=None, dropout_rng=None):
        logits = fwd(params, input_ids, pad_mask, dropout_rng).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        valid = labels != -100
        tgt = jnp.where(valid, labels, 0)
        ll = jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        return -(ll * valid).sum() / jnp.maximum(valid.sum(), 1)

    return loss
