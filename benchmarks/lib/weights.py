"""Weights from the seed, made on the device in one jitted call.

The program supplies only the tree of shapes (``jax.eval_shape`` of its
``init``); the values are the benchmark's own, so the plain reference and
the program start from the same numbers and neither made them. Every leaf
is normal(0, ``init_scale``), biases too (a zero bias would hide a missing
bias term from every comparison); LayerNorm scales are 1 + normal."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key from any non-negative whole seed, also one past 2**31."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def weight_builder(shapes, init_scale: float = 0.02):
    """``build(key) -> tree`` like ``shapes`` (of ``ShapeDtypeStruct``), one
    jitted program; the same key gives the same values."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [jax.tree_util.keystr(p) for p, _ in leaves]

    @jax.jit
    def build(key):
        out = []
        for k, path, (_, leaf) in zip(jax.random.split(key, len(leaves)), paths, leaves):
            noise = jax.random.normal(k, leaf.shape, jnp.float32) * init_scale
            if path.endswith("['scale']"):
                noise = 1.0 + noise
            out.append(noise.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return build


def flat_dict(tree) -> dict:
    """``{"a/b/c": leaf}`` for a nested dict: how the references take their
    weights and how leaves are named in comparisons."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        out["/".join(str(getattr(k, "key", k)) for k in path)] = leaf
    return out


def family_weights(family, seed: int, scale: float, model=None):
    """The tree of weights the cell's program and its reference both start
    from: ``family``'s parameter shapes filled from ``seed`` at ``scale``."""
    shapes = family.param_shapes(model if model is not None else family.model())
    return weight_builder(shapes, scale)(seed_key(seed))
