"""Train state: parameters, optimizer state, step counter and RNG in one
pytree — the jitted-loop replacement for the Lightning module state."""

from __future__ import annotations

from typing import Any, Callable

import jax
import optax
from flax import struct


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: Any
    rng: jax.Array
    apply_fn: Callable = struct.field(pytree_node=False)
    tx: optax.GradientTransformation = struct.field(pytree_node=False)

    @classmethod
    def create(cls, apply_fn, params, tx, rng):
        """The state at step 0, timed as the ``startup/state_create`` span of
        the start-up record (``obs/startup.py``; attrs ``leaves`` and
        ``param_bytes``). The span closes once the optimizer state exists and
        waits for no device: it times what the host did, ``tx.init`` building
        its small programs leaf by leaf among it. The body is ``_create`` at
        the end of this file, and this method keeps its ten lines: compiled
        programs' metadata names the lines of ``apply_gradients``, which
        therefore stay where they were.
        """
        return _create(cls, apply_fn, params, tx, rng)

    def apply_gradients(self, grads):
        updates, opt_state = self.tx.update(grads, self.opt_state, self.params)
        params = optax.apply_updates(self.params, updates)
        return self.replace(step=self.step + 1, params=params, opt_state=opt_state)


def _create(cls, apply_fn, params, tx, rng):
    import jax.numpy as jnp

    from perceiver_io_tpu.obs import startup

    leaves = jax.tree_util.tree_leaves(params)
    param_bytes = sum(x.size * x.dtype.itemsize for x in leaves if hasattr(x, "dtype"))
    with startup.span(startup.STATE_CREATE, leaves=len(leaves), param_bytes=int(param_bytes)):
        return cls(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=tx.init(params),
            rng=rng,
            apply_fn=apply_fn,
            tx=tx,
        )
