"""Manifest AI's Brumby-14B-Base as one pipeline stage of eight: the program's
``DecoderLanguageModel`` under ``layer_types`` of ``"power_retention"`` alone
(every layer a power retention layer on the grouped-query skeleton, a dense
SwiGLU, an untied head) behind the harness's family interface, for the
``decode`` driver. Parameter shapes, the traffic (ids uniform over the
vocabulary, every row its own) and the compiled greedy generator are the
decoder-only family's of ``families/deepseek_v3.py``.

What the published ``config.json`` has no key for (the degree, the gate, the
normalisation, that q/k norm and rotary stay) is the file's ``assumed``; program
and reference share every one.

**The seeded gate has to remember.** ``lib/weights.py`` draws every leaf at
``init_scale`` (0.02). For the gate's bias that means a logit around 0 and
``gamma`` = 0.5: every state halves a token, nothing older than ten tokens
reaches a logit, and a prompt pass that dropped its carry at a chunk boundary,
or lost the state at the hand-off to the steps, would pass ``correct``
(``PERF.md`` 6, PR 39 and PR 41 over again). So the family hands the program and
the reference alike, inside the one compiled generator, ``b_g = logit(1 - r)``
with ``r`` log-uniform in ``[seeded_forget_min, seeded_forget_max]`` (the file's,
1e-4 to 1e-2) read off the seeded leaf through the normal distribution's own
cumulative function, a head and a layer (:func:`remembering`); ``W_g`` stays as
drawn, so a token's gate still depends on the token. The slow heads then keep
the whole prompt.

Every prompt position passes the whole stack, so there is no latent window:
``latents`` is ``seq_len``, the published context, and a cell's ``num_latents``
is 1. No cell trains this family (``PERF.md`` 4); ``train_flops`` is the count
the harness asks every family for."""

from __future__ import annotations

import math

from benchmarks.families import deepseek_v3
from benchmarks.lib import brumby_cost
from benchmarks.reference import brumby as reference

# the published keys the program's config takes under the same names
MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "intermediate_size", "num_attention_heads", "num_key_value_heads",
    "head_dim", "rms_norm_eps", "rope_theta", "max_position_embeddings", "tie_word_embeddings", "layer_types",
    "init_scale",
)


def remembering(name: str, leaf, init_scale: float, forget_min: float, forget_max: float):
    """A seeded leaf of a retention layer as the family hands it on (the module
    docstring): the gate's bias ``b_g`` in float32, ``logit(1 - r)`` with ``r``
    log-uniform over the range, read off the seeded noise; every other leaf as
    it is. ``name`` is the leaf's own name."""
    import jax
    import jax.numpy as jnp

    if name != "b_g":
        return leaf
    u = jax.scipy.stats.norm.cdf(leaf.astype(jnp.float32) / init_scale)  # uniform over (0, 1), from the seed
    r = jnp.exp(math.log(forget_min) + u * (math.log(forget_max) - math.log(forget_min)))
    return jnp.log1p(-r) - jnp.log(r)


class Family(deepseek_v3.Family):
    def __init__(self, config: dict):
        if (config["attention_bias"] or config["hidden_act"] != "silu" or config["rope_scaling"] is not None
                or config["sliding_window"] is not None or config["use_sliding_window"]
                or set(config["layer_types"]) != {"power_retention"} or config["retention_degree"] != 2):
            raise ValueError("families/brumby.py: retention layers alone at degree 2, no bias on the projections, silu, a plain rotary, no window")
        self.cfg = {k: config[k] for k in MODEL_KEYS}
        # what the program's one configuration class also asks for: every layer dense, q/k norms, a rotary without scaling
        self.cfg.update(layer_types=tuple(config["layer_types"]), first_k_dense_replace=config["num_hidden_layers"],
                        qk_norm=True, rope_scaling=None)
        self.forget_range = (float(config["seeded_forget_min"]), float(config["seeded_forget_max"]))
        self.compute_dtype = config["dtypes"]["compute"]
        self.param_dtype = config["dtypes"]["params"]
        # nothing the generator owns slides, and nothing it owns grows: the states have one size whatever the context
        self.seq_len = self.latents = self.cfg["max_position_embeddings"]

    def model(self):
        import jax.numpy as jnp

        from perceiver_io_tpu.models.text.decoder_lm import DecoderLanguageModel, DecoderLanguageModelConfig

        try:
            config = DecoderLanguageModelConfig(**self.cfg)
        except (TypeError, ValueError) as refusal:  # a program from before the retention layer: say so and stop, as for a cell without a file
            raise SystemExit(f"benchmarks/families/brumby.py: the program's decoder configuration refuses the file's: {refusal}") from None
        return DecoderLanguageModel(config, dtype=jnp.dtype(self.compute_dtype), param_dtype=jnp.dtype(self.param_dtype))

    def _remembering(self, name: str, leaf):
        return remembering(name, leaf, self.cfg["init_scale"], *self.forget_range)

    def generate_fn(self, model, num_latents: int, new_tokens: int, cache_dtype: str):
        """The program's compiled greedy generator over the seeded tree, its gates' biases made to remember."""
        import jax

        generate = super().generate_fn(model, num_latents, new_tokens, cache_dtype)

        def leaf_of(path, leaf):
            return self._remembering(getattr(path[-1], "key", ""), leaf)

        return jax.jit(lambda params, prompts: generate(jax.tree_util.tree_map_with_path(leaf_of, params), prompts))

    def train_flops(self, batch_size: int) -> float:
        return brumby_cost.train_flops(self.cfg, batch_size, self.seq_len)

    def reference_logits(self, precision: str, latents: int):
        """``(weights, ids (B, N)) -> logits (B, latents, V)`` over the last ``latents`` positions."""
        return lambda w, ids: reference.logits({k: self._remembering(k.rsplit("/", 1)[-1], v) for k, v in w.items()},
                                               ids, self.cfg, precision, latents)
