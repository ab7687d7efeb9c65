"""AI21's Jamba2-3B whole on one chip: the program's ``DecoderLanguageModel``
under its hybrid configuration (26 Mamba-1 state-space layers and 2
grouped-query attention layers by ``attn_layer_period`` / ``attn_layer_offset``,
a dense SwiGLU in every layer, a tied head) behind the harness's family
interface, for the ``decode`` driver. Parameter shapes, the traffic (ids uniform
over the vocabulary, every row its own) and the compiled greedy generator are
the decoder-only family's of ``families/deepseek_v3.py``.

The published ``config.json`` gives the layer order as a period and an offset
and the feed-forward's kind through ``num_experts`` (1: dense everywhere); the
program's configuration takes them as ``layer_types`` and
``first_k_dense_replace``. What the config has no key for (the mixer's three
inner norms, ``W_dt``'s bias, ``head_dim``) is the file's ``assumed``.

**The seeded recurrence has to remember.** ``lib/weights.py`` draws every leaf at
``init_scale`` (0.02). For ``a_log`` and ``dt_bias`` that gives ``A = -1`` and a
step size of ``softplus(0) = 0.69``: every state halves each token, nothing
older than ten tokens reaches a logit, and a prompt pass that dropped its carry
at a chunk boundary, or a state stored at half its precision, would pass
``correct`` (``PERF.md`` 6, PR 39's router bias over again). So the family hands
the program and the reference alike, inside the one compiled generator, the
Mamba reference initialisation around the seeded noise (:func:`remembering`):
``a_log = log(1..N)`` over the channels plus the noise, ``dt_bias =
softplus^-1(D0)`` with ``D0`` log-uniform in ``[dt_min, dt_max]`` (the file's,
1e-3 to 1e-1) read off the seeded leaf through the normal distribution's own
cumulative function, and ``d_skip = 1 +`` the noise. Under it the slow states
keep a thousand tokens.

Every prompt position passes the whole stack, so there is no latent window:
``latents`` is ``seq_len``, the published context, and a cell's ``num_latents``
is 1. No cell trains this family (``PERF.md`` 4); ``train_flops`` is the count
the harness asks every family for."""

from __future__ import annotations

import math

from benchmarks.families import deepseek_v3
from benchmarks.lib import jamba_cost
from benchmarks.reference import jamba as reference

# the published keys the program's config takes under the same names
MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "intermediate_size", "num_attention_heads", "num_key_value_heads",
    "head_dim", "rms_norm_eps", "max_position_embeddings", "tie_word_embeddings", "mamba_expand", "mamba_d_state",
    "mamba_dt_rank", "mamba_d_conv", "init_scale",
)


def layer_types(config: dict) -> tuple:
    """The program's name for each layer's mixer, from the published period and offset (the ``jamba`` rule)."""
    return tuple("full_attention" if i % config["attn_layer_period"] == config["attn_layer_offset"] else "mamba"
                 for i in range(config["num_hidden_layers"]))


def remembering(name: str, leaf, init_scale: float, dt_min: float, dt_max: float):
    """A seeded leaf of a mixer as the family hands it on (the module
    docstring): ``a_log``, ``dt_bias`` and ``d_skip`` in float32 around the
    seeded noise, every other leaf as it is. ``name`` is the leaf's own name."""
    import jax
    import jax.numpy as jnp

    if name not in ("a_log", "dt_bias", "d_skip"):
        return leaf
    noise = leaf.astype(jnp.float32)
    if name == "a_log":  # (N, d): state n decays at rate n
        return jnp.log(jnp.arange(1, leaf.shape[0] + 1, dtype=jnp.float32))[:, None] + noise
    if name == "d_skip":
        return 1.0 + noise
    u = jax.scipy.stats.norm.cdf(noise / init_scale)  # uniform over (0, 1), from the seed
    dt0 = jnp.exp(math.log(dt_min) + u * (math.log(dt_max) - math.log(dt_min)))
    return dt0 + jnp.log(-jnp.expm1(-dt0))  # softplus^-1


class Family(deepseek_v3.Family):
    def __init__(self, config: dict):
        if (config["num_experts"] != 1 or config["mamba_proj_bias"] or not config["mamba_conv_bias"]
                or config["sliding_window"] is not None or config["hidden_act"] != "silu"):
            raise ValueError("families/jamba.py: dense feed-forwards, a convolution bias, no projection bias, no window, silu")
        self.cfg = {k: config[k] for k in MODEL_KEYS}
        self.cfg.update(
            layer_types=layer_types(config),
            # what the program's one configuration class also asks for: every layer dense, no rotary on a full layer
            first_k_dense_replace=config["num_hidden_layers"], full_attention_rotary=False, rope_scaling=None,
        )
        # the reference reads the published rule itself
        self.reference_cfg = dict(self.cfg, attn_layer_period=config["attn_layer_period"],
                                  attn_layer_offset=config["attn_layer_offset"])
        self.dt_range = (float(config["seeded_dt_min"]), float(config["seeded_dt_max"]))
        self.compute_dtype = config["dtypes"]["compute"]
        self.param_dtype = config["dtypes"]["params"]
        # nothing the generator owns slides: a call's caches hold its prompt and its new tokens
        self.seq_len = self.latents = self.cfg["max_position_embeddings"]

    def model(self):
        import dataclasses

        import jax.numpy as jnp

        from perceiver_io_tpu.models.text.decoder_lm import DecoderLanguageModel, DecoderLanguageModelConfig

        missing = set(self.cfg) - {f.name for f in dataclasses.fields(DecoderLanguageModelConfig)}
        if missing:  # a program from before the state-space layer: say so and stop, as for a cell without a file
            raise SystemExit(f"benchmarks/families/jamba.py: the program's decoder configuration has no {sorted(missing)}")
        return DecoderLanguageModel(DecoderLanguageModelConfig(**self.cfg),
                                    dtype=jnp.dtype(self.compute_dtype), param_dtype=jnp.dtype(self.param_dtype))

    def _remembering(self, name: str, leaf):
        return remembering(name, leaf, self.cfg["init_scale"], *self.dt_range)

    def generate_fn(self, model, num_latents: int, new_tokens: int, cache_dtype: str):
        """The program's compiled greedy generator over the seeded tree, its recurrences' leaves made to remember."""
        import jax

        generate = super().generate_fn(model, num_latents, new_tokens, cache_dtype)

        def leaf_of(path, leaf):
            return self._remembering(getattr(path[-1], "key", ""), leaf)

        return jax.jit(lambda params, prompts: generate(jax.tree_util.tree_map_with_path(leaf_of, params), prompts))

    def train_flops(self, batch_size: int) -> float:
        return jamba_cost.train_flops(self.cfg, batch_size, self.seq_len)

    def reference_logits(self, precision: str, latents: int):
        """``(weights, ids (B, N)) -> logits (B, latents, V)`` over the last ``latents`` positions."""
        return lambda w, ids: reference.logits({k: self._remembering(k.rsplit("/", 1)[-1], v) for k, v in w.items()},
                                               ids, self.reference_cfg, precision, latents)
