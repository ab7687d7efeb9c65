"""Mellum 2's language model as one pipeline stage: the program's
``DecoderLanguageModel`` under its grouped-query configuration behind the
harness's family interface, for the ``decode`` driver. Parameter shapes, the
traffic (ids uniform over the vocabulary, every row its own) and the compiled
greedy generator are the decoder-only family's of ``families/deepseek_v3.py``.

The published ``config.json`` names the router's width ``num_experts`` and
keeps the rotary of each layer type under ``rope_parameters``; the program's
configuration takes them as ``n_routed_experts`` and ``rope_scaling`` (the
full layers' YaRN; the window layers rotate plainly). Every expert is held.

Every prompt position passes the whole stack, so there is no latent window:
``latents`` is ``seq_len``, the published context, and a cell's
``num_latents`` is 1 (the driver asks the reference for the last
``num_latents + new_tokens - 1`` positions, the served ones). No cell trains
this family; ``train_flops`` is the count the harness asks every family for."""

from __future__ import annotations

from benchmarks.families import deepseek_v3
from benchmarks.lib import mellum_cost
from benchmarks.reference import mellum as reference

# the published keys the program's config takes under the same names
MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "moe_intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "num_experts_per_tok", "rms_norm_eps", "sliding_window",
    "max_position_embeddings", "init_scale",
)
YARN_KEYS = ("factor", "beta_fast", "beta_slow", "attention_factor", "original_max_position_embeddings")


class Family(deepseek_v3.Family):
    def __init__(self, config: dict):
        self.cfg = {k: config[k] for k in MODEL_KEYS}
        rope, depth = config["rope_parameters"], config["num_hidden_layers"]
        if (not config["norm_topk_prob"] or rope["sliding_attention"]["rope_type"] != "default"
                or set(config["mlp_layer_types"][:depth]) != {"sparse"}):
            raise ValueError("families/mellum.py: renormalised top-k weights, plain rotary on the window layers, every layer sparse")
        self.cfg.update(
            # the file keeps the published list whole; the stage runs its first ``num_hidden_layers`` entries
            layer_types=tuple(config["layer_types"][:depth]), n_routed_experts=config["num_experts"],
            rope_theta=float(rope["full_attention"]["rope_theta"]),
            rope_scaling={k: rope["full_attention"][k] for k in YARN_KEYS},
            # what the program's one configuration class also asks for: no dense layer, no shared expert, no groups
            first_k_dense_replace=0, n_shared_experts=0, n_group=1, topk_group=1, scoring_func="softmax",
        )
        self.compute_dtype = config["dtypes"]["compute"]
        self.param_dtype = config["dtypes"]["params"]
        # nothing the generator owns slides: a call's growing caches hold its prompt and its new tokens
        self.seq_len = self.latents = self.cfg["max_position_embeddings"]

    def model(self):
        import jax.numpy as jnp

        from perceiver_io_tpu.models.text.decoder_lm import (
            DecoderLanguageModel, DecoderLanguageModelConfig, YarnConfig,
        )

        cfg = dict(self.cfg, rope_scaling=YarnConfig(**self.cfg["rope_scaling"]))
        return DecoderLanguageModel(DecoderLanguageModelConfig(**cfg),
                                    dtype=jnp.dtype(self.compute_dtype), param_dtype=jnp.dtype(self.param_dtype))

    def train_flops(self, batch_size: int) -> float:
        return mellum_cost.train_flops(self.cfg, batch_size, self.seq_len)

    def reference_logits(self, precision: str, latents: int):
        """``(weights, ids (B, N)) -> logits (B, latents, V)`` over the last ``latents`` positions."""
        return lambda w, ids: reference.logits(w, ids, self.cfg, precision, latents)
