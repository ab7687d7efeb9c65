"""LongCat-Flash's language model as one chip of 32 that share each layer: the
program's ``DecoderLanguageModel`` under its shortcut-connected block (two
latent attentions and two dense feed-forwards a layer beside a share of the
experts, softmax-routed under a bias over experts of which 256 have no
weights), behind the harness's family interface, for the ``decode`` driver.
Parameter shapes, the traffic (ids uniform over the held slice of the
vocabulary, every row its own) and the compiled greedy generator are the
decoder-only family's of ``families/deepseek_v3.py``.

The published ``config.json`` has its own key names: ``num_layers``,
``ffn_hidden_size``, ``expert_ffn_hidden_size``, ``moe_topk``; the file keeps
them (with the experts held counted under ``n_routed_experts`` and the
router's outputs as ``router_width``) and they are mapped to the program's
names here. What the config has no key for (the two scale factors' form, no
renormalisation, the block's wiring) is the file's ``assumed``.

**The seeded router bias.** ``lib/weights.py`` draws every leaf at
``init_scale`` (0.02), the router's float32 bias too. Beside sigmoid scores of
0.2 to 0.8 (the other two routed families) that moves a choice now and then;
beside softmax probabilities over 768 outputs (0.0013 in the mean, 0.011 at
the twelfth largest) it *is* the choice: the ten outputs with the largest bias
take every token, two of a token's twelve picks are its own, a held expert
that is among the ten gets 4096 pairs a chunk and one that is not gets none
(``PERF.md`` 6, PR 39: the cell's first four seeds spread by 1.4%, and the
branch weighed 0.6 in the sum where an unbiased choice weighs 1.6). A stored
bias of a checkpoint is of the probabilities' own size. So the family hands
the program and the reference alike the seeded bias times the file's
``router_bias_scale`` (0.1: a bias of 0.002 moves 1.6 of a token's 12 picks and
no output takes more than 6% of the tokens), inside the one compiled
generator; the program still chooses on ``p + b`` as published.

Every prompt position passes the whole stack, so there is no latent window:
``latents`` is ``seq_len``, the published context, and a cell's
``num_latents`` is 1. No cell trains this family; ``train_flops`` is the count
the harness asks every family for."""

from __future__ import annotations

from benchmarks.families import deepseek_v3
from benchmarks.lib import longcat_cost
from benchmarks.reference import longcat_flash as reference

# the published keys the program's config takes under the same names
MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "mla_scale_q_lora", "mla_scale_kv_lora", "zero_expert_num", "rms_norm_eps",
    "max_position_embeddings", "init_scale",
)
# and those it takes under its own
RENAMED = {"num_layers": "num_hidden_layers", "ffn_hidden_size": "intermediate_size",
           "expert_ffn_hidden_size": "moe_intermediate_size", "moe_topk": "num_experts_per_tok"}


class Family(deepseek_v3.Family):
    def __init__(self, config: dict):
        if config["attention_method"] != "MLA" or config["zero_expert_type"] != "identity" or config["attention_bias"]:
            raise ValueError("families/longcat_flash.py: latent attention without biases, identity zero-computation experts")
        self.cfg = {k: config[k] for k in MODEL_KEYS}
        self.cfg.update({ours: config[theirs] for theirs, ours in RENAMED.items()})
        self.cfg.update(
            # the file counts the experts held under the published key; the router keeps its width, whose last
            # ``zero_expert_num`` outputs have no weights
            n_routed_experts=config["router_width"] - config["zero_expert_num"], n_held_experts=config["n_routed_experts"],
            held_experts_start=config["held_experts_start"], routed_scaling_factor=float(config["routed_scaling_factor"]),
            rope_theta=float(config["rope_theta"]), rope_scaling=None,
            block="shortcut", scoring_func="softmax_biased", first_k_dense_replace=0, n_shared_experts=0, n_group=1, topk_group=1,
        )
        self.router_bias_scale = float(config["router_bias_scale"])
        self.compute_dtype = config["dtypes"]["compute"]
        self.param_dtype = config["dtypes"]["params"]
        # nothing the generator owns slides: a call's caches hold its prompt and its new tokens
        self.seq_len = self.latents = self.cfg["max_position_embeddings"]

    def model(self):
        import dataclasses

        import jax.numpy as jnp

        from perceiver_io_tpu.models.text.decoder_lm import DecoderLanguageModel, DecoderLanguageModelConfig

        missing = set(self.cfg) - {f.name for f in dataclasses.fields(DecoderLanguageModelConfig)}
        if missing:  # a program from before the block: say so and stop, as for a cell without a file
            raise SystemExit(f"benchmarks/families/longcat_flash.py: the program's decoder configuration has no {sorted(missing)}")
        return DecoderLanguageModel(DecoderLanguageModelConfig(**self.cfg),
                                    dtype=jnp.dtype(self.compute_dtype), param_dtype=jnp.dtype(self.param_dtype))

    def generate_fn(self, model, num_latents: int, new_tokens: int, cache_dtype: str):
        """The program's compiled greedy generator over the seeded tree, its router biases brought to the probabilities' size."""
        import jax

        generate = super().generate_fn(model, num_latents, new_tokens, cache_dtype)
        scale = self.router_bias_scale

        def with_scaled_bias(path, leaf):
            return leaf * scale if getattr(path[-1], "key", None) == "gate_bias" else leaf

        return jax.jit(lambda params, prompts: generate(jax.tree_util.tree_map_with_path(with_scaled_bias, params), prompts))

    def train_flops(self, batch_size: int) -> float:
        return longcat_cost.train_flops(self.cfg, batch_size, self.seq_len)

    def reference_logits(self, precision: str, latents: int):
        """``(weights, ids (B, N)) -> logits (B, latents, V)`` over the last ``latents`` positions."""
        scale = self.router_bias_scale
        return lambda w, ids: reference.logits({k: v * scale if k.endswith("/gate_bias") else v for k, v in w.items()},
                                               ids, self.cfg, precision, latents)
