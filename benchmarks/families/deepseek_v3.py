"""DeepSeek-V3's language model as one chip's share of an expert-parallel
deployment: the program's ``DecoderLanguageModel`` behind the harness's
family interface, for the ``decode`` driver.

Every prompt position passes the whole stack, so there is no latent window:
``latents`` is ``seq_len``, and a cell's ``num_latents`` equals its
``prompt_len`` (the driver's arithmetic of what a reference forward returns
then comes out as "every served position"). No cell trains this family;
``train_flops`` is the count the harness asks every family for."""

from __future__ import annotations

import numpy as np

from benchmarks.lib import dsv3_cost
from benchmarks.reference import deepseek_v3 as reference

# the published keys the program's config takes under the same names
MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "first_k_dense_replace", "intermediate_size",
    "moe_intermediate_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "n_shared_experts", "num_experts_per_tok", "n_group", "topk_group",
    "routed_scaling_factor", "rms_norm_eps", "rope_theta", "init_scale",
)
YARN_KEYS = ("factor", "beta_fast", "beta_slow", "mscale", "mscale_all_dim", "original_max_position_embeddings")


class Family:
    def __init__(self, config: dict):
        self.cfg = {k: config[k] for k in MODEL_KEYS}
        # the file counts the experts held under the published key; the router keeps its width
        self.cfg.update(
            n_routed_experts=config["router_width"], n_held_experts=config["n_routed_experts"],
            held_experts_start=config["held_experts_start"], rope_scaling=dict(config["rope_scaling"]),
            max_position_embeddings=config["max_position_embeddings"],
        )
        self.compute_dtype = config["dtypes"]["compute"]
        self.param_dtype = config["dtypes"]["params"]
        # no window to slide: a call's cache holds its prompt and its new tokens, and both fit the published context
        self.seq_len = self.latents = self.cfg["max_position_embeddings"]

    # ---------------------------------------------------------- the program

    def model(self):
        import jax.numpy as jnp

        from perceiver_io_tpu.models.text.decoder_lm import (
            DecoderLanguageModel, DecoderLanguageModelConfig, YarnConfig,
        )

        cfg = dict(self.cfg, rope_scaling=YarnConfig(**{k: self.cfg["rope_scaling"][k] for k in YARN_KEYS}))
        return DecoderLanguageModel(DecoderLanguageModelConfig(**cfg),
                                    dtype=jnp.dtype(self.compute_dtype), param_dtype=jnp.dtype(self.param_dtype))

    def param_shapes(self, model):
        import jax
        import jax.numpy as jnp

        return jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))

    # ---------------------------------------------------------- the traffic

    def prompts(self, seed: int, call: int, batch_size: int, prompt_len: int):
        """Call ``call``'s prompts: ids uniform over the held slice of the vocabulary, every row its own."""
        rng = np.random.default_rng([seed, 1, call])
        return rng.integers(0, self.cfg["vocab_size"], size=(batch_size, prompt_len), dtype=np.int32)

    def generate_fn(self, model, num_latents: int, new_tokens: int, cache_dtype: str):
        """The program's compiled greedy generator: (params, prompts) -> prompts + new tokens."""
        import jax.numpy as jnp

        from perceiver_io_tpu.generation import GenerationConfig, make_generate_fn

        return make_generate_fn(model, num_latents=num_latents, config=GenerationConfig(max_new_tokens=new_tokens),
                                cache_dtype=jnp.dtype(cache_dtype))

    # -------------------------------------------------------- the yardstick

    def train_flops(self, batch_size: int) -> float:
        return dsv3_cost.train_flops(self.cfg, batch_size, self.seq_len)

    def reference_logits(self, precision: str, latents: int):
        """``(weights, ids (B, N)) -> logits (B, latents, V)`` over the last ``latents`` positions."""
        return lambda w, ids: reference.logits(w, ids, self.cfg, precision, latents)
