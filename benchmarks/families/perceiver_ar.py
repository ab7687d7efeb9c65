"""Perceiver AR causal language model: the program's ``CausalLanguageModel``
behind the harness's family interface."""

from __future__ import annotations

import numpy as np

from benchmarks.lib import flops
from benchmarks.reference import perceiver_ar as reference

MODEL_KEYS = (
    "vocab_size", "max_seq_len", "max_latents", "num_channels", "num_heads",
    "num_self_attention_layers", "num_self_attention_rotary_layers",
    "self_attention_widening_factor", "cross_attention_widening_factor",
    "cross_attention_dropout", "post_attention_dropout", "residual_dropout",
    "output_norm", "output_bias", "abs_pos_emb", "init_scale",
)


class Family:
    sample_unit = "tokens"

    def __init__(self, config: dict):
        self.cfg = {k: config[k] for k in MODEL_KEYS}
        self.compute_dtype = config["dtypes"]["compute"]
        self.seq_len, self.latents = self.cfg["max_seq_len"], self.cfg["max_latents"]
        self.prefix_len = self.seq_len - self.latents
        self.keep = self.prefix_len - int(self.prefix_len * self.cfg["cross_attention_dropout"])
        self.units_per_sample = self.seq_len

    # ---------------------------------------------------------- the program

    def model(self):
        import jax.numpy as jnp

        from perceiver_io_tpu.models.text import CausalLanguageModel, CausalLanguageModelConfig

        return CausalLanguageModel(CausalLanguageModelConfig(**self.cfg), dtype=jnp.dtype(self.compute_dtype))

    def param_shapes(self, model):
        import jax
        import jax.numpy as jnp

        ids = jnp.zeros((1, self.seq_len), jnp.int32)
        return jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids, prefix_len=self.prefix_len))

    def train_loss_fn(self, model):
        from perceiver_io_tpu.training import clm_loss_fn

        return clm_loss_fn(model.apply, max_latents=self.latents)

    # ---------------------------------------------------------- the traffic

    def train_batch(self, seed: int, step: int, batch_size: int) -> dict:
        """Step ``step``'s batch: uniform random tokens, inputs and labels one
        apart, and a uniformly random sorted keep set per row (the host-side
        prefix-dropout draw of ``training/prefix_dropout.py``)."""
        rng = np.random.default_rng([seed, step])
        t = rng.integers(0, self.cfg["vocab_size"], size=(batch_size, self.seq_len + 1), dtype=np.int32)
        r = rng.random((batch_size, self.prefix_len), dtype=np.float32)
        keep_idx = np.sort(np.argpartition(r, self.keep, axis=1)[:, :self.keep], axis=1).astype(np.int32)
        return {"input_ids": t[:, :-1], "labels": t[:, 1:], "pad_mask": None, "prefix_keep_idx": keep_idx}

    def prompts(self, seed: int, call: int, batch_size: int, prompt_len: int):
        """Call ``call``'s prompts: uniform random tokens, every row its own."""
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
        return flops.perceiver_ar_train_flops(self.cfg, batch_size)

    def flash_calls(self, batch_size: int) -> list:
        """The attention calls of one forward pass, by shape."""
        heads = self.cfg["num_heads"]
        d = self.cfg["num_channels"] // heads
        base = {"batch": batch_size, "heads": heads, "d_qk": d, "d_v": d, "causal": True}
        cross = {**base, "n_q": self.latents, "n_kv": self.keep + self.latents}
        self_ = {**base, "n_q": self.latents, "n_kv": self.latents}
        return [cross] + [self_] * self.cfg["num_self_attention_layers"]

    def reference_logits(self, precision: str, latents: int):
        """``(weights, ids (B, N)) -> logits (B, latents, V)`` over the last ``latents`` positions."""
        return lambda w, ids: reference.logits(w, ids, None, self.cfg, precision, latents)

    def reference_loss(self, precision: str):
        return lambda w, batch: reference.loss(w, batch, self.cfg, precision)

    @staticmethod
    def reference_batch(batch: dict) -> dict:
        return {k: v for k, v in batch.items() if v is not None}
