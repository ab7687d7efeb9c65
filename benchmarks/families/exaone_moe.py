"""K-EXAONE's language model as one chip of eight that share each layer: the
program's ``DecoderLanguageModel`` under its grouped-query configuration with
a share of sigmoid-routed experts, a shared expert, a leading dense layer and
the multi-token-prediction module, behind the harness's family interface, for
the ``decode`` driver. Parameter shapes' method, the traffic (ids uniform over
the held slice of the vocabulary, every row its own) and the compiled greedy
generator are the decoder-only family's of ``families/deepseek_v3.py``; the
generator drafts with the module because the configuration has one
(``num_nextn_predict_layers`` 1), and still returns the prompts with their
new tokens.

The published ``config.json`` names the router's width ``num_experts`` (the
file counts the experts held under that key and keeps the width as
``router_width``), the shared experts ``num_shared_experts``, and keeps one
plain ``rope_parameters``. What the config has no key for (the q/k norm, which
layers rotate, the module's form) is the file's ``assumed``.

Every prompt position passes the whole stack, so there is no latent window:
``latents`` is ``seq_len``, the published context, and a cell's
``num_latents`` is 1. No cell trains this family; ``train_flops`` is the count
the harness asks every family for."""

from __future__ import annotations

from benchmarks.families import deepseek_v3
from benchmarks.lib import kexaone_cost
from benchmarks.reference import exaone_moe as reference

# the published keys the program's config takes under the same names
MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "first_k_dense_replace", "intermediate_size",
    "moe_intermediate_size", "num_attention_heads", "num_key_value_heads", "head_dim", "num_experts_per_tok", "n_group",
    "topk_group", "routed_scaling_factor", "scoring_func", "rms_norm_eps", "sliding_window", "max_position_embeddings",
    "num_nextn_predict_layers", "init_scale",
)


class Family(deepseek_v3.Family):
    def __init__(self, config: dict):
        self.cfg = {k: config[k] for k in MODEL_KEYS}
        depth, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
        if (not config["norm_topk_prob"] or config["rope_parameters"]["rope_type"] != "default"
                or list(config["mlp_layer_types"][:depth]) != ["dense"] * dense + ["sparse"] * (depth - dense)
                or any((w == 0) != (t == "full_attention") for w, t in zip(config["sliding_windows"], config["layer_types"]))
                or config["mtp_sliding_windows"] != [0] * len(config["mtp_layer_types"])):
            raise ValueError("families/exaone_moe.py: renormalised top-k weights, plain rotary, first_k_dense_replace dense "
                             "layers then sparse ones, a window on the sliding layers alone")
        self.cfg.update(
            # the file keeps the published lists whole; the chip runs their first ``num_hidden_layers`` entries
            layer_types=tuple(config["layer_types"][:depth]), mtp_layer_types=tuple(config["mtp_layer_types"]),
            n_routed_experts=config["router_width"], n_held_experts=config["num_experts"],
            held_experts_start=config["held_experts_start"], n_shared_experts=config["num_shared_experts"],
            rope_theta=float(config["rope_parameters"]["rope_theta"]), rope_scaling=None,
            # the file's ``assumed``: the EXAONE 4.0 family's q/k norm, and rotary on the window layers alone
            qk_norm=True, full_attention_rotary=False,
        )
        self.compute_dtype = config["dtypes"]["compute"]
        self.param_dtype = config["dtypes"]["params"]
        # nothing the generator owns slides: a call's growing caches hold its prompt and its new tokens
        self.seq_len = self.latents = self.cfg["max_position_embeddings"]

    def model(self):
        import dataclasses

        import jax.numpy as jnp

        from perceiver_io_tpu.models.text.decoder_lm import DecoderLanguageModel, DecoderLanguageModelConfig

        missing = set(self.cfg) - {f.name for f in dataclasses.fields(DecoderLanguageModelConfig)}
        if missing:  # a program from before the module: say so and stop, as for a cell without a file
            raise SystemExit(f"benchmarks/families/exaone_moe.py: the program's decoder configuration has no {sorted(missing)}")
        return DecoderLanguageModel(DecoderLanguageModelConfig(**self.cfg),
                                    dtype=jnp.dtype(self.compute_dtype), param_dtype=jnp.dtype(self.param_dtype))

    def param_shapes(self, model):
        import jax
        import jax.numpy as jnp

        # the module's weights are made where a forward asks it for its drafts
        return jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), drafts=True))

    def train_flops(self, batch_size: int) -> float:
        return kexaone_cost.train_flops(self.cfg, batch_size, self.seq_len)

    def reference_logits(self, precision: str, latents: int):
        """``(weights, ids (B, N)) -> logits (B, latents, V)`` of the main model over the last ``latents`` positions."""
        return lambda w, ids: reference.logits(w, ids, self.cfg, precision, latents)

    def reference_draft_logits(self, precision: str, latents: int):
        """The module's logits over the last ``latents`` of the ``N - 1`` positions that have a token after them."""
        return lambda w, ids: reference.mtp_logits(w, ids, self.cfg, precision, latents)
