"""Ling-3.0-flash-VL's language model as one chip of four that share each layer:
the program's ``DecoderLanguageModel`` under ``layer_types`` of ``"kda"`` and
``"latent_attention"`` (Kimi delta attention layers beside latent attention as a
layer kind, a leading dense layer, then a share of sigmoid-routed experts with a
shared expert, an untied head) behind the harness's family interface, for the
``decode_routed`` driver. Parameter shapes, the traffic (ids uniform over the
held slice of the vocabulary, every row its own) and the compiled greedy
generator are the decoder-only family's of ``families/deepseek_v3.py``.

The published ``config.json`` names the router's width ``num_experts`` (the
file counts the experts held under that key and keeps the width as
``router_width``), the rule ``score_function``, the shared expert by its width.
What the config has no key for, or a key that reads two ways (the gate's bounded
form, the delta layers' heads, the norm before their output gate, the layer
order), is the file's ``assumed``; program and reference share every one.

**The seeded gate has to remember.** ``lib/weights.py`` draws every leaf at
``init_scale`` (0.02). For a delta layer that means ``A_log`` and ``dt_bias``
around 0, a log-decay of ``-5 sigmoid(.)`` = -2.5 a token a channel: nothing
older than three tokens reaches a logit, and a prompt pass that dropped its
carry at a chunk boundary, or lost the state at the hand-off to the steps, would
pass ``correct`` (``PERF.md`` 6, PR 41 and PR 46 over again). So the family
hands the program and the reference alike, inside the one compiled generator,
``dt_bias = logit(r / 5) / exp(A_log)`` with ``r`` log-uniform in
``[seeded_forget_min, seeded_forget_max]`` (the file's, 1e-4 to 1e-2) read off
the seeded leaf through the normal distribution's own cumulative function, a
channel and a layer (:func:`remembering`): at a zero projection a channel
forgets ``r`` a token, and ``W_f`` stays as drawn, so a token's gate still
depends on the token (``x W_f`` has a standard deviation of about 1). **The
seeded router bias** is scaled by the file's ``router_bias_scale`` as
``families/longcat_flash.py`` does, so that a call's time does not follow the
seed.

Every prompt position passes the whole stack, so there is no latent window:
``latents`` is ``seq_len``, the published context, and a cell's ``num_latents``
is 1. No cell trains this family (``PERF.md`` 4); ``train_flops`` is the count
the harness asks every family for."""

from __future__ import annotations

import math

from benchmarks.families import deepseek_v3
from benchmarks.lib import ling_cost
from benchmarks.reference import ling as reference

# the published keys the program's config takes under the same names
MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "first_k_dense_replace", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "head_dim",
    "num_experts_per_tok", "n_group", "topk_group", "rms_norm_eps", "max_position_embeddings", "short_conv_kernel_size",
    "init_scale",
)


def published_layer_types(config: dict) -> list:
    """The kinds of the stage's layers by the file's rule: published layer ``i`` is latent attention where ``(i + 1) % layer_group_size == 0``."""
    first = config["first_published_layer"]
    return ["latent_attention" if (i + 1) % config["layer_group_size"] == 0 else "kda" for i in range(first, first + config["num_hidden_layers"])]


def remembering(flat: dict, init_scale: float, forget_min: float, forget_max: float, lower_bound: float, bias_scale: float) -> dict:
    """The seeded leaves as the family hands them on (the module docstring),
    ``flat`` a ``{"a/b/leaf": array}`` dict: every delta layer's ``dt_bias`` in
    float32, ``logit(r / |lower_bound|) / exp(A_log)`` with ``r`` log-uniform over
    the range, read off the seeded noise; every router's bias times
    ``bias_scale``; every other leaf as it is."""
    import jax
    import jax.numpy as jnp

    out = dict(flat)
    for name, leaf in flat.items():
        if name.endswith("/gate_bias"):
            out[name] = leaf * bias_scale
        if not name.endswith("/dt_bias"):
            continue
        u = jax.scipy.stats.norm.cdf(leaf.astype(jnp.float32) / init_scale)  # uniform over (0, 1), from the seed
        share = jnp.exp(math.log(forget_min) + u * (math.log(forget_max) - math.log(forget_min))) / abs(lower_bound)
        heads = flat[name[: -len("dt_bias")] + "a_log"].astype(jnp.float32)
        rate = jnp.repeat(jnp.exp(heads), leaf.shape[0] // heads.shape[0])
        out[name] = (jnp.log(share) - jnp.log1p(-share)) / rate
    return out


class Family(deepseek_v3.Family):
    def __init__(self, config: dict):
        depth = config["num_hidden_layers"]
        first = config["first_published_layer"]
        held = range(first, first + depth)
        if (config["score_function"] != "sigmoid" or not config["moe_router_enable_expert_bias"] or not config["norm_topk_prob"]
                or not config["kda_safe_gate"] or not config["no_kda_lora"] or config["use_kda_lora"] or config["q_lora_rank"] is not None
                or config["gated_attention_proj_granularity_type"] != "head_wise" or config["use_nGPT"] or config["scale_router_input"]
                or config["value_norm"] or config["up_proj_norm"] or config["rotary_dim"] != config["qk_rope_head_dim"]
                or list(config["layer_types"]) != published_layer_types(config)
                or config["moe_shared_expert_intermediate_size"] % config["moe_intermediate_size"]
                or any(config[key][i] for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list") for i in held)):
            raise ValueError("families/ling.py: sigmoid scores under a bias with renormalised weights, the bounded full-rank gate, no "
                             "query latent, a head-wise attention gate, layer_types by layer_group_size, no clamped SwiGLU in the held layers")
        self.cfg = {k: config[k] for k in MODEL_KEYS}
        self.cfg.update(
            # the file counts the experts held under the published key; the router keeps its width
            n_routed_experts=config["router_width"], n_held_experts=config["num_experts"],
            held_experts_start=config["held_experts_start"],
            n_shared_experts=config["moe_shared_expert_intermediate_size"] // config["moe_intermediate_size"],
            routed_scaling_factor=float(config["routed_scaling_factor"]), rope_theta=float(config["rope_theta"]), rope_scaling=None,
            kda_lower_bound=float(config["kda_lower_bound"]), layer_types=tuple(config["layer_types"]),
            scoring_func="sigmoid", mla_head_gate=True,
        )
        self.seeding = dict(forget_min=float(config["seeded_forget_min"]), forget_max=float(config["seeded_forget_max"]),
                            bias_scale=float(config["router_bias_scale"]))
        self.compute_dtype = config["dtypes"]["compute"]
        self.param_dtype = config["dtypes"]["params"]
        # nothing the generator owns slides: the latent cache holds a call's prompt and new tokens, the states have one size
        self.seq_len = self.latents = self.cfg["max_position_embeddings"]

    def model(self):
        import jax.numpy as jnp

        from perceiver_io_tpu.models.text.decoder_lm import DecoderLanguageModel, DecoderLanguageModelConfig

        try:
            config = DecoderLanguageModelConfig(**self.cfg)
        except (TypeError, ValueError) as refusal:  # a program from before the delta layer: say so and stop, as for a cell without a file
            raise SystemExit(f"benchmarks/families/ling.py: the program's decoder configuration refuses the file's: {refusal}") from None
        return DecoderLanguageModel(config, dtype=jnp.dtype(self.compute_dtype), param_dtype=jnp.dtype(self.param_dtype))

    def seeded(self, flat: dict) -> dict:
        return remembering(flat, self.cfg["init_scale"], lower_bound=self.cfg["kda_lower_bound"], **self.seeding)

    def generate_fn(self, model, num_latents: int, new_tokens: int, cache_dtype: str):
        """The program's compiled greedy generator over the seeded tree, its gates made to remember and its router biases scaled."""
        import jax

        from benchmarks.lib.weights import flat_dict

        generate = super().generate_fn(model, num_latents, new_tokens, cache_dtype)

        def handed_on(params):
            flat = self.seeded(flat_dict(params))  # in the tree's own order of leaves
            return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(params), list(flat.values()))

        return jax.jit(lambda params, prompts: generate(handed_on(params), prompts))

    def train_flops(self, batch_size: int) -> float:
        return ling_cost.train_flops(self.cfg, batch_size, self.seq_len)

    def reference_logits(self, precision: str, latents: int):
        """``(weights, ids (B, N)) -> logits (B, latents, V)`` over the last ``latents`` positions."""
        return lambda w, ids: reference.logits(self.seeded(w), ids, self.cfg, precision, latents)
