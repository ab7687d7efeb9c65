"""dots3-note-prev's language model as one chip of eight that share each layer:
the program's ``DecoderLanguageModel`` under ``layer_types`` of
``"full_attention"`` and ``"sliding_attention"`` that select *latent* attention
(a full layer the configuration's own sizes under a lightning indexer and a
top-``index_topk`` selection, a sliding layer the ``swa_*`` sizes behind
``sliding_window_size`` positions), a leading dense layer, then a share of
sigmoid-routed experts with a shared expert, an untied head, behind the
harness's family interface, for the ``decode_routed`` driver. Parameter shapes,
the traffic (ids uniform over the held slice of the vocabulary, every row its
own) and the compiled greedy generator are the decoder-only family's of
``families/deepseek_v3.py``.

The file keeps every published key at its published value save the three under
``reduced``, and ``layer_types`` whole: the chip runs the entries ``held_layers``
names. What the config has no key for, or a key that reads two ways (the
rescale, the gate, the indexer's norm and rotary, the window's convention), is
the file's ``assumed``; program and reference share every one.

**The seeded attentions have to count.** ``lib/weights.py`` draws every leaf at
``init_scale`` (0.02). At these widths the leading dense feed-forward then adds
a standard deviation of 2.3 a channel to a residual stream that an attention
adds 0.3 to, and a wrong selection, a wrong window or a missing gate would move
a served logit by less than bfloat16 does (``PERF.md`` 6, PR 53's taps and PR
41's gates over again: a seeded leaf's scale is part of the traffic). So the
family hands the program and the reference alike, inside the one compiled
generator, the value columns of every attention's ``w_ukv`` times the file's
``seeded_attention_out_scale`` (4; the attended values, and so the attention's
output, are four times as large: what ``w_o`` times 4 would give, from a leaf a
fifth of its size, so that the scaled copies a call holds are 0.2 GB and not
0.6): an attention is then a third of the stream. No width is touched. The softmax needs no help: under the rescale of the normed
latents the seeded attention logits have a standard deviation of 2.

Every prompt position passes the whole stack, so there is no latent window:
``latents`` is ``seq_len``, the published context, and a cell's ``num_latents``
is 1. No cell trains this family (``PERF.md`` 4); ``train_flops`` is the count
the harness asks every family for. ``reference_logits`` takes
``"<precision>:<wrong>"`` for the wrong models of ``reference/dots3.py::WRONG``."""

from __future__ import annotations

from benchmarks.families import deepseek_v3
from benchmarks.lib import dots3_cost
from benchmarks.reference import dots3 as reference

# the published keys the program's config takes under the same names
MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "first_k_dense_replace", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "n_shared_experts", "num_experts_per_tok", "n_group", "topk_group", "rms_norm_eps", "max_position_embeddings", "scoring_func",
    "index_n_heads", "index_head_dim", "index_topk", "swa_q_lora_rank", "swa_kv_lora_rank", "swa_num_attention_heads",
    "swa_qk_nope_head_dim", "swa_qk_rope_head_dim", "swa_v_head_dim", "sliding_window_size", "tie_word_embeddings", "init_scale",
)


def scaled(flat: dict, out_scale: float, value_dims: dict) -> dict:
    """The seeded leaves as the family hands them on (the module docstring), ``flat`` a ``{"a/b/leaf": array}`` dict:
    the value columns of every attention's ``w_ukv`` (a head's ``[k_nope | v]``, ``value_dims[leaf's columns]`` the
    ``(nope, v)`` of the attention it belongs to) times ``out_scale``, every other leaf as it is."""
    import jax.numpy as jnp

    out = dict(flat)
    for name, leaf in flat.items():
        if name.endswith("/attn/w_ukv"):
            nope, v = value_dims[leaf.shape[1]]
            factors = jnp.concatenate([jnp.ones((nope,), leaf.dtype), jnp.full((v,), out_scale, leaf.dtype)])
            out[name] = (leaf.reshape(leaf.shape[0], -1, nope + v) * factors).reshape(leaf.shape)
    return out


class Family(deepseek_v3.Family):
    def __init__(self, config: dict):
        held = list(config["held_layers"])
        if (config["scoring_func"] != "sigmoid" or config["topk_method"] != "noaux_tc" or not config["norm_topk_prob"]
                or config["rope_scaling"] is not None or config["attention_bias"] or config["hidden_act"] != "silu"
                or config["moe_layer_freq"] != 1 or not config["apply_mla_qkv_lora_rescale"]
                or config["attention_gate_type"] != "headwise" or config["swa_attention_gate_type"] != "headwise"
                or config["num_key_value_heads"] != config["num_attention_heads"]
                or config["swa_num_key_value_heads"] != config["swa_num_attention_heads"]
                or len(held) != config["num_hidden_layers"] or held != sorted(set(held)) or held[0] != 0
                or held[-1] >= len(config["layer_types"])):
            raise ValueError("families/dots3.py: sigmoid scores under a bias with renormalised weights, plain rotary, no attention "
                             "bias, experts in every layer after the dense ones, the rescaled latents, head-wise gates, a key-value "
                             "head a head, held_layers rising from layer 0 within the published layer_types")
        self.cfg = {k: config[k] for k in MODEL_KEYS}
        self.cfg.update(
            # the file keeps the published list whole; the chip runs the entries ``held_layers`` names
            layer_types=tuple(config["layer_types"][i] for i in held),
            # the file counts the experts held under the published key; the router keeps its width
            n_routed_experts=config["router_width"], n_held_experts=config["n_routed_experts"],
            held_experts_start=config["held_experts_start"], routed_scaling_factor=float(config["routed_scaling_factor"]),
            rope_theta=float(config["rope_theta"]), swa_rope_theta=float(config["swa_rope_theta"]), rope_scaling=None,
            # the file's ``assumed``: LongCat-Flash's rescale of the normed latents, Ling 3.0's head-wise gate
            mla_scale_q_lora=True, mla_scale_kv_lora=True, mla_head_gate=True,
        )
        self.out_scale = float(config["seeded_attention_out_scale"])
        self.compute_dtype = config["dtypes"]["compute"]
        self.param_dtype = config["dtypes"]["params"]
        # nothing the generator owns slides: the growing caches hold a call's prompt and new tokens, a ring has one size
        self.seq_len = self.latents = self.cfg["max_position_embeddings"]

    def model(self):
        import jax.numpy as jnp

        from perceiver_io_tpu.models.text.decoder_lm import DecoderLanguageModel, DecoderLanguageModelConfig

        try:
            config = DecoderLanguageModelConfig(**self.cfg)
        except (TypeError, ValueError) as refusal:  # a program from before these layer kinds: say so and stop, as for a cell without a file
            raise SystemExit(f"benchmarks/families/dots3.py: the program's decoder configuration refuses the file's: {refusal}") from None
        return DecoderLanguageModel(config, dtype=jnp.dtype(self.compute_dtype), param_dtype=jnp.dtype(self.param_dtype))

    def seeded(self, flat: dict) -> dict:
        c = self.cfg
        dims = {c["num_attention_heads"] * (c["qk_nope_head_dim"] + c["v_head_dim"]): (c["qk_nope_head_dim"], c["v_head_dim"]),
                c["swa_num_attention_heads"] * (c["swa_qk_nope_head_dim"] + c["swa_v_head_dim"]): (c["swa_qk_nope_head_dim"], c["swa_v_head_dim"])}
        return scaled(flat, self.out_scale, dims)

    def generate_fn(self, model, num_latents: int, new_tokens: int, cache_dtype: str):
        """The program's compiled greedy generator over the seeded tree, its attentions' value projections scaled."""
        import jax

        from benchmarks.lib.weights import flat_dict

        generate = super().generate_fn(model, num_latents, new_tokens, cache_dtype)

        def handed_on(params):
            flat = self.seeded(flat_dict(params))  # in the tree's own order of leaves
            return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(params), list(flat.values()))

        return jax.jit(lambda params, prompts: generate(handed_on(params), prompts))

    def train_flops(self, batch_size: int) -> float:
        return dots3_cost.train_flops(self.cfg, batch_size, self.seq_len)

    def reference_logits(self, precision: str, latents: int):
        """``(weights, ids (B, N)) -> logits (B, latents, V)`` over the last ``latents`` positions; ``"float32:every_key"`` plants a fault."""
        precision, _, wrong = precision.partition(":")
        return lambda w, ids: reference.logits(self.seeded(w), ids, self.cfg, precision, latents, wrong or None)
