"""Microsoft's Phi-4-mini-flash-reasoning whole on one chip: the program's
``DecoderLanguageModel`` under its decoder-hybrid-decoder configuration (SambaY:
Mamba-1 and differential window attention below, one differential full
attention whose keys and values are the shared cache, gated memory units and
cross-attentions above it, LayerNorms, a dense SwiGLU in every layer, a tied
head) behind the harness's family interface, for the ``decode`` driver.
Parameter shapes, the traffic (ids uniform over the vocabulary, every row its
own) and the compiled greedy generator are the decoder-only family's of
``families/deepseek_v3.py``.

The published ``config.json`` gives the layer order through ``mb_per_layer``
and ``num_hidden_layers`` (:func:`layer_types`, the ``phi4flash`` rule) and no
head size; the program's configuration takes ``layer_types`` and ``head_dim``.
What the config has no key for (the rule itself, the Mamba sizes, which heads
pair, ``lam0``, the subnorm, the attention biases) is the file's ``assumed``.

**The seeded recurrence has to remember**, as ``families/jamba.py`` says and by
its :func:`~benchmarks.families.jamba.remembering`: ``a_log``, ``dt_bias`` and
``d_skip`` of the nine Mamba mixers are handed to program and reference alike
as the Mamba reference initialisation around the seeded noise, inside the one
compiled generator (that family's ``generate_fn``, inherited). **And it has to
be heard.** ``lib/weights.py`` draws the convolution's taps at ``init_scale``
(0.02) too, which leaves a mixer's ``x`` at 0.02 a channel: a Mamba layer and a
gated memory unit then add a twentieth of what an attention adds to the
residual, and a memory taken after the gate, wrong in all seven units, passed
``correct`` (0.024 under a limit of 0.7: this PR's first chip runs). So this
family's :meth:`Family._remembering` also hands on ``conv_w``, as the file's
``seeded_conv_centre`` around the seeded noise: 0.5 = ``1 / sqrt(K)``, under
which the convolution keeps its inputs' variance (``x`` at 0.61 a channel, a
mixer and a unit at 0.55 in the residual where an attention adds 0.41:
reckoned from seeded weights at the published widths). On the chip that wrong
model then reads 1.5 to 1.9 against the sound program's 0.12 to 0.22 (``PERF.md``
2); at ``1 / K`` it read 0.42 beside a sound 0.23, no room for a limit between them.

Every prompt position passes the self-decoder, so there is no latent window:
``latents`` is ``seq_len``, the published context, and a cell's ``num_latents``
is 1. ``reference_logits`` takes, beside the harness's precisions,
``"float32:<wrong>"`` for the wrong models of ``reference/phi4flash.py`` (the
builder's controls of what the limit sees). No cell trains this family
(``PERF.md`` 4); ``train_flops`` is the count the harness asks every family for."""

from __future__ import annotations

from benchmarks.families import jamba
from benchmarks.lib import phi4flash_cost
from benchmarks.reference import phi4flash as reference

# the published keys the program's config takes under the same names
MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "intermediate_size", "num_attention_heads", "num_key_value_heads",
    "sliding_window", "layer_norm_eps", "max_position_embeddings", "tie_word_embeddings", "init_scale",
    "mamba_expand", "mamba_d_state", "mamba_dt_rank", "mamba_d_conv",
)


def layer_types(config: dict) -> tuple:
    """The program's name for each layer's mixer, by the ``phi4flash`` rule:
    every ``mb_per_layer``-th layer is the state-space side (a Mamba mixer
    below the cross-decoder, a gated memory unit in it), the others attend (a
    window below ``num_hidden_layers // 2 + 1``, that layer full, cross above it)."""
    n, per = config["num_hidden_layers"], config["mb_per_layer"]
    owner = n // 2 + 1
    return tuple(
        ("mamba" if i < owner else "gmu") if i % per == 0
        else "sliding_attention" if i < owner else "full_attention" if i == owner else "cross_attention"
        for i in range(n)
    )


class Family(jamba.Family):
    def __init__(self, config: dict):
        if (config["mlp_bias"] or config["lm_head_bias"] or config["hidden_act"] != "silu" or config["mb_per_layer"] != 2
                or config["hidden_size"] % config["num_attention_heads"]):
            raise ValueError("families/phi4flash.py: no MLP or head bias, silu, every second layer the state-space side")
        self.cfg = {k: config[k] for k in MODEL_KEYS}
        self.cfg.update(
            layer_types=layer_types(config), head_dim=config["hidden_size"] // config["num_attention_heads"],
            differential_attention=True, mamba_inner_norms=False,
            # what the program's one configuration class also asks for: every layer dense, no rotary anywhere
            first_k_dense_replace=config["num_hidden_layers"], rope_scaling=None,
        )
        self.reference_cfg = dict(self.cfg, mb_per_layer=config["mb_per_layer"])  # the reference reads the published rule itself
        self.dt_range = (float(config["seeded_dt_min"]), float(config["seeded_dt_max"]))
        self.conv_centre = float(config["seeded_conv_centre"])
        self.compute_dtype = config["dtypes"]["compute"]
        self.param_dtype = config["dtypes"]["params"]
        # nothing the generator owns slides: the shared cache holds a call's prompt and new tokens, rings and states have one size
        self.seq_len = self.latents = self.cfg["max_position_embeddings"]

    def model(self):
        import jax.numpy as jnp

        from perceiver_io_tpu.models.text.decoder_lm import DecoderLanguageModel, DecoderLanguageModelConfig

        try:
            config = DecoderLanguageModelConfig(**self.cfg)
        except (TypeError, ValueError) as refusal:  # a program from before these layer kinds: say so and stop, as for a cell without a file
            raise SystemExit(f"benchmarks/families/phi4flash.py: the program's decoder configuration refuses the file's: {refusal}") from None
        return DecoderLanguageModel(config, dtype=jnp.dtype(self.compute_dtype), param_dtype=jnp.dtype(self.param_dtype))

    def _remembering(self, name: str, leaf):
        if name == "conv_w":  # (K, d): the module docstring's taps
            import jax.numpy as jnp

            return self.conv_centre + leaf.astype(jnp.float32)
        return super()._remembering(name, leaf)

    def train_flops(self, batch_size: int) -> float:
        return phi4flash_cost.train_flops(self.cfg, batch_size, self.seq_len)

    def reference_logits(self, precision: str, latents: int):
        """``(weights, ids (B, N)) -> logits (B, latents, V)`` over the last ``latents`` positions."""
        precision, _, wrong = precision.partition(":")
        return lambda w, ids: reference.logits({k: self._remembering(k.rsplit("/", 1)[-1], v) for k, v in w.items()},
                                               ids, self.reference_cfg, precision, latents, wrong or None)
