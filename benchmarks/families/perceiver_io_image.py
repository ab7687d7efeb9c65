"""Perceiver IO image classifier: the program's ``ImageClassifier`` behind
the harness's family interface."""

from __future__ import annotations

import numpy as np

from benchmarks.lib import flops
from benchmarks.reference import perceiver_io_image as reference


class Family:
    sample_unit = "images"
    units_per_sample = 1

    def __init__(self, config: dict):
        self.cfg = config
        self.compute_dtype = config["dtypes"]["compute"]
        self.image_shape = tuple(config["image_shape"])

    # ---------------------------------------------------------- the program

    def model(self):
        import jax.numpy as jnp

        from perceiver_io_tpu.core.config import ClassificationDecoderConfig
        from perceiver_io_tpu.models.vision.image_classifier import (
            ImageClassifier, ImageClassifierConfig, ImageEncoderConfig,
        )

        c = self.cfg
        config = ImageClassifierConfig(
            encoder=ImageEncoderConfig(
                image_shape=self.image_shape,
                num_frequency_bands=c["num_frequency_bands"],
                num_cross_attention_heads=c["num_cross_attention_heads"],
                num_cross_attention_layers=c["num_cross_attention_layers"],
                cross_attention_widening_factor=c["cross_attention_widening_factor"],
                num_self_attention_heads=c["num_self_attention_heads"],
                num_self_attention_layers_per_block=c["num_self_attention_layers_per_block"],
                num_self_attention_blocks=c["num_self_attention_blocks"],
                first_self_attention_block_shared=c["first_self_attention_block_shared"],
                self_attention_widening_factor=c["self_attention_widening_factor"],
                dropout=c["dropout"],
                init_scale=c["init_scale"],
            ),
            decoder=ClassificationDecoderConfig(
                num_classes=c["num_classes"],
                num_output_query_channels=c["num_output_query_channels"],
                num_cross_attention_heads=c["decoder_num_cross_attention_heads"],
                cross_attention_widening_factor=c["decoder_cross_attention_widening_factor"],
                dropout=c["dropout"],
                init_scale=c["init_scale"],
            ),
            num_latents=c["num_latents"],
            num_latent_channels=c["num_latent_channels"],
        )
        return ImageClassifier(config, dtype=jnp.dtype(self.compute_dtype))

    def param_shapes(self, model):
        import jax
        import jax.numpy as jnp

        image = jnp.zeros((1,) + self.image_shape, jnp.float32)
        return jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), image))

    def train_loss_fn(self, model):
        from perceiver_io_tpu.training import classification_loss_fn

        return classification_loss_fn(model.apply)

    # ---------------------------------------------------------- the traffic

    def train_batch(self, seed: int, step: int, batch_size: int) -> dict:
        """Step ``step``'s batch: standard-normal pixels and uniform labels."""
        rng = np.random.default_rng([seed, step])
        image = rng.standard_normal((batch_size,) + self.image_shape, dtype=np.float32)
        label = rng.integers(0, self.cfg["num_classes"], size=(batch_size,), dtype=np.int32)
        return {"image": image, "label": label}

    # -------------------------------------------------------- the yardstick

    def train_flops(self, batch_size: int) -> float:
        return flops.perceiver_io_image_train_flops(self.cfg, batch_size)

    def flash_calls(self, batch_size: int) -> list:
        """The attention calls of one forward pass, by shape (the decoder's
        single query is left out: it is no flash call)."""
        c = self.cfg
        pixels = int(np.prod(self.image_shape[:-1]))
        in_ch = self.image_shape[-1] + len(self.image_shape[:-1]) * (2 * c["num_frequency_bands"] + 1)
        cross = {"batch": batch_size, "heads": c["num_cross_attention_heads"], "n_q": c["num_latents"],
                 "n_kv": pixels, "d_qk": in_ch // c["num_cross_attention_heads"],
                 "d_v": in_ch // c["num_cross_attention_heads"], "causal": False}
        d = c["num_latent_channels"] // c["num_self_attention_heads"]
        self_ = {"batch": batch_size, "heads": c["num_self_attention_heads"], "n_q": c["num_latents"],
                 "n_kv": c["num_latents"], "d_qk": d, "d_v": d, "causal": False}
        layers = c["num_self_attention_layers_per_block"] * c["num_self_attention_blocks"]
        return [cross] + [self_] * layers

    def reference_loss(self, precision: str):
        return lambda w, batch: reference.loss(w, batch, self.cfg, precision)

    @staticmethod
    def reference_batch(batch: dict) -> dict:
        return dict(batch)
