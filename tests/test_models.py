"""Construction + forward-shape tests for the task models
(reference pattern: tests/text_classifier_test.py:36-46 and friends)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.core.config import ClassificationDecoderConfig
from perceiver_io_tpu.models.audio import SymbolicAudioModel, SymbolicAudioModelConfig
from perceiver_io_tpu.models.text import (
    CausalLanguageModel,
    CausalLanguageModelConfig,
    MaskedLanguageModel,
    MaskedLanguageModelConfig,
    TextClassifier,
    TextClassifierConfig,
    TextDecoderConfig,
    TextEncoderConfig,
)
from perceiver_io_tpu.models.vision import (
    ImageClassifier,
    ImageClassifierConfig,
    ImageEncoderConfig,
    OpticalFlow,
    OpticalFlowConfig,
    OpticalFlowDecoderConfig,
    OpticalFlowEncoderConfig,
)

VOCAB = 101
MAX_SEQ_LEN = 32
B = 2


def small_text_encoder_config():
    return TextEncoderConfig(
        vocab_size=VOCAB,
        max_seq_len=MAX_SEQ_LEN,
        num_input_channels=32,
        num_cross_attention_heads=2,
        num_self_attention_heads=2,
        num_self_attention_layers_per_block=2,
    )


@pytest.mark.slow
def test_text_classifier_shapes():
    config = TextClassifierConfig(
        encoder=small_text_encoder_config(),
        decoder=ClassificationDecoderConfig(
            num_classes=2, num_output_query_channels=32, num_cross_attention_heads=2
        ),
        num_latents=8,
        num_latent_channels=16,
    )
    model = TextClassifier(config)
    x = jnp.zeros((B, MAX_SEQ_LEN), jnp.int32)
    pad = jnp.zeros((B, MAX_SEQ_LEN), bool)
    params = model.init(jax.random.PRNGKey(0), x, pad)
    logits = model.apply(params, x, pad)
    assert logits.shape == (B, 2)


@pytest.mark.parametrize("tied", [True, False])
@pytest.mark.slow
def test_masked_language_model_shapes(tied):
    config = MaskedLanguageModelConfig(
        encoder=small_text_encoder_config(),
        decoder=TextDecoderConfig(
            vocab_size=VOCAB,
            max_seq_len=MAX_SEQ_LEN,
            num_output_query_channels=None if tied else 24,
            num_cross_attention_heads=2,
        ),
        num_latents=8,
        num_latent_channels=16,
    )
    model = MaskedLanguageModel(config)
    n = MAX_SEQ_LEN - 4  # logits truncated to input length
    x = jnp.zeros((B, n), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), x)
    logits = model.apply(params, x)
    assert logits.shape == (B, n, VOCAB)


def test_causal_language_model_shapes():
    config = CausalLanguageModelConfig(
        vocab_size=VOCAB,
        max_seq_len=MAX_SEQ_LEN,
        max_latents=16,
        num_channels=32,
        num_heads=4,
        num_self_attention_layers=2,
    )
    model = CausalLanguageModel(config)
    x = jnp.zeros((B, MAX_SEQ_LEN), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), x, prefix_len=16)
    out = model.apply(params, x, prefix_len=16)
    assert out.logits.shape == (B, 16, VOCAB)


def test_symbolic_audio_model_vocab():
    config = SymbolicAudioModelConfig(
        max_seq_len=MAX_SEQ_LEN, max_latents=16, num_channels=32, num_heads=4, num_self_attention_layers=1
    )
    assert config.vocab_size == 389
    model = SymbolicAudioModel(config)
    x = jnp.zeros((B, MAX_SEQ_LEN), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), x, prefix_len=16)
    out = model.apply(params, x, prefix_len=16)
    assert out.logits.shape == (B, 16, 389)


@pytest.mark.slow
def test_image_classifier_shapes():
    config = ImageClassifierConfig(
        encoder=ImageEncoderConfig(
            image_shape=(14, 14, 1),
            num_frequency_bands=8,
            num_cross_attention_heads=1,
            num_self_attention_heads=2,
            num_self_attention_layers_per_block=2,
        ),
        decoder=ClassificationDecoderConfig(
            num_classes=10, num_output_query_channels=32, num_cross_attention_heads=1
        ),
        num_latents=8,
        num_latent_channels=16,
    )
    model = ImageClassifier(config)
    x = jnp.zeros((B, 14, 14, 1))
    params = model.init(jax.random.PRNGKey(0), x)
    logits = model.apply(params, x)
    assert logits.shape == (B, 10)


def test_image_classifier_rejects_wrong_shape():
    config = ImageClassifierConfig(
        encoder=ImageEncoderConfig(image_shape=(14, 14, 1), num_frequency_bands=8),
        decoder=ClassificationDecoderConfig(num_classes=10, num_output_query_channels=32),
        num_latents=8,
        num_latent_channels=16,
    )
    model = ImageClassifier(config)
    with pytest.raises(ValueError, match="different from required shape"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((B, 16, 16, 1)))


@pytest.mark.slow
def test_optical_flow_shapes():
    h, w = 16, 24
    config = OpticalFlowConfig(
        encoder=OpticalFlowEncoderConfig(
            image_shape=(h, w),
            num_patch_input_channels=5,
            num_patch_hidden_channels=16,
            num_frequency_bands=4,
            num_cross_attention_heads=1,
            num_self_attention_heads=2,
            num_self_attention_layers_per_block=1,
        ),
        decoder=OpticalFlowDecoderConfig(image_shape=(h, w), num_cross_attention_heads=1),
        num_latents=8,
        num_latent_channels=16,
    )
    model = OpticalFlow(config)
    x = jnp.zeros((B, 2, h, w, 5))
    params = model.init(jax.random.PRNGKey(0), x)
    flow = model.apply(params, x)
    assert flow.shape == (B, h, w, 2)
    # rescale_factor shrinks outputs
    assert float(jnp.max(jnp.abs(flow))) < 1.0


def test_weight_shared_encoder_blocks():
    """Repeated cross-attention with sharing has the same parameter count as a
    single layer; unshared adds parameters (reference: modules.py:579-602)."""
    def build(first_shared):
        cfg = TextClassifierConfig(
            encoder=TextEncoderConfig(
                vocab_size=VOCAB,
                max_seq_len=MAX_SEQ_LEN,
                num_input_channels=32,
                num_cross_attention_layers=2,
                num_self_attention_blocks=2,
                first_cross_attention_layer_shared=first_shared,
                first_self_attention_block_shared=True,
                num_cross_attention_heads=2,
                num_self_attention_heads=2,
                num_self_attention_layers_per_block=1,
            ),
            decoder=ClassificationDecoderConfig(
                num_classes=2, num_output_query_channels=32, num_cross_attention_heads=2
            ),
            num_latents=8,
            num_latent_channels=16,
        )
        model = TextClassifier(cfg)
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((B, MAX_SEQ_LEN), jnp.int32), None)
        return sum(p.size for p in jax.tree.leaves(params))

    assert build(first_shared=False) > build(first_shared=True)


class TestEncoderValidationRules:
    """Constructor validation parity (reference: PerceiverEncoder.__init__
    rules, perceiver/model/core/modules.py:497-516)."""

    def _encoder(self, **overrides):
        import jax
        import jax.numpy as jnp

        from perceiver_io_tpu.core.adapter import TokenInputAdapter
        from perceiver_io_tpu.core.modules import PerceiverEncoder

        adapter = TokenInputAdapter(vocab_size=32, max_seq_len=16, num_input_channels=16)
        kwargs = dict(
            input_adapter=adapter,
            num_latents=4,
            num_latent_channels=16,
            num_cross_attention_heads=2,
            num_self_attention_heads=2,
            num_self_attention_layers_per_block=1,
        )
        kwargs.update(overrides)
        enc = PerceiverEncoder(**kwargs)
        return enc.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

    def test_cross_attention_layers_must_be_positive(self):
        import pytest

        with pytest.raises(ValueError, match="num_cross_attention_layers must be > 0"):
            self._encoder(num_cross_attention_layers=0)

    def test_self_attention_blocks_must_be_positive(self):
        import pytest

        with pytest.raises(ValueError, match="num_self_attention_blocks must be > 0"):
            self._encoder(num_self_attention_blocks=0)

    def test_cross_layers_bounded_by_blocks(self):
        import pytest

        with pytest.raises(ValueError, match="must be <= num_self_attention_blocks"):
            self._encoder(num_cross_attention_layers=3, num_self_attention_blocks=2)

    def test_head_divisibility(self):
        import pytest

        with pytest.raises(ValueError, match="divisible by num_heads"):
            self._encoder(num_cross_attention_qk_channels=18, num_cross_attention_heads=4)


class TestActivationCheckpointing:
    """Remat (reference: fairscale checkpoint_wrapper, modules.py:933-956) and
    its host-offload variant (reference: activation_offloading / CPU offload,
    config.py:60-61,75-76 — here offload_dot_with_no_batch_dims to
    pinned_host): both must leave forward values and gradients unchanged."""

    def _clm(self, **flags):
        config = CausalLanguageModelConfig(
            vocab_size=VOCAB,
            max_seq_len=MAX_SEQ_LEN,
            max_latents=8,
            num_channels=32,
            num_heads=4,
            num_self_attention_layers=2,
            cross_attention_dropout=0.0,
            **flags,
        )
        return CausalLanguageModel(config)

    @pytest.mark.parametrize("flag", ["activation_checkpointing", "activation_offloading"])
    @pytest.mark.slow
    def test_clm_values_and_grads_unchanged(self, flag):
        base = self._clm()
        wrapped = self._clm(**{flag: True})
        ids = jnp.asarray(
            jax.random.randint(jax.random.PRNGKey(1), (B, MAX_SEQ_LEN), 0, VOCAB)
        )
        params = base.init(jax.random.PRNGKey(0), ids, prefix_len=24)

        def loss(model, p):
            return model.apply(p, ids, prefix_len=24).logits.astype(jnp.float32).mean()

        ref, ref_g = jax.jit(jax.value_and_grad(lambda p: loss(base, p)))(params)
        out, out_g = jax.jit(jax.value_and_grad(lambda p: loss(wrapped, p)))(params)
        assert float(out) == pytest.approx(float(ref), abs=1e-6)
        for a, b in zip(jax.tree.leaves(out_g), jax.tree.leaves(ref_g)):
            assert jnp.allclose(a, b, atol=1e-6)

    @pytest.mark.slow
    def test_image_classifier_offloading_builds_and_runs(self):
        config = ImageClassifierConfig(
            encoder=ImageEncoderConfig(
                image_shape=(14, 14, 1),
                num_frequency_bands=8,
                num_cross_attention_heads=1,
                num_self_attention_heads=2,
                num_self_attention_layers_per_block=2,
            ),
            decoder=ClassificationDecoderConfig(
                num_classes=10, num_output_query_channels=32, num_cross_attention_heads=1
            ),
            num_latents=8,
            num_latent_channels=16,
            activation_offloading=True,
        )
        model = ImageClassifier(config)
        x = jnp.zeros((B, 14, 14, 1))
        params = model.init(jax.random.PRNGKey(0), x)

        def loss(p):
            return model.apply(p, x).astype(jnp.float32).sum()

        g = jax.jit(jax.grad(loss))(params)
        assert all(jnp.all(jnp.isfinite(le)) for le in jax.tree.leaves(g))


def test_pos_embedding_slice_path_matches_gather():
    """The scatter-free (abs_pos=None) embedding path must equal the explicit
    arange gather path, including clip behavior past max_seq_len."""
    from perceiver_io_tpu.core.adapter import TokenInputAdapter
    from perceiver_io_tpu.core.position import positions

    adapter = TokenInputAdapter(vocab_size=50, max_seq_len=12, num_input_channels=16)
    x = jnp.asarray(np.random.default_rng(0).integers(0, 50, size=(2, 12)))
    params = adapter.init(jax.random.PRNGKey(0), x)

    fast = adapter.apply(params, x)  # abs_pos=None
    ref = adapter.apply(params, x, positions(2, 12))
    np.testing.assert_allclose(np.asarray(fast), np.asarray(ref), atol=1e-7)

    # longer than the table: positions clip to the last row on both paths
    x_long = jnp.asarray(np.random.default_rng(1).integers(0, 50, size=(2, 15)))
    fast_long = adapter.apply(params, x_long)
    ref_long = adapter.apply(params, x_long, positions(2, 15))
    np.testing.assert_allclose(np.asarray(fast_long), np.asarray(ref_long), atol=1e-7)


# --- the parameter tree, written down at PR 29 (commit 42ece42) ----------------
#
# PR 30 renamed the LayerNorm module class of ``ops/layernorm.py``. Every site
# is a named attribute or passes ``name=``, so no path may move: a checkpoint
# of before loads after.

PARAMETER_TREES = {
    "perceiver_ar": """
        params/input_adapter/pos_embedding/embedding 32x16
        params/input_adapter/txt_embedding/embedding 101x16
        params/out_norm/bias 16
        params/out_norm/scale 16
        params/output_adapter/bias 101
        params/perceiver_ar/cross_attention/cross_attn/attention/k_proj/kernel 16x16
        params/perceiver_ar/cross_attention/cross_attn/attention/o_proj/bias 16
        params/perceiver_ar/cross_attention/cross_attn/attention/o_proj/kernel 16x16
        params/perceiver_ar/cross_attention/cross_attn/attention/q_proj/kernel 16x16
        params/perceiver_ar/cross_attention/cross_attn/attention/v_proj/kernel 16x16
        params/perceiver_ar/cross_attention/cross_attn/kv_norm/bias 16
        params/perceiver_ar/cross_attention/cross_attn/kv_norm/scale 16
        params/perceiver_ar/cross_attention/cross_attn/q_norm/bias 16
        params/perceiver_ar/cross_attention/cross_attn/q_norm/scale 16
        params/perceiver_ar/cross_attention/mlp/LayerNorm_0/bias 16
        params/perceiver_ar/cross_attention/mlp/LayerNorm_0/scale 16
        params/perceiver_ar/cross_attention/mlp/dense_1/kernel 16x64
        params/perceiver_ar/cross_attention/mlp/dense_2/kernel 64x16
        params/perceiver_ar/self_attention/layer_0/mlp/LayerNorm_0/bias 16
        params/perceiver_ar/self_attention/layer_0/mlp/LayerNorm_0/scale 16
        params/perceiver_ar/self_attention/layer_0/mlp/dense_1/kernel 16x64
        params/perceiver_ar/self_attention/layer_0/mlp/dense_2/kernel 64x16
        params/perceiver_ar/self_attention/layer_0/self_attn/attention/k_proj/kernel 16x16
        params/perceiver_ar/self_attention/layer_0/self_attn/attention/o_proj/kernel 16x16
        params/perceiver_ar/self_attention/layer_0/self_attn/attention/q_proj/kernel 16x16
        params/perceiver_ar/self_attention/layer_0/self_attn/attention/v_proj/kernel 16x16
        params/perceiver_ar/self_attention/layer_0/self_attn/norm/bias 16
        params/perceiver_ar/self_attention/layer_0/self_attn/norm/scale 16
    """,
    "image_classifier": """
        params/decoder/cross_attn/cross_attn/attention/k_proj/bias 16
        params/decoder/cross_attn/cross_attn/attention/k_proj/kernel 16x16
        params/decoder/cross_attn/cross_attn/attention/o_proj/bias 16
        params/decoder/cross_attn/cross_attn/attention/o_proj/kernel 16x16
        params/decoder/cross_attn/cross_attn/attention/q_proj/bias 16
        params/decoder/cross_attn/cross_attn/attention/q_proj/kernel 16x16
        params/decoder/cross_attn/cross_attn/attention/v_proj/bias 16
        params/decoder/cross_attn/cross_attn/attention/v_proj/kernel 16x16
        params/decoder/cross_attn/cross_attn/kv_norm/bias 16
        params/decoder/cross_attn/cross_attn/kv_norm/scale 16
        params/decoder/cross_attn/cross_attn/q_norm/bias 16
        params/decoder/cross_attn/cross_attn/q_norm/scale 16
        params/decoder/cross_attn/mlp/LayerNorm_0/bias 16
        params/decoder/cross_attn/mlp/LayerNorm_0/scale 16
        params/decoder/cross_attn/mlp/dense_1/bias 16
        params/decoder/cross_attn/mlp/dense_1/kernel 16x16
        params/decoder/cross_attn/mlp/dense_2/bias 16
        params/decoder/cross_attn/mlp/dense_2/kernel 16x16
        params/decoder/output_adapter/linear/bias 5
        params/decoder/output_adapter/linear/kernel 16x5
        params/decoder/output_query_provider/query 1x16
        params/encoder/cross_attn_1/cross_attn/attention/k_proj/bias 21
        params/encoder/cross_attn_1/cross_attn/attention/k_proj/kernel 21x21
        params/encoder/cross_attn_1/cross_attn/attention/o_proj/bias 16
        params/encoder/cross_attn_1/cross_attn/attention/o_proj/kernel 21x16
        params/encoder/cross_attn_1/cross_attn/attention/q_proj/bias 21
        params/encoder/cross_attn_1/cross_attn/attention/q_proj/kernel 16x21
        params/encoder/cross_attn_1/cross_attn/attention/v_proj/bias 21
        params/encoder/cross_attn_1/cross_attn/attention/v_proj/kernel 21x21
        params/encoder/cross_attn_1/cross_attn/kv_norm/bias 21
        params/encoder/cross_attn_1/cross_attn/kv_norm/scale 21
        params/encoder/cross_attn_1/cross_attn/q_norm/bias 16
        params/encoder/cross_attn_1/cross_attn/q_norm/scale 16
        params/encoder/cross_attn_1/mlp/LayerNorm_0/bias 16
        params/encoder/cross_attn_1/mlp/LayerNorm_0/scale 16
        params/encoder/cross_attn_1/mlp/dense_1/bias 16
        params/encoder/cross_attn_1/mlp/dense_1/kernel 16x16
        params/encoder/cross_attn_1/mlp/dense_2/bias 16
        params/encoder/cross_attn_1/mlp/dense_2/kernel 16x16
        params/encoder/latent_provider/query 4x16
        params/encoder/self_attn_1/layer_0/mlp/LayerNorm_0/bias 16
        params/encoder/self_attn_1/layer_0/mlp/LayerNorm_0/scale 16
        params/encoder/self_attn_1/layer_0/mlp/dense_1/bias 16
        params/encoder/self_attn_1/layer_0/mlp/dense_1/kernel 16x16
        params/encoder/self_attn_1/layer_0/mlp/dense_2/bias 16
        params/encoder/self_attn_1/layer_0/mlp/dense_2/kernel 16x16
        params/encoder/self_attn_1/layer_0/self_attn/attention/k_proj/bias 16
        params/encoder/self_attn_1/layer_0/self_attn/attention/k_proj/kernel 16x16
        params/encoder/self_attn_1/layer_0/self_attn/attention/o_proj/bias 16
        params/encoder/self_attn_1/layer_0/self_attn/attention/o_proj/kernel 16x16
        params/encoder/self_attn_1/layer_0/self_attn/attention/q_proj/bias 16
        params/encoder/self_attn_1/layer_0/self_attn/attention/q_proj/kernel 16x16
        params/encoder/self_attn_1/layer_0/self_attn/attention/v_proj/bias 16
        params/encoder/self_attn_1/layer_0/self_attn/attention/v_proj/kernel 16x16
        params/encoder/self_attn_1/layer_0/self_attn/norm/bias 16
        params/encoder/self_attn_1/layer_0/self_attn/norm/scale 16
    """,
}


def _tiny_perceiver_ar():
    model = CausalLanguageModel(CausalLanguageModelConfig(
        vocab_size=101, max_seq_len=32, max_latents=8, num_channels=16, num_heads=2,
        num_self_attention_layers=1, output_norm=True))
    return lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32), prefix_len=24)


def _tiny_image_classifier():
    model = ImageClassifier(ImageClassifierConfig(
        encoder=ImageEncoderConfig(image_shape=(8, 8, 3), num_frequency_bands=4, num_cross_attention_heads=1,
                                   num_self_attention_heads=2, num_self_attention_layers_per_block=1,
                                   num_self_attention_blocks=2),
        decoder=ClassificationDecoderConfig(num_classes=5, num_output_query_channels=16, num_cross_attention_heads=1),
        num_latents=4, num_latent_channels=16))
    return lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3), jnp.float32))


@pytest.mark.parametrize("name,build", [("perceiver_ar", _tiny_perceiver_ar), ("image_classifier", _tiny_image_classifier)],
                         ids=["perceiver_ar", "image_classifier"])
def test_parameter_tree_paths_and_shapes_are_the_written_ones(name, build):
    shapes = jax.eval_shape(build())
    got = [
        "/".join(str(k.key) for k in path) + " " + "x".join(str(n) for n in leaf.shape)
        for path, leaf in jax.tree_util.tree_leaves_with_path(shapes)
    ]
    assert got == [line.strip() for line in PARAMETER_TREES[name].strip().splitlines()]


# --- CrossAttention in prefix mode: the flash route against the einsum route --


def _prefix_cross_attention(rotary: bool):
    from perceiver_io_tpu.core.modules import CrossAttention
    from perceiver_io_tpu.core.position import frequency_position_encoding, positions

    heads, d, n_p, n_q = 4, 16, 200, 128  # the prefix ends inside a 128-wide kv block
    ca = CrossAttention(num_heads=heads, num_q_input_channels=heads * d, num_kv_input_channels=heads * d,
                        causal_attention=True)
    rng = np.random.default_rng(3)
    x_q = jnp.asarray(rng.normal(size=(B, n_q, heads * d)), jnp.float32)
    x_p = jnp.asarray(rng.normal(size=(B, n_p, heads * d)), jnp.float32)
    kwargs = {}
    if rotary:
        rope_k = frequency_position_encoding(positions(B, n_p + n_q), d // 2)
        kwargs = {"rope_q": rope_k[:, n_p:], "rope_k": rope_k}
    params = ca.init(jax.random.PRNGKey(0), x_q, x_kv_prefix=x_p)
    return ca, params, x_q, x_p, kwargs


@pytest.mark.parametrize("case", ["plain", "rotary", "pad_mask", "param_grads"])
def test_cross_attention_prefix_mode_flash_matches_einsum(case):
    """``CrossAttention(x_q, x_kv_prefix=...)`` builds [prefix; latents] and
    attends right-aligned causally: with the packed flash kernels (interpret
    mode here) and with the einsum path, outputs and parameter gradients."""
    from perceiver_io_tpu.ops.flash_attention import default_flash

    ca, params, x_q, x_p, kwargs = _prefix_cross_attention(rotary=case == "rotary")
    if case == "pad_mask":
        kwargs["pad_mask"] = jnp.zeros((B, x_p.shape[1] + x_q.shape[1]), bool).at[:, :7].set(True)

    def out(params, flash):
        with default_flash(flash):
            return ca.apply(params, x_q, x_kv_prefix=x_p, **kwargs).last_hidden_state

    if case != "param_grads":
        np.testing.assert_allclose(np.asarray(out(params, True)), np.asarray(out(params, False)), atol=2e-5)
        return
    on = jax.grad(lambda p: jnp.sum(out(p, True) ** 2))(params)
    off = jax.grad(lambda p: jnp.sum(out(p, False) ** 2))(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(on), jax.tree.leaves(off)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=1e-4,
                                   err_msg=jax.tree_util.keystr(path))
