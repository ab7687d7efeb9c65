"""Training loop: loss decreases on a learnable toy task; FSDP/data-parallel
sharding compiles and runs on a virtual 8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.core.config import ClassificationDecoderConfig
from perceiver_io_tpu.models.text import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.models.vision import ImageClassifier, ImageClassifierConfig, ImageEncoderConfig
from perceiver_io_tpu.parallel import fsdp_param_shardings, make_mesh, shard_batch
from perceiver_io_tpu.training import (
    TrainState,
    classification_loss_fn,
    clm_loss_fn,
    constant_with_warmup,
    cosine_with_warmup,
    make_optimizer,
)
from perceiver_io_tpu.training.loop import make_train_step, shard_train_state


def small_classifier():
    config = ImageClassifierConfig(
        encoder=ImageEncoderConfig(
            image_shape=(8, 8, 1),
            num_frequency_bands=4,
            num_cross_attention_heads=1,
            num_self_attention_heads=2,
            num_self_attention_layers_per_block=1,
        ),
        decoder=ClassificationDecoderConfig(
            num_classes=2, num_output_query_channels=16, num_cross_attention_heads=1
        ),
        num_latents=4,
        num_latent_channels=16,
    )
    return ImageClassifier(config)


def toy_batch(n=32):
    """Learnable task: label = whether the mean pixel is positive."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 8, 8, 1)).astype(np.float32)
    x += rng.choice([-1.0, 1.0], size=(n, 1, 1, 1))
    y = (x.mean(axis=(1, 2, 3)) > 0).astype(np.int32)
    return {"image": jnp.asarray(x), "label": jnp.asarray(y)}


def test_schedules():
    cos = cosine_with_warmup(1.0, training_steps=100, warmup_steps=10, min_fraction=0.1)
    assert float(cos(0)) == 0.0
    assert float(cos(5)) == pytest.approx(0.5)
    assert float(cos(10)) == pytest.approx(1.0)
    assert float(cos(100)) == pytest.approx(0.1, abs=1e-6)
    const = constant_with_warmup(2.0, warmup_steps=4)
    assert float(const(2)) == pytest.approx(1.0)
    assert float(const(50)) == pytest.approx(2.0)


@pytest.mark.slow
def test_classifier_learns():
    model = small_classifier()
    batch = toy_batch()
    params = model.init(jax.random.PRNGKey(0), batch["image"])
    tx = make_optimizer(3e-3, gradient_clip=1.0)
    state = TrainState.create(model.apply, params, tx, jax.random.PRNGKey(1))
    step = make_train_step(classification_loss_fn(model.apply))

    first_loss = None
    for _ in range(40):
        state, metrics = step(state, batch)
        if first_loss is None:
            first_loss = float(metrics["loss"])
    assert float(metrics["loss"]) < first_loss * 0.1
    assert float(metrics["acc"]) > 0.9
    assert int(state.step) == 40


@pytest.mark.slow
def test_clm_train_step_runs():
    config = CausalLanguageModelConfig(
        vocab_size=50, max_seq_len=24, max_latents=8, num_channels=32,
        num_heads=4, num_self_attention_layers=2, cross_attention_dropout=0.5,
    )
    model = CausalLanguageModel(config)
    rng = np.random.default_rng(0)
    t = rng.integers(0, 50, size=(4, 25))
    x = jnp.asarray(t[:, :-1])
    pad = jnp.zeros((4, 24), bool)
    batch = {"labels": jnp.asarray(t[:, 1:]), "input_ids": x, "pad_mask": pad}
    params = model.init(jax.random.PRNGKey(0), x, prefix_len=16)
    tx = make_optimizer(1e-3)
    state = TrainState.create(model.apply, params, tx, jax.random.PRNGKey(1))
    step = make_train_step(clm_loss_fn(model.apply, max_latents=8))
    state, metrics = step(state, batch)
    loss0 = float(metrics["loss"])
    state, metrics = step(state, batch)
    assert np.isfinite(loss0) and np.isfinite(float(metrics["loss"]))
    # near-uniform init: loss ~ log(vocab)
    assert loss0 == pytest.approx(np.log(50), rel=0.3)


def test_clm_rejects_short_sequences():
    config = CausalLanguageModelConfig(
        vocab_size=50, max_seq_len=24, max_latents=16, num_channels=32,
        num_heads=4, num_self_attention_layers=1,
    )
    model = CausalLanguageModel(config)
    loss = clm_loss_fn(model.apply, max_latents=16)
    batch = {
        "labels": jnp.zeros((1, 8), jnp.int32),
        "input_ids": jnp.zeros((1, 8), jnp.int32),
        "pad_mask": jnp.zeros((1, 8), bool),
    }
    with pytest.raises(ValueError, match="at least 16"):
        loss(None, batch, jax.random.PRNGKey(0))


@pytest.mark.parametrize("mesh_shape", [{"data": 8}, {"data": 2, "fsdp": 4}, {"fsdp": 8}])
@pytest.mark.slow
def test_sharded_training(mesh_shape):
    """DDP / FSDP / hybrid parity: one SPMD program over an 8-device mesh
    (replaces reference DDPStrategy + FSDPStrategy, SURVEY §2.7 P1-P2)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    mesh = make_mesh(**mesh_shape)

    model = small_classifier()
    batch = toy_batch(n=16)
    params = model.init(jax.random.PRNGKey(0), batch["image"])
    tx = make_optimizer(1e-3, gradient_clip=1.0)
    state = TrainState.create(model.apply, params, tx, jax.random.PRNGKey(1))
    state = shard_train_state(state, mesh, min_weight_size=0)
    batch = shard_batch(batch, mesh)

    step = make_train_step(classification_loss_fn(model.apply))
    state, metrics = step(state, batch)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))

    if mesh.shape["fsdp"] > 1:
        # at least one parameter is actually sharded over fsdp
        shardings = jax.tree.leaves(fsdp_param_shardings(state.params, mesh, min_weight_size=0))
        assert any("fsdp" in str(s.spec) for s in shardings)
        placed = [p.sharding for p in jax.tree.leaves(state.params)]
        assert any("fsdp" in str(s.spec) for s in placed if hasattr(s, "spec"))


def test_sharded_step_keeps_the_placed_layout():
    """``make_train_step(mesh=...)`` hands the state back under the shardings
    ``shard_train_state`` placed it in, so the second call builds nothing.
    Unpinned, GSPMD returns small replicated parameters fsdp-sharded and the
    second call compiles the step again."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    mesh = make_mesh(data=2, fsdp=2, devices=jax.devices()[:4])
    model = small_classifier()
    batch = toy_batch(n=8)
    params = model.init(jax.random.PRNGKey(0), batch["image"])
    state = TrainState.create(model.apply, params, make_optimizer(1e-3), jax.random.PRNGKey(1))
    # a threshold between the model's leaf sizes: some sharded, some replicated
    sizes = sorted(p.size for p in jax.tree.leaves(params))
    threshold = sizes[len(sizes) // 2]
    state = shard_train_state(state, mesh, min_weight_size=threshold)
    placed = [x.sharding for x in jax.tree.leaves(state)]
    assert any("fsdp" in str(s.spec) for s in placed) and any(not any(s.spec) for s in placed)
    batch = shard_batch(batch, mesh)

    step = make_train_step(
        classification_loss_fn(model.apply), donate=False, mesh=mesh, min_weight_size=threshold
    )
    for _ in range(2):
        state, _ = step(state, batch)
        assert [x.sharding for x in jax.tree.leaves(state)] == placed
    assert step._cache_size() == 1


def test_flash_kernels_run_per_batch_shard_under_a_mesh():
    """GSPMD cannot partition a Mosaic kernel, so under ``kernel_mesh`` (what
    ``make_train_step(mesh=...)`` traces its loss in) the packed flash
    kernels sit in a shard_map over the batch axes: same values and
    gradients as the unsharded call (interpret mode here)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    from jax.sharding import NamedSharding, PartitionSpec as P

    from perceiver_io_tpu.ops.flash_attention import flash_attention_packed, kernel_mesh

    mesh = make_mesh(data=2, fsdp=2, devices=jax.devices()[:4])
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(4, n, 32)), jnp.float32) for n in (128, 256, 256))

    def loss(q, k, v):
        out = flash_attention_packed(q, k, v, num_heads=2, causal=True, block_q=64, block_kv=128)
        return (out * out).sum()

    want = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    batch = NamedSharding(mesh, P(("data", "fsdp")))
    sharded = [jax.device_put(x, batch) for x in (q, k, v)]
    with kernel_mesh(mesh, ("data", "fsdp")):
        step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
        assert "shard_map" in str(jax.make_jaxpr(loss)(*sharded))
        got = step(*sharded)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_gradient_accumulation():
    model = small_classifier()
    batch = toy_batch(n=8)
    params = model.init(jax.random.PRNGKey(0), batch["image"])
    tx = make_optimizer(1e-3, accumulate_grad_batches=4)
    state = TrainState.create(model.apply, params, tx, jax.random.PRNGKey(1))
    step = make_train_step(classification_loss_fn(model.apply))
    p0 = jax.tree.leaves(state.params)[0].copy()
    for i in range(3):
        state, _ = step(state, batch)
    # parameters unchanged until the 4th micro-step
    np.testing.assert_array_equal(np.asarray(jax.tree.leaves(state.params)[0]), np.asarray(p0))
    state, _ = step(state, batch)
    assert not np.array_equal(np.asarray(jax.tree.leaves(state.params)[0]), np.asarray(p0))


@pytest.mark.slow
def test_mlm_memorizes_fixed_batch():
    """End-to-end MLM gradient flow: a fixed masked batch is driven well
    below the output-marginal plateau (~2.8 nats on this corpus) — the
    contextual-learning escape that streaming smoke runs only reach with
    longer budgets (docs/results/RESULTS.md)."""
    from perceiver_io_tpu.core.config import PerceiverIOConfig
    from perceiver_io_tpu.data.text import SyntheticTextDataModule
    from perceiver_io_tpu.models.text import MaskedLanguageModel, TextDecoderConfig, TextEncoderConfig
    from perceiver_io_tpu.training.losses import masked_lm_loss_fn

    dm = SyntheticTextDataModule(task="mlm", max_seq_len=128, batch_size=16, cache_dir=None)
    batch = next(iter(dm.train_batches()))
    config = PerceiverIOConfig(
        encoder=TextEncoderConfig(vocab_size=dm.vocab_size, max_seq_len=128),
        decoder=TextDecoderConfig(vocab_size=dm.vocab_size, max_seq_len=128),
        num_latents=64,
        num_latent_channels=64,
    )
    model = MaskedLanguageModel(config)
    params = model.init(jax.random.PRNGKey(0), np.zeros((1, 128), np.int32))
    state = TrainState.create(model.apply, params, make_optimizer(1e-3), jax.random.PRNGKey(1))
    step = make_train_step(masked_lm_loss_fn(model.apply))
    first = None
    for _ in range(300):
        state, metrics = step(state, batch)
        if first is None:
            first = float(metrics["loss"])
    assert first > 4.0  # starts near uniform ln(262) ~ 5.6
    assert float(metrics["loss"]) < 2.0  # breaks the ~2.8 marginal plateau


def test_microbatched_step_matches_full_batch():
    """microbatch=k chunking inside the step is the full-batch step: same
    gradients (fp reassociation tolerance) and same loss for a
    deterministic-loss model (prefix dropout off — chunks draw different
    dropout keys by design)."""
    import numpy as np

    from perceiver_io_tpu.models.text import CausalLanguageModel, CausalLanguageModelConfig
    from perceiver_io_tpu.training import TrainState, clm_loss_fn, make_optimizer
    from perceiver_io_tpu.training.loop import make_train_step

    config = CausalLanguageModelConfig(
        vocab_size=64, max_seq_len=32, max_latents=8, num_channels=32,
        num_heads=4, num_self_attention_layers=1, cross_attention_dropout=0.0,
    )
    model = CausalLanguageModel(config)
    rng = np.random.default_rng(0)
    t = rng.integers(0, 64, size=(4, 33))
    batch = {
        "labels": jnp.asarray(t[:, 1:]),
        "input_ids": jnp.asarray(t[:, :-1]),
        "pad_mask": None,
    }
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"][:, :9], prefix_len=1)
    loss_fn = clm_loss_fn(model.apply, max_latents=8)

    def state():
        tx = make_optimizer(1e-2, gradient_clip=1.0)
        return TrainState.create(model.apply, params, tx, jax.random.PRNGKey(1))

    s_full, m_full = make_train_step(loss_fn, donate=False)(state(), batch)
    s_mb, m_mb = make_train_step(loss_fn, donate=False, microbatch=2)(state(), batch)

    np.testing.assert_allclose(float(m_mb["loss"]), float(m_full["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s_mb.params), jax.tree.leaves(s_full.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4)

    with pytest.raises(ValueError, match="does not divide"):
        make_train_step(loss_fn, donate=False, microbatch=3)(state(), batch)


def test_compact_adam_matches_optax_adam():
    """scale_by_adam_compact at f32 storage IS optax.adam; at bf16 storage it
    tracks it to moment-storage precision (the HBM-diet optimizer,
    docs/performance.md round-4)."""
    import optax

    from perceiver_io_tpu.training.optim import scale_by_adam_compact

    params = {"w": jnp.linspace(-1.0, 1.0, 32).reshape(4, 8), "b": jnp.ones((8,))}
    grads = [
        {"w": jnp.sin(jnp.arange(32.0)).reshape(4, 8) * 0.1, "b": jnp.cos(jnp.arange(8.0))},
        {"w": jnp.full((4, 8), -0.05), "b": jnp.arange(8.0) * 0.01},
        {"w": jnp.ones((4, 8)) * 0.2, "b": -jnp.ones((8,)) * 0.3},
    ]

    ref = optax.scale_by_adam()
    f32 = scale_by_adam_compact(moment_dtype="float32")
    b16 = scale_by_adam_compact(moment_dtype="bfloat16")
    s_ref, s_f32, s_b16 = ref.init(params), f32.init(params), b16.init(params)
    for g in grads:
        u_ref, s_ref = ref.update(g, s_ref)
        u_f32, s_f32 = f32.update(g, s_f32)
        u_b16, s_b16 = b16.update(g, s_b16)
        for a, b in zip(jax.tree.leaves(u_ref), jax.tree.leaves(u_f32)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)
        for a, b in zip(jax.tree.leaves(u_ref), jax.tree.leaves(u_b16)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0.05, atol=0.05)
    # storage dtype honored (the point of the transform)
    assert all(m.dtype == jnp.bfloat16 for m in jax.tree.leaves(s_b16.mu))
    assert all(v.dtype == jnp.bfloat16 for v in jax.tree.leaves(s_b16.nu))


def test_make_optimizer_moment_dtype():
    from perceiver_io_tpu.training.optim import make_optimizer as mk

    params = {"w": jnp.ones((4, 4))}
    tx = mk(1e-3, moment_dtype="bfloat16")
    state = tx.init(params)
    moments = [x for x in jax.tree.leaves(state) if hasattr(x, "dtype") and x.shape == (4, 4)]
    assert moments and all(m.dtype == jnp.bfloat16 for m in moments)
    # a full update runs and changes params in the right direction
    u, _ = tx.update({"w": jnp.ones((4, 4))}, state, params)
    assert float(jax.tree.leaves(u)[0].sum()) < 0
    with pytest.raises(ValueError, match="moment_dtype"):
        mk(1e-3, optimizer="sgd", moment_dtype="bfloat16")


def test_microbatch_loss_weighting_declarations():
    """masked-LM (count-normalized) is rejected at build time for
    microbatch>1; classification (per-example mean) declares itself uniform
    and is allowed even with a padded batch (ADVICE r3: explicit contract
    instead of pad_mask key sniffing alone)."""
    from perceiver_io_tpu.training import classification_loss_fn, masked_lm_loss_fn, mse_loss_fn

    mlm = masked_lm_loss_fn(lambda *a, **k: None)
    assert mlm.uniform_weighting is False
    with pytest.raises(ValueError, match="uniform_weighting=False"):
        make_train_step(mlm, microbatch=2)

    clf_apply_calls = []

    def clf_apply(params, x, **kwargs):
        clf_apply_calls.append(kwargs.get("pad_mask") is not None)
        return jnp.zeros((x.shape[0], 4))

    clf = classification_loss_fn(clf_apply)
    assert clf.uniform_weighting is True
    step = make_train_step(clf, microbatch=2, donate=False)
    params = {"w": jnp.zeros((2,))}
    tx = make_optimizer(1e-2)
    state = TrainState.create(None, params, tx, jax.random.PRNGKey(0))
    batch = {
        "x": jnp.zeros((4, 8)),
        "label": jnp.zeros((4,), jnp.int32),
        "pad_mask": jnp.zeros((4, 8), bool),  # padded batch: still allowed
    }
    _, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert mse_loss_fn(lambda *a, **k: jnp.zeros((2, 2))).uniform_weighting is True
