"""The decoder-only model under its fifth configuration (the Jamba family:
Mamba-1 state-space layers with an attention layer every ``period`` layers, a
dense SwiGLU in every layer, a tied head) against its plain reference, at tiny
widths that keep the published shape: hidden 64, ``d_inner`` 128, 4 states, a
``dt`` rank of 8, a convolution of 4, 4 query heads on 1 key-value head of 16,
an attention layer at offset 1 of every 3.

Tolerances as in ``tests/test_decoder_lm.py``: float32 products at "highest"
precision on both sides, so the program and ``benchmarks/reference/jamba.py``
differ in the order of float32 sums alone (the scan kernel sums a token's
states in state order, the reference through ``jnp.sum``), 2e-4 absolute on
logits of magnitude up to about 10. A state dropped at one token moves the
same logits by thousands of times that, and a test says so. The weights are
seeded as the benchmark's family seeds them (``families/jamba.py::remembering``:
decay rates 1 to N, step sizes of 1e-3 to 1e-1), so the state remembers the
whole of these sequences."""

import dataclasses
import functools
import importlib
import json
import os
import subprocess
import sys
import traceback

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from benchmarks.families.jamba import layer_types, remembering
from benchmarks.lib import jamba_cost
from benchmarks.lib.weights import flat_dict
from benchmarks.reference import jamba as reference
from perceiver_io_tpu import generation
from perceiver_io_tpu.core.cache import KVCache, RecurrentState, init_recurrent_state
from perceiver_io_tpu.core.ssm import MambaMixer
from perceiver_io_tpu.generation import GenerationConfig, make_generate_fn
from perceiver_io_tpu.models.text.decoder_lm import DecoderLanguageModel, DecoderLanguageModelConfig
from perceiver_io_tpu.ops import selective_scan as ss

fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")  # the package exports a function of that name

TOL = 2e-4
VOCAB = 96
PERIOD, OFFSET = 3, 1


def tiny_config(**kw) -> DecoderLanguageModelConfig:
    depth = kw.pop("num_hidden_layers", 4)
    published = dict(num_hidden_layers=depth, attn_layer_period=PERIOD, attn_layer_offset=OFFSET)
    base = dict(
        vocab_size=VOCAB, hidden_size=64, num_hidden_layers=depth, first_k_dense_replace=depth, intermediate_size=96,
        num_attention_heads=4, num_key_value_heads=1, head_dim=16, layer_types=layer_types(published),
        full_attention_rotary=False, rope_scaling=None, tie_word_embeddings=True, mamba_expand=2, mamba_d_state=4,
        mamba_dt_rank=8, mamba_d_conv=4, init_scale=0.3, max_position_embeddings=512,
    )
    base.update(kw)
    return DecoderLanguageModelConfig(**base)


def reference_cfg(config: DecoderLanguageModelConfig) -> dict:
    return dict(dataclasses.asdict(config), attn_layer_period=PERIOD, attn_layer_offset=OFFSET)


def seeded(config, seed: int, batch: int = 2, n: int = 13):
    """The model, its weights drawn from ``seed`` with the recurrences' leaves as the family hands them on, and prompts."""
    model = DecoderLanguageModel(config)
    k_ids, k_init = jax.random.split(jax.random.PRNGKey(seed))
    ids = jax.random.randint(k_ids, (batch, n), 0, config.vocab_size)
    params = model.init(k_init, ids)
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: remembering(getattr(path[-1], "key", ""), leaf, config.init_scale, 1e-3, 1e-1), params)
    return model, params, ids


def served_logits(model, params, ids, new_tokens: int, cache_dtype=jnp.float32):
    """Greedy decoding through the generator's own decoder (prompt pass, then
    one-token steps over the recurrent states and the cache): the logits the
    tokens were read from, (B, new_tokens, V), the tokens, and the state at the end."""
    decoder = generation._decoder_of(model)
    prefill = jax.jit(lambda p, i: decoder.prefill(p, i, None, 1, new_tokens, cache_dtype))
    step = jax.jit(lambda p, w, t: decoder.step(p, w, (), t))
    logits, window, consts = prefill(params, ids)
    assert consts == ()
    out, tokens = [logits[:, -1]], []
    for _ in range(new_tokens - 1):
        tokens.append(jnp.argmax(out[-1], axis=-1))
        logits, window = step(params, window, tokens[-1])
        out.append(logits[:, -1])
    tokens.append(jnp.argmax(out[-1], axis=-1))
    return np.stack([np.asarray(o) for o in out], axis=1), np.stack([np.asarray(t) for t in tokens], axis=1), window[0]


def scan_args(rows, length, d_inner, d_state, seed=0):
    """``x``, the step sizes (0.02 to 0.15), ``B``, ``C`` and ``A`` of a scan."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    a = -jnp.exp(jnp.log(jnp.arange(1, d_state + 1, dtype=jnp.float32))[:, None] + 0.02 * jax.random.normal(ks[4], (d_state, d_inner)))
    return (0.5 * jax.random.normal(ks[0], (rows, length, d_inner)), jax.nn.softplus(0.3 * jax.random.normal(ks[1], (rows, length, d_inner)) - 3.0),
            jax.random.normal(ks[2], (rows, length, d_state)), jax.random.normal(ks[3], (rows, length, d_state)), a)


# ------------------------------------------------------------ the whole model


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("n", [2, 4, 21], ids=["shorter_than_the_convolution", "the_convolution", "longer"])
def test_full_forward_matches_the_reference(seed, n):
    config = tiny_config()
    model, params, ids = seeded(config, seed, n=n)
    got = np.asarray(model.apply(params, ids))
    want = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config)))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("kernel", [False, True], ids=["lax_scan", "scan_kernel"])
@pytest.mark.parametrize("n", [2, 13, 40], ids=lambda n: f"prompt{n}")
def test_prompt_pass_then_decode_through_state_and_cache_matches_the_references_full_forward(n, kernel):
    """Every served position: the prompt pass hands each Mamba layer's window
    and state and the attention layer's keys and values to 11 one-token steps,
    for a prompt shorter than the convolution (the window keeps zeros), of a
    usual length, and longer than the kernel's time chunk of these sizes."""
    new = 12
    config = tiny_config()
    model, params, ids = seeded(config, 3, n=n)
    with fa.default_flash(kernel):
        got, tokens, state = served_logits(model, params, ids, new)
    full = np.concatenate([np.asarray(ids), tokens[:, :-1]], axis=1)
    want = np.asarray(reference.logits(flat_dict(params), jnp.asarray(full), reference_cfg(config), last=new))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    # two kinds of state in one generator state: three of one size, and a cache that grew
    assert [type(c) for c in state] == [RecurrentState, KVCache, RecurrentState, RecurrentState]
    assert state[0].conv.shape == (2, 3, 128) and state[0].ssm.shape == (2, 4, 128) and state[0].ssm.dtype == jnp.float32
    assert state[1].k.shape == (2, n + new, 16) and int(state[1].length) == n + new - 1


def test_the_generator_serves_the_same_tokens_and_a_bfloat16_cache_keeps_the_state_float32():
    config = tiny_config()
    model, params, ids = seeded(config, 5, n=9)
    _, tokens, _ = served_logits(model, params, ids, 8)
    out = make_generate_fn(model, config=GenerationConfig(max_new_tokens=8))(params, ids)
    np.testing.assert_array_equal(np.asarray(out[:, 9:]), tokens)
    _, _, state = served_logits(model, params, ids, 3, cache_dtype=jnp.bfloat16)
    assert state[0].conv.dtype == jnp.bfloat16 and state[1].k.dtype == jnp.bfloat16 and state[0].ssm.dtype == jnp.float32


def test_a_state_dropped_at_one_token_is_not_the_model():
    """What the comparison is for: the reference with one Mamba layer's state
    zeroed before one token of the prompt moves the served logits by thousands of tolerances."""
    config = tiny_config()
    model, params, ids = seeded(config, 0, n=21)
    want = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config), last=4))
    broken = np.asarray(reference.logits(flat_dict(params), ids, reference_cfg(config), last=4,
                                         mamba_fn=lambda *a: reference.mamba(*a, break_carry_at=16)))
    assert np.abs(broken - want).max() > 1000 * TOL


def test_the_layer_order_follows_offset_and_period():
    published = dict(num_hidden_layers=28, attn_layer_period=14, attn_layer_offset=7)
    kinds = layer_types(published)
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == [7, 21] and kinds.count("mamba") == 26
    assert reference.layer_kinds(published) == tuple("attention" if k == "full_attention" else "mamba" for k in kinds)
    model, params, _ = seeded(tiny_config(num_hidden_layers=7), 0)
    mixers = ["mixer" in params["params"][f"layer_{i}"] for i in range(7)]
    assert mixers == [True, False, True, True, False, True, True]
    assert all(("attn" in params["params"][f"layer_{i}"]) != m for i, m in enumerate(mixers))


def test_the_head_is_the_embedding_table():
    config = tiny_config()
    model, params, ids = seeded(config, 1)
    assert "head" not in params["params"]
    table = params["params"]["embedding"]
    hidden = jax.random.normal(jax.random.PRNGKey(9), (2, 3, config.hidden_size))
    got = model.apply(params, hidden, method="logits")
    scale = params["params"]["out_norm"]["scale"]
    normed = hidden / jnp.sqrt(jnp.mean(hidden * hidden, -1, keepdims=True) + config.rms_norm_eps) * scale
    np.testing.assert_allclose(np.asarray(got), np.asarray(normed @ table.T), atol=1e-5, rtol=0)
    untied = DecoderLanguageModel(dataclasses.replace(config, tie_word_embeddings=False))
    assert "head" in untied.init(jax.random.PRNGKey(0), ids)["params"]


def test_a_module_needs_a_head_and_attention_layers():
    with pytest.raises(ValueError, match="multi-token-prediction"):
        tiny_config(num_nextn_predict_layers=1)
    with pytest.raises(ValueError, match="sliding_window"):
        tiny_config(layer_types=("sliding_attention", "mamba", "mamba", "mamba"))


# ----------------------------------------------------------------- the kernel


@pytest.mark.parametrize("rows,length,d_inner,d_state,x_dtype", [
    (2, 20, 256, 4, jnp.float32), (2, 256, 2048, 16, jnp.float32), (1, 300, 1024, 16, jnp.float32), (3, 7, 64, 8, jnp.float32),
    (1, 128, 1024, 16, jnp.float32), (1, 256, 5120, 16, jnp.float32), (2, 300, 2048, 16, jnp.bfloat16),
], ids=["short", "two_chunks_two_tiles", "no_multiple_of_the_chunk", "narrower_than_the_lanes", "one_chunk", "the_cells_width", "bfloat16_x"])
def test_the_scan_kernel_agrees_with_a_token_by_token_scan(rows, length, d_inner, d_state, x_dtype):
    """Interpret mode against ``lax.scan``: ``y`` at every token and the rows'
    final state, at lengths that are and are not multiples of the time chunk
    (128) or of a slab of 8 tokens and widths of one, two and the cell's five
    channel tiles. Float32 on both sides (a bfloat16 ``x`` is widened on the
    loaded registers, and the reference is held to the widened values): the
    sum over the states runs in another order, 1e-5 on values of magnitude 1."""
    args = scan_args(rows, length, d_inner, d_state)
    args = (args[0].astype(x_dtype),) + args[1:]
    y, state = ss.selective_scan(*args)
    want_y, want_state = ss.selective_scan_reference(args[0].astype(jnp.float32), *args[1:])
    assert y.shape == (rows, length, d_inner) and state.shape == (rows, d_state, d_inner)
    assert y.dtype == jnp.float32 and state.dtype == jnp.float32
    assert np.abs(np.asarray(want_y)).max() > 0.3 and np.abs(np.asarray(want_state)).max() > 0.1
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(state), np.asarray(want_state), atol=1e-6, rtol=0)
    plan = next(p for p in ss.ssm_scan_plans() if (p["length"], p["d_inner"], p["d_state"]) == (length, d_inner, d_state))
    assert plan["time_chunk"] == min(128, -(-length // 8) * 8) and plan["channel_tile"] == min(d_inner, 1024)
    assert plan["grid_steps"] == (d_inner // plan["channel_tile"]) * -(-length // plan["time_chunk"])
    # x as it arrives, the step size and y float32, double-buffered; A and the state: a block's two buffers and a turned copy each
    assert plan["vmem_bytes"] == (2 * plan["time_chunk"] * (jnp.dtype(x_dtype).itemsize + 8) + 6 * d_state * 4) * plan["channel_tile"]
    assert ss.ssm_scan_kernel_name(length, d_inner, d_state) == f"ssm_scan_l{length}_d{d_inner}_n{d_state}"


# The scan as the program ran it until PR 47, kept as the plain function the kernel is held to **to the bit**: the
# wrapper views ``[.., D]`` as ``[.., D / 128, 128]`` (on the chip: a physical copy of each stream) and the kernel
# takes a token a loop trip off that view. The kernel of ``ops/selective_scan.py`` reads the rows as they are and
# turns 8 tokens x 8 lane tiles on registers: the same operations on the same values in the same order.


def _view4d_kernel(bc_ref, x_ref, dt_ref, a_ref, y_ref, state_ref, h_scr, *, d_state, chunk, length):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _start():
        h_scr[...] = jnp.zeros_like(h_scr)

    def token(t, h):
        dt = dt_ref[0, t]
        dtx = dt * x_ref[0, t]
        y = None
        base = t * (2 * d_state)
        new = []
        for n in range(d_state):
            h_n = jnp.exp(dt * a_ref[n]) * h[n] + dtx * bc_ref[base + n]
            y_n = h_n * bc_ref[base + d_state + n]
            y = y_n if y is None else y + y_n
            new.append(h_n)
        y_ref[0, t] = y
        return tuple(new)

    steps = chunk if length % chunk == 0 else jnp.minimum(chunk, length - j * chunk)
    h = lax.fori_loop(0, steps, token, tuple(h_scr[n] for n in range(d_state)))
    for n in range(d_state):
        h_scr[n] = h[n]

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        state_ref[0] = h_scr[...]


@jax.jit
def scan_over_the_4d_view(x, dt, b, c, a):
    rows, length, d_inner = x.shape
    d_state = b.shape[-1]
    groups, sub, lanes = ss._tile_shape(d_inner)
    chunk = min(ss.TIME_CHUNK, -(-length // 8) * 8)
    n_chunks = -(-length // chunk)
    f32 = jnp.float32
    bc = jnp.concatenate([b.astype(f32), c.astype(f32)], axis=-1)
    bc = jnp.pad(bc, ((0, 0), (0, n_chunks * chunk - length), (0, 0))).reshape(-1)
    view = lambda t: t.astype(f32).reshape(*t.shape[:-1], groups, lanes)  # noqa: E731
    stream = pl.BlockSpec((1, chunk, sub, lanes), lambda r, i, j: (r, j, i, 0))
    y, state = pl.pallas_call(
        functools.partial(_view4d_kernel, d_state=d_state, chunk=chunk, length=length),
        grid=(rows, groups // sub, n_chunks),
        in_specs=[pl.BlockSpec((chunk * 2 * d_state,), lambda r, i, j: (r * n_chunks + j,), memory_space=pltpu.SMEM),
                  stream, stream, pl.BlockSpec((d_state, sub, lanes), lambda r, i, j: (0, i, 0))],
        out_specs=[stream, pl.BlockSpec((1, d_state, sub, lanes), lambda r, i, j: (r, 0, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, length, groups, lanes), f32), jax.ShapeDtypeStruct((rows, d_state, groups, lanes), f32)],
        scratch_shapes=[pltpu.VMEM((d_state, sub, lanes), f32)],
        interpret=True,
    )(bc, view(x), view(dt), view(a))
    return y.reshape(rows, length, d_inner), state.reshape(rows, d_state, d_inner)


BIT_CASES = {  # lengths under a slab, with a partial slab, of whole chunks, and three chunks with a partial slab at the end
    "7": (3, 7, 64, 8, "float32"), "20": (2, 20, 256, 4, "float32"), "256": (1, 256, 2048, 16, "bfloat16"), "300": (1, 300, 1024, 16, "float32"),
}


def same_bits_as_the_4d_view(rows, length, d_inner, d_state, x_dtype):
    args = scan_args(rows, length, d_inner, d_state)
    args = (args[0].astype(x_dtype),) + args[1:]
    for got, want in zip(ss.selective_scan(*args), scan_over_the_4d_view(*args), strict=True):
        np.testing.assert_array_equal(np.asarray(got).view(np.uint32), np.asarray(want).view(np.uint32))


@pytest.fixture(scope="module")
def bit_results():
    """``BIT_CASES`` run by one child process (``python tests/test_jamba.py``) held to an instruction set without
    FMA: XLA's CPU backend contracts ``a * b + c`` where its vectoriser pleases, so two interpret-mode programs of
    the same arithmetic differ in the last bit by how their loops were cut (``tests/test_rotary_kernel.py``); without
    the instruction both round every product, as the chip's vector unit does. ``{length: "ok" or a traceback}``."""
    flags = f"{os.environ.get('XLA_FLAGS', '')} --xla_cpu_max_isa=AVX".strip()
    env = dict(os.environ, XLA_FLAGS=flags, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join([root, env.get("PYTHONPATH", "")])
    done = subprocess.run([sys.executable, os.path.abspath(__file__)], env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.splitlines()[-1])


@pytest.mark.parametrize("length", sorted(BIT_CASES, key=int))
def test_the_scan_kernel_is_the_scan_over_the_4d_view_to_the_bit(length, bit_results):
    """``y`` and the final state of the kernel equal, bit for bit, what the
    4-D-view formulation computes: moving the layout turn from HBM into VMEM
    changed no operation, no operand and no order of a sum."""
    assert bit_results[length] == "ok", bit_results[length]


def test_the_state_carries_across_time_chunks():
    """A row of 300 tokens is three chunks: its final state is the state of the
    first 256 tokens carried through the last 44, not the last chunk's alone."""
    args = scan_args(1, 300, 1024, 16)
    _, state = ss.selective_scan(*args)
    _, head = ss.selective_scan_reference(*(a[:, :256] if a.ndim == 3 else a for a in args))
    _, want = ss.selective_scan_reference(*(a[:, 256:] if a.ndim == 3 else a for a in args), state=head)
    _, alone = ss.selective_scan_reference(*(a[:, 256:] if a.ndim == 3 else a for a in args))
    np.testing.assert_allclose(np.asarray(state), np.asarray(want), atol=1e-6, rtol=0)
    assert np.abs(np.asarray(alone) - np.asarray(want)).max() > 1e-3


def test_differentiation_through_the_kernel_raises():
    args = scan_args(1, 8, 64, 4)
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda x: ss.selective_scan(x, *args[1:])[0].sum())(args[0])


# ------------------------------------------------------------------ the mixer


@pytest.mark.parametrize("kernel", [False, True], ids=["lax_scan", "scan_kernel"])
def test_expand_hands_its_window_and_state_to_step(kernel):
    """``expand`` over 10 tokens then ``step`` on the 11th equals ``expand`` over
    all 11, in the output and in the state; the window is the last three
    convolution inputs, and from an empty state one step equals a row of one token."""
    config = tiny_config()
    mixer = MambaMixer(config)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 11, config.hidden_size))
    params = mixer.init(jax.random.PRNGKey(1), u, method="expand")
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: remembering(getattr(path[-1], "key", ""), leaf, config.init_scale, 1e-3, 1e-1), params)
    with fa.default_flash(kernel):
        whole, end = mixer.apply(params, u, method="expand")
        head, state = mixer.apply(params, u[:, :10], method="expand")
    last, stepped = mixer.apply(params, u[:, 10:], state, method="step")
    np.testing.assert_allclose(np.asarray(head), np.asarray(whole[:, :10]), atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(last), np.asarray(whole[:, 10:]), atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(stepped.ssm), np.asarray(end.ssm), atol=1e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(stepped.conv), np.asarray(end.conv), atol=1e-6, rtol=0)
    x_in = (u @ params["params"]["w_in"])[..., :128]
    np.testing.assert_allclose(np.asarray(end.conv), np.asarray(x_in[:, -3:]), atol=1e-5, rtol=0)
    empty = init_recurrent_state(2, config.mamba_d_conv, config.mamba_d_state, 128)
    first, _ = mixer.apply(params, u[:, :1], empty, method="step")
    np.testing.assert_allclose(np.asarray(first), np.asarray(whole[:, :1]), atol=1e-5, rtol=0)
    assert (empty.conv.shape, empty.ssm.shape, empty.ssm.dtype) == ((2, 3, 128), (2, 4, 128), jnp.float32)


# ------------------------------------------------------- the published widths


def test_the_published_configuration_counts_3_029_337_472_parameters():
    """``jax.eval_shape`` of the program under the benchmark's configuration, against the hand count of ``lib/jamba_cost.py``."""
    from benchmarks import run

    config = run.load_json("configs", "jamba2-3b")
    family = importlib.import_module("benchmarks.families.jamba").Family(config)
    shapes = family.param_shapes(family.model())
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n == jamba_cost.held_params(family.cfg) == 3_029_337_472
    mixer = shapes["params"]["layer_0"]["mixer"]
    assert mixer["a_log"].shape == (16, 5120) and mixer["w_in"].shape == (2560, 10240) and mixer["w_x"].shape == (5120, 192)
    assert mixer["conv_w"].shape == (4, 5120) and mixer["w_dt"].shape == (160, 5120) and mixer["w_out"].shape == (5120, 2560)
    assert shapes["params"]["layer_7"]["attn"]["w_k"].shape == (2560, 128) and "head" not in shapes["params"]


def test_the_instrumented_generator_taps_the_state(tmp_path):
    import json

    from perceiver_io_tpu.obs.events import EventLog

    config = tiny_config()
    model, params, ids = seeded(config, 2, n=9)
    events = EventLog(str(tmp_path))
    fn = generation.make_instrumented_generate_fn(model, config=GenerationConfig(max_new_tokens=4), events=events, probes=True)
    out, stats = fn(params, ids)
    assert out.shape == (2, 13) and stats.outcome == "ok"
    snapshot = fn.registry.snapshot()
    snap = {**snapshot["counters"], **snapshot["gauges"]}
    assert snap["ssm_state_abs_max"] > 0 and snap["ssm_state_nonfinite_total"] == 0
    rows = [json.loads(line) for line in open(tmp_path / "events.jsonl")]
    request = next(r for r in rows if r.get("event") == "request")
    assert request["ssm_state_abs_max"] == pytest.approx(snap["ssm_state_abs_max"]) and request["ssm_state_nonfinite"] == 0
    assert "moe_local_share" not in request  # no expert layer: the decoder opens no ``moe.*`` tap
    compile_row = next(r for r in rows if r.get("event") == "compile" and "ssm_layers" in r)
    assert compile_row["ssm_layers"] == 3 and compile_row["kv_cache_full_layers"] == 1 and compile_row["ssm_state_dtype"] == "float32"
    assert compile_row["ssm_state_bytes"] == 3 * 2 * 4 * 128 * 4 and compile_row["ssm_conv_bytes"] == 3 * 2 * 3 * 128 * 4
    assert compile_row["kv_cache_full_bytes"] == 2 * 13 * 2 * 16 * 4
    assert isinstance(compile_row["ssm_scan"], list)  # the kernels' plans traced so far (none where the kernels are off)


if __name__ == "__main__":  # the child of ``bit_results``
    results = {}
    for name, case in BIT_CASES.items():
        try:
            same_bits_as_the_4d_view(*case)
            results[name] = "ok"
        except Exception:  # reported to the parent's case of that name
            results[name] = traceback.format_exc()[-3000:]
    print(json.dumps(results))
