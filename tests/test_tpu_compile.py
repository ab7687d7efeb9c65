"""The main path's kernels compile for a v5e at flagship widths.

Interpret mode proves a Pallas kernel's arithmetic, not that Mosaic will
lower it: block shapes off the (8, 128) tiling, or too much VMEM, pass every
interpret-mode test and are refused on the chip. The TPU compiler is
installed here and compiles for a chip that is described, not attached, so
these tests catch that class with no chip time. Nothing runs: a passing
compile says nothing about results or speed.

All in one file, topology described inside a module-scoped fixture: the
process that describes it holds libtpu until it exits, so a second test file
(another xdist worker) or a topology call at import would fail.
"""

import collections
import hashlib
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from perceiver_io_tpu.core.attention import MultiHeadAttention
from perceiver_io_tpu.core.cache import KVCache, PagedKVCache
from perceiver_io_tpu.ops import paged_attention as pa

# the package re-exports a function under the module's name
fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")

# flagship attention geometry (chip_smoke.flagship_config): 512 channels, 8 heads
HEADS, CHANNELS = 8, 512
CONTEXT, LATENTS = 16384, 1024
TRAIN_CHUNK = 4  # samples per gradient chunk of the batch-32 train step
SLOTS = 8  # engine decode slots / batched decode
PAGE = 128  # the page size chip_smoke.py's serve phase runs


@pytest.fixture(scope="module")
def four_chips():
    """The four described (not attached) chips of a v5e 2x2 host. The
    persistent compilation cache is off while they are in use: a compile for
    a described chip is written to the cache but cannot be read back without
    the chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe means no compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(four_chips):
    """One described v5e chip."""
    return SingleDeviceSharding(four_chips[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Lower the Pallas kernels for Mosaic: off the chip the backend is the
    CPU, and ``_interpret_default`` would pick the interpreter (the embedding
    gradient kernel of ``ops/gathers.py`` asks the flash module's rule)."""
    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    monkeypatch.setattr(pa, "_interpret_default", lambda: False)


def _compile(fn, *args):
    # conftest runs the suite at "highest" matmul precision, which Mosaic
    # refuses for bf16 operands; the chip runs at the default
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


@pytest.mark.parametrize("n_kv", [8704, 1024], ids=["cross_8704", "self_1024"])
def test_flash_attention_packed_fwd_bwd(one_chip, mosaic, n_kv):
    """Cross-attention after prefix dropout (7680 kept + 1024 latents) and
    the latent self-attention, forward and backward."""
    q = jax.ShapeDtypeStruct((TRAIN_CHUNK, LATENTS, CHANNELS), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((TRAIN_CHUNK, n_kv, CHANNELS), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = fa.flash_attention_packed(q, k, v, HEADS, causal=True, sm_scale=0.125)
        return out.astype(jnp.float32).sum()

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    # the forward kernel and the one backward kernel (the 1024 latents are
    # one q block), neither replaced by an einsum fallback
    assert text.count("tpu_custom_call") >= 2 and f"flash_bwd_q{LATENTS}_kv{n_kv}" in text


def _kernel_arguments(lowered_text: str, name: str) -> dict:
    """How many arguments of the Mosaic kernel ``name``'s body are the call's
    inputs and how many its scratch (by the serialized module's own
    attributes), and the operands of its custom call."""
    import base64
    import re

    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    (call,) = [line for line in lowered_text.splitlines() if "tpu_custom_call" in line and f'kernel_name = "{name}"' in line]
    body = re.search(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', call).group(1)
    with ir.Context() as ctx:
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(body))
        func = module.body.operations[0]  # ``main``; the index maps follow it as ``transform_<i>``
        return {
            "arguments": len(func.regions[0].blocks[0].arguments),
            "scratch": ir.IntegerAttr(func.attributes["scratch_operands"]).value,
            "grid": len(ir.DenseI64ArrayAttr(func.attributes["iteration_bounds"])),
            "operands": len(re.search(r"stablehlo\.custom_call @tpu_custom_call\(([^)]*)\)", call).group(1).split(",")),
        }


@pytest.mark.parametrize("n_kv,scratch", [(1024, 0), (8704, 3)], ids=["self_1024", "cross_8704"])
def test_the_packed_forward_takes_the_scratch_and_the_bias_its_call_needs(one_chip, mosaic, n_kv, scratch):
    """PR 48, at the 16k step's two calls: the latent self-attention's keys
    are one block, so its forward is the plain softmax with no statistics
    scratch; the cross-attention's four kv blocks keep the online one's m, l
    and acc. Neither call has a pad mask or padded keys, so neither kernel
    has a bias operand: q, k and v in, o and lse out. (That Mosaic compiles
    both kernels is ``test_flash_attention_packed_fwd_bwd``'s to hold.)"""
    q = jax.ShapeDtypeStruct((TRAIN_CHUNK, LATENTS, CHANNELS), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((TRAIN_CHUNK, n_kv, CHANNELS), jnp.bfloat16, sharding=one_chip)
    with jax.default_matmul_precision("default"):
        lowered = jax.jit(lambda q, k, v: fa.flash_attention_packed(q, k, v, HEADS, causal=True, sm_scale=1.0)).lower(q, kv, kv)
    kernel = _kernel_arguments(lowered.as_text(), f"flash_fwd_q{LATENTS}_kv{n_kv}")
    assert kernel == {"arguments": 3 + 3 + 2 + scratch, "scratch": scratch, "grid": 3, "operands": 3}


def test_flash_attention_image_cross_fwd_bwd(one_chip, mosaic):
    """The image model's cross-attention (512 latents over 224 x 224 pixels,
    one head of 261 channels, padded to 264): the heads-major forward and its
    one-kernel backward, two rows."""
    q = jax.ShapeDtypeStruct((2, 1, 512, 261), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 1, 224 * 224, 261), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, sm_scale=261**-0.5).astype(jnp.float32).sum()

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert "flash_fwd_q512_kv50176" in text and "flash_bwd_q512_kv50176" in text


def test_embed_position_table_grad_at_the_16k_step(one_chip, mosaic):
    """The backward of the compact prefix-dropout embedding's position
    lookup at the shape of ``ar16k-train-b32`` (32 rows x 7 680 kept of
    15 360 positions, 512 channels): the named kernel, and nothing of the
    inverse-gather VJP it replaced (no (B, N, C) rows, no sort)."""
    from perceiver_io_tpu.ops import gathers

    batch, kept, positions = 32, CONTEXT // 2 - LATENTS // 2, CONTEXT - LATENTS
    table = jax.ShapeDtypeStruct((positions, CHANNELS), jnp.bfloat16, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((batch, kept), jnp.int32, sharding=one_chip)
    cot = jax.ShapeDtypeStruct((batch, kept, CHANNELS), jnp.bfloat16, sharding=one_chip)

    def table_grad(t, i, g):
        return jax.grad(lambda t_: jnp.vdot(gathers.gather_table_rows(t_, i).astype(jnp.float32), g.astype(jnp.float32)))(t)

    text = _compile(table_grad, table, idx, cot)
    assert gathers.embed_grad_kernel_name(positions, kept) == "embed_pos_grad_n15360_k7680"
    assert "embed_pos_grad_n15360_k7680" in text and "tpu_custom_call" in text
    assert f"[{batch * positions},{CHANNELS}]" not in text and f"[{batch},{positions},{CHANNELS}]" not in text
    assert " sort(" not in text and "scatter(" not in text


def test_embed_position_table_grad_on_batch_shards(four_chips, mosaic):
    """The same backward inside a data x fsdp program: GSPMD cannot partition
    a Mosaic call, so under ``kernel_mesh`` the kernel runs on each chip's 8
    rows and the partial tables are summed across the chips."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from perceiver_io_tpu.ops import gathers

    mesh = Mesh(np.asarray(four_chips).reshape(2, 2), ("data", "fsdp"))
    rows, whole = NamedSharding(mesh, P(("data", "fsdp"))), NamedSharding(mesh, P())
    batch, kept, positions = 32, CONTEXT // 2 - LATENTS // 2, CONTEXT - LATENTS
    table = jax.ShapeDtypeStruct((positions, CHANNELS), jnp.bfloat16, sharding=whole)
    idx = jax.ShapeDtypeStruct((batch, kept), jnp.int32, sharding=rows)
    cot = jax.ShapeDtypeStruct((batch, kept, CHANNELS), jnp.bfloat16, sharding=rows)

    def table_grad(t, i, g):
        with fa.kernel_mesh(mesh, ("data", "fsdp")):
            return jax.grad(lambda t_: jnp.vdot(gathers.gather_table_rows(t_, i).astype(jnp.float32), g.astype(jnp.float32)))(t)

    text = _compile(table_grad, table, idx, cot)
    assert "embed_pos_grad_n15360_k7680" in text and "all-reduce" in text
    assert f"[{batch // 4 * positions},{CHANNELS}]" not in text and "all-gather" not in text


@pytest.mark.parametrize(
    "batch,rows,mesh_shape",
    [(32, 8704, None), (64, 16128, None), (2, 300, None), (32, 8704, (2, 2))],
    ids=["train_keys", "prompt_keys_part_block", "rows_under_a_block", "data_x_fsdp"],
)
def test_rotary_kernel_forward_and_backward(four_chips, one_chip, mosaic, batch, rows, mesh_shape):
    """The rotation of a packed (B, N, 512) array at the two cells' key
    shapes (``ar16k-train-b32``'s 8704 kept-prefix-plus-latent rows; the
    decode cell's 16 128-row prompt, whose last block is half a block) and at
    a row count under one block: two named kernels fed in the packed layout,
    no float32 array of the keys' size, no head-broadcast table, no copy of
    either. Under ``kernel_mesh`` each chip rotates its own rows."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from perceiver_io_tpu.core.attention import rotate_slots_major
    from perceiver_io_tpu.core.position import frequency_position_encoding

    d, r = CHANNELS // HEADS, CHANNELS // HEADS // 2
    mesh = None if mesh_shape is None else Mesh(np.asarray(four_chips).reshape(mesh_shape), ("data", "fsdp"))
    sharding = one_chip if mesh is None else NamedSharding(mesh, P(("data", "fsdp")))
    t = jax.ShapeDtypeStruct((batch, rows, CHANNELS), jnp.bfloat16, sharding=sharding)
    pos = jax.ShapeDtypeStruct((batch, rows), jnp.int32, sharding=sharding)

    def rotate(x, pos):  # as the call sites do: the 4-D view of the packed projection
        x4 = rotate_slots_major(x.reshape(*x.shape[:2], HEADS, d), frequency_position_encoding(pos, r), True)
        return x4.reshape(x.shape)

    def both(t, g, pos):
        with fa.kernel_mesh(mesh, ("data", "fsdp")):
            out, vjp = jax.vjp(lambda x: rotate(x, pos), t)
            return out, vjp(g)[0]

    text = _compile(both, t, t, pos)
    local = batch if mesh is None else batch // 4
    assert f"rotary_fwd_n{rows}_c{CHANNELS}" in text and f"rotary_bwd_n{rows}_c{CHANNELS}" in text
    assert f"f32[{local},{rows},{CHANNELS}]" not in text and f"f32[{local},{rows},{HEADS}," not in text
    assert f"bf16[{local},{rows},{CHANNELS}]{{2,1,0" in text and f"[{local},{rows},{CHANNELS}]{{1," not in text
    assert "all-gather" not in text and "all-reduce" not in text


@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.int8], ids=["bf16", "int8"])
def test_decode_attention_16k_cache(one_chip, cache_dtype):
    """One decode token per sequence against a full-context cache: the
    block-diagonal GEMM route of ``MultiHeadAttention`` (XLA, no kernel),
    with the cache stored bf16 and int8."""
    mha = MultiHeadAttention(
        num_heads=HEADS, num_q_input_channels=CHANNELS, num_kv_input_channels=CHANNELS,
        causal_attention=True, dtype=jnp.bfloat16,
    )

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x = sds((SLOTS, 1, CHANNELS), jnp.bfloat16)
    scales = sds((SLOTS, CONTEXT), jnp.bfloat16) if cache_dtype == jnp.int8 else None
    cache = KVCache(
        k=sds((SLOTS, CONTEXT, CHANNELS), cache_dtype),
        v=sds((SLOTS, CONTEXT, CHANNELS), cache_dtype),
        length=sds((), jnp.int32),
        k_scale=scales,
        v_scale=scales,
    )
    params = jax.eval_shape(
        lambda: mha.init(jax.random.PRNGKey(0), jnp.zeros((1, 1, CHANNELS)), jnp.zeros((1, 1, CHANNELS)))
    )
    params = jax.tree.map(lambda p: sds(p.shape, p.dtype), params)

    def step(params, x, cache):
        out = mha.apply(params, x, x, kv_cache=cache)
        return out.last_hidden_state, out.kv_cache

    text = _compile(step, params, x, cache)
    # the cache must reach the GEMMs in its stored dtype (no f32 copy of it)
    assert f"f32[{SLOTS},{CONTEXT},{CHANNELS}]" not in text


@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_paged_decode_attention_lowers(one_chip, mosaic, pool_dtype):
    """The page-walk kernel at the serve phase's geometry: 8 slots, 16k
    tokens per slot. Its q/out/bias blocks are single rows, which Mosaic
    refuses unless they sit behind a unit axis (PR 23)."""
    pages_per_slot = CONTEXT // PAGE
    num_pages = 1 + SLOTS * pages_per_slot

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cache = PagedKVCache(
        k=sds((num_pages, PAGE, CHANNELS), pool_dtype),
        v=sds((num_pages, PAGE, CHANNELS), pool_dtype),
        page_table=sds((SLOTS, pages_per_slot), jnp.int32),
        length=sds((SLOTS,), jnp.int32),
    )
    assert pa.paged_kernel_supported(cache, HEADS, CHANNELS // HEADS, CHANNELS // HEADS)
    q = sds((SLOTS, HEADS, CHANNELS // HEADS), pool_dtype)
    text = _compile(pa.paged_decode_attention, q, cache)
    assert "tpu_custom_call" in text


# DeepSeek-V3's widths (benchmarks/configs/deepseek-v3-ep16.json): the kernels
# of the decoder-only model's prompt pass and its expert layer
DSV3_HEADS, DSV3_HIDDEN, DSV3_EXPERT_WIDTH, DSV3_HELD = 128, 7168, 2048, 16


def test_flash_attention_at_mla_head_widths(one_chip, mosaic):
    """The expanded prompt pass: 128 heads of 192 query/key channels (128 +
    64 rotary, not a multiple of the 128 lanes) and 128 value channels, four
    1024-token rows a chunk, through the heads-major forward kernel."""
    def sds(d):
        return jax.ShapeDtypeStruct((4, DSV3_HEADS, 1024, d), jnp.bfloat16, sharding=one_chip)

    text = _compile(lambda q, k, v: fa.flash_attention(q, k, v, causal=True, sm_scale=0.1), sds(192), sds(192), sds(128))
    assert text.count("tpu_custom_call") >= 1 and "flash_fwd_q1024_kv1024" in text


@pytest.mark.parametrize("heads", [DSV3_HEADS, 64], ids=["dsv3", "longcat"])
def test_token_major_latent_flash_forward_lowers(one_chip, mosaic, heads):
    """The expanded prompt pass on what its up-projections write: four
    1024-token rows a chunk, a head's ``q_nope``, ``k_nope`` and ``v`` each a
    128-lane block of a token-major operand, the rotary halves of two heads
    one block, the diagonal tile in four bands."""
    def sds(width):
        return jax.ShapeDtypeStruct((4, 1024, width), jnp.bfloat16, sharding=one_chip)

    text = _compile(lambda *operands: fa.flash_attention_mla(*operands, heads, sm_scale=0.1),
                    sds(heads * 128), sds(heads * 64), sds(heads * 256), sds(64))
    assert "tpu_custom_call" in text and f"flash_mla_fwd_q1024_kv1024_h{heads}" in text
    plan = {row["geometry"]: row for row in fa.tile_plans()}[f"q1024_kv1024_h{heads}"]
    assert (plan["block_q"], plan["band_rows"], plan["tiles_run"], plan["tiles_skipped"]) == (1024, 256, 40, 24)


# the rows of a pass the cells hand the grouped product, with the geometry (experts held, hidden, width) and its row tile
GROUPED_PRODUCTS = {
    "prompt_chunk": (5120, DSV3_HELD, DSV3_HIDDEN, DSV3_EXPERT_WIDTH, 256),
    "smallest_pass": (256, DSV3_HELD, DSV3_HIDDEN, DSV3_EXPERT_WIDTH, 256),
    "mellum_chunk": (65536, 64, 2304, 896, 256),
    "ling_pass": (4096, 128, 2560, 768, 128),
    "ling_step": (384, 128, 2560, 768, 128),
    "kexaone_chunk": (10240, 16, 6144, 2048, 256),
}


@pytest.mark.parametrize("pass_", list(GROUPED_PRODUCTS))
@pytest.mark.parametrize("direction", ["up", "down"])
def test_grouped_expert_product_lowers(one_chip, monkeypatch, pass_, direction):
    """The held experts' grouped product (a traced number of visits in the
    grid, the group of a visit read from prefetched scalars, the contraction
    whole so that an expert's weight block stays in VMEM across its visits) at
    the rows of a pass the expert layer takes for a prompt chunk of 8192
    tokens and for the 384 tokens from which it takes this path at all, and
    at Mellum's, Ling's (its decode step's 384 rows too) and K-EXAONE's
    products: every block a cell runs, compiled before a chip is asked."""
    gm = importlib.import_module("perceiver_io_tpu.ops.grouped_matmul")
    moe = importlib.import_module("perceiver_io_tpu.core.moe")
    monkeypatch.setattr(gm, "_interpret_default", lambda: False)
    rows, held, hidden, width, tile = GROUPED_PRODUCTS[pass_]
    cuts = moe._cuts(hidden, width, held)
    assert cuts.row_tile == tile
    if hidden == DSV3_HIDDEN:
        assert cuts == (384, 256, 65536)  # the crossing and the tile PR 28 measured at this geometry stay; a pass has no cap since PR 50
        assert moe._pass_rows(8192 * 8, 16 / 256, cuts) == 5120 and moe._pass_rows(cuts.grouped_min_tokens * 8, 16 / 256, cuts) == 256
    k, n = (hidden, width) if direction == "up" else (width, hidden)
    lhs = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((held, k, n), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((held,), jnp.int32, sharding=one_chip)
    text = _compile(lambda a, w, s: gm.grouped_matmul(a, w, s, tm=tile), lhs, rhs, sizes)
    assert f"moe_experts_prefill_m{rows}_k{k}_n{n}" in text and "tpu_custom_call" in text
    plan = next(p for p in gm.moe_tile_plans() if (p["m"], p["k"], p["n"], p["tm"]) == (rows, k, n, tile))
    assert plan["weights_resident"] and plan["tiles_k"] == 1 and plan["vmem_bytes"] <= gm._VMEM_LIMIT


# tokens of a prompt chunk, rows of its pass (``moe._pass_rows``), hidden size, row tile: the share-held cells' expert layers
COMBINE_SHAPES = {"ling": (8192, 4096, 2560, 128), "kexaone": (8192, 10240, 6144, 256), "dsv3": (8192, 5120, 7168, 256),
                  "ling_step": (128, 384, 2560, 128)}


@pytest.mark.parametrize("geometry", sorted(COMBINE_SHAPES))
def test_the_segment_sum_combine_lowers(one_chip, monkeypatch, geometry):
    """``ops/moe_combine.py``'s kernel (a traced number of visits, the tokens'
    buffer aliased and updated in place, a row added at a time at an offset
    read from prefetched scalars) at a prompt chunk of each share-held
    geometry and at Ling's decode step."""
    gm = importlib.import_module("perceiver_io_tpu.ops.grouped_matmul")
    mc = importlib.import_module("perceiver_io_tpu.ops.moe_combine")
    monkeypatch.setattr(gm, "_interpret_default", lambda: False)
    t, r, h, tile = COMBINE_SHAPES[geometry]
    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    text = _compile(lambda y, rows, w, tokens: mc.moe_combine(y, rows, w, tokens, row_tile=tile),
                    shape((t, h), jnp.float32), shape((r, h), jnp.bfloat16), shape((r,), jnp.float32), shape((r,), jnp.int32))
    assert f"moe_combine_t{t}_r{r}_h{h}" in text and "tpu_custom_call" in text and "output_to_operand_aliasing" in text


# ------------------------------------------ Mellum 2: the windowed forward, the cell's generator, what stays the parent's


def _canonical(text: str):
    """A lowered program's text with the source locations stripped and every
    serialized Mosaic body replaced by the hash of its module printed without
    debug info: two trees give the same text iff they lower to the same
    program, wherever their lines and checkouts lie. Returns the text and the
    number of bodies found."""
    import base64
    import hashlib
    import re

    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def body(match):
        with ir.Context() as ctx:
            tpu.register_dialect(ctx)
            ctx.allow_unregistered_dialects = True  # the serialized module names its dialect ``stable_mosaic``
            printed = ir.Module.parse(base64.b64decode(match.group(1))).operation.get_asm(enable_debug_info=False)
        return "body: " + hashlib.sha256(printed.encode()).hexdigest()

    text, n = re.subn(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)
    return re.sub(r"loc\(.*", "", text), n


# sha256 of the canonical lowered text of two calls without a window, taken on
# the parent commit (PR 31) and read again on this tree: PR 32 gave
# ``tile_plan`` a window and the file a windowed forward, and a call without
# one lowers to the parent's program (the four accepted cells' whole programs
# were compared the same way before any chip time: PERF.md 6, PR 32). A PR
# that means to change these kernels updates the hashes: the packed call's is
# PR 48's own (2176 keys are one block and the call has no pad mask: the plain
# forward, no bias operand in either kernel); the heads-major one stands.
UNWINDOWED_GOLDEN = {
    "heads_major_causal": "fa11ab7986d9763ab6888566fb0ec5e8eb3db4dfbe0539feb8d1760a20997966",
    "packed_causal_fwd_bwd": "9da2911f9ca060194c9e42a8a7f811070d2a3ba682d48d76a99d7dfecd00c80d",
}


@pytest.mark.parametrize("call", sorted(UNWINDOWED_GOLDEN))
def test_a_call_without_a_window_lowers_to_the_parents_program(one_chip, mosaic, call):
    import hashlib

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    if call == "heads_major_causal":  # the expanded MLA prompt pass's call, two rows of four heads
        fn = lambda q, k, v: fa.flash_attention(q, k, v, causal=True, sm_scale=0.1)  # noqa: E731
        args = (sds(2, 4, 1024, 192), sds(2, 4, 1024, 192), sds(2, 4, 1024, 128))
    else:  # the Perceiver AR cross-attention's call, forward and backward, kv cut to 2176
        def fn(q, k, v):
            loss = lambda q, k, v: fa.flash_attention_packed(q, k, v, 8, causal=True, sm_scale=0.125).astype(jnp.float32).sum()  # noqa: E731
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        args = (sds(2, 1024, 512), sds(2, 2176, 512), sds(2, 2176, 512))
    with jax.default_matmul_precision("default"):
        text, bodies = _canonical(jax.jit(fn).lower(*args).as_text())
    assert bodies == (1 if call == "heads_major_causal" else 2)
    assert hashlib.sha256(text.encode()).hexdigest() == UNWINDOWED_GOLDEN[call]


MELLUM_HEADS, MELLUM_KV_HEADS, MELLUM_HEAD_DIM, MELLUM_WINDOW, MELLUM_PROMPT = 32, 4, 128, 1024, 8192


@pytest.mark.parametrize("window", [MELLUM_WINDOW, None], ids=["window_layer", "full_layer"])
def test_grouped_query_flash_forward_lowers(one_chip, mosaic, window):
    """One 8192-token row of the prompt pass: 32 query heads in the projection
    layout on 4 key-value heads, blocks of 1024, two kv blocks a q block under
    the window and up to eight without."""
    q = jax.ShapeDtypeStruct((1, MELLUM_PROMPT, MELLUM_HEADS * MELLUM_HEAD_DIM), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, MELLUM_KV_HEADS, MELLUM_PROMPT, MELLUM_HEAD_DIM), jnp.bfloat16, sharding=one_chip)
    text = _compile(lambda q, k, v: fa.flash_attention_gqa(q, k, v, MELLUM_HEADS, window=window, sm_scale=0.088), q, kv, kv)
    name = "flash_fwd_q8192_kv8192" + ("" if window is None else "_w1024")
    assert "tpu_custom_call" in text and name in text
    plan = fa.tile_plan(MELLUM_PROMPT, MELLUM_PROMPT, True, window=window or MELLUM_PROMPT)
    assert plan.block_q == 1024 and plan.tiles_run == (600 if window else 2112)


_GENERATORS = {}
_LOWERED = {}  # sha256 of each generator's canonical lowered text (``_canonical``)


def _cell_generator(workload: str, family: str, one_chip, monkeypatch, heads_major: bool = False):
    """A decode cell's generator as the benchmark builds it (the family's
    model and generator at the cell's sizes), compiled for a described v5e
    with every Pallas kernel lowered for Mosaic; once a module run.
    ``heads_major``: a latent-attention cell's prompt pass on the path that
    shapes off the token-major kernel's take (the program of before PR 42)."""
    from benchmarks import run

    if heads_major:
        monkeypatch.setattr(importlib.import_module("perceiver_io_tpu.core.mla"), "mla_flash_supported", lambda *_: False)
        workload, cell_name = workload + "/heads_major", workload
    else:
        cell_name = workload
    if workload not in _GENERATORS:
        gm = importlib.import_module("perceiver_io_tpu.ops.grouped_matmul")
        monkeypatch.setattr(gm, "_interpret_default", lambda: False)
        cell = run.load_json("workloads", cell_name)
        fam = importlib.import_module(f"benchmarks.families.{family}").Family(run.load_json("configs", cell["config"]))
        p = cell["params"]
        model = fam.model()
        shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), fam.param_shapes(model))
        ids = jax.ShapeDtypeStruct((p["batch_size"], p["prompt_len"]), jnp.int32, sharding=one_chip)
        generate = fam.generate_fn(model, p["num_latents"], p["new_tokens"], p["cache_dtype"])
        with fa.default_flash(True), jax.default_matmul_precision("default"):
            lowered = generate.lower(shapes, ids)
            _LOWERED[workload] = hashlib.sha256(_canonical(lowered.as_text())[0].encode()).hexdigest()
            _GENERATORS[workload] = lowered.compile()
    return _GENERATORS[workload]


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes


def test_the_mellum_cells_generator_fits_the_chip(one_chip, mosaic, monkeypatch):
    """``mellum2-pp4-decode-b32`` as the benchmark builds it (the family's
    model and generator at the cell's sizes: 3.795B bfloat16 parameters, 32
    prompts of 8192 tokens, 256 new tokens, bfloat16 caches), compiled for a
    described v5e: under the 16.9 GB the runtime offers with 2 GB to spare
    (13.24 GB here reads 11.08 GB on the chip, PERF.md 6, PR 33), both flash kernels
    and the grouped expert kernels in it."""
    import re

    compiled = _cell_generator("mellum2-pp4-decode-b32", "mellum", one_chip, monkeypatch)
    m = compiled.memory_analysis()
    assert 7.58e9 < m.argument_size_in_bytes < 7.60e9  # the weights and the prompts
    total = _device_bytes(compiled)
    assert total < 14.9e9, f"{total / 1e9:.2f} GB"
    text = compiled.as_text()
    assert set(re.findall(r"flash_fwd_q\d+_kv\d+(?:_w\d+)?", text)) == {"flash_fwd_q8192_kv8192", "flash_fwd_q8192_kv8192_w1024"}
    assert "moe_experts_prefill_m65536_k2304_n896" in text and "moe_experts_prefill_m65536_k896_n2304" in text
    # every expert is held: the expert rows come back by a gather, and no layer scatter-adds into the chunk's tokens (PR 33)
    assert not re.search(r"f32\[8192,2304\]\{[^}]*\} scatter\(", text)
    assert len(re.findall(r"bf16\[65536,2304\]\{[^}]*\} gather\(", text)) == 2 * 8  # ``x[token]`` and the combine, a layer


# ------------------------------------------ K-EXAONE: the speculative generator at the cell's sizes


def test_the_kexaone_cells_generator_fits_the_chip(one_chip, mosaic, monkeypatch):
    """``kexaone-ep8-mtp-decode-b64`` as the benchmark builds it (4.54B
    bfloat16 parameters, 64 prompts of 1024 tokens, 512 new tokens, bfloat16
    caches with a length a row), compiled for a described v5e: under the
    16.9 GB the runtime offers with 2 GB to spare, the window-128 and the full
    flash kernels and the grouped expert kernels in it."""
    import re

    compiled = _cell_generator("kexaone-ep8-mtp-decode-b64", "exaone_moe", one_chip, monkeypatch)
    m = compiled.memory_analysis()
    assert 9.08e9 < m.argument_size_in_bytes < 9.10e9  # the weights and the prompts
    total = _device_bytes(compiled)
    assert total < 14.9e9, f"{total / 1e9:.2f} GB"
    text = compiled.as_text()
    assert set(re.findall(r"flash_fwd_q\d+_kv\d+(?:_w\d+)?", text)) == {"flash_fwd_q1024_kv1024", "flash_fwd_q1024_kv1024_w128"}
    assert "moe_experts_prefill_m10240_k6144_n2048" in text and "moe_experts_prefill_m10240_k2048_n6144" in text  # a chunk's one pass


@pytest.mark.parametrize("slots,window", [(1552, None), (144, 128)], ids=["growing", "ring"])
def test_the_speculative_steps_attention_kernel_lowers(one_chip, mosaic, slots, window):
    """``ops/gqa_verify.py`` at the cell's shapes (64 rows of 8 key-value
    heads, two positions of 8 queries, bfloat16 caches of whole tiles), alone:
    one Mosaic call whose two caches alias their operands."""
    from perceiver_io_tpu.ops.gqa_verify import gqa_verify, gqa_verify_kernel_name, gqa_verify_supported

    assert gqa_verify_supported((512, slots, 128), jnp.bfloat16, 8, 2, 8, window)
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)  # noqa: E731
    lengths = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip)
    text = _compile(lambda q, kn, vn, k, v, n: gqa_verify(q, kn, vn, k, v, n, heads=8, window=window, sm_scale=128 ** -0.5),
                    shape(512, 16, 128), shape(512, 2, 128), shape(512, 2, 128), shape(512, slots, 128), shape(512, slots, 128), lengths)
    assert gqa_verify_kernel_name(window is not None, 512, 16, slots, 128) in text and "tpu_custom_call" in text
    assert "output_to_operand_aliasing={{0}: (4, {}), {1}: (5, {})}" in text


def test_the_speculative_step_is_one_kernel_a_layer_over_row_major_caches(one_chip, mosaic, monkeypatch):
    """The same generator: each of a speculative step's six attentions (the
    stack's four rings and its full layer under ``spec/verify``, the module's
    full layer under ``mtp/block``) is one ``gqa_verify_*`` call that takes the
    two carried cache arrays as they are and hands them back in their own
    buffers; the decode ``while`` carries the twelve arrays row-major, in
    whole bfloat16 sublane tiles (1552 slots where the prompt and the new
    tokens need 1537, rings of 144 where the window needs 129), and nothing
    in its body copies, turns, scatters into or slices into a cache or holds
    float32 scores of a growing layer (PERF.md 6, PR 44: the parent's carry
    was slot-major ``{2,0,1}``, each write a scatter behind a relayout copy,
    a layer three passes). The rings' fill by the prompt pass stays XLA's
    scatter, once a call."""
    import re

    text = _cell_generator("kexaone-ep8-mtp-decode-b64", "exaone_moe", one_chip, monkeypatch).as_text()
    ring, full = "gqa_verify_ring_r512_q16_s144_d128", "gqa_verify_full_r512_q16_s1552_d128"
    loop, body = _loop_around(text, ring)
    calls = [i for i in body if i.opcode == "custom-call" and i.name.startswith("gqa_verify_")]
    assert sorted(re.sub(r"\.\d+$", "", i.name) for i in calls) == [full] * 2 + [ring] * 4
    assert len(re.findall(r"%gqa_verify_\w+[.\d]* = ", text)) == 6  # none outside the loop
    scopes = collections.Counter()
    for call in calls:
        assert "output_to_operand_aliasing={{0}: (4, {}), {1}: (5, {})}" in call.line  # the caches come back in their own buffers
        scope = re.search(r'op_name="[^"]*while/body/decode/(?:[^"/]*/)?(spec/verify|mtp/block)/[^"]*attn/(window|full)/jit\(gqa_verify\)', call.line)
        assert scope, call.line[:600]
        scopes[scope.groups()] += 1
    assert scopes == {("spec/verify", "window"): 4, ("spec/verify", "full"): 1, ("mtp/block", "full"): 1}
    cache = r"bf16\[512,(?:1552|144),128\]"
    result = lambda ins: ins.line.split(" = ", 1)[1].split(f" {ins.opcode}(", 1)[0]  # noqa: E731
    for ins in body:
        assert "kv_cache_write" not in ins.line, ins.line[:300]  # XLA's per-row scatters are gone with their scope
        assert not re.search(r"f32\[512,16,15\d\d\]", result(ins)), ins.line[:300]
        if re.search(cache, result(ins)):
            assert ins.opcode not in ("scatter", "dynamic-update-slice", "copy", "transpose", "copy-start", "copy-done"), ins.line[:300]
            assert not re.search(cache + r"\{(?!2,1,0)", result(ins)), ins.line[:300]  # row-major wherever it appears
    carried = result(loop)
    assert len(re.findall(r"bf16\[512,1552,128\]\{2,1,0[:}]", carried)) == 2 * 2 and len(re.findall(r"bf16\[512,144,128\]\{2,1,0[:}]", carried)) == 2 * 4
    assert not re.search(cache + r"\{(?!2,1,0)", carried) and not re.search(r"bf16\[512,(?:1537|129),128\]", text)


# sha256 of the canonical lowered text (``_canonical``) of the four decode
# cells whose generators run none of the code PR 44 changed, taken on its
# parent commit (d708ae6) by the same lowering: ``core/gqa.py::step``, the
# ``KVCache`` / ``WindowKVCache`` / ``LatentCache`` paths and the prompt passes
# lower to the parent's programs (``tools/step_hlo.py --same`` says the same of
# the compiled modules). A PR that means to change one of these programs
# updates its hash: Jamba's is PR 47's own (the scan kernel reads and writes
# the rows as the projections leave them) and **stands through PR 54, whose
# change it bypasses** (no expert layer); Mellum's, DeepSeek-V3's and LongCat's
# are PR 54's own (``ops/grouped_matmul.py`` holds the contraction and the
# column whole: the kernels' blocks and bodies differ, the rest of each program is PR 50's;
# K-EXAONE's and Ling's generators, which change with them, are not pinned).
PARENT_GENERATORS = {
    "mellum2-pp4-decode-b32": ("mellum", "7b50b744f8b6428b96d5040be7c6bc6ed85225444bd5d2fe72c6e486f5adc608"),
    "jamba2-3b-decode-b256": ("jamba", "37c48df32f761ff881f8e7a99ecd629d39bf5825793aa83afac1ef2a1f1020cd"),
    "dsv3-ep16-decode-b64": ("deepseek_v3", "5d4bdcf814047da9f4c759635ffba248ce53686661cdc9616a512aeebf11f32c"),
    "longcat-ep32-decode-b64": ("longcat_flash", "9133383956b4104dae1791e8f992df5e644522b081cb6d109211e60ca3ba881b"),
}


@pytest.mark.parametrize("workload", sorted(PARENT_GENERATORS))
def test_a_generator_without_a_speculative_step_lowers_to_the_parents_program(one_chip, mosaic, monkeypatch, workload):
    family, golden = PARENT_GENERATORS[workload]
    _cell_generator(workload, family, one_chip, monkeypatch)
    assert _LOWERED[workload] == golden


# a share-held cell's family, the hidden size, and the kernels of a prompt chunk's pass: combine and down-projection
SHARE_HELD_GENERATORS = {
    "dsv3-ep16-decode-b64": ("deepseek_v3", 7168, "moe_combine_t8192_r5120_h7168", "moe_experts_prefill_m5120_k2048_n7168"),
    "kexaone-ep8-mtp-decode-b64": ("exaone_moe", 6144, "moe_combine_t8192_r10240_h6144", "moe_experts_prefill_m10240_k2048_n6144"),
    "longcat-ep32-decode-b64": ("longcat_flash", 6144, "moe_combine_t4096_r1280_h6144", "moe_experts_prefill_m1280_k2048_n6144"),
    "ling3-ep4-decode-b128-p2k": ("ling", 2560, "moe_combine_t8192_r4096_h2560", "moe_experts_prefill_m4096_k768_n2560"),
}


@pytest.mark.parametrize("workload", sorted(SHARE_HELD_GENERATORS))
def test_a_share_held_generator_sums_its_rows_by_token_and_fits_the_chip(one_chip, mosaic, monkeypatch, workload):
    """The four cells whose expert layers hold a share of the experts, as the
    benchmark builds them: no float32 scatter-add of rows into a chunk's
    tokens anywhere in the compiled generator (PR 50), the segment-sum kernel
    at the rows of a pass sized for an even routing (``moe._pass_rows``: one
    pass a chunk a layer, four or five of 4096 rows at Ling's geometry, where
    the scatter-add's passes were 1024 rows), and
    the program still under the 14.9 GB the cells are held to (Ling's counted
    as its own test counts it: without the aliased states and cache that the
    analysis holds twice)."""
    import re

    family, hidden, combine, down = SHARE_HELD_GENERATORS[workload]
    compiled = _cell_generator(workload, family, one_chip, monkeypatch)
    text = compiled.as_text()
    assert not re.search(rf"f32\[\d+,{hidden}\]\{{[^}}]*\}} scatter\(", text)
    assert combine in text and down in text and "moe_experts_prefill_m1024_" not in text
    twice = 6 * 128 * 32 * 128 * 128 * 4 + 128 * 2304 * 576 * 2 if family == "ling" else 0
    total = _device_bytes(compiled) - twice
    assert total < 14.9e9, f"{total / 1e9:.2f} GB"


# ------------------------------------------ LongCat-Flash: the shortcut-connected block at the cell's sizes


def test_the_longcat_cells_generator_fits_the_chip(one_chip, mosaic, monkeypatch):
    """``longcat-ep32-decode-b64`` as the benchmark builds it (5.17B bfloat16
    parameters, 64 prompts of 1024 tokens, 512 new tokens, eight bfloat16
    latent caches), compiled for a described v5e: under the 16.9 GB the
    runtime offers with 2 GB to spare, the flash kernel at the latent
    attention's head widths and the grouped expert kernels in it, and the
    eight caches filled row-major by the prompt pass."""
    import re

    compiled = _cell_generator("longcat-ep32-decode-b64", "longcat_flash", one_chip, monkeypatch)
    m = compiled.memory_analysis()
    assert 10.34e9 < m.argument_size_in_bytes < 10.36e9  # the weights and the prompts
    total = _device_bytes(compiled)
    assert total < 14.9e9, f"{total / 1e9:.2f} GB"
    text = compiled.as_text()
    assert set(re.findall(r"flash_\w*fwd_q\d+_kv\d+(?:_[wh]\d+)?", text)) == {"flash_mla_fwd_q1024_kv1024_h64"}
    assert "moe_experts_prefill_m1280_k6144_n2048" in text and "moe_experts_prefill_m1280_k2048_n6144" in text  # a chunk's one pass
    # the prompt pass lays each cache's rows out row-major (a pad to the capacity); a step's append is the kernel's (below)
    assert len(re.findall(r"bf16\[64,1536,576\]\{2,1,0[^}]*\} pad\(", text)) == 8


# ------------------------------------------ the absorbed step's cache side: one kernel, a row-major cache updated in place


def _loop_around(text: str, needle):
    """The one ``while`` whose body (its fusions and calls included) holds
    instructions named ``needle...``, or for which ``needle(while, body)``
    holds: the ``while`` and every instruction its body reaches."""
    import re

    from perceiver_io_tpu.analysis.graph import parse_hlo_computations

    computations = parse_hlo_computations(text)

    def reach(name, seen):
        if name in seen or name not in computations:
            return []
        seen.add(name)
        found = list(computations[name])
        for ins in computations[name]:
            for callee in re.findall(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", ins.line):
                found += reach(callee, seen)
        return found

    loops = []
    for instructions in computations.values():
        for ins in instructions:
            if ins.opcode == "while":
                inside = reach(re.search(r"body=%?([\w.\-]+)", ins.line).group(1), set())
                if needle(ins, inside) if callable(needle) else any(i.name.startswith(needle) for i in inside):
                    loops.append((ins, inside))
    assert len(loops) == 1, [ins.name for ins, _ in loops]
    return loops[0]


@pytest.mark.parametrize(
    "workload,family,heads,capacity,sites",
    [("longcat-ep32-decode-b64", "longcat_flash", 64, 1536, 8), ("dsv3-ep16-decode-b64", "deepseek_v3", 128, 1280, 5)],
    ids=["longcat", "dsv3"],
)
def test_the_absorbed_step_is_one_kernel_over_a_row_major_cache(one_chip, mosaic, monkeypatch, workload, family, heads, capacity, sites):
    """Both latent-attention generators compiled for a described v5e: every
    absorbed attention of a decode step is one ``mla_absorb_*`` call under
    ``decode/.../mla/absorb``; the loop carries each cache row-major and
    nothing in its body appends to, copies or turns a cache, or writes the
    float32 scores (PERF.md 6, PR 40: the 167 us lane-strided append and the
    25 MB round trip a ``{1,2,0}`` carry cost an attention)."""
    import re

    compiled = _cell_generator(workload, family, one_chip, monkeypatch)
    assert _device_bytes(compiled) < 14.9e9
    text = compiled.as_text()
    name = f"mla_absorb_h{heads}_s{capacity}_w576"
    loop, body = _loop_around(text, name)
    calls = [i for i in body if i.opcode == "custom-call" and i.name.startswith(name)]
    assert len(calls) == sites and len(re.findall(rf"%{name}[.\d]* = ", text)) == sites  # none outside the loop
    for call in calls:
        assert re.search(r'op_name="[^"]*decode/[^"]*mla/absorb/[^"]*pallas_call"', call.line), call.line
        assert "output_to_operand_aliasing={{0}: (3, {})}" in call.line  # the cache comes back in its own buffer
    cache = rf"bf16\[64,{capacity},576\]"
    result = lambda ins: ins.line.split(" = ", 1)[1].split(f" {ins.opcode}(", 1)[0]  # noqa: E731
    for ins in body:
        if re.search(cache, result(ins)):
            assert ins.opcode not in ("dynamic-update-slice", "copy", "transpose", "copy-start", "copy-done"), ins.line[:300]
            assert not re.search(cache + r"\{(?!2,1,0)", result(ins)), ins.line[:300]  # row-major wherever it appears
        assert not re.search(rf"f32\[64,{heads},{capacity}\]", result(ins)), ins.line[:300]
    # the carry itself: ``sites`` caches, each ``{2,1,0}``
    assert len(re.findall(cache + r"\{2,1,0[:}]", result(loop))) == sites and not re.search(cache + r"\{(?!2,1,0)", result(loop))


# ------------------------------------------ the expanded prompt pass: the kernel reads what the up-projections write

_PREFETCHES = ("copy-start", "copy-done", "slice-start", "slice-done")  # memory-space assignment's moves between HBM and VMEM


def _result(ins) -> str:
    return ins.line.split(" = ", 1)[1].split(f" {ins.opcode}(", 1)[0]


def _elements(result: str) -> int:
    """The largest array a result type names, in elements."""
    import math
    import re

    return max((math.prod(int(d) for d in dims.split(",") if d) for dims in re.findall(r"[a-z]+\d*\[([\d,]*)\]", result)), default=0)


@pytest.mark.parametrize(
    "workload,family,heads,sites",
    [("longcat-ep32-decode-b64", "longcat_flash", 64, 8), ("dsv3-ep16-decode-b64", "deepseek_v3", 128, 5)],
    ids=["longcat", "dsv3"],
)
def test_the_expanded_prompt_pass_hands_the_kernel_what_the_up_projections_write(one_chip, mosaic, monkeypatch, workload, family, heads, sites):
    """Both latent-attention generators compiled for a described v5e: every
    attention of a prompt chunk is one ``flash_mla_fwd_*`` call and one
    rotation of the queries' rotary halves; in the chunk loops' bodies nothing
    under ``mla/expand`` copies, turns, broadcasts, concatenates, gathers or
    slices an array of more than 2^24 elements (PERF.md 6, PR 42: sixteen such
    passes over 67 to 201 MB a chunk stood between the products and the
    heads-major kernel); ``w_uq``'s column sets are cut in front of the loops,
    once a call; and the decode loop's body computes what it computed with the
    heads-major prompt pass in front of it (the same instructions in the same
    order; which weights the compiler prefetches into VMEM is its own)."""
    import re

    from perceiver_io_tpu.analysis.graph import parse_hlo_computations

    text = _cell_generator(workload, family, one_chip, monkeypatch).as_text()
    name = f"flash_mla_fwd_q1024_kv1024_h{heads}"
    computations = parse_hlo_computations(text)
    bodies = [ins_list for ins_list in computations.values() if any(i.opcode == "custom-call" and i.name.startswith(name) for i in ins_list)]
    assert sum(len([i for i in body if i.name.startswith(name)]) for body in bodies) == sites == len(re.findall(rf"%{name}[.\d]* = ", text))
    assert not re.search(r"flash_fwd_q\d+", text)  # the heads-major kernel is in neither phase
    moved = ("copy", "transpose", "broadcast", "concatenate", "gather", "slice", "pad", "reshape")
    for body in bodies:
        assert len([i for i in body if i.name.startswith(f"rotary_fwd_n1024_c{heads * 64}")]) == len([i for i in body if i.name.startswith(name)])
        assert any(i.opcode == "dynamic-update-slice" or "dynamic_update_slice" in i.line for i in body)  # a chunk loop's body
        for ins in body:
            if "mla/expand" in ins.line and ins.opcode in moved:
                assert _elements(_result(ins)) <= 2 ** 24, ins.line[:300]
            called = re.search(r"calls=%?([\w.\-]+)", ins.line)
            if "mla/expand" in ins.line and ins.opcode == "fusion" and "kind=kLoop" in ins.line and called:
                # a loop fusion that only moves data is a copy by another name: it computes (a norm, a rotation) or is small
                inside = {i.opcode for i in computations[called.group(1)]} - {"parameter", "bitcast", "tuple", "get-tuple-element", "constant"}
                assert not inside <= set(moved) or _elements(_result(ins)) <= 2 ** 24, ins.line[:300]
    # the weight's two column sets: cut in the entry computation (``sites`` weights, two sets each), in no loop's body
    cuts = [i for ins_list in computations.values() for i in ins_list
            if "split_w_uq" in i.line or re.search(rf"bf16\[1536,{heads},(?:128|64)\]", _result(i))]
    entry = {i.name for i in computations[re.search(r"^ENTRY\s+%?([\w.\-]+)", text, re.M).group(1)]}
    assert cuts and all(i.name in entry for i in cuts), [i.name for i in cuts if i.name not in entry]

    def decode_body(program: str):
        _, body = _loop_around(program, f"mla_absorb_h{heads}")
        return [(i.opcode, re.sub(r"\{[^}]*\}", "", _result(i))) for i in body
                if i.opcode not in _PREFETCHES + ("parameter", "tuple", "get-tuple-element", "bitcast", "custom-call")]

    before = _cell_generator(workload, family, one_chip, monkeypatch, heads_major=True).as_text()
    assert re.search(r"flash_fwd_q1024_kv1024", before) and name not in before
    assert decode_body(text) == decode_body(before)


# ------------------------------------------ the hybrid stack: a float32 recurrent state carried in place beside two caches


def test_the_jamba_cells_generator_carries_its_state_in_place(one_chip, mosaic, monkeypatch):
    """``jamba2-3b-decode-b256`` as the benchmark builds it (3.03B bfloat16
    parameters whole, 256 prompts of 256 tokens, 384 new tokens), compiled for a
    described v5e: under the 16.9 GB the runtime offers, the scan kernel in the
    prompt pass (one geometry, 26 calls on ``bf16[16,256,5120]`` / ``f32[16,256,5120]``
    operands as the projections leave them, none in the decode loop), and the
    decode loop carrying the 26 states ``[256, 16, 5120]`` **float32 and
    row-major**: nothing in its body turns, converts or slices into a state
    (what the compiler's memory-space assignment does with one, a
    ``copy-start`` / ``slice-start`` between HBM and VMEM at the same layout,
    moves the bytes the update has to move anyway and is no relayout), and no
    state of half the precision exists anywhere in the program."""
    import re

    compiled = _cell_generator("jamba2-3b-decode-b256", "jamba", one_chip, monkeypatch)
    m = compiled.memory_analysis()
    assert 6.05e9 < m.argument_size_in_bytes < 6.07e9  # the weights whole (the tied table once) and the prompts
    total = _device_bytes(compiled)
    assert total < 14.9e9, f"{total / 1e9:.2f} GB"
    text = compiled.as_text()
    assert set(re.findall(r"ssm_scan_l\d+_d\d+_n\d+", text)) == {"ssm_scan_l256_d5120_n16"}
    state = r"f32\[256,16,5120\]"
    result = lambda ins: ins.line.split(" = ", 1)[1].split(f" {ins.opcode}(", 1)[0]  # noqa: E731
    loop, body = _loop_around(text, lambda loop, inside: re.search(state, result(loop)))  # the decode loop: the one that carries a state
    assert len(re.findall(state + r"\{2,1,0[:}]", result(loop))) == 26 and not re.search(state + r"\{(?!2,1,0)", result(loop))
    assert not any(i.opcode == "custom-call" and "ssm_scan" in i.name for i in body)  # the kernel is the prompt pass's
    for ins in body:
        if re.search(state, result(ins)):
            assert ins.opcode not in ("copy", "transpose", "convert", "dynamic-update-slice"), ins.line[:300]
            assert not re.search(state + r"\{(?!2,1,0)", result(ins)), ins.line[:300]
    assert not re.search(r"bf16\[256,16,5120\]", text) and not re.search(r"f32\[65536,5120,16\]|f32\[256,256,5120,16\]", text)
    # the scan kernel takes the rows as the projections leave them and writes ``y`` and the final state in the mixer's
    # shapes (PR 47): the ``[.., 40, 128]`` view of a stream or of the state, a physical copy on the chip, exists nowhere,
    # and under ``ssm/scan`` XLA produces no array of a stream's size (a convert of ``x``, a reshape of ``y``)
    from perceiver_io_tpu.analysis.graph import parse_hlo_computations

    assert not re.search(r"\[16,(?:256|16),40,128\]", text)
    scan = [i for instructions in parse_hlo_computations(text).values() for i in instructions if "ssm/scan" in i.line]
    assert sum(i.opcode == "custom-call" for i in scan) == 26
    operands = r"operand_layout_constraints=\{f32\[131072\]\{0\}, bf16\[16,256,5120\]\{2,1,0\}, f32\[16,256,5120\]\{2,1,0\}, f32\[16,5120\]\{1,0\}\}"
    results = r"\(f32\[16,256,5120\]\{2,1,0[:}].*?, f32\[16,16,5120\]\{2,1,0[:}]"
    for ins in scan:
        if ins.opcode == "custom-call":
            assert ins.name.startswith("ssm_scan_l256_d5120_n16") and re.search(operands, ins.line) and re.match(results, result(ins)), ins.line[:400]
        elif ins.opcode != "get-tuple-element":
            assert _elements(result(ins)) < 16 * 256 * 5120, ins.line[:300]


# ------------------------------------------ a decoder-hybrid-decoder stack: one cache that eight layers read, a prompt pass cut at it


def test_the_phi4flash_cells_generator_shares_one_cache_and_cuts_its_prompt_pass(one_chip, mosaic, monkeypatch):
    """``phi4flash-decode-b32-p8k`` as the benchmark builds it (3.85B bfloat16
    parameters whole, 32 prompts of 8192 tokens, 256 new tokens), compiled for a
    described v5e: under the 16.9 GB the runtime offers with 2 GB to spare; the
    decode loop carries **one** pair of arrays of the shared cache's shape
    (``bf16[320, 8448, 128]``: 10 key-value pairs a row, keys and values), which
    its body updates in place by two ``dynamic-update-slice`` and never copies,
    turns or converts, whatever the number of layers that read it; and the
    prompt pass runs 35 chunk loops (17 layers' mixers and feed-forwards and the
    owning layer's key and value projections), not 64, with nothing under
    ``prefill/last`` larger than one position a row against the prompt's keys."""
    import re

    compiled = _cell_generator("phi4flash-decode-b32-p8k", "phi4flash", one_chip, monkeypatch)
    m = compiled.memory_analysis()
    assert 7.70e9 < m.argument_size_in_bytes < 7.72e9  # the weights whole (the tied table once) and the prompts
    total = _device_bytes(compiled)
    assert total < 14.9e9, f"{total / 1e9:.2f} GB"
    text = compiled.as_text()
    # the window layers alone run a flash forward over the prompt: the owning layer's attention is cut to its last position
    assert set(re.findall(r"flash_diff_fwd_q\d+_kv\d+(?:_w\d+)?", text)) == {"flash_diff_fwd_q8192_kv8192_w512"}
    assert set(re.findall(r"ssm_scan_l\d+_d\d+_n\d+", text)) == {"ssm_scan_l8192_d5120_n16"}
    shared = r"bf16\[320,8448,128\]"
    result = lambda ins: ins.line.split(" = ", 1)[1].split(f" {ins.opcode}(", 1)[0]  # noqa: E731
    loop, body = _loop_around(text, lambda loop, inside: re.search(shared, result(loop)))  # the decode loop: the one that carries the cache
    assert len(re.findall(shared, result(loop))) == 2  # keys and values, once: no copy a reading layer
    assert len(re.findall(r"bf16\[320,512,128\]", result(loop))) == 2 * 8 and len(re.findall(r"f32\[32,16,5120\]", result(loop))) == 9
    written = [ins for ins in body if re.search(shared, result(ins)) and ins.opcode not in ("parameter", "get-tuple-element", "bitcast", "fusion", "tuple")]
    assert sorted(ins.opcode for ins in written) == ["dynamic-update-slice"] * 2, [ins.line[:200] for ins in written]
    from perceiver_io_tpu.analysis.graph import parse_hlo_computations

    everything = [ins for instructions in parse_hlo_computations(text).values() for ins in instructions]
    chunk_loops = [ins for ins in everything if ins.opcode == "while" and "prefill/chunk_io" in ins.line]
    assert len(chunk_loops) == 2 * 17 + 1
    last = [ins for ins in everything if "prefill/last" in ins.line and ins.opcode not in ("parameter", "get-tuple-element", "tuple")]
    assert last and max(_elements(_result(ins)) for ins in last) <= 320 * 4 * 8192  # a key pair's four queries against the prompt's keys


# ------------------------------------------ states alone: five float32 retention states updated in place by the step's kernel


def test_the_brumby_cells_generator_carries_its_states_in_place(one_chip, mosaic, monkeypatch):
    """``brumby-pp8-decode-b32-p4k`` as the benchmark builds it (3.21B bfloat16
    parameters, 32 prompts of 4096 tokens, 256 new tokens), compiled for a
    described v5e: one geometry of the chunk kernel in the prompt pass (five
    calls, none in the decode loop) and one of the step's kernel in the loop
    (five calls, none outside it); the decode loop carries the five states
    ``f32[32,8,8320,128]`` row-major, each the aliased operand and result of its
    layer's kernel (``output_to_operand_aliasing``), and nothing in the body
    copies, turns, converts or slices into one; no state of half the precision
    and no feature map of a token (``[.., 8320]`` or ``[.., 65, 128]`` wide over
    the prompt's tokens) exists anywhere outside the kernels. The memory
    analysis here counts each aliased kernel result as a buffer of its own
    (5.5 GB): without them the program is under the 14.9 GB the other cells
    are held to, and the chip reads 14.18 GB (PERF.md 6, PR 46)."""
    import re

    compiled = _cell_generator("brumby-pp8-decode-b32-p4k", "brumby", one_chip, monkeypatch)
    m = compiled.memory_analysis()
    assert 6.41e9 < m.argument_size_in_bytes < 6.43e9  # the weights (embedding and head apart) and the prompts
    states = 5 * 32 * 8 * (8320 * 128 + 65 * 128) * 4
    total = _device_bytes(compiled) - states
    assert total < 14.9e9, f"{total / 1e9:.2f} GB"
    text = compiled.as_text()
    assert set(re.findall(r"power_ret_chunk_l\d+_c\d+_h\d+_d\d+", text)) == {"power_ret_chunk_l4096_c256_h40_d128"}
    assert set(re.findall(r"power_ret_step_b\d+_h\d+_d\d+", text)) == {"power_ret_step_b32_h40_d128"}
    state = r"f32\[32,8,8320,128\]"
    result = lambda ins: ins.line.split(" = ", 1)[1].split(f" {ins.opcode}(", 1)[0]  # noqa: E731
    loop, body = _loop_around(text, lambda loop, inside: re.search(state, result(loop)))  # the decode loop: the one that carries a state
    assert len(re.findall(state + r"\{3,2,1,0[:}]", result(loop))) == 5 and not re.search(state + r"\{(?!3,2,1,0)", result(loop))
    kernels = [i for i in body if i.opcode == "custom-call" and "power_ret_step" in i.name]
    assert len(kernels) == 5 and all("output_to_operand_aliasing={{1}: (4, {}), {2}: (5, {})}" in i.line for i in kernels)
    assert not any(i.opcode == "custom-call" and "power_ret_chunk" in i.name for i in body)  # the chunk kernel is the prompt pass's
    for ins in body:
        if re.search(state, result(ins)):
            assert ins.opcode in ("custom-call", "get-tuple-element", "tuple", "parameter", "bitcast"), ins.line[:300]
            assert not re.search(state + r"\{(?!3,2,1,0)", result(ins)), ins.line[:300]
    assert not re.search(r"bf16\[32,8,8320,128\]", text)
    assert not re.search(r"(bf16|f32)\[(\d+,)*(4096|131072)(,\d+)*,(8320|8256|65,128)\]", text)


def test_the_ling_cells_generator_carries_its_delta_states_in_place(one_chip, mosaic, monkeypatch):
    """``ling3-ep4-decode-b128-p2k`` as the benchmark builds it (5.23B bfloat16
    parameters, 128 prompts of 2048 tokens, 256 new tokens), compiled for a
    described v5e: one geometry of the chunk kernel in the prompt pass (six
    calls, none in the decode loop) and one of the step's kernel in the loop
    (six calls, none outside it); the decode loop carries the six states
    ``f32[128,32,128,128]`` row-major, each the aliased operand and result of its
    layer's kernel (``output_to_operand_aliasing``), and nothing in the body
    copies, turns, converts or slices into one; **no state of half the
    precision exists anywhere**, and no second copy: the memory analysis here
    counts each aliased kernel result as a buffer of its own (the six states
    and the latent cache), and without them the program is under the 14.9 GB
    the other cells are held to. A decode step's experts run the grouped
    kernels (no ``[128,2560,768]`` array is laid out again: the dense path's
    5.8 GB of copies are what would not fit), and the one latent layer's step
    is the absorbed kernel over its cache."""
    import re

    from perceiver_io_tpu.analysis.graph import parse_hlo_computations

    compiled = _cell_generator("ling3-ep4-decode-b128-p2k", "ling", one_chip, monkeypatch)
    m = compiled.memory_analysis()
    assert 10.46e9 < m.argument_size_in_bytes < 10.47e9  # the weights and the prompts
    states = 6 * 128 * 32 * 128 * 128 * 4
    total = _device_bytes(compiled) - states - 128 * 2304 * 576 * 2
    assert total < 14.9e9, f"{total / 1e9:.2f} GB"
    text = compiled.as_text()
    assert set(re.findall(r"kda_chunk_l\d+_c\d+_h\d+_d\d+", text)) == {"kda_chunk_l2048_c128_h32_d128"}
    assert set(re.findall(r"kda_step_b\d+_h\d+_d\d+", text)) == {"kda_step_b128_h32_d128"}
    assert set(re.findall(r"mla_absorb_h\d+_s\d+_w\d+", text)) == {"mla_absorb_h32_s2304_w576"}
    state = r"f32\[128,32,128,128\]"
    result = lambda ins: ins.line.split(" = ", 1)[1].split(f" {ins.opcode}(", 1)[0]  # noqa: E731
    loop, body = _loop_around(text, lambda loop, inside: re.search(state, result(loop)))  # the decode loop: the one that carries a state
    assert len(re.findall(state + r"\{3,2,1,0[:}]", result(loop))) == 6 and not re.search(state + r"\{(?!3,2,1,0)", result(loop))
    kernels = [i for i in body if i.opcode == "custom-call" and "kda_step" in i.name]
    assert len(kernels) == 6 and all("output_to_operand_aliasing={{1}: (5, {})}" in i.line for i in kernels)
    assert not any(i.opcode == "custom-call" and "kda_chunk" in i.name for i in body)  # the chunk kernel is the prompt pass's
    assert len(re.findall(r"%kda_chunk_l2048_c128_h32_d128[.\d]* = ", text)) == 6 and len(re.findall(r"%kda_step_b128_h32_d128[.\d]* = ", text)) == 6
    # the prompt pass shapes q, k and v inside the chunk kernel (PR 52): each call takes the three raw projections of a chunk of two rows
    # and the three tap tables, and under ``kda/conv`` nothing is left that is larger than the windows (the prompt pass's tails
    # ``[2,3,4096]`` a chunk; a step's ``[128,4,4096]``): no convolution, silu or l2 norm of a ``[2,2048,4096]`` array in XLA
    instructions = [i for ins_list in parse_hlo_computations(text).values() for i in ins_list]
    chunk_calls = [i for i in instructions if i.opcode == "custom-call" and i.name.startswith("kda_chunk_l2048_c128_h32_d128")]
    assert len(chunk_calls) == 6
    for call in chunk_calls:
        operands = re.search(r"operand_layout_constraints=\{([^=]*)\}, ", call.line).group(1)
        assert len(re.findall(r"bf16\[2,2048,4096\]", operands)) == 3 and len(re.findall(r"f32\[3,4,4096\]", operands)) == 1, call.line[:600]
    for ins in instructions:
        if "kda/conv" in ins.line and ins.opcode not in ("parameter", "get-tuple-element", "tuple", "bitcast"):
            assert _elements(_result(ins)) <= 128 * 4 * 4096, ins.line[:300]
    for ins in body:
        if re.search(state, result(ins)):
            assert ins.opcode in ("custom-call", "get-tuple-element", "tuple", "parameter", "bitcast"), ins.line[:300]
            assert not re.search(state + r"\{(?!3,2,1,0)", result(ins)), ins.line[:300]
    assert not re.search(r"bf16\[128,32,128,128\]", text) and not re.search(r"bf16\[\d+,\d+,32,128,128\]", text)
    # a step's experts: the grouped kernels on its 384 rows, in the loop; the weights as the arguments hold them
    assert any(i.opcode == "custom-call" and "moe_experts_prefill_m384_k2560_n768" in i.name for i in body)
    assert not re.search(r"bf16\[128,2560,768\]\{(?!2,1,0)", text) and not re.search(r"bf16\[128,768,2560\]\{(?!2,1,0)", text)


# ------------------------------------------ the MLP's exact GELU: evaluated once a layer and kept


# ------------------------------------------ dots3-note: latent attention that chooses its keys, at the cell's sizes


DOTS3_TOKENS, DOTS3_CHUNK, DOTS3_TOPK, DOTS3_WINDOW, DOTS3_HEADS_A_PASS = 32768, 2048, 2048, 513, 8


@pytest.mark.parametrize("kernel", ["index_scores", "select", "masked_flash", "window_flash"])
def test_the_sparse_latent_attentions_kernels_lower(one_chip, mosaic, kernel):
    """The four kernels of ``ops/dsa.py`` at the shapes of ``dots3-ep8-decode-b4-p32k`` (one row of 32 768 tokens, a
    chunk of 2048 queries, 8 heads a pass): a one-lane slice broadcast over a tile, a bitcast, an int8 tile written
    into an aliased buffer at a prefetched offset and a (1024, 1024) int8 mask tile are what interpret mode cannot refuse."""
    from perceiver_io_tpu.ops import dsa

    n, h = DOTS3_TOKENS, DOTS3_HEADS_A_PASS
    shape = lambda dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)  # noqa: E731
    if kernel == "index_scores":
        text = _compile(lambda q, k, w, first: dsa.index_scores(q, k, w, 64, first), shape((1, DOTS3_CHUNK, 64 * 128)), shape((1, n, 128)),
                        shape((1, DOTS3_CHUNK, 64), jnp.float32), shape((), jnp.int32))
        name = dsa.index_scores_kernel_name(DOTS3_CHUNK, n, 64)
    elif kernel == "select":
        text = _compile(lambda mask, scores, first: dsa.select_mask_into(mask, scores, DOTS3_TOPK, first), shape((1, n, n), jnp.int8),
                        shape((1, DOTS3_CHUNK, n), jnp.float32), shape((), jnp.int32))
        name = dsa.select_kernel_name(DOTS3_CHUNK, n, DOTS3_TOPK)
    elif kernel == "masked_flash":
        text = _compile(lambda a, b, c, d, m: dsa.flash_attention_mla_masked(a, b, c, d, m, h, sm_scale=192 ** -0.5), shape((1, n, h * 128)),
                        shape((1, n, h * 64)), shape((1, n, h * 256)), shape((1, n, 64)), shape((1, n, n), jnp.int8))
        name = dsa.masked_flash_kernel_name(n, h)
    else:
        text = _compile(lambda q, a, b, v: dsa.flash_attention_mla_window(q, a, b, v, h, DOTS3_WINDOW, sm_scale=0.0625), shape((1, n, h * 256)),
                        shape((1, n, h * 128)), shape((1, n, h * 128)), shape((1, n, h * 128)))
        name = dsa.window_flash_kernel_name(n, h, DOTS3_WINDOW)
    assert "tpu_custom_call" in text and name in text


def test_the_dots3_cells_generator_fits_the_chip_and_carries_three_cache_kinds_in_place(one_chip, mosaic, monkeypatch):
    """``dots3-ep8-decode-b4-p32k`` as the benchmark builds it (4.09B bfloat16 parameters, 4 prompts of 32 768 tokens,
    256 new tokens), compiled for a described v5e: under the 14.9 GB the other cells are held to (14.56 GB here at 8
    heads a pass); the four kernels of the prompt pass each in one geometry and none of them in the decode loop; the
    loop carries two latent caches ``bf16[4,33024,576]``, two index caches ``bf16[4,33024,128]`` and three rings
    ``bf16[4,544,1088]`` row-major, written by ``dynamic-update-slice`` in place, and nothing in its body copies or turns
    one; the selection's mask is never an XLA array of more than a chunk's scores beside it (no ``[1,32768,32768]``
    float array anywhere); a step selects with one ``top-k`` / sort a full layer and gathers 2048 rows of 576."""
    import re

    compiled = _cell_generator("dots3-ep8-decode-b4-p32k", "dots3", one_chip, monkeypatch)
    m = compiled.memory_analysis()
    assert 8.17e9 < m.argument_size_in_bytes < 8.18e9  # the weights and the prompts
    total = _device_bytes(compiled)
    assert total < 14.9e9, f"{total / 1e9:.2f} GB"
    text = compiled.as_text()
    assert set(re.findall(r"dsa_index_scores_q\d+_kv\d+_h\d+", text)) == {"dsa_index_scores_q2048_kv32768_h64"}
    assert set(re.findall(r"dsa_select_q\d+_kv\d+_k\d+", text)) == {"dsa_select_q2048_kv32768_k2048"}
    assert set(re.findall(r"flash_mla_masked_fwd_q\d+_kv\d+_h\d+", text)) == {"flash_mla_masked_fwd_q32768_kv32768_h8"}
    assert set(re.findall(r"flash_mla_window_fwd_q\d+_kv\d+_h\d+_w\d+", text)) == {"flash_mla_window_fwd_q32768_kv32768_h8_w513"}
    assert not re.search(r"(f32|bf16|s32)\[1,32768,32768\]", text)  # the score matrix of a row is never whole
    latent, index, ring = r"bf16\[4,33024,576\]", r"bf16\[4,33024,128\]", r"bf16\[4,544,1088\]"
    result = lambda ins: ins.line.split(" = ", 1)[1].split(f" {ins.opcode}(", 1)[0]  # noqa: E731
    loop, body = _loop_around(text, lambda loop, inside: re.search(ring, result(loop)))  # the decode loop: the one that carries a ring
    for shape, count in ((latent, 2), (index, 2), (ring, 3)):
        assert len(re.findall(shape + r"\{2,1,0[:}]", result(loop))) == count and not re.search(shape + r"\{(?!2,1,0)", result(loop))
        moved = [i for i in body if i.opcode in ("copy", "transpose") and re.search(shape, result(i))]
        assert not moved, [i.name for i in moved]
    assert not any(i.opcode == "custom-call" and ("dsa_" in i.name or "flash_mla" in i.name) for i in body)  # the prompt pass's kernels


@pytest.mark.parametrize("mesh_shape", [None, (2, 2)], ids=["one_chip", "data_x_fsdp"])
def test_mlp_gelu_is_not_expanded_again_inside_the_gemms(four_chips, one_chip, mesh_shape):
    """An ``MLP`` with its residual, forward and backward, at the hidden shape
    of ``ar16k-train-b32`` ([32, 1024, 512] bfloat16, widening 4), compiled
    for a described v5e. Left to autodiff XLA expands ``erfc`` again on the
    input of the forward ``dense_2``, ``dW2`` and ``dy W2^T`` GEMMs (three
    fusions with an ``exponential`` and two ``divide``s of the hidden shape,
    each bound by the VPU: PERF.md 6, PR 35). Under ``core.modules.gelu_exact``
    ``erfc`` is expanded once, in ``dense_1``'s epilogue, which hands on ``h``
    and ``erfc`` in bfloat16; the ``dy W2^T`` GEMM holds the one exponential
    of ``erfc``'s derivative and no divide, the other two GEMMs none, and no
    float32 array of the hidden shape lies between fusions: the pin that keeps
    a later JAX or XLA from re-expanding in silence. The same on a data x
    fsdp mesh of the four chips (rows over both axes, the kernels' rows over
    fsdp, as ``parallel.mesh`` places them): the rule is plain XLA, GSPMD
    partitions it, and each chip holds a quarter of the rows."""
    import importlib.util
    import os

    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from perceiver_io_tpu.core.modules import MLP

    spec = importlib.util.spec_from_file_location(
        "step_hlo", os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "tools", "step_hlo.py"))
    step_hlo = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(step_hlo)

    mlp = MLP(num_channels=CHANNELS, widening_factor=4, dtype=jnp.bfloat16)
    rows = 32
    if mesh_shape is None:
        batch, weights = one_chip, lambda leaf: one_chip
    else:
        mesh = Mesh(np.asarray(four_chips).reshape(mesh_shape), ("data", "fsdp"))
        batch = NamedSharding(mesh, P(("data", "fsdp")))
        weights = lambda leaf: NamedSharding(mesh, P("fsdp") if leaf.ndim == 2 else P())  # noqa: E731
        rows //= 4
    x = jax.ShapeDtypeStruct((32, LATENTS, CHANNELS), jnp.bfloat16, sharding=batch)
    params = jax.eval_shape(lambda: mlp.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, CHANNELS), jnp.bfloat16)))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=weights(s)), params)

    def loss(params, x):
        return (x + mlp.apply(params, x)).astype(jnp.float32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1)), params, x)
    hidden = f"[{rows},{LATENTS},{4 * CHANNELS}]"
    holding = [r for r in step_hlo.entry_fusions(text, "f32" + hidden) if r["exponential"]]
    assert len(holding) == 2, [(r["name"], r["op_name"]) for r in holding]
    forward, backward = sorted(holding, key=lambda r: "transpose" in r["op_name"])
    assert forward["op_name"].endswith("dense_1/dot_general") and (forward["exponential"], forward["divide"]) == (1, 2)
    assert forward["shapes"] == ["bf16" + hidden] * 2  # h and erfc, as the barrier holds them
    assert "transpose" in backward["op_name"] and "/mlp/" in backward["op_name"]  # the ``dy W2^T`` GEMM, named by its root
    assert (backward["exponential"], backward["divide"]) == (1, 0)
    assert step_hlo.entry_buffers(text, "f32" + hidden) == []


def test_chip_smoke_imports_on_the_cpu_and_keeps_the_benchmarks_widths():
    """``chip_smoke.py`` carries ``kernel_smoke`` and ``flagship_config`` itself
    (``bench.py`` is gone), imports where JAX has only the CPU, and its flagship
    is the benchmark's ``perceiver-ar-small-16k`` field for field."""
    import dataclasses
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location("chip_smoke", os.path.join(root, "chip_smoke.py"))
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    assert "bench" not in sys.modules and not hasattr(chip_smoke, "bench")
    assert callable(chip_smoke.kernel_smoke)

    config = chip_smoke.flagship_config(chip_smoke.SEQ_LEN, chip_smoke.LATENTS)
    with open(os.path.join(root, "benchmarks", "configs", "perceiver-ar-small-16k.json")) as f:
        published = json.load(f)
    fields = {f.name for f in dataclasses.fields(config)}
    held = {k: v for k, v in published.items() if k in fields}
    assert {"max_seq_len", "max_latents", "num_channels", "num_heads", "num_self_attention_layers",
            "cross_attention_dropout", "vocab_size"} <= set(held)
    assert {k: getattr(config, k) for k in held} == held
