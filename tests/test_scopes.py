"""The scope vocabulary (``obs/xplane.py``): the one rule from an ``op_name``
to (phase, layer, path), and the instruction-to-scope table of compiled
programs: a tiny Perceiver AR train step and a tiny decoder-only generator
under each attention / expert configuration, compiled here for the CPU.
What the table is joined with, a device trace, exists on the chip alone:
``benchmarks/tests/test_scopes.py`` holds that side on recorded rows."""

import jax
import jax.numpy as jnp
import pytest

from perceiver_io_tpu.analysis.graph import parse_hlo_computations
from perceiver_io_tpu.generation import GenerationConfig, make_generate_fn
from perceiver_io_tpu.models.text import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.models.text.decoder_lm import DecoderLanguageModel, DecoderLanguageModelConfig, YarnConfig
from perceiver_io_tpu.obs.xplane import LAYER_SCOPES, UNSCOPED, OpScope, instruction_scopes, op_scope, scope_of

MODEL = "jit(train_step)/jvp(CausalLanguageModel)/perceiver_ar/perceiver_ar._forward/perceiver_ar._attend"
BACK = MODEL.replace("jvp(CausalLanguageModel)", "transpose(jvp(CausalLanguageModel))")
PATH = "CausalLanguageModel/perceiver_ar/perceiver_ar._forward/perceiver_ar._attend"
CHUNK = "jit(fn)/prefill/prefill/chunk_io/while/body/closed_call/prefill/DecoderLanguageModel.ffn_layer/layer_2.feed_forward"


def _case_id(value):
    """A test id from an ``op_name``: its last scope part and its primitive."""
    return None if isinstance(value, OpScope) else "-".join(value.split(";")[0].split("/")[-2:])


RULE_CASES = [
    # forward and backward of one module are one layer
    (f"{MODEL}/self_attend/self_attention/layer_3/mlp/mlp/dense_1/dot_general",
     OpScope("forward", "mlp", f"{PATH}/self_attend/self_attention/layer_3/mlp/mlp/dense_1")),
    (f"{BACK}/self_attend/self_attention/layer_3/mlp/mlp/dense_1/dot_general",
     OpScope("backward", "mlp", f"{PATH}/self_attend/self_attention/layer_3/mlp/mlp/dense_1")),
    # a closed layer keeps what it holds: the MLP's own LayerNorm is the MLP's
    (f"{MODEL}/self_attend/self_attention/layer_3/mlp/mlp/LayerNorm_0/mul",
     OpScope("forward", "mlp", f"{PATH}/self_attend/self_attention/layer_3/mlp/mlp/LayerNorm_0")),
    # flax module names mark the projections and the norms; a scope inside the block wins over the block
    (f"{BACK}/cross_attend/cross_attention/cross_attn/attention/qkv_proj/k_proj/dot_general",
     OpScope("backward", "qkv_proj", f"{PATH}/cross_attend/cross_attention/cross_attn/attention/qkv_proj/k_proj")),
    (f"{MODEL}/cross_attend/cross_attention/cross_attn/attention/o_proj/add",
     OpScope("forward", "o_proj", f"{PATH}/cross_attend/cross_attention/cross_attn/attention/o_proj")),
    (f"{MODEL}/cross_attend/cross_attention/cross_attn/kv_concat/kv_norm/rsqrt",
     OpScope("forward", "norm", f"{PATH}/cross_attend/cross_attention/cross_attn/kv_concat/kv_norm")),
    (f"{MODEL}/self_attend/self_attention/layer_0/self_attn/attention/attention._packed_flash/rotary/rotary/cos",
     OpScope("forward", "rotary", f"{PATH}/self_attend/self_attention/layer_0/self_attn/attention/attention._packed_flash/rotary/rotary")),
    # a kernel's name scope is no layer: the kernel is its block's
    (f"{BACK}/cross_attend/cross_attention/cross_attn/attention/attention._packed_flash/flash_attention_packed/"
     "jit(_flash_packed)/flash_bwd_q1024_kv8704/pallas_call",
     OpScope("backward", "cross_attend", f"{PATH}/cross_attend/cross_attention/cross_attn/attention/attention._packed_flash/"
             "flash_attention_packed/flash_bwd_q1024_kv8704")),
    # a transform wraps the first scope opened under it
    ("jit(train_step)/jvp(loss)/jit(take_along_axis)/gather", OpScope("forward", "loss", "loss")),
    ("jit(train_step)/transpose(jvp(loss))/mul", OpScope("backward", "loss", "loss")),
    ("jit(train_step)/optimizer/jit(_adamw_update)/mul", OpScope("optimizer", "optimizer", "optimizer")),
    # remat and vmap wrappers, a loop's body: dropped
    ("jit(f)/transpose(jvp(checkpoint))/rematted_computation/self_attend/mlp/dot_general", OpScope("backward", "mlp", "self_attend/mlp")),
    ("jit(f)/vmap(jvp(embed))/checkpoint/input_adapter/add", OpScope("forward", "input_adapter", "embed/input_adapter")),
    ("jit(fn)/decode/while/body/closed_call/decode/CausalLanguageModel/perceiver_ar/perceiver_ar._decode_step/self_attend/"
     "self_attention/layer_1/self_attn/attention/decode_attend/dot_general",
     OpScope("decode", "self_attend", "decode/decode/CausalLanguageModel/perceiver_ar/perceiver_ar._decode_step/self_attend/"
             "self_attention/layer_1/self_attn/attention/decode_attend")),
    ("jit(fn)/decode/while/cond/lt", OpScope("decode", UNSCOPED, "decode")),
    # two-part scopes; a kernel inside an expert layer's pass loop; the loops' own writes
    (f"{CHUNK}/ffn/moe/experts/while/body/jit(grouped_matmul)/moe_experts_prefill_m1024_k2048_n6144/pallas_call",
     OpScope("prefill", "moe/experts", "prefill/prefill/chunk_io/prefill/DecoderLanguageModel.ffn_layer/layer_2.feed_forward/ffn/"
             "moe/experts/moe_experts_prefill_m1024_k2048_n6144")),
    (f"{CHUNK}/ffn/moe/experts/while/body/moe/combine/sort",
     OpScope("prefill", "moe/combine", "prefill/prefill/chunk_io/prefill/DecoderLanguageModel.ffn_layer/layer_2.feed_forward/ffn/"
             "moe/experts/moe/combine")),
    (f"{CHUNK}/ffn/moe/experts/while/body/moe/combine/jit(moe_combine)/moe_combine_t8192_r2560_h7168/pallas_call",
     OpScope("prefill", "moe/combine", "prefill/prefill/chunk_io/prefill/DecoderLanguageModel.ffn_layer/layer_2.feed_forward/ffn/"
             "moe/experts/moe/combine/moe_combine_t8192_r2560_h7168")),
    (f"{CHUNK}/ffn_norm/mul", OpScope("prefill", "norm", "prefill/prefill/chunk_io/prefill/DecoderLanguageModel.ffn_layer/"
                                      "layer_2.feed_forward/ffn_norm")),
    ("jit(fn)/prefill/prefill/chunk_io/while/body/dynamic_update_slice", OpScope("prefill", "chunk_io", "prefill/prefill/chunk_io")),
    # an attention layer of the decoder-only class is read whole: its q/k norms and projections are its own
    ("jit(fn)/decode/while/body/decode/spec/verify/DecoderLanguageModel.verify_step/layer_1.verify/attn.verify/attn/window/"
     "attn._project/k_norm/mul",
     OpScope("decode", "attn/window", "decode/decode/spec/verify/DecoderLanguageModel.verify_step/layer_1.verify/attn.verify/"
             "attn/window/attn._project/k_norm")),
    ("jit(fn)/decode/while/body/decode/DecoderLanguageModel.draft_step/mtp/block/block.verify/attn.verify/attn/full/kv_cache_write/scatter",
     OpScope("decode", "attn/full", "decode/decode/DecoderLanguageModel.draft_step/mtp/block/block.verify/attn.verify/attn/full/"
             "kv_cache_write")),
    # the engine's own phases are the generator's
    ("jit(step)/decode_paged/sample/argmax", OpScope("decode", "sample", "decode_paged/sample")),
    # XLA joins merged instructions' names: the first is read
    ("jit(fn)/decode/spec/accept/broadcast_in_dim;jit(fn)/decode/spec/rollback/reshape", OpScope("decode", "spec/accept", "decode/spec/accept")),
    # no scope at all
    ("jit(fn)/reshape", OpScope("", UNSCOPED, "")),
    ("fusion.12", OpScope("", UNSCOPED, "")),
]


@pytest.mark.parametrize("op_name,want", RULE_CASES, ids=[f"{i:02d}-{_case_id(c[0])}" for i, c in enumerate(RULE_CASES)])
def test_the_rule(op_name, want):
    assert op_scope(op_name) == want
    assert scope_of(op_name) == (want.path or UNSCOPED)


def test_vocabulary_is_what_the_rule_returns():
    for layer in LAYER_SCOPES:
        assert op_scope(f"jit(f)/{layer}/add").layer == layer


# ---------------------------------------------------------------- compiled programs

TRIVIAL = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast")
UNSCOPED_LIMIT = 0.02  # of the program's own instructions (those with an ``op_name``)
COMPILER_MADE_LIMIT = 0.05  # with the compiler's own added: copies and converts it hoists out of a loop


def assert_few_unscoped(table):
    rows = timed(table)
    bare = {name: row for name, row in rows.items() if row["layer"] == UNSCOPED}
    own = sorted((name, row["phase"], row["path"]) for name, row in bare.items() if not row["inherited"] and row["path"])
    assert len(own) < UNSCOPED_LIMIT * len(rows), (len(own), len(rows), own)
    assert len(bare) < COMPILER_MADE_LIMIT * len(rows), (len(bare), len(rows), sorted(bare))


@pytest.fixture(scope="module")
def fresh_compiles():
    """The persistent compilation cache off while this file's programs compile: its key leaves the locations out, so
    an executable cached before a scope was opened would come back with the names it was compiled with."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def timed(table):
    """The rows that can take device time: no container, no free instruction."""
    return {name: row for name, row in table.items() if not row["container"] and row["opcode"] not in TRIVIAL}


def reported_instructions(text):
    """Every instruction of the entry computation and of what a ``while``, ``conditional`` or ``call`` of those runs:
    not the inside of a fusion, a reducer or a comparator. Found from the text alone, not through the table's walk."""
    import re

    computations = parse_hlo_computations(text)
    todo, seen = [re.search(r"^ENTRY\s+%?([\w.\-]+)", text, re.M).group(1)], set()
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for ins in computations[name]:
            if ins.opcode in ("while", "conditional", "call"):
                todo += re.findall(r"(?:body|condition|to_apply|true_computation|false_computation)=%?([\w.\-]+)", ins.line)
                for branches in re.findall(r"branch_computations=\{([^}]*)\}", ins.line):
                    todo += [b.strip().lstrip("%") for b in branches.split(",")]
    return {ins.name for name in seen for ins in computations[name]}


@pytest.fixture(scope="module")
def train_table(fresh_compiles):
    from perceiver_io_tpu.training import TrainState, clm_loss_fn, make_optimizer
    from perceiver_io_tpu.training.loop import make_train_step

    seq, latents, keep = 48, 16, 16
    config = CausalLanguageModelConfig(vocab_size=40, max_seq_len=seq, max_latents=latents, num_channels=32, num_heads=4,
                                       num_self_attention_layers=2, num_self_attention_rotary_layers=1, cross_attention_dropout=0.5)
    model = CausalLanguageModel(config)
    ids = jnp.zeros((2, seq), jnp.int32)
    tx = make_optimizer(1e-3, gradient_clip=1.0, weight_decay=0.01)
    state = jax.eval_shape(lambda: TrainState.create(
        model.apply, model.init(jax.random.PRNGKey(0), ids, prefix_len=seq - latents), tx, jax.random.PRNGKey(1)))
    batch = {"input_ids": ids, "labels": ids, "pad_mask": None,
             "prefix_keep_idx": jnp.tile(jnp.arange(keep, dtype=jnp.int32), (2, 1))}
    text = make_train_step(clm_loss_fn(model.apply, max_latents=latents)).lower(state, batch).compile().as_text()
    return text, instruction_scopes(text)


def test_train_step_every_instruction_has_a_row(train_table):
    text, table = train_table
    assert reported_instructions(text) <= set(table)


def test_train_step_layers_and_phases(train_table):
    rows = timed(train_table[1])
    cells = {(row["phase"], row["layer"]) for row in rows.values()}
    # forward and backward of one module share a layer
    for layer in ("mlp", "qkv_proj", "o_proj", "norm", "rotary", "embed", "loss"):
        assert {("forward", layer), ("backward", layer)} <= cells, layer
    assert {phase for phase, _ in cells} <= {"forward", "backward", "optimizer", ""}
    # the optimizer's instructions are under ``optimizer``, and nothing else is
    named = [row for row in rows.values() if not row["inherited"]]
    assert sum(row["phase"] == "optimizer" for row in named) > 20
    assert all((row["phase"] == "optimizer") == ("optimizer" in row["path"].split("/")) for row in named)
    assert all(row["layer"] == "optimizer" for row in named if row["phase"] == "optimizer")


def test_train_step_unscoped_share(train_table):
    assert_few_unscoped(train_table[1])


def decoder_config(kind: str) -> DecoderLanguageModelConfig:
    base = dict(vocab_size=96, hidden_size=64, moe_intermediate_size=32, init_scale=0.3)
    if kind == "mla_sigmoid":  # latent attention, sigmoid-routed experts of which a share is held, a shared expert
        return DecoderLanguageModelConfig(
            **base, num_hidden_layers=3, first_k_dense_replace=1, intermediate_size=160, num_attention_heads=4, q_lora_rank=24,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16, n_held_experts=4,
            held_experts_start=4, num_experts_per_tok=2, n_group=4, topk_group=2, max_position_embeddings=64)
    gqa = dict(num_attention_heads=8, num_key_value_heads=2, head_dim=16, sliding_window=8, num_experts_per_tok=2,
               rope_theta=500000.0, max_position_embeddings=512,
               rope_scaling=YarnConfig(factor=4.0, beta_fast=32.0, beta_slow=1.0, original_max_position_embeddings=8,
                                       attention_factor=1.1386))
    if kind == "gqa_softmax":  # window and full grouped-query layers, softmax-routed experts all held
        return DecoderLanguageModelConfig(
            **base, **gqa, num_hidden_layers=4, first_k_dense_replace=0,
            layer_types=("sliding_attention", "sliding_attention", "sliding_attention", "full_attention"),
            n_routed_experts=8, n_shared_experts=0, n_group=1, topk_group=1, scoring_func="softmax")
    # the multi-token-prediction module: the generator's speculative loop
    return DecoderLanguageModelConfig(
        **base, **gqa, num_hidden_layers=3, first_k_dense_replace=1, intermediate_size=160,
        layer_types=("sliding_attention", "full_attention", "sliding_attention"), n_routed_experts=8, n_held_experts=4,
        n_group=1, topk_group=1, qk_norm=True, num_nextn_predict_layers=1)


@pytest.fixture(scope="module", params=["mla_sigmoid", "gqa_softmax", "mtp_speculative"])
def generator_table(request, fresh_compiles):
    config = decoder_config(request.param)
    model = DecoderLanguageModel(config)
    ids = jnp.zeros((4, 16), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids, drafts=bool(config.num_nextn_predict_layers)))
    generate = make_generate_fn(model, config=GenerationConfig(max_new_tokens=6), cache_dtype=jnp.bfloat16)
    text = generate.lower(params, ids).compile().as_text()
    return request.param, text, instruction_scopes(text)


def test_generator_every_instruction_has_a_row(generator_table):
    _, text, table = generator_table
    assert reported_instructions(text) <= set(table)
    assert any(row["container"] and row["opcode"] == "while" for row in table.values())


def test_generator_prefill_and_decode_partition_it(generator_table):
    kind, _, table = generator_table
    rows = timed(table)
    assert all(row["phase"] in ("prefill", "decode") for row in rows.values() if not row["inherited"]), \
        sorted((name, row["path"]) for name, row in rows.items() if not row["inherited"] and row["phase"] not in ("prefill", "decode"))
    cells = {(row["phase"], row["layer"]) for row in rows.values()}
    attention = {"mla_sigmoid": {("prefill", "mla/expand"), ("decode", "mla/absorb")},
                 "gqa_softmax": {(p, f"attn/{a}") for p in ("prefill", "decode") for a in ("window", "full")},
                 "mtp_speculative": {(p, f"attn/{a}") for p in ("prefill", "decode") for a in ("window", "full")}}[kind]
    shared = {("prefill", "embed"), ("decode", "embed"), ("prefill", "logits"), ("decode", "logits"), ("prefill", "norm"),
              ("decode", "norm"), ("prefill", "moe/route"), ("decode", "moe/route"), ("prefill", "moe/experts"),
              ("decode", "moe/experts"), ("prefill", "chunk_io"), ("decode", "residual"), ("decode", "loop_io")}
    assert attention | shared <= cells, sorted((attention | shared) - cells)
    if kind != "mla_sigmoid":  # the grouped-query caches are filled by writes of their own
        assert ("prefill", "cache_fill") in cells
    if kind != "gqa_softmax":
        assert {("prefill", "dense_mlp"), ("decode", "dense_mlp")} <= cells
    if kind != "mtp_speculative":  # the speculative loop takes the argmax inside ``spec/accept``
        assert ("decode", "sample") in cells
    else:
        assert {("decode", "spec/accept"), ("decode", "mtp/draft"), ("prefill", "mtp/project")} <= cells


def test_generator_unscoped_share(generator_table):
    assert_few_unscoped(generator_table[2])


def test_a_table_without_an_entry_is_refused():
    with pytest.raises(ValueError, match="ENTRY"):
        instruction_scopes("%fused_computation (p: f32[2]) -> f32[2] {\n}\n")


SNIPPET = """HloModule jit_f, is_scheduled=true

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(f)/decode/while/body/decode/self_attend/mlp/mul"}
  ROOT %s = f32[8]{0} scatter(%p, %m, %m), to_apply=%region
}

%body (c: (s32[], f32[8])) -> (s32[], f32[8]) {
  %c = (s32[], f32[8]{0}) parameter(0)
  %x = f32[8]{0} get-tuple-element(%c), index=1
  %copy.1 = f32[8]{0} copy(%x)
  %fusion.1 = f32[8]{0} fusion(%copy.1), kind=kCustom, calls=%fused_computation
  %copy.2 = f32[8]{0} copy(%fusion.1)
  %i = s32[] get-tuple-element(%c), index=0
  ROOT %t = (s32[], f32[8]{0}) tuple(%i, %copy.2)
}

%cond (c: (s32[], f32[8])) -> pred[] {
  %c.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt = pred[] constant(true), metadata={op_name="jit(f)/decode/while/cond/lt"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %e = f32[8]{0} exponential(%a), metadata={op_name="jit(f)/prefill/embed/exp"}
  %n = f32[8]{0} negate(%a), metadata={op_name="jit(f)/prefill/logits/neg"}
  %copy.3 = f32[8]{0} copy(%a)
  %both = f32[8]{0} add(%copy.3, %e), metadata={op_name="jit(f)/prefill/embed/add"}
  %also = f32[8]{0} add(%copy.3, %n), metadata={op_name="jit(f)/prefill/logits/add"}
  %zero = s32[] constant(0)
  %init = (s32[], f32[8]{0}) tuple(%zero, %both)
  %while.1 = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(f)/decode/while"}
  ROOT %out = f32[8]{0} get-tuple-element(%while.1), index=1
}
"""


def test_inheritance_containers_and_fusions_on_a_snippet():
    table = instruction_scopes(SNIPPET)
    assert set(table) >= {"while.1", "copy.1", "copy.2", "copy.3", "fusion.1", "lt", "t"} and "m" not in table and "s" not in table
    assert table["while.1"]["container"] and not table["fusion.1"]["container"]
    # an unnamed fusion whose root has no name takes the one scope that all it holds agree on
    assert (table["fusion.1"]["phase"], table["fusion.1"]["layer"], table["fusion.1"]["inherited"]) == ("decode", "mlp", False)
    # a compiler-made copy takes the scope of what it feeds: through a chain, and a body's root from its loop
    assert (table["copy.1"]["phase"], table["copy.1"]["layer"], table["copy.1"]["inherited"]) == ("decode", "mlp", True)
    assert table["copy.1"]["path"] == table["copy.2"]["path"] == "decode/decode/self_attend/mlp"  # a layer comes with its path
    assert table["copy.3"]["path"] == ""
    assert (table["t"]["phase"], table["t"]["layer"]) == ("decode", UNSCOPED)
    # where what it feeds gives no layer (the loop's carry), it takes the layer of what it reads
    assert (table["copy.2"]["phase"], table["copy.2"]["layer"], table["copy.2"]["inherited"]) == ("decode", "mlp", True)
    # users that disagree on the layer leave it open and still give the phase
    assert (table["copy.3"]["phase"], table["copy.3"]["layer"], table["copy.3"]["inherited"]) == ("prefill", UNSCOPED, True)
    assert (table["e"]["phase"], table["e"]["layer"], table["e"]["inherited"]) == ("prefill", "embed", False)
