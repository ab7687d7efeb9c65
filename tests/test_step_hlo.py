"""``tools/step_hlo.py``'s parser on a stored snippet of the 16k step's
module as the TPU compiler printed it for a described v5e (PR 34's tree: the
forward ``dense_2`` GEMM of layer 6 with the exact GELU expanded in a nested
fusion on its input, and layer 3's plain ``dW1`` GEMM). The compile itself
needs the TPU compiler and two minutes, and is the tool's, not a test's."""

import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def step_hlo():
    spec = importlib.util.spec_from_file_location("step_hlo", os.path.join(HERE, "..", "tools", "step_hlo.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def text():
    with open(os.path.join(HERE, "data", "step_hlo_snippet.txt")) as f:
        return f.read()


def test_entry_fusions_by_name_scope_cycles_and_counts(step_hlo, text):
    gelu, plain = step_hlo.entry_fusions(text, "f32[32,1024,2048]")
    assert (gelu["name"], gelu["stem"], gelu["kind"]) == ("convert_reduce_fusion.21", "convert_reduce_fusion", "kOutput")
    assert gelu["op_name"].endswith("layer_6/mlp/mlp/dense_2/dot_general") and "transpose" not in gelu["op_name"]
    assert gelu["shapes"] == ["f32[32,1024]", "f32[32,1024]", "bf16[32,1024,512]"]
    # the expansion sits in a kLoop fusion nested in the GEMM's: counted through the call
    assert (gelu["estimated_cycles"], gelu["exponential"], gelu["divide"]) == (1375476, 1, 2)
    assert (plain["name"], plain["stem"], plain["estimated_cycles"]) == ("fusion.1048", "fusion", 558751)
    assert (plain["exponential"], plain["divide"]) == (0, 0) and plain["op_name"].startswith("jit(train_step)/transpose(")
    # the one rule reads both: forward and backward of one module are one layer
    assert step_hlo.scope_column(plain["op_name"], 2) == "backward mlp | mlp/dense_1"
    assert step_hlo.scope_column(gelu["op_name"], 2) == "forward mlp | mlp/dense_2"


@pytest.mark.parametrize("shape,counts", [("f32[32,1024,2048]", (1, 2)), ("bf16[32,1024,2048]", (0, 0)), ("f32[32,1024,512]", (0, 0))])
def test_counts_are_of_the_given_shape_alone(step_hlo, text, shape, counts):
    gelu = step_hlo.entry_fusions(text, shape)[0]
    assert (gelu["exponential"], gelu["divide"]) == counts


def test_entry_buffers_and_computations(step_hlo, text):
    computations, entry = step_hlo.parse_entry(text)
    assert entry == "main.345" and len(computations) == 4
    assert [ins.name for ins in computations[entry]] == ["state_step.1", "convert_reduce_fusion.21", "fusion.1048", "copy.473", "tuple.1"]
    assert step_hlo.entry_buffers(text, "f32[32,8704,8,16,2]") == ["copy.473"]
    assert step_hlo.entry_buffers(text, "f32[32,1024,2048]") == []  # the float32 expansion never leaves its fusion
    assert step_hlo.entry_buffers(text, "s32[]") == ["tuple.1"]  # a parameter is no write


def test_text_without_an_entry_is_refused(step_hlo):
    with pytest.raises(ValueError, match="ENTRY"):
        step_hlo.parse_entry("%fused_computation (p: f32[2]) -> f32[2] {\n}\n")


def test_summary_by_phase_and_layer_and_text_without_metadata(step_hlo, text):
    # both GEMM fusions are the MLP's, one of each phase; the copy and the parameter have no op_name
    assert sorted(row[:3] for row in step_hlo.scopes_summary(text) if row[1] == "mlp") == [("backward", "mlp", 1), ("forward", "mlp", 1)]
    bare = step_hlo.without_metadata(text)
    assert "op_name" not in bare and "metadata={" not in bare and "fusion.1048" in bare
    moved = text.replace("layer_6/mlp/mlp/dense_2", "layer_6/renamed_scope/mlp/dense_2")
    assert moved != text and step_hlo.without_metadata(moved) == bare


def test_main_reads_a_stored_module(step_hlo, capsys):
    assert step_hlo.main(["--text", os.path.join(HERE, "data", "step_hlo_snippet.txt"), "--scope", "dense_2"]) == 0
    out = capsys.readouterr().out
    assert "convert_reduce_fusion.21" in out and "fusion.1048 " not in out.split("by name stem")[0]
    assert "1 of 2 entry fusions hold an exponential of f32[32,1024,2048]" in out
