"""A cell's program compiled for a described v5e, read fusion by fusion.

    python tools/step_hlo.py --workload ar16k-train-b32 --shape 'f32[32,1024,2048]'
    python tools/step_hlo.py --workload mellum2-pp4-decode-b32 --save generator.hlo
    python tools/step_hlo.py --text step.hlo --shape 'f32[32,1024,2048]' --scope mlp
    python tools/step_hlo.py --same parent.hlo change.hlo

No chip: the TPU compiler installed here compiles the cell's one program (a
train cell's optimizer step, a decode cell's generator: what
``benchmarks/lib/scopes.py::lower_program`` builds for the per-layer metrics
that read the scopes, so the tool and those metrics cannot disagree; flash on,
the Pallas kernels lowered for Mosaic) for
``topologies.get_topology_desc("v5e:2x2")``'s first chip, and this prints
every fusion of the entry computation: its name stem (the device trace groups
operations by it), XLA's kind, the phase, layer and path its ``op_name``
gives (``obs.xplane.op_scope``, the one rule), its result shapes,
``estimated_cycles``, and how many ``exponential`` and ``divide`` instructions
of ``--shape`` it holds (nested fusions included); then the whole module's
instructions by phase and layer (``obs.xplane.instruction_scopes``). That is
how PR 31 named ``fusion.9`` and how PR 35 found the exact GELU expanded
inside three GEMM fusions a layer. On the chip the same table is joined with
the trace: a traced benchmark run prints device time by phase and layer.

``--same A B`` compares two stored module texts once metadata is stripped
(every ``metadata={...}``, the stack-frame tables, and the debug locations
inside each Pallas kernel's serialized Mosaic module): what a change of
named scopes alone must leave equal.

``estimated_cycles`` is XLA's own cost model: it matched the chip on the
GELU-bound GEMMs (1 375 476 for 1.34 ms) and overstates others; a time comes
from a chip run alone. ``--text`` reads a stored module (``--save`` writes
one) in the compile's place. Nothing here is imported by code a cell runs.
"""

from __future__ import annotations

import argparse
import collections
import importlib
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # the parser, the scope rule and the cells' builder are the repository's own
    sys.path.insert(0, REPO)

_SHAPE = re.compile(r"\b([a-z]+\d*\[[\d,]*\])")
_COUNTED = ("exponential", "divide")


def parse_entry(text: str) -> tuple[dict, str]:
    """``analysis/graph.py::parse_hlo_computations`` of an HLO module's text, and the entry computation's name."""
    from perceiver_io_tpu.analysis.graph import parse_hlo_computations

    entry = re.search(r"^ENTRY\s+%?([\w.\-]+)", text, re.M)
    if entry is None:
        raise ValueError("the text holds no ENTRY computation")
    return parse_hlo_computations(text), entry.group(1)


def _result(ins) -> str:
    """The result type of a parsed instruction: what stands between ``=`` and the opcode."""
    return ins.line.split(" = ", 1)[1].split(f" {ins.opcode}(", 1)[0]


def _shapes(result_type: str) -> list:
    """``bf16[32,1024,512]{2,1,0:T(8,128)(2,1)}`` -> ``['bf16[32,1024,512]']``; a tuple gives each."""
    return _SHAPE.findall(result_type)


def count_ops(computations: dict, name: str, shape: str) -> dict:
    """``exponential`` and ``divide`` instructions of result ``shape`` in a
    computation and in the fusions it calls."""
    counts = collections.Counter()
    for ins in computations.get(name, ()):
        if ins.opcode in _COUNTED and shape in _shapes(_result(ins)):
            counts[ins.opcode] += 1
        if ins.opcode == "fusion":
            counts.update(count_ops(computations, _field(ins.line, r"calls=%?([\w.\-]+)"), shape))
    return {op: counts[op] for op in _COUNTED}


def _field(line: str, pattern: str, default: str = "") -> str:
    found = re.search(pattern, line)
    return found.group(1) if found else default


def stem(name: str) -> str:
    """``convert_reduce_fusion.21`` -> ``convert_reduce_fusion``: the device trace's group."""
    return re.sub(r"(\.\d+)+$", "", name)


def entry_fusions(text: str, shape: str) -> list:
    """One row per fusion of the entry computation, in program order."""
    computations, entry = parse_entry(text)
    rows = []
    for ins in computations[entry]:
        if ins.opcode != "fusion":
            continue
        cycles = _field(ins.line, r'"estimated_cycles":"(\d+)"')
        rows.append({
            "name": ins.name, "stem": stem(ins.name), "kind": _field(ins.line, r"kind=(\w+)"),
            "op_name": _field(ins.line, r'op_name="([^"]*)"'), "shapes": _shapes(_result(ins)),
            "estimated_cycles": int(cycles) if cycles else None,
            **count_ops(computations, _field(ins.line, r"calls=%?([\w.\-]+)"), shape),
        })
    return rows


def entry_buffers(text: str, shape: str) -> list:
    """Names of the entry computation's instructions whose result holds an
    array of ``shape``: what is written to memory between fusions."""
    computations, entry = parse_entry(text)
    return [ins.name for ins in computations[entry] if shape in _shapes(_result(ins)) and ins.opcode != "parameter"]


def compile_cell(workload: str) -> "jax.stages.Compiled":
    """The cell's program compiled for one described v5e chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.lib.scopes import lower_program
    from benchmarks.run import load_json

    fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")
    # a compile for a described chip is written to the persistent cache but cannot be read back without the chip
    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    cell = load_json("workloads", workload)
    config = load_json("configs", cell["config"])
    family = importlib.import_module(f"benchmarks.families.{config['family']}").Family(config)
    # the backend here is the CPU: lower the kernels for Mosaic all the same
    fa._interpret_default = lambda: False
    importlib.import_module("perceiver_io_tpu.ops.grouped_matmul")._interpret_default = lambda: False
    with fa.default_flash(True):
        return lower_program(cell, family, sharding=chip).compile()


def scope_column(op_name: str, parts: int = 3) -> str:
    """``phase layer | the path's last parts`` by the one rule (``obs.xplane.op_scope``)."""
    from perceiver_io_tpu.obs.xplane import op_scope

    scope = op_scope(op_name)
    return f"{scope.phase or '-'} {scope.layer} | {'/'.join(scope.path.split('/')[-parts:])}"


def scopes_summary(text: str) -> list:
    """``[(phase, layer, instructions, of them inherited)]`` over the module's
    instructions that can take device time, most first."""
    from perceiver_io_tpu.obs.xplane import instruction_scopes

    counts = collections.Counter()
    inherited = collections.Counter()
    for row in instruction_scopes(text).values():
        if row["container"] or row["opcode"] in ("parameter", "constant", "get-tuple-element", "tuple", "bitcast"):
            continue
        counts[(row["phase"] or "-", row["layer"])] += 1
        inherited[(row["phase"] or "-", row["layer"])] += row["inherited"]
    return [(phase, layer, n, inherited[(phase, layer)]) for (phase, layer), n in counts.most_common()]


_METADATA = re.compile(r",? ?(?<![A-Za-z_])metadata=\{[^{}]*\}")
_FRAME_TABLES = re.compile(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n(.+\n)*")
_KERNEL_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


def without_metadata(text: str) -> str:
    """An optimised module's text with everything a named scope can move taken
    out: each instruction's ``metadata={...}``, the stack-frame tables, and,
    inside each Pallas kernel's serialized Mosaic module, the debug locations
    (the module is decoded and printed without them)."""
    import base64
    import hashlib

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def kernel(found):
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True  # the serialized form is in a dialect of its own
        with ctx:
            body = ir.Module.parse(base64.b64decode(found.group(1))).operation.get_asm(enable_debug_info=False)
        return f'"body":"<mosaic module without locations, sha256 {hashlib.sha256(body.encode()).hexdigest()[:16]}>"'

    return _KERNEL_BODY.sub(kernel, _FRAME_TABLES.sub("\n", _METADATA.sub("", text)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", help="a cell: benchmarks/workloads/<name>.json")
    ap.add_argument("--text", help="read this stored module text; compile nothing")
    ap.add_argument("--save", help="write the compiled module's text here")
    ap.add_argument("--shape", default="f32[32,1024,2048]", help="count exponential/divide instructions of this result shape")
    ap.add_argument("--scope", default="", help="print only fusions whose op_name holds this")
    ap.add_argument("--json", help="write the rows here as JSON")
    ap.add_argument("--same", nargs=2, metavar="TEXT", help="compare two stored module texts once metadata is stripped")
    args = ap.parse_args(argv)
    if args.same:
        a, b = (without_metadata(open(path).read()).splitlines() for path in args.same)
        differ = sum(x != y for x, y in zip(a, b)) + abs(len(a) - len(b))
        print(f"{args.same[0]} and {args.same[1]}: {len(a)} and {len(b)} lines without metadata, {differ} differ")
        return 1 if differ else 0
    if bool(args.workload) == bool(args.text):
        ap.error("give --workload or --text")
    if args.text:
        with open(args.text) as f:
            text = f.read()
    else:
        compiled = compile_cell(args.workload)
        text = compiled.as_text()
        print(f"memory_analysis: {compiled.memory_analysis()}")
        if args.save:
            with open(args.save, "w") as f:
                f.write(text)
    rows = entry_fusions(text, args.shape)
    shown = [r for r in rows if args.scope in r["op_name"]]
    print(f"{'fusion':42s} {'kind':8s} {'cycles':>9s} {'exp':>3s} {'div':>3s}  scope | shapes")
    for r in shown:
        print(f"{r['name']:42s} {r['kind']:8s} {r['estimated_cycles'] or 0:9d} {r['exponential']:3d} {r['divide']:3d}  "
              f"{scope_column(r['op_name'])} | {' '.join(r['shapes'])}")
    groups = collections.defaultdict(lambda: [0, 0])
    for r in rows:
        groups[r["stem"]][0] += 1
        groups[r["stem"]][1] += r["estimated_cycles"] or 0
    print("\nby name stem: fusions, estimated_cycles")
    for name, (n, cycles) in sorted(groups.items(), key=lambda kv: -kv[1][1]):
        print(f"  {name:40s} {n:4d} {cycles:12d}")
    holding = [r for r in rows if r["exponential"]]
    print(f"\n{len(holding)} of {len(rows)} entry fusions hold an exponential of {args.shape}: "
          f"{sum(r['exponential'] for r in holding)} exponentials, {sum(r['divide'] for r in holding)} divides, "
          f"{sum(r['estimated_cycles'] or 0 for r in holding)} estimated cycles")
    print(f"{len(entry_buffers(text, args.shape))} entry instructions write an array of {args.shape}")
    print("\nthe module's instructions by phase and layer (of them placed by inheritance)")
    for phase, layer, n, inherited in scopes_summary(text):
        print(f"  {phase:10s} {layer:16s} {n:5d} ({inherited})")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
