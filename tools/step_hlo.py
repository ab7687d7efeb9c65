"""A train cell's step compiled for a described v5e, read fusion by fusion.

    python tools/step_hlo.py --workload ar16k-train-b32 --shape 'f32[32,1024,2048]'
    python tools/step_hlo.py --text step.hlo --shape 'f32[32,1024,2048]' --scope mlp

No chip: the TPU compiler installed here compiles the cell's optimizer step
(the program ``benchmarks/drivers/train.py`` builds: same model, optimizer,
``make_train_step`` and batch shapes; flash on, the Pallas kernels lowered
for Mosaic) for ``topologies.get_topology_desc("v5e:2x2")``'s first chip, and
this prints every fusion of the entry computation: its name stem (the
device trace groups operations by it), XLA's kind, the ``op_name`` scope it
came from, its result shapes, ``estimated_cycles``, and how many
``exponential`` and ``divide`` instructions of ``--shape`` it holds (nested
fusions included). That is how PR 31 named ``fusion.9`` and how PR 35 found
the exact GELU expanded inside three GEMM fusions a layer.

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

_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(")
_SHAPE = re.compile(r"\b([a-z]+\d*\[[\d,]*\])")
_COUNTED = ("exponential", "divide")


def parse_computations(text: str) -> tuple[dict, str]:
    """``{computation: [(name, result type, opcode, line)]}`` of an HLO
    module's text, and the entry computation's name."""
    computations, entry, current = {}, None, None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = computations.setdefault(head.group(2), [])
            entry = head.group(2) if head.group(1) else entry
        elif line.startswith("}"):
            current = None
        elif current is not None:
            inst = _INSTRUCTION.match(line)
            if inst:
                current.append((inst.group(1), inst.group(2), inst.group(3), line))
    if entry is None:
        raise ValueError("the text holds no ENTRY computation")
    return computations, entry


def _shapes(result_type: str) -> list:
    """``bf16[32,1024,512]{2,1,0:T(8,128)(2,1)}`` -> ``['bf16[32,1024,512]']``; a tuple gives each."""
    return _SHAPE.findall(result_type)


def count_ops(computations: dict, name: str, shape: str) -> dict:
    """``exponential`` and ``divide`` instructions of result ``shape`` in a
    computation and in the fusions it calls."""
    counts = collections.Counter()
    for _, result, opcode, line in computations.get(name, ()):
        if opcode in _COUNTED and shape in _shapes(result):
            counts[opcode] += 1
        if opcode == "fusion":
            counts.update(count_ops(computations, _field(line, r"calls=%?([\w.\-]+)"), shape))
    return {op: counts[op] for op in _COUNTED}


def _field(line: str, pattern: str, default: str = "") -> str:
    found = re.search(pattern, line)
    return found.group(1) if found else default


def stem(name: str) -> str:
    """``convert_reduce_fusion.21`` -> ``convert_reduce_fusion``: the device trace's group."""
    return re.sub(r"(\.\d+)+$", "", name)


def entry_fusions(text: str, shape: str) -> list:
    """One row per fusion of the entry computation, in program order."""
    computations, entry = parse_computations(text)
    rows = []
    for name, result, opcode, line in computations[entry]:
        if opcode != "fusion":
            continue
        cycles = _field(line, r'"estimated_cycles":"(\d+)"')
        rows.append({
            "name": name, "stem": stem(name), "kind": _field(line, r"kind=(\w+)"),
            "op_name": _field(line, r'op_name="([^"]*)"'), "shapes": _shapes(result),
            "estimated_cycles": int(cycles) if cycles else None,
            **count_ops(computations, _field(line, r"calls=%?([\w.\-]+)"), shape),
        })
    return rows


def entry_buffers(text: str, shape: str) -> list:
    """Names of the entry computation's instructions whose result holds an
    array of ``shape``: what is written to memory between fusions."""
    computations, entry = parse_computations(text)
    return [name for name, result, opcode, _ in computations[entry] if shape in _shapes(result) and opcode != "parameter"]


def compile_step(workload: str) -> "jax.stages.Compiled":
    """The cell's optimizer step compiled for one described v5e chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, REPO)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.lib.weights import seed_key, weight_builder
    from benchmarks.run import load_json
    from perceiver_io_tpu.training import TrainState, make_optimizer
    from perceiver_io_tpu.training.loop import make_train_step

    fa = importlib.import_module("perceiver_io_tpu.ops.flash_attention")
    # a compile for a described chip is written to the persistent cache but cannot be read back without the chip
    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    cell = load_json("workloads", workload)
    if cell["driver"] != "train":
        raise SystemExit(f"tools/step_hlo.py: {workload} is a {cell['driver']} cell; this reads a train cell's step")
    config = load_json("configs", cell["config"])
    family = importlib.import_module(f"benchmarks.families.{config['family']}").Family(config)
    p = cell["params"]
    model = family.model()
    build = weight_builder(family.param_shapes(model), family.cfg["init_scale"])
    tx = make_optimizer(p["learning_rate"], gradient_clip=p["gradient_clip"], weight_decay=p["weight_decay"],
                        moment_dtype=p["adam_moment_dtype"])

    def described(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree)

    state = described(jax.eval_shape(lambda: TrainState.create(model.apply, build(seed_key(0)), tx, jax.random.PRNGKey(1))))
    batch = described(family.train_batch(0, 0, p["batch_size"]))
    fa._interpret_default = lambda: False  # the backend here is the CPU: lower the kernels for Mosaic all the same
    with fa.default_flash(True):
        step = make_train_step(family.train_loss_fn(model), microbatch=p["microbatch"])
        return step.lower(state, batch).compile()


def scope_tail(op_name: str, parts: int = 5) -> str:
    return "/".join(op_name.split("/")[-parts:])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", help="a train cell: benchmarks/workloads/<name>.json")
    ap.add_argument("--text", help="read this stored module text; compile nothing")
    ap.add_argument("--save", help="write the compiled module's text here")
    ap.add_argument("--shape", default="f32[32,1024,2048]", help="count exponential/divide instructions of this result shape")
    ap.add_argument("--scope", default="", help="print only fusions whose op_name holds this")
    ap.add_argument("--json", help="write the rows here as JSON")
    args = ap.parse_args(argv)
    if bool(args.workload) == bool(args.text):
        ap.error("give --workload or --text")
    if args.text:
        with open(args.text) as f:
            text = f.read()
    else:
        compiled = compile_step(args.workload)
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
              f"{scope_tail(r['op_name'])} | {' '.join(r['shapes'])}")
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
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
