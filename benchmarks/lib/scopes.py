"""Device time by program scope: the join between a traced window's device
rows (instruction name, start, duration) and the compiled program's own
table of which phase and layer each instruction belongs to
(``perceiver_io_tpu.obs.xplane.instruction_scopes``).

A reader has the rows and no capture, so the table is made again:
``lower_program`` builds what the cell's driver builds (``drivers/train.py``'s
optimizer step, ``drivers/decode.py``'s generator) over shapes alone (no weight
is drawn, no state made), and its compile has to be a hit in the persistent
cache, where the executable that the window ran was written minutes earlier.
The table is that executable's own text: the names in it are the names in the
trace. A scope is metadata on an instruction, so the cache's key does not
hold it: an executable cached before the program opened a scope shows the
scopes it was compiled with.

Nothing is read, and every reader says ``not read: <why>`` and returns
``None``, where the program has no ``instruction_scopes`` (a parent commit),
where the compile missed the cache, or where instructions worth more than
``UNKNOWN_LIMIT`` of the traced device time are not in the table (the rule of
``lib/flash_groups.py``: a wrong join must not read as a number).

A layer's time is the sum over leaf instructions (``while``, ``conditional``
and ``call`` are left out: their bodies' instructions report the same time
again), clipped to the window, on the first device plane.
"""

from __future__ import annotations

import glob
import os
import time
from typing import Callable, Dict, List, Optional, Tuple

from benchmarks.lib import trace

UNKNOWN_LIMIT = 0.001  # of the traced device time
UNSCOPED = "<unscoped>"
KERNEL_NAME_HOLDS = "flash"  # as lib/flash_groups.py selects the flash kernels
EXPERT_KERNEL_NAME_HOLDS = "moe_experts_prefill"
ATTENTION_BLOCKS = ("cross_attend", "self_attend")  # the Perceiver family's attention blocks, by path
DECODER_ATTENTION = ("mla/absorb", "mla/expand", "attn/window", "attn/full")
EMBED_LAYERS = ("embed", "prefix_dropout", "input_adapter")
MOE_GLUE_LAYERS = ("moe/route", "moe/experts", "moe/combine")


def lower_program(cell: dict, family, sharding=None):
    """The cell's one program, lowered over ``jax.ShapeDtypeStruct``
    arguments as its driver lowers it over arrays. ``sharding`` describes a
    chip that is not attached (``tools/step_hlo.py``); a run on the chip
    leaves it out, as the drivers do."""
    import jax
    import jax.numpy as jnp

    def shapes_of(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding,
                                                           weak_type=getattr(x, "weak_type", False)), tree)

    p = cell["params"]
    model = family.model()
    weights = family.param_shapes(model)
    if cell["driver"] == "train":
        from perceiver_io_tpu.training import TrainState, make_optimizer
        from perceiver_io_tpu.training.loop import make_train_step

        tx = make_optimizer(p["learning_rate"], gradient_clip=p["gradient_clip"], weight_decay=p["weight_decay"],
                            moment_dtype=p["adam_moment_dtype"])
        state = jax.eval_shape(lambda w: TrainState.create(model.apply, w, tx, jax.random.PRNGKey(1)), weights)
        batch = family.train_batch(0, 0, p["batch_size"])
        step = make_train_step(family.train_loss_fn(model), microbatch=p["microbatch"])
        return step.lower(shapes_of(state), shapes_of(batch))
    generate = family.generate_fn(model, p["num_latents"], p["new_tokens"], p["cache_dtype"])
    prompts = jax.ShapeDtypeStruct((p["batch_size"], p["prompt_len"]), jnp.int32, sharding=sharding)
    return generate.lower(shapes_of(weights), prompts)


def program_scopes(run: dict) -> Tuple[Optional[Dict[str, Dict]], str]:
    """``(table, note)``: the instruction-to-scope table of the executable
    that the window ran, or ``(None, why)``. A test, or a tool that holds a
    stored table, hands it in as ``run["scope_table"]``."""
    if run.get("scope_table") is not None:
        return run["scope_table"], "the table came with the run"
    try:
        from perceiver_io_tpu.obs.xplane import instruction_scopes
    except ImportError:
        return None, "the program has no obs.xplane.instruction_scopes"
    import jax

    from benchmarks.lib.programs import Programs

    t0 = time.perf_counter()
    lowered = lower_program(run["cell"], run["family"])
    # an entry's file starts with the module's name: where the cache holds none (no cache, or a cache that caps an
    # entry's size), the compile would be a miss and minutes long for nothing
    name = lowered.compiler_ir().operation.attributes["sym_name"].value
    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir or not glob.glob(os.path.join(cache_dir, name + "-*")):
        return None, f"the persistent cache ({cache_dir}) holds no executable of {name}: the window's was not kept"
    programs = Programs()
    compiled = lowered.compile()
    seconds = time.perf_counter() - t0
    if programs.hits < 1 or programs.misses:
        return None, (f"the program's compile was no hit in the persistent cache ({programs.hits} hits, "
                      f"{programs.misses} misses, {seconds:.1f} s): its text need not be the text of what ran")
    return instruction_scopes(compiled.as_text()), f"cache hit, lowered and read in {seconds:.1f} s"


class ScopeTimes:
    """Leaf device time of a traced window by phase and layer."""

    def __init__(self, rows: List[Tuple[str, float, Dict]], container_ns: float):
        self.rows = rows  # (instruction name, ns in the window, its row of the table)
        self.container_ns = container_ns
        self.leaf_ns = sum(ns for _, ns, _ in rows)

    def sum(self, keep: Callable[[str, Dict], bool]) -> float:
        return sum(ns for name, ns, row in self.rows if keep(name, row))

    def by(self, key: Callable[[str, Dict], object], keep: Callable[[str, Dict], bool] = lambda name, row: True) -> Dict:
        out: Dict = {}
        for name, ns, row in self.rows:
            if keep(name, row):
                out[key(name, row)] = out.get(key(name, row), 0.0) + ns
        return out


def join(events, table: Dict[str, Dict]) -> Tuple[Optional[ScopeTimes], str]:
    """The window's device rows under the table: ``(times, "")``, or
    ``(None, why)`` where too much of the time has a name the table lacks."""
    totals = trace.totals_by_name(events)
    unknown = {name: ns for name, ns in totals.items() if name not in table}
    everything = sum(totals.values())
    if everything <= 0:
        return None, "the window holds no device operation"
    if sum(unknown.values()) > UNKNOWN_LIMIT * everything:
        worst = sorted(unknown, key=unknown.get, reverse=True)[:4]
        return None, (f"{len(unknown)} instruction names, {100 * sum(unknown.values()) / everything:.2f}% of the device "
                      f"time, are not in the program's table ({', '.join(worst)}{', ...' if len(unknown) > 4 else ''})")
    rows = [(name, ns, table[name]) for name, ns in totals.items() if name in table and not table[name]["container"]]
    containers = sum(ns for name, ns in totals.items() if name in table and table[name]["container"])
    return ScopeTimes(rows, containers), ""


def per(run: dict) -> Tuple[Dict[str, float], str]:
    """What a phase's time is divided by, and the unit's name: a train
    cell's steps; a decode cell's calls for the prompt pass and the loop's
    steps, ``calls x (new_tokens - 1)``, for the decode phase."""
    counters = run["counters"]
    if "steps" in counters:
        return {"": float(counters["steps"])}, "step"
    calls = float(counters["calls"])
    return {"": calls, "decode": calls * (run["cell"]["params"]["new_tokens"] - 1)}, "call (decode: step)"


def table_line(times: ScopeTimes, divide: Dict[str, float], unit: str) -> str:
    cells = times.by(lambda name, row: (row["phase"] or "-", row["layer"]))
    parts = [f"{phase}/{layer} {ns / 1e6 / divide.get(phase, divide['']):.3f} ({100 * ns / times.leaf_ns:.2f}%)"
             for (phase, layer), ns in sorted(cells.items(), key=lambda kv: -kv[1])]
    return f"scopes: device ms a {unit} by phase/layer (share of leaf time): " + "; ".join(parts)


def times(run: dict, metric: str) -> Optional[ScopeTimes]:
    """The run's :class:`ScopeTimes`, made once a run; ``None`` with a
    ``not read`` line under ``metric``'s name where there is none. The first
    call prints the cell's whole table on one line, what was placed by
    inheritance, and the largest instructions left without a layer."""
    if "scope_times" not in run:  # kept with the run, which every reader of the process is handed
        run["scope_times"] = _make(run)
    found, why = run["scope_times"]
    if found is None:
        print(f"{metric}: not read: {why}", flush=True)
    return found


def _make(run: dict) -> Tuple[Optional[ScopeTimes], str]:
    if run["trace"] is None:
        return None, "no trace"
    table, note = program_scopes(run)
    if table is None:
        return None, note
    plane = sorted(run["trace"]["devices"])[0]
    found, why = join(trace.clip(run["trace"]["devices"][plane], run["trace_window"]), table)
    if found is None:
        return None, why
    divide, unit = per(run)
    inherited = found.sum(lambda name, row: row["inherited"])
    print(f"scopes: {note}; {len(found.rows)} leaf instructions, {found.leaf_ns / 1e9:.4f} s of leaf device time against "
          f"busy_s {run['busy_s']:.4f}; containers (left out) {found.container_ns / 1e9:.4f} s; placed by inheritance "
          f"{100 * inherited / found.leaf_ns:.2f}%", flush=True)
    print(table_line(found, divide, unit), flush=True)
    bare = sorted(((ns, name, row) for name, ns, row in found.rows if row["layer"] == UNSCOPED), reverse=True)[:12]
    print("scopes: largest instructions without a layer, ms a " + unit.split(" ")[0] + ": "
          + ", ".join(f"{name} ({row['opcode']}, {row['phase'] or 'no phase'}) {ns / 1e6 / divide['']:.3f}" for ns, name, row in bare),
          flush=True)
    return found, note


def in_attention_block(row: Dict) -> bool:
    return any(part in ATTENTION_BLOCKS for part in row["path"].split("/")) and row["layer"] != "mlp"


def read(run: dict, metric: str, keep: Callable[[str, Dict], bool], over: str = "",
         parts: Optional[Callable[[str, Dict], object]] = None) -> Optional[float]:
    """``metric``: the leaf time of the instructions ``keep`` takes, in ms a
    step (a call for a decode cell's prompt pass; ``over="decode"``: a step
    of the decode loop), or with ``over="leaf"`` in percent of all leaf time.
    ``parts`` names the pieces that the reader prints beside the value."""
    found = times(run, metric)
    if found is None:
        return None
    ns = found.sum(keep)
    if over == "leaf":
        return 100.0 * ns / found.leaf_ns
    divide, unit = per(run)
    steps = divide.get(over, divide[""])
    if parts is not None:
        pieces = sorted(found.by(parts, keep).items(), key=lambda kv: -kv[1])
        print(f"{metric}: ms a {'step' if over or 'steps' in run['counters'] else 'call'}: "
              + ", ".join(f"{k or '-'} {v / 1e6 / steps:.3f}" for k, v in pieces), flush=True)
    return ns / 1e6 / steps
