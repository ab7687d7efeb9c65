"""XSpace (xplane.pb) reader + per-scope rollup — aggregate device-op time
from a ``jax.profiler.trace`` capture without TensorFlow/tensorboard.

Lifted from ``tools/xplane.py`` (which now shims to this module) and grown
into a library: besides the per-op totals the CLI always printed, the
:func:`rollup` API aggregates event durations by the ``jax.named_scope`` /
flax-module path embedded in XLA op names
(``jit(train_step)/.../perceiver_ar/cross_attention/fusion.123``), so a
captured trace reads by *module* ("cross_attention: 8.1 ms") instead of by
raw HLO op name. The framework's scopes are threaded through
``core/modules.py``, ``core/attention.py``, ``ops/flash_attention.py`` and
``generation.py`` (prefill vs. decode).

Wire-format notes (tensorflow/core/profiler/protobuf/xplane.proto):
  XSpace:        planes = 1 (repeated XPlane)
  XPlane:        id=1, name=2, lines=3 (repeated XLine),
                 event_metadata=4 (map<int64, XEventMetadata>),
                 stat_metadata=5 (map<int64, XStatMetadata{id=1, name=2}>)
  XLine:         id=1, display_name? name=2/3, events=4 — fields probed
  XEvent:        metadata_id=1, offset_ps=2, duration_ps=3,
                 stats=4 (repeated XStat)
  XEventMetadata: id=1, name=2, display_name=3, stats=5
  XStat:         metadata_id=1, str_value=5, ref_value=7 (interned string:
                 the stat_metadata entry's NAME is the value)

The metadata name/display_name of a device-plane op event is the raw HLO
instruction name ("fusion.123"); the framework path
("jit(step)/.../cross_attend/fusion.123") rides in a stat whose
stat-metadata name is ``tf_op`` / ``long_name`` / ``hlo_op`` — attached to
the event or to its event metadata. The rollup resolves those stats so
scopes work on real captures, not just on names that happen to contain "/".
"""

from __future__ import annotations

import collections
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, NamedTuple, Optional, Tuple


def _varint(buf: bytes, i: int):
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, i
        shift += 7


def fields(buf: bytes):
    """Yield (field_number, wire_type, value) over a protobuf message."""
    i = 0
    n = len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        fnum, wt = tag >> 3, tag & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            val = buf[i : i + ln]
            i += ln
        elif wt == 5:
            val = int.from_bytes(buf[i : i + 4], "little")
            i += 4
        elif wt == 1:
            val = int.from_bytes(buf[i : i + 8], "little")
            i += 8
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield fnum, wt, val


# stat names that carry the framework op path (jax named_scope / module path)
SCOPE_STAT_NAMES = frozenset({"tf_op", "long_name", "hlo_op", "op_name"})


def _parse_stats(stats_msgs, stat_names):
    """Resolve XStat messages against the plane's stat-metadata name table;
    returns the best scope-path value found (str_value or interned
    ref_value), or ''.

    Scope-bearing stat names mix real framework paths (``tf_op`` /
    ``op_name``) with ``hlo_op``, whose value is just the raw HLO
    instruction name — so a value containing '/' wins regardless of the
    stats' serialization order, and a bare op name is only the fallback."""
    fallback = ""
    for stat in stats_msgs:
        mid = None
        sval = ""
        rval = None
        for f, w, v in fields(stat):
            if f == 1 and w == 0:
                mid = v
            elif f == 5 and w == 2:
                sval = v.decode(errors="replace")
            elif f == 7 and w == 0:
                rval = v
        if mid is None or stat_names.get(mid, "") not in SCOPE_STAT_NAMES:
            continue
        val = sval or (stat_names.get(rval, "") if rval is not None else "")
        if "/" in val:
            return val
        if val and not fallback:
            fallback = val
    return fallback


def parse_plane(plane: bytes):
    name, metadata, _, lines, _ = parse_plane_full(plane)
    return name, metadata, lines


def parse_plane_full(plane: bytes):
    """``(name, metadata, scope_hints, lines, stat_names)`` — ``metadata``
    maps event-metadata id -> display name; ``scope_hints`` maps the ids
    whose metadata stats carry a framework op path (``SCOPE_STAT_NAMES``)
    to that path; ``stat_names`` is the plane's stat-metadata name table
    (needed to resolve per-event stats)."""
    name = ""
    metadata = {}
    lines = []
    stat_names = {}
    meta_stats = {}  # metadata id -> raw XStat messages (resolved after the scan)
    for fnum, wt, val in fields(plane):
        if fnum == 2 and wt == 2:
            name = val.decode(errors="replace")
        elif fnum == 3 and wt == 2:
            lines.append(val)
        elif fnum == 5 and wt == 2:
            # stat_metadata map entry: key=1, value=2 XStatMetadata{id=1, name=2}
            k = v = None
            for f2, w2, v2 in fields(val):
                if f2 == 1:
                    k = v2
                elif f2 == 2:
                    v = v2
            if k is not None and v is not None:
                for f3, w3, v3 in fields(v):
                    if f3 == 2 and w3 == 2:
                        stat_names[k] = v3.decode(errors="replace")
        elif fnum == 4 and wt == 2:
            # map entry: key=1 varint, value=2 XEventMetadata
            k = v = None
            for f2, w2, v2 in fields(val):
                if f2 == 1:
                    k = v2
                elif f2 == 2:
                    v = v2
            if k is not None and v is not None:
                mname = ""
                mdisplay = ""
                stats = []
                for f3, w3, v3 in fields(v):
                    if f3 == 2 and w3 == 2:
                        mname = v3.decode(errors="replace")
                    elif f3 == 3 and w3 == 2:
                        mdisplay = v3.decode(errors="replace")
                    elif f3 == 5 and w3 == 2:
                        stats.append(v3)
                metadata[k] = mdisplay or mname
                if stats:
                    meta_stats[k] = stats
    # stat_metadata can appear after event_metadata in the stream — resolve last
    scope_hints = {}
    for k, stats in meta_stats.items():
        hint = _parse_stats(stats, stat_names)
        if hint:
            scope_hints[k] = hint
    return name, metadata, scope_hints, lines, stat_names


def parse_line_events(line: bytes):
    """Yield (line_name, metadata_id, duration_ps) for each XEvent on the line."""
    for lname, mid, dur, _ in iter_line_events(line):
        yield lname, mid, dur


def iter_line_events(line: bytes, stat_names: Optional[Dict[int, str]] = None):
    """Yield (line_name, metadata_id, duration_ps, scope_hint) per XEvent —
    ``scope_hint`` is the framework op path from the event's own stats
    (resolved against ``stat_names``), or '' when absent."""
    stat_names = stat_names or {}
    lname = ""
    evs = []
    for fnum, wt, val in fields(line):
        if fnum in (2, 11) and wt == 2:
            lname = val.decode(errors="replace") or lname
        elif fnum == 4 and wt == 2:  # XLine.events
            mid = dur = 0
            stats = []
            for f2, w2, v2 in fields(val):
                if f2 == 1:
                    mid = v2
                elif f2 == 3:
                    dur = v2
                elif f2 == 4 and w2 == 2:  # XEvent.stats
                    stats.append(v2)
            hint = _parse_stats(stats, stat_names) if stats else ""
            evs.append((mid, dur, hint))
    for mid, dur, hint in evs:
        yield lname, mid, dur, hint


def resolve_capture(path: str) -> str:
    """A capture directory resolves to its newest ``*.xplane.pb``."""
    if os.path.isdir(path):
        pbs = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
        if not pbs:
            raise FileNotFoundError(f"no xplane.pb under {path}")
        path = pbs[-1]
    return path


@dataclass
class PlaneSummary:
    """Per-op totals for one XPlane — what the CLI has always printed —
    plus the per-op framework scope paths the stats provided (empty when a
    capture carries none)."""

    name: str
    per_op: "collections.Counter" = field(default_factory=collections.Counter)
    counts: "collections.Counter" = field(default_factory=collections.Counter)
    per_line: "collections.Counter" = field(default_factory=collections.Counter)
    op_scopes: Dict[str, str] = field(default_factory=dict)

    @property
    def total_ps(self) -> int:
        return sum(self.per_line.values())


def iter_planes(path: str, line_filter: str = "") -> Iterator[PlaneSummary]:
    """Per-op duration totals for every plane in a capture (file or dir)."""
    path = resolve_capture(path)
    with open(path, "rb") as f:
        buf = f.read()
    for fnum, wt, plane in fields(buf):
        if fnum != 1 or wt != 2:
            continue
        name, metadata, scope_hints, lines, stat_names = parse_plane_full(plane)
        summary = PlaneSummary(name=name)
        for line in lines:
            for lname, mid, dur, hint in iter_line_events(line, stat_names):
                if line_filter and line_filter not in lname:
                    continue
                op = metadata.get(mid, f"#{mid}")
                summary.per_op[op] += dur
                summary.counts[op] += 1
                summary.per_line[lname] += dur
                hint = hint or scope_hints.get(mid, "")
                if hint and op not in summary.op_scopes:
                    summary.op_scopes[op] = hint
        if summary.per_op:
            yield summary


UNSCOPED = "<unscoped>"

# ---------------------------------------------------------------------------
# the scope vocabulary: one rule from an ``op_name`` to (phase, layer, path)
# ---------------------------------------------------------------------------
#
# An ``op_name`` is the name stack JAX gave the operation: transform wrappers,
# ``jax.named_scope`` names, flax module and method names, and the primitive
# last (``jit(train_step)/transpose(jvp(CausalLanguageModel))/perceiver_ar/
# self_attend/self_attention/layer_3/mlp/mlp/dense_1/dot_general``). The
# vocabulary below is every layer boundary the program marks; the table of
# docs/observability.md#scopes lists each with its module and its readers.

# a named scope that says which part of the program runs (the train step's
# ``forward`` and ``backward`` are read from the transform wrappers instead)
PHASE_SCOPES = {
    "prefill": "prefill", "shared_prefill": "prefill",
    "decode": "decode", "decode_spec": "decode", "decode_paged": "decode",
    "optimizer": "optimizer",
}
# named scopes that are layers; a two-part scope (``moe/route``) is listed whole
LAYER_SCOPES = frozenset({
    # Perceiver family (core/modules.py, core/attention.py, core/position.py)
    "embed", "prefix_dropout", "input_adapter", "output_adapter", "cross_attend", "self_attend", "mlp",
    "logits", "qkv_proj", "rotary", "loss", "sample", "optimizer",
    # decoder-only class (models/text/decoder_lm.py, core/mla.py, core/gqa.py, core/moe.py)
    "mla/expand", "mla/absorb", "attn/window", "attn/full", "moe/route", "moe/experts", "moe/combine",
    "moe/shared", "moe/zero", "mtp/project", "mtp/block", "mtp/draft", "spec/verify", "spec/accept", "spec/rollback",
    "dense_mlp", "norm", "residual", "chunk_io", "cache_fill", "loop_io",
    # a state-space mixer (core/ssm.py): the scan is the prompt pass's, the update a step's
    "ssm/proj_in", "ssm/conv", "ssm/select", "ssm/scan", "ssm/update", "ssm/out",
    # a power retention layer (core/retention.py): the chunked form is the prompt pass's, the update a step's
    "ret/proj", "ret/gate", "ret/chunk", "ret/update", "ret/out",
    # a Kimi delta attention layer (core/kda.py): the chunked form is the prompt pass's, the update a step's
    "kda/proj", "kda/conv", "kda/gate", "kda/chunk", "kda/update", "kda/out",
    # differential attention (core/diff_attention.py): the flash form is the prompt pass's, the step a token's; the
    # layer that owns the shared cache writes under ``yoco/kv`` and the cross layers read under ``yoco/cross``
    "diff/proj", "diff/flash", "diff/step", "diff/combine", "yoco/kv", "yoco/cross",
    # a gated memory unit (core/ssm.py). ``prefill/last`` is a cut prompt pass's last position (models/text/decoder_lm.py):
    # every layer under it opens its own scope, so it names a table row only for what none of them claims; it is there for
    # the compiled text, where tests/test_tpu_compile.py finds by it what the pass runs at one position a row
    "gmu", "prefill/last",
    # latent attention that chooses its keys (core/dsa.py): the indexer's projections, its scores, the selection and the
    # attention over the selected keys in the prompt pass; a step's score, selection, gather and attention; what the layer
    # shares with plain latent attention stays under ``mla/expand`` and ``mla/absorb``. ``mla/window`` is the second latent
    # attention behind its window in the pass, ``mla/window_step`` a step over its ring
    "dsa/index", "dsa/score", "dsa/select", "dsa/attend", "dsa/step_score", "dsa/step_select", "dsa/step_gather",
    "dsa/step_attend", "mla/window", "mla/window_step",
})
# flax module names that mark a layer no scope is opened for
MODULE_LAYERS = {"q_proj": "qkv_proj", "k_proj": "qkv_proj", "v_proj": "qkv_proj", "o_proj": "o_proj"}
_NORM_MODULE = re.compile(r"(^|_)norm$|^(Layer|RMS)Norm_\d+$")
# a layer that is read whole: what it holds inside (the MLP's own LayerNorm, an attention's projections
# and q/k norms) is its own
CLOSED_LAYERS = frozenset({"mlp", "dense_mlp", "mla/expand", "mla/absorb", "attn/window", "attn/full",
                           "ssm/proj_in", "ssm/conv", "ssm/select", "ssm/scan", "ssm/update", "ssm/out",
                           "ret/proj", "ret/gate", "ret/chunk", "ret/update", "ret/out",
                           "kda/proj", "kda/conv", "kda/gate", "kda/chunk", "kda/update", "kda/out",
                           "diff/proj", "diff/flash", "diff/step", "diff/combine", "yoco/kv", "yoco/cross",
                           "gmu", "dsa/index", "dsa/score", "dsa/select", "dsa/attend", "dsa/step_score", "dsa/step_select",
                           "dsa/step_gather", "dsa/step_attend", "mla/window", "mla/window_step"})
# parts of a name stack that are no scope: what a transform or a loop wraps around the names. A transform
# wraps the first scope opened under it (``transpose(jvp(loss))`` is the scope ``loss``); ``jit`` wraps the name
# of a function, which is no scope
_WRAPPER = re.compile(r"^(\w+)\((.*)\)$")
_STRUCTURE = frozenset({"while", "body", "cond", "closed_call", "checkpoint", "remat", "rematted_computation",
                        "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr", "pjit", "branch_0_fun",
                        "branch_1_fun"})


def _unwrap(part: str) -> str:
    """The scope a name-stack part holds once its transform wrappers are off ('' for a jitted function's name)."""
    while (wrapped := _WRAPPER.match(part)) is not None:
        if wrapped.group(1) in ("jit", "pjit"):
            return ""
        part = wrapped.group(2)
    return part


class OpScope(NamedTuple):
    """Where an operation belongs: ``phase`` (``prefill``, ``decode``,
    ``forward``, ``backward``, ``optimizer`` or ''), ``layer`` (a name of the
    vocabulary, or ``<unscoped>``) and ``path`` (every scope and module name
    of the stack, wrappers and the primitive dropped; '' where none is left)."""

    phase: str
    layer: str
    path: str


def _layer_of(parts: List[str], i: int) -> Optional[str]:
    """The layer that ``parts[i]`` marks, alone or with the part before it."""
    part = parts[i]
    if i and f"{parts[i - 1]}/{part}" in LAYER_SCOPES:
        return f"{parts[i - 1]}/{part}"
    if part in LAYER_SCOPES:
        return part
    if part in MODULE_LAYERS:
        return MODULE_LAYERS[part]
    return "norm" if _NORM_MODULE.search(part) else None


def op_scope(op_name: str) -> OpScope:
    """The one rule from an ``op_name`` to its phase, layer and path.

    Transform wrappers (``jit(..)``, ``jvp(..)``, ``transpose(..)``,
    ``vmap(..)``, ``checkpoint``), loop parts (``while/body/cond``) and the
    trailing primitive are dropped. Of what is left the phase is the first
    phase scope (in a train step: ``backward`` under a ``transpose(`` wrapper,
    else ``forward`` under a ``jvp(``), and the layer the innermost part the
    vocabulary knows, except that nothing is looked for inside a closed layer
    (the MLP keeps its LayerNorm). Forward and backward of one module so give
    one layer; a name that holds no known part is ``<unscoped>``. XLA joins
    the names of merged instructions with ``;``: the first is read."""
    raw = op_name.split(";", 1)[0].split("/")
    parts = [p for p in map(_unwrap, raw[:-1]) if p and p not in _STRUCTURE]
    phase = next((PHASE_SCOPES[p] for p in parts if p in PHASE_SCOPES), "")
    if not phase and any(p.startswith("transpose(") for p in raw):
        phase = "backward"
    elif not phase and any("jvp(" in p for p in raw):
        phase = "forward"
    layer = None
    for i in range(len(parts)):
        layer = _layer_of(parts, i) or layer
        if layer in CLOSED_LAYERS:
            break
    return OpScope(phase, layer or UNSCOPED, "/".join(parts))


def scope_of(op_name: str, depth: Optional[int] = None) -> str:
    """The path of :func:`op_scope`, cut to its ``depth`` leading parts:
    ``jit(train_step)/jit(main)/perceiver_ar/cross_attention/fusion.3`` is
    ``perceiver_ar/cross_attention``; a name with no scope path is ``<unscoped>``."""
    path = op_scope(op_name).path
    if not path:
        return UNSCOPED
    return path if depth is None else "/".join(path.split("/")[:depth])


_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"(?:body|condition|to_apply|calls|true_computation|false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
CONTAINER_OPCODES = frozenset({"while", "conditional", "call"})


def _called_computations(line: str) -> List[str]:
    branches = _BRANCHES.search(line)
    return _CALLED.findall(line) + ([b.strip().lstrip("%") for b in branches.group(1).split(",")] if branches else [])


def instruction_scopes(hlo_text: str) -> Dict[str, Dict]:
    """``{instruction name: {opcode, phase, layer, path, container, inherited}}``
    over a compiled module's entry computation and every computation a
    ``while``, ``conditional``, ``call`` or async ``-start`` of it runs (the
    device trace reports those instructions; what a fusion holds inside is
    the fusion's). Built on ``analysis/graph.py::parse_hlo_computations``.

    ``container`` marks the instructions whose time their bodies'
    instructions also report. A fusion without an ``op_name`` takes its
    root's, or the one scope that all it holds agree on. An instruction the
    compiler made and gave no ``op_name`` (a layout ``copy``, a
    ``slice-done``) takes the phase, and the layer, of the instructions it
    feeds where they all have one and the same (a computation's root: of the
    instruction that runs the computation; where that gives nothing: of the
    instructions it reads), and is marked ``inherited``."""
    from perceiver_io_tpu.analysis.graph import parse_hlo_computations

    computations = parse_hlo_computations(hlo_text)
    entry = re.search(r"^ENTRY\s+%?([\w.\-]+)", hlo_text, re.M)
    if entry is None:
        raise ValueError("the text holds no ENTRY computation")
    table: Dict[str, Dict] = {}
    pending = [(entry.group(1), None)]  # (computation, the scope of the instruction that runs it)
    seen = set()
    while pending:
        name, caller = pending.pop()
        if name in seen or name not in computations:
            continue
        seen.add(name)
        instructions = computations[name]
        users: Dict[str, List[str]] = {}
        unnamed = []  # in the order walked: users first
        for ins in instructions:
            for operand in ins.operands:
                users.setdefault(operand, []).append(ins.name)
        # users come later in the text: walk it backwards, so that a chain of unnamed instructions resolves in one pass
        for ins in reversed(instructions):
            called = _called_computations(ins.line)
            container = ins.opcode in CONTAINER_OPCODES or (ins.opcode.endswith("-start") and bool(called))
            named = _OP_NAME.search(ins.line)
            inside = []  # an unnamed fusion: the scopes of what it holds, the root's first
            if named is None and ins.opcode == "fusion" and called:
                held = sorted(computations.get(called[0], ()), key=lambda i: not i.line.startswith("ROOT "))
                inside = [op_scope(m.group(1)) for m in map(_OP_NAME.search, (i.line for i in held)) if m]
            inherited = False
            if named is not None:
                scope = op_scope(named.group(1))
            elif inside and len({(s.phase, s.layer) for s in inside}) == 1:
                scope = inside[0]
            else:
                fed = [table[u] for u in users.get(ins.name, ())] or ([caller] if caller else [])
                phases, layers = {f["phase"] for f in fed}, {f["layer"] for f in fed}
                agreed = len(layers) == 1 and layers != {UNSCOPED}  # the layer comes with its path: readers ask both
                scope = OpScope(phases.pop() if len(phases) == 1 else "", layers.pop() if agreed else UNSCOPED,
                                fed[0]["path"] if agreed else "")
                inherited = bool(scope.phase) or agreed
            table[ins.name] = {"opcode": ins.opcode, "phase": scope.phase, "layer": scope.layer, "path": scope.path,
                               "container": container, "inherited": inherited}
            if named is None and not inside:
                unnamed.append(ins)
            if container:
                pending.extend((c, table[ins.name]) for c in called)
        # what feeds nothing that has a scope (a copy into the program's result) takes the scope of what it reads
        for ins in reversed(unnamed):
            row, read = table[ins.name], [table[o] for o in ins.operands]
            for key, none in (("phase", ""), ("layer", UNSCOPED)):
                found = {r[key] for r in read}
                if row[key] == none and len(found) == 1 and found != {none}:
                    row[key], row["inherited"] = found.pop(), True
                    if key == "layer":
                        row["path"] = read[0]["path"]
    return table


@dataclass
class ScopeRollup:
    """Per-scope aggregation of one plane's events."""

    plane: str
    # scope -> (total duration ps, event count)
    scopes: Dict[str, Tuple[int, int]] = field(default_factory=dict)

    @property
    def total_ps(self) -> int:
        return sum(d for d, _ in self.scopes.values())

    def top(self, n: int = 30) -> List[Tuple[str, int, int]]:
        rows = [(s, d, c) for s, (d, c) in self.scopes.items()]
        rows.sort(key=lambda r: -r[1])
        return rows[:n]


def rollup_planes(
    planes: List[PlaneSummary], depth: Optional[int] = None
) -> List[ScopeRollup]:
    """Aggregate already-parsed :class:`PlaneSummary` objects by named scope
    — pure aggregation, no re-read of the capture (the parse dominates on
    multi-hundred-MB captures, so callers holding planes reuse them)."""
    out = []
    for plane in planes:
        scopes: Dict[str, List[int]] = {}
        for op, dur in plane.per_op.items():
            # prefer the stat-provided framework path (device planes name
            # events by raw HLO op; the jax op_name path rides in a stat)
            s = scope_of(plane.op_scopes.get(op, op), depth=depth)
            agg = scopes.setdefault(s, [0, 0])
            agg[0] += dur
            agg[1] += plane.counts[op]
        out.append(
            ScopeRollup(plane=plane.name, scopes={s: (d, c) for s, (d, c) in scopes.items()})
        )
    return out


def rollup(
    path: str, depth: Optional[int] = None, line_filter: str = ""
) -> List[ScopeRollup]:
    """Aggregate a capture by named scope instead of raw op name.

    The per-plane total equals :func:`iter_planes`'s (and the CLI's) total
    exactly: every event lands in one scope bucket.
    """
    return rollup_planes(list(iter_planes(path, line_filter=line_filter)), depth=depth)


def summarize(
    path: str,
    top: int = 30,
    line_filter: str = "",
    by_scope: bool = False,
    depth: Optional[int] = None,
    print_fn=print,
) -> List[PlaneSummary]:
    """Print per-plane totals (per-op, or per-scope with ``by_scope``) and
    return the plane summaries — the ``tools/xplane.py`` CLI behavior as a
    callable."""
    resolved = resolve_capture(path)
    size = os.path.getsize(resolved)
    print_fn(f"{resolved} ({size/1e6:.0f} MB)")
    planes = list(iter_planes(resolved, line_filter=line_filter))
    scoped = rollup_planes(planes, depth=depth) if by_scope else None
    for i, plane in enumerate(planes):
        print_fn(f"\n=== plane: {plane.name} | lines: {dict(plane.per_line.most_common(6))}")
        print_fn(f"    sum of event time: {plane.total_ps/1e9:.3f} ms")
        if by_scope:
            for s, d, c in scoped[i].top(top):
                print_fn(f"  {d/1e9:9.3f} ms {c:6d}x  {s[:100]}")
        else:
            for op, d in plane.per_op.most_common(top):
                print_fn(f"  {d/1e9:9.3f} ms {plane.counts[op]:6d}x  {op[:100]}")
    return planes


# ---------------------------------------------------------------------------
# one capture on one timeline (jax.profiler.ProfileData)
# ---------------------------------------------------------------------------

DEVICE_PLANE_PREFIX = "/device:"
DEVICE_OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ENVIRONMENT_PLANE = "Task Environment"


def load_capture(path: str) -> Dict:
    """One capture as plain rows on the profiler's timeline, nanoseconds
    since the capture started:

    - ``profile_start_ns``: the epoch time (``time.time_ns()`` clock) of
      that zero, from the ``Task Environment`` plane — subtract it from a
      span row's ``start_ns`` to place the row on this timeline;
    - ``length_ns``: the capture's length;
    - ``device_ops``: ``{plane: [(name, start_ns, duration_ns), ...]}``,
      each device plane's "XLA Ops" line under the instruction names;
    - ``annotations``: ``[(name, start_ns, duration_ns, span_id), ...]``,
      the host plane's events that carry a ``span_id`` stat — the spans an
      ``obs.trace.Tracer`` opened while the capture ran.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(resolve_capture(path))
    start = stop = None
    device_ops: Dict[str, List[tuple]] = {}
    annotations: List[tuple] = []
    for plane in data.planes:
        if plane.name == ENVIRONMENT_PLANE:
            stats = dict(plane.stats)
            start, stop = stats.get("profile_start_time"), stats.get("profile_stop_time")
        elif plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    device_ops.setdefault(plane.name, []).extend(
                        (e.name.split(" = ", 1)[0].lstrip("%"), float(e.start_ns), float(e.duration_ns))
                        for e in line.events
                    )
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    span_id = dict(e.stats).get("span_id")
                    if span_id is not None:
                        annotations.append((e.name, float(e.start_ns), float(e.duration_ns), str(span_id)))
    if start is None:
        raise ValueError(f"{path}: the capture has no '{ENVIRONMENT_PLANE}' plane with profile_start_time")
    return {
        "profile_start_ns": int(start),
        "length_ns": int(stop) - int(start),
        "device_ops": device_ops,
        "annotations": sorted(annotations, key=lambda a: a[1]),
    }
