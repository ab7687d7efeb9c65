"""Normalize compiled-graph artifacts into streams the lint rules consume.

Three views of one jitted function, increasingly late in the pipeline:

- **jaxpr** (``trace`` + ``iter_ops`` / ``iter_consts``): every equation of
  the ``ClosedJaxpr`` — including the bodies of ``pjit`` / ``scan`` /
  ``cond`` / ``custom_vjp`` calls — flattened into :class:`OpNode` records
  carrying the primitive name, the ``jax.named_scope`` path the op was
  traced under (PR 1 threads these through the model), operand/result
  shapes+dtypes, and the eqn params. Closed-over array constants become
  :class:`ConstInfo` records (a weight baked into the graph shows up here,
  not in the arguments).
- **lowered StableHLO** (``lower``): the pre-optimization module text, plus
  any "donated buffers were not usable" warnings jax emits while lowering
  (XLA:CPU drops donation at this point — the warning is the only trace).
- **compiled HLO** (``compile_text``): the post-optimization executable
  text — the only place GSPMD-inserted collectives and committed
  input/output buffer aliases exist (``collective_counts`` /
  ``count_output_aliases`` parse it).

Everything here is read-only inspection: no rule logic, no severities —
that lives in :mod:`perceiver_io_tpu.analysis.rules`.
"""

from __future__ import annotations

import dataclasses
import math
import re
import warnings
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr

from perceiver_io_tpu.obs.xplane import op_scope


@dataclasses.dataclass(frozen=True)
class AvalInfo:
    """Shape/dtype of one operand or result."""

    shape: Tuple[int, ...]
    dtype: str

    @property
    def numel(self) -> int:
        n = 1
        for d in self.shape:
            n *= int(d)
        return n


@dataclasses.dataclass(frozen=True)
class OpNode:
    """One jaxpr equation, with scope attribution."""

    primitive: str
    scope: str  # named_scope path, e.g. "prefill/cross_attend"; "" at top
    invars: Tuple[AvalInfo, ...]
    outvars: Tuple[AvalInfo, ...]
    params: Dict[str, Any]  # eqn params with nested jaxprs stripped
    depth: int  # nesting depth of enclosing call equations


@dataclasses.dataclass(frozen=True)
class ConstInfo:
    """One closed-over array constant of the traced graph."""

    shape: Tuple[int, ...]
    dtype: str
    nbytes: int
    scope: str  # name stack of the call eqn whose body closes over it


def _aval_info(v) -> Optional[AvalInfo]:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return None
    return AvalInfo(tuple(int(d) for d in shape), str(dtype))


def _scope_of(eqn) -> str:
    stack = getattr(eqn.source_info, "name_stack", None)
    return "" if stack is None else str(stack)


def _join_scope(outer: str, inner: str) -> str:
    if not outer:
        return inner
    if not inner or inner == outer or inner.startswith(outer + "/"):
        # inner stacks usually repeat the full path already — don't double it
        return inner or outer
    return f"{outer}/{inner}"


def _sub_jaxprs(value) -> List[Jaxpr]:
    """Jaxpr bodies hiding in one eqn param value (pjit/scan carry a
    ClosedJaxpr, cond a tuple of branches, custom_vjp nested callables)."""
    out: List[Jaxpr] = []
    if isinstance(value, ClosedJaxpr):
        out.append(value.jaxpr)
    elif isinstance(value, Jaxpr):
        out.append(value)
    elif isinstance(value, (tuple, list)):
        for v in value:
            out.extend(_sub_jaxprs(v))
    return out


def trace(fn, *args, **kwargs) -> ClosedJaxpr:
    """``jax.make_jaxpr`` with kwargs support — the jaxpr view of ``fn``.

    Trace-time feature flags (``fast_kernels`` etc.) must be active around
    this call, exactly as they must be active around ``jax.jit``."""
    if kwargs:
        return jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    return jax.make_jaxpr(fn)(*args)


def iter_ops(closed: ClosedJaxpr) -> Iterator[OpNode]:
    """Every equation of ``closed`` and all nested call bodies, in program
    order, as :class:`OpNode` records."""
    stack: List[Tuple[Jaxpr, str, int]] = [(closed.jaxpr, "", 0)]
    while stack:
        jpr, outer_scope, depth = stack.pop()
        for eqn in jpr.eqns:
            scope = _join_scope(outer_scope, _scope_of(eqn))
            subs: List[Jaxpr] = []
            params: Dict[str, Any] = {}
            for k, v in eqn.params.items():
                nested = _sub_jaxprs(v)
                if nested:
                    subs.extend(nested)
                else:
                    params[k] = v
            yield OpNode(
                primitive=eqn.primitive.name,
                scope=scope,
                invars=tuple(a for a in (_aval_info(v) for v in eqn.invars) if a),
                outvars=tuple(a for a in (_aval_info(v) for v in eqn.outvars) if a),
                params=params,
                depth=depth,
            )
            for sub in subs:
                stack.append((sub, scope, depth + 1))


def iter_consts(closed: ClosedJaxpr) -> Iterator[ConstInfo]:
    """Array constants closed over anywhere in the graph, deduplicated by
    object identity (a const threaded through nested call bodies counts
    once — at its outermost appearance)."""
    seen: set = set()
    stack: List[Tuple[ClosedJaxpr, str]] = [(closed, "")]
    while stack:
        cj, scope = stack.pop()
        for const in cj.consts:
            if id(const) in seen:
                continue
            seen.add(id(const))
            shape = getattr(const, "shape", None)
            dtype = getattr(const, "dtype", None)
            if shape is None or dtype is None:
                continue  # python scalars etc.
            # from shape x itemsize: jax's typed ndarray consts carry no nbytes
            nbytes = math.prod(int(d) for d in shape) * np.dtype(dtype).itemsize
            yield ConstInfo(tuple(int(d) for d in shape), str(dtype), nbytes, scope)
        for eqn in cj.jaxpr.eqns:
            scope = _scope_of(eqn)
            for v in eqn.params.values():
                if isinstance(v, ClosedJaxpr):
                    stack.append((v, scope))
                elif isinstance(v, (tuple, list)):
                    for item in v:
                        if isinstance(item, ClosedJaxpr):
                            stack.append((item, scope))


_DONATION_DROPPED_RE = re.compile(r"donated buffers were not usable", re.IGNORECASE)


def lower(fn, args=(), kwargs=None, donate_argnums: Tuple[int, ...] = ()):
    """Lower ``fn`` and capture jax's dropped-donation warnings.

    Returns ``(lowered, dropped_donation_messages)``. A function that is
    already jitted (has ``.lower``) is lowered as-is — its own
    ``donate_argnums`` apply; otherwise it is wrapped in ``jax.jit`` with
    the given ``donate_argnums``."""
    kwargs = kwargs or {}
    target = fn if hasattr(fn, "lower") else jax.jit(fn, donate_argnums=donate_argnums)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        lowered = target.lower(*args, **kwargs)
    dropped = [str(w.message) for w in caught if _DONATION_DROPPED_RE.search(str(w.message))]
    return lowered, dropped


def compile_text(lowered) -> str:
    """Post-optimization HLO text of the compiled executable."""
    return lowered.compile().as_text()


# collective ops as they appear in optimized HLO (plus their async -start
# split forms, whose result type is a TUPLE — hence the paren alternative);
# GSPMD emits these — the jaxpr has no trace of them unless the program used
# shard_map/pmap explicitly
COLLECTIVE_KINDS = (
    "all-gather", "all-reduce", "reduce-scatter", "collective-permute", "all-to-all",
)

_COLLECTIVE_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)"
    r"(?:-start)?\("
)


def collective_counts(hlo_text: str) -> Dict[str, int]:
    """Occurrences of each collective op kind in compiled HLO text (async
    ``-start`` forms count once; their ``-done`` halves are not counted)."""
    counts: Dict[str, int] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        kind = m.group(1)
        counts[kind] = counts.get(kind, 0) + 1
    return counts


# ---------------------------------------------------------- HLO text parsing

# bytes per element of the HLO primitive types that appear in these programs
_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z]\w*)\[([\d,]*)\]")
_COMP_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->")
_INSTR_RE = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"([\w\-]+)\(")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


@dataclasses.dataclass(frozen=True)
class HloInstr:
    """One instruction of a compiled-HLO computation, as parsed from text."""

    name: str
    opcode: str
    operands: Tuple[str, ...]  # operand instruction names (same computation)
    scope: str  # the path of obs.xplane.op_scope (the one rule) over metadata op_name, '' when none
    line: str


def _shape_bytes(text: str) -> int:
    """Total bytes of every array shape literal in ``text`` (an estimate:
    result-type tokens like ``f32[128,256]{1,0}``; layout braces ignored)."""
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dtype, dims = m.group(1), m.group(2)
        itemsize = _HLO_DTYPE_BYTES.get(dtype)
        if itemsize is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * itemsize
    return total


def parse_hlo_computations(hlo_text: str) -> Dict[str, List[HloInstr]]:
    """Split compiled HLO text into computations of :class:`HloInstr`, in
    scheduled (textual) order, with operand edges resolved within each
    computation. Robust to tuple result types (async ``-start`` ops) and to
    attribute noise after the operand list."""
    comps: Dict[str, List[HloInstr]] = {}
    names_in_comp: set = set()
    cur: Optional[str] = None
    for raw in hlo_text.splitlines():
        header = _COMP_HEADER_RE.match(raw)
        if header and raw.rstrip().endswith("{"):
            cur = header.group(1)
            comps[cur] = []
            names_in_comp = set()
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(raw)
        if not m:
            continue
        name, rest = m.group(1), m.group(2)
        # strip the result type: a parenthesized tuple or one token
        if rest.startswith("("):
            depth = 0
            for j, ch in enumerate(rest):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        break
            body = rest[j + 1 :].lstrip()
        else:
            parts = rest.split(None, 1)
            body = parts[1] if len(parts) > 1 else parts[0]
        om = _OPCODE_RE.match(body)
        if not om:
            continue
        # operand list: up to the matching close paren of the opcode's paren
        seg = body[om.end():]
        depth, j = 1, 0
        while j < len(seg) and depth:
            if seg[j] == "(":
                depth += 1
            elif seg[j] == ")":
                depth -= 1
            j += 1
        operands = tuple(
            op for op in re.findall(r"%([\w.\-]+)", seg[:j]) if op in names_in_comp
        )
        named = _OP_NAME_RE.search(raw)
        comps[cur].append(
            HloInstr(name, om.group(1), operands, op_scope(named.group(1)).path if named else "", raw.strip())
        )
        names_in_comp.add(name)
    return comps


def collective_stats(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """Per-kind collective ``{count, bytes}`` over a compiled module — the
    collective table graphcheck's fingerprints and ``chip_smoke.py --chips 4``
    record. ``bytes`` is an *estimate* from the result-type shape literals of
    each collective instruction (async ``-start`` tuples include the operand
    alias, so async modules over-count roughly 2x — comparable run-over-run,
    not an exact traffic meter)."""
    stats: Dict[str, Dict[str, int]] = {}
    for instrs in parse_hlo_computations(hlo_text).values():
        for ins in instrs:
            for kind in COLLECTIVE_KINDS:
                if ins.opcode == kind or ins.opcode == kind + "-start":
                    s = stats.setdefault(kind, {"count": 0, "bytes": 0})
                    s["count"] += 1
                    # result type sits between "= " and the opcode
                    head = ins.line.split(ins.opcode + "(", 1)[0]
                    s["bytes"] += _shape_bytes(head)
                    break
    return stats


_NUM_PARTITIONS_RE = re.compile(r"num_partitions=(\d+)")


def hlo_num_partitions(hlo_text: str) -> int:
    """SPMD partition count from the HloModule header (1 when absent —
    a single-device module)."""
    m = _NUM_PARTITIONS_RE.search(hlo_text)
    return int(m.group(1)) if m else 1


def count_output_aliases(hlo_text: str) -> int:
    """Number of parameter buffers the compiled module aliases into outputs
    (the committed form of ``donate_argnums``). 0 means every donation was
    dropped (or none was declared)."""
    # syntax (on the HloModule line): input_output_alias={ {0}: (0, {},
    # may-alias), {1}: (2, {}) } — nested braces, so regex alone can't
    # delimit it; brace-count from the opening "{". One "(param, ...)"
    # group per aliased output.
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return 0
    i = hlo_text.index("{", start)
    depth, j = 0, i
    while j < len(hlo_text):
        if hlo_text[j] == "{":
            depth += 1
        elif hlo_text[j] == "}":
            depth -= 1
            if depth == 0:
                break
        j += 1
    return hlo_text[i:j].count("(")
