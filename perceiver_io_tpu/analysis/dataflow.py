"""Value-flow (def-use / provenance) analysis over the traced jaxpr.

:mod:`graph` answers "which ops exist, under which scope"; this module
answers **"where does this value come from and who consumes it"**. The
builder inlines every sub-jaxpr the call-like primitives carry —
``jit`` / ``scan`` / ``while`` / ``cond`` / ``shard_map`` /
``custom_jvp_call`` / ``custom_vjp_call`` / ``remat`` — binding inner
jaxpr variables to the SAME value nodes as the outer operands, so a
def-use chain crosses call boundaries the way data actually does. On top
of the graph sit the four dataflow analyses the :mod:`rules` consume:

- :func:`rng_reuse_findings` / :func:`replicated_key_findings` — PRNG key
  identities (``random_split`` rows are told apart by their static slice
  indices) consumed by two draws, and keys entering a ``shard_map`` region
  replicated that reach a draw with no device-index ``fold_in`` (the PR-4
  replicated-dropout-key class);
- :func:`live_node_ids` / :func:`dead_nodes` — reachability to the jaxpr
  outputs or an effect (the dead-compute rule weights the rest by
  :func:`node_flops`);
- :func:`propagate_shardings` — forward abstract interpretation of the
  declared input ``PartitionSpec``s, predicting GSPMD reshard points
  (mismatched-axis joins, slices of a sharded dim) BEFORE compile;
- :func:`cache_sites` — the KV-cache append inventory (layout, dtype and
  append-index provenance) the cross-program rule compares between the
  prefill and decode programs.

Everything is trace-level: no lowering, no compile. Provenance chains
render as one op per line via :meth:`Dataflow.render_chain`.
"""

from __future__ import annotations

import dataclasses
from fnmatch import fnmatch
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

from perceiver_io_tpu.analysis import graph as G
from perceiver_io_tpu.analysis.graph import _join_scope, _scope_of


@dataclasses.dataclass
class DfValue:
    """One SSA value of the threaded graph."""

    vid: int
    aval: Optional[G.AvalInfo]
    kind: str  # "op" | "input" | "const" | "literal" | "adapter"
    label: str  # "arg3" for inputs; the defining primitive for op values
    def_nid: Optional[int]
    uses: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class DfNode:
    """One equation, with value-level operand/result edges."""

    nid: int
    primitive: str
    scope: str
    depth: int
    params: Dict[str, Any]  # eqn params with nested jaxprs stripped
    invals: Tuple[int, ...]
    outvals: Tuple[int, ...]
    parent: Optional[int]  # enclosing call-equation node id
    region: Tuple[str, ...]  # primitives of the enclosing call eqns
    effectful: bool


# call-like primitives the builder threads through (everything else with a
# nested jaxpr — sort comparators, custom roots — stays an opaque node)
CALL_PRIMS = frozenset(
    {
        "jit", "closed_call", "call", "remat2", "scan", "while", "cond",
        "shard_map", "custom_jvp_call", "custom_vjp_call",
    }
)


class Dataflow:
    """The threaded value graph of one traced function."""

    def __init__(self):
        self.nodes: List[DfNode] = []
        self.values: List[DfValue] = []
        self.input_vids: List[int] = []  # top-level jaxpr invars, in order
        self.output_vids: List[int] = []  # top-level jaxpr outvars, in order
        # value-to-value flow edges the call threading introduces (body
        # outputs -> eqn outputs, scan xs -> per-iteration slices, loopback)
        self.alias_src: Dict[int, List[int]] = {}  # dst vid -> src vids
        self.alias_dst: Dict[int, List[int]] = {}  # src vid -> dst vids
        self.loop_vids: Set[int] = set()  # carry binders fed by a loopback

    # ------------------------------------------------------------- queries

    def def_node(self, vid: int) -> Optional[DfNode]:
        nid = self.values[vid].def_nid
        return None if nid is None else self.nodes[nid]

    def uses_of(self, vid: int) -> List[DfNode]:
        return [self.nodes[n] for n in self.values[vid].uses]

    def enclosing(self, nid: int, primitive: str) -> Optional[int]:
        """Nearest ancestor call node of ``primitive`` (None when outside)."""
        cur = self.nodes[nid].parent
        while cur is not None:
            if self.nodes[cur].primitive == primitive:
                return cur
            cur = self.nodes[cur].parent
        return None

    def _step(self, item: Tuple[str, int], forward: bool):
        """Successors (forward) / predecessors (backward) of one bipartite
        item ``("v", vid)`` or ``("n", nid)``."""
        kind, idx = item
        if kind == "v":
            if forward:
                for n in self.values[idx].uses:
                    yield ("n", n)
                for dst in self.alias_dst.get(idx, ()):
                    yield ("v", dst)
            else:
                if self.values[idx].def_nid is not None:
                    yield ("n", self.values[idx].def_nid)
                for src in self.alias_src.get(idx, ()):
                    yield ("v", src)
        else:
            node = self.nodes[idx]
            for v in (node.outvals if forward else node.invals):
                yield ("v", v)

    def _reach(self, seeds: Iterable[Tuple[str, int]], forward: bool) -> Set[Tuple[str, int]]:
        seen: Set[Tuple[str, int]] = set(seeds)
        stack = list(seen)
        while stack:
            item = stack.pop()
            for nxt in self._step(item, forward):
                if nxt not in seen:
                    seen.add(nxt)
                    stack.append(nxt)
        return seen

    def forward_node_ids(self, vids: Iterable[int]) -> Set[int]:
        """Node ids reachable downstream of any of ``vids``."""
        return {i for k, i in self._reach([("v", v) for v in vids], True) if k == "n"}

    def backward_node_ids(self, vids: Iterable[int]) -> Set[int]:
        """Node ids upstream of any of ``vids``."""
        return {i for k, i in self._reach([("v", v) for v in vids], False) if k == "n"}

    # ------------------------------------------------------- liveness / DCE

    def live_node_ids(self) -> Set[int]:
        """Nodes whose work can reach a jaxpr output or an effect."""
        seeds: List[Tuple[str, int]] = [("v", v) for v in self.output_vids]
        effectful = [n for n in self.nodes if n.effectful]
        seeds += [("n", n.nid) for n in effectful]
        seeds += [("v", v) for n in effectful for v in n.invals]
        return {i for k, i in self._reach(seeds, False) if k == "n"} | {
            n.nid for n in effectful
        }

    def dead_nodes(self) -> List[DfNode]:
        """Nodes (call boundaries excluded — their dead bodies are reported
        op by op) whose outputs reach neither an output nor an effect."""
        live = self.live_node_ids()
        return [
            n for n in self.nodes
            if n.nid not in live and n.primitive not in CALL_PRIMS
        ]

    # --------------------------------------------------- provenance chains

    def find_chain(self, src_nid: int, dst_nid: int) -> Optional[List[DfNode]]:
        """Shortest dataflow path from ``src_nid`` to ``dst_nid`` (BFS over
        the value graph), as the sequence of ops along it — or None.

        Call-boundary nodes also carry a conservative operand->output edge
        (liveness needs it for opaque calls); the chain search first blocks
        passing THROUGH threaded call nodes so the path routes via the
        actual body ops, and falls back to the shortcut edges only when no
        body path exists."""
        return self._find_chain(src_nid, dst_nid, block_calls=True) or self._find_chain(
            src_nid, dst_nid, block_calls=False
        )

    def _find_chain(
        self, src_nid: int, dst_nid: int, block_calls: bool
    ) -> Optional[List[DfNode]]:
        from collections import deque

        start = ("n", src_nid)
        prev: Dict[Tuple[str, int], Tuple[str, int]] = {}
        q = deque([start])
        seen = {start}
        goal = ("n", dst_nid)
        while q:
            item = q.popleft()
            if item == goal:
                chain: List[DfNode] = []
                cur: Optional[Tuple[str, int]] = item
                while cur is not None:
                    if cur[0] == "n":
                        chain.append(self.nodes[cur[1]])
                    cur = prev.get(cur)
                return chain[::-1]
            if (
                block_calls
                and item[0] == "n"
                and item != start
                and self.nodes[item[1]].primitive in CALL_PRIMS
            ):
                continue  # route through the body, not over the boundary
            for nxt in self._step(item, True):
                if nxt not in seen:
                    seen.add(nxt)
                    prev[nxt] = item
                    q.append(nxt)
        return None

    def render_chain(self, chain: Sequence[DfNode], max_ops: int = 8) -> str:
        """One op per line: ``primitive dtype[shape] @ scope``, the scope
        path from source to sink. Long chains elide the middle."""
        if len(chain) > max_ops:
            head = (max_ops + 1) // 2
            tail = max_ops - head
            rows = list(chain[:head]) + [None] + list(chain[-tail:])
            elided = len(chain) - max_ops
        else:
            rows, elided = list(chain), 0
        lines = []
        for i, node in enumerate(rows):
            arrow = "" if i == 0 else "-> "
            if node is None:
                lines.append(f"{arrow}... ({elided} ops)")
                continue
            aval = None
            if node.outvals:
                aval = self.values[node.outvals[0]].aval
            sig = f"{aval.dtype}[{'x'.join(map(str, aval.shape))}]" if aval else "?"
            lines.append(f"{arrow}{node.primitive} {sig} @ {node.scope or '<top>'}")
        return "\n".join(lines)

    def provenance(self, src_nid: int, dst_nid: int, max_ops: int = 8) -> Optional[str]:
        chain = self.find_chain(src_nid, dst_nid)
        return None if chain is None else self.render_chain(chain, max_ops=max_ops)

    def provenance_to_input(self, nid: int, max_ops: int = 8) -> str:
        """Greedy upstream walk from ``nid`` to a graph input/const — the
        "where did this come from" rendering when no specific source op is
        known."""
        chain = [self.nodes[nid]]
        cur = self.nodes[nid]
        seen = {nid}
        while True:
            step = None
            for vid in cur.invals:
                src = self._resolve_def(vid)
                if src is not None and src.nid not in seen:
                    step = src
                    break
            if step is None:
                break
            seen.add(step.nid)
            chain.append(step)
            cur = step
        return self.render_chain(chain[::-1], max_ops=max_ops)

    def _resolve_def(self, vid: int, _guard: Optional[Set[int]] = None) -> Optional[DfNode]:
        """The op defining ``vid``, following alias edges (body outputs,
        loopbacks) to the real producer."""
        _guard = _guard or set()
        if vid in _guard:
            return None
        _guard.add(vid)
        srcs = self.alias_src.get(vid)
        if srcs:
            return self._resolve_def(srcs[0], _guard)
        nid = self.values[vid].def_nid
        return None if nid is None else self.nodes[nid]


# ------------------------------------------------------------------ builder


def _as_body(value) -> Tuple[Optional[Jaxpr], tuple]:
    """``(jaxpr, consts)`` of a Jaxpr/ClosedJaxpr param value."""
    if isinstance(value, ClosedJaxpr):
        return value.jaxpr, tuple(value.consts)
    if isinstance(value, Jaxpr):
        return value, ()
    return None, ()


class _Builder:
    def __init__(self):
        self.df = Dataflow()
        self.env: Dict[Any, int] = {}  # Var -> vid

    # -- values -----------------------------------------------------------

    def new_value(self, aval, kind: str, label: str = "", def_nid=None) -> int:
        vid = len(self.df.values)
        self.df.values.append(DfValue(vid, aval, kind, label, def_nid))
        return vid

    def alias(self, src: int, dst: int, loop: bool = False) -> None:
        self.df.alias_src.setdefault(dst, []).append(src)
        self.df.alias_dst.setdefault(src, []).append(dst)
        if loop:
            self.df.loop_vids.add(dst)

    def read(self, atom) -> int:
        if isinstance(atom, Literal):
            return self.new_value(G._aval_info(atom), "literal", repr(atom.val))
        vid = self.env.get(atom)
        if vid is None:  # unbound var (defensive): treat as an input
            vid = self.new_value(G._aval_info(atom), "input", "unbound")
            self.env[atom] = vid
        return vid

    def bind(self, var, vid: int) -> None:
        if type(var).__name__ == "DropVar":
            return
        self.env[var] = vid

    def bind_consts(self, jaxpr: Jaxpr, consts: tuple, scope: str) -> None:
        for cv, c in zip(jaxpr.constvars, consts):
            self.bind(cv, self.new_value(G._aval_info(cv), "const", scope))

    # -- nodes ------------------------------------------------------------

    def add_node(
        self, eqn, scope, depth, parent, region, invals, n_out_fresh=True
    ) -> DfNode:
        params = {}
        for k, v in eqn.params.items():
            body, _ = _as_body(v)
            nested = body is not None or (
                isinstance(v, (tuple, list)) and any(_as_body(x)[0] is not None for x in v)
            )
            if not nested:
                params[k] = v
        nid = len(self.df.nodes)
        outvals = tuple(
            self.new_value(G._aval_info(v), "op", eqn.primitive.name, def_nid=nid)
            for v in eqn.outvars
        )
        node = DfNode(
            nid=nid,
            primitive=eqn.primitive.name,
            scope=scope,
            depth=depth,
            params=params,
            invals=tuple(invals),
            outvals=outvals,
            parent=parent,
            region=region,
            effectful=bool(getattr(eqn, "effects", None)),
        )
        self.df.nodes.append(node)
        for v in invals:
            self.df.values[v].uses.append(nid)
        return node

    # -- walking ----------------------------------------------------------

    def walk(self, jaxpr: Jaxpr, scope: str, depth: int, parent, region) -> None:
        for eqn in jaxpr.eqns:
            eqn_scope = _join_scope(scope, _scope_of(eqn))
            prim = eqn.primitive.name
            invals = [self.read(v) for v in eqn.invars]
            handler = getattr(self, f"_call_{prim}", None)
            if prim in CALL_PRIMS:
                handler = handler or self._call_generic
                handler(eqn, eqn_scope, depth, parent, region, invals)
            else:
                node = self.add_node(eqn, eqn_scope, depth, parent, region, invals)
                for var, vid in zip(eqn.outvars, node.outvals):
                    self.bind(var, vid)

    def _finish_call(self, eqn, node: DfNode, body_out_vids: Sequence[int]) -> None:
        """Bind eqn outvars to the node's fresh outputs and alias the body
        outputs into them (the actual flow)."""
        for var, vid in zip(eqn.outvars, node.outvals):
            self.bind(var, vid)
        for src, dst in zip(body_out_vids, node.outvals):
            self.alias(src, dst)

    def _call_generic(self, eqn, scope, depth, parent, region, invals) -> None:
        """jit / remat / closed_call / custom_jvp / custom_vjp: one body,
        operands aligned to the body's trailing invars (consts-first calling
        conventions keep their leading operands as plain node inputs)."""
        body = consts = None
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            body, consts = _as_body(eqn.params.get(key))
            if body is not None:
                break
        if body is None or len(body.invars) > len(invals):
            self.add_node(eqn, scope, depth, parent, region, invals)
            for var, vid in zip(eqn.outvars, self.df.nodes[-1].outvals):
                self.bind(var, vid)
            return
        node = self.add_node(eqn, scope, depth, parent, region, invals)
        self.bind_consts(body, consts, scope)
        for var, vid in zip(body.invars, invals[len(invals) - len(body.invars):]):
            self.bind(var, vid)
        self.walk(body, scope, depth + 1, node.nid, region + (eqn.primitive.name,))
        self._finish_call(eqn, node, [self.read(v) for v in body.outvars])

    def _call_scan(self, eqn, scope, depth, parent, region, invals) -> None:
        body, consts = _as_body(eqn.params["jaxpr"])
        nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
        node = self.add_node(eqn, scope, depth, parent, region, invals)
        self.bind_consts(body, consts, scope)
        for var, vid in zip(body.invars[: nc + nk], invals[: nc + nk]):
            self.bind(var, vid)
        for var, xs_vid in zip(body.invars[nc + nk :], invals[nc + nk :]):
            adapter = self.new_value(G._aval_info(var), "adapter", "scan-x")
            self.alias(xs_vid, adapter)
            self.bind(var, adapter)
        self.walk(body, scope, depth + 1, node.nid, region + ("scan",))
        body_out = [self.read(v) for v in body.outvars]
        for carry_out, init_vid in zip(body_out[:nk], invals[nc : nc + nk]):
            self.alias(carry_out, init_vid, loop=True)
        self._finish_call(eqn, node, body_out)

    def _call_while(self, eqn, scope, depth, parent, region, invals) -> None:
        cond_j, cond_c = _as_body(eqn.params["cond_jaxpr"])
        body_j, body_c = _as_body(eqn.params["body_jaxpr"])
        cn, bn = eqn.params["cond_nconsts"], eqn.params["body_nconsts"]
        init = invals[cn + bn :]
        node = self.add_node(eqn, scope, depth, parent, region, invals)
        self.bind_consts(cond_j, cond_c, scope)
        for var, vid in zip(cond_j.invars, invals[:cn] + init):
            self.bind(var, vid)
        self.walk(cond_j, scope, depth + 1, node.nid, region + ("while",))
        self.bind_consts(body_j, body_c, scope)
        for var, vid in zip(body_j.invars, invals[cn : cn + bn] + init):
            self.bind(var, vid)
        self.walk(body_j, scope, depth + 1, node.nid, region + ("while",))
        body_out = [self.read(v) for v in body_j.outvars]
        for carry_out, init_vid in zip(body_out, init):
            self.alias(carry_out, init_vid, loop=True)
        self._finish_call(eqn, node, body_out)

    def _call_cond(self, eqn, scope, depth, parent, region, invals) -> None:
        node = self.add_node(eqn, scope, depth, parent, region, invals)
        operands = invals[1:]
        for branch in eqn.params["branches"]:
            bj, bc = _as_body(branch)
            if bj is None or len(bj.invars) != len(operands):
                continue
            self.bind_consts(bj, bc, scope)
            for var, vid in zip(bj.invars, operands):
                self.bind(var, vid)
            self.walk(bj, scope, depth + 1, node.nid, region + ("cond",))
            for src, dst in zip([self.read(v) for v in bj.outvars], node.outvals):
                self.alias(src, dst)
        for var, vid in zip(eqn.outvars, node.outvals):
            self.bind(var, vid)

    def _call_shard_map(self, eqn, scope, depth, parent, region, invals) -> None:
        body, consts = _as_body(eqn.params["jaxpr"])
        if body is None or len(body.invars) != len(invals):
            self._call_generic(eqn, scope, depth, parent, region, invals)
            return
        node = self.add_node(eqn, scope, depth, parent, region, invals)
        self.bind_consts(body, consts, scope)
        for var, vid in zip(body.invars, invals):
            self.bind(var, vid)
        self.walk(body, scope, depth + 1, node.nid, region + ("shard_map",))
        self._finish_call(eqn, node, [self.read(v) for v in body.outvars])


def build(closed: ClosedJaxpr) -> Dataflow:
    """The threaded value graph of a ``ClosedJaxpr`` (see :func:`analyze`
    for the trace-and-build convenience)."""
    b = _Builder()
    b.bind_consts(closed.jaxpr, tuple(closed.consts), "")
    for i, var in enumerate(closed.jaxpr.invars):
        vid = b.new_value(G._aval_info(var), "input", f"arg{i}")
        b.bind(var, vid)
        b.df.input_vids.append(vid)
    b.walk(closed.jaxpr, "", 0, None, ())
    b.df.output_vids = [b.read(v) for v in closed.jaxpr.outvars]
    return b.df


def analyze(fn, *args, **kwargs) -> Dataflow:
    """Trace ``fn`` (feature contexts apply, exactly as around ``jax.jit``)
    and build its :class:`Dataflow`."""
    return build(G.trace(fn, *args, **kwargs))


# ----------------------------------------------------------- FLOPs weights

# pure data movement: dead instances are bookkeeping noise, not lost compute
DATA_MOVEMENT_PRIMS = frozenset(
    {
        "reshape", "broadcast_in_dim", "transpose", "convert_element_type",
        "slice", "squeeze", "dynamic_slice", "dynamic_update_slice",
        "concatenate", "pad", "rev", "copy", "device_put",
        "bitcast_convert_type", "gather", "iota", "split",
        "random_wrap", "random_unwrap", "stop_gradient", "optimization_barrier",
    }
)


def node_flops(node: DfNode, values: Sequence[DfValue]) -> int:
    """Estimated FLOPs of one op: exact-ish for ``dot_general`` (2*M*N*K),
    the max operand/result element count for everything else."""
    out_numel = max((values[v].aval.numel for v in node.outvals if values[v].aval), default=0)
    in_numel = max((values[v].aval.numel for v in node.invals if values[v].aval), default=0)
    if node.primitive == "dot_general":
        dn = node.params.get("dimension_numbers")
        lhs = values[node.invals[0]].aval if node.invals else None
        if dn and lhs:
            (lc, _), _ = dn
            k = 1
            for d in lc:
                k *= lhs.shape[d]
            return 2 * out_numel * k
        return 2 * out_numel * max(in_numel, 1)
    if node.primitive == "conv_general_dilated":
        return 2 * out_numel * max(in_numel // max(out_numel, 1), 1)
    return max(out_numel, in_numel)


# ------------------------------------------------------------ RNG analyses

RANDOM_SINK_PRIMS = frozenset({"random_bits", "random_gamma", "threefry2x32"})
KEY_DERIVE_PRIMS = frozenset({"random_split", "random_fold_in", "random_seed"})
_KEY_PASSTHROUGH_PRIMS = frozenset(
    {
        "random_wrap", "random_unwrap", "convert_element_type", "copy",
        "device_put", "optimization_barrier", "reshape", "squeeze",
        "broadcast_in_dim", "transpose", "stop_gradient",
    }
)


def is_key_like(aval: Optional[G.AvalInfo]) -> bool:
    """A PRNG key value: a typed key array, or the raw ``uint32[..., 2]``
    threefry form."""
    if aval is None:
        return False
    if aval.dtype.startswith("key<"):
        return True
    return aval.dtype == "uint32" and bool(aval.shape) and aval.shape[-1] == 2


def _key_identity(df: Dataflow, vid: int, memo: Dict[int, tuple]) -> tuple:
    """A hashable identity for the entropy a key value carries: two values
    with the same identity yield IDENTICAL random draws. ``random_split``
    rows are distinguished by the static slice indices that extract them;
    anything dynamic or unrecognized is conservatively fresh."""
    if vid in memo:
        return memo[vid]
    memo[vid] = ("loop", vid)  # provisional: cycles (scan carries) stay fresh
    srcs = df.alias_src.get(vid)
    if srcs:
        out = _key_identity(df, srcs[0], memo) if len(srcs) == 1 else ("merge", vid)
        memo[vid] = out
        return out
    node = df.values[vid].def_nid
    if node is None:
        out = ("source", vid)
    else:
        n = df.nodes[node]
        if n.primitive in KEY_DERIVE_PRIMS:
            out = ("derive", n.nid)
        elif n.primitive in _KEY_PASSTHROUGH_PRIMS and n.invals:
            out = _key_identity(df, n.invals[0], memo)
        elif n.primitive == "slice" and n.invals:
            out = (
                _key_identity(df, n.invals[0], memo),
                "slice",
                tuple(n.params.get("start_indices", ())),
                tuple(n.params.get("limit_indices", ())),
            )
        else:
            out = ("op", n.nid)
    memo[vid] = out
    return out


@dataclasses.dataclass
class ReuseFinding:
    """One key identity drawn from more than once (or drawn AND re-derived
    from — the children correlate with the draw)."""

    kind: str  # "draw-draw" | "draw-derive"
    origin_nid: Optional[int]  # defining op of the shared identity
    sink_nids: Tuple[int, ...]
    derive_nids: Tuple[int, ...]


def rng_reuse_findings(df: Dataflow) -> List[ReuseFinding]:
    memo: Dict[int, tuple] = {}
    by_identity: Dict[tuple, Dict[str, list]] = {}
    for node in df.nodes:
        if node.primitive in RANDOM_SINK_PRIMS:
            kind = "sinks"
        elif node.primitive in KEY_DERIVE_PRIMS and node.primitive != "random_seed":
            kind = "derives"
        else:
            continue
        if not node.invals or not is_key_like(df.values[node.invals[0]].aval):
            continue
        ident = _key_identity(df, node.invals[0], memo)
        by_identity.setdefault(ident, {"sinks": [], "derives": []})[kind].append(node.nid)
    out: List[ReuseFinding] = []
    for ident, groups in by_identity.items():
        sinks, derives = groups["sinks"], groups["derives"]
        origin, root = None, ident
        while isinstance(root, tuple) and root and isinstance(root[0], tuple):
            root = root[0]  # unwrap slice identities down to the root event
        if isinstance(root, tuple) and root and root[0] in ("derive", "op"):
            origin = root[1]
        if len(sinks) >= 2:
            out.append(ReuseFinding("draw-draw", origin, tuple(sinks), tuple(derives)))
        elif sinks and derives:
            out.append(ReuseFinding("draw-derive", origin, tuple(sinks), tuple(derives)))
    return out


@dataclasses.dataclass
class ReplicatedKeyFinding:
    """A key that enters a ``shard_map`` region replicated and reaches a
    random draw without a device-index ``fold_in`` on the way — every
    shard draws the same randomness (the PR-4 bug class)."""

    shard_map_nid: int
    key_vid: int
    sink_nid: int


def _fold_is_device_varying(df: Dataflow, fold: DfNode, region_nid: int) -> bool:
    """Does this ``random_fold_in``'s data operand depend on a device index
    (``axis_index``) taken inside THIS region? An axis_index from a
    different (or nested) shard_map region varies over the wrong mesh axes
    and does not decorrelate this region's shards."""
    if len(fold.invals) < 2:
        return False
    upstream = df.backward_node_ids([fold.invals[1]])
    return any(
        df.nodes[n].primitive == "axis_index"
        and df.enclosing(n, "shard_map") == region_nid
        for n in upstream
    )


def replicated_key_findings(df: Dataflow) -> List[ReplicatedKeyFinding]:
    out: List[ReplicatedKeyFinding] = []
    for sm in df.nodes:
        if sm.primitive != "shard_map":
            continue
        in_specs = sm.params.get("in_specs") or ()
        replicated_keys = {
            vid
            for i, vid in enumerate(sm.invals)
            if i < len(in_specs)
            and not any(in_specs[i])
            and is_key_like(df.values[vid].aval)
        }
        if not replicated_keys:
            continue
        for node in df.nodes:
            if node.primitive not in RANDOM_SINK_PRIMS or not node.invals:
                continue
            if df.enclosing(node.nid, "shard_map") != sm.nid and node.parent != sm.nid:
                # only sinks inside THIS region (at any nesting depth)
                if sm.nid not in _ancestors(df, node.nid):
                    continue
            hit = _traces_to_replicated(df, node.invals[0], replicated_keys, sm.nid)
            if hit is not None:
                out.append(ReplicatedKeyFinding(sm.nid, hit, node.nid))
    return out


def _ancestors(df: Dataflow, nid: int) -> Set[int]:
    out: Set[int] = set()
    cur = df.nodes[nid].parent
    while cur is not None:
        out.add(cur)
        cur = df.nodes[cur].parent
    return out


def _traces_to_replicated(
    df: Dataflow, vid: int, replicated: Set[int], region_nid: int,
    _seen: Optional[Set[int]] = None,
) -> Optional[int]:
    """Walk the key ancestry of ``vid``; a device-varying ``fold_in`` ends
    the walk (safe), reaching a replicated region input returns it."""
    _seen = _seen if _seen is not None else set()
    if vid in _seen:
        return None
    _seen.add(vid)
    if vid in replicated:
        return vid
    for src in df.alias_src.get(vid, ()):
        hit = _traces_to_replicated(df, src, replicated, region_nid, _seen)
        if hit is not None:
            return hit
    nid = df.values[vid].def_nid
    if nid is None:
        return None
    node = df.nodes[nid]
    if node.primitive == "random_fold_in":
        if _fold_is_device_varying(df, node, region_nid):
            return None  # decorrelated per device: safe beyond this point
        return _traces_to_replicated(df, node.invals[0], replicated, region_nid, _seen)
    if node.primitive in KEY_DERIVE_PRIMS or node.primitive in _KEY_PASSTHROUGH_PRIMS \
            or node.primitive in ("slice", "squeeze"):
        if node.invals:
            return _traces_to_replicated(df, node.invals[0], replicated, region_nid, _seen)
    return None


# ------------------------------------------------- sharding-flow propagation

# per-value state: a tuple with one entry per dim — a tuple of mesh axis
# names, or None (unsharded/unknown on that dim)
Dims = Tuple[Optional[Tuple[str, ...]], ...]


@dataclasses.dataclass
class ShardingConflict:
    """A predicted GSPMD reshard point: the op's operand/result layouts
    cannot be satisfied without moving data across devices."""

    nid: int
    kind: str  # "mismatched-operands" | "sliced-sharded-dim" | "updated-sharded-dim" | "concat-on-sharded-dim"
    dim: int
    axes: Tuple[str, ...]


def _spec_to_dims(spec, ndim: int) -> Dims:
    """Normalize a ``PartitionSpec``-like (or None) to a per-dim tuple."""
    entries = tuple(spec) if spec is not None else ()
    out: List[Optional[Tuple[str, ...]]] = []
    for i in range(ndim):
        e = entries[i] if i < len(entries) else None
        if e is None:
            out.append(None)
        elif isinstance(e, (tuple, list)):
            out.append(tuple(str(a) for a in e) or None)
        else:
            out.append((str(e),))
    return tuple(out)


def propagate_shardings(
    df: Dataflow, input_specs: Sequence[Optional[object]]
) -> Tuple[List[ShardingConflict], Dict[int, Dims]]:
    """Forward-propagate declared input PartitionSpecs through the value
    graph and collect predicted reshard points.

    Deliberately conservative: only *definite* layout breaks are reported —
    an op joining two operands sharded by DIFFERENT mesh axes on the same
    dim, or a (dynamic_)slice / dynamic_update_slice that cuts a sharded
    dim (GSPMD realigns both with collective-permute / all-to-all class
    collectives when the result feeds real compute; a reduce-only consumer
    can let it mask instead, which is why the rule reports at warn
    severity). Dim shardings lost to unmodeled ops become *unknown*, which
    never conflicts — missing a reshard is possible, a prediction always
    names a genuine layout break. ``shard_map`` interiors are per-shard
    programs and are skipped; region outputs take their layout from
    ``out_specs``.
    """
    state: Dict[int, Dims] = {}
    for vid, spec in zip(df.input_vids, input_specs):
        aval = df.values[vid].aval
        if aval is not None and spec is not None:
            state[vid] = _spec_to_dims(spec, len(aval.shape))

    def get(vid: int, guard: Optional[Set[int]] = None) -> Optional[Dims]:
        aval = df.values[vid].aval

        def ranked(dims: Optional[Dims]) -> Optional[Dims]:
            # alias edges can cross rank changes (a scan's stacked xs vs its
            # per-iteration slice, body outputs vs stacked ys): a layout
            # whose rank does not match this value is meaningless here and
            # must become unknown, not shifted onto the wrong dims
            if dims is None:
                return None
            if aval is not None and len(dims) != len(aval.shape):
                return None
            return dims

        if vid in state:
            return ranked(state[vid])
        guard = guard or set()
        if vid in guard:
            return None
        guard.add(vid)
        srcs = df.alias_src.get(vid)
        if not srcs:
            return None
        dims = [d for d in (get(s, guard) for s in srcs) if d is not None]
        if not dims:
            return None
        first = dims[0]
        return ranked(first if all(d == first for d in dims) else None)

    conflicts: List[ShardingConflict] = []

    def sharded_axes(dims: Optional[Dims], d: int) -> Tuple[str, ...]:
        if dims is None or d >= len(dims) or dims[d] is None:
            return ()
        return dims[d]

    for node in df.nodes:
        if "shard_map" in node.region:
            continue  # per-shard interior: mesh layout does not apply
        prim = node.primitive
        if prim == "shard_map":
            out_specs = node.params.get("out_specs") or ()
            for i, vid in enumerate(node.outvals):
                aval = df.values[vid].aval
                if aval is None or i >= len(out_specs):
                    continue
                state[vid] = _spec_to_dims(out_specs[i], len(aval.shape))
            continue
        if prim in CALL_PRIMS:
            continue  # flow resolves through the threaded body aliases
        out_aval = df.values[node.outvals[0]].aval if node.outvals else None
        if out_aval is None:
            continue
        in_states = [get(v) for v in node.invals]
        in_avals = [df.values[v].aval for v in node.invals]

        if prim in ("slice", "dynamic_slice"):
            src, aval = (in_states[0], in_avals[0]) if in_states else (None, None)
            if src is not None and aval is not None:
                sizes = (
                    node.params.get("slice_sizes")
                    if prim == "dynamic_slice"
                    else tuple(
                        l - s
                        for s, l in zip(
                            node.params.get("start_indices", ()),
                            node.params.get("limit_indices", ()),
                        )
                    )
                )
                new = list(src)
                for d in range(min(len(aval.shape), len(sizes or ()))):
                    axes = sharded_axes(src, d)
                    if axes and sizes[d] != aval.shape[d]:
                        conflicts.append(
                            ShardingConflict(node.nid, "sliced-sharded-dim", d, axes)
                        )
                        new[d] = None
                state[node.outvals[0]] = tuple(new)
            continue
        if prim == "dynamic_update_slice":
            src = in_states[0] if in_states else None
            op_aval = in_avals[0] if in_avals else None
            upd_aval = in_avals[1] if len(in_avals) > 1 else None
            if src is not None and op_aval is not None and upd_aval is not None:
                for d in range(min(len(op_aval.shape), len(upd_aval.shape))):
                    axes = sharded_axes(src, d)
                    if axes and upd_aval.shape[d] != op_aval.shape[d]:
                        conflicts.append(
                            ShardingConflict(node.nid, "updated-sharded-dim", d, axes)
                        )
                state[node.outvals[0]] = src
            continue
        if prim == "concatenate":
            axis = int(node.params.get("dimension", -1))
            merged: List[Optional[Tuple[str, ...]]] = [None] * len(out_aval.shape)
            for st in in_states:
                if st is None:
                    continue
                for d in range(len(out_aval.shape)):
                    axes = sharded_axes(st, d)
                    if not axes:
                        continue
                    if d == axis:
                        conflicts.append(
                            ShardingConflict(node.nid, "concat-on-sharded-dim", d, axes)
                        )
                    elif merged[d] is None:
                        merged[d] = axes
                    elif merged[d] != axes:
                        conflicts.append(
                            ShardingConflict(node.nid, "mismatched-operands", d,
                                             tuple(merged[d]) + axes)
                        )
            if 0 <= axis < len(merged):
                merged[axis] = None  # the joined axis never keeps a layout
            state[node.outvals[0]] = tuple(merged)
            continue
        if prim == "broadcast_in_dim":
            src, aval = (in_states[0], in_avals[0]) if in_states else (None, None)
            if src is not None and aval is not None:
                bd = node.params.get("broadcast_dimensions", ())
                new: List[Optional[Tuple[str, ...]]] = [None] * len(out_aval.shape)
                for i, d in enumerate(bd):
                    if i < len(src) and aval.shape[i] > 1:
                        new[d] = src[i]
                state[node.outvals[0]] = tuple(new)
            continue
        if prim == "transpose":
            src = in_states[0] if in_states else None
            if src is not None:
                perm = node.params.get("permutation", ())
                state[node.outvals[0]] = tuple(
                    src[p] if p < len(src) else None for p in perm
                )
            continue
        if prim == "reshape":
            src, aval = (in_states[0], in_avals[0]) if in_states else (None, None)
            if src is not None and aval is not None:
                in_nontrivial = [d for d in aval.shape if d != 1]
                out_nontrivial = [d for d in out_aval.shape if d != 1]
                if in_nontrivial == out_nontrivial:
                    # only size-1 dims added/removed: carry shardings across
                    src_iter = [s for d, s in zip(aval.shape, src) if d != 1]
                    new, j = [], 0
                    for d in out_aval.shape:
                        if d == 1:
                            new.append(None)
                        else:
                            new.append(src_iter[j] if j < len(src_iter) else None)
                            j += 1
                    state[node.outvals[0]] = tuple(new)
            continue
        if prim in ("reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                    "reduce_and", "reduce_or", "argmax", "argmin"):
            src = in_states[0] if in_states else None
            if src is not None:
                axes = set(node.params.get("axes", ()))
                state[node.outvals[0]] = tuple(
                    s for d, s in enumerate(src) if d not in axes
                )
            continue
        if prim == "dot_general":
            dn = node.params.get("dimension_numbers")
            if dn and len(in_states) >= 2 and in_avals[0] and in_avals[1]:
                (lc, rc), (lb, rb) = dn
                lhs, rhs = in_states[0], in_states[1]
                new: List[Optional[Tuple[str, ...]]] = []
                for lbd, rbd in zip(lb, rb):
                    la, ra = sharded_axes(lhs, lbd), sharded_axes(rhs, rbd)
                    if la and ra and la != ra:
                        conflicts.append(
                            ShardingConflict(node.nid, "mismatched-operands",
                                             len(new), la + ra)
                        )
                    new.append(la or ra or None)
                for d in range(len(in_avals[0].shape)):
                    if d not in lc and d not in lb:
                        new.append(sharded_axes(lhs, d) or None)
                for d in range(len(in_avals[1].shape)):
                    if d not in rc and d not in rb:
                        new.append(sharded_axes(rhs, d) or None)
                if len(new) == len(out_aval.shape):
                    state[node.outvals[0]] = tuple(new)
            continue

        # elementwise-shaped (operands scalar or same-shape as the result):
        # merge operand layouts; different mesh axes on one dim = reshard
        elementwise = all(
            a is None or not a.shape or a.shape == out_aval.shape for a in in_avals
        )
        if elementwise and in_states:
            merged = [None] * len(out_aval.shape)
            conflicted = set()
            for st, aval in zip(in_states, in_avals):
                if st is None or aval is None or not aval.shape:
                    continue
                for d in range(len(out_aval.shape)):
                    axes = sharded_axes(st, d)
                    if not axes:
                        continue
                    if merged[d] is None:
                        merged[d] = axes
                    elif merged[d] != axes and d not in conflicted:
                        conflicted.add(d)
                        conflicts.append(
                            ShardingConflict(node.nid, "mismatched-operands", d,
                                             tuple(merged[d]) + axes)
                        )
            for vid in node.outvals:
                aval = df.values[vid].aval
                if aval is not None and len(aval.shape) == len(merged):
                    state[vid] = tuple(merged)
        # anything else: outputs stay unknown (never conflicts)
    return conflicts, state


# -------------------------------------------------------- cache-site survey


@dataclasses.dataclass
class CacheSite:
    """One KV-cache append under a cache scope: a ``dynamic_update_slice``
    (the contiguous discipline) or a ``scatter`` (the paged discipline's
    page-indexed write) — the layout facts the cross-program rule compares."""

    nid: int
    scope: str
    tail: str  # the scope path from the matched cache label on
    dtype: str
    rank: int
    update_dims: Tuple[int, ...]  # dims the append writes a sub-range of
    phase: str  # "loop" (inside scan/while) | "prompt"
    index_origin: str  # "carried" | "static" | "input" | "mixed"
    primitive: str = "dynamic_update_slice"
    # whether the write index's provenance passes through a gather — the
    # signature of a page-table-indexed append (the index is LOOKED UP from
    # a table, not carried directly); what the declared-paged-companion
    # branch of cross-program-consistency requires
    index_via_gather: bool = False

    @property
    def layout(self) -> tuple:
        return (self.tail, self.dtype, self.rank, self.update_dims)


def _index_origin(df: Dataflow, vids: Sequence[int]) -> str:
    kinds = set()
    for vid in vids:
        v = df.values[vid]
        if v.kind == "literal":
            kinds.add("static")
            continue
        upstream = df._reach([("v", vid)], forward=False)
        up_vids = {i for k, i in upstream if k == "v"}
        if up_vids & df.loop_vids:
            kinds.add("carried")
        elif any(df.values[i].kind == "input" for i in up_vids):
            kinds.add("input")
        elif all(
            df.values[i].kind in ("const", "literal")
            or df.values[i].def_nid is not None
            for i in up_vids
        ) and not any(df.values[i].kind == "input" for i in up_vids):
            kinds.add("static")
        else:
            kinds.add("other")
    if kinds <= {"static"}:
        return "static"
    if "carried" in kinds:
        return "carried"
    if kinds == {"input"} or kinds == {"input", "static"}:
        return "input"
    return "mixed"


def _index_via_gather(df: Dataflow, vids: Sequence[int]) -> bool:
    """Whether any write-index operand's backward provenance passes through
    a gather (``jnp.take``/``take_along_axis`` lower to it) — the
    page-table-lookup signature the paged companion check requires."""
    ups = df._reach([("v", v) for v in vids], forward=False)
    return any(
        k == "n" and df.nodes[i].primitive == "gather" for k, i in ups
    )


def cache_sites(
    df: Dataflow, scopes: Sequence[str] = ("*kv_cache_append*", "*paged_kv_append*")
) -> List[CacheSite]:
    """Every cache-append site whose scope matches one of the cache-scope
    patterns: ``dynamic_update_slice`` (contiguous discipline) and
    ``scatter`` (the paged discipline's page-indexed write, ``.at[ids,
    offs].set``)."""
    out: List[CacheSite] = []
    for node in df.nodes:
        if node.primitive not in ("dynamic_update_slice", "scatter"):
            continue
        if not any(fnmatch(node.scope, p) for p in scopes):
            continue
        op_aval = df.values[node.invals[0]].aval if node.invals else None
        upd_aval = df.values[node.invals[1]].aval if len(node.invals) > 1 else None
        if op_aval is None or upd_aval is None:
            continue
        if node.primitive == "scatter":
            # scatter eqn operands: (operand, scatter_indices, updates) —
            # the comparable "update" aval is the updates operand, and the
            # written dims are whatever the scatter's update window misses;
            # for layout purposes record no update_dims (the paged pools
            # have no per-request slot axis to compare)
            upd_aval = df.values[node.invals[2]].aval if len(node.invals) > 2 else upd_aval
            idx_vids = [node.invals[1]]
            update_dims: Tuple[int, ...] = ()
        else:
            idx_vids = list(node.invals[2:])
            update_dims = tuple(
                d
                for d in range(min(len(op_aval.shape), len(upd_aval.shape)))
                if upd_aval.shape[d] != op_aval.shape[d]
            )
        # the scope tail from the last segment matching a cache label on
        segments = node.scope.split("/")
        tail = node.scope
        for i in range(len(segments) - 1, -1, -1):
            if any(fnmatch(segments[i], p.strip("*") and f"*{p.strip('*')}*" or p)
                   for p in scopes):
                tail = "/".join(segments[i:])
                break
        in_loop = any(r in ("scan", "while") for r in node.region)
        out.append(
            CacheSite(
                nid=node.nid,
                scope=node.scope,
                tail=tail,
                dtype=op_aval.dtype,
                rank=len(op_aval.shape),
                update_dims=update_dims,
                phase="loop" if in_loop else "prompt",
                index_origin=_index_origin(df, idx_vids),
                primitive=node.primitive,
                index_via_gather=_index_via_gather(df, idx_vids),
            )
        )
    return out
