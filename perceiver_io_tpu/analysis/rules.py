"""Lint rules over the normalized graph views, with a registry.

Each rule is a function ``rule(ctx) -> list[Violation]`` registered under a
kebab-case name with a default severity and the graph views it needs
(``jaxpr`` — cheap, trace only; ``lowered`` / ``compiled`` — require
lowering/compiling the function). A rule whose policy inputs are absent
(e.g. ``dtype-drift`` with no declared bf16 scopes) returns nothing rather
than guessing — the policy is the declaration of intent the graph is
checked against.

Scope matching is ``fnmatch`` over the ``jax.named_scope`` path recorded on
each op (PR 1 threads these labels through the model: ``cross_attend``,
``prefill``, ``decode``, ``qkv_proj``, …), so rules attribute violations to
the module that traced the op, not just to a primitive index.
"""

from __future__ import annotations

import dataclasses
import re
from fnmatch import fnmatch
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from perceiver_io_tpu.analysis import graph as G

SEVERITIES = ("info", "warn", "error")


@dataclasses.dataclass(frozen=True)
class Violation:
    rule: str
    severity: str  # "info" | "warn" | "error"
    scope: str  # named_scope path of the offending op ("" = top level)
    message: str
    op: Optional[str] = None  # primitive / HLO op kind, when applicable

    @property
    def key(self) -> str:
        """The string allowlist entries match against: ``rule:scope``."""
        return f"{self.rule}:{self.scope or '<top>'}"

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["key"] = self.key
        return d


@dataclasses.dataclass
class LintPolicy:
    """What the caller declares about the function under lint — rules only
    fire against declared intent (plus the always-wrong cases)."""

    # dtype-drift: scopes declared to run bf16 compute (fnmatch patterns);
    # f32 matmul-class ops inside them are drift
    bf16_scopes: Tuple[str, ...] = ()
    # hot-concat: scopes where a materialized concatenate is a lost fusion
    # (attention/generation paths). Structural filters keep glue out: the
    # output must be a real activation (rank >= 3 — batch/seq/channels) and
    # the CONCATENATED axis must be long (>= min_concat_axis) — RoPE's
    # rotate-half and frequency-table concats join short channel axes and
    # pass, the [prefix; latents] kv build joins the sequence axis and fires
    hot_scopes: Tuple[str, ...] = (
        "*cross_attend*", "*self_attend*", "*attention*", "*attend*",
        "*decode*", "*prefill*", "*flash*", "*kv_concat*",
    )
    min_concat_numel: int = 1024
    min_concat_axis: int = 128
    # any concatenate whose OUTPUT has a dimension of one of these sizes
    # fires regardless of scope — the "this exact tensor must never be
    # built" form of the rule
    concat_dim_sizes: Tuple[int, ...] = ()
    # unsorted/non-unique gathers are only suspicious where a sorted or
    # fused access was the design (attention kv reads, decode cache reads)
    gather_scopes: Tuple[str, ...] = (
        "*cross_attend*", "*self_attend*", "*attend*", "*kv_cache*", "*flash*",
    )
    min_gather_numel: int = 1024
    # const-capture: array constants >= this many bytes baked into the
    # jaxpr are closed-over weights, not blessed epsilon tables
    const_bytes_limit: int = 1 << 16
    # donation-dropped: argnums the caller declares donated (for plain fns;
    # an already-jitted fn carries its own) — checked against the compiled
    # executable's committed input/output aliases
    donate_argnums: Tuple[int, ...] = ()
    expect_donation: bool = False  # require aliases even without argnums info
    # collective-budget: max allowed per compiled module, e.g.
    # {"all-gather": 2, "all-reduce": 1} or {"total": 4}; None disables
    collective_budget: Optional[Dict[str, int]] = None
    # peak-memory-budget: static budget (bytes) for the compiled module's
    # temp+argument buffers (analysis/memory.py breakdown:
    # compiled.memory_analysis() with an HLO-text fallback); None disables
    peak_memory_budget_bytes: Optional[int] = None
    # replicated-large-tensor: entry parameters >= this many bytes left
    # FULLY replicated in a partitioned (num_partitions > 1) module — under
    # a mesh with an fsdp axis, a large replicated tensor is per-device HBM
    # bought for nothing; None disables
    replicated_bytes_limit: Optional[int] = None
    # implicit-reshard: budget for the resharding collectives GSPMD inserts
    # when declared input/output shardings disagree with the compute
    # placement (all-to-all, collective-permute), e.g. {"collective-permute":
    # 2}; a missing kind allows 0 and {} allows none. None disables. Ring
    # attention's deliberate permutes must be budgeted by the caller.
    reshard_budget: Optional[Dict[str, int]] = None
    # rng-key-reuse (dataflow): armed when True — a PRNG key identity
    # consumed by >= 2 random draws with no split/fold_in between them, and
    # keys entering a shard_map region replicated (in_specs = P()) that
    # reach a draw without a device-index fold_in on the way (the PR-4
    # replicated-dropout-key class). Inert until declared.
    check_rng: bool = False
    # dead-compute (dataflow): armed when set — ops whose outputs reach
    # neither the jaxpr outputs nor an effect. FLOPs-weighted: a dead
    # matmul-class op at/over this many estimated FLOPs is an error, other
    # dead compute warn, dead data movement (reshape/broadcast/...) info.
    dead_compute_min_flops: Optional[int] = None
    # sharding-flow (dataflow): armed when declared — propagate input
    # PartitionSpecs forward through the jaxpr and report predicted GSPMD
    # reshard points BEFORE compile (the trace-time complement of the
    # compiled-HLO implicit-reshard rule). True reads the committed
    # NamedShardings off the (already device_put) args; or pass an explicit
    # flat tuple with one PartitionSpec (or None) per arg leaf.
    sharding_flow: Optional[object] = None
    # cross-program-consistency (dataflow): the companion program this one
    # must agree with on KV-cache layout, dtype and append-index provenance
    # (decode declares prefill as its companion). Inert until declared.
    companion: Optional["CompanionProgram"] = None
    # scope labels that mark cache-append sites (core/attention.py labels
    # its dynamic_update_slice writes "kv_cache_append"; the paged engine
    # labels its page-indexed scatters "paged_kv_append" — surveyed
    # everywhere so an undeclared paged append can never hide)
    cache_scopes: Tuple[str, ...] = ("*kv_cache_append*", "*paged_kv_append*")
    # cross-program-consistency, paged half: scope labels whose appends this
    # program DECLARES as page-table-indexed (the decode_paged program
    # declares "*paged_kv_append*"). A declared paged append must have a
    # dynamic write index whose provenance walks a table (gather) and a
    # dtype the companion's prompt pass actually builds; an UNdeclared
    # scatter-based cache append is flagged — the paged layout is a declared
    # companion, not an allowlist hole. Empty = this program has no paged
    # discipline.
    paged_cache_scopes: Tuple[str, ...] = ()
    # per-rule severity overrides, e.g. {"hot-concat": "warn"}
    severity_overrides: Dict[str, str] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class CompanionProgram:
    """The other half of a cross-program contract: a function + example
    args whose traced graph the linted program is checked against (the
    decode target names the prefill program here). The trace is built once
    and cached — repeated checks against one companion pay one trace."""

    name: str
    fn: object
    args: tuple
    kwargs: Optional[dict] = None
    _dataflow: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    def dataflow(self):
        if self._dataflow is None:
            from perceiver_io_tpu.analysis import dataflow as D

            self._dataflow = D.analyze(self.fn, *self.args, **(self.kwargs or {}))
        return self._dataflow


class RuleContext:
    """Lazily materialized graph views shared by all rules in one check."""

    def __init__(
        self,
        fn,
        args: tuple,
        kwargs: dict,
        policy: LintPolicy,
        closed_jaxpr=None,
    ):
        import jax

        self.fn = fn
        self.args = args
        self.kwargs = kwargs
        self.policy = policy
        self.backend = jax.default_backend()
        self._closed = closed_jaxpr
        self._ops: Optional[List[G.OpNode]] = None
        self._consts: Optional[List[G.ConstInfo]] = None
        self._lowered = None
        self._dropped_donations: Optional[List[str]] = None
        self._compiled = None
        self._compiled_text: Optional[str] = None
        self._dataflow = None

    @property
    def closed_jaxpr(self):
        if self._closed is None:
            self._closed = G.trace(self.fn, *self.args, **self.kwargs)
        return self._closed

    @property
    def ops(self) -> List[G.OpNode]:
        if self._ops is None:
            self._ops = list(G.iter_ops(self.closed_jaxpr))
        return self._ops

    @property
    def consts(self) -> List[G.ConstInfo]:
        if self._consts is None:
            self._consts = list(G.iter_consts(self.closed_jaxpr))
        return self._consts

    @property
    def dataflow(self):
        """The def-use/provenance graph (analysis/dataflow.py) — built once
        from the shared trace and reused by every dataflow rule."""
        if self._dataflow is None:
            from perceiver_io_tpu.analysis import dataflow as D

            self._dataflow = D.build(self.closed_jaxpr)
        return self._dataflow

    def _ensure_lowered(self):
        if self._lowered is None:
            self._lowered, self._dropped_donations = G.lower(
                self.fn, self.args, self.kwargs, donate_argnums=self.policy.donate_argnums
            )
        return self._lowered

    @property
    def dropped_donations(self) -> List[str]:
        self._ensure_lowered()
        return self._dropped_donations or []

    @property
    def compiled(self):
        """The compiled executable — shared by every compiled-level rule in
        one check, so text parsing and memory_analysis pay one compile."""
        if self._compiled is None:
            self._compiled = self._ensure_lowered().compile()
        return self._compiled

    @property
    def compiled_text(self) -> str:
        if self._compiled_text is None:
            self._compiled_text = self.compiled.as_text()
        return self._compiled_text


@dataclasses.dataclass(frozen=True)
class Rule:
    name: str
    default_severity: str
    needs: str  # "jaxpr" | "compiled"
    fn: Callable[[RuleContext], List[Violation]]
    doc: str


RULES: Dict[str, Rule] = {}


def register_rule(name: str, severity: str, needs: str, doc: str):
    """Register a rule under ``name``; see docs/static-analysis.md for the
    how-to-add-a-rule walkthrough this decorator anchors."""

    def deco(fn):
        RULES[name] = Rule(name, severity, needs, fn, doc)
        return fn

    return deco


def _severity(ctx: RuleContext, rule: str, default: Optional[str] = None) -> str:
    return ctx.policy.severity_overrides.get(rule, default or RULES[rule].default_severity)


def _match(scope: str, patterns: Sequence[str]) -> bool:
    return any(fnmatch(scope, p) for p in patterns)


# ---------------------------------------------------------------- the rules


# matmul-class compute: where running f32 instead of bf16 silently doubles
# MXU time and HBM traffic; elementwise f32 islands (softmax, norms) are
# deliberate numerics and not flagged
_COMPUTE_PRIMS = ("dot_general", "conv_general_dilated")


@register_rule(
    "dtype-drift",
    severity="error",
    needs="jaxpr",
    doc="f32 matmul-class ops inside a declared-bf16 scope (unintended upcast)",
)
def dtype_drift(ctx: RuleContext) -> List[Violation]:
    pats = ctx.policy.bf16_scopes
    if not pats:
        return []
    out = []
    for op in ctx.ops:
        if op.primitive not in _COMPUTE_PRIMS:
            continue
        if not _match(op.scope, pats):
            continue
        f32_out = [o for o in op.outvars if o.dtype == "float32"]
        if not f32_out:
            continue
        out.append(
            Violation(
                rule="dtype-drift",
                severity=_severity(ctx, "dtype-drift"),
                scope=op.scope,
                op=op.primitive,
                message=(
                    f"{op.primitive} computes float32 "
                    f"{'x'.join(map(str, f32_out[0].shape))} inside a "
                    "declared-bf16 scope — unintended upcast "
                    "(preferred_element_type or a f32 operand leaking in?)"
                ),
            )
        )
    return out


@register_rule(
    "const-capture",
    severity="error",
    needs="jaxpr",
    doc="large array constants baked into the jaxpr (closed-over weights)",
)
def const_capture(ctx: RuleContext) -> List[Violation]:
    limit = ctx.policy.const_bytes_limit
    out = []
    for c in ctx.consts:
        if c.nbytes < limit:
            continue
        out.append(
            Violation(
                rule="const-capture",
                severity=_severity(ctx, "const-capture"),
                scope=c.scope,
                message=(
                    f"{c.dtype}[{'x'.join(map(str, c.shape))}] "
                    f"({c.nbytes / 1e6:.2f} MB) is baked into the graph as a "
                    "constant — a closed-over weight is re-staged on every "
                    "compile and excluded from donation/sharding; pass it as "
                    "an argument"
                ),
            )
        )
    return out


@register_rule(
    "hot-concat",
    severity="error",
    needs="jaxpr",
    doc="concatenate (or unsorted gather) materialized inside attention/generation scopes",
)
def hot_concat(ctx: RuleContext) -> List[Violation]:
    p = ctx.policy
    out = []
    for op in ctx.ops:
        if op.primitive == "concatenate":
            out_aval = op.outvars[0] if op.outvars else None
            axis = int(op.params.get("dimension", -1))
            big = (
                out_aval is not None
                and out_aval.numel >= p.min_concat_numel
                and len(out_aval.shape) >= 3
                and 0 <= axis < len(out_aval.shape)
                and out_aval.shape[axis] >= p.min_concat_axis
            )
            in_hot = _match(op.scope, p.hot_scopes) and big
            # forbidden-size check is on the CONCATENATED axis only — an
            # untouched axis that happens to equal the forbidden size (e.g.
            # a seq dim on a channel-axis RoPE concat) is not a kv build
            dim_hit = (
                p.concat_dim_sizes
                and out_aval is not None
                and 0 <= axis < len(out_aval.shape)
                and out_aval.shape[axis] in p.concat_dim_sizes
            )
            if not (in_hot or dim_hit):
                continue
            shape = "x".join(map(str, op.outvars[0].shape)) if op.outvars else "?"
            why = (
                f"builds a {shape} tensor with a forbidden dimension "
                f"(sizes {tuple(p.concat_dim_sizes)})"
                if dim_hit and not in_hot
                else f"materializes a {shape} tensor on the hot path"
            )
            out.append(
                Violation(
                    rule="hot-concat",
                    severity=_severity(ctx, "hot-concat"),
                    scope=op.scope,
                    op="concatenate",
                    message=f"concatenate {why} — feed the segments to the consumer "
                    "as separate operands",
                )
            )
        elif op.primitive == "gather":
            if not _match(op.scope, p.gather_scopes):
                continue
            if op.outvars and op.outvars[0].numel < p.min_gather_numel:
                continue
            if op.params.get("indices_are_sorted") or op.params.get("unique_indices"):
                continue
            shape = "x".join(map(str, op.outvars[0].shape)) if op.outvars else "?"
            out.append(
                Violation(
                    rule="hot-concat",
                    severity=_severity(ctx, "hot-concat", "warn"),
                    scope=op.scope,
                    op="gather",
                    message=(
                        f"unsorted non-unique gather ({shape}) in an attention "
                        "scope — its backward lowers to a serializing "
                        "scatter-add; use ops/gathers.py scatter-free routes"
                    ),
                )
            )
    return out


@register_rule(
    "donation-dropped",
    severity="error",
    needs="compiled",
    doc="declared donate_argnums whose buffers the compiled executable does not alias",
)
def donation_dropped(ctx: RuleContext) -> List[Violation]:
    p = ctx.policy
    declared = (
        bool(p.donate_argnums)
        or p.expect_donation
        or _fn_donates(ctx.fn)
        # authoritative across jax versions: the lowered module's args_info
        # records per-arg donation (pjit hides donate_argnums attributes) —
        # reached only when this rule runs, i.e. the compiled view was
        # already requested, so the lowering is not an extra cost
        or _lowered_donates(ctx)
    )
    if not declared:
        return []
    dropped = ctx.dropped_donations
    aliased = G.count_output_aliases(ctx.compiled_text)
    if aliased > 0 and not dropped:
        return []
    # on the CPU backend a dropped donation costs no HBM traffic: a warning
    # there, an error where it is a real per-step copy
    sev = "warn" if ctx.backend == "cpu" else _severity(ctx, "donation-dropped")
    detail = dropped[0] if dropped else "no input_output_alias in the compiled module"
    return [
        Violation(
            rule="donation-dropped",
            severity=sev,
            scope="",
            message=(
                "buffer donation was declared but not committed "
                f"({detail}) — the step pays a full params+opt-state copy "
                "of HBM traffic every call"
            ),
        )
    ]


def _fn_donates(fn) -> bool:
    """Best-effort attribute probe: does a jitted ``fn`` advertise its own
    donate_argnums? On the pinned jax 0.4.37 PjitFunction these attributes
    do not exist (always False) — :func:`_lowered_donates` is the
    authoritative check once a lowering is available; this probe only
    serves check()'s pre-lowering auto-compile decision on jax versions
    that do expose them."""
    for attr in ("_jit_info", "_fun"):
        info = getattr(fn, attr, None)
        if info is not None and getattr(info, "donate_argnums", None):
            return True
    return False


def _lowered_donates(ctx: RuleContext) -> bool:
    """Whether the lowered module's ``args_info`` marks any argument
    donated — the per-version-stable record of ``donate_argnums``."""
    import jax

    try:
        info = getattr(ctx._ensure_lowered(), "args_info", None)
        leaves = jax.tree_util.tree_leaves(
            info, is_leaf=lambda x: hasattr(x, "donated")
        )
        return any(getattr(x, "donated", False) for x in leaves)
    except Exception:  # noqa: BLE001 — a probe, not a gate
        return False


@register_rule(
    "collective-budget",
    severity="error",
    needs="compiled",
    doc="all-gather/all-reduce/reduce-scatter counts in the compiled module vs a declared budget",
)
def collective_budget(ctx: RuleContext) -> List[Violation]:
    budget = ctx.policy.collective_budget
    if budget is None:
        return []
    counts = G.collective_counts(ctx.compiled_text)
    out = []
    total_budget = budget.get("total")
    if total_budget is not None and sum(counts.values()) > total_budget:
        out.append(
            Violation(
                rule="collective-budget",
                severity=_severity(ctx, "collective-budget"),
                scope="",
                message=(
                    f"{sum(counts.values())} collectives in the compiled module "
                    f"exceed the declared total budget {total_budget} "
                    f"(breakdown: {counts})"
                ),
            )
        )
    for kind, n in sorted(counts.items()):
        cap = budget.get(kind)
        if cap is not None and n > cap:
            out.append(
                Violation(
                    rule="collective-budget",
                    severity=_severity(ctx, "collective-budget"),
                    scope="",
                    op=kind,
                    message=(
                        f"{n}x {kind} in the compiled module exceeds the "
                        f"declared budget {cap} — an implicit resharding "
                        "(GSPMD) crept into the step"
                    ),
                )
            )
    return out


@register_rule(
    "peak-memory-budget",
    severity="error",
    needs="compiled",
    doc="temp+argument bytes of the compiled module vs a declared static budget",
)
def peak_memory_budget(ctx: RuleContext) -> List[Violation]:
    budget = ctx.policy.peak_memory_budget_bytes
    if budget is None:
        return []
    from perceiver_io_tpu.analysis.memory import memory_breakdown

    mb = memory_breakdown(ctx.compiled)
    if mb.gate_bytes <= budget:
        return []
    return [
        Violation(
            rule="peak-memory-budget",
            severity=_severity(ctx, "peak-memory-budget"),
            scope="",
            message=(
                f"compiled module needs {mb.gate_bytes / 1e6:.1f} MB "
                f"(temp {mb.temp_bytes / 1e6:.1f} + args "
                f"{mb.argument_bytes / 1e6:.1f}, {mb.method}) — over the "
                f"declared {budget / 1e6:.1f} MB budget; a re-materialized "
                "activation or lost fusion grew the static footprint"
            ),
        )
    ]


# one entry parameter of a partitioned module, with its committed sharding:
# `%param.1 = f32[512,512]{1,0} parameter(1), sharding={replicated}` —
# fusion-internal parameters carry no sharding attribute, so matching the
# attribute restricts this to the entry computation's real inputs
_PARAM_SHARDING_RE = re.compile(
    r"=\s*(\S+)\s+parameter\(\d+\),\s*sharding=\{(replicated)\}"
)


@register_rule(
    "replicated-large-tensor",
    severity="error",
    needs="compiled",
    doc="large entry parameters left fully replicated in a partitioned module",
)
def replicated_large_tensor(ctx: RuleContext) -> List[Violation]:
    limit = ctx.policy.replicated_bytes_limit
    if limit is None:
        return []
    text = ctx.compiled_text
    if G.hlo_num_partitions(text) <= 1:
        return []  # single-device module: replication is not a choice
    out = []
    for line in text.splitlines():
        pm = _PARAM_SHARDING_RE.search(line)
        if pm is None:
            continue
        nbytes = G._shape_bytes(pm.group(1))
        if nbytes < limit:
            continue
        # the op_name of an entry parameter is the argument's own label
        name = G._OP_NAME_RE.search(line)
        scope = name.group(1) if name else ""
        out.append(
            Violation(
                rule="replicated-large-tensor",
                severity=_severity(ctx, "replicated-large-tensor"),
                scope=scope,
                op="parameter",
                message=(
                    f"{pm.group(1)} ({nbytes / 1e6:.2f} MB) enters the "
                    f"partitioned module fully replicated — every device "
                    "holds the whole tensor; shard it over the fsdp axis "
                    "(parallel/mesh.py param_shardings / shard_train_state)"
                ),
            )
        )
    return out


# collectives whose appearance means GSPMD moved data to fix a sharding
# mismatch rather than to compute a reduction
_RESHARD_KINDS = ("all-to-all", "collective-permute")


@register_rule(
    "implicit-reshard",
    severity="error",
    needs="compiled",
    doc="all-to-all / unbudgeted collective-permute in compiled HLO (GSPMD resharding)",
)
def implicit_reshard(ctx: RuleContext) -> List[Violation]:
    budget = ctx.policy.reshard_budget
    if budget is None:
        return []
    counts = G.collective_counts(ctx.compiled_text)
    out = []
    for kind in _RESHARD_KINDS:
        n = counts.get(kind, 0)
        cap = int(budget.get(kind, 0))
        if n <= cap:
            continue
        out.append(
            Violation(
                rule="implicit-reshard",
                severity=_severity(ctx, "implicit-reshard"),
                scope="",
                op=kind,
                message=(
                    f"{n}x {kind} in the compiled module (budget {cap}) — "
                    "GSPMD is resharding mid-step because declared input/"
                    "output shardings disagree with the compute placement; "
                    "align the specs (or budget a deliberate permute, e.g. "
                    "ring attention)"
                ),
            )
        )
    return out


# ----------------------------------------------------------- dataflow rules


@register_rule(
    "rng-key-reuse",
    severity="error",
    needs="jaxpr",
    doc="a PRNG key drawn from twice with no split/fold_in between, or a "
    "replicated key reaching a draw inside shard_map without a device-index fold_in",
)
def rng_key_reuse(ctx: RuleContext) -> List[Violation]:
    if not ctx.policy.check_rng:
        return []
    from perceiver_io_tpu.analysis import dataflow as D

    df = ctx.dataflow
    out: List[Violation] = []
    for f in D.rng_reuse_findings(df):
        sinks = [df.nodes[n] for n in f.sink_nids]
        where = ", ".join(f"{s.primitive} @ {s.scope or '<top>'}" for s in sinks[:3])
        origin = ""
        if f.origin_nid is not None:
            o = df.nodes[f.origin_nid]
            origin = f" (key from {o.primitive} @ {o.scope or '<top>'})"
        if f.kind == "draw-draw":
            msg = (
                f"one PRNG key feeds {len(f.sink_nids)} random draws with no "
                f"split/fold_in between them{origin}: {where} — the draws are "
                "bit-identical; split the key per consumer"
            )
        else:
            d = df.nodes[f.derive_nids[0]]
            msg = (
                f"a PRNG key is drawn from AND re-derived with "
                f"{d.primitive}{origin}: {where} — the child keys correlate "
                "with the draw; split first, consume the children only"
            )
        out.append(
            Violation(
                rule="rng-key-reuse",
                severity=_severity(ctx, "rng-key-reuse"),
                scope=sinks[0].scope,
                op=sinks[0].primitive,
                message=msg,
            )
        )
    for f in D.replicated_key_findings(df):
        sink = df.nodes[f.sink_nid]
        chain = df.provenance_to_input(f.sink_nid, max_ops=6)
        out.append(
            Violation(
                rule="rng-key-reuse",
                severity=_severity(ctx, "rng-key-reuse"),
                scope=sink.scope,
                op=sink.primitive,
                message=(
                    "a PRNG key enters the shard_map region REPLICATED "
                    "(in_specs=P()) and reaches a random draw with no "
                    "device-index fold_in on the path — every shard draws "
                    "IDENTICAL randomness (fold in lax.axis_index first)"
                    + (f"; path:\n{chain}" if chain else "")
                ),
            )
        )
    return out


@register_rule(
    "dead-compute",
    severity="error",
    needs="jaxpr",
    doc="ops whose outputs reach neither the jaxpr outputs nor an effect, "
    "FLOPs-weighted (dead matmul = error, dead reshape = info)",
)
def dead_compute(ctx: RuleContext) -> List[Violation]:
    limit = ctx.policy.dead_compute_min_flops
    if limit is None:
        return []
    from perceiver_io_tpu.analysis import dataflow as D

    df = ctx.dataflow
    out: List[Violation] = []
    cheap: Dict[Tuple[str, str], int] = {}  # (severity, scope) -> count
    for node in df.dead_nodes():
        flops = D.node_flops(node, df.values)
        if node.primitive in D.DATA_MOVEMENT_PRIMS:
            sev = "info"
        elif node.primitive in _COMPUTE_PRIMS and flops >= limit:
            sev = _severity(ctx, "dead-compute")
        else:
            sev = "warn" if flops >= limit else "info"
        if sev in ("info", "warn"):
            cheap[(sev, node.scope)] = cheap.get((sev, node.scope), 0) + 1
            continue
        aval = df.values[node.outvals[0]].aval if node.outvals else None
        shape = "x".join(map(str, aval.shape)) if aval else "?"
        out.append(
            Violation(
                rule="dead-compute",
                severity=sev,
                scope=node.scope,
                op=node.primitive,
                message=(
                    f"{node.primitive} ({shape}, ~{flops / 1e6:.1f} MFLOP) "
                    "reaches neither the jaxpr outputs nor an effect — dead "
                    "compute XLA may still schedule; chain:\n"
                    + df.provenance_to_input(node.nid, max_ops=5)
                ),
            )
        )
    for (sev, scope), n in sorted(cheap.items()):
        kind = "data-movement/cheap" if sev == "info" else "compute"
        out.append(
            Violation(
                rule="dead-compute",
                severity=sev,
                scope=scope,
                message=f"{n} dead {kind} op(s) (outputs reach no output/effect)",
            )
        )
    return out


@register_rule(
    "sharding-flow",
    severity="warn",
    needs="jaxpr",
    doc="predicted GSPMD reshard points from propagating the declared input "
    "PartitionSpecs through the jaxpr (pre-compile)",
)
def sharding_flow(ctx: RuleContext) -> List[Violation]:
    declared = ctx.policy.sharding_flow
    if declared is None or declared is False:
        return []
    from perceiver_io_tpu.analysis import dataflow as D

    df = ctx.dataflow
    if declared is True:
        import jax

        leaves = jax.tree_util.tree_leaves((ctx.args, ctx.kwargs))
        specs = []
        for leaf in leaves:
            s = getattr(leaf, "sharding", None)
            specs.append(getattr(s, "spec", None))
    else:
        specs = list(declared)
    if len(specs) != len(df.input_vids):
        return []  # cannot align leaves with jaxpr inputs — stay silent
    conflicts, _ = D.propagate_shardings(df, specs)
    out = []
    for c in conflicts:
        node = df.nodes[c.nid]
        predicted = (
            "collective-permute" if c.kind in ("sliced-sharded-dim", "updated-sharded-dim")
            else "all-to-all/collective-permute"
        )
        out.append(
            Violation(
                rule="sharding-flow",
                severity=_severity(ctx, "sharding-flow"),
                scope=node.scope,
                op=node.primitive,
                message=(
                    f"{node.primitive} {c.kind} on dim {c.dim} "
                    f"(mesh axes {c.axes}) — GSPMD will insert a {predicted} "
                    "here to realign the layouts; chain:\n"
                    + df.provenance_to_input(c.nid, max_ops=5)
                ),
            )
        )
    return out


@register_rule(
    "cross-program-consistency",
    severity="error",
    needs="jaxpr",
    doc="the prefill and decode programs must agree on KV-cache layout, "
    "dtype, and append-index provenance",
)
def cross_program_consistency(ctx: RuleContext) -> List[Violation]:
    comp = ctx.policy.companion
    if comp is None:
        return []
    from perceiver_io_tpu.analysis import dataflow as D

    scopes = ctx.policy.cache_scopes
    ours = D.cache_sites(ctx.dataflow, scopes)
    theirs = D.cache_sites(comp.dataflow(), scopes)
    if not ours and not theirs:
        return []  # nothing cache-shaped to compare
    sev = _severity(ctx, "cross-program-consistency")
    out: List[Violation] = []

    # ---- paged half: declared page-table-indexed appends ------------------
    paged_pats = ctx.policy.paged_cache_scopes
    paged_sites = [s for s in ours if paged_pats and _match(s.scope, paged_pats)]
    ours = [s for s in ours if s not in paged_sites]
    companion_dtypes = {s.dtype for s in theirs}
    for s in paged_sites:
        if s.index_origin == "static":
            out.append(
                Violation(
                    rule="cross-program-consistency",
                    severity=sev,
                    scope=s.scope,
                    op=s.primitive,
                    message=(
                        "declared-paged cache append has a STATIC write index "
                        "— the append position does not advance with the "
                        "decoded length (slots will be overwritten)"
                    ),
                )
            )
        elif not s.index_via_gather:
            out.append(
                Violation(
                    rule="cross-program-consistency",
                    severity=sev,
                    scope=s.scope,
                    op=s.primitive,
                    message=(
                        "declared-paged cache append's write index never "
                        "walks a page table (no gather in its provenance) — "
                        "the append is not page-table-indexed; either route "
                        "it through the page table or undeclare the paged "
                        "scope"
                    ),
                )
            )
        if companion_dtypes and s.dtype not in companion_dtypes:
            out.append(
                Violation(
                    rule="cross-program-consistency",
                    severity=sev,
                    scope=s.scope,
                    op=s.primitive,
                    message=(
                        f"paged cache append stores dtype {s.dtype} but "
                        f"{comp.name} builds caches only in "
                        f"{sorted(companion_dtypes)} — the pool and the "
                        "prompt pass disagree on storage dtype"
                    ),
                )
            )
    # an UNdeclared scatter-based cache append is exactly the allowlist hole
    # the declaration exists to close: flag it rather than letting it fall
    # through the slice-shaped checks below
    undeclared = [s for s in ours if s.primitive == "scatter"]
    ours = [s for s in ours if s.primitive != "scatter"]
    for s in undeclared:
        out.append(
            Violation(
                rule="cross-program-consistency",
                severity=sev,
                scope=s.scope,
                op="scatter",
                message=(
                    "scatter-based cache append without a declared paged "
                    "companion (policy.paged_cache_scopes) — declare the "
                    "paged layout or use the contiguous append"
                ),
            )
        )

    def multiset(sites):
        counts: Dict[tuple, int] = {}
        for s in sites:
            counts[s.layout] = counts.get(s.layout, 0) + 1
        return counts

    our_prompt = [s for s in ours if s.phase == "prompt"]
    their_prompt = [s for s in theirs if s.phase == "prompt"]
    # a program running the PAGED discipline (declared) has no contiguous
    # prompt appends of its own — its prompt pass is the companion program
    # itself (prefill/decode disaggregation), so the multiset comparison is
    # vacuous there, not a mismatch
    skip_prompt_cmp = bool(paged_pats) and not our_prompt
    if not skip_prompt_cmp and multiset(our_prompt) != multiset(their_prompt):
        ours_only = {k for k in multiset(our_prompt)} - {k for k in multiset(their_prompt)}
        theirs_only = {k for k in multiset(their_prompt)} - {k for k in multiset(our_prompt)}
        out.append(
            Violation(
                rule="cross-program-consistency",
                severity=sev,
                scope=our_prompt[0].scope if our_prompt else "",
                message=(
                    f"prompt-phase cache appends disagree with {comp.name}: "
                    f"this program only: {sorted(ours_only)}; {comp.name} "
                    f"only: {sorted(theirs_only)} — the two programs are "
                    "building caches with different layout/dtype"
                ),
            )
        )
    loop_sites = [s for s in ours if s.phase == "loop"]
    their_layouts = {(s.tail, s.dtype, s.rank, s.update_dims) for s in theirs}
    for s in loop_sites:
        if s.index_origin != "carried":
            out.append(
                Violation(
                    rule="cross-program-consistency",
                    severity=sev,
                    scope=s.scope,
                    op="dynamic_update_slice",
                    message=(
                        f"decode-loop cache append index provenance is "
                        f"'{s.index_origin}', not the loop carry — the append "
                        "position does not advance with the decoded length "
                        "(cache slots will be overwritten or stale)"
                    ),
                )
            )
        if their_layouts and s.layout not in their_layouts:
            out.append(
                Violation(
                    rule="cross-program-consistency",
                    severity=sev,
                    scope=s.scope,
                    op="dynamic_update_slice",
                    message=(
                        f"decode-loop cache append {s.layout} matches no "
                        f"{comp.name} cache site — the loop writes a cache "
                        "layout/dtype the prompt pass never built"
                    ),
                )
            )
    return out


_CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback", "debug_print")


@register_rule(
    "callback-in-jit",
    severity="error",
    needs="jaxpr",
    doc="host callbacks (pure_callback/io_callback/debug prints) inside a hot jitted fn",
)
def callback_in_jit(ctx: RuleContext) -> List[Violation]:
    out = []
    for op in ctx.ops:
        if op.primitive not in _CALLBACK_PRIMS:
            continue
        out.append(
            Violation(
                rule="callback-in-jit",
                severity=_severity(ctx, "callback-in-jit"),
                scope=op.scope,
                op=op.primitive,
                message=(
                    f"{op.primitive} in the traced graph — a host round-trip "
                    "per call serializes the device stream (a debug print or "
                    "debug_unique_indices left on?)"
                ),
            )
        )
    return out
