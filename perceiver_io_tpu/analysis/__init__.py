"""Static analysis of the compiled train & decode graphs (graphlint).

What XLA actually compiles is the artifact this reproduction optimizes —
and regressions there (f32 upcasts, weights baked in as constants, a
re-materialized kv concat, dropped buffer donation, an implicit all-gather)
are invisible to output-equivalence tests. This package lints jaxprs and
lowered/compiled HLO of any jitted function against declared intent:

    from perceiver_io_tpu import analysis
    report = analysis.check(step_fn, (state, batch),
                            rules=("hot-concat", "callback-in-jit"),
                            policy=analysis.LintPolicy(...))
    assert report.ok()

Entry points: :func:`check` (pytest/programmatic), ``tools/graphlint.py``
(CLI over the flagship functions) and the trainer's ``graphlint`` event
(obs/events.py). On top of
the scope/shape rules, :mod:`dataflow` adds a def-use/provenance engine
(value threading through pjit/scan/cond/shard_map/custom_vjp bodies) and
the four dataflow rules — ``rng-key-reuse``, ``dead-compute``,
``sharding-flow``, ``cross-program-consistency``. Rule catalog and
allowlist syntax: docs/static-analysis.md.

:mod:`hostgraph` + :mod:`hostrules` extend the same discipline to the
HOST side (Hostline): AST/CFG analysis of the serving/obs packages with
the five protocol rules — ``books-exactness``, ``shared-state-race``,
``clock-discipline``, ``grant-pairing``, ``event-schema`` — behind
``tools/hostlint.py`` / ``tasks.py hostlint``
(docs/static-analysis.md#hostlint).
"""

from perceiver_io_tpu._startup import RECORD as _STARTUP

_IMPORTING = _STARTUP.open("startup/import", package=__name__)

from perceiver_io_tpu.analysis.check import GraphLintError, Report, check
from perceiver_io_tpu.analysis.dataflow import (
    CacheSite,
    Dataflow,
    DfNode,
    DfValue,
    ReplicatedKeyFinding,
    ReuseFinding,
    ShardingConflict,
    analyze,
    build,
    cache_sites,
    propagate_shardings,
    replicated_key_findings,
    rng_reuse_findings,
)
from perceiver_io_tpu.analysis.fingerprint import (
    DiffTolerances,
    FingerprintDiff,
    GraphFingerprint,
    diff_fingerprints,
    fingerprint,
)
from perceiver_io_tpu.analysis.graph import (
    AvalInfo,
    ConstInfo,
    OpNode,
    collective_counts,
    count_output_aliases,
    iter_consts,
    iter_ops,
    trace,
)
from perceiver_io_tpu.analysis.hostgraph import (
    CFG,
    HostGraph,
    build_cfg,
    build_host_graph,
    build_package_graph,
)
from perceiver_io_tpu.analysis.hostrules import (
    HOST_RULES,
    HostPolicy,
    default_host_policy,
    host_check,
    load_allowlist,
)
from perceiver_io_tpu.analysis.memory import MemoryBreakdown, memory_breakdown
from perceiver_io_tpu.analysis.rules import (
    RULES,
    CompanionProgram,
    LintPolicy,
    Violation,
    register_rule,
)

__all__ = [
    "AvalInfo",
    "CacheSite",
    "CompanionProgram",
    "ConstInfo",
    "Dataflow",
    "DfNode",
    "DfValue",
    "ReplicatedKeyFinding",
    "ReuseFinding",
    "ShardingConflict",
    "analyze",
    "build",
    "cache_sites",
    "propagate_shardings",
    "replicated_key_findings",
    "rng_reuse_findings",
    "DiffTolerances",
    "FingerprintDiff",
    "GraphFingerprint",
    "GraphLintError",
    "CFG",
    "HOST_RULES",
    "HostGraph",
    "HostPolicy",
    "build_cfg",
    "build_host_graph",
    "build_package_graph",
    "default_host_policy",
    "host_check",
    "load_allowlist",
    "LintPolicy",
    "MemoryBreakdown",
    "OpNode",
    "RULES",
    "Report",
    "Violation",
    "check",
    "diff_fingerprints",
    "fingerprint",
    "memory_breakdown",
    "collective_counts",
    "count_output_aliases",
    "iter_consts",
    "iter_ops",
    "register_rule",
    "trace",
]

_STARTUP.close(_IMPORTING)
