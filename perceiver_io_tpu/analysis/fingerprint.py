"""GraphFingerprint — the canonical, diffable summary of one compiled program.

PR 3's graphlint answers "is this graph acceptable *now*"; nothing stopped a
later PR from silently regressing what an earlier one certified — the hot
scopes' concat inventory, the sharded step's collectives, peak memory.
This module makes those guarantees *contracts*: a fingerprint is extracted
from each flagship program (train flat, train data x fsdp, prefill,
decode), committed under ``contracts/``, and every
``tools/graphcheck.py`` run re-extracts the live graphs and semantically
diffs them against the committed snapshots — classifying each change as
regression / improvement / neutral instead of failing on any byte drift.

A fingerprint records, per program:

- per-kind collective ``{count, bytes}`` over the compiled HLO
  (GSPMD-inserted included — the jaxpr never sees those);
- the hot-scope concat inventory (the ``[prefix; latents]`` kv build and
  friends — a NEW entry is a tensor re-materialized on the hot path);
- committed donation alias count, captured-const bytes, a dtype histogram
  of the traced ops, XLA-reported FLOPs, and the static peak-HBM breakdown
  (:mod:`perceiver_io_tpu.analysis.memory`).

Serialization is stable (sorted keys) so contract diffs in review are
line-readable. The differ refuses to compare fingerprints taken on a
different backend / partition count / feature set — that is a *stale
contract* (re-snapshot with ``--update --reason``), not a regression.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

from perceiver_io_tpu.analysis import graph as G
from perceiver_io_tpu.analysis.memory import memory_breakdown

FINGERPRINT_SCHEMA_VERSION = 1

# the flagship programs graphcheck snapshots; the sharded pair runs on the
# DEFAULT_MESH_SPEC submesh (tools/graphcheck.py provisions virtual devices).
# Canonical definition lives in flagship.py (build_programs builds them for
# BOTH the lint gate and these contracts); re-exported here for the CLIs.
from perceiver_io_tpu.analysis.flagship import DEFAULT_MESH_SPEC, PROGRAMS  # noqa: E402


@dataclasses.dataclass
class GraphFingerprint:
    """One program's graph identity, every field diffable."""

    name: str
    backend: str
    n_partitions: int
    features: Tuple[str, ...]  # trace-time kernel feature set
    n_ops: int
    dtype_histogram: Dict[str, int]  # result dtype -> producing-op count
    hot_concats: Tuple[Dict[str, Any], ...]  # {scope, axis, shape}
    captured_const_bytes: int
    collectives: Dict[str, Dict[str, int]]  # kind -> {count, bytes}
    donation_aliases: Optional[int]  # None when not compiled
    flops: Optional[float]
    memory: Optional[Dict[str, Any]]  # MemoryBreakdown.to_dict()
    schema_version: int = FINGERPRINT_SCHEMA_VERSION

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["features"] = sorted(self.features)
        d["hot_concats"] = [dict(h) for h in self.hot_concats]
        return d

    def to_json(self, **kwargs) -> str:
        kwargs.setdefault("sort_keys", True)
        kwargs.setdefault("indent", 1)
        return json.dumps(self.to_dict(), **kwargs)

    @classmethod
    def from_dict(cls, d: dict) -> "GraphFingerprint":
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in fields}
        kw["features"] = tuple(kw.get("features", ()))
        kw["hot_concats"] = tuple(dict(h) for h in kw.get("hot_concats", ()))
        return cls(**kw)


def _concat_key(entry: Dict[str, Any]) -> Tuple[str, int, Tuple[int, ...]]:
    """Full site identity — scope alone is not unique (microbatch-unrolled
    chunks re-trace the same scope) and a shape change at one site is a
    different tensor being built, so shape is part of the key."""
    return (str(entry["scope"]), int(entry["axis"]), tuple(int(d) for d in entry["shape"]))


def fingerprint(
    fn,
    args: tuple = (),
    kwargs: Optional[dict] = None,
    *,
    name: Optional[str] = None,
    compiled: bool = True,
    hot_scopes: Optional[Sequence[str]] = None,
    min_concat_numel: int = 1024,
    min_concat_axis: int = 128,
    donate_argnums: Tuple[int, ...] = (),
    closed_jaxpr=None,
) -> GraphFingerprint:
    """Extract a fingerprint from ``fn`` traced with ``args``/``kwargs``.

    ``compiled=False`` keeps the trace-only fields (milliseconds — what the
    trainer's ``graphcheck`` event records); collectives/donation/FLOPs/
    memory need the compiled module. ``closed_jaxpr`` reuses a pre-traced
    ``ClosedJaxpr`` of the same fn/args (``analysis.check`` callers share
    one trace). Trace-time feature flags must be active AROUND this call,
    exactly as around ``jax.jit``."""
    import jax

    from fnmatch import fnmatch

    from perceiver_io_tpu.analysis.rules import LintPolicy
    from perceiver_io_tpu.ops.flash_attention import fast_features

    kwargs = kwargs or {}
    hot = tuple(hot_scopes) if hot_scopes is not None else LintPolicy().hot_scopes
    closed = closed_jaxpr if closed_jaxpr is not None else G.trace(fn, *args, **kwargs)
    ops = list(G.iter_ops(closed))

    dtype_hist: Dict[str, int] = {}
    concats: List[Dict[str, Any]] = []
    for op in ops:
        for out in op.outvars:
            dtype_hist[out.dtype] = dtype_hist.get(out.dtype, 0) + 1
        if op.primitive != "concatenate" or not op.outvars:
            continue
        out = op.outvars[0]
        axis = int(op.params.get("dimension", -1))
        if not (
            any(fnmatch(op.scope, p) for p in hot)
            and out.numel >= min_concat_numel
            and len(out.shape) >= 3
            and 0 <= axis < len(out.shape)
            and out.shape[axis] >= min_concat_axis
        ):
            continue
        concats.append({"scope": op.scope, "axis": axis, "shape": list(out.shape)})
    concats.sort(key=lambda c: (c["scope"], c["axis"], c["shape"]))
    const_bytes = sum(c.nbytes for c in G.iter_consts(closed))

    collectives: Dict[str, Dict[str, int]] = {}
    aliases: Optional[int] = None
    flops: Optional[float] = None
    memory: Optional[Dict[str, Any]] = None
    n_partitions = 1
    if compiled:
        lowered, _ = G.lower(fn, args, kwargs, donate_argnums=donate_argnums)
        exe = lowered.compile()
        text = exe.as_text()
        collectives = G.collective_stats(text)
        aliases = G.count_output_aliases(text)
        memory = memory_breakdown(exe, text).to_dict()
        n_partitions = G.hlo_num_partitions(text)
        try:
            cost = exe.cost_analysis()
            entry = cost[0] if isinstance(cost, (list, tuple)) else cost
            raw = entry.get("flops") if hasattr(entry, "get") else None
            flops = float(raw) if raw is not None else None
        except Exception:  # noqa: BLE001 — unimplemented on some plugins
            flops = None

    return GraphFingerprint(
        name=name or getattr(fn, "__name__", None) or repr(fn),
        backend=jax.default_backend(),
        n_partitions=n_partitions,
        features=tuple(sorted(fast_features())),
        n_ops=len(ops),
        dtype_histogram=dict(sorted(dtype_hist.items())),
        hot_concats=tuple(concats),
        captured_const_bytes=int(const_bytes),
        collectives={k: dict(v) for k, v in sorted(collectives.items())},
        donation_aliases=aliases,
        flops=flops,
        memory=memory,
    )


# ------------------------------------------------------------------ the diff


@dataclasses.dataclass(frozen=True)
class DiffTolerances:
    """How much drift each fingerprint field absorbs before the differ
    classifies it — XLA version bumps wiggle temp sizes and fusion counts,
    and the gate must catch *decisions*, not byte noise."""

    memory_frac: float = 0.05  # temp+arg bytes (the peak-memory gate)
    collective_bytes_frac: float = 0.10  # same count, fatter collectives
    flops_frac: float = 0.02
    const_bytes: int = 1 << 16  # absolute slack for captured consts


@dataclasses.dataclass(frozen=True)
class Delta:
    field: str
    kind: str  # "regression" | "improvement" | "neutral"
    detail: str


@dataclasses.dataclass
class FingerprintDiff:
    name: str
    comparable: bool
    reason: str  # why not comparable ("" when comparable)
    deltas: List[Delta]

    @property
    def regressions(self) -> List[Delta]:
        return [d for d in self.deltas if d.kind == "regression"]

    @property
    def improvements(self) -> List[Delta]:
        return [d for d in self.deltas if d.kind == "improvement"]

    @property
    def ok(self) -> bool:
        return self.comparable and not self.regressions

    def format(self) -> str:
        if not self.comparable:
            return f"graphcheck {self.name}: NOT COMPARABLE — {self.reason}"
        head = (
            f"graphcheck {self.name}: {len(self.regressions)} regression(s), "
            f"{len(self.improvements)} improvement(s), "
            f"{len(self.deltas) - len(self.regressions) - len(self.improvements)} neutral"
        )
        lines = [head]
        order = {"regression": 0, "improvement": 1, "neutral": 2}
        for d in sorted(self.deltas, key=lambda d: order[d.kind]):
            lines.append(f"  {d.kind.upper():11s} {d.field}  {d.detail}")
        return "\n".join(lines)

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "comparable": self.comparable,
            "reason": self.reason,
            "ok": self.ok,
            "deltas": [dataclasses.asdict(d) for d in self.deltas],
        }


def _classify(new_worse: bool, new_better: bool) -> str:
    return "regression" if new_worse else ("improvement" if new_better else "neutral")


def diff_fingerprints(
    old: GraphFingerprint,
    new: GraphFingerprint,
    tolerances: Optional[DiffTolerances] = None,
) -> FingerprintDiff:
    """Semantic diff ``old`` (the committed contract) vs ``new`` (the live
    graph). More collectives / a new hot concat / fewer donation aliases /
    fatter memory or FLOPs beyond tolerance = regression; the mirror image
    = improvement; op-count and dtype-histogram drift = neutral detail."""
    tol = tolerances or DiffTolerances()
    for field in ("backend", "n_partitions", "schema_version"):
        a, b = getattr(old, field), getattr(new, field)
        if a != b:
            return FingerprintDiff(
                name=new.name,
                comparable=False,
                reason=(
                    f"{field} changed ({a!r} -> {b!r}); the contract was "
                    "snapshotted in a different environment — re-record it "
                    "(tools/graphcheck.py --update --reason '...')"
                ),
                deltas=[],
            )
    if tuple(sorted(old.features)) != tuple(sorted(new.features)):
        return FingerprintDiff(
            name=new.name,
            comparable=False,
            reason=(
                f"kernel feature set changed ({sorted(old.features)} -> "
                f"{sorted(new.features)}): a feature graduated or was demoted "
                "— re-snapshot the contract alongside the ledger transition"
            ),
            deltas=[],
        )

    deltas: List[Delta] = []

    # collectives: any count growth is a regression — GSPMD inserted traffic
    for kind in sorted(set(old.collectives) | set(new.collectives)):
        o = old.collectives.get(kind, {"count": 0, "bytes": 0})
        n = new.collectives.get(kind, {"count": 0, "bytes": 0})
        if n["count"] != o["count"]:
            deltas.append(
                Delta(
                    field=f"collectives.{kind}.count",
                    kind=_classify(n["count"] > o["count"], n["count"] < o["count"]),
                    detail=f"{o['count']} -> {n['count']}",
                )
            )
        elif o["count"] and abs(n["bytes"] - o["bytes"]) > tol.collective_bytes_frac * max(o["bytes"], 1):
            deltas.append(
                Delta(
                    field=f"collectives.{kind}.bytes",
                    kind=_classify(n["bytes"] > o["bytes"], n["bytes"] < o["bytes"]),
                    detail=f"{o['bytes']} -> {n['bytes']} (same count, fatter tensors)",
                )
            )

    # hot-scope concats: a MULTISET over (scope, axis, shape) — a new site,
    # MORE concats at an existing site (unrolled chunks share one scope), or
    # a shape change at one site are all a re-materialized kv build
    old_c: Dict[tuple, int] = {}
    for c in old.hot_concats:
        old_c[_concat_key(c)] = old_c.get(_concat_key(c), 0) + 1
    new_c: Dict[tuple, int] = {}
    for c in new.hot_concats:
        new_c[_concat_key(c)] = new_c.get(_concat_key(c), 0) + 1
    for key in sorted(set(old_c) | set(new_c)):
        o, n = old_c.get(key, 0), new_c.get(key, 0)
        if n == o:
            continue
        scope, axis, shape = key
        site = f"scope={scope!r} axis={axis} shape={list(shape)}"
        if o == 0:
            detail = f"NEW concat at {site}" + (f" x{n}" if n > 1 else "")
        elif n == 0:
            detail = f"concat at {site} is gone"
        else:
            detail = f"concat count at {site}: {o} -> {n}"
        deltas.append(
            Delta(field="hot_concats", kind=_classify(n > o, n < o), detail=detail)
        )

    # donation: fewer committed aliases = the step pays state-copy traffic
    if old.donation_aliases is not None and new.donation_aliases is not None:
        if new.donation_aliases != old.donation_aliases:
            deltas.append(
                Delta(
                    field="donation_aliases",
                    kind=_classify(
                        new.donation_aliases < old.donation_aliases,
                        new.donation_aliases > old.donation_aliases,
                    ),
                    detail=f"{old.donation_aliases} -> {new.donation_aliases}",
                )
            )

    if abs(new.captured_const_bytes - old.captured_const_bytes) > tol.const_bytes:
        deltas.append(
            Delta(
                field="captured_const_bytes",
                kind=_classify(
                    new.captured_const_bytes > old.captured_const_bytes,
                    new.captured_const_bytes < old.captured_const_bytes,
                ),
                detail=f"{old.captured_const_bytes} -> {new.captured_const_bytes}",
            )
        )

    # memory: gate_bytes (temp+args) beyond tolerance; method change = stale
    if old.memory and new.memory:
        if old.memory.get("method") != new.memory.get("method"):
            deltas.append(
                Delta(
                    field="memory.method",
                    kind="neutral",
                    detail=(
                        f"{old.memory.get('method')} -> {new.memory.get('method')} "
                        "(breakdowns not comparable across methods; consider --update)"
                    ),
                )
            )
        else:
            o_gate = int(old.memory["gate_bytes"])
            n_gate = int(new.memory["gate_bytes"])
            if abs(n_gate - o_gate) > tol.memory_frac * max(o_gate, 1):
                deltas.append(
                    Delta(
                        field="memory.gate_bytes",
                        kind=_classify(n_gate > o_gate, n_gate < o_gate),
                        detail=(
                            f"temp+args {o_gate / 1e6:.2f} MB -> {n_gate / 1e6:.2f} MB "
                            f"(temp {old.memory['temp_bytes']} -> {new.memory['temp_bytes']})"
                        ),
                    )
                )

    if old.flops is not None and new.flops is not None:
        if abs(new.flops - old.flops) > tol.flops_frac * max(old.flops, 1.0):
            deltas.append(
                Delta(
                    field="flops",
                    kind=_classify(new.flops > old.flops, new.flops < old.flops),
                    detail=f"{old.flops:.3e} -> {new.flops:.3e}",
                )
            )

    if old.dtype_histogram != new.dtype_histogram:
        changed = {
            k: (old.dtype_histogram.get(k, 0), new.dtype_histogram.get(k, 0))
            for k in set(old.dtype_histogram) | set(new.dtype_histogram)
            if old.dtype_histogram.get(k, 0) != new.dtype_histogram.get(k, 0)
        }
        deltas.append(
            Delta(
                field="dtype_histogram",
                kind="neutral",
                detail=f"op counts shifted: {dict(sorted(changed.items()))} "
                "(dtype-drift rules the intent; histogram drift alone is not a verdict)",
            )
        )
    if old.n_ops != new.n_ops:
        deltas.append(Delta("n_ops", "neutral", f"{old.n_ops} -> {new.n_ops}"))

    return FingerprintDiff(name=new.name, comparable=True, reason="", deltas=deltas)


# ------------------------------------------------------------- contract store

CONTRACT_SCHEMA_VERSION = 1


def contract_path(contracts_dir: str, program: str) -> str:
    return os.path.join(contracts_dir, f"{program}.json")


def save_contract(
    contracts_dir: str,
    program: str,
    fp: GraphFingerprint,
    reason: str,
    geometry: str = "micro",
) -> str:
    """Write one program's contract; ``reason`` is mandatory — the committed
    file records WHY the snapshot moved, so `git log contracts/` reads as a
    decision history."""
    if not reason or not reason.strip():
        raise ValueError("a contract update needs a non-empty --reason")
    os.makedirs(contracts_dir, exist_ok=True)
    path = contract_path(contracts_dir, program)
    doc = {
        "schema_version": CONTRACT_SCHEMA_VERSION,
        "program": program,
        "geometry": geometry,
        "updated_reason": reason.strip(),
        "fingerprint": fp.to_dict(),
    }
    with open(path, "w") as f:
        json.dump(doc, f, sort_keys=True, indent=1)
        f.write("\n")
    return path


def load_contract(contracts_dir: str, program: str) -> Optional[dict]:
    path = contract_path(contracts_dir, program)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def validate_contract(doc: dict) -> List[str]:
    """Schema problems of one contracts/<program>.json document (empty =
    valid) — the tier-1 artifact-schema test and every loader share this."""
    problems: List[str] = []
    for key, typ in (
        ("schema_version", int),
        ("program", str),
        ("geometry", str),
        ("updated_reason", str),
        ("fingerprint", dict),
    ):
        if key not in doc:
            problems.append(f"missing key {key!r}")
        elif not isinstance(doc[key], typ):
            problems.append(f"{key!r} must be {typ.__name__}, got {type(doc[key]).__name__}")
    if problems:
        return problems
    fp = doc["fingerprint"]
    for key, typ in (
        ("name", str),
        ("backend", str),
        ("n_partitions", int),
        ("features", list),
        ("n_ops", int),
        ("dtype_histogram", dict),
        ("hot_concats", list),
        ("captured_const_bytes", int),
        ("collectives", dict),
        ("schema_version", int),
    ):
        if key not in fp:
            problems.append(f"fingerprint missing key {key!r}")
        elif not isinstance(fp[key], typ):
            problems.append(
                f"fingerprint.{key} must be {typ.__name__}, got {type(fp[key]).__name__}"
            )
    if not problems:
        for kind, stats in fp["collectives"].items():
            if not isinstance(stats, dict) or not {"count", "bytes"} <= set(stats):
                problems.append(f"collectives[{kind!r}] must carry count+bytes")
        for c in fp["hot_concats"]:
            if not isinstance(c, dict) or not {"scope", "axis", "shape"} <= set(c):
                problems.append("hot_concats entries must carry scope/axis/shape")
        if fp.get("memory") is not None and "gate_bytes" not in fp["memory"]:
            problems.append("fingerprint.memory must carry gate_bytes")
    return problems


# --------------------------------------------------- flagship program builders


def flagship_fingerprints(
    programs: Sequence[str] = PROGRAMS,
    geometry: str = "micro",
    mesh_spec: str = DEFAULT_MESH_SPEC,
    features: Optional[Sequence[str]] = None,
) -> Dict[str, GraphFingerprint]:
    """Fingerprint the flagship programs — the SAME functions graphlint
    lints (:mod:`perceiver_io_tpu.analysis.flagship` builds them).
    ``features`` follows :func:`~perceiver_io_tpu.analysis.
    flagship.lint_flagship` semantics: an explicit set also forces the flash
    routes on; ``None`` keeps the ambient/default kernels. The sharded step
    (``train_sharded``, GSPMD) needs the ``mesh_spec`` submesh worth of
    devices — tools/graphcheck.py provisions virtual CPU devices when the host is short."""
    from perceiver_io_tpu.analysis.flagship import build_programs, features_context

    with features_context(features):
        built = build_programs(programs, geometry=geometry, mesh_spec=mesh_spec)
        return {
            p: fingerprint(built[p].fn, built[p].args, name=p) for p in programs
        }


def check_contracts(
    contracts_dir: str,
    programs: Optional[Sequence[str]] = None,
    geometry: str = "micro",
    mesh_spec: str = DEFAULT_MESH_SPEC,
    features: Optional[Sequence[str]] = None,
    tolerances: Optional[DiffTolerances] = None,
    live: Optional[Dict[str, GraphFingerprint]] = None,
) -> dict:
    """Diff the live flagship graphs against the committed contracts.

    Returns ``{"status", "programs": {name: {...}}, "fingerprints"}`` with
    status ``passed`` / ``regressed`` / ``stale`` (not comparable or schema-
    invalid) / ``missing`` (no contract yet — run ``--update``), worst wins.
    ``live`` injects pre-extracted fingerprints (tests plant regressions
    through this seam; production callers leave it None)."""
    programs = tuple(programs) if programs else PROGRAMS
    fps = dict(live) if live is not None else flagship_fingerprints(
        programs, geometry=geometry, mesh_spec=mesh_spec, features=features
    )
    rank = {"passed": 0, "missing": 1, "stale": 2, "regressed": 3}
    status = "passed"
    results: Dict[str, dict] = {}
    for p in programs:
        doc = load_contract(contracts_dir, p)
        if doc is None:
            entry = {"status": "missing", "detail": f"no contract at {contract_path(contracts_dir, p)}"}
        else:
            problems = validate_contract(doc)
            if problems:
                entry = {"status": "stale", "detail": f"invalid contract: {problems}"}
            else:
                d = diff_fingerprints(
                    GraphFingerprint.from_dict(doc["fingerprint"]), fps[p], tolerances
                )
                if not d.comparable:
                    entry = {"status": "stale", "detail": d.reason, "diff": d.to_dict()}
                elif d.regressions:
                    entry = {
                        "status": "regressed",
                        "detail": "; ".join(f"{x.field}: {x.detail}" for x in d.regressions),
                        "diff": d.to_dict(),
                    }
                else:
                    entry = {"status": "passed", "diff": d.to_dict()}
        results[p] = entry
        if rank[entry["status"]] > rank[status]:
            status = entry["status"]
    return {"status": status, "programs": results, "fingerprints": fps}
