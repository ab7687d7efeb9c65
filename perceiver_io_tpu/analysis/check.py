"""``analysis.check`` — lint one jitted function, get a :class:`Report`.

Designed for three callers with different budgets:

- **pytest** — ``assert analysis.check(fn, args, rules=("hot-concat",),
  policy=...).clean`` (trace-only, milliseconds);
- **the trainer** — jaxpr-only rules at fit start, violations emitted as a
  ``graphlint`` event (obs/events.py);
- **tools/graphlint.py** — the full rule set including the compiled-module
  rules (donation, collectives) over the flagship functions.

Compilation is opt-in by consequence, not by flag: rules that need the
compiled module run only when their policy inputs are declared (or
``compiled=True`` forces it), so the cheap path never pays a compile.
"""

from __future__ import annotations

import dataclasses
import json
from fnmatch import fnmatch
from typing import Dict, List, Optional, Sequence, Tuple

from perceiver_io_tpu.analysis.rules import (
    RULES,
    LintPolicy,
    RuleContext,
    Violation,
)

_SEV_RANK = {"info": 0, "warn": 1, "error": 2}


@dataclasses.dataclass
class Report:
    """Outcome of one ``check``: surviving violations (most severe first),
    allowlisted ones kept for transparency, and which rules ran/skipped."""

    name: str
    backend: str
    n_ops: int
    rules_run: Tuple[str, ...]
    rules_skipped: Tuple[str, ...]  # compiled-level rules without inputs
    violations: List[Violation]
    allowed: List[Violation]

    @property
    def clean(self) -> bool:
        """No violations at all (allowlisted ones excluded)."""
        return not self.violations

    def ok(self, fail_on: str = "error") -> bool:
        """True when no violation is at or above ``fail_on`` severity."""
        if fail_on == "none":
            return True
        bar = _SEV_RANK[fail_on]
        return not any(_SEV_RANK[v.severity] >= bar for v in self.violations)

    def count(self, severity: str) -> int:
        return sum(1 for v in self.violations if v.severity == severity)

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "backend": self.backend,
            "n_ops": self.n_ops,
            "rules_run": list(self.rules_run),
            "rules_skipped": list(self.rules_skipped),
            "ok": self.ok(),
            "clean": self.clean,
            "counts": {s: self.count(s) for s in ("error", "warn", "info")},
            "violations": [v.to_dict() for v in self.violations],
            "allowed": [v.to_dict() for v in self.allowed],
        }

    def to_json(self, **kwargs) -> str:
        return json.dumps(self.to_dict(), **kwargs)

    def format(self) -> str:
        """Human report: one header line, one line per violation."""
        head = (
            f"graphlint {self.name}: {len(self.violations)} violation(s) "
            f"[{self.count('error')} error / {self.count('warn')} warn / "
            f"{self.count('info')} info], {len(self.allowed)} allowlisted, "
            f"{self.n_ops} ops, backend={self.backend}, "
            f"rules={','.join(self.rules_run)}"
        )
        lines = [head]
        for v in sorted(self.violations, key=lambda v: -_SEV_RANK[v.severity]):
            lines.append(f"  {v.severity.upper():5s} {v.key}  {v.message}")
        for v in self.allowed:
            lines.append(f"  allow {v.key}  (suppressed)")
        return "\n".join(lines)

    def raise_if(self, fail_on: str = "error") -> "Report":
        """Raise ``GraphLintError`` when not :meth:`ok`; returns self."""
        if not self.ok(fail_on):
            raise GraphLintError(self.format())
        return self


class GraphLintError(AssertionError):
    """A lint violation at or above the requested severity."""


def _allowed(v: Violation, allow: Sequence[str]) -> bool:
    return any(fnmatch(v.key, pat) or fnmatch(v.rule, pat) for pat in allow)


def check(
    fn,
    args: tuple = (),
    kwargs: Optional[dict] = None,
    *,
    rules: Optional[Sequence[str]] = None,
    allow: Sequence[str] = (),
    policy: Optional[LintPolicy] = None,
    compiled: Optional[bool] = None,
    name: Optional[str] = None,
    closed_jaxpr=None,
) -> Report:
    """Lint ``fn`` traced with ``args``/``kwargs``.

    :param rules: rule names to run (default: all registered). Unknown names
        raise — a typo must not silently skip a gate.
    :param allow: allowlist patterns, ``fnmatch``-ed against each
        violation's ``rule`` and ``rule:scope`` key (e.g.
        ``"hot-concat:*kv_concat*"`` or ``"donation-dropped"``). Suppressed
        violations stay visible in ``report.allowed``.
    :param policy: the declared intent rules check against
        (:class:`LintPolicy`); defaults are conservative.
    :param compiled: force (True) or forbid (False) lowering+compiling for
        the compiled-module rules. Default ``None``: compile exactly when an
        active compiled-level rule has its policy inputs declared
        (``donate_argnums``/``expect_donation``, ``collective_budget``,
        ``peak_memory_budget_bytes``, ``replicated_bytes_limit``,
        ``reshard_budget``).
        A jitted ``fn``'s OWN donate_argnums are detected from the lowered
        module once the rule runs, but pjit does not expose them before
        lowering (jax 0.4.37) — to audit such a fn without policy hints,
        pass ``compiled=True`` (or declare ``expect_donation=True``).
        The dataflow rules (``rng-key-reuse``, ``dead-compute``,
        ``sharding-flow``, ``cross-program-consistency``) are jaxpr-level
        but policy-gated the same way: they run only when their policy
        inputs are declared and otherwise land in ``rules_skipped``.
    :param name: label for reports (default: the function's ``__name__``).
    :param closed_jaxpr: a pre-traced ``ClosedJaxpr`` of ``fn(*args)`` to
        reuse (callers that also :func:`~perceiver_io_tpu.analysis.
        fingerprint.fingerprint` the same fn share one trace); default:
        trace here.

    Trace-time feature flags (``fast_kernels``) must be active AROUND this
    call — ``check`` traces like ``jax.jit`` would.
    """
    kwargs = kwargs or {}
    policy = policy or LintPolicy()
    selected = list(RULES) if rules is None else list(rules)
    unknown = [r for r in selected if r not in RULES]
    if unknown:
        raise ValueError(f"unknown rule(s) {unknown}; registered: {sorted(RULES)}")
    from perceiver_io_tpu.analysis.rules import SEVERITIES

    bad_sev = {r: s for r, s in policy.severity_overrides.items() if s not in SEVERITIES}
    if bad_sev:
        # fail at configuration time, not on the first violation — a typo'd
        # override must not lie dormant until the lint it disarms fires
        raise ValueError(f"invalid severity override(s) {bad_sev}; valid: {SEVERITIES}")

    ctx = RuleContext(fn, args, kwargs, policy, closed_jaxpr=closed_jaxpr)

    def compiled_inputs_declared(rule_name: str) -> bool:
        if rule_name == "donation-dropped":
            from perceiver_io_tpu.analysis.rules import _fn_donates

            return bool(policy.donate_argnums) or policy.expect_donation or _fn_donates(fn)
        if rule_name == "collective-budget":
            return policy.collective_budget is not None
        if rule_name == "peak-memory-budget":
            return policy.peak_memory_budget_bytes is not None
        if rule_name == "replicated-large-tensor":
            return policy.replicated_bytes_limit is not None
        if rule_name == "implicit-reshard":
            return policy.reshard_budget is not None
        return True

    # jaxpr-level rules that are policy-gated like the compiled trio: they
    # surface in rules_skipped when unarmed instead of silently running empty
    def jaxpr_inputs_declared(rule_name: str) -> bool:
        if rule_name == "rng-key-reuse":
            return policy.check_rng
        if rule_name == "dead-compute":
            return policy.dead_compute_min_flops is not None
        if rule_name == "sharding-flow":
            return policy.sharding_flow is not None and policy.sharding_flow is not False
        if rule_name == "cross-program-consistency":
            return policy.companion is not None
        return True

    run: List[str] = []
    skipped: List[str] = []
    raw: List[Violation] = []
    for rname in selected:
        rule = RULES[rname]
        if rule.needs == "compiled":
            want = compiled if compiled is not None else compiled_inputs_declared(rname)
            if not want:
                skipped.append(rname)
                continue
        elif not jaxpr_inputs_declared(rname):
            skipped.append(rname)
            continue
        raw.extend(rule.fn(ctx))
        run.append(rname)

    violations = [v for v in raw if not _allowed(v, allow)]
    suppressed = [v for v in raw if _allowed(v, allow)]
    violations.sort(key=lambda v: (-_SEV_RANK[v.severity], v.key))
    return Report(
        name=name or getattr(fn, "__name__", None) or repr(fn),
        backend=ctx.backend,
        n_ops=len(ctx.ops),
        rules_run=tuple(run),
        rules_skipped=tuple(skipped),
        violations=violations,
        allowed=suppressed,
    )
