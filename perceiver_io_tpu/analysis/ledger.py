"""The feature-graduation ledger — staged → measured → default_on as data.

Perf levers (the paged decode kernel, speculative decode, the int8 cache
and weights) shipped default-off with A/Bs staged but
unmeasured; "remember to flip it after the TPU run" is not a system. The
ledger (``contracts/ledger.json``, committed next to the BENCH_*.json
artifacts it cites) makes graduation a state machine:

- ``staged``     — implemented, equivalence-certified, default-off;
- ``measured``   — the named A/B ran on real hardware and the delta is
  recorded in a committed BENCH artifact;
- ``default_on`` — the feature is the default path; graphcheck fingerprints
  the flagship programs UNDER the feature, so its graph guarantees become
  contract terms.

Transitions are forward one step at a time (staged → measured →
default_on); demotions may jump anywhere backward but, like every
transition, must carry a reason — the history is the audit trail.
``floors`` pins committed bench numbers (e.g. train ``vs_baseline``) so a
future round can't silently re-commit a slower artifact:
``tools/graphcheck.py`` checks both, ``tasks.py perf`` gates on it.
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Any, List, Optional, Tuple

LEDGER_STATES = ("staged", "measured", "default_on")
LEDGER_SCHEMA_VERSION = 1
LEDGER_FILE = "ledger.json"


def ledger_path(contracts_dir: str) -> str:
    return os.path.join(contracts_dir, LEDGER_FILE)


def load_ledger(contracts_dir: str) -> Optional[dict]:
    path = ledger_path(contracts_dir)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def save_ledger(contracts_dir: str, ledger: dict) -> str:
    problems = validate_ledger(ledger)
    if problems:
        raise ValueError(f"refusing to write an invalid ledger: {problems}")
    os.makedirs(contracts_dir, exist_ok=True)
    path = ledger_path(contracts_dir)
    with open(path, "w") as f:
        json.dump(ledger, f, sort_keys=True, indent=1)
        f.write("\n")
    return path


def _legal_transition(prev: str, nxt: str) -> bool:
    """Forward: one step at a time. Backward (demotion): any earlier state."""
    i, j = LEDGER_STATES.index(prev), LEDGER_STATES.index(nxt)
    return j == i + 1 or j < i


def validate_ledger(ledger: Any) -> List[str]:
    """Schema + state-machine problems (empty = valid): every feature in a
    known state, every history entry reasoned, every recorded transition
    legal, floors well-typed."""
    problems: List[str] = []
    if not isinstance(ledger, dict):
        return ["ledger must be a JSON object"]
    if not isinstance(ledger.get("schema_version"), int):
        problems.append("schema_version must be an int")
    features = ledger.get("features")
    if not isinstance(features, dict):
        return problems + ["features must be an object"]
    for name, feat in features.items():
        where = f"features[{name!r}]"
        if not isinstance(feat, dict):
            problems.append(f"{where} must be an object")
            continue
        state = feat.get("state")
        if state not in LEDGER_STATES:
            problems.append(f"{where}.state must be one of {LEDGER_STATES}, got {state!r}")
        history = feat.get("history", [])
        if not isinstance(history, list) or not history:
            problems.append(f"{where}.history must be a non-empty list")
            continue
        prev = None
        for i, entry in enumerate(history):
            if not isinstance(entry, dict):
                problems.append(f"{where}.history[{i}] must be an object")
                continue
            st = entry.get("state")
            if st not in LEDGER_STATES:
                problems.append(f"{where}.history[{i}].state invalid: {st!r}")
                continue
            if not str(entry.get("reason", "")).strip():
                problems.append(f"{where}.history[{i}] needs a non-empty reason")
            if i == 0 and st != "staged":
                problems.append(f"{where}.history must start at 'staged', got {st!r}")
            if prev is not None and not _legal_transition(prev, st):
                problems.append(
                    f"{where}.history[{i}]: illegal transition {prev!r} -> {st!r} "
                    f"(forward moves go one step: {' -> '.join(LEDGER_STATES)})"
                )
            prev = st
        if state in LEDGER_STATES and prev is not None and prev != state:
            problems.append(f"{where}.state {state!r} != last history state {prev!r}")
        if state == "measured" and not feat.get("evidence"):
            problems.append(f"{where}: 'measured' needs evidence (the BENCH artifact/AB)")
    floors = ledger.get("floors", {})
    if not isinstance(floors, dict):
        problems.append("floors must be an object")
    else:
        for name, floor in floors.items():
            if not isinstance(floor, dict) or not {"artifact", "key"} <= set(floor):
                problems.append(f"floors[{name!r}] must carry artifact/key")
            elif "min" not in floor and "max" not in floor:
                # a floor pins a direction: min (throughput-like, higher is
                # better) and/or max (latency-like ceiling, e.g. p99 TPOT)
                problems.append(f"floors[{name!r}] must carry min and/or max")
            else:
                for bound in ("min", "max"):
                    if bound in floor and not isinstance(floor[bound], (int, float)):
                        problems.append(f"floors[{name!r}].{bound} must be a number")
    return problems


def feature_state(ledger: Optional[dict], name: str) -> Optional[str]:
    if not ledger:
        return None
    feat = ledger.get("features", {}).get(name)
    return feat.get("state") if isinstance(feat, dict) else None


def default_on_features(ledger: Optional[dict]) -> Tuple[str, ...]:
    """The kernel feature set graphcheck fingerprints under: graduation IS
    the contract changing, so the linted graph tracks the ledger."""
    if not ledger:
        return ()
    return tuple(
        sorted(
            name
            for name, feat in ledger.get("features", {}).items()
            if isinstance(feat, dict) and feat.get("state") == "default_on"
        )
    )


def advance(ledger: dict, feature: str, state: str, reason: str,
            evidence: Optional[dict] = None) -> dict:
    """Return a new ledger with ``feature`` moved to ``state`` (legal
    transitions only, reason mandatory). Pure — callers persist via
    :func:`save_ledger`."""
    if state not in LEDGER_STATES:
        raise ValueError(f"unknown state {state!r}; valid: {LEDGER_STATES}")
    if not reason or not reason.strip():
        raise ValueError("a ledger transition needs a non-empty reason")
    out = json.loads(json.dumps(ledger))  # deep copy, JSON-clean
    feats = out.setdefault("features", {})
    feat = feats.get(feature)
    if feat is None:
        if state != "staged":
            raise ValueError(f"new feature {feature!r} must enter at 'staged'")
        feat = feats[feature] = {"state": state, "history": []}
    else:
        if not _legal_transition(feat["state"], state):
            raise ValueError(
                f"illegal transition {feat['state']!r} -> {state!r} for "
                f"{feature!r} (forward moves go one step: {' -> '.join(LEDGER_STATES)})"
            )
        feat["state"] = state
    if evidence:
        feat["evidence"] = {**feat.get("evidence", {}), **evidence}
    feat.setdefault("history", []).append({"state": state, "reason": reason.strip()})
    problems = validate_ledger(out)
    if problems:
        raise ValueError(f"transition produced an invalid ledger: {problems}")
    return out


# ------------------------------------------------------------- bench floors

_ROUND_RE = re.compile(r"_r(\d+)\.json$")


def _latest_artifact(repo_root: str, pattern: str) -> Optional[str]:
    """Highest-round match of an ``X_r*.json`` glob pattern."""
    best, best_n = None, -1
    for path in glob.glob(os.path.join(repo_root, pattern)):
        m = _ROUND_RE.search(path)
        n = int(m.group(1)) if m else 0
        if n > best_n:
            best, best_n = path, n
    return best


def doc_matches(doc: Any, match: Optional[dict]) -> bool:
    """True iff every dotted key of a floor's ``match`` clause holds in the
    doc: the sentinel value ``"*"`` requires presence (non-null), anything
    else requires equality. No clause matches everything."""
    for dotted, want in (match or {}).items():
        got = _dig(doc, dotted)
        if (got is None) if want == "*" else (got != want):
            return False
    return True


def _floor_artifact(repo_root: str, floor: dict) -> Optional[str]:
    """The artifact a floor reads: the highest round of its glob whose doc
    satisfies the floor's optional ``match`` clause. One ``X_r*.json``
    family can hold rounds of several modes (LOAD_r01 sequential-closed,
    r02 engine-closed, r03 engine-open); without the clause every floor
    would read whatever mode committed last — an open-loop round silently
    standing in for the closed-loop certification and vice versa."""
    match = floor.get("match")
    if not match:
        return _latest_artifact(repo_root, floor["artifact"])
    rounds = []
    for path in glob.glob(os.path.join(repo_root, floor["artifact"])):
        m = _ROUND_RE.search(path)
        rounds.append((int(m.group(1)) if m else 0, path))
    for _, path in sorted(rounds, reverse=True):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        if doc_matches(doc, match):
            return path
    return None


def _dig(doc: Any, dotted: str) -> Any:
    cur = doc
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def check_bench_floors(ledger: Optional[dict], repo_root: str) -> List[str]:
    """Failures of the ledger's committed-bench floors (empty = all hold):
    each floor names an artifact glob (latest round wins), a dotted key
    into its JSON, and the minimum the value must meet."""
    if not ledger:
        return []
    failures: List[str] = []
    for name, floor in ledger.get("floors", {}).items():
        path = _floor_artifact(repo_root, floor)
        if path is None:
            clause = f" with {floor['match']}" if floor.get("match") else ""
            failures.append(f"{name}: no artifact matches {floor['artifact']!r}{clause}")
            continue
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            failures.append(f"{name}: {os.path.basename(path)} unreadable ({e})")
            continue
        value = _dig(doc, floor["key"])
        if not isinstance(value, (int, float)):
            failures.append(
                f"{name}: {os.path.basename(path)}:{floor['key']} missing or non-numeric"
            )
            continue
        if "min" in floor and value < floor["min"]:
            failures.append(
                f"{name}: {os.path.basename(path)}:{floor['key']} = {value} "
                f"below floor {floor['min']}"
            )
        if "max" in floor and value > floor["max"]:
            failures.append(
                f"{name}: {os.path.basename(path)}:{floor['key']} = {value} "
                f"above ceiling {floor['max']}"
            )
    return failures
