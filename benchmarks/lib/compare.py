"""The numbers a run is judged by, each with a limit of its own."""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Tuple


def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float]) -> Tuple[float, str]:
    """The widest gap, over leaves, between the program's norm of a leaf and
    the reference's norm of it: the difference of the two norms (not the norm
    of a difference), over the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero)."""
    if set(program) != set(reference):
        raise ValueError(f"leaves differ: {sorted(set(program) ^ set(reference))[:4]}")
    median = statistics.median(reference.values())
    worst, at = -1.0, ""
    for leaf, ref in reference.items():
        gap = abs(program[leaf] - ref) / max(ref, median)
        if math.isnan(worst):
            break  # a NaN gap wins and stays
        if math.isnan(gap) or gap > worst:
            worst, at = gap, leaf
    return worst, at


def worst_leaf_difference(differences: Dict[str, float], reference: Dict[str, float]) -> Tuple[float, str]:
    """The widest norm, over leaves, of the difference between the program's
    leaf and the reference's, over the reference's norm of that leaf or of
    the median leaf, whichever is larger. First order in a rounding error,
    where a gap between two norms is second order."""
    median = statistics.median(reference.values())
    return max((d / max(reference[leaf], median) if not math.isnan(d) else math.inf, leaf)
               for leaf, d in differences.items())


def check(name: str, value: float, limit: float, note: str = "") -> dict:
    """One compared number beside its limit; ``ok`` needs a finite value at
    or under the limit."""
    ok = limit is not None and math.isfinite(value) and value <= limit
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok), "note": note}


def all_ok(checks: List[dict]) -> bool:
    return bool(checks) and all(c["ok"] for c in checks)
