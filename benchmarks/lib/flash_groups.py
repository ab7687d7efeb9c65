"""The flash kernels of a train step in two groups, by the names the program
gives them: ``flash_<pass>_q<n_q>_kv<n_kv>`` with ``<pass>`` one of ``fwd``,
``dq``, ``dkv`` (``perceiver_io_tpu/ops/flash_attention.py::_kernel_name``).

The groups come from the benchmark's own count of the attention calls
(``family.flash_calls``), never from the program: the *long* group is the
calls whose ``n_kv`` is the largest in that list (the one cross-attention
over the input: 1024 x 8704 in Perceiver AR after prefix dropout, 512 x 50176
in the image model), the *short* group the rest (the latent self-attention
layers). A group's kernels are the device operations whose lower-cased name
holds "flash" and ``_q<n_q>_kv<n_kv>`` of one of its calls; its share of the
roofline is the least time the chip could take for its calls over the steps
of the window (``lib/flops.py``, forward and backward, recomputed scores not
counted) over the device time of those kernels.

``None`` means the names could not be trusted: flash kernels worth more than
1% of all flash time carry no geometry of either group (the parent of the PR
that named them, or a later rename). A stale name must not read as a gain.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmarks.lib import flops, trace

KERNEL_NAME_HOLDS = "flash"  # as layers/flash_roofline.train.py selects
GEOMETRY = re.compile(r"_q(\d+)_kv(\d+)(?!\d)")
PASSES = ("fwd", "dq", "dkv")
UNMATCHED_LIMIT = 0.01  # of all flash kernel time


def split_calls(calls: Sequence[Dict]) -> Dict[str, List[Dict]]:
    """``{"long": [...], "short": [...]}``: the calls at the largest
    ``n_kv`` of the list, and the rest."""
    longest = max(c["n_kv"] for c in calls)
    return {"long": [c for c in calls if c["n_kv"] == longest],
            "short": [c for c in calls if c["n_kv"] != longest]}


def geometry_of(name: str) -> Optional[Tuple[int, int]]:
    """``(n_q, n_kv)`` from a kernel's name, or None where it holds none."""
    m = GEOMETRY.search(name.lower())
    return (int(m.group(1)), int(m.group(2))) if m else None


def pass_of(name: str) -> str:
    low = name.lower()
    return next((p for p in PASSES if f"_{p}_" in low), "other")


def group_times(events: Iterable[Sequence], calls: Sequence[Dict]) -> Dict:
    """Device ns of the flash kernels in ``events`` per group and pass:
    ``{"long": {pass: ns}, "short": {pass: ns}, "unmatched": ns,
    "unmatched_names": [...], "flash": ns}``."""
    groups = {which: {(c["n_q"], c["n_kv"]) for c in group} for which, group in split_calls(calls).items()}
    out: Dict = {"long": {}, "short": {}, "unmatched": 0.0, "unmatched_names": set(), "flash": 0.0}
    for name, _, dur in events:
        if KERNEL_NAME_HOLDS not in name.lower():
            continue
        out["flash"] += dur
        geometry = geometry_of(name)
        which = next((w for w, shapes in groups.items() if geometry in shapes), None)
        if which is None:
            out["unmatched"] += dur
            out["unmatched_names"].add(name)
        else:
            p = pass_of(name)
            out[which][p] = out[which].get(p, 0.0) + dur
    out["unmatched_names"] = sorted(out["unmatched_names"])
    return out


def ideal_seconds(calls: Sequence[Dict], peaks: Dict) -> Dict[str, float]:
    """Each group's roofline seconds for one step, forward and backward."""
    return {which: flops.roofline_seconds(group, peaks, training=True)["seconds"] if group else 0.0
            for which, group in split_calls(calls).items()}


def read(run: Dict, which: str) -> Optional[float]:
    """The ``which`` ("long" or "short") group's share of its roofline over
    the traced steps, in percent; prints the group's ms a step by pass with
    the ideal beside it. ``None``: no trace, no such group, or names that
    cannot be trusted (module docstring)."""
    if run["trace"] is None or "steps" not in run["counters"]:
        return None
    metric = f"flash_{which}_roofline.train"
    plane = sorted(run["trace"]["devices"])[0]
    events = trace.clip(run["trace"]["devices"][plane], run["trace_window"])
    calls = run["family"].flash_calls(run["counters"]["batch_size"])
    times = group_times(events, calls)
    if times["flash"] <= 0:
        return None
    if times["unmatched"] > UNMATCHED_LIMIT * times["flash"]:
        names = times["unmatched_names"]
        print(f"{metric}: {len(names)} flash kernel names, {100 * times['unmatched'] / times['flash']:.1f}% of the "
              f"flash time, hold the geometry of no attention call ({', '.join(names[:4])}"
              f"{', ...' if len(names) > 4 else ''}): not read", flush=True)
        return None
    kernel_ns = sum(times[which].values())
    if kernel_ns <= 0:
        return None
    steps = run["counters"]["steps"]
    ideal = ideal_seconds(calls, run["peaks"])[which]
    shapes = sorted({(c["n_q"], c["n_kv"]) for c in split_calls(calls)[which]})
    by_pass = ", ".join(f"{p} {times[which].get(p, 0.0) / 1e6 / steps:.3f}" for p in (*PASSES, "other")
                        if p in times[which])
    print(f"{metric}: {kernel_ns / 1e6 / steps:.3f} ms a step in the {which} group "
          f"({' '.join(f'q{q}_kv{kv}' for q, kv in shapes)}: {by_pass}) against {ideal * 1e3:.3f} ms at the roofline",
          flush=True)
    return 100.0 * ideal * steps / (kernel_ns / 1e9)
