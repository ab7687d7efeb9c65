"""From a profiler trace to numbers: device busy time (the union of the
intervals in which an operation ran), idle share, time per operation name,
and the longest idle gaps named by what the host was doing.

The reduction works on plain ``[name, start_ns, duration_ns]`` rows so that a
small recorded trace can be kept as JSON with the tests. ``load_xplane`` turns
a ``.xplane.pb`` into such rows with ``jax.profiler.ProfileData`` alone."""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Sequence  # [name, start_ns, duration_ns]

DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(event_name: str) -> str:
    """The instruction's name from a device event's name: the TPU runtime
    names an operation by its whole HLO text (``%fusion.12 = bf16[...] ...``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load_xplane(path: str, host_prefix: str = "bench/") -> Dict:
    """``{"devices": {plane: [event, ...]}, "host": [event, ...]}``: the
    operations of each device plane's "XLA Ops" line under their instruction
    names, and the host's annotations whose name starts with ``host_prefix``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        [op_name(e.name), float(e.start_ns), float(e.duration_ns)] for e in line.events
                    )
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events if e.name.startswith(host_prefix)
                )
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def clip(events: Iterable[Event], window: Optional[Tuple[float, float]]) -> List[List]:
    """The part of each event inside ``window`` (start_ns, end_ns)."""
    if window is None:
        return [list(e) for e in events]
    lo, hi = window
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append([name, a, b - a])
    return out


def merged_intervals(events: Iterable[Event]) -> List[Tuple[float, float]]:
    spans = sorted((start, start + dur) for _, start, dur in events if dur > 0)
    merged: List[List[float]] = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(events: Iterable[Event]) -> float:
    """Nanoseconds in which at least one event ran (nested and overlapping
    events count once)."""
    return sum(b - a for a, b in merged_intervals(events))


def idle_share(events: Iterable[Event], window: Tuple[float, float]) -> float:
    """1 minus busy over the window's length, as a share in 0..1."""
    lo, hi = window
    if hi <= lo:
        raise ValueError(f"empty window {window}")
    return 1.0 - busy_ns(clip(events, window)) / (hi - lo)


def totals_by_name(events: Iterable[Event]) -> Dict[str, float]:
    """Summed duration in ns per event name."""
    out: Dict[str, float] = {}
    for name, _, dur in events:
        out[name] = out.get(name, 0.0) + dur
    return out


def top(totals: Dict[str, float], n: int = 10) -> List[List]:
    """The ``n`` largest entries as ``[name, seconds]``."""
    rows = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows]


def idle_gaps(events: Iterable[Event], host: Iterable[Event], window: Tuple[float, float]) -> Dict[str, float]:
    """Idle ns inside ``window`` per host annotation: each gap between busy
    intervals goes to the innermost (shortest) host annotation that covers
    its midpoint, or to ``"(no annotation)"``."""
    lo, hi = window
    busy = merged_intervals(clip(events, window))
    gaps, at = [], lo
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    host = list(host)
    out: Dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) / 2
        covering = [h for h in host if h[1] <= mid <= h[1] + h[2]]
        name = min(covering, key=lambda h: h[2])[0] if covering else "(no annotation)"
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def host_window(host: Iterable[Event], name: str) -> Tuple[float, float]:
    """The span of the one host annotation called ``name``."""
    found = [h for h in host if h[0] == name]
    if len(found) != 1:
        raise ValueError(f"expected one host annotation {name!r}, found {len(found)}")
    return found[0][1], found[0][1] + found[0][2]
