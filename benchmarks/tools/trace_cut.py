"""Look at one profiler trace by hand, and cut a small piece of it for the
tests.

    python3 benchmarks/tools/trace_cut.py <dir-or-.xplane.pb> [--out rows.json] [--events 400]

Prints every plane with its lines and event counts, the device operations
that took most time, and the ``bench/`` host annotations. ``--out`` writes
the first ``--events`` device operations inside the traced window, with the
host annotations over them, as the plain rows ``lib/trace.py`` reduces."""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.lib import trace  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("path")
    p.add_argument("--out", default=None)
    p.add_argument("--events", type=int, default=400)
    args = p.parse_args(argv)
    path = args.path if args.path.endswith(".pb") else trace.find_xplane(args.path)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines[:40]:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events; first {[e.name for e in events[:3]]}")
    data = trace.load_xplane(path)
    window = trace.host_window(data["host"], "bench/window")
    for plane, events in sorted(data["devices"].items()):
        inside = trace.clip(events, window)
        print(f"{plane}: {len(inside)} ops in the window, busy {trace.busy_ns(inside) / 1e9:.4f} s of "
              f"{(window[1] - window[0]) / 1e9:.4f} s")
        for name, seconds in trace.top(trace.totals_by_name(inside), 40):
            print(f"  {seconds:10.6f} s  {name}")
        stems = trace.totals_by_name([[name.rsplit(".", 1)[0] if name.rsplit(".", 1)[-1].isdigit() else name, a, d]
                                      for name, a, d in inside])
        print("  by name without its number:")
        for name, seconds in trace.top(stems, 15):
            print(f"  {seconds:10.6f} s  {name}")
    print("host annotations:", json.dumps(trace.top(trace.totals_by_name(data["host"]), 20)))
    if args.out:
        plane = sorted(data["devices"])[0]
        ops = sorted(trace.clip(data["devices"][plane], window), key=lambda e: e[1])[: args.events]
        end = ops[-1][1] + ops[-1][2]
        host = [h for h in data["host"] if h[0] != "bench/window" and h[1] < end and h[1] + h[2] > window[0]]
        rows = {"devices": {plane: ops}, "host": [["bench/window", window[0], end - window[0]]] + host}
        with open(args.out, "w") as f:
            json.dump(rows, f)
        print(f"wrote {len(ops)} device ops and {len(host)} host annotations to {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
