"""Cut a piece of a traced run for ``benchmarks/tests/test_scopes.py``: a few
thousand device rows of a kept ``.xplane.pb`` with the part of the program's
instruction-to-scope table that names them, and what every scope reader
gives on the piece.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds 20 --trace 1 --keep-trace <dir>
    python3 benchmarks/tools/scopes_cut.py --workload <cell> --xplane <dir> --out benchmarks/tests/data/scopes_<cell>.json

Run on the chip, after the traced run and with its cache: the table is made
as the readers make it (``lib/scopes.py::program_scopes``, a cache hit); or
anywhere with ``--text``, the module text of the same program compiled for a
described chip (the instruction names are the same). A
train cell's piece is ``--steps`` whole steps; a decode cell's the last
``--prompt-rows`` rows of a prompt pass and ``--steps`` whole steps of the decode
loop behind it, with ``new_tokens`` set to what the piece holds. Rows are clipped to
the piece, a ``while`` too, so the piece's busy time is its own."""

from __future__ import annotations

import argparse
import collections
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)


def starts_of_first_recurring(events, at_least: int):
    """The start times of the first instruction (in time) that runs at least ``at_least`` times."""
    counts = collections.Counter(name for name, _, _ in events)
    marker = next(name for name, _, _ in events if counts[name] >= at_least)
    return sorted(start for name, start, _ in events if name == marker)


def cut_train(events, steps: int):
    """``steps`` whole steps: from one start of the window's first instruction to a later one."""
    at = starts_of_first_recurring(sorted(events, key=lambda e: e[1]), steps + 2)
    return [(at[1], at[1 + steps])]


def cut_decode(events, table, prompt_rows: int, steps: int):
    """The last ``prompt_rows`` rows before a call's decode loop, and ``steps`` whole iterations of that loop."""
    events = sorted(events, key=lambda e: e[1])
    loops = [e for e in events if e[0] in table and table[e[0]]["container"] and table[e[0]]["phase"] == "decode"]
    loop = max(loops, key=lambda e: e[2])
    at = starts_of_first_recurring([e for e in events if loop[1] <= e[1] < loop[1] + loop[2] and e[0] != loop[0]], steps + 11)
    call = [e for e in events if e[1] + e[2] <= loop[1]]  # what ran before the loop: the prompt pass
    head = call[-prompt_rows:]
    return [(head[0][1], loop[1]), (at[10], at[10 + steps])]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--xplane", required=True, help="the kept trace: a .xplane.pb or the directory that holds it")
    ap.add_argument("--out", required=True)
    ap.add_argument("--text", help="a stored module text (tools/step_hlo.py --save) for the table, in the chip compile's place")
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--prompt-rows", type=int, default=1500)
    args = ap.parse_args(argv)

    from benchmarks import run as bench_run
    from benchmarks.lib import scopes, trace

    bench_run.enable_cache()
    cell = bench_run.load_json("workloads", args.workload)
    config = bench_run.load_json("configs", cell["config"])
    family = importlib.import_module(f"benchmarks.families.{config['family']}").Family(config)
    path = args.xplane if os.path.isfile(args.xplane) else trace.find_xplane(args.xplane)
    data = trace.load_xplane(path)
    window = trace.host_window(data["host"], "bench/window")
    plane = sorted(data["devices"])[0]
    events = trace.clip(data["devices"][plane], window)
    if args.text:
        from perceiver_io_tpu.obs.xplane import instruction_scopes

        with open(args.text) as f:
            table, note = instruction_scopes(f.read()), f"the table of {os.path.basename(args.text)}"
    else:
        table, note = scopes.program_scopes({"cell": cell, "family": family})
    if table is None:
        raise SystemExit(f"benchmarks/tools/scopes_cut.py: no table: {note}")

    train = cell["driver"] == "train"
    pieces = cut_train(events, args.steps) if train else cut_decode(events, table, args.prompt_rows, args.steps)
    rows = [row for piece in pieces for row in trace.clip(events, piece)]
    params = dict(cell["params"]) if train else dict(cell["params"], new_tokens=args.steps + 1)
    counters = {"steps": args.steps, "batch_size": params["batch_size"]} if train else {"calls": 1}
    run = {
        "cell": dict(cell, params=params), "family": family, "counters": counters,
        "trace": {"devices": {plane: rows}, "host": []}, "trace_window": (pieces[0][0], pieces[-1][1]),
        "busy_s": trace.busy_ns(rows) / 1e9, "scope_table": {name: table[name] for name, _, _ in rows if name in table},
    }
    bench = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    suffix = ".train" if train else ".decode"
    expected = {}
    for metric in bench["per_layer"]:
        name = metric["name"]
        source = open(os.path.join(CHECKOUT, "benchmarks", "layers", name + ".py")).read()
        if name.endswith(suffix) and "scopes.read(" in source and args.workload in metric.get("workloads", ()):
            expected[name] = bench_run.load_module("layers", name).read(run)
    found = scopes.times(run, "scopes_cut")
    out = {
        "cell": args.workload, "from": f"{note}; {os.path.basename(path)}; pieces {pieces}", "params": params,
        "counters": counters, "plane": plane, "rows": rows, "table": run["scope_table"], "busy_ns": trace.busy_ns(rows),
        "leaf_ns": found.leaf_ns, "container_ns": found.container_ns, "expected": expected,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, separators=(",", ":"))
    print(f"{args.out}: {len(rows)} rows, {len(out['table'])} names, busy {out['busy_ns'] / 1e6:.3f} ms, leaf "
          f"{found.leaf_ns / 1e6:.3f} ms, expected {expected}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
