"""The control of a cell's ``correct``: the plain reference put in the
program's place, computed in the nearest precision below the one the
configuration states, at the cell's own size. It has to come out as NOT
correct. The benchmark's own runs never run this; a builder runs it on the
chip when a limit is set or changed.

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 [--precision fp8]

Prints, for each seed, each compared number of the control against the
float32 reference beside the cell's limit."""

from __future__ import annotations

import argparse
import json
import os
import sys
import types

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)


def control_checks(cell: dict, config: dict, seed: int, precision: str) -> list:
    """The cell's compared numbers with the reference at ``precision`` in the
    program's place."""
    import importlib

    from benchmarks import run

    family = importlib.import_module(f"benchmarks.families.{config['family']}").Family(config)
    driver = run.load_module("drivers", cell["driver"])
    ctx = types.SimpleNamespace(seed=seed, cell=cell, config=config, family=family, mark=run.mark)
    return driver.control(ctx, precision)


def main(argv=None) -> int:
    from benchmarks import run

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--precision", default="fp8", help="fp8 (the control) or bfloat16 (where a sound program sits)")
    args = p.parse_args(argv)
    cell = run.load_json("workloads", args.workload)
    config = run.load_json("configs", cell["config"])
    run.enable_cache()
    run.require_chips(cell["chips"])
    for seed in (int(s) for s in args.seeds.split(",")):
        checks = control_checks(cell, config, seed, args.precision)
        print(json.dumps({"workload": args.workload, "seed": seed, "precision": args.precision,
                          "correct": all(c["ok"] for c in checks),
                          "checks": {c["name"]: {"value": c["value"], "limit": c["limit"]} for c in checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
