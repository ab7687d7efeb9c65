"""graphcheck CLI — compiled-graph contracts, the graduation ledger, and
committed-bench floors, as one perf-CI gate.

Extracts a GraphFingerprint (analysis/fingerprint.py: collectives, hot-scope
concats, donation aliases, captured consts, dtype histogram, FLOPs, static
peak-HBM breakdown) from each flagship program — train flat, train
data x fsdp (GSPMD), prefill, decode —
and semantically diffs it against the committed snapshot in ``contracts/``.
A regression (more collectives, a new hot concat, fewer donation aliases,
fatter memory/FLOPs beyond tolerance) fails the gate; an improvement or
neutral drift passes and is printed. The graduation ledger
(``contracts/ledger.json``, analysis/ledger.py) is schema- and
state-machine-validated, its ``default_on`` features pick the kernel
feature set the graphs are fingerprinted under, and its ``floors`` pin
committed BENCH_*.json numbers.

    python tools/graphcheck.py                          # the gate (tasks.py perf)
    python tools/graphcheck.py --programs train_flat,decode
    python tools/graphcheck.py --update --reason "paged graduated (serve cell A/B)"
    python tools/graphcheck.py --json graphcheck.json

--update etiquette: a snapshot move is a REVIEWED decision — the reason
lands in the contract file, so `git log contracts/` reads as the decision
history. Never --update to silence a regression you don't understand.

Exit codes: 0 clean; 1 regression / floor failure / invalid ledger;
2 missing or stale (incomparable) contracts — run --update; 3 internal
error (the check itself broke — distinct from "the graph got worse").

Hosts with fewer devices than the sharded programs need re-exec with
virtual CPU devices automatically (same trick as tools/graphlint.py).
Workflow and contract format: docs/static-analysis.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # `python tools/graphcheck.py` from anywhere
    sys.path.insert(0, REPO)


def _ensure_devices(n: int) -> None:
    """Re-exec with ``n`` virtual CPU devices unless the environment
    already provides them (utils/compat.ensure_cli_virtual_devices)."""
    from perceiver_io_tpu.utils.compat import ensure_cli_virtual_devices

    ensure_cli_virtual_devices(n, __file__)


def main(argv=None) -> int:
    from perceiver_io_tpu.analysis.fingerprint import DEFAULT_MESH_SPEC, PROGRAMS

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--contracts", default=os.path.join(REPO, "contracts"),
                   help="contracts directory (default: <repo>/contracts)")
    p.add_argument("--programs", default=",".join(PROGRAMS),
                   help=f"comma list of programs to check (known: {','.join(PROGRAMS)})")
    p.add_argument("--geometry", choices=("micro", "flagship"), default="micro",
                   help="micro (default): flagship architecture at toy sizes — "
                        "graph-shape contracts are geometry-invariant and this "
                        "compiles in seconds on CPU")
    p.add_argument("--mesh", default=DEFAULT_MESH_SPEC, metavar="data=N[,fsdp=M]",
                   help="submesh for the sharded train programs "
                        f"(default {DEFAULT_MESH_SPEC}; re-execs with virtual "
                        "CPU devices when the host has too few)")
    p.add_argument("--features", default=None,
                   help="override the kernel feature set ('all', 'none', or a "
                        "comma list); default: the "
                        "ledger's default_on features")
    p.add_argument("--update", action="store_true",
                   help="re-snapshot the selected programs' contracts instead "
                        "of checking (requires --reason)")
    p.add_argument("--reason", default=None,
                   help="why the contract moved (recorded in the file; "
                        "mandatory with --update)")
    p.add_argument("--json", default=None, metavar="PATH",
                   help="write the full result JSON artifact")
    p.add_argument("--skip-floors", action="store_true",
                   help="skip the ledger's committed-bench floor checks")
    args = p.parse_args(argv)

    try:
        from perceiver_io_tpu.analysis import ledger as L
        from perceiver_io_tpu.analysis.fingerprint import (
            check_contracts,
            flagship_fingerprints,
            save_contract,
        )
        from perceiver_io_tpu.parallel.mesh import parse_mesh_spec, required_devices

        programs = tuple(x for x in args.programs.split(",") if x)
        unknown = [x for x in programs if x not in PROGRAMS]
        if unknown:
            print(f"unknown program(s) {unknown}; known: {PROGRAMS}")
            return 3
        if "train_sharded" in programs:
            _ensure_devices(required_devices(parse_mesh_spec(args.mesh)))

        ledger = L.load_ledger(args.contracts)
        ledger_problems = L.validate_ledger(ledger) if ledger is not None else []
        features = None
        if args.features is not None:
            from perceiver_io_tpu.ops.flash_attention import ALL_FEATURES

            features = {
                "all": tuple(ALL_FEATURES), "none": ()
            }.get(args.features, tuple(f for f in args.features.split(",") if f))
        elif ledger is not None and not ledger_problems:
            features = L.default_on_features(ledger) or None

        if args.update:
            if not args.reason or not args.reason.strip():
                print("--update requires --reason (the recorded justification)")
                return 3
            fps = flagship_fingerprints(
                programs, geometry=args.geometry, mesh_spec=args.mesh, features=features
            )
            updated = {}
            for name in programs:
                path = save_contract(
                    args.contracts, name, fps[name], args.reason, geometry=args.geometry
                )
                updated[name] = path
                print(f"updated {path}")
            if args.json:
                with open(args.json, "w") as f:
                    json.dump(
                        {"status": "updated", "reason": args.reason.strip(),
                         "updated": updated},
                        f, sort_keys=True, indent=1,
                    )
                print(f"wrote {args.json}")
            return 0

        result = check_contracts(
            args.contracts, programs=programs, geometry=args.geometry,
            mesh_spec=args.mesh, features=features,
        )
        for name in programs:
            entry = result["programs"][name]
            if "diff" in entry and entry["diff"].get("comparable"):
                from perceiver_io_tpu.analysis.fingerprint import (
                    Delta,
                    FingerprintDiff,
                )

                d = FingerprintDiff(
                    name=name, comparable=True, reason="",
                    deltas=[Delta(**x) for x in entry["diff"]["deltas"]],
                )
                print(d.format())
            else:
                print(f"graphcheck {name}: {entry['status']} — {entry.get('detail', '')}")
            print()

        if ledger is None:
            print("graphcheck: no contracts/ledger.json — feature graduation untracked")
        elif ledger_problems:
            print(f"graphcheck: INVALID ledger: {ledger_problems}")
        else:
            for fname, feat in sorted(ledger.get("features", {}).items()):
                print(f"ledger: {fname} = {feat['state']}")
        floor_failures = []
        if not args.skip_floors and ledger is not None and not ledger_problems:
            floor_failures = L.check_bench_floors(ledger, REPO)
            for f in floor_failures:
                print(f"bench floor FAILED: {f}")

        if args.json:
            doc = {
                "status": result["status"],
                "programs": result["programs"],
                "ledger": {
                    "present": ledger is not None,
                    "problems": ledger_problems,
                    "features": {
                        k: v.get("state")
                        for k, v in (ledger or {}).get("features", {}).items()
                    },
                },
                "floor_failures": floor_failures,
            }
            with open(args.json, "w") as f:
                json.dump(doc, f, sort_keys=True, indent=1)
            print(f"wrote {args.json}")

        if result["status"] == "regressed" or floor_failures or ledger_problems:
            print("graphcheck FAILED (regression / floor / ledger)")
            return 1
        if result["status"] in ("missing", "stale"):
            print("graphcheck: contracts missing or stale — "
                  "run tools/graphcheck.py --update --reason '...'")
            return 2
        print(f"graphcheck ok ({len(programs)} program(s) match contracts)")
        return 0
    except SystemExit:
        raise
    except Exception as e:  # noqa: BLE001 — the check broke, not the graph
        import traceback

        traceback.print_exc()
        print(f"graphcheck internal error: {e}")
        return 3


if __name__ == "__main__":
    sys.exit(main())
