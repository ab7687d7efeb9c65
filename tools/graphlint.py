"""graphlint CLI — static analysis of the flagship compiled graphs.

Lints the flagship train step, prefill and decode functions
(perceiver_io_tpu/analysis/flagship.py builds them) against the full rule
set and prints a human report per target plus, optionally, one JSON artifact. Exit status follows ``--fail-on``, so
this is the CI gate `tasks.py graphlint` wraps:

    python tools/graphlint.py --fail-on error
    python tools/graphlint.py --geometry flagship --no-compiled   # trace-only
    python tools/graphlint.py --kernel-features paged             # A/B the lint
    python tools/graphlint.py --json graphlint.json --allow 'hot-concat:*mlp*'
    python tools/graphlint.py --mesh data=2,fsdp=4 --targets train  # sharded step
    python tools/graphlint.py --programs all --no-compiled  # the graphcheck
                                                            # programs, dataflow rules

``--mesh data=N[,fsdp=M]`` lints the SHARDED flagship train step (GSPMD:
``make_train_step`` on ``shard_train_state`` inputs). When the host
has fewer devices than the mesh needs, the CLI re-execs itself with that
many virtual CPU devices (the __graft_entry__ dryrun trick).

``--programs all`` lints the graphcheck programs (train_flat,
train_sharded, prefill, decode and the rest of
``analysis.flagship.PROGRAMS``) with per-program policies
that arm the dataflow rules — rng-key-reuse, dead-compute, sharding-flow
(on the sharded step), cross-program-consistency (decode vs prefill).
This is the gate ``tasks.py perf`` runs after graphcheck.

Exit codes: 0 — no violation at/above ``--fail-on``; 1 — violations found;
2 — usage error (e.g. an unknown ``--rules``/``--programs`` name — the
message lists what is registered); 3 — a rule or target build CRASHED (the
lint itself is broken, which CI must not confuse with either verdict).
The contract is shared with tools/hostlint.py through
perceiver_io_tpu/analysis/lintcli.py.

Rule catalog and allowlist syntax: docs/static-analysis.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:  # `python tools/graphlint.py` from anywhere
    sys.path.insert(0, _REPO)

from perceiver_io_tpu.analysis.lintcli import (  # noqa: E402
    add_common_lint_args,
    finish_lint,
    lint_crashed,
    parse_rules,
)


def _ensure_devices(n: int) -> None:
    """Re-exec with ``n`` virtual CPU devices unless the environment
    already provides them (utils/compat.ensure_cli_virtual_devices)."""
    from perceiver_io_tpu.utils.compat import ensure_cli_virtual_devices

    ensure_cli_virtual_devices(n, __file__)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--geometry", choices=("micro", "flagship"), default="micro",
                   help="micro (default): flagship architecture at toy sizes — "
                        "graph-shape rules are geometry-invariant and this "
                        "compiles in seconds on CPU; flagship: the real 16k "
                        "geometry (compiling it is a TPU-sized job — pair "
                        "with --no-compiled elsewhere)")
    p.add_argument("--targets", default="train,prefill,decode",
                   help="comma list of flagship functions to lint")
    p.add_argument("--programs", default=None, metavar="P1,P2|all",
                   help="lint the graphcheck programs instead of the "
                        "--targets trio: train_flat, train_sharded (GSPMD), "
                        "prefill, decode, ... — 'all' "
                        "or a comma list; the sharded step re-execs with "
                        "virtual CPU devices when the host is short. This is "
                        "the dataflow-rule gate `tasks.py perf` runs")
    add_common_lint_args(
        p,
        allow_help="extra allowlist entry (repeatable), fnmatch-ed against "
                   "'rule' and 'rule:scope' — e.g. 'hot-concat:*decode*'",
    )
    p.add_argument("--compiled", dest="compiled", action="store_true", default=None,
                   help="force lowering+compiling (the donation/collective rules)")
    p.add_argument("--no-compiled", dest="compiled", action="store_false",
                   help="forbid compiling — trace-only rules")
    p.add_argument("--kernel-features", default=None,
                   help="trace-time flash kernel feature set to lint under: "
                        "'all', 'none', or a comma list (e.g. 'paged')")
    p.add_argument("--collective-budget", default=None,
                   help="JSON dict enabling the collective-budget rule, e.g. "
                        "'{\"all-gather\": 2, \"total\": 4}'")
    p.add_argument("--mesh", default=None, metavar="data=N[,fsdp=M]",
                   help="shard the train target over this data/fsdp mesh and "
                        "lint the distributed step (re-execs with virtual CPU "
                        "devices when the host has too few)")
    args = p.parse_args(argv)

    from perceiver_io_tpu.analysis.rules import RULES

    rules = parse_rules(p, args.rules, RULES)

    programs = None
    if args.programs:
        from perceiver_io_tpu.analysis.flagship import DEFAULT_MESH_SPEC, PROGRAMS

        programs = (
            tuple(PROGRAMS)
            if args.programs == "all"
            else tuple(x for x in args.programs.split(",") if x)
        )
        unknown_programs = [x for x in programs if x not in PROGRAMS]
        if unknown_programs:
            p.error(
                f"unknown program(s) {', '.join(unknown_programs)}; known: "
                f"{', '.join(PROGRAMS)}"
            )
        if "train_sharded" in programs:
            from perceiver_io_tpu.parallel.mesh import parse_mesh_spec, required_devices

            _ensure_devices(required_devices(parse_mesh_spec(DEFAULT_MESH_SPEC)))

    mesh = None
    if args.mesh:
        from perceiver_io_tpu.parallel.mesh import (
            mesh_from_spec,
            parse_mesh_spec,
            required_devices,
        )

        _ensure_devices(required_devices(parse_mesh_spec(args.mesh)))
        mesh = mesh_from_spec(args.mesh)

    from perceiver_io_tpu.analysis.flagship import lint_flagship, lint_programs

    features = None
    if args.kernel_features is not None:
        from perceiver_io_tpu.ops.flash_attention import ALL_FEATURES

        features = {
            "all": tuple(ALL_FEATURES), "none": ()
        }.get(args.kernel_features, tuple(f for f in args.kernel_features.split(",") if f))

    budget = json.loads(args.collective_budget) if args.collective_budget else None
    try:
        if programs is not None:
            reports = lint_programs(
                programs,
                geometry=args.geometry,
                rules=rules,
                allow=tuple(args.allow),
                compiled=args.compiled,
                features=features,
            )
        else:
            reports = lint_flagship(
                geometry=args.geometry,
                targets=tuple(t for t in args.targets.split(",") if t),
                rules=rules,
                allow=tuple(args.allow),
                compiled=args.compiled,
                collective_budget=budget,
                features=features,
                mesh=mesh,
            )
    except Exception as e:  # noqa: BLE001 — a rule/build CRASH is not a verdict
        # exit 3, distinct from 1 (violations found): CI must not read "the
        # linter itself broke" as "the graph got worse" — or, with
        # --fail-on none, as a pass
        return lint_crashed("graphlint", e)

    return finish_lint("graphlint", reports, fail_on=args.fail_on,
                       json_path=args.json)


if __name__ == "__main__":
    sys.exit(main())
