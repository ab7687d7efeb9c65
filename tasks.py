"""Dev task runner (reference: tasks.py:7-101). The reference uses `invoke`;
that package isn't a framework dependency, so this is a dependency-free
equivalent with the same task names:

    python tasks.py test [--cov]
    python tasks.py test-fast          # the sub-2-minute subset (-m "not slow")
    python tasks.py code-check         # ruff lint over the package + tests
    python tasks.py clean              # caches + test + build artifacts
    python tasks.py build              # sdist/wheel via pyproject
    python tasks.py docker [--tag TAG]
    python tasks.py bench [...args]    # BENCHMARK.json's command: benchmarks/run.py (real chip)
    python tasks.py graphlint [...]    # static-analysis gate (compiled graphs)
    python tasks.py perf [...]         # perf CI: graphcheck contracts + graphlint + ledger floors + obs gate
    python tasks.py obs [...]          # observability gate (spans/requests/SLO + obs_diff self-check)
    python tasks.py load [...]         # serving load gate (closed-loop loadgen + flight recorder + /metrics)
    python tasks.py sim [...]          # discrete-event scale gate (multi-tenant sim of the real engine)
    python tasks.py dryrun [...]       # 8-virtual-device multichip certification
    python tasks.py chaos [...]        # fault-injection gate (preempt/NaN/torn-save/elastic resume/serving)
"""

from __future__ import annotations

import argparse
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).parent

TASKS = {}


def task(fn):
    TASKS[fn.__name__.replace("_", "-")] = fn
    return fn


def run(*cmd: str, env: dict | None = None) -> None:
    print("+", " ".join(cmd))
    subprocess.run(cmd, cwd=ROOT, check=True, env=env)


@task
def test(args):
    cmd = [sys.executable, "-m", "pytest", "tests", "--durations=25", "-q"]
    if args.cov:
        cmd += ["--cov=perceiver_io_tpu", "--cov-report=term"]
    if args.rest:
        cmd += args.rest
    run(*cmd)


@task
def test_fast(args):
    run(sys.executable, "-m", "pytest", "tests", "-q", "-m", "not slow", *args.rest)


@task
def code_check(args):
    run(sys.executable, "-m", "ruff", "check", "perceiver_io_tpu", "tests", "examples", *args.rest)


@task
def clean_cache(args=None):
    for pattern in ("**/__pycache__", "**/*.pyc", "**/*.pyo"):
        for p in ROOT.glob(pattern):
            if ".git" in p.parts:
                continue
            shutil.rmtree(p, ignore_errors=True) if p.is_dir() else p.unlink(missing_ok=True)
    shutil.rmtree(ROOT / ".mypy_cache", ignore_errors=True)


@task
def clean_test(args=None):
    for name in (".pytest_cache", "htmlcov"):
        shutil.rmtree(ROOT / name, ignore_errors=True)
    (ROOT / ".coverage").unlink(missing_ok=True)


@task
def clean_preproc(args=None):
    shutil.rmtree(ROOT / ".cache", ignore_errors=True)


@task
def clean_build(args=None):
    shutil.rmtree(ROOT / "dist", ignore_errors=True)


@task
def clean(args=None):
    clean_cache()
    clean_test()
    clean_build()


@task
def build(args):
    clean()
    run(sys.executable, "-m", "build", "--sdist", "--wheel")


@task
def docker(args):
    run("docker", "build", "-t", "perceiver-io-tpu", ".")
    if args.tag:
        run("docker", "tag", "perceiver-io-tpu", f"perceiver-io-tpu:{args.tag}")


@task
def bench(args):
    """The benchmark the driver reads (``BENCHMARK.json``'s command), e.g.
    ``--workload ar16k-train-b32 --seed 1 --seconds 20 --trace 1``."""
    run("python3", "benchmarks/run.py", *args.rest)


@task
def dryrun(args):
    """Multichip certification gate: the forced-8-device dryrun (every mesh
    kind, the ring strategy, sharded decode, the pipelines) plus
    the distributed test suites — which otherwise only run when someone
    remembers to. Extra args go to pytest (e.g. ``-k ring``)."""
    env = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\S+", "", env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
    # dryrun_multichip provisions its own virtual devices (subprocess respawn)
    run(sys.executable, "-c", "import __graft_entry__; __graft_entry__.dryrun_multichip(8)")
    run(
        sys.executable, "-m", "pytest",
        "tests/test_sharded_step.py", "tests/test_distributed.py",
        "tests/test_seq_parallel_step.py", "tests/test_ring_attention.py",
        "-q", *args.rest,
        env=env,
    )


@task
def chaos(args):
    """Fault-injection gate (tools/chaos.py; docs/robustness.md): SIGTERM
    preemption + auto-resume equivalence (unsharded AND data x fsdp mesh),
    loader fetch retries, NaN-grad sentinel skip/rollback, torn-save
    quarantine, the four mesh-ELASTIC resume scenarios (elastic_shrink
    8->4, elastic_grow 4->8, flat_to_mesh, mesh_to_flat — kill and resume
    run on different virtual-device topologies, trajectory must match
    <= 1e-6 with a span-attributed resume.reshard event and a clean
    graphlint pass on the new mesh), and the SERVING scenarios
    (serve_overload / serve_kill_mid_decode / serve_deadline / serve_drain
    / serve_breaker / the engine + speculative kill scenarios — the
    Shedline front end and Pageline engine under injected failures, clean
    books certified, docs/robustness.md#serving-hardening — plus the
    Evictline pair: serve_evict_storm, page-pressure preemption with
    token-exact resume, and serve_crash_recover, a journal-backed engine
    restart with books balanced across it,
    docs/robustness.md#engine-eviction-and-recovery — and the Shareline
    storm: serve_prefix_storm, N same-prefix requests served off ONE
    prefill of the shared run, token-exact vs the unshared reference with
    refcounts balanced at drain, docs/serving.md#prefix-sharing). Extra
    args go to tools/chaos.py; ``--scenarios`` takes names or fnmatch
    globs (e.g. ``--scenarios 'serve_*'``)."""
    run(sys.executable, "tools/chaos.py", *args.rest)


@task
def graphlint(args):
    """Static-analysis gate over the flagship compiled graphs
    (tools/graphlint.py; docs/static-analysis.md)."""
    run(sys.executable, "tools/graphlint.py", "--fail-on", "error", *args.rest)


@task
def hostlint(args):
    """Static protocol analysis of the host-side serving stack
    (tools/hostlint.py; docs/static-analysis.md#hostlint): CFG/call-graph
    rules — books-exactness, shared-state-race, clock-discipline,
    grant-pairing, event-schema — over perceiver_io_tpu/serving/ + obs/
    with the committed reasoned allowlist. Pure-AST: no JAX, no compile,
    sub-second. Gates at warn — an unsuppressed warn is a finding that
    never got triaged."""
    run(sys.executable, "tools/hostlint.py", "--fail-on", "warn", *args.rest)


@task
def obs(args):
    """Observability gate (tools/obs_gate.py; docs/observability.md): a
    10-step synthetic fit + instrumented generate requests, event-stream
    schema/span validation, obs_report render, obs_diff run-vs-itself
    (must be clean). Extra args pass through (e.g. ``--baseline DIR``,
    ``--out DIR --keep`` to record a new baseline)."""
    run(sys.executable, "tools/obs_gate.py", *args.rest)


@task
def load(args):
    """Serving-observability gate (tools/loadgen.py; docs/observability.md#
    serving-observability-loadline): a 200-request closed-loop load run
    through the instrumented decode path with the flight recorder and the
    /metrics///slo scrape server live — validates the event stream, asserts
    a planted SLO breach produces exactly one flight dump naming the
    breaching span, run-vs-itself comparability diff must be clean, and the
    ledger's LOAD_r*.json floors must hold. Extra args pass through (e.g.
    ``--smoke``, ``--write-artifact``, ``--mode open --rate 20``)."""
    run(sys.executable, "tools/loadgen.py", *args.rest)


@task
def sim(args):
    """Discrete-event scale gate (tools/sim.py; docs/serving.md#
    multi-tenant-telemetry): drives a seeded multi-tenant workload through
    the REAL engine front end — admission, page allocator, Evictline,
    breaker, books — under a ManualClock with service times sampled from
    the committed LOAD artifact, at thousands of simulated req/s in
    seconds of host time. Asserts books balanced + allocator audits clean,
    per-tenant /metrics series and /slo?tenant= live, Jain's-fairness /
    starvation SIM floors, and a run-vs-itself diff_sim clean. Extra args
    pass through (e.g. ``--smoke``, ``--write-artifact``,
    ``--diff OLD NEW``)."""
    run(sys.executable, "tools/sim.py", *args.rest)


@task
def perf(args):
    """The standing perf-CI gate (docs/static-analysis.md): graphcheck —
    compiled-graph contracts vs contracts/, graduation-ledger validation,
    the ledger's floors on the committed BENCH_extra / LOAD / SIM records
    (no script of this tree writes a BENCH_extra file any more) — then the
    graphlint rule gate, then the
    dataflow rules (rng-key-reuse, dead-compute, sharding-flow,
    cross-program-consistency) over all seven flagship programs, then the
    observability gate — the RUNTIME leg: with ``OBS_BASELINE_RUN`` set to
    a recorded baseline run directory (``tasks.py obs --out DIR --keep``),
    obs_diff classifies MFU/goodput/step-p99/SLO drift against it under
    declared tolerances (stale = not comparable ≠ regression) — and
    then the serving-load smoke gate (``tools/loadgen.py --smoke``:
    closed-loop load telemetry + flight recorder + LOAD floors), then the
    spec-decode smoke (``tools/spec_smoke.py``: speculative draft/verify
    token-exactness + rng-chain alignment + acceptance sanity on the tiny
    gate model), and
    finally the serve-chaos smoke (``tools/chaos.py --scenarios
    serve_kill_mid_decode,serve_crash_recover --smoke``: a mid-decode kill
    through the hardened front end with the clean-books audit, plus an
    engine crash recovered token-exactly from the write-ahead journal with
    books balanced across the restart), the fleet-chaos smoke
    (``tools/chaos.py --scenarios serve_fleet_failover --smoke``: a
    replica killed mid-decode behind the FleetRouter, its journal
    replayed token-exactly onto the survivor with fleet books balanced),
    and the simulation smoke
    (``tools/sim.py --smoke``: the Simline multi-tenant discrete-event
    gate over the real engine control plane — fairness + books + SIM
    floors + per-tenant scrape surface). Extra args go to
    tools/graphcheck.py (e.g. ``--programs train_flat,decode``)."""
    # hostlint first: the cheapest leg (pure AST, no compile) fails fast
    # on a serving-protocol regression before anything compiles a graph
    run(sys.executable, "tools/hostlint.py", "--fail-on", "warn")
    run(sys.executable, "tools/graphcheck.py", *args.rest)
    run(sys.executable, "tools/graphlint.py", "--fail-on", "error")
    # trace-only on purpose: graphcheck just compiled the same
    # programs; the dataflow rules need only the jaxpr
    run(sys.executable, "tools/graphlint.py", "--programs", "all",
        "--no-compiled", "--fail-on", "error")
    obs_cmd = [sys.executable, "tools/obs_gate.py"]
    baseline = os.environ.get("OBS_BASELINE_RUN")
    if baseline:
        obs_cmd += ["--baseline", baseline]
    run(*obs_cmd)
    # serving-load leg (CI-fast): a small closed-loop run through the
    # instrumented path — events validate, planted breach -> one flight
    # dump, run-vs-itself diff clean, LOAD_r* ledger floors hold
    run(sys.executable, "tools/loadgen.py", "--smoke")
    # engine leg (Pageline, docs/serving.md): the same closed loop through
    # the continuous-batching paged-KV engine — books + page-allocator
    # audits, a planted mid-decode kill inside a live batch, engine gauges
    # on /metrics, and the engine throughput/p99-TPOT ledger floors
    run(sys.executable, "tools/loadgen.py", "--smoke", "--engine")
    # prefix-sharing leg (Shareline, docs/serving.md#prefix-sharing): the
    # shared-vs-unshared two-leg A/B in smoke size on the wide gate model —
    # legs token-bit-exact, refcounts/index drained clean, sharing counters
    # on /metrics (the full-size measured round is `tasks.py load --prefix`)
    run(sys.executable, "tools/loadgen.py", "--smoke", "--prefix")
    # spec-decode smoke leg (Specline): greedy token-exactness + rng-chain
    # alignment + acceptance-rate sanity of the speculative draft/verify
    # pair on the tiny gate model (tools/spec_smoke.py)
    run(sys.executable, "tools/spec_smoke.py")
    # serve-chaos smoke leg: kill a request mid-decode through the hardened
    # front end and audit the books, tear the ENGINE down mid-decode and
    # recover it token-exactly from the write-ahead journal (Evictline),
    # and serve a same-prefix storm off ONE shared prefill with refcounts
    # balanced at drain (Shareline; --smoke keeps the legs greedy-only/
    # CI-fast — the full serve_* family runs under `tasks.py chaos`)
    run(sys.executable, "tools/chaos.py", "--scenarios",
        "serve_kill_mid_decode,serve_crash_recover,serve_prefix_storm",
        "--smoke")
    # fleet-chaos smoke leg (Fleetline, docs/serving.md#fleet): kill a
    # REPLICA mid-decode behind the FleetRouter — the survivor replays its
    # write-ahead journal token-exactly, the fleet books balance across
    # the handoff, one flight dump names the dead replica (the full
    # serve_fleet_*/sim_fleet family runs under `tasks.py chaos`)
    run(sys.executable, "tools/chaos.py", "--scenarios",
        "serve_fleet_failover", "--smoke")
    # simulation smoke leg (Simline): two tenants at ~1k simulated req/s
    # through the REAL engine front end under a ManualClock — books +
    # fairness + per-tenant /metrics///slo + self-diff, SIM ledger floors
    # (the full-size 3-tenant 10k req/s run is `tasks.py sim`)
    run(sys.executable, "tools/sim.py", "--smoke")


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("task", choices=sorted(TASKS))
    parser.add_argument("--cov", action="store_true", help="coverage (test)")
    parser.add_argument("--tag", help="docker image tag")
    parser.add_argument("rest", nargs="*", help="extra args passed through")
    # unknown flags flow through to the task's tool (`tasks.py load --smoke`,
    # `tasks.py chaos --scenarios preempt`) instead of dying in argparse
    args, unknown = parser.parse_known_args(argv)
    args.rest = args.rest + unknown
    TASKS[args.task](args)


if __name__ == "__main__":
    main()
