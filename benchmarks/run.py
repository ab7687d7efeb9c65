"""The benchmark's one entry: run one cell once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is ``workloads/<cell>.json``; it names a configuration
(``configs/<config>.json``, which names its family under ``families/``) and a
driver (``drivers/<driver>.py``). Which metrics a cell reports is read from
``BENCHMARK.json``; a per-layer metric is ``layers/<metric>.py`` with a
``read(run)``. Nothing here names a cell, a configuration or a metric.

The run fails before measuring unless JAX finds TPU chips, at least as many
as the cell asks for. Earlier lines are free text; the last line of standard
output is the result as one JSON object."""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

# a traced run measures this long, whatever --seconds says: the profiler's
# trace of a longer window runs to gigabytes, and a few steady seconds hold
# everything a per-layer metric reads
TRACE_SECONDS = 3.0


def load_json(kind: str, name: str, root: str = HERE) -> dict:
    """``<kind>/<name>.json`` under the benchmark's directory; a missing file
    is an error that names it."""
    path = os.path.join(root, kind, name + ".json")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmarks/run.py: {kind[:-1]} {name!r} has no file {os.path.relpath(path, CHECKOUT)}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` as a module (metric names hold dots, so the file
    is loaded by path)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmarks/run.py: {kind[:-1]} {name!r} has no file {os.path.relpath(path, CHECKOUT)}")
    spec = importlib.util.spec_from_file_location(f"benchmarks.{kind}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def declared_metrics(bench: dict, cell_name: str, group: str, end_to_end=None) -> dict:
    """The metrics of ``group`` that ``BENCHMARK.json`` declares for this
    cell, by name. A metric without a ``workloads`` key belongs to every cell
    (per-layer: every cell that reports the end-to-end metric it moves)."""
    out = {}
    for metric in bench[group]:
        cells = metric.get("workloads")
        if cells is not None:
            if cell_name in cells:
                out[metric["name"]] = metric
        elif group == "end_to_end" or metric["moves"] in end_to_end:
            out[metric["name"]] = metric
    return out


def mark(what: str) -> None:
    """A free-text line with the seconds since the process started."""
    print(f"[{time.perf_counter() - PROCESS_START:8.3f} s] {what}", flush=True)


class Context:
    """What a driver gets: the cell, its configuration and family, the
    arguments, and the clock marks of the measured window."""

    def __init__(self, args, cell, config, family, programs):
        self.seed, self.trace = args.seed, bool(args.trace)
        self.window_seconds = min(args.seconds, TRACE_SECONDS) if self.trace else args.seconds
        self.cell, self.config, self.family, self.programs = cell, config, family, programs
        self.keep_trace = args.keep_trace
        self.mark = mark
        self.setup_s = self.window_s = None
        self.programs_in_window = None
        self.trace_data = None
        self.memory_peak_bytes = None

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends where it starts. A traced run
        records it with the profiler, under one host annotation."""
        import jax

        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if self.trace else None
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        built = self.programs.n
        t0 = time.perf_counter()
        self.setup_s = t0 - PROCESS_START
        try:
            with jax.profiler.TraceAnnotation("bench/window"):
                yield
            self.window_s = time.perf_counter() - t0
            self.programs_in_window = self.programs.n - built
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
        if trace_dir:
            from benchmarks.lib import trace

            path = trace.find_xplane(trace_dir)
            self.trace_data = trace.load_xplane(path)
            if self.keep_trace:
                os.makedirs(self.keep_trace, exist_ok=True)
                shutil.copy(path, self.keep_trace)
            shutil.rmtree(trace_dir, ignore_errors=True)

    def after_window(self):
        """Call once the program's state is freed and before the reference
        runs: the peak memory read here is the program's."""
        import jax

        n = self.cell["chips"]
        stats = [d.memory_stats() or {} for d in jax.devices()[:n]]  # the CPU backend reports none
        print(f"memory_stats: {json.dumps(stats)}", flush=True)
        # the TPU runtime keeps live arrays and the scratch of running programs
        # in two pools and reports a peak for each; a step holds both at once
        self.memory_peak_bytes = max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0) for s in stats)


def enable_cache() -> str:
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else at a fixed path inside the checkout. Every program is kept,
    however quick its compile."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(CHECKOUT, ".cache", "jax")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_compilation_cache_max_size", -1)  # never evict: a cell's programs run to hundreds of MB
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def require_chips(n: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        raise SystemExit(
            f"benchmarks/run.py: the cell needs {n} TPU chip(s); JAX found "
            f"{len(devices)} x {devices[0].platform} ({devices[0].device_kind})"
        )
    return devices


def trace_summary(ctx, n_chips: int) -> dict:
    """``busy_s``, ``window_s`` and the breakdown from a traced window."""
    from benchmarks.lib import trace

    data = ctx.trace_data
    window = trace.host_window(data["host"], "bench/window")
    planes = sorted(data["devices"])[:n_chips]
    if not planes:
        raise SystemExit("benchmarks/run.py: the trace holds no device plane")
    busy = [trace.busy_ns(trace.clip(data["devices"][p], window)) for p in planes]
    first = trace.clip(data["devices"][planes[0]], window)
    gaps = trace.idle_gaps(first, [h for h in data["host"] if h[0] != "bench/window"], window)
    return {
        "window": window,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (window[1] - window[0]) / 1e9,
        "breakdown": {"device_ops": trace.top(trace.totals_by_name(first)), "idle_gaps": trace.top(gaps)},
    }


def run_cell(args, devices, data_root: str = HERE, bench_path: str = os.path.join(CHECKOUT, "BENCHMARK.json")) -> dict:
    """Everything but the look for a chip: set the cell up, run its window,
    check it, read its metrics. Returns the result object. ``data_root`` and
    ``bench_path`` let a test point at tiny cells of its own."""
    from benchmarks.lib.peaks import load_peaks
    from benchmarks.lib.programs import Programs

    with open(bench_path) as f:
        bench = json.load(f)
    cell = load_json("workloads", args.workload, data_root)
    config = load_json("configs", cell["config"], data_root)
    family = importlib.import_module(f"benchmarks.families.{config['family']}").Family(config)
    driver = load_module("drivers", cell["driver"])
    programs = Programs()
    ctx = Context(args, cell, config, family, programs)
    out = driver.run(ctx)

    checks = list(out["checks"])
    checks.append({"name": "programs_built_in_window", "value": ctx.programs_in_window, "limit": 0,
                   "ok": ctx.programs_in_window == 0, "note": ""})
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}) {'ok' if c['ok'] else 'FAILED'} {c['note']}",
              flush=True)
    print(f"programs: {json.dumps(programs.snapshot())}; setup_s {ctx.setup_s:.3f}; window_s {ctx.window_s:.3f}",
          flush=True)

    end_to_end = {**out["end_to_end"], "setup_s": ctx.setup_s}
    declared = declared_metrics(bench, cell["name"], "end_to_end")
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    result = {"correct": all(c["ok"] for c in checks), "attempted": out["attempted"], "failed": out["failed"]}
    if not ctx.trace:
        missing = sorted(set(declared) - set(end_to_end))
        if missing:
            raise SystemExit(f"benchmarks/run.py: the {cell['driver']} driver gave no {missing}")
        result["metrics"] = {k: {"value": end_to_end[k], "unit": declared[k]["unit"]} for k in declared}
    else:
        summary = trace_summary(ctx, cell["chips"])
        run = {"cell": cell, "config": config, "family": family, "peaks": load_peaks(devices[0].device_kind),
               "end_to_end": end_to_end, "counters": out["counters"], "trace": ctx.trace_data,
               "trace_window": summary["window"], "busy_s": summary["busy_s"], "window_s": summary["window_s"],
               "chips": cell["chips"]}
        metrics = {}
        for name, metric in declared_metrics(bench, cell["name"], "per_layer", declared).items():
            value = load_module("layers", name).read(run)
            if value is not None:
                metrics[name] = {"value": value, "unit": metric["unit"]}
        result["metrics"] = metrics
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["breakdown"] = summary["breakdown"]
    result["device"] = device
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True, help="a cell: workloads/<name>.json")
    p.add_argument("--seed", type=int, required=True, help="inputs and weights")
    p.add_argument("--seconds", type=float, required=True, help="length of the measured window")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0, help="1: a short traced window, per-layer metrics")
    p.add_argument("--keep-trace", default=None, help="a directory to copy the raw .xplane.pb into (for a human)")
    args = p.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("benchmarks/run.py: --seed must not be negative")
    cell = load_json("workloads", args.workload)
    cache_dir = enable_cache()
    devices = require_chips(cell["chips"])
    mark("jax imported, chips found")
    print(f"cell {args.workload} seed {args.seed} seconds {args.seconds} trace {args.trace}"
          f"{f' (a traced window is {TRACE_SECONDS} s)' if args.trace else ''}; cache {cache_dir}", flush=True)
    result = run_cell(args, devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
