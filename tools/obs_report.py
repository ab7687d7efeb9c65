"""Render a run directory's telemetry (``events.jsonl`` /
``events-p*.jsonl`` shards + ``run_manifest.json``) into a plain-text run
summary.

    python tools/obs_report.py <run_dir> [--max-compile-rows N]

Sections: the manifest (what the run ran on), event counts, compile events
(the recompile audit — a second compile of the same function within one
process is a shape leak; resumed runs legitimately append another first
compile), the latest throughput/MFU/goodput log row, the step-time
percentiles from ``step`` span rows, every span name with its count, total
and self time and, when a profiler capture sits in the run dir, the device
idle time under each (``obs.trace.host_device_breakdown``: the span rows
laid on the capture's timeline), the goodput breakdown from ``fit_end``,
and per-request SLO stats (TTFT + histogram-derived TPOT percentiles from
``request`` rows). Stdlib-only but for the span table: runs anywhere the run
directory can be copied to (the shard merge and percentile math are
inlined; the span table needs ``perceiver_io_tpu.obs`` importable).
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
from typing import Dict, List, Optional


def _read_shard(path: str) -> List[Dict]:
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError:
                continue  # a torn tail line from a killed run is expected
    return events


def load_events(run_dir: str) -> List[Dict]:
    """All shards of the run, merged into one stream. Uses the canonical
    skew-tolerant merge (``obs.events.merged_events``) when the package is
    importable; the stdlib fallback concatenates shards sorted by ``ts``."""
    try:
        from perceiver_io_tpu.obs.events import merged_events

        return merged_events(run_dir)
    except ImportError:
        pass
    paths = []
    single = os.path.join(run_dir, "events.jsonl")
    if os.path.exists(single):
        paths.append(single)
    paths.extend(sorted(glob.glob(os.path.join(run_dir, "events-p*.jsonl"))))
    events = []
    for p in paths:
        events.extend(_read_shard(p))
    if len(paths) > 1:
        events.sort(key=lambda e: float(e.get("ts", 0.0)))
    return events


def _pct(values: List[float], p: float) -> float:
    """Nearest-rank percentile (stdlib; exact order statistic)."""
    s = sorted(values)
    return s[max(int(math.ceil(p / 100.0 * len(s))) - 1, 0)]


def load_manifest(run_dir: str) -> Optional[Dict]:
    path = os.path.join(run_dir, "run_manifest.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def _table(rows: List[List[str]], header: List[str]) -> List[str]:
    widths = [max(len(r[i]) for r in [header] + rows) for i in range(len(header))]
    out = ["  ".join(h.ljust(w) for h, w in zip(header, widths))]
    out.append("  ".join("-" * w for w in widths))
    for r in rows:
        out.append("  ".join(c.ljust(w) for c, w in zip(r, widths)))
    return out


def render(run_dir: str, max_compile_rows: int = 20) -> str:
    """The run summary as one string (the CLI prints it; tests assert on it)."""
    lines: List[str] = [f"run: {os.path.abspath(run_dir)}"]
    manifest = load_manifest(run_dir)
    if manifest is not None:
        lines.append("")
        lines.append("== manifest ==")
        for key in (
            "created_at",
            "jax_version",
            "backend",
            "device_kind",
            "device_count",
            "process_count",
            "mesh",
            "config_hash",
        ):
            if key in manifest:
                lines.append(f"  {key}: {_fmt(manifest[key])}")

    events = load_events(run_dir)
    if not events:
        lines.append("\nno events.jsonl (telemetry off, or the run never logged)")
        return "\n".join(lines)

    counts: Dict[str, int] = {}
    for e in events:
        counts[e.get("event", "?")] = counts.get(e.get("event", "?"), 0) + 1
    lines.append("")
    lines.append("== events ==")
    lines.append("  " + ", ".join(f"{k}: {v}" for k, v in sorted(counts.items())))

    compiles = [e for e in events if e.get("event") == "compile"]
    if compiles:
        lines.append("")
        lines.append("== compiles ==")
        per_fn: Dict[str, List[float]] = {}
        for e in compiles:
            per_fn.setdefault(e.get("fn", "?"), []).append(float(e.get("wall_s", 0.0)))
        rows = [
            [fn, str(len(walls)), f"{sum(walls):.3f}s"]
            for fn, walls in sorted(per_fn.items())
        ]
        lines.extend("  " + r for r in _table(rows[:max_compile_rows], ["fn", "count", "wall"]))
        # shape-leak signal: an event's n_compiles counter > 1 means the SAME
        # process compiled the same fn twice — a raw per-file count would
        # false-positive on resumed runs, whose new process appends its own
        # legitimate first compile to the shared events.jsonl
        leaks = sorted({e.get("fn", "?") for e in compiles if e.get("n_compiles", 1) > 1})
        if leaks:
            lines.append(f"  WARNING: recompiles after the first on: {', '.join(leaks)}")
        # the newest row's flash tile plans hold every geometry traced so far
        plans = next((e["flash_tiles"] for e in reversed(compiles) if e.get("flash_tiles")), [])
        if plans:
            lines.append("  flash tile plans (score tiles of 128 x 128):")
            rows = [
                [
                    r["geometry"] + (" causal" if r["causal"] else ""), f"{r['block_q']} x {r['block_kv']}",
                    str(r["band_rows"] or "-"), str(r["tiles_run"]), str(r["tiles_masked"]), str(r["tiles_skipped"]),
                    f"{r['run_share']:.3f}",
                ]
                for r in plans
            ]
            header = ["call", "blocks", "band_rows", "run", "masked", "skipped", "run_share"]
            lines.extend("  " + r for r in _table(rows, header))
        plans = next((e["embed_tiles"] for e in reversed(compiles) if e.get("embed_tiles")), [])
        if plans:
            lines.append("  position-table gradient of the compact embedding:")
            rows = [
                [f"{r['kept']} of {r['positions']} x {r['batch']}", r["route"], str(r["tile"] or "-"),
                 str(r["grid_steps"] or "-"), f"{r['onehot_flops'] / 1e9:.1f}"]
                for r in plans
            ]
            lines.extend("  " + r for r in _table(rows, ["call", "route", "tile", "grid_steps", "one-hot GFLOP"]))
        plans = next((e["moe_tiles"] for e in reversed(compiles) if e.get("moe_tiles")), [])
        if plans:
            lines.append("  the experts' grouped products:")
            rows = [
                [f"{r['m']} x {r['k']} x {r['n']}", f"{r['tm']} x {r['tk']} x {r['tn']}", f"{r['tiles_k']} x {r['tiles_n']}",
                 f"{r['rhs_block_bytes'] / 1e6:.2f}", f"{r['vmem_bytes'] / 1e6:.1f}", "yes" if r["weights_resident"] else "no"]
                for r in plans
            ]
            header = ["product", "blocks", "k x n tiles", "weight block MB", "VMEM MB", "weights resident"]
            lines.extend("  " + r for r in _table(rows, header))
        plans = next((e["mlp_gelu"] for e in reversed(compiles) if e.get("mlp_gelu")), [])
        if plans:
            lines.append("  the MLPs' exact GELU under differentiation:")
            rows = [
                [f"{r['rows']} x {r['width']} {r['dtype']}", str(r["sites"]), r["residuals"],
                 f"{r['residual_bytes'] / 1e6:.1f}", str(r["erfc_evals_per_site"])]
                for r in plans
            ]
            lines.extend("  " + r for r in _table(rows, ["hidden", "sites", "kept", "MB a site", "erfc a site"]))

    logs = [e for e in events if e.get("event") == "log"]
    if logs:
        last = logs[-1]
        lines.append("")
        lines.append(f"== latest log row (step {last.get('step')}) ==")
        for key in sorted(last):
            if key in ("ts", "event", "step"):
                continue
            lines.append(f"  {key}: {_fmt(last[key])}")

    spans = [e for e in events if e.get("event") == "span"]
    steps = [s for s in spans if s.get("name") == "step"]
    if steps:
        lines.append("")
        lines.append(f"== step breakdown ({len(steps)} step spans) ==")
        durs = [float(s["dur_ms"]) for s in steps]
        low = "  (low_n: exact order statistics)" if len(durs) < 5 else ""
        lines.append(
            f"  step_ms: p50 {_pct(durs, 50):.4g}  p99 {_pct(durs, 99):.4g}  "
            f"mean {sum(durs)/len(durs):.4g}{low}"
        )
        for attr in ("input_wait_ms", "dispatch_ms"):
            vals = [
                float(s["attrs"][attr])
                for s in steps
                if isinstance(s.get("attrs"), dict) and attr in s["attrs"]
            ]
            if vals:
                lines.append(f"  {attr}: mean {sum(vals)/len(vals):.4g}")
        for phase in ("checkpoint", "eval"):
            rows = [s for s in spans if s.get("name") == phase]
            if rows:
                total = sum(float(s["dur_ms"]) for s in rows)
                lines.append(f"  {phase}: {len(rows)}x, total {total:.4g} ms")

    if spans:
        # every span name with its count, total and self time; with a
        # profiler capture in the run dir also the device idle time under
        # each (needs the package; host side only without it)
        pbs = sorted(glob.glob(os.path.join(run_dir, "**", "*.xplane.pb"), recursive=True))
        try:
            try:
                from perceiver_io_tpu.obs.trace import host_device_breakdown
            except ImportError:  # run as a script: the package lies beside tools/
                import sys

                sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
                from perceiver_io_tpu.obs.trace import host_device_breakdown

            bd = host_device_breakdown(spans, pbs[-1] if pbs else None)
        except ImportError:
            bd = None
            lines.append("")
            lines.append("  (install the package for the per-span breakdown)")
        if bd is not None:
            lines.append("")
            lines.append(
                f"== spans ({len(spans)} rows"
                + (f", device idle from {os.path.relpath(pbs[-1], run_dir)}" if "device" in bd else "")
                + ") =="
            )
            rows = [
                [name, str(v["count"]), f"{v['total_ms']:.4g}", f"{v['self_ms']:.4g}",
                 f"{v['idle_ms']:.4g}" if "idle_ms" in v else ""]
                for name, v in sorted(bd["spans"].items(), key=lambda kv: -kv[1]["total_ms"])
            ]
            lines.extend("  " + r for r in _table(rows, ["span", "count", "total ms", "self ms", "device idle ms"]))
            dev = bd.get("device")
            if dev:
                lines.append(
                    f"  device: busy {dev['busy_ms']:.4g} ms, idle {dev['idle_ms']:.4g} ms "
                    f"of {dev['window_ms']:.4g} ms under the spans"
                )
            # what the process did before its first step (obs/startup.py): imports by
            # package, trace / lower / compile by function, largest self time first
            from perceiver_io_tpu.obs.startup import IMPORT, startup_table

            table = startup_table(spans)
            if table:
                lines.append("")
                lines.append(f"== start-up ({sum(a['self_ms'] for a in table) / 1e3:.4g} s in {len(table)} rows) ==")
                # every import row (twenty a process: the record keeps them whatever else it drops), then the largest of the rest
                rest = [a for a in table if a["name"] != IMPORT]
                shown = sorted([a for a in table if a["name"] == IMPORT] + rest[:max_compile_rows], key=lambda a: -a["self_ms"])
                rows = [
                    [a["name"], str(a["what"]), a["cache"] or "", str(a["count"]), f"{a['total_ms']:.4g}",
                     f"{a['self_ms']:.4g}"]
                    for a in shown
                ]
                lines.extend("  " + r for r in _table(rows, ["span", "package / fn", "cache", "count", "total ms",
                                                            "self ms"]))
                if len(rest) > max_compile_rows:
                    left = sum(a["self_ms"] for a in rest[max_compile_rows:])
                    lines.append(f"  ... {len(rest) - max_compile_rows} more rows, {left:.4g} ms of self time")

    # Probeline per-scope trends (probe events: one snapshot per log
    # boundary, scopes keyed "NNN:name" — sorted == topological order) and
    # blast-radius reports. Non-finite stats arrive as JSON null (the
    # strict-JSON NaN policy), so None in a stat column means NONFINITE.
    probe_rows = [e for e in events if e.get("event") == "probe"]
    if probe_rows:
        series: Dict[str, List] = {}
        for e in probe_rows:
            for k, st in (e.get("scopes") or {}).items():
                if isinstance(st, dict):
                    series.setdefault(k, []).append(st)
        lines.append("")
        lines.append(
            f"== probes ({len(probe_rows)} snapshots, {len(series)} scopes) =="
        )

        def _bare(key):
            # must track obs.probes.scope_of — inlined because this renderer
            # stays stdlib-only (same pattern as the GROWTH fallback below)
            head, sep, tail = key.partition(":")
            return tail if sep and head.isdigit() else key

        def _spaced(vals, n=5):
            if len(vals) <= n:
                return vals
            idx = [round(i * (len(vals) - 1) / (n - 1)) for i in range(n)]
            return [vals[i] for i in idx]

        rows = []
        for k in sorted(series)[:48]:
            pts = series[k]
            main_key = "rms" if "rms" in pts[-1] else ("l2" if "l2" in pts[-1] else "ratio")
            vals = [s.get(main_key) for s in pts]
            bad = any(v is None for v in vals) or any(
                (s.get("nonfinite_frac") or 0) > 0 for s in pts
            )
            trend = " -> ".join("nan" if v is None else f"{v:.3g}" for v in _spaced(vals))
            rows.append([_bare(k), f"{main_key}: {trend}", "NONFINITE" if bad else ""])
        lines.extend("  " + r for r in _table(rows, ["scope", "trend (first -> last)", ""]))

    for b in (e for e in events if e.get("event") == "probe.blast"):
        lines.append(
            f"  BLAST [{b.get('trigger')}] step {b.get('step')}: first non-finite scope "
            f"{b.get('scope')!r} ({b.get('n_affected')}/{b.get('n_scopes')} scopes affected)"
        )

    ends = [e for e in events if e.get("event") == "fit_end"]
    if ends:
        end = ends[-1]
        lines.append("")
        lines.append("== goodput (fit_end) ==")
        for key in sorted(end):
            if key in ("ts", "event"):
                continue
            lines.append(f"  {key}: {_fmt(end[key])}")

    # per-request SLO stats; "generate" is the pre-request-event legacy kind
    reqs = [e for e in events if e.get("event") in ("request", "generate")]
    if reqs:
        lines.append("")
        outcomes: Dict[str, int] = {}
        for r in reqs:
            o = str(r.get("outcome", "ok"))
            outcomes[o] = outcomes.get(o, 0) + 1
        lines.append(
            f"== requests ({len(reqs)}: "
            + ", ".join(f"{k} {v}" for k, v in sorted(outcomes.items()))
            + ") =="
        )
        ok = [r for r in reqs if r.get("outcome", "ok") == "ok"]
        # steady-state stats exclude calls that paid a compile; when EVERY
        # call compiled there is no steady state — say so instead of
        # presenting compile-inflated latencies as clean numbers
        warm = [g for g in ok if not g.get("compiled")]
        if warm:
            note = "  (warm requests only)" if len(warm) < len(ok) else ""
        else:
            warm = ok
            note = "  (ALL requests paid a compile — latencies include it)"
        for key in ("ttft_s", "prefill_s", "per_token_s", "tokens_per_sec"):
            vals = [float(g[key]) for g in warm if g.get(key) is not None]
            if vals and not (key == "prefill_s" and any("ttft_s" in g for g in warm)):
                lines.append(
                    f"  {key}: mean {sum(vals)/len(vals):.4g}  "
                    f"min {min(vals):.4g}  max {max(vals):.4g}" + note
                )
        # TPOT percentiles over every decoded token: merged per-request
        # log-bucket histograms (exact addition — global bucket bounds).
        # Canonical math lives in obs.metrics (the bucket base is
        # load-bearing for every committed tpot_hist); the inline copy is
        # only the no-package fallback, same pattern as load_events.
        def _merge_hists(rows_):
            out: Dict[int, int] = {}
            for g in rows_:
                for k, v in (g.get("tpot_hist") or {}).items():
                    out[int(k)] = out.get(int(k), 0) + int(v)
            return out

        try:
            from perceiver_io_tpu.obs.metrics import percentile_from_counts as _hpct
        except ImportError:
            growth = 2.0 ** 0.25  # must track obs.metrics.GROWTH

            def _hpct(counts, p):
                n = sum(counts.values())
                target, seen = max(int(math.ceil(p / 100.0 * n)), 1), 0
                for idx in sorted(counts):
                    seen += counts[idx]
                    if seen >= target:
                        return growth ** (idx + 0.5)
        merged = _merge_hists(warm)
        n_tok = sum(merged.values())
        if n_tok:
            low = "  (low_n)" if n_tok < 5 else ""
            lines.append(
                f"  tpot_s ({n_tok} tokens): p50 {_hpct(merged, 50):.4g}  "
                f"p90 {_hpct(merged, 90):.4g}  p99 {_hpct(merged, 99):.4g}{low}" + note
            )
        # queue-wait (loadgen-issued requests carry admission telemetry)
        qws = [float(g["queue_wait_s"]) for g in warm if g.get("queue_wait_s") is not None]
        if qws:
            lines.append(
                f"  queue_wait_s: p50 {_pct(qws, 50):.4g}  p99 {_pct(qws, 99):.4g}  "
                f"mean {sum(qws)/len(qws):.4g}" + note
            )
        # batched-engine occupancy (Pageline, docs/serving.md): requests
        # served by the continuous-batching engine carry the batch size
        # their decode steps ran at
        bsz = [float(g["batch_size_at_decode"]) for g in reqs
               if g.get("batch_size_at_decode") is not None]
        if bsz:
            lines.append(
                f"  batch_size_at_decode: mean {sum(bsz)/len(bsz):.4g}  "
                f"min {min(bsz):.4g}  max {max(bsz):.4g}  ({len(bsz)} engine requests)"
            )
        # prefix sharing (Shareline, docs/serving.md#prefix-sharing): hit
        # rate over the run's requests plus what the hits came to — pages
        # referenced instead of recomputed, prompt tokens prefill skipped
        hit_rows = [e for e in events if e.get("event") == "serve.prefix_hit"]
        if hit_rows:
            pages_shared = sum(int(h.get("pages_matched", 0)) for h in hit_rows)
            skipped = sum(int(h.get("tokens_skipped", 0)) for h in hit_rows)
            lines.append(
                f"  prefix_hit_rate: {len(hit_rows) / len(reqs):.3f}  "
                f"({len(hit_rows)}/{len(reqs)} requests, {pages_shared} pages "
                f"shared, {skipped} prompt tokens skipped)"
            )
        # per-tenant rollup (Simline, docs/serving.md#multi-tenant-telemetry):
        # tenant-stamped request rows become one line per tenant — outcome
        # rates, TTFT/TPOT percentiles, and the pages-held peak read from
        # the labeled engine gauge's high-water mark in the metrics rows
        tenants = sorted({str(r["tenant"]) for r in reqs if r.get("tenant") is not None})
        if tenants:
            peaks: Dict[str, float] = {}
            for e in events:
                if e.get("event") == "metrics":
                    for k, v in (e.get("gauge_peaks") or {}).items():
                        if k.startswith("engine_kv_pages_used{") and isinstance(v, (int, float)):
                            peaks[k] = max(peaks.get(k, 0.0), float(v))
            rows = []
            for t in tenants:
                trows = [r for r in reqs if str(r.get("tenant")) == t]
                n_t = len(trows)
                by_outcome: Dict[str, int] = {}
                for r in trows:
                    o = str(r.get("outcome", "ok"))
                    by_outcome[o] = by_outcome.get(o, 0) + 1
                tok = [r for r in trows if r.get("outcome", "ok") == "ok"]
                ttfts = [float(r["ttft_s"]) for r in tok if r.get("ttft_s") is not None]
                th = _merge_hists(tok)
                peak = peaks.get(f'engine_kv_pages_used{{tenant="{t}"}}')
                rows.append([
                    t,
                    str(n_t),
                    f"{by_outcome.get('ok', 0) / n_t:.3f}",
                    f"{by_outcome.get('shed', 0) / n_t:.3f}",
                    f"{by_outcome.get('timeout', 0) / n_t:.3f}",
                    f"{_pct(ttfts, 50):.4g}" if ttfts else "-",
                    f"{_pct(ttfts, 99):.4g}" if ttfts else "-",
                    f"{_hpct(th, 50):.4g}" if th else "-",
                    f"{_hpct(th, 99):.4g}" if th else "-",
                    f"{peak:.4g}" if peak is not None else "-",
                ])
            lines.append("")
            lines.append(f"== tenants ({len(tenants)}) ==")
            lines.extend("  " + r for r in _table(rows, [
                "tenant", "reqs", "ok", "shed", "timeout",
                "ttft_p50", "ttft_p99", "tpot_p50", "tpot_p99", "pages_peak",
            ]))

    # engine gauges (Pageline): the LAST registry snapshot's engine_* gauges
    # plus their run maxima — batch occupancy and page-pool utilization
    metric_rows = [e for e in events if e.get("event") == "metrics"]
    engine_series: Dict[str, List[float]] = {}
    for e in metric_rows:
        for k, v in (e.get("gauges") or {}).items():
            if k.startswith("engine_") and isinstance(v, (int, float)):
                engine_series.setdefault(k, []).append(float(v))
    if engine_series:
        lines.append("")
        lines.append("== engine (paged KV / continuous batching) ==")
        for k in sorted(engine_series):
            vals = engine_series[k]
            lines.append(f"  {k}: last {vals[-1]:.4g}  max {max(vals):.4g}")

    # per-request tail attribution: queue-wait -> prefill -> decode ->
    # compile-if-cold, the compile leg joined from span-stamped compile
    # events. Canonical join lives in obs.slo.request_breakdowns; the
    # inline copy is the no-package fallback (same pattern as load_events).
    bd = None
    if reqs:
        try:
            from perceiver_io_tpu.obs.slo import request_breakdowns

            bd = request_breakdowns(events)
        except ImportError:
            compile_s: Dict[str, float] = {}
            for e in events:
                if e.get("event") == "compile" and e.get("span_id") is not None:
                    compile_s[e["span_id"]] = compile_s.get(e["span_id"], 0.0) + float(
                        e.get("wall_s", 0.0)
                    )
            brows = []
            for r in reqs:
                brows.append(
                    {
                        "request_id": r.get("request_id"),
                        "outcome": r.get("outcome", "ok"),
                        "compiled": bool(r.get("compiled")),
                        "queue_wait_ms": None
                        if r.get("queue_wait_s") is None
                        else 1e3 * float(r["queue_wait_s"]),
                        "prefill_ms": None
                        if r.get("ttft_s") is None
                        else 1e3 * float(r["ttft_s"]),
                        "decode_ms": None
                        if r.get("decode_s") is None
                        else 1e3 * float(r["decode_s"]),
                        "compile_ms": 1e3 * compile_s.get(r.get("span_id"), 0.0),
                        "service_ms": 1e3
                        * sum(float(r.get(k) or 0.0) for k in ("ttft_s", "decode_s")),
                        "total_ms": 1e3
                        * sum(
                            float(r.get(k) or 0.0)
                            for k in ("queue_wait_s", "ttft_s", "decode_s")
                        ),
                    }
                )
            ok_rows = [b for b in brows if b["outcome"] == "ok"]
            warm_rows = [b for b in ok_rows if not b["compiled"]]
            pool = warm_rows or ok_rows
            med = {}
            for key in ("queue_wait_ms", "prefill_ms", "decode_ms", "service_ms", "total_ms"):
                vals = sorted(float(b[key]) for b in pool if b.get(key) is not None)
                if vals:
                    n = len(vals)
                    med[key] = vals[n // 2] if n % 2 else 0.5 * (vals[n // 2 - 1] + vals[n // 2])
            bd = {"n": len(brows), "requests": brows, "medians": med,
                  "warm_only": bool(warm_rows)}
    if bd and bd["n"]:
        lines.append("")
        lines.append(
            f"== request breakdown (queue -> prefill -> decode, {bd['n']} requests"
            + ("" if bd.get("warm_only", True) else "; ALL cold")
            + ") =="
        )
        med = bd.get("medians", {})
        if med:
            lines.append(
                "  medians: "
                + "  ".join(
                    f"{k.replace('_ms', '')} {med[k]:.4g} ms"
                    for k in (
                        "queue_wait_ms", "prefill_ms", "decode_ms",
                        "compile_ms_cold", "service_ms", "total_ms",
                    )
                    if k in med
                )
            )
        slowest = sorted(
            (b for b in bd["requests"] if b.get("total_ms") is not None),
            key=lambda b: -float(b["total_ms"]),
        )[:5]
        if slowest:
            rows = [
                [
                    str(b.get("request_id") or "?")[:10],
                    *(
                        "-" if b.get(k) is None else f"{float(b[k]):.4g}"
                        for k in (
                            "queue_wait_ms", "prefill_ms", "decode_ms",
                            "compile_ms", "total_ms",
                        )
                    ),
                    b.get("outcome", "ok") + (" (cold)" if b.get("compiled") else ""),
                ]
                for b in slowest
            ]
            lines.extend(
                "  " + r
                for r in _table(
                    rows,
                    ["request", "queue_ms", "prefill_ms", "decode_ms",
                     "compile_ms", "total_ms", "outcome"],
                )
            )
    return "\n".join(lines)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("run_dir", help="directory holding events.jsonl / run_manifest.json")
    p.add_argument(
        "--max-compile-rows", type=int, default=20, help="cap on compile-table rows"
    )
    args = p.parse_args()
    print(render(args.run_dir, max_compile_rows=args.max_compile_rows))


if __name__ == "__main__":
    main()
