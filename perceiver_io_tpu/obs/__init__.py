"""Observability subsystem: structured run events, request/step spans, a
metrics registry, per-request SLO aggregation, MFU/goodput accounting,
recompile tracking, and labeled device-trace rollups.

One measurement surface for every perf PR (ISSUE 1), the request-level
Spanline layer (ISSUE 8), and the in-graph Probeline numerics layer
(ISSUE 9 — ``obs.probes``: per-scope activation/gradient stats as aux
outputs of the compiled step, blast-radius attribution on sentinel trips,
decode health gauges): the trainer emits ``events.jsonl`` +
``run_manifest.json`` next to ``metrics.csv`` (sharded per process on
multi-host programs, merged back by ``obs.events.merged_events``); host
spans (``obs.trace``) attribute every ``fault.*``/``compile``/``resume``
event to the step or request it happened in; instrumented generation emits
per-request ``request`` rows aggregated by ``obs.slo``; counters/gauges/
log-bucketed histograms live in ``obs.metrics`` with Prometheus/JSON
exporters; the benches report analytic MFU against a per-device peak-FLOPs
table; traces captured with ``utils.profiling.trace`` aggregate by
``jax.named_scope`` module instead of raw HLO op names (``obs.xplane``);
and silent shape-driven recompiles surface as ``compile`` events
(``obs.recompile``). The serving-observability layer (ISSUE 11) rides on
top: ``obs.loadgen`` drives seeded closed/open-loop synthetic load through
the instrumented path (queue-wait accounted per request), ``obs.flightrec``
keeps a bounded ring of recent telemetry and dumps it atomically on SLO
breach / error / sentinel trip / SIGUSR1, and ``obs.server`` exposes
``/metrics`` + ``/healthz`` + ``/slo`` from a stdlib HTTP thread. Render a
run directory with ``tools/obs_report.py``; diff two runs with
``tools/obs_diff.py``; drive and gate load with ``tools/loadgen.py``.
"""

from perceiver_io_tpu._startup import RECORD as _STARTUP

_IMPORTING = _STARTUP.open("startup/import", package=__name__)

from perceiver_io_tpu.obs.events import (  # noqa: F401
    EVENT_SCHEMA_VERSION,
    KNOWN_EVENT_KINDS,
    REQUEST_OUTCOMES,
    EventLog,
    config_hash,
    event_shards,
    merged_events,
    validate_events,
    write_run_manifest,
)
from perceiver_io_tpu.obs.probes import (  # noqa: F401
    ProbeConfig,
    blast_report,
    decode_health,
    probe,
    probes_live_report,
    snapshot_to_host,
)
from perceiver_io_tpu.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
)
from perceiver_io_tpu.obs.mfu import (  # noqa: F401
    GoodputTracker,
    clm_train_telemetry,
    device_peak_flops,
)
from perceiver_io_tpu.obs.flightrec import FlightRecorder, SLOBounds  # noqa: F401
from perceiver_io_tpu.obs.loadgen import (  # noqa: F401
    LoadReport,
    WorkloadSpec,
    arrival_schedule,
    build_load_doc,
    diff_load,
    run_load,
    summarize_load,
)
from perceiver_io_tpu.obs.recompile import RecompileTracker, shape_signature  # noqa: F401
from perceiver_io_tpu.obs.server import ObsServer  # noqa: F401
from perceiver_io_tpu.obs.slo import (  # noqa: F401
    build_slo_report,
    request_breakdowns,
    write_slo_report,
)
from perceiver_io_tpu.obs.trace import (  # noqa: F401
    Span,
    Tracer,
    current_span,
    current_span_id,
    host_device_breakdown,
)
from perceiver_io_tpu.obs import startup  # noqa: F401  (adopts the start-up record, registers JAX's listeners)

__all__ = [
    "EVENT_SCHEMA_VERSION",
    "KNOWN_EVENT_KINDS",
    "REQUEST_OUTCOMES",
    "ProbeConfig",
    "blast_report",
    "decode_health",
    "probe",
    "probes_live_report",
    "snapshot_to_host",
    "EventLog",
    "config_hash",
    "event_shards",
    "merged_events",
    "validate_events",
    "write_run_manifest",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "default_registry",
    "GoodputTracker",
    "clm_train_telemetry",
    "device_peak_flops",
    "RecompileTracker",
    "shape_signature",
    "build_slo_report",
    "request_breakdowns",
    "write_slo_report",
    "FlightRecorder",
    "SLOBounds",
    "LoadReport",
    "WorkloadSpec",
    "arrival_schedule",
    "build_load_doc",
    "diff_load",
    "run_load",
    "summarize_load",
    "ObsServer",
    "Span",
    "Tracer",
    "current_span",
    "current_span_id",
    "host_device_breakdown",
]

_STARTUP.close(_IMPORTING)
