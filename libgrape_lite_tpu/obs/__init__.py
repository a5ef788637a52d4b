"""obs/ — structured superstep tracing and a metrics registry.

One event model unifies the signals PR 2–4 left scattered (op/byte
ledger, guard breach bundles, ad-hoc perf_counter logs): the worker
emits nested host spans (`peval`, `superstep`, `chunk`,
`checkpoint_write`, ...) with a sync-before-close timing convention
(see tracer.py), guard/ft/loader attach their events to the same
timeline, and a `MetricsRegistry` accumulates counters/gauges/
histograms snapshotted at query end.  Export: JSONL + Chrome
`trace_event` JSON (Perfetto-loadable) and Prometheus-text/JSON
metrics dumps.  docs/OBSERVABILITY.md is the user guide;
scripts/trace_report.py renders the per-superstep table.

Off by default: `obs.tracer()` returns a disabled singleton whose
`span()` is a sub-microsecond no-op (pinned by test; while a
`jax.profiler` session records it is a `TraceAnnotation` named
`grape.<name>`, so the span lands in the profiler's trace), and
arming is a
host-side decision invisible to jit tracing — the fused hot path's
lowered HLO is byte-identical disarmed vs armed (pinned by test).

Arming: GRAPE_TRACE=/path/trace.json, GRAPE_METRICS=/path/metrics
(env, read once lazily), `--trace`/`--metrics` (CLI), or
`obs.configure(...)` (API).

The telemetry plane (PR 15) layers four always-on surfaces on top:
`federation` (one namespaced snapshot()/reset() over every *_STATS
registry), `exporter` (live OpenMetrics HTTP endpoint, armed via
GRAPE_METRICS_PORT / --metrics_port), `slo` (latency objectives with
error-budget burn; breach = instant + counter, never an exception),
and `recorder` (a flight-recorder ring dumping correlated postmortem
bundles on guard breach / fence violation / deadline storm).

The gang plane (PR 20) extends all of it across ranks: `gang`
(per-rank sidecar files, a clock-offset handshake over the existing
host allgather, a rank-0 assembler producing ONE merged Perfetto
timeline, and the distributed flight recorder dumping every rank's
postmortem under one shared incident id).
`scripts/trace_report.py --gang` renders the merged timeline.
"""

from libgrape_lite_tpu.obs import federation
from libgrape_lite_tpu.obs import gang
from libgrape_lite_tpu.obs.config import (
    METRICS_ENV,
    TRACE_ENV,
    armed,
    configure,
    flush,
    history,
    metrics,
    reset,
    trace_id,
    tracer,
)
from libgrape_lite_tpu.obs.exporter import (
    METRICS_PORT_ENV,
    MetricsExporter,
    maybe_start_from_env,
    start_exporter,
    stop_exporter,
)
from libgrape_lite_tpu.obs.export import (
    load_trace,
    rollup,
    write_chrome_trace,
)
from libgrape_lite_tpu.obs.federation import FederatedStats
from libgrape_lite_tpu.obs import slo
from libgrape_lite_tpu.obs.metrics import (
    NULL_METRICS,
    MetricsRegistry,
)
from libgrape_lite_tpu.obs.recorder import RECORDER, FlightRecorder
from libgrape_lite_tpu.obs.tracer import Span, Tracer

__all__ = [
    "federation",
    "gang",
    "slo",
    "FederatedStats",
    "METRICS_PORT_ENV",
    "MetricsExporter",
    "maybe_start_from_env",
    "start_exporter",
    "stop_exporter",
    "RECORDER",
    "FlightRecorder",
    "METRICS_ENV",
    "TRACE_ENV",
    "armed",
    "configure",
    "flush",
    "history",
    "metrics",
    "reset",
    "trace_id",
    "tracer",
    "load_trace",
    "rollup",
    "write_chrome_trace",
    "MetricsRegistry",
    "NULL_METRICS",
    "Span",
    "Tracer",
]
