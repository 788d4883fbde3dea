"""paddle_tpu.observability — unified metrics, structured event timeline,
and chrome-trace export across jit / training / serving.

The TPU-native rebuild of the reference's profiler subsystem's LIVE
half (SURVEY.md N20 host tracer + P26 Python Profiler): where
``paddle_tpu.profiler`` wraps ``jax.profiler`` device traces, this
package answers the production questions a device trace cannot —
"why did step time spike", "which function retraced", "how deep is the
serving queue" — from one process-wide place.

Three layers (see each module's docstring):

* :mod:`~paddle_tpu.observability.metrics` — typed Counter / Gauge /
  Histogram / Summary registry with label sets; ``snapshot()`` (nested JSON) and
  ``render_prometheus()`` (text exposition); absorbs the PR 2
  ``profiler.counters()`` provider registry.
* :mod:`~paddle_tpu.observability.events` — bounded ring-buffer
  structured event log with chrome-trace/Perfetto JSON export.
* :mod:`~paddle_tpu.observability.span` — the span log:
  ``span(name, rid=None, **args)`` writes one record a span into the
  events ring when it ends (start and duration in ns on the profiler's
  clock, thread, cause, request id, arguments), keeps count and seconds
  per name (``span.seconds``) and opens a ``jax.profiler
  .TraceAnnotation``; ``build(program, key)`` keeps one record a
  program build, by phase.

Phase 2 (request-scoped + externally visible):

* :mod:`~paddle_tpu.observability.tracing` — per-request
  :class:`RequestTrace` flight records in a bounded
  :class:`FlightRecorder` (all live + last-N finished), exportable as
  chrome async spans.
* :mod:`~paddle_tpu.observability.slo` — declared objectives over
  step-sized rolling windows; compliance, multi-window burn rate, and
  an overall ``slo_healthy`` readiness signal.
* :mod:`~paddle_tpu.observability.server` — stdlib HTTP exporter
  (``/metrics``, ``/healthz``, ``/readyz``, ``/debug/requests``,
  ``/debug/slo``, ``/debug/programs``, ``/trace``) on a background
  thread.

Phase 3 (the performance observatory):

* :mod:`~paddle_tpu.observability.profiling` — per-compiled-program
  cost cards (XLA cost/memory analysis, compile seconds, bucket
  metadata) in a process-wide :class:`ProgramCardRegistry`; the
  engine's cost model for per-request attribution.
* :mod:`~paddle_tpu.observability.memory` — device-memory ledger
  reconciling component-accounted bytes against ``jax.live_arrays()``
  (leak-detector delta) plus the backend-bandwidth lookup.

Phase 4 (the mesh stack):

* :mod:`~paddle_tpu.observability.comms` — collective-comms ledger:
  a jaxpr walker counting collectives by (op, axis) with analytic
  ring-algorithm wire bytes, an ICI/DCN interconnect-bandwidth
  datasheet + modeled comms-seconds roofline, mesh telemetry
  (``/debug/mesh``, chrome-trace mesh stamp), and skew gauges
  (pipeline-bubble ratio, MoE expert-load imbalance).

Phase 5 (the fleet observatory):

* :mod:`~paddle_tpu.observability.loadgen` — seeded, fully
  deterministic workload traces (heavy-tailed lengths, MMPP bursty
  multi-tenant arrivals, Zipf shared-prefix populations, batch/
  deadline/abort mixes) with byte-identical serialization, a live
  HTTP/SSE replay harness against the serving gateway, and per-
  tenant/per-tier SLO-attainment rollups reconstructed from flight
  records.

CLI: ``python -m paddle_tpu.observability
{snapshot,prometheus,trace,programs,mesh,serve}``.
"""

from __future__ import annotations

from . import (comms, events, loadgen, memory, metrics, profiling, slo,
               tracing)
from .events import export_chrome_trace
from .loadgen import SLOSpec, WorkloadSpec, WorkloadTrace
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    counter,
    default_registry,
    gauge,
    histogram,
    render_prometheus,
    snapshot,
    validate_exposition,
    value,
)
from .memory import MemoryLedger
from .profiling import ProgramCard, ProgramCardRegistry
from .server import TelemetryServer
from .slo import Objective, SLOTracker
from .span import current_span, span, span_depth
from .tracing import FlightRecorder, RequestTrace

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry",
    "counter", "gauge", "histogram", "value",
    "default_registry", "snapshot", "render_prometheus",
    "validate_exposition",
    "events", "metrics", "span", "current_span", "span_depth",
    "export_chrome_trace", "reset",
    "slo", "tracing",
    "RequestTrace", "FlightRecorder", "Objective", "SLOTracker",
    "TelemetryServer",
    "comms", "memory", "profiling",
    "MemoryLedger", "ProgramCard", "ProgramCardRegistry",
    "loadgen",
    "WorkloadSpec", "WorkloadTrace", "SLOSpec",
]


def reset():
    """Clear every metric value AND the event timeline (test isolation)."""
    metrics.reset()
    events.clear()
