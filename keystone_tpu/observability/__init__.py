"""Observability subsystem: one plane for metrics and spans.

KeystoneML's operator decisions (auto-caching, solver selection) run on
*measured* profiles; this package gives the runtime the same treatment:

- ``MetricsRegistry`` (registry.py): process-global catalogue of named,
  labeled counters / gauges / latency summaries / native histograms
  (``RegistryHistogram``: Prometheus ``le`` buckets that aggregate
  exactly across scrapes and replicas), built on the
  ``Counter``/``LatencyRecorder`` primitives in ``utils/profiling.py``.
  ``ServingMetrics`` registers itself here; the executor, the solvers,
  the runtime's compile listeners and the request gateway publish here.
- ``span`` / ``Tracer`` (tracing.py): the one span call. Always a
  ``ks:<name>`` TraceMe on the JAX profiler's clock (recorded while a
  profiler session runs); with ``enable_tracing()`` also a ring of
  recent spans with parent links, Chrome trace-event JSON export for
  chrome://tracing / Perfetto. Off, a span costs its TraceMe and no
  lock.
- ``AdminServer`` (admin.py): stdlib-http background thread serving
  ``/metrics`` (Prometheus text exposition v0.0.4), ``/varz`` (JSON),
  ``/healthz``, and ``/tracez`` (recent spans). Off unless started —
  ``python -m keystone_tpu --admin-port 8080 <App>`` wires it up.

The serving engine's per-bucket compile/dispatch counters, the
micro-batcher's queue depth and request latency, workflow executor node
spans, the auto-cache profile's spans and the runtime's compile counts
all land here, so the bucket
autoscaler (``serving/autoscale.py``) and any external scraper read one
consistent surface.
"""

from keystone_tpu.observability.admin import (
    AdminServer,
    build_info,
    start_admin_server,
    stop_admin_server,
)
from keystone_tpu.observability.attribution import (
    AttributionLedger,
    EngineAttribution,
    RowClaimQueue,
    attribution_document,
    attribution_from_samples,
)
from keystone_tpu.observability.drift import DriftDetector, psi
from keystone_tpu.observability.device import (
    DeviceMemorySampler,
    compiled_cost_model,
    device_memory_stats,
    device_table,
    peaks_for,
)
from keystone_tpu.observability.flight import (
    FlightRecord,
    FlightRecorder,
)
from keystone_tpu.observability.otlp import OtlpSpanExporter
from keystone_tpu.observability.registry import (
    DEFAULT_HISTOGRAM_BUCKETS,
    Exemplar,
    MetricFamily,
    MetricsRegistry,
    RegistryHistogram,
    Sample,
    get_global_registry,
    reset_global_registry,
)
from keystone_tpu.observability.slo import Slo, SloMonitor
from keystone_tpu.observability.stitch import (
    StitchedTrace,
    TraceStitcher,
    phase_decomposition,
)
from keystone_tpu.observability.tracing import (
    Span,
    TraceContext,
    Tracer,
    disable_tracing,
    enable_tracing,
    format_traceparent,
    get_tracer,
    parse_traceparent,
    span,
)

__all__ = [
    "AdminServer",
    "AttributionLedger",
    "DEFAULT_HISTOGRAM_BUCKETS",
    "DeviceMemorySampler",
    "DriftDetector",
    "EngineAttribution",
    "RowClaimQueue",
    "attribution_document",
    "attribution_from_samples",
    "psi",
    "compiled_cost_model",
    "device_memory_stats",
    "device_table",
    "peaks_for",
    "Exemplar",
    "FlightRecord",
    "FlightRecorder",
    "MetricFamily",
    "MetricsRegistry",
    "OtlpSpanExporter",
    "RegistryHistogram",
    "Sample",
    "Slo",
    "SloMonitor",
    "Span",
    "StitchedTrace",
    "TraceContext",
    "TraceStitcher",
    "Tracer",
    "build_info",
    "disable_tracing",
    "enable_tracing",
    "format_traceparent",
    "get_global_registry",
    "get_tracer",
    "parse_traceparent",
    "phase_decomposition",
    "reset_global_registry",
    "span",
    "start_admin_server",
    "stop_admin_server",
]
