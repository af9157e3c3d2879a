"""Process-wide telemetry: metrics registry, span tracer, exporters,
object lifecycle tracing, flight recorder, runtime health probes.

See docs/observability.md for the full catalog of exported metrics.
"""

from .devicetelemetry import (DEVICE_TELEMETRY, DeviceTelemetry,
                              capture_device_trace, device_cost_block,
                              device_status, env_fingerprint,
                              record_launch, register_program)
from .export import (escape_help, escape_label_value, log_snapshot_task,
                     render_prometheus, snapshot)
from .federation import (FEDERATION_VERSION, Aggregator,
                         FederationPublisher, http_transport,
                         mergeable_snapshot)
from .flightrec import FLIGHT_RECORDER, FlightRecorder
from .health import HealthMonitor, LoopLagProbe
from .lifecycle import LIFECYCLE, LifecycleTracer
from .metrics import (DEFAULT_LATENCY_BUCKETS, DEFAULT_SIZE_BUCKETS,
                      REGISTRY, Counter, Gauge, Histogram, Registry,
                      peer_bucket, peer_bucket_label, set_peer_buckets)
from .profiling import PROFILER, SamplingProfiler, cost_status
from .tracing import (TRACE_CTX_LEN, TRACER, SkewEstimator, Span,
                      TraceContext, Tracer, current_span, interval,
                      new_span_id, new_trace_id, set_batch, trace)

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "REGISTRY",
    "DEFAULT_LATENCY_BUCKETS", "DEFAULT_SIZE_BUCKETS",
    "peer_bucket", "peer_bucket_label", "set_peer_buckets",
    "Span", "Tracer", "TRACER", "trace", "interval", "current_span",
    "set_batch",
    "TraceContext", "TRACE_CTX_LEN", "SkewEstimator",
    "new_trace_id", "new_span_id",
    "render_prometheus", "snapshot", "log_snapshot_task",
    "escape_help", "escape_label_value",
    "LifecycleTracer", "LIFECYCLE",
    "FlightRecorder", "FLIGHT_RECORDER",
    "HealthMonitor", "LoopLagProbe",
    "SamplingProfiler", "PROFILER", "cost_status",
    "DeviceTelemetry", "DEVICE_TELEMETRY", "register_program",
    "record_launch", "device_status", "device_cost_block",
    "capture_device_trace", "env_fingerprint",
    "Aggregator", "FederationPublisher", "FEDERATION_VERSION",
    "http_transport", "mergeable_snapshot",
]
