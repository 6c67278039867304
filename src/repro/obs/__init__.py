"""Unified observability layer: tracing, metrics, health, live export.

Five pieces, all zero-required-dependency and inert by default:

  obs.trace    — nestable context-manager spans with monotonic wall time
                 and optional device-sync boundaries, each also a
                 `jax.profiler` annotation while tracing; Chrome trace-event
                 JSON (Perfetto) + human tree export + a bounded ring of
                 recently completed spans for live inspection.
  obs.metrics  — typed Counter/Gauge/Histogram registry with JSONL
                 snapshot export and cross-registry merge; the system's
                 `diagnostics=` dicts are a read-out view over it.
  obs.health   — declarative `HealthRule` engine turning raw instruments
                 into ok/degraded/unhealthy verdicts, with default rule
                 packs for serving, ingestion, and solver numerics.
  obs.export   — `TelemetryExporter`: a background thread sampling the
                 registry with delta-aware timestamped records (JSONL
                 time series) and serving /metrics (Prometheus text),
                 /healthz, /varz, /tracez over stdlib HTTP.
  obs.profile  — `trace_device`: a `jax.profiler` device trace over a
                 block, with a tracer installed so the program spans
                 (which open a TraceAnnotation each while tracing) land
                 on its host planes, on the device trace's clock.

Span/metric naming scheme and the diagnostics-dict compatibility
contract: see ROADMAP.md "Observability".
"""
from . import export, health, metrics, profile, trace
from .export import TelemetryExporter
from .health import HealthEngine, HealthRule, HealthStatus
from .metrics import Counter, Gauge, Histogram, Registry
from .trace import Span, Tracer

__all__ = [
    "export", "health", "metrics", "profile", "trace",
    "Counter", "Gauge", "Histogram", "Registry", "Span", "Tracer",
    "TelemetryExporter", "HealthEngine", "HealthRule", "HealthStatus",
]
