"""Observability: span tracer, metrics registry, and the contract auditor.

See ``docs/ARCHITECTURE.md`` ("Observability") for the track/lane map and
the invariant list the auditor enforces.
"""
from .audit import AuditError, AuditReport, audit
from .metrics import (BYTES_BUCKETS, LATENCY_MS_BUCKETS, Counter, Gauge,
                      Histogram, MetricsRegistry, serve_metrics)
from .tracer import (MACHINE_TRACKS, Tracer, maybe_span, resolve_tracer,
                     span_overlap_ms)

__all__ = [
    "AuditError", "AuditReport", "audit",
    "BYTES_BUCKETS", "LATENCY_MS_BUCKETS", "Counter", "Gauge", "Histogram",
    "MetricsRegistry", "serve_metrics",
    "MACHINE_TRACKS", "Tracer", "maybe_span", "resolve_tracer",
    "span_overlap_ms",
]
