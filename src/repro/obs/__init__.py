"""``repro.obs`` — unified observability: metrics, tracing, surfaces.

Three pillars over one design rule (near-zero cost when disabled,
bounded memory when enabled):

* ``repro.obs.registry`` — the process-wide metrics registry
  (counters / gauges / fixed-log-bucket histograms), wired through the
  streaming engine, ``Mapper``, ``DeviceResidency``, ``ResilientMapper``
  and ``MappingService``;
* ``repro.obs.tracing``  — chunk-lifecycle span tracing exported as
  Chrome trace-event JSON (Perfetto-loadable), sharing clock reads with
  ``stage_times_s`` so the two surfaces agree by construction;
* ``repro.obs.logjson`` / ``repro.obs.server`` — structured JSON
  logging, Prometheus text exposition, and the jax profiler server for
  the launchers (``--trace-out`` / ``--metrics-out`` / ``--log-json`` /
  ``--metrics-port`` / ``--profiler-port``).

The package is a **leaf**: nothing here imports ``repro.core`` or
``repro.index``, so every layer may instrument itself without cycles.
"""
from . import logjson, server, validate
from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       disable_metrics, enable_metrics, metrics)
from .tracing import (Tracer, clear_ctx, disable_tracing, enable_tracing,
                      get_ctx, set_ctx, span, tracer)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "enable_metrics", "disable_metrics", "metrics",
    "Tracer", "enable_tracing", "disable_tracing", "tracer",
    "set_ctx", "get_ctx", "clear_ctx", "span",
    "logjson", "server", "validate",
]
