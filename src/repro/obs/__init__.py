"""Observability primitives shared by the serve plane: metrics
(counters/gauges/log-bucket histograms), the bounded lifecycle trace
ring, and ``span``, the host spans that a running profiler records on
the device's clock.  See DESIGN.md §16."""
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import EVENT_KINDS, Trace, TraceEvent, span

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "EVENT_KINDS", "Trace", "TraceEvent", "span"]
