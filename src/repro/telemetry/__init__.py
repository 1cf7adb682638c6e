"""Telemetry: per-link NoC heatmaps, Chrome-trace timelines, metrics.

Zero-overhead-when-off instrumentation threaded through the simulator,
serving loop and DSE:

* :mod:`repro.telemetry.heatmap` — :class:`LinkRecorder` hooks
  ``NoCTransport`` accounting and resolves the per-class
  ``TrafficCounters`` totals down to individual mesh links, with an
  exact-integer conservation check against the counters *and* the
  energy model's routed byte-hops.
* :mod:`repro.telemetry.spans` — nestable host wall-clock
  :class:`Span`/:class:`Profiler` with named counter totals (``count``)
  plus the streaming stage x frame timeline, exported as Chrome
  trace-event JSON (Perfetto-viewable).
* :mod:`repro.telemetry.metrics` — Prometheus-style
  counters/gauges/histograms with labelled series and JSON snapshots,
  backing ``serve_stream``.

``python -m repro.telemetry`` renders heatmaps and summarizes traces.
"""
from repro.telemetry.heatmap import (FlowStats, LinkHeatmap, LinkRecorder,
                                     TRAFFIC_CLASSES, check_conservation,
                                     record_run)
from repro.telemetry.metrics import (DEFAULT_BUCKETS, MetricFamily,
                                     MetricsRegistry)
from repro.telemetry.spans import (Profiler, TRACE_PID_HOST, TRACE_PID_SIM,
                                   active_profiler, chrome_trace, count,
                                   count_device_call, load_chrome_trace, span,
                                   stream_timeline_events,
                                   validate_chrome_trace, write_chrome_trace)

__all__ = [
    "FlowStats", "LinkHeatmap", "LinkRecorder", "TRAFFIC_CLASSES",
    "check_conservation", "record_run",
    "DEFAULT_BUCKETS", "MetricFamily", "MetricsRegistry",
    "Profiler", "TRACE_PID_HOST", "TRACE_PID_SIM", "active_profiler",
    "chrome_trace", "count", "count_device_call", "load_chrome_trace", "span",
    "stream_timeline_events",
    "validate_chrome_trace", "write_chrome_trace",
]
