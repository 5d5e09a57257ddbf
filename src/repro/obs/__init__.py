"""Structured observability layer (DESIGN.md §10).

One registry, four primitives, a pluggable sink protocol:

  Counter          monotone host-side total (ops, psyncs, redeliveries)
  Gauge            last-written level (backlog depth, lane budget)
  Histogram        log2-bucketed distribution with EXACT sample-based
                   p50/p99/p999 (per-request latency, span durations)
  span(name)       a span on the profiler's clock (``TraceAnnotation``),
                   recording into a histogram when given a registry

Everything accumulates HOST-SIDE only: nothing in this package is ever
traced into a jit program, and device counters (``n_psync``/``n_ops``
and friends, which live in donated device state) cross to the host only
at force/flush/snapshot boundaries through registered *collectors* --
see :meth:`MetricsRegistry.register_collector`.

``MetricsRegistry.snapshot()`` is the one read path every structure's
ad-hoc telemetry (psync counters, router ``last_route``, scratch-pool
stats, ``pipeline_abandoned``, overflow latches, recovery histograms)
is reachable through; sinks (:class:`InMemorySink`, :class:`JSONLSink`)
receive whole snapshots via :meth:`MetricsRegistry.emit`.
"""
from repro.obs.bridge import DeviceCounterBridge
from repro.obs.meta import bench_meta
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               span)
from repro.obs.sinks import InMemorySink, JSONLSink, Sink

__all__ = ["Counter", "DeviceCounterBridge", "Gauge", "Histogram",
           "MetricsRegistry", "span", "InMemorySink", "JSONLSink", "Sink",
           "bench_meta"]
