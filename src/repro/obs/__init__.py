"""Unified observability layer: spans, metrics, exporters (PR 8).

One clock, one timeline: the ``Tracer`` records per-request spans through
every executor (lock-step, staged, elastic), the ``MetricsRegistry`` absorbs
the previously-fragmented telemetry (monitor gauges, ``StageStats``,
``GenStats``, ``ScaleEvent``s), and the exporters render both as
Chrome/Perfetto ``trace_event`` JSON or JSONL.  Clocks are injected: live
runs use the wall clock, the deterministic simulator records spans in
virtual time — bit-identical across replays.

The served path's own spans and counters go through ``span``, ``count``
and ``observe``: each span is a ``jax.profiler.TraceAnnotation`` (on the
device trace's clock) and, where the caller holds a ``Tracer``, a ``Span``
there too; counters and observations land in ``REGISTRY``, the process's
bounded registry on the raw ``perf_counter`` timebase.
"""
from repro.obs.decompose import (STAGE_ORDER, decomposition_summary,
                                 request_components)
from repro.obs.export import (chrome_trace_doc, validate_chrome_trace,
                              write_chrome_trace, write_jsonl)
from repro.obs.metrics import MetricPoint, MetricsRegistry
from repro.obs.tracer import (REGISTRY, Span, Tracer, VirtualClock,
                              WallClock, attach_pipeline, count, observe,
                              span)

__all__ = [
    "Span", "Tracer", "WallClock", "VirtualClock", "attach_pipeline",
    "span", "count", "observe", "REGISTRY",
    "MetricPoint", "MetricsRegistry",
    "chrome_trace_doc", "write_chrome_trace", "write_jsonl",
    "validate_chrome_trace",
    "STAGE_ORDER", "request_components", "decomposition_summary",
]
