"""``repro.obs`` — zero-dependency tracing + metrics for the fit() stack.

Three pieces, all off by default and near-zero-cost when off:

* ``repro.obs.trace`` — per-run structured tracing: ``RunTrace`` holds
  the per-round records every driver emits (round index, live count,
  realized alpha, removal threshold, stopping-rule margin, uplink rows,
  achieved wire bytes, wall/compile split) and per-run counters
  (host<->device bytes, programs traced), plus ``Span``/``event``
  timelines in ``trace="full"`` mode, the spans mirrored into
  ``jax.profiler``. Activated by the ``fit(trace=...)``
  knob; drivers publish through the ambient ``current_trace()`` so no
  driver signature changes when tracing is off.
* ``repro.obs.metrics`` — one registry over the repo's scattered
  counters (``streaming.tree.TRACE_COUNTS``, the kmeans/kmeans‖ retrace
  counters, autotune cache hits/misses, wire-tally scoping) behind a
  single ``read()``/``reset()``/``scope()`` API, plus owned counters,
  gauges, histograms (serving latency) and event logs (drift
  re-clusters).
* ``repro.obs.export`` + ``repro.obs.report`` — JSONL and Chrome
  trace-event (Perfetto-viewable) exporters and the run-report CLI:
  ``python -m repro.obs.report <trace.jsonl> [other.jsonl]`` renders a
  round-by-round table for one run or a diff of two.
"""
from repro.obs.metrics import REGISTRY, MetricsRegistry
from repro.obs.trace import (ROUND_SCHEMA, RunTrace, Span, clock,
                             current_trace, emit_round, event, run_trace,
                             set_clock, span)

__all__ = [
    "REGISTRY", "MetricsRegistry", "ROUND_SCHEMA", "RunTrace", "Span",
    "clock", "current_trace", "emit_round", "event", "run_trace",
    "set_clock", "span",
]
