"""``repro.obs`` — low-overhead tracing + histogram metrics for serving.

The serving stack's third observability layer, beside the cumulative
``StageProfiler`` phases and the per-subsystem counters:

* ``trace``   — ``span``: the hot path's one span entry point (always a
  ``jax.profiler.TraceAnnotation`` ``serve.<name>``, plus the
  ``StageProfiler`` phase and the ring-buffer event when those are on);
  ``Tracer``: lock-protected bounded ring buffer of span /
  instant events with thread ids and propagated request/group context
  (submit → admission → claim → group → pack → dispatch → collect);
* ``export``  — Chrome trace-event JSON serialization (Perfetto-loadable;
  one track per real thread + a synthetic track per outstanding stage-2
  group) and the per-worker merge used by ``repro.dist.runner``;
* ``metrics`` — ``Histogram`` / ``MetricsRegistry``: log-bucketed
  p50/p90/p99 without sample retention, unifying the scattered serving
  counters behind one ``snapshot()``.

Configured by the ``ObsPlan`` section of ``repro.serve.plan.ServePlan``
(``obs__trace=True`` + ``launch/serve.py --trace out.json`` /
``benchmarks/load.py --trace``); off-by-default ring tracing keeps the
hot path at a ``tracer is None`` check. The profiler annotations have no
switch: they cost about a microsecond a span with no profiler session
running, and a captured profile (``jax.profiler.start_trace`` or
``start_server`` + XProf/Perfetto) shows the ``serve.*`` spans beside
the device's operations.
"""
from repro.obs.export import (  # noqa: F401
    chrome_events,
    merge_trace_files,
    trace_payload,
    write_trace,
)
from repro.obs.metrics import Histogram, MetricsRegistry  # noqa: F401
from repro.obs.trace import DEFAULT_CAPACITY, Tracer, span  # noqa: F401
