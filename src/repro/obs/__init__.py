"""repro.obs — tracing + metrics for the whole similarity stack.

Two halves, both zero-overhead when unused:

* ``repro.obs.trace`` — a thread-aware span tracer with two sinks.
  With neither on (the default) every ``span()`` call returns one shared
  no-op singleton: no allocation, no lock, no clock read on the hot path.
  With the Chrome tracer enabled, spans record B/E event pairs (wall
  time, thread id, byte/counter attributes) that export as
  Chrome/Perfetto trace-event JSON and aggregate into the per-phase table
  the CLI prints after a ``--trace`` run.  Inside a JAX profiler session
  each span is also a ``repro.<name>`` annotation in the profiler trace.

* ``repro.obs.metrics`` — a process-wide metrics registry (counters,
  gauges, latency histograms) whose ``snapshot()`` is taken under one
  lock, so concurrent readers always see an internally consistent view
  (``SimilarityService.metrics()`` is built on it).  ``count_jit_events``
  feeds it the ``jit.lowerings`` / ``jit.compiles`` counters.

See docs/OBSERVABILITY.md for the full walkthrough.
"""
from repro.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    count_jit_events,
    default_registry,
    jit_counts,
)
from repro.obs.trace import (  # noqa: F401
    Tracer,
    aggregate_phases,
    current_path,
    disable,
    enable,
    enabled,
    format_phase_table,
    get_tracer,
    span,
    validate_chrome_trace,
)
