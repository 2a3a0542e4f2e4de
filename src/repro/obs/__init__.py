"""``repro.obs`` — lightweight, always-compilable observability.

Three components (see docs/observability.md for the user guide):

* **span tracer** (``trace``): nested thread-aware spans over the
  engine's pipeline stages, halo planning, and launcher steps.  Every
  span annotates the ``jax.profiler`` trace and credits its pass's stall
  clock; only an enabled ``Tracer`` keeps events in memory, and a traced
  run is bit-identical to an untraced one.  ``SPANS`` names the engine's
  and the partition CLI's spans.
* **stall attribution** (``stall``): per-chunk read / queue-wait /
  dispatch / device-wait / fetch / write / fold seconds per pass, rolled
  into a ``PipelineStallReport`` (the signal behind adaptive
  ``pipeline_depth``) and into every run's stage seconds.
* **metrics registry** (``metrics``): counters / gauges / histograms
  (edges streamed, chunks in flight, replication-state bytes, DCN vs ICI
  lane rows) with a JSON-safe snapshot.

``export`` turns a tracer into Chrome ``trace_event`` JSON (Perfetto),
renders the ``--trace-summary`` table, and hosts the optional
``jax.profiler`` session hook.
"""
from .export import (TraceValidationError, chrome_trace,
                     jax_profiler_session, trace_summary_table,
                     validate_chrome_trace, write_chrome_trace)
from .metrics import (NULL_REGISTRY, Counter, Gauge, Histogram,
                      MetricsRegistry, NullRegistry, get_registry,
                      use_registry)
from .stall import (CHUNK_STAGES, STAGES, PassStall, PipelineStallReport,
                    StallClock)
from .trace import (NULL_TRACER, PASS_PHASES, SPANS, NullTracer, Tracer,
                    get_tracer, use_tracer)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "NullRegistry",
    "NULL_REGISTRY", "get_registry", "use_registry",
    "NullTracer", "NULL_TRACER", "PASS_PHASES", "SPANS", "Tracer",
    "get_tracer", "use_tracer",
    "CHUNK_STAGES", "STAGES", "PassStall", "PipelineStallReport",
    "StallClock",
    "TraceValidationError", "chrome_trace", "jax_profiler_session",
    "trace_summary_table", "validate_chrome_trace", "write_chrome_trace",
]
