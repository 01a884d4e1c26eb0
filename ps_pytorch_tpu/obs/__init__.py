"""Observability: structured tracing, event schema, profiler windows.

Three layers (ARCHITECTURE §7g):

- ``obs.schema`` — the unified JSONL event registry (kind -> required
  fields + int contract), ``run_header`` records, run ids;
- ``obs.trace`` — the host-side span tracer (ring-buffered, flushed
  once per window while the device is busy, Chrome-trace exportable)
  and NULL_TRACER, the zero-cost off switch;
- ``obs.profiler`` — bounded ``jax.profiler`` capture windows for
  ``--profile-dir``.

Contract: tracer-off adds zero host syncs, tracer-on reuses the
driver's existing per-window sync points — pslint PSL004 patrols this
tree in strict mode (tests/test_obs.py pins it).
"""

from .profiler import ProfileWindow
from .schema import (
    EVENT_KINDS,
    SCHEMA_VERSION,
    new_run_id,
    run_header,
    validate_event,
)
from .trace import (
    NULL_TRACER,
    NullTracer,
    Tracer,
    chrome_trace_events,
    summarize_spans,
)

__all__ = [
    "EVENT_KINDS",
    "NULL_TRACER",
    "NullTracer",
    "ProfileWindow",
    "SCHEMA_VERSION",
    "Tracer",
    "chrome_trace_events",
    "new_run_id",
    "run_header",
    "summarize_spans",
    "validate_event",
]
