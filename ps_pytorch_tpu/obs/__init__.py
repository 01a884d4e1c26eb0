"""Observability: structured tracing, event schema, profiler windows.

Five layers (ARCHITECTURE §7g; the last two: PERF.md section 3):

- ``obs.schema`` — the unified JSONL event registry (kind -> required
  fields + int contract), ``run_header`` records, run ids;
- ``obs.trace`` — the host-side span tracer (ring-buffered, flushed
  once per window while the device is busy, Chrome-trace exportable),
  NULL_TRACER, the zero-cost off switch, and the process's set-up
  record (``setup_tracer``: always on, nothing in a loop records there);
- ``obs.profiler`` — bounded ``jax.profiler`` capture windows for
  ``--profile-dir``;
- ``obs.scopes`` — the names a step program writes INSIDE itself
  (``scope(MIXER_KDA)``: one vocabulary) and ``ScopedStep``, the jitted
  step that gives the census of its own executable;
- ``obs.hlo`` — the one reader of a compiled program's text: phase,
  scope and kind of work of every instruction, joined to a device
  capture by instruction name (``tools/trace_report.py device``).

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
    format_setup_summary,
    setup_line_once,
    setup_span,
    setup_summary,
    setup_tracer,
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
    "format_setup_summary",
    "new_run_id",
    "run_header",
    "setup_line_once",
    "setup_span",
    "setup_summary",
    "setup_tracer",
    "summarize_spans",
    "validate_event",
]
