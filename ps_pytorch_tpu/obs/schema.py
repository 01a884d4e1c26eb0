"""The unified event schema for every metrics/trace JSONL stream.

Before this module, the metrics JSONL was a bag of ad-hoc record shapes:
each subsystem invented its own ``kind`` and field names as it grew
(``step`` everywhere, but typed float in one emitter and int in another;
counters serialized as floats by the trainer's blanket ``float(v)``
sweep). The registry below is the single source of truth: every kind the
framework emits, with its required fields and the fields that are
integers BY CONTRACT — ``validate_event`` rejects unknown kinds and
missing fields, and coerces the declared int fields so a record means
the same thing no matter which emitter produced it.

Compatibility note: JSONL files written before the registry existed may
carry float-typed counters (``skipped_steps: 3.0``) and no ``t_wall``
stamp. Readers should ``int()`` counters defensively on old files; new
files are normalized at the write choke points (``trainer.
append_metrics_line`` and ``obs.trace.Tracer.flush``).

A stream begins with one ``run_header`` record carrying the run's
identity and clock base:

- ``run_id``: random id shared by every stream of one run (metrics
  JSONL, per-process trace files), so a multihost merge can group them;
- ``schema_version``: this module's ``SCHEMA_VERSION``;
- ``t_wall`` / ``t_mono``: ``time.time()`` and ``time.perf_counter()``
  read together at header time. Span records carry monotonic offsets
  (drift-free durations); the header's wall clock maps them onto one
  cross-process timeline (tools/trace_report.py's merge rule —
  multihost wall clocks are NTP-aligned to well under a log window).

This module is deliberately host-pure: no jax import, no device access —
it can never add a sync to the paths it observes.
"""

from __future__ import annotations

import dataclasses
import time
import uuid
from typing import Dict, Optional, Tuple

SCHEMA_VERSION = 1


@dataclasses.dataclass(frozen=True)
class EventSpec:
    """One registered event kind: required fields plus the fields that
    are integers by contract (coerced, not just checked — the trainer's
    metrics sweep floats every device scalar it fetches)."""

    required: Tuple[str, ...]
    int_fields: Tuple[str, ...] = ()
    doc: str = ""


# kind -> spec. Extra fields are always allowed (records are open:
# workload-specific metrics ride along), but a registered kind's
# required core is guaranteed present and its counters int-typed.
EVENT_KINDS: Dict[str, EventSpec] = {
    "run_header": EventSpec(
        required=("run_id", "schema_version", "component", "t_mono"),
        int_fields=("schema_version", "pid"),
        doc="stream identity + clock base; first record of every stream",
    ),
    "train": EventSpec(
        required=("step", "loss", "time_cost"),
        int_fields=("step", "epoch", "skipped_steps", "skip_streak"),
        doc="one per log window: window-averaged step walltime + metrics",
    ),
    "eval": EventSpec(
        required=("step", "loss"),
        int_fields=("step",),
        doc="full-test-split validation pass",
    ),
    "train_lm": EventSpec(
        required=("step", "loss", "time_cost"),
        int_fields=("step", "moe_rows_here", "moe_max_expert_rows",
                    "moe_min_expert_rows", "moe_tokens_unserved",
                    "moe_passes", "moe_buffer_rows", "moe_combine_rows_read",
                    "ssd_chunks_cut_off", "kda_chunks_cut_off"),
        doc="LM trainer log window (cli/train_lm.py); the moe_* routing "
            "counters ride along for a family with dropless expert layers, "
            "ssd_chunks_cut_off for one with state-space layers, "
            "kda_chunks_cut_off for one with delta-rule layers, "
            "eva_remote_mass (a float) for one with EVA attention",
    ),
    "grad_skip": EventSpec(
        required=("step", "skipped_steps", "skip_streak"),
        int_fields=("step", "skipped_steps", "skip_streak"),
        doc="non-finite guard skipped >=1 step since the last window",
    ),
    "straggler": EventSpec(
        required=("step", "time_cost", "threshold"),
        int_fields=("step",),
        doc="one slow step (watchdog armed, below storm escalation)",
    ),
    "straggler_storm": EventSpec(
        required=("step", "start_step", "consecutive", "threshold"),
        int_fields=("step", "start_step", "consecutive"),
        doc="N consecutive slow steps escalated into one condition",
    ),
    "straggler_storm_end": EventSpec(
        required=("step", "start_step", "consecutive"),
        int_fields=("step", "start_step", "consecutive"),
        doc="storm closed; carries the true span length",
    ),
    "mask_adapt": EventSpec(
        required=("step", "from", "to", "window_start", "slow_steps",
                  "window_steps"),
        int_fields=("step", "from", "to", "window_start", "slow_steps",
                    "window_steps"),
        doc="adaptive partial-aggregation count change at a window close",
    ),
    "resume_reshape": EventSpec(
        required=("step", "from", "to"),
        int_fields=("step",),
        doc="elastic resume re-carved the checkpoint onto a new geometry",
    ),
    "ckpt_quarantined": EventSpec(
        required=("step", "path"),
        int_fields=("step",),
        doc="corrupt checkpoint renamed *.corrupt during resume fallback",
    ),
    "ckpt_write_failed": EventSpec(
        required=("step", "path", "error"),
        int_fields=("step",),
        doc="checkpoint write failed (reported at failure time)",
    ),
    "autotune": EventSpec(
        required=("run", "model", "network", "grid", "n_candidates",
                  "n_pruned", "gate"),
        int_fields=("n_points", "n_candidates", "n_pruned"),
        doc="one ranked knob-search evidence record (tune/search.py); "
            "carries its own nested run_header under 'run'",
    ),
    "span": EventSpec(
        required=("name", "t", "dur"),
        int_fields=("depth", "step", "tick", "slot", "rid",
                    "new_tokens", "weights_step", "from_step", "to_step",
                    "bytes", "block", "wall_ns", "err_ns"),
        doc="one traced host-side phase: t/dur are seconds on the "
            "stream header's monotonic clock; a clock_sync span pairs "
            "that clock with the wall clock (wall_ns +- err_ns at t). An "
            "instant's other attributes (a plan's, a state's, a census's) "
            "ride along as their emitter made them; the function that "
            "returns them says what they mean",
    ),
    # ---- serving request lifecycle (ARCHITECTURE §7i): every submitted
    # request terminates in EXACTLY one of request_done | request_shed |
    # deadline_expired — the zero-silent-drops contract the chaos drill
    # asserts by partitioning rids over these three kinds
    "request_done": EventSpec(
        required=("rid", "new_tokens", "weights_step"),
        int_fields=("rid", "new_tokens", "weights_step"),
        doc="one request completed (its new-token budget reached); "
            "met_deadline rides along when the request carried one",
    ),
    "request_shed": EventSpec(
        required=("rid", "projected_wait_s", "queue_depth", "slo_budget_s"),
        int_fields=("rid", "queue_depth"),
        doc="admission controller refused the arrival at submit time: "
            "projected queue wait exceeded the SLO budget (the evidence "
            "rides in the record)",
    ),
    "deadline_expired": EventSpec(
        required=("rid", "where", "deadline_s"),
        int_fields=("rid", "tokens_done"),
        doc="request deadline passed before completion; 'where' is "
            "submit (dead on arrival) | queue (expired before "
            "admission) | decode (evicted mid-decode, partial tokens)",
    ),
    "rollover_abort": EventSpec(
        required=("from_step", "staged_step", "reason"),
        int_fields=("from_step", "staged_step"),
        doc="a staged rollover was abandoned (corrupt/unreadable staged "
            "checkpoint at swap time, or the drain watchdog expired); "
            "service continues on from_step",
    ),
    "admission_adapt": EventSpec(
        required=("state", "projected_wait_s", "queue_depth",
                  "window_submits", "window_sheds"),
        int_fields=("queue_depth", "window_submits", "window_sheds",
                    "windows"),
        doc="admission controller state change (admitting <-> shedding) "
            "with the window evidence that drove it",
    ),
}


def new_run_id() -> str:
    """Random 12-hex run id — shared across one run's streams."""
    return uuid.uuid4().hex[:12]


def validate_event(record: dict) -> dict:
    """Validate (and normalize, in place) one JSONL record against the
    registry. Raises ValueError on a missing/unknown ``kind`` or a
    missing required field; coerces the kind's declared int fields.
    Returns the record for call-site chaining."""
    kind = record.get("kind")
    if kind is None:
        raise ValueError(f"event record has no 'kind': {record!r}")
    spec = EVENT_KINDS.get(kind)
    if spec is None:
        raise ValueError(
            f"unknown event kind {kind!r} — register it in "
            f"obs/schema.EVENT_KINDS (known: {sorted(EVENT_KINDS)})"
        )
    missing = [f for f in spec.required if f not in record]
    if missing:
        raise ValueError(
            f"event kind {kind!r} is missing required field(s) "
            f"{missing}: {record!r}"
        )
    for f in spec.int_fields:
        v = record.get(f)
        if v is not None and not isinstance(v, bool) and f in record:
            record[f] = int(v)
    return record


def run_header(
    component: str,
    run_id: Optional[str] = None,
    geometry: Optional[dict] = None,
    pid: int = 0,
) -> dict:
    """Build the stream-opening run_header record (clock base read NOW:
    t_wall and t_mono are one paired sample)."""
    rec = {
        "kind": "run_header",
        "run_id": run_id or new_run_id(),
        "schema_version": SCHEMA_VERSION,
        "component": component,
        "t_wall": round(time.time(), 6),
        "t_mono": round(time.perf_counter(), 6),
        "pid": int(pid),
    }
    if geometry is not None:
        rec["geometry"] = geometry
    return rec
