"""Merge per-process span-trace streams into one timeline and summarize.

The observability layer (ps_pytorch_tpu/obs, ARCHITECTURE §7g) writes
one JSONL stream per process per component: a ``run_header`` record
(run id, schema version, wall+monotonic clock base) followed by
``span`` records whose ``t``/``dur`` are seconds on the header's
monotonic clock. This tool:

- merges any number of streams (train + serve, multiple hosts) into ONE
  perfetto-loadable Chrome trace (``--out``). Multihost merge rule: a
  span's absolute time is its stream's newest ``clock_sync`` wall time
  at or before it plus the monotonic offset since (``header.t_wall +
  span.t`` in a stream without one) — monotonic offsets keep durations
  drift-free, the per-process wall base places the streams on a shared
  timeline (hosts are NTP-aligned to well under
  a log window, and each process keeps its own ``pid`` lane so skew
  never interleaves within a track);
- overlays metrics-JSONL events (``--metrics``: grad_skip, straggler
  storms, mask_adapt, resume_reshape, checkpoint quarantine/failure) as
  instant markers via their ``t_wall`` stamps;
- prints a summary: per-phase count and p50/p99/total duration (under
  `setup`, apart: the phases of the set-up record a trainer's first flush
  writes into its stream, and where each stream's time from the process's
  birth to its first `step` went),
  per-component fraction of loop walltime by phase (where does a
  step's time go: the spans opened directly under the trainers' `step`
  span — fetch vs dispatch vs window_close — or the serve tick's
  depth-0 spans), every instant with its attributes (train_lm's
  `flash_plan`: the flash kernels' tile plan and, as `kda_plan` for a
  delta-rule layer, what `remat` keeps of a layer: `remat_saves`,
  `saved_bytes_per_layer`), and a nesting
  check (child spans must sit inside their parents — a violation means
  a tracer bug, not a workload property);
- ``--require-phases a,b,c`` exits nonzero unless every named phase is
  present (the smoke gate).

Where a step's DEVICE time goes, by the scopes the program writes inside
its step (obs/scopes.py) and the phase jax writes around them:

  python tools/trace_report.py device <profile dir>
      joins `<profile dir>/step_scopes.json` (the compiled step's census:
      `cli.train_lm --profile-dir`, `cli.train --profile-dir` write it when
      their capture stops) with the capture's `XLA Ops` lines by
      instruction name and prints ms a step by phase and by scope, the
      share in fusions that hold more than one scope (`mixed`, booked to
      the scope of the instruction that does most work: obs/hlo.py) and the
      share it could not place.

The earlier one-off analysis tool folds in as a subcommand:

  python tools/trace_report.py overlap <hlo|trace|topology|jaxpr> [...]
      -> tools/overlap_report.py (comm/compute overlap evidence;
         `jaxpr --overlap on|off` reports the pipelined wire's
         schedule-freedom numbers, `trace` reads a capture's device
         ops and takes the per-bucket `bucket_reduce_o<offset>` scopes
         from the step_scopes.json beside it — §6g)

Usage:
  python tools/trace_report.py runs/trace/ --metrics runs/metrics.jsonl \\
      --out runs/trace_merged.json --summary-out runs/trace_summary.json
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

from ps_pytorch_tpu.obs import (  # noqa: E402
    chrome_trace_events,
    setup_summary,
    summarize_spans,
)

# metrics-JSONL kinds rendered as instant overlay markers on the merged
# timeline (anything else in the metrics stream is ignored here)
OVERLAY_KINDS = (
    "grad_skip", "straggler", "straggler_storm", "straggler_storm_end",
    "mask_adapt", "resume_reshape", "ckpt_quarantined", "ckpt_write_failed",
)

# tiny tolerance for the nesting check: span times round to 1 µs in the
# files, so exact-boundary children can overhang by a rounding quantum
_NEST_EPS_S = 5e-6


def load_stream(path: str) -> List[Tuple[dict, List[dict]]]:
    """One trace file -> list of (run_header, spans) SEGMENTS.

    Tracer.flush appends, so re-running with the same --trace dir (a
    --resume continuation) writes a fresh run_header mid-file — and each
    segment's span offsets are on ITS OWN header's monotonic clock, so
    they must be rebased per segment, never against the first header."""
    segments: List[Tuple[dict, List[dict]]] = []
    header: Optional[dict] = None
    spans: List[dict] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            kind = rec.get("kind")
            if kind == "run_header":
                if header is not None:
                    segments.append((header, spans))
                header, spans = rec, []
            elif kind == "span":
                if header is None:
                    raise SystemExit(
                        f"{path}: span record before any run_header — "
                        f"not an obs trace stream"
                    )
                spans.append(rec)
    if header is not None:
        segments.append((header, spans))
    return segments


def discover(inputs: List[str]) -> List[str]:
    """Expand dirs to their trace_*.jsonl files; pass files through."""
    out: List[str] = []
    for item in inputs:
        if os.path.isdir(item):
            out.extend(sorted(glob.glob(os.path.join(item, "trace_*.jsonl"))))
        else:
            out.append(item)
    return out


def check_nesting(spans: List[dict]) -> int:
    """Count nesting violations within one stream: spans sorted by start
    must close inside whatever span is open above them (classic interval
    stack). Async interval spans (request lifecycles, rollover drains)
    overlap the stack by design and are excluded. Returns the violation
    count."""
    # at equal starts the LONGER span is the parent and must enter the
    # stack first, hence the -end tiebreak
    ordered = sorted(
        (
            (float(s["t"]), float(s["t"]) + float(s["dur"]))
            for s in spans if not s.get("async")
        ),
        key=lambda se: (se[0], -se[1]),
    )
    stack: List[float] = []
    bad = 0
    for start, end in ordered:
        while stack and stack[-1] <= start + _NEST_EPS_S:
            stack.pop()
        if stack and end > stack[-1] + _NEST_EPS_S:
            bad += 1
        stack.append(end)
    return bad


def merge(
    trace_files: List[str], metrics_files: List[str]
) -> Tuple[dict, dict]:
    """-> (chrome_trace dict, summary dict)."""
    streams = []
    for path in trace_files:
        segments = load_stream(path)
        if not segments:
            # a span file without identity cannot be placed on the wall
            # timeline; surface it instead of silently mis-merging
            raise SystemExit(
                f"{path}: no run_header record — not an obs trace stream"
            )
        for header, spans in segments:
            streams.append((path, header, spans))
    overlays = []
    for path in metrics_files or []:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                rec = json.loads(line)
                if rec.get("kind") in OVERLAY_KINDS and "t_wall" in rec:
                    overlays.append(rec)
    if not streams and not overlays:
        raise SystemExit("no trace streams and no overlay events found")

    walls = [h["t_wall"] for _, h, _ in streams]
    walls += [o["t_wall"] for o in overlays]
    t0_wall = min(walls)

    events: List[dict] = []
    used_pids = set()
    for i, (path, header, spans) in enumerate(streams):
        # distinct pid lane per stream even if two headers claim pid 0
        # (train + serve on one host)
        pid = int(header.get("pid", 0))
        while pid in used_pids:
            pid += 100
        used_pids.add(pid)
        events.extend(
            chrome_trace_events(header, spans, pid=pid, t0_wall=t0_wall)
        )
    for o in overlays:
        events.append({
            "name": o["kind"],
            "cat": "event",
            "ph": "i",
            "s": "g",  # global scope: draws a full-height marker line
            "ts": round((o["t_wall"] - t0_wall) * 1e6, 3),
            "pid": 0,
            "tid": 0,
            "args": {k: v for k, v in o.items() if k != "t_wall"},
        })

    all_spans = [s for _, _, spans in streams for s in spans]
    # the set-up record a trainer's first flush wrote into its stream
    # (cat "setup": `build*`, `setup.*`, jax's own intervals by program,
    # `process_start`) stands under its own heading: its phases, and for
    # each stream where the time from the process's birth to the first
    # `step` went (obs/trace.setup_summary, the set-up log line's numbers)
    is_setup = lambda s: s.get("cat") == "setup"
    phases = summarize_spans([s for s in all_spans if not is_setup(s)])
    setup = {"phases": summarize_spans([s for s in all_spans if is_setup(s)]),
             "time_to_first_step": []}
    for _, header, spans in streams:
        records = [s for s in spans if is_setup(s)]
        if records:
            first = min((float(s["t"]) for s in spans if s["name"] == "step"),
                        default=max(float(s["t"]) + float(s["dur"]) for s in records))
            setup["time_to_first_step"].append({
                "component": header.get("component"), "pid": header.get("pid", 0),
                **{k: v if v is None else round(v, 6)
                   for k, v in setup_summary(first, records, base=0.0).items()}})
    # fraction of loop walltime by TOP-LEVEL phase, per component (a
    # nested span — h2d under fetch — must not double-count, and async
    # intervals overlap the loop phases so they must not either).
    # AGGREGATED over every stream of the component: a multihost merge
    # has one stream per process and a straggler host's dispatch/sync
    # split must weigh in, not be overwritten by the last-listed file.
    # A stream whose loop iterations are `step` spans (the trainers)
    # splits THEIR time by the phases opened directly under them, the
    # rest as `step.self`; set-up (`build`) is not loop time. A stream
    # without them (the serve tick) splits by its depth-0 spans.
    totals: Dict[str, Dict[str, float]] = {}
    for _, header, spans in streams:
        by = totals.setdefault(header.get("component", "?"), {})
        stacked = [s for s in spans if not s.get("async")]
        steps = [s for s in stacked
                 if s["name"] == "step" and s.get("depth", 0) == 0]
        if steps:
            phases_of = [s for s in stacked if s.get("parent") == "step"]
            by["step.self"] = by.get("step.self", 0.0) + max(
                sum(float(s["dur"]) for s in steps)
                - sum(float(s["dur"]) for s in phases_of), 0.0)
        else:
            phases_of = [s for s in stacked if s.get("depth", 0) == 0]
        for s in phases_of:
            by[s["name"]] = by.get(s["name"], 0.0) + float(s["dur"])
    fractions: Dict[str, Dict[str, float]] = {}
    for comp, by in totals.items():
        total = sum(by.values())
        if total > 0:
            fractions[comp] = {
                k: round(v / total, 4) for k, v in sorted(by.items())
            }
    nest_bad = sum(check_nesting(spans) for _, _, spans in streams)
    # an instant carries its payload in attrs (train_lm's `flash_plan`):
    # print it whole
    instants = [
        {k: v for k, v in s.items() if k not in ("kind", "cat", "dur", "depth")}
        for s in all_spans if s.get("cat") == "instant"
    ]
    summary = {
        "streams": [
            {
                "path": path,
                "component": h.get("component"),
                "run_id": h.get("run_id"),
                "pid": h.get("pid", 0),
                "schema_version": h.get("schema_version"),
                "n_spans": len(spans),
            }
            for path, h, spans in streams
        ],
        "n_overlay_events": len(overlays),
        "phases": phases,
        "setup": setup,
        "instants": instants,
        "fraction_of_loop_walltime": fractions,
        "nesting_violations": nest_bad,
        "nesting_ok": nest_bad == 0,
    }
    trace = {"traceEvents": events, "displayTimeUnit": "ms"}
    return trace, summary


def device_report(profile_dir: str) -> dict:
    """`step_scopes.json` joined with the newest capture under
    `profile_dir`: ms a step of device time by phase, by scope and by
    pair of scopes in mixed fusions, per device averaged."""
    from ps_pytorch_tpu.obs.hlo import instruction_of, is_placed, time_by_place
    from ps_pytorch_tpu.obs.profiler import device_planes

    with open(os.path.join(profile_dir, "step_scopes.json")) as f:
        census = json.load(f)
    table = census["instructions"]
    capture, planes = device_planes(profile_dir)
    if capture is None:
        raise SystemExit(f"no capture (plugins/profile/*/*.xplane.pb) under {profile_dir}")
    joined, steps = [], 0
    for plane in planes:
        events, runs = [], {}
        for line in plane.lines:
            if line.name == "XLA Modules":
                for e in line.events:
                    name = e.name.split("(")[0]
                    runs.setdefault(name, []).append(e.duration_ns)
            elif line.name == "XLA Ops":
                for e in line.events:
                    ins = instruction_of(e.name, table)
                    if ins is None or table[ins][2] != "container":
                        events.append((e.name, e.duration_ns * 1e-9))
        if events:
            joined.append(time_by_place(table, events))
            # the step program is the module that took most time
            steps = max(steps, len(max(runs.values(), key=sum, default=[])))
    if not joined:
        raise SystemExit(
            f"the capture under {profile_dir} has no device plane with an `XLA Ops` line "
            "(a CPU capture holds host events only)")
    steps, n = max(steps, 1), len(joined)
    ms = lambda seconds: round(1e3 * seconds / n / steps, 4)
    by_phase: Dict[str, float] = {}
    by_scope: Dict[str, float] = {}
    mixed: Dict[str, float] = {}
    total = unplaced = unfound = inherited = mixed_all = 0.0
    for j in joined:
        total += j["total"]
        unfound += j["unfound"]
        inherited += j["inherited"]
        for (phase, scope, work), s in j["by_place"].items():
            if is_placed((phase, scope)):
                by_phase[phase] = by_phase.get(phase, 0.0) + s
                by_scope[scope] = by_scope.get(scope, 0.0) + s
            else:
                unplaced += s
        for (here, other), s in j["mixed"].items():
            mixed[f"{here} | {other}"] = mixed.get(f"{here} | {other}", 0.0) + s
        mixed_all += j["mixed_total"]
    rank = lambda d: {k: ms(v) for k, v in sorted(d.items(), key=lambda kv: -kv[1])}
    return {
        "program": census.get("program"), "devices": n, "steps": steps,
        "step_ms": ms(total), "ms_by_phase": rank(by_phase), "ms_by_scope": rank(by_scope),
        "mixed_ms": ms(mixed_all), "mixed_ms_by_pair": dict(list(rank(mixed).items())[:12]),
        "unplaced_ms": ms(unplaced + unfound), "not_in_the_census_ms": ms(unfound),
        "placed_by_a_neighbour_ms": ms(inherited),
    }


def print_device_report(report: dict) -> None:
    step = report["step_ms"] or 1.0
    pct = lambda v: f"{v:10.3f} ms {100 * v / step:6.2f}%"
    print(f"{report['program']}: {report['step_ms']:.3f} ms of device time a step "
          f"({report['steps']} steps, {report['devices']} device(s))")
    for title, key in (("by phase", "ms_by_phase"), ("by scope", "ms_by_scope")):
        print(title)
        for name, v in report[key].items():
            print(f"  {name:28s}{pct(v)}")
    print(f"mixed (fusions of more than one scope, booked as obs/hlo.py says){pct(report['mixed_ms'])}")
    for pair, v in report["mixed_ms_by_pair"].items():
        print(f"  {pair:60s}{pct(v)}")
    print(f"{'unplaced':30s}{pct(report['unplaced_ms'])}  (not in the census "
          f"{report['not_in_the_census_ms']:.3f} ms; placed by a neighbour "
          f"{report['placed_by_a_neighbour_ms']:.3f} ms)")


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "device":
        if len(argv) != 2:
            print("usage: tools/trace_report.py device <profile dir>", file=sys.stderr)
            return 2
        print_device_report(device_report(argv[1]))
        return 0
    # the folded one-off tool rides as a subcommand (its module remains
    # the implementation and keeps its own CLI working)
    if argv and argv[0] == "overlap":
        import overlap_report

        overlap_report.main(argv[1:])
        return 0

    p = argparse.ArgumentParser(
        "tools/trace_report.py",
        description="merge obs span-trace streams; see module docstring",
    )
    p.add_argument("inputs", nargs="+",
                   help="trace dirs (trace_*.jsonl inside) and/or files")
    p.add_argument("--metrics", action="append", default=[],
                   help="metrics JSONL to overlay as instant markers "
                        "(repeatable)")
    p.add_argument("--out", default=None,
                   help="write the merged Chrome trace JSON here "
                        "(load in perfetto/chrome://tracing)")
    p.add_argument("--summary-out", default=None,
                   help="write the summary JSON here")
    p.add_argument("--require-phases", default=None,
                   help="comma-separated phase names that must appear; "
                        "missing ones exit 1 (smoke gate)")
    args = p.parse_args(argv)

    files = discover(args.inputs)
    if not files and not args.metrics:
        print(f"no trace_*.jsonl under {args.inputs}", file=sys.stderr)
        return 1
    trace, summary = merge(files, args.metrics)
    print(json.dumps(summary, indent=2))
    if args.out:
        d = os.path.dirname(args.out)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(trace, f)
        print(f"# merged trace: {args.out} "
              f"({len(trace['traceEvents'])} events)", file=sys.stderr)
    if args.summary_out:
        with open(args.summary_out, "w") as f:
            json.dump(summary, f, indent=2)
    if args.require_phases:
        need = {s for s in args.require_phases.split(",") if s}
        missing = sorted(need - set(summary["phases"]) - set(summary["setup"]["phases"]))
        if missing:
            print(f"missing required phases: {missing}", file=sys.stderr)
            return 1
        if "spans_dropped" in summary["phases"]:
            # the tracer's bounded ring evicted spans (obs/trace.py's
            # spans_dropped meta marker): the timeline is silently
            # truncated, so a gate that demands complete phases must
            # not pass it — probe/smoke runs would bank partial
            # evidence as if it were whole
            print(
                "required phases present but the stream carries a "
                "spans_dropped marker — the span ring overflowed and "
                "the timeline is incomplete (raise the tracer ring "
                "size or flush more often)",
                file=sys.stderr,
            )
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
