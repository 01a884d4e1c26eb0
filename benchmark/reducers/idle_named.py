"""Of the device's idle time in the traced stretch, the share whose gap's
middle lies under a program span other than `step`. Percent.

The join of the program's spans with the capture does not rest on a thread's
name. Every `dispatch` span of the program encloses one
`PJRT_LoadedExecutable_Execute` event of the runtime's `main/*` host line,
which `trace.load` keeps. Where the capture's clock is the wall clock, the
spans are put on it by the stream's `clock_sync` records; where it counts
from the start of the capture (jax 0.9.0 / libtpu 0.0.34: `start_ns` is
normalised to the session's start), the offset is the one that puts every
Execute event of the capture inside the dispatch span of the same ordinal,
the capture having started inside the log line at `window_t0`. Either way the
join is then checked: each `dispatch` span in the stretch must hold exactly
one Execute event, and each run of the step program kept by `trim` must start
after the `dispatch` that enqueued it has started. Under 99% on either, the
metric is left out and the `[bench] idle_by_program_span` line says why.
"""

from __future__ import annotations

import json
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmark.reducers import host_spans as hs
from benchmark.reducers import trace as tr

EXECUTE = "PJRT_LoadedExecutable_Execute"
WALL_CLOCK_FROM_S = 1e9     # a capture clock past this counts from 1970, not from its start
ROUNDING_S = 2e-6           # a span's start and duration are each written to the microsecond
DEVICE_CLOCK_SLACK_S = 5e-4 # the capture puts device events on the host's clock to about 0.1 ms:
#                             a recorded run starts 0.12 ms before the Execute event that launched it
JOINED_SHARE = 0.99
UNNAMED = ("step", "outside_any_span", "shorter_gaps")


def _say(obj: dict) -> None:
    print(f"[bench] idle_by_program_span {json.dumps(obj)}", flush=True)


def join(ev: dict) -> Tuple[Optional[float], dict]:
    """(offset, check): capture time = a span's `t_abs` + offset; None when
    the spans cannot be put on the capture's clock or the check fails."""
    trace, spans = ev["trace"], ev.get("spans") or []
    execs = np.array(sorted((s, s + d) for name, s, d in trace.get("host", [])
                            if name == EXECUTE))
    disp = [s for s in hs.named(spans, "dispatch") if s["t_abs"] >= ev["window_t0"]]
    check: dict = {"execute_events": len(execs), "dispatch_spans_after_window_t0": len(disp)}
    if not len(execs) or not disp:
        return None, {**check, "join_ok": False, "why": "no Execute events or no dispatch spans"}
    d0 = np.array([s["t_abs"] for s in disp])
    d1 = d0 + np.array([s["dur"] for s in disp])
    tol = ROUNDING_S
    if execs[0, 0] > WALL_CLOCK_FROM_S:
        syncs = [s for s in hs.named(spans, "clock_sync") if "wall_ns" in s]
        if not syncs:
            return None, {**check, "join_ok": False, "capture_clock": "wall",
                          "why": "the stream has no clock_sync record"}
        before = [s for s in syncs if s["t_abs"] <= d0[0]]
        sync = before[-1] if before else syncs[0]
        offset = sync["wall_ns"] * 1e-9 - sync["t_abs"]
        err_ns = max(int(s.get("err_ns", 0)) for s in syncs)
        tol += err_ns * 1e-9
        check.update(capture_clock="wall", largest_err_ns=err_ns)
    else:
        n = min(len(execs), len(disp))
        hi = float(np.min(execs[:n, 0] - d0[:n]))   # every Execute starts after its dispatch
        lo = float(np.max(execs[:n, 1] - d1[:n]))   # and ends before it
        offset = (lo + hi) / 2
        check.update(capture_clock="from_capture_start", offset_slack_us=1e6 * (hi - lo),
                     capture_started_after_window_t0_ms=1e3 * (-offset - ev["window_t0"]))
    # which dispatch holds each Execute event, with the offset applied
    a, b = d0 + offset, d1 + offset
    at = np.searchsorted(a, execs[:, 0] + tol, side="right") - 1
    held = (at >= 0) & (execs[:, 1] <= b[np.clip(at, 0, None)] + tol)
    owner = np.where(held, at, -1)
    windows = list((trace.get("windows") or {}).values())
    runs = next(iter((trace.get("modules") or {}).values()), [])
    if not windows or not runs:
        return None, {**check, "join_ok": False, "why": "no traced stretch"}
    t0, t1 = min(w[0] for w in windows), max(w[1] for w in windows)
    inside = np.flatnonzero((b > t0) & (a < t1))
    holds_one = [int(np.sum(owner == i)) == 1 for i in inside]
    # the runs `trim` kept end with the first run of the capture's last
    # block, which the k-th Execute from the capture's end enqueued
    k = int(ev["cell"].traffic["block_steps"])
    after = []
    for i, run in enumerate(reversed(runs)):
        j = len(execs) - k - i
        after.append(bool(0 <= j and owner[j] >= 0 and run[1] >= a[owner[j]] - tol - DEVICE_CLOCK_SLACK_S))
    share = lambda oks: sum(oks) / len(oks) if oks else 0.0
    check.update(dispatch_spans_in_stretch=len(inside),
                 dispatch_holds_one_execute_pct=100 * share(holds_one),
                 run_starts_after_its_dispatch_pct=100 * share(after))
    ok = share(holds_one) >= JOINED_SHARE and share(after) >= JOINED_SHARE
    check["join_ok"] = ok
    if not ok:
        check["why"] = "the spans and the capture do not line up"
    return (offset if ok else None), check


def _gaps(ops, window) -> np.ndarray:
    """[[start, end], ...] of the idle gaps between the ops inside `window`."""
    iv = np.array(sorted(tr.intervals(ops)))
    if not len(iv):
        return np.array([list(window)])
    covered = np.maximum.accumulate(iv[:, 1])
    starts = np.concatenate([[window[0]], covered])
    ends = np.concatenate([iv[:, 0], [window[1]]])
    keep = ends > starts
    return np.stack([starts[keep], ends[keep]], axis=1)


def _innermost(points: np.ndarray, s0: np.ndarray, s1: np.ndarray, names: List[str]) -> List[str]:
    """The name of the shortest span over each point."""
    if not len(s0):
        return ["outside_any_span"] * len(points)
    over = (s0[None, :] <= points[:, None]) & (s1[None, :] >= points[:, None])
    dur = np.where(over, (s1 - s0)[None, :], np.inf)
    pick = np.argmin(dur, axis=1)
    return [names[p] if over[i, p] else "outside_any_span" for i, p in enumerate(pick)]


def reduce(args: dict, ev: dict):
    trace = ev.get("trace")
    if not trace or not any(trace["devices"].values()) or not ev.get("spans"):
        return None
    offset, check = join(ev)
    if offset is None:
        _say(check)
        return None
    windows = trace.get("windows") or {}
    by_middle: Dict[str, float] = {}
    by_overlap: Dict[str, float] = {}
    idle = 0.0
    for dev, ops in trace["devices"].items():
        if not ops:
            continue
        window = windows.get(dev) or (min(o[1] for o in ops), max(o[1] + o[2] for o in ops))
        gaps = _gaps(ops, window)
        idle += float(np.sum(gaps[:, 1] - gaps[:, 0]))
        order = np.argsort(gaps[:, 0] - gaps[:, 1])       # longest first
        longest, rest = gaps[order[:tr.NAMED_GAPS]], gaps[order[tr.NAMED_GAPS:]]
        shorter = float(np.sum(rest[:, 1] - rest[:, 0]))
        live = [s for s in ev["spans"] if s["dur"] > 0 and not s.get("async")
                and s["t_abs"] + offset < window[1] and hs.end(s) + offset > window[0]]
        s0 = np.array([s["t_abs"] + offset for s in live])
        s1 = np.array([hs.end(s) + offset for s in live])
        names = [s["name"] for s in live]
        for name, (g0, g1) in zip(_innermost(longest.mean(axis=1), s0, s1, names), longest):
            by_middle[name] = by_middle.get(name, 0.0) + (g1 - g0)
        # the same gaps split where one span ends and the next begins
        cuts = np.unique(np.concatenate([s0, s1, list(window)]))
        cuts = cuts[(cuts >= window[0]) & (cuts <= window[1])]
        labels = _innermost((cuts[:-1] + cuts[1:]) / 2, s0, s1, names)
        under = np.clip(np.minimum(longest[:, 1:2], cuts[None, 1:])
                        - np.maximum(longest[:, 0:1], cuts[None, :-1]), 0, None).sum(axis=0)
        for name, t in zip(labels, under):
            if t > 0:
                by_overlap[name] = by_overlap.get(name, 0.0) + float(t)
        for table in (by_middle, by_overlap):
            if shorter:
                table["shorter_gaps"] = table.get("shorter_gaps", 0.0) + shorter
    if idle <= 0:
        return None
    named_pct = lambda table: 100.0 * sum(v for k, v in table.items() if k not in UNNAMED) / idle
    rank = lambda table: {k: round(1e3 * v, 4) for k, v in
                          sorted(table.items(), key=lambda kv: -kv[1])}
    _say({**check, "idle_ms": 1e3 * idle, "idle_ms_by_span_over_the_gaps_middle": rank(by_middle),
          "idle_ms_by_span_split_at_span_edges": rank(by_overlap),
          "named_pct_split_at_span_edges": named_pct(by_overlap)})
    return named_pct(by_middle)
