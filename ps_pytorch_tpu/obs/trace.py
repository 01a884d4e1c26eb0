"""Low-overhead host-side span tracer for the train and serve ticks.

Design constraints (the whole point — observability must not perturb
the observed):

- ZERO host syncs by construction: a span reads ``time.perf_counter()``
  twice and appends a dict to a bounded ring. This module never imports
  device-touching APIs — no ``jax.device_get``, no ``block_until_ready``
  — and pslint's PSL004 patrols the whole ``obs/`` tree in strict mode
  (every function is a hot-path loop body by contract, and
  ``block_until_ready`` is flagged here even though it is the blessed
  barrier primitive elsewhere), so a future edit cannot sneak one in.
- Tracer OFF is a shared no-op: ``NULL_TRACER.span(...)`` returns one
  reusable null context manager; instrumented call sites stay
  unconditional and pay ~a method call per phase per step.
- Spans buffer in an in-memory ring (``deque(maxlen=ring)``) and flush
  to the per-process trace file once per window, at a moment the
  device is busy: the trainer flushes right after the dispatch that
  follows a log window (never between the window's ``sync`` and that
  dispatch — there the device is idle and the write would lengthen the
  gap a traced run is there to measure), the serve loop every Nth tick.

Each trace file is a JSONL stream: one ``run_header`` record (run id,
schema version, wall+monotonic clock base — obs/schema.py), then one
``span`` record per completed span with ``t``/``dur`` in seconds on the
header's monotonic clock. A span opened inside another records its
``parent``'s name and inherits its ``step``, so the spans of one loop
iteration share an identifier. ``clock_sync`` records (one taken at
construction, one per flush) pair the wall clock with the span clock:
``wall_ns`` is ``time.time_ns()`` read between two ``perf_counter()``
reads, ``t`` their midpoint and ``err_ns`` half their distance, so a
span's wall-clock time is good to microseconds however long the run
(the header's single ``t_wall`` drifts). ``tools/trace_report.py``
merges any number of per-process files into one perfetto-loadable
Chrome trace on that wall clock and summarizes p50/p99 per phase.

When ``annotate=True`` each span also enters a
``jax.profiler.TraceAnnotation`` scope of the same name, so the host
phases appear as named regions on the profiler timeline captured by
``--profile-dir`` (obs/profiler.py). TraceAnnotation is a TraceMe that
no-ops when no profiler session is active — safe to leave on.
"""

from __future__ import annotations

import bisect
import collections
import json
import os
import time
from typing import Dict, List, Optional

from .schema import new_run_id, run_header, validate_event


class _NullSpan:
    """Reusable no-op context manager (the tracer-off fast path)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Tracer-off: every operation is inert; one shared instance
    (NULL_TRACER) keeps instrumented call sites unconditional."""

    enabled = False
    run_id = None

    def span(self, name, cat="phase", **attrs):
        return _NULL_SPAN

    def add(self, name, t0, dur, cat="phase", **attrs):
        return None

    def instant(self, name, cat="instant", **attrs):
        return None

    def now(self) -> float:
        return 0.0

    def drain(self) -> List[dict]:
        return []

    def flush(self) -> int:
        return 0


NULL_TRACER = NullTracer()


class _Span:
    __slots__ = ("_tracer", "_name", "_cat", "_attrs", "_t0", "_depth",
                 "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str, attrs: dict):
        self._tracer, self._name, self._cat = tracer, name, cat
        self._attrs = attrs
        self._ann = None

    def __enter__(self):
        tr = self._tracer
        self._depth = len(tr._stack)
        if tr._stack:
            # the spans of one loop iteration share its identifier: a
            # child records who opened it and inherits the parent's step
            # (the loader's gather/h2d cannot know the step they feed)
            top = tr._stack[-1]
            inherited = {"parent": top._name}
            if "step" in top._attrs:
                inherited["step"] = top._attrs["step"]
            self._attrs = {**inherited, **self._attrs}
        tr._stack.append(self)
        if tr._ann_cls is not None:
            self._ann = tr._ann_cls(self._name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        tr = self._tracer
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tr._stack.pop()
        tr._append(
            self._name, self._t0 - tr._base, end - self._t0, self._cat,
            self._depth, self._attrs,
        )
        return False


class Tracer:
    """One component's span stream (train loop, serve loop, bench leg).

    ``path=None`` keeps spans in memory only (``drain()`` them — the
    bench legs do); with a path, ``flush()`` appends the drained spans
    as JSONL after writing the run_header once."""

    enabled = True

    def __init__(
        self,
        component: str,
        path: Optional[str] = None,
        run_id: Optional[str] = None,
        ring: int = 65536,
        annotate: bool = False,
        geometry: Optional[dict] = None,
        pid: int = 0,
    ):
        self.component = component
        self.path = path
        self.run_id = run_id or new_run_id()
        self.pid = int(pid)
        self.header = run_header(
            component, run_id=self.run_id, geometry=geometry, pid=pid
        )
        # span t/dur are seconds on THIS clock base (the header's t_mono)
        self._base = self.header["t_mono"]
        self._buf: collections.deque = collections.deque(maxlen=max(ring, 1))
        self._stack: List[_Span] = []
        # the wall clock paired with the span clock NOW; written right
        # after the header (a pathless tracer never writes it)
        self._sync0 = self._clock_sync()
        self.dropped = 0  # ring overflow count (oldest spans evicted)
        self._dropped_reported = 0  # watermark already flushed as a marker
        self._header_written = False
        self._ann_cls = None
        if annotate:
            try:
                from jax.profiler import TraceAnnotation

                self._ann_cls = TraceAnnotation
            except Exception:  # profiler unavailable: spans still record
                self._ann_cls = None

    # ------------------------------------------------------------ recording
    def span(self, name: str, cat: str = "phase", **attrs):
        """Context manager timing one phase; nesting depth is recorded
        from the live span stack."""
        return _Span(self, name, cat, attrs)

    def now(self) -> float:
        """Seconds on this tracer's clock (for explicit add() spans)."""
        return time.perf_counter() - self._base

    def add(self, name: str, t0: float, dur: float, cat: str = "phase",
            **attrs) -> None:
        """Record an already-measured span (``t0`` from ``now()``) — for
        intervals that start and end in different calls, e.g. a serve
        rollover drain (staged in one tick, swapped several ticks later)
        or a request lifecycle. Marked ``async``: these intervals
        overlap the synchronous span stack without nesting in it, so
        the nesting validator skips them and the Chrome export gives
        them their own thread lane."""
        attrs = dict(attrs)
        attrs["async"] = True
        self._append(name, t0, dur, cat, len(self._stack), attrs)

    def instant(self, name: str, cat: str = "instant", **attrs) -> None:
        self._append(name, self.now(), 0.0, cat, len(self._stack), attrs)

    def _clock_sync(self) -> dict:
        """One ``clock_sync`` record: the wall clock read between two
        reads of the span clock. Host-pure — two clock reads, no I/O."""
        a = time.perf_counter()
        wall_ns = time.time_ns()
        b = time.perf_counter()
        return {
            "kind": "span", "name": "clock_sync", "cat": "meta",
            "t": round((a + b) / 2 - self._base, 9), "dur": 0.0,
            "depth": 0, "async": True, "wall_ns": wall_ns,
            "err_ns": int((b - a) * 5e8) + 1,
        }

    def _append(self, name, t, dur, cat, depth, attrs) -> None:
        if len(self._buf) == self._buf.maxlen:
            self.dropped += 1  # deque evicts the OLDEST span silently
        rec = {
            "kind": "span",
            "name": name,
            "cat": cat,
            "t": round(t, 6),
            "dur": round(max(dur, 0.0), 6),
            "depth": depth,
        }
        if attrs:
            rec.update(attrs)
        self._buf.append(rec)

    # -------------------------------------------------------------- output
    def drain(self) -> List[dict]:
        """Remove and return every buffered span record."""
        out = list(self._buf)
        self._buf.clear()
        return out

    def flush(self) -> int:
        """Append drained spans (validated) to the trace file, closed by
        one ``clock_sync`` record; writes the run_header (and the
        construction-time ``clock_sync``) first on the first flush. Call
        once per window while the device is busy (right after a
        dispatch), never per step and never where the device waits for
        the host. Returns spans written, the ``clock_sync`` records apart.

        A pathless (in-memory) tracer is a no-op here — the ring keeps
        its spans for a later ``drain()``: the serve engine flushes
        periodically by contract, and the bench leg's memory tracer must
        not lose its measurement to those flushes."""
        if self.path is None:
            return 0
        spans = self.drain()
        if self.dropped > self._dropped_reported:
            # surface ring truncation IN the stream: trace_report's
            # per-phase summary then shows a spans_dropped marker
            # instead of a silently incomplete timeline
            spans.append({
                "kind": "span", "name": "spans_dropped", "cat": "meta",
                "t": round(self.now(), 6), "dur": 0.0, "depth": 0,
                "async": True, "dropped_total": self.dropped,
            })
            self._dropped_reported = self.dropped
        if not spans:
            return 0
        n = len(spans)
        spans.append(self._clock_sync())
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(self.path, "a") as f:
            if not self._header_written:
                f.write(json.dumps(validate_event(dict(self.header))) + "\n")
                self._header_written = True
                spans.insert(0, self._sync0)
            for rec in spans:
                f.write(json.dumps(validate_event(rec)) + "\n")
        return n


# ------------------------------------------------------------------ reports

def summarize_spans(spans: List[dict]) -> Dict[str, dict]:
    """Per-phase duration stats from span records: count, total, p50/p99
    seconds. Shared by the bench legs (in-memory drain) and
    tools/trace_report.py (merged files)."""
    by_name: Dict[str, List[float]] = {}
    for s in spans:
        if s.get("kind") == "span":
            by_name.setdefault(s["name"], []).append(float(s["dur"]))
    out: Dict[str, dict] = {}
    for name, durs in sorted(by_name.items()):
        durs.sort()
        out[name] = {
            "count": len(durs),
            "total_s": round(sum(durs), 6),
            "p50_s": round(_pct_sorted(durs, 50.0), 6),
            "p99_s": round(_pct_sorted(durs, 99.0), 6),
        }
    return out


def _pct_sorted(xs: List[float], q: float) -> float:
    """Nearest-rank-with-interpolation percentile of a SORTED list
    (numpy-free: obs stays importable without the array stack)."""
    if not xs:
        return 0.0
    if len(xs) == 1:
        return xs[0]
    pos = (q / 100.0) * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    frac = pos - lo
    return xs[lo] * (1.0 - frac) + xs[hi] * frac


def chrome_trace_events(
    header: dict, spans: List[dict], pid: Optional[int] = None,
    t0_wall: float = 0.0,
) -> List[dict]:
    """Convert one stream (header + span records) to Chrome trace_event
    dicts. ``ts`` is microseconds of (wall base + span monotonic offset
    − ``t0_wall``) — the multihost merge rule: every process's spans
    land on one wall-clock timeline, durations stay monotonic-clock-
    accurate. The wall base of a span is the newest ``clock_sync`` at
    or before it (the header's ``t_wall`` in a stream without one), so
    a long run's spans do not drift off the other hosts'."""
    p = int(header.get("pid", 0)) if pid is None else pid
    syncs = sorted(
        (float(s["t"]), s["wall_ns"] * 1e-9 - float(s["t"]) - t0_wall)
        for s in spans if s.get("name") == "clock_sync" and "wall_ns" in s
    )
    sync_ts = [t for t, _ in syncs]
    header_base = float(header.get("t_wall", 0.0)) - t0_wall
    out: List[dict] = [
        {
            "name": "process_name",
            "ph": "M",
            "pid": p,
            "tid": 0,
            "args": {
                "name": f"{header.get('component', '?')} "
                        f"p{header.get('pid', 0)} "
                        f"[{header.get('run_id', '?')}]"
            },
        }
    ]
    for s in spans:
        if s.get("kind") != "span":
            continue
        # async intervals (request lifecycles, rollover drains) overlap
        # the synchronous stack arbitrarily; per-slot thread lanes keep
        # each track properly nested (one slot serves one request at a
        # time, so a slot's lane never self-overlaps)
        tid = 0
        if s.get("async"):
            tid = 10 + int(s.get("slot", -1)) + 1
        base = header_base
        if syncs:
            i = bisect.bisect_right(sync_ts, float(s["t"])) - 1
            base = syncs[max(i, 0)][1]
        ev = {
            "name": s["name"],
            "cat": s.get("cat", "phase"),
            "ph": "X",
            "ts": round((base + float(s["t"])) * 1e6, 3),
            "dur": round(float(s["dur"]) * 1e6, 3),
            "pid": p,
            "tid": tid,
        }
        args = {
            k: v for k, v in s.items()
            if k not in ("kind", "name", "cat", "t", "dur")
        }
        if args:
            ev["args"] = args
        out.append(ev)
    return out
