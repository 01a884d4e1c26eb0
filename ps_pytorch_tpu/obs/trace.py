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
import functools
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
        with_setup: bool = False,
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
        # a run's own stream (the Trainer's, cli.train_lm's): its first
        # flush also writes what the process's set-up record holds of
        # this stream's life (setup_tracer, below). Asked for, not the
        # rule for every stream with a file: the serve loop's and a bench
        # leg's share the process with the record and are not its run
        self._with_setup = with_setup
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

    def snapshot(self) -> List[dict]:
        """Every buffered span record, left where it is: a reader of the
        set-up record does not take the spans from the next reader."""
        return list(self._buf)

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
                spans[:0] = [self._sync0] + (
                    _setup_records_since(self._base) if self._with_setup else [])
            for rec in spans:
                f.write(json.dumps(validate_event(rec)) + "\n")
        return n


# ------------------------------------------------------- the set-up record
#
# ONE pathless Tracer a process, on whether or not --trace is given: what
# the program does between the process's birth and its first steady step
# happens once, so recording it costs nothing a step (nothing in a loop body
# may record here). Who records what, and which metric or log line reads
# it: PERF.md section 3.

SETUP_RING = 2048
_SETUP_TID = 1  # the set-up record's lane in a stream's Chrome trace
# the three intervals jax itself times around every program it makes
# (jax/_src/dispatch.py), by the span each becomes here
_JAX_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
_JAX_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
# setup_summary's parts that are read from spans, in the order they take a
# moment two of them cover: a cache load inside a first call is a cache load
SETUP_PARTS = (
    ("cache_load_s", ("jax.cache_load",)),
    ("trace_lower_s", ("jax.trace", "jax.lower")),
    ("first_call_s", ("setup.first_call",)),
    ("build_s", ("build", "setup.devices", "setup.lm_config", "setup.init_state",
                 "setup.make_step", "setup.shard_state", "setup.first_batch")),
)

_SETUP: Optional[Tracer] = None
_listening = False
_jax_open = 0  # a jit traced inside another's trace or lowering leaves no span of its own


def process_age_s() -> Optional[float]:
    """Seconds since this process was started: /proc/self/stat's start time
    against the clock it is counted on. None where there is no /proc."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        born = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return time.clock_gettime(time.CLOCK_BOOTTIME) - born
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def setup_tracer() -> Tracer:
    """The process's set-up record, made at first use. It opens with the
    `process_start` instant (`age_s`: how old the process was then: the
    interpreter, the imports and, where the caller started the device
    first, the runtime's bring-up) and from then on holds jax's own
    intervals by program name beside the program's set-up spans."""
    global _SETUP
    if _SETUP is None:
        _SETUP = Tracer("setup", path=None, ring=SETUP_RING)
        age = process_age_s()
        if age is not None:
            _SETUP.instant("process_start", age_s=round(age, 6))
        _listen_to_jax()
    return _SETUP


def setup_span(name: str):
    """Decorator for a function of the program's set-up: each call is one
    span `name` of the set-up record (never on a function a loop calls)."""
    def wrap(fn):
        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            with setup_tracer().span(name):
                return fn(*args, **kwargs)
        return spanned
    return wrap


def _listen_to_jax() -> None:
    """Registers the listeners once a process. They fire where jax traces,
    lowers, compiles or loads a program, never at a call that finds its
    executable: a steady loop compiles nothing and records nothing."""
    global _listening
    if _listening:
        return
    try:
        from jax import monitoring
    except ImportError:  # a host-only use of the tracer: nothing to listen to
        return
    monitoring.register_scalar_listener(_on_jax_start)
    monitoring.register_event_time_span_listener(_on_jax_span)
    monitoring.register_event_duration_secs_listener(_on_jax_duration)
    _listening = True


def _on_jax_start(event, value, **_):
    global _jax_open
    if event in _JAX_SPANS:
        _jax_open += 1


def _on_jax_span(event, start, end, fun_name="", **_):
    """The outermost intervals only: the functions jax traces while it
    traces or lowers another (a rule written in jnp: a few hundred a step
    program) are that program's time. jax's events carry wall-clock ends;
    the span's start is this clock's now less the duration, so the record
    stays on the one clock."""
    global _jax_open
    name = _JAX_SPANS.get(event)
    if name is None:
        return
    _jax_open = max(_jax_open - 1, 0)
    if _jax_open or _SETUP is None:
        return
    dur = end - start
    _SETUP.add(name, _SETUP.now() - dur, dur, program=str(fun_name))


def _on_jax_duration(event, secs, **_):
    if event == _JAX_CACHE_LOAD and _SETUP is not None:
        _SETUP.add("jax.cache_load", _SETUP.now() - secs, secs)


def _setup_records_since(base: float) -> List[dict]:
    """The set-up record as a run's own stream writes it: the records of
    that stream's life (from its clock base on) and the process's birth, on
    the stream's clock, in a category and a lane of their own (they come
    from another span stack, so `async`)."""
    if _SETUP is None:
        return []
    shift = _SETUP._base - base
    return [{**rec, "t": round(rec["t"] + shift, 6), "cat": "setup", "async": True}
            for rec in _SETUP.snapshot()
            if rec["t"] + shift >= 0.0 or rec["name"] == "process_start"]


def _covered_s(intervals) -> float:
    """Seconds the (start, end) intervals cover together."""
    total, reach = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > reach:
            total += b - max(a, reach)
            reach = b
    return total


def setup_summary(until: Optional[float] = None, records: Optional[List[dict]] = None,
                  base: float = 0.0) -> dict:
    """Where the time from the process's birth to `until` went (`until` on
    `time.perf_counter()`, now if None), read from the set-up record's
    spans that had ended by then (`records` on the clock base `base`: the
    process's own where None).

    Seven parts in seconds that sum to `stretch_s`: `before_program_s` (the
    process's age at the record's first moment), then `cache_load_s`,
    `trace_lower_s`, `first_call_s`, `build_s` as SETUP_PARTS lists them,
    each without what an earlier one covers; `warm_s`, from the end of the
    last step program's first call to `until` without what the four cover
    there (the first steps' remainder, warm-up; None where no step was
    called); `unplaced_s`, the rest (from the record's first moment to that
    call's end, under none of the above). A part with no record of its
    kind reads 0.0 (None only where the record holds nothing at all, and
    `before_program_s` where there is no `process_start`). Beside them,
    overlapping: `programs` and `compile_s` (jax's backend-compile step,
    cache loads included: inside a first call or a build) and `cache_hits`.
    benchmark/reducers/setup_spans.py makes the same numbers from the same
    record by its own rules (tests/test_setup_record.py holds them equal)."""
    if records is None:
        tracer = setup_tracer()
        records, base = tracer.snapshot(), tracer._base
    end = (time.perf_counter() if until is None else until) - base
    records = [r for r in records if r["t"] + r["dur"] <= end + 1e-6]
    born = next((r for r in records if r["name"] == "process_start"), None)
    first = born["t"] if born else min((r["t"] for r in records), default=end)
    spans = lambda names: [(max(r["t"], first), r["t"] + r["dur"])
                           for r in records if r["name"] in names]
    out = {"before_program_s": born["age_s"] if born else None}
    taken: list = []
    for part, names in SETUP_PARTS:
        own = spans(names)
        # no record of its kind in a record that holds others: 0 s (a cold
        # cache loads nothing); only an empty record says nothing
        out[part] = _covered_s(own + taken) - _covered_s(taken) if records else None
        taken += own
    called = max((b for _, b in spans(("setup.first_call",))), default=None)
    split = end if called is None else called
    after = _covered_s([(max(a, split), b) for a, b in taken if b > split])
    out["warm_s"] = None if called is None else (end - split) - after
    out["unplaced_s"] = (split - first) - (_covered_s(taken) - after)
    out["stretch_s"] = (out["before_program_s"] or 0.0) + end - first
    compiles = spans(("jax.compile",))
    out.update(programs=len(compiles), compile_s=_covered_s(compiles),
               cache_hits=len(spans(("jax.cache_load",))))
    return out


def format_setup_summary(s: dict) -> str:
    """The one log line at the end of set-up (cli.train, cli.train_lm)."""
    sec = lambda key: "%.1f" % (s[key] or 0.0)
    return (
        f"set-up {sec('stretch_s')} s: before the program {sec('before_program_s')}, "
        f"build {sec('build_s')}, trace+lower {sec('trace_lower_s')}, "
        f"cache load {sec('cache_load_s')}, first call {sec('first_call_s')}, "
        f"first steps and warm-up {sec('warm_s')}, unplaced {sec('unplaced_s')} "
        f"({s['programs']} programs in {sec('compile_s')} s of compile or load, "
        f"{s['cache_hits']} from the cache)")


_line_given = False


def setup_line_once() -> Optional[str]:
    """`format_setup_summary` of the set-up so far, the first time a
    process asks; None ever after (a run's first log step asks: the second
    `train()` call of a process, or a second Trainer, is not its set-up)."""
    global _line_given
    if _line_given:
        return None
    _line_given = True
    return format_setup_summary(setup_summary())


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
    lane_named = False
    for s in spans:
        if s.get("kind") != "span":
            continue
        # async intervals (request lifecycles, rollover drains) overlap
        # the synchronous stack arbitrarily; per-slot thread lanes keep
        # each track properly nested (one slot serves one request at a
        # time, so a slot's lane never self-overlaps)
        tid = 0
        if s.get("cat") == "setup":
            # the process's set-up record, written into this stream by
            # its first flush: a lane of its own, named once
            tid = _SETUP_TID
            if not lane_named:
                lane_named = True
                out.append({"name": "thread_name", "ph": "M", "pid": p,
                            "tid": tid, "args": {"name": "set-up"}})
        elif s.get("async"):
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
