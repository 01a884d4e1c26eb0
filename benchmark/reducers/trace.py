"""The device trace, read with jax.profiler.ProfileData only, and interval
arithmetic over it. A trace is normalised to plain lists so that a small
recorded one can be kept as JSON with the tests.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, List, Sequence, Tuple

import numpy as np

Interval = Tuple[float, float]
OP_LINES = ("XLA Ops",)
# ops that only contain other ops; their own span says nothing of its own
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?(_|$)")


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of `a` that no interval of `b` covers."""
    out, cover = [], union(b)
    for s, e in union(a):
        cur = s
        for cs, ce in cover:
            if ce <= cur:
                continue
            if cs >= e:
                break
            if cs > cur:
                out.append((cur, cs))
            cur = max(cur, ce)
        if cur < e:
            out.append((cur, e))
    return out


_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")
_names: Dict[str, str] = {}


def short_name(text: str) -> str:
    """An op's event name is its whole HLO line; keep the op's own name
    and its largest output: `fusion.512_f32_2048_32_32_64`."""
    if text in _names:
        return _names[text]
    name, _, rest = text.partition(" = ")
    name = name.lstrip("%")
    depth, end = 0, len(rest)
    for i, ch in enumerate(rest):          # the result type ends where the
        depth += ch == "("                 # op keyword starts, outside parens
        depth -= ch == ")"
        if ch == " " and depth == 0:
            end = i
            break
    best, size = "", -1
    for dtype, dims in _SHAPE.findall(rest[:end]):
        n = 1
        for d in dims.split(","):
            n *= int(d) if d else 1
        if n > size:
            best, size = dtype + "_" + dims.replace(",", "_"), n
    _names[text] = out = f"{name}_{best}" if best else name
    return out


def start(profile_dir: str) -> None:
    """Device ops and the program's own annotations; no Python call tracing,
    which slows the host loop it is there to watch."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1   # annotations; level 2 logs every host-side
    #                              layout chunk of an upload (300,000 events
    #                              a block) and slows the loader tenfold
    jax.profiler.start_trace(profile_dir, profiler_options=opts)


def _newest_capture(profile_dir: str):
    """ProfileData of the newest capture under `profile_dir`, or None."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")), key=os.path.getmtime)
    return ProfileData.from_file(files[-1]) if files else None


def load(profile_dir: str) -> dict:
    """{"devices": {plane: [[name, start_s, dur_s], ...]},
        "host": [[name, start_s, dur_s], ...]} from the newest capture."""
    data = _newest_capture(profile_dir)
    if data is None:
        return {"devices": {}, "host": []}
    devices: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    asyncs: Dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[plane.name] = [
                        [e.name.split("(")[0], e.start_ns * 1e-9, e.duration_ns * 1e-9]
                        for e in line.events]
                if line.name == "Async XLA Ops":
                    asyncs[plane.name] = [
                        [short_name(e.name), e.start_ns * 1e-9, e.duration_ns * 1e-9]
                        for e in line.events]
                if line.name in OP_LINES:
                    ops += [[short_name(e.name), e.start_ns * 1e-9, e.duration_ns * 1e-9]
                            for e in line.events]
            devices[plane.name] = ops
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("main/") or line.name == "python":
                    host += [[e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                             for e in line.events
                             if e.duration_ns > 0 and not e.name.startswith("$")]
    return {"devices": devices, "modules": modules, "async": asyncs, "host": host}


BOUNDARY_RUNS = 2


def step_runs(runs: Sequence[Sequence]) -> list:
    """The runs of the step program: of the module that took most time."""
    by_name: Dict[str, float] = {}
    for name, _, dur in runs:
        by_name[name] = by_name.get(name, 0.0) + dur
    main = max(by_name, key=by_name.get)
    return [r for r in runs if r[0] == main]


def trim(trace: dict, blocks: int, block_steps: int):
    """(trace, steps): the trace cut to one stretch of runs of the step
    program. The capture holds `blocks`
    whole blocks of `block_steps` runs, the last run of the capture being
    the last of a block. The stretch goes from the third run of the first
    block to the first run of the last block, both inside, so every block
    boundary between them is in it: the host's drain, log line and span
    flush, and the device waiting for the next dispatch. What is left out
    is a stall of the profiler's own: right after a block's first run the
    device stands still for 0.8 s under the profiler (ResNet step, eleven
    thousand ops a run; host-clocked blocks read 2.9 s traced and 2.09 s
    untraced), so the stretch starts after the first block's and ends before
    the last block's. A trace without module events is returned as it is."""
    mods = trace.get("modules") or {}
    if not mods or not blocks or not block_steps:
        return trace, 0
    out, steps = {"devices": {}, "modules": {}, "async": {}, "windows": {},
                  "host": trace.get("host", [])}, 0
    for dev, runs in mods.items():
        whole = step_runs(runs)[-blocks * block_steps:]
        kept = whole[BOUNDARY_RUNS:(blocks - 1) * block_steps + 1]
        if not kept:
            continue
        t0, t1 = kept[0][1], kept[-1][1] + kept[-1][2]
        out["modules"][dev] = kept
        inside = lambda ops: [o for o in ops if t0 <= o[1] and o[1] + o[2] <= t1]
        out["devices"][dev] = inside(trace["devices"].get(dev, []))
        out["async"][dev] = inside((trace.get("async") or {}).get(dev, []))
        out["windows"][dev] = (t0, t1)
        steps = len(kept)
    return out, steps


def run_gaps_ms(trace: dict) -> Dict[str, list]:
    """Per device, the idle time between consecutive runs of the step
    program over the whole capture, in ms: where a stall sits."""
    out = {}
    for dev, runs in (trace.get("modules") or {}).items():
        main = step_runs(runs)
        out[dev] = [round(1e3 * (b[1] - a[1] - a[2]), 3) for a, b in zip(main, main[1:])]
    return out


def describe(profile_dir: str) -> dict:
    """Planes, lines and the commonest event names: for a look by hand."""
    data = _newest_capture(profile_dir)
    out = {}
    for plane in data.planes if data is not None else []:
        lines = {}
        for line in plane.lines:
            names: Dict[str, int] = {}
            for e in line.events:
                names[e.name] = names.get(e.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:12]
            lines[line.name] = {"events": sum(names.values()), "top": top}
        out[plane.name] = lines
    return out


def save_json(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load_json(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def intervals(ops, pattern: str = None, exclude: str = None) -> List[Interval]:
    """Intervals of the ops whose name matches `pattern` (and not `exclude`);
    container ops are left out unless the pattern names them."""
    inc = re.compile(pattern) if pattern else None
    exc = re.compile(exclude) if exclude else None
    out = []
    for name, start, dur in ops:
        if inc is None and CONTAINERS.search(name):
            continue
        if inc is not None and not inc.search(name):
            continue
        if exc is not None and exc.search(name):
            continue
        out.append((start, start + dur))
    return out


def device_ops(trace: dict, with_async: bool = False) -> Dict[str, list]:
    """Per device, the ops of the `XLA Ops` line; with `with_async`, also
    those of `Async XLA Ops` (copies and collectives that overlap it)."""
    out = {}
    for dev, ops in trace["devices"].items():
        extra = (trace.get("async") or {}).get(dev, []) if with_async else []
        if ops or extra:
            out[dev] = list(ops) + list(extra)
    return out


def busy_and_window(trace: dict) -> Tuple[float, float]:
    """Seconds an op ran and the length of the traced stretch, both averaged
    over devices. The stretch is each device's own where `trim` cut one,
    else first op start to last op end over all devices."""
    devs = {d: ops for d, ops in trace["devices"].items() if ops}
    if not devs:
        return 0.0, 0.0
    busy = sum(total(intervals(ops)) for ops in devs.values()) / len(devs)
    windows = trace.get("windows") or {}
    if all(d in windows for d in devs):
        return busy, sum(windows[d][1] - windows[d][0] for d in devs) / len(devs)
    start = min(o[1] for ops in devs.values() for o in ops)
    end = max(o[1] + o[2] for ops in devs.values() for o in ops)
    return busy, end - start


NAMED_GAPS = 2000


def breakdown(trace: dict, host_spans: Sequence[Sequence] = (), top: int = 10) -> dict:
    """The device ops that took most time (summed over the window, averaged
    over devices) and the longest idle gaps by the host span beside them."""
    named = {d: ops for d, ops in trace["devices"].items() if ops}
    if not named:
        return {"device_ops": [], "idle_gaps": []}
    devs, dev0 = list(named.values()), next(iter(named))
    by_name: Dict[str, float] = {}
    for ops in devs:
        for name, _, dur in ops:
            if not CONTAINERS.search(name):
                by_name[name] = by_name.get(name, 0.0) + dur / len(devs)
    ops0 = devs[0]
    busy = union(intervals(ops0))
    span = ((trace.get("windows") or {}).get(dev0)
            or ((busy[0][0], busy[-1][1]) if busy else None))
    gaps = sorted(subtract([tuple(span)], busy) if span else [],
                  key=lambda g: g[0] - g[1])
    spans = [s for s in (list(host_spans) or trace.get("host", [])) if s[2] > 0]
    starts = np.array([s[1] for s in spans])
    ends = starts + np.array([s[2] for s in spans])
    by_span: Dict[str, float] = {}
    # a step of ten thousand ops leaves as many gaps of a microsecond: the
    # longest are named one by one, by the innermost host span over their
    # middle, and the rest are summed under one name
    for a, b in gaps[:NAMED_GAPS]:
        mid, key = (a + b) / 2, "outside_any_span"
        if len(spans):
            over = np.flatnonzero((starts <= mid) & (ends >= mid))
            if len(over):
                key = spans[over[np.argmin(ends[over] - starts[over])]][0]
        by_span[key] = by_span.get(key, 0.0) + (b - a)
    if gaps[NAMED_GAPS:]:
        by_span["shorter_gaps"] = sum(b - a for a, b in gaps[NAMED_GAPS:])
    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(by_name), "idle_gaps": rank(by_span)}
