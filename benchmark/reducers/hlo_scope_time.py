"""Device time per step by the scopes the program writes INSIDE its step
(ps_pytorch_tpu/obs/scopes.py) and the phase jax writes around them.
Milliseconds.

The program keeps the last step it built under a name (`args["program"]`:
"lm_train_step" | "ps_train_step") and that step gives the census of its own
executable: {instruction: [phase, scope, work, mixed, via]} (obs/hlo.py has
the rules, the one for a fusion of several scopes among them). A device
event's name starts with its instruction's name (`reducers/trace.short_name`
keeps it), so the census joins to `ev["trace"]` by name. An op counts where

- `phase` (an expression, optional) matches its phase: forward | backward |
  remat | update | input | other;
- `scope` (an expression, optional) matches its scope path, and `less`
  (optional) does not;
- its work is none of `less_work` (optional list: "dot", "kernel",
  "collective", "reduce", "other");
- or, with `"unplaced": true` and nothing else, where it has no scope of the
  vocabulary, no phase ("other"), or no instruction in the census.

The union of the counted ops' intervals on each device, averaged over
devices, over the steps traced: `scope_time`'s arithmetic. One `[bench]
scopes` line a run says what the join found. Under 99% of device time found
in the census every metric of this reducer is left out and the line says
why; a program that keeps no census (the parent of the PR that brought the
scopes) gives None without a word.
"""

from __future__ import annotations

import json
import re
import time
from typing import Dict, List, Optional, Tuple

from benchmark.reducers import trace as tr

FOUND_SHARE = 0.99
PAIRS_SHOWN = 12


def _say(obj: dict) -> None:
    print(f"[bench] scopes {json.dumps(obj)}", flush=True)


def program_census(program: str) -> Optional[dict]:
    """The census of the last step the program built under `program`, or
    None where the program has no such registry or the step has not run."""
    try:
        from ps_pytorch_tpu.obs.scopes import last_step
    except ImportError:
        return None
    step = last_step(program)
    if step is None:
        return None
    t0 = time.perf_counter()
    try:
        census = step.scopes()
    except RuntimeError:        # built and never called
        return None
    return {**census, "census_s": time.perf_counter() - t0}


def placed(row) -> bool:
    """obs/hlo.is_placed, for a row that may be None (no instruction in the
    census); spelt here because a program without the census has no obs/hlo."""
    return row is not None and bool(row[1]) and row[0] != "other"


def join(ev: dict, census: dict) -> dict:
    """`time_where(keep)`: ms a step in the ops whose place `keep` accepts
    (None: no instruction in the census), and what the `[bench] scopes`
    line reports."""
    from ps_pytorch_tpu.obs.hlo import instruction_of

    table = census["instructions"]
    steps = ev["steps_traced"]
    spans: Dict[Tuple[str, Optional[tuple]], List[Tuple[float, float]]] = {}
    found_as: Dict[str, Optional[tuple]] = {}
    total = found = inherited = 0.0
    unfound: Dict[str, float] = {}
    devices = tr.device_ops(ev["trace"])
    for dev, ops in devices.items():
        for name, start, dur in ops:
            if tr.CONTAINERS.search(name):
                continue
            if name not in found_as:
                ins = instruction_of(name, table)
                found_as[name] = None if ins is None else (
                    *table[ins][:3], tuple(table[ins][3]), table[ins][4])
            row = found_as[name]
            total += dur
            if row is None:
                unfound[name] = unfound.get(name, 0.0) + dur
            else:
                found += dur
                inherited += dur if row[4] else 0.0
            spans.setdefault((dev, row and row[:4]), []).append((start, start + dur))
    # a device runs its ops in series, so a selection's time is the sum of
    # its places' times, each the union of that place's intervals
    seconds: Dict[Optional[tuple], float] = {}
    for (dev, row), intervals in spans.items():
        seconds[row] = seconds.get(row, 0.0) + tr.total(intervals)
    n = max(len(devices), 1)
    ms = lambda s: 1e3 * s / n / steps

    def time_where(keep) -> float:
        return ms(sum(s for row, s in seconds.items() if keep(row)))

    rows = {row for row in seconds if row is not None}
    by_phase = {p: time_where(lambda r, p=p: placed(r) and r[0] == p)
                for p in sorted({r[0] for r in rows if placed(r)})}
    by_scope = {s: time_where(lambda r, s=s: placed(r) and r[1].split("/")[0] == s)
                for s in sorted({r[1].split("/")[0] for r in rows if placed(r)})}
    pairs: Dict[str, float] = {}
    for here, other in sorted({(f"{r[0]}:{r[1]}", o) for r in rows for o in r[3]}):
        pairs[f"{here} | {other}"] = time_where(
            lambda r, h=here, o=other: r is not None and f"{r[0]}:{r[1]}" == h and o in r[3])
    shown = dict(sorted(pairs.items(), key=lambda kv: -kv[1])[:PAIRS_SHOWN])
    line = {
        "program": census.get("program"), "census_s": census.get("census_s"),
        "census_read_s": census.get("read_s"),
        "instructions": len(table), "found_pct": 100.0 * found / total if total else 0.0,
        "step_ms": time_where(lambda r: True), "ms_by_phase": by_phase,
        "ms_by_top_scope": by_scope,
        "mixed_ms": time_where(lambda r: r is not None and bool(r[3])),
        "mixed_ms_by_pair": shown,
        "unplaced_ms": time_where(lambda r: not placed(r)),
        "placed_by_a_neighbour_ms": ms(inherited),
        "unfound_ms": ms(total - found),
        "unfound_top": [[k, ms(v)] for k, v in
                        sorted(unfound.items(), key=lambda kv: -kv[1])[:5]],
    }
    ok = total > 0 and found / total >= FOUND_SHARE
    if not ok:
        line["why"] = (f"under {100 * FOUND_SHARE:.0f}% of device time has its instruction in "
                       "the census: every metric of hlo_scope_time is left out")
    return {"ok": ok, "time_where": time_where, "line": line}


def _joined(ev: dict, program: str) -> Optional[dict]:
    """The join of this run, made once and kept on the evidence."""
    kept = ev.setdefault("_hlo_scope_time", {})
    if program not in kept:
        # a census recorded beside a capture (tools/record_scopes.py) stands
        # in for the registry of a program that is not running
        census = (ev.get("census") or {}).get(program) or program_census(program)
        kept[program] = None if census is None else join(ev, census)
        if kept[program] is not None:
            _say(kept[program]["line"])
    return kept[program]


def reduce(args: dict, ev: dict):
    trace = ev.get("trace")
    if not trace or not trace.get("devices") or not ev.get("steps_traced"):
        return None
    joined = _joined(ev, args["program"])
    if joined is None or not joined["ok"]:
        return None
    if args.get("unplaced"):
        return joined["time_where"](lambda r: not placed(r))
    phase, scope, less = (re.compile(args[k]) if args.get(k) else None
                          for k in ("phase", "scope", "less"))
    less_work = tuple(args.get("less_work", ()))

    def keep(row) -> bool:
        return (placed(row) and (phase is None or phase.search(row[0]) is not None)
                and (scope is None or scope.search(row[1]) is not None)
                and (less is None or less.search(row[1]) is None)
                and row[2] not in less_work)

    return joined["time_where"](keep)
