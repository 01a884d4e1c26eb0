"""Cuts what tools/record_spans.py kept of one traced run down to what the
span reducers read, for tests/test_reducers.py: the program's span stream
from three blocks before `window_t0` to the end of the capture, the
capture's step-program runs and Execute events whole, and the device's busy
time as intervals (ops closer together than MERGE_S merged into one, which
takes the ten thousand microsecond gaps of a ResNet step out and leaves the
block boundaries). The readings of every span reducer on the cut are pinned
beside it.

    python benchmark/tools/make_join_fixture.py <cell> [<traces dir>]
"""

import contextlib
import gzip
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

MERGE_S = 2e-6
TRACE_BLOCKS = 2
SPAN_KINDS = ("boundary_time", "span_duration", "span_share", "idle_named")


def read(fixture: dict, cell) -> dict:
    """Every span reducer's reading of a fixture, and the idle reducer's line."""
    from benchmark import reducers
    from benchmark.reducers import trace as tr

    k = int(cell.traffic["block_steps"])
    trace, steps = tr.trim(fixture["trace"], TRACE_BLOCKS, k)
    ev = {"trace": trace, "steps_traced": steps, "cell": cell,
          "spans": fixture["spans"], "window_t0": fixture["window_t0"]}
    out = {}
    said = io.StringIO()
    with contextlib.redirect_stdout(said):
        for m in cell.per_layer:
            if m["kind"] in SPAN_KINDS:
                out[m["name"]] = reducers.reduce(m["kind"], m.get("args", {}), ev)
    line = said.getvalue().strip()
    return {"metrics": out, "line": json.loads(line.split(" ", 2)[2]) if line else None}


if __name__ == "__main__":
    from benchmark import run, spec
    from benchmark.reducers import host_spans as hs
    from benchmark.reducers import trace as tr

    name = sys.argv[1]
    src = sys.argv[2] if len(sys.argv) > 2 else os.path.join(ROOT, "chiprun_out", "traces")
    cell = spec.load_cell(name)
    full = tr.load_json(os.path.join(src, name + ".trace.json.gz"))
    with open(os.path.join(src, name + ".window_t0.json")) as f:
        t0 = json.load(f)["window_t0"]
    spans = run.read_spans(os.path.join(src, name + ".spans.jsonl"))
    closes = [w for w in hs.named(spans, "window_close") if w["t_abs"] <= t0][-4:]
    spans = [s for s in spans if s["t_abs"] >= closes[0]["t_abs"] or s["name"] == "build"]
    small = {"devices": {}, "modules": {}, "async": {},
             "host": [e for e in full["host"] if e[0] == "PJRT_LoadedExecutable_Execute"]}
    for dev in sorted(full["modules"])[:1]:
        small["modules"][dev] = tr.step_runs(full["modules"][dev])
        busy = []
        for a, b in tr.union(tr.intervals(full["devices"][dev])):
            if busy and a - busy[-1][1] < MERGE_S:
                busy[-1][1] = b
            else:
                busy.append([a, b])
        small["devices"][dev] = [["busy", a, b - a] for a, b in busy]
    fixture = {"trace": small, "spans": spans, "window_t0": t0,
               "origin": "one v5e chip, PR 24, tools/record_spans.py"}
    fixture["expected"] = read(fixture, cell)
    data = os.path.join(ROOT, "benchmark", "tests", "data")
    with gzip.open(os.path.join(data, name + ".join.json.gz"), "wt") as f:
        json.dump(fixture, f)
    print(json.dumps(fixture["expected"], indent=1))
