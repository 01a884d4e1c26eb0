"""Cuts a recorded capture (tools/record_trace.py) down to the end of one
block and the start of the next on at most two devices, and pins what every
device-trace reducer of the cell reads from it, for tests/test_reducers.py.
The fixture is read as `blocks` = 2 blocks of `block_steps` runs, the real
boundary between them: ops are kept for the runs `trim` keeps and one run
either side.

    python benchmark/tools/make_trace_fixture.py <cell> <block_steps> [<traces dir>]
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

if __name__ == "__main__":
    from benchmark import reducers, spec
    from benchmark.reducers import trace as tr

    name, k = sys.argv[1], int(sys.argv[2])
    src = sys.argv[3] if len(sys.argv) > 3 else os.path.join(ROOT, "chiprun_out", "traces")
    cell = spec.load_cell(name)
    real_k = int(cell.traffic["block_steps"])
    full = tr.load_json(os.path.join(src, name + ".trace.json.gz"))
    small = {"devices": {}, "async": {}, "modules": {}, "host": []}
    for dev in sorted(full["modules"])[:2]:
        runs = tr.step_runs(full["modules"][dev])
        # the last block boundary of the capture sits real_k runs from its end
        # (a recording of one block's inside has none: its last 2k runs serve)
        cut = len(runs) - (real_k if len(runs) >= 2 * real_k else k)
        runs = runs[cut - k:cut + k]
        lo, hi = runs[tr.BOUNDARY_RUNS - 1][1], runs[k + 1][1] + runs[k + 1][2]
        inside = lambda ops: [o for o in ops if lo <= o[1] and o[1] + o[2] <= hi]
        small["modules"][dev] = runs
        small["devices"][dev] = inside(full["devices"][dev])
        small["async"][dev] = inside((full.get("async") or {}).get(dev, []))
    data = os.path.join(ROOT, "benchmark", "tests", "data")
    os.makedirs(data, exist_ok=True)
    tr.save_json(small, os.path.join(data, name + ".trace.json.gz"))
    cut, got = tr.trim(small, 2, k)
    ev = {"trace": cut, "steps_traced": got, "cell": cell,
          "peaks": spec.load_peaks("TPU v5 lite")}
    metrics = {m["name"]: reducers.reduce(m["kind"], m.get("args", {}), ev)
               for m in cell.per_layer if m["source"] == "device_trace"}
    busy, window = tr.busy_and_window(cut)
    with open(os.path.join(data, name + ".expected.json"), "w") as f:
        json.dump({"blocks": 2, "block_steps": k, "steps_traced": got, "busy_s": busy,
                   "window_s": window, "gaps_between_runs_ms": tr.run_gaps_ms(small),
                   "metrics": metrics,
                   "origin": "one v5e host, PR 23, tools/record_trace.py"}, f, indent=1)
    print(got, busy, window, metrics)
