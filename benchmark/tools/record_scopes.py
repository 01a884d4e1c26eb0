"""tools/record_trace.py, keeping the step's census too: one traced run of a
cell, the whole capture under chiprun_out/traces/ and beside it
<cell>.scopes.json, what the program's step says of its own executable
(ps_pytorch_tpu/obs/scopes.py: {instruction: [phase, scope, work, mixed,
via]}), which `reducers/hlo_scope_time.py` joins to the capture by name.

    python benchmark/tools/record_scopes.py <workload> <seed> <seconds>

With --cut, no chip: cuts tests/data/<cell>.scopes.json.gz from the two, as
tools/make_trace_fixture.py cuts a capture (two blocks of <block_steps> runs
on one device, the census kept for the instructions the cut holds), and pins
beside it what every `hlo_scope_time` metric of the cell reads there.

    python benchmark/tools/record_scopes.py --cut <workload> <block_steps> [<traces dir>]
"""

import contextlib
import gzip
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

KIND = "hlo_scope_time"
TRACE_BLOCKS = 2


def programs(cell):
    return sorted({m["args"]["program"] for m in cell.per_layer if m["kind"] == KIND})


def read(fixture: dict, cell) -> dict:
    """Every `hlo_scope_time` metric's reading of a fixture, and the line."""
    from benchmark import reducers
    from benchmark.reducers import trace as tr

    trace, steps = tr.trim(fixture["trace"], TRACE_BLOCKS, fixture["block_steps"])
    ev = {"trace": trace, "steps_traced": steps, "cell": cell, "census": fixture["census"]}
    out, said = {}, io.StringIO()
    with contextlib.redirect_stdout(said):
        for m in cell.per_layer:
            if m["kind"] == KIND:
                out[m["name"]] = reducers.reduce(KIND, m["args"], ev)
    line = said.getvalue().strip()
    return {"metrics": out, "line": json.loads(line.split(" ", 2)[2]) if line else None}


def cut(name: str, k: int, src: str) -> None:
    from benchmark import spec
    from benchmark.reducers import trace as tr
    from ps_pytorch_tpu.obs.hlo import instruction_of

    cell = spec.load_cell(name)
    real_k = int(cell.traffic["block_steps"])
    full = tr.load_json(os.path.join(src, name + ".trace.json.gz"))
    with open(os.path.join(src, name + ".scopes.json")) as f:
        recorded = json.load(f)
    dev = sorted(full["modules"])[0]
    runs = tr.step_runs(full["modules"][dev])
    at = len(runs) - (real_k if len(runs) >= 2 * real_k else k)   # make_trace_fixture's cut
    runs = runs[at - k:at + k]
    lo, hi = runs[tr.BOUNDARY_RUNS - 1][1], runs[k + 1][1] + runs[k + 1][2]
    ops = [o for o in full["devices"][dev] if lo <= o[1] and o[1] + o[2] <= hi]
    census = {}
    for program, c in recorded.items():
        held = {instruction_of(o[0], c["instructions"]) for o in ops} - {None}
        census[program] = {"program": program,
                           "instructions": {n: c["instructions"][n] for n in sorted(held)}}
    fixture = {"trace": {"devices": {dev: ops}, "modules": {dev: runs}, "async": {}, "host": []},
               "census": census, "block_steps": k,
               "origin": recorded[next(iter(recorded))].get("origin", "")}
    fixture["expected"] = read(fixture, cell)
    with gzip.open(os.path.join(ROOT, "benchmark", "tests", "data",
                                name + ".scopes.json.gz"), "wt") as f:
        json.dump(fixture, f)
    print(json.dumps(fixture["expected"], indent=1))


def record(name: str, seed: int, seconds: float) -> int:
    from benchmark import run, spec
    from benchmark.reducers import hlo_scope_time

    cell = spec.load_cell(name)
    out = os.path.join(ROOT, "chiprun_out", "traces")
    reduce_all = run.per_layer_metrics

    def keeping(cell_, res, *a, **kw):
        got = reduce_all(cell_, res, *a, **kw)
        os.makedirs(out, exist_ok=True)
        kept = {p: hlo_scope_time.program_census(p) for p in programs(cell)}
        for c in kept.values():
            c["origin"] = f"tools/record_scopes.py {name} {seed} {seconds}"
            c.pop("by_place", None)
        with open(os.path.join(out, name + ".scopes.json"), "w") as f:
            json.dump(kept, f)
        return got

    run.per_layer_metrics = keeping
    jax = run.setup_jax()
    devices = run.find_chips(jax, cell.chips)
    peaks = spec.load_peaks(devices[0].device_kind)
    return run.run_cell(cell, seed, seconds, 1, devices, peaks, record_to=out)


if __name__ == "__main__":
    if sys.argv[1] == "--cut":
        cut(sys.argv[2], int(sys.argv[3]),
            sys.argv[4] if len(sys.argv) > 4 else os.path.join(ROOT, "chiprun_out", "traces"))
    else:
        sys.exit(record(sys.argv[1], int(sys.argv[2]), float(sys.argv[3])))
