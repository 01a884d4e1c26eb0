"""tools/record_trace.py, keeping the program's span stream too: one traced
run of a cell, the whole capture under chiprun_out/traces/ and beside it
<cell>.spans.jsonl (the trainer's own file) and <cell>.window_t0.json.
tools/make_join_fixture.py cuts tests/data/<cell>.join.json.gz from them.

    python benchmark/tools/record_spans.py <workload> <seed> <seconds>
"""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

if __name__ == "__main__":
    from benchmark import run, spec

    cell = spec.load_cell(sys.argv[1])
    out = os.path.join(ROOT, "chiprun_out", "traces")
    read_spans = run.read_spans

    def keeping(path):
        os.makedirs(out, exist_ok=True)
        if path and os.path.exists(path):
            shutil.copy(path, os.path.join(out, cell.name + ".spans.jsonl"))
        return read_spans(path)

    run.read_spans = keeping
    reduce_all = run.per_layer_metrics

    def noting(cell_, res, *a, **kw):
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, cell.name + ".window_t0.json"), "w") as f:
            json.dump({"window_t0": res["evidence"]["window_t0"],
                       "warmup_block_s": res["evidence"]["warmup_block_s"]}, f)
        return reduce_all(cell_, res, *a, **kw)

    run.per_layer_metrics = noting
    jax = run.setup_jax()
    devices = run.find_chips(jax, cell.chips)
    peaks = spec.load_peaks(devices[0].device_kind)
    sys.exit(run.run_cell(cell, int(sys.argv[2]), float(sys.argv[3]), 1, devices,
                          peaks, record_to=out))
