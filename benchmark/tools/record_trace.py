"""Runs one traced run of a cell and keeps the whole capture, normalised
by reducers/trace.py, with the list of planes and lines, under
chiprun_out/traces/. tools/make_trace_fixture.py cuts tests/data/ from it.

    python benchmark/tools/record_trace.py <workload> <seed> <seconds>
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

if __name__ == "__main__":
    from benchmark import run, spec

    cell = spec.load_cell(sys.argv[1])
    jax = run.setup_jax()
    devices = run.find_chips(jax, cell.chips)
    peaks = spec.load_peaks(devices[0].device_kind)
    sys.exit(run.run_cell(cell, int(sys.argv[2]), float(sys.argv[3]), 1, devices,
                          peaks, record_to=os.path.join(ROOT, "chiprun_out", "traces")))
