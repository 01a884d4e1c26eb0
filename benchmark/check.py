"""Readings for the limits of `correct`: several seeds in one process, so a
dozen seeds cost one compile and not twelve.

    python benchmark/check.py --workload <name> --seeds 1,2,3 [--control]

For each seed it prints the numbers `run.py` compares (the program's first
steps against the plain reference). With --control the configuration's
next-lower precision stands in the program's place: a limit belongs above
the largest sound reading and below the smallest control reading.
`run.py` never calls this; the benchmark's own runs do the comparison
themselves.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control", action="store_true",
                    help="the reference at the control's operand precision "
                         "(traffic `control_operand`) in the program's place")
    ap.add_argument("--out", default=None, help="also append the rows to this file")
    args = ap.parse_args(argv)
    from benchmark import compare, drivers, run, spec
    from benchmark.compiles import CompileWatch

    cell = spec.load_cell(args.workload)
    jax = run.setup_jax()
    devices = run.find_chips(jax, cell.chips)
    watch = CompileWatch().install()
    driver = drivers.load(cell.kind)
    worst = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        out_dir = tempfile.mkdtemp(prefix="bench_check_")
        t0 = time.perf_counter()
        try:
            ctx = {"out_dir": out_dir, "compiles": watch, "devices": devices}
            prog, ref = driver.check(cell, seed, args.control, ctx)
        finally:
            shutil.rmtree(out_dir, ignore_errors=True)
        numbers = compare.training_numbers(prog, ref)
        row = {"workload": cell.name, "seed": seed, "control": args.control,
               "numbers": numbers,
               "worst_grad": compare.worst_leaves(prog["grad_norms"], ref["grad_norms"], ref["leaf_names"]),
               "worst_dparam": compare.worst_leaves(prog["dparam_norms"], ref["dparam_norms"], ref["leaf_names"]), "loss": prog["loss"], "ref_loss": ref["loss"],
               "seconds": time.perf_counter() - t0}
        print("[check] " + json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
        for k, v in numbers.items():
            worst[k] = (min if args.control else max)(worst.get(k, v), v)
    which = "smallest" if args.control else "largest"
    print("[check] " + json.dumps({"workload": cell.name, "control": args.control,
                                   which: worst, "limits": cell.limits}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
