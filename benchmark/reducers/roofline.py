"""A kernel's share of its roofline: the least time the chip could take for
the operations and bytes the algorithm needs, over the kernel's measured
time per step. The work is the function `work` of the module under
benchmark/flops/ that `module` names, or else of the configuration's own
(`"flops"` in its file). Percent; it cannot pass 100, and nothing here
clips it."""

from benchmark import flops
from benchmark.reducers import scope_time


def reduce(args: dict, ev: dict):
    ms = scope_time.reduce({"pattern": args["pattern"]}, ev)
    if ms is None:
        return None
    cell, peaks = ev["cell"], ev["peaks"]
    module = flops.load(args.get("module") or cell.config["flops"])
    work = getattr(module, args["work"])(cell.config, cell.traffic)
    least_s = max(work["flops"] / peaks[work["peak"]],
                  work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)
