"""`roofline` for a kernel whose work is sized at run time: the function
`work` also gets the run's counters named in `counters`, so the least time
is that of the work the traced steps really did (the driver gives each as a
mean over the steps whose time is read). A run that lacks one of them gives
no metric. Evidence with no counters at all is a recorded capture without
its run: the work function then counts what its configuration and traffic
alone give. Percent; nothing here clips it."""

from benchmark import flops
from benchmark.reducers import scope_time


def reduce(args: dict, ev: dict):
    ms = scope_time.reduce({"pattern": args["pattern"]}, ev)
    if ms is None:
        return None
    counted = None
    if ev.get("counters") is not None:
        if any(name not in ev["counters"] for name in args["counters"]):
            return None
        counted = {name: ev["counters"][name] for name in args["counters"]}
    cell, peaks = ev["cell"], ev["peaks"]
    module = flops.load(args.get("module") or cell.config["flops"])
    work = getattr(module, args["work"])(cell.config, cell.traffic, counted)
    least_s = max(work["flops"] / peaks[work["peak"]],
                  work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)
