"""Median per step of the summed durations of the named host spans of the
program's tracer, over the steps inside the measured window. Milliseconds."""

import statistics


def reduce(args: dict, ev: dict):
    names, per_step = set(args["spans"]), {}
    for s in ev.get("spans") or []:
        if s["name"] in names and "step" in s and s["t_abs"] >= ev["window_t0"]:
            per_step[s["step"]] = per_step.get(s["step"], 0.0) + s["dur"]
    if not per_step:
        return None
    return 1e3 * statistics.median(per_step.values())
