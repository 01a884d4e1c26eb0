"""The part of the matching ops' time (the collectives) in which no other
op runs on that device, per step, averaged over devices. Milliseconds."""

from benchmark.reducers import trace as tr


def reduce(args: dict, ev: dict):
    trace = ev.get("trace")
    if not trace or not trace["devices"] or not ev.get("steps_traced"):
        return None
    per_dev = []
    for dev, ops in tr.device_ops(trace, with_async=True).items():
        wire = tr.intervals(ops, args["pattern"])
        if not wire:
            continue
        other = tr.intervals(trace["devices"][dev], exclude=args["pattern"])
        per_dev.append(tr.total(tr.subtract(wire, other)))
    if not per_dev:
        return None
    return 1e3 * sum(per_dev) / len(per_dev) / ev["steps_traced"]
