"""Device time per step in the ops whose name matches `pattern` (all ops
when it is absent): the union of their intervals on each device, averaged
over devices, over the steps traced. `with_async` adds the ops of the
`Async XLA Ops` line. Milliseconds."""

from benchmark.reducers import trace as tr


def reduce(args: dict, ev: dict):
    trace = ev.get("trace")
    if not trace or not trace["devices"] or not ev.get("steps_traced"):
        return None
    per_dev = [tr.total(tr.intervals(ops, args.get("pattern"), args.get("exclude")))
               for ops in tr.device_ops(trace, args.get("with_async", False)).values()]
    if not per_dev or not any(per_dev):
        return None
    return 1e3 * sum(per_dev) / len(per_dev) / ev["steps_traced"]
