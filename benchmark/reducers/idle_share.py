"""1 minus the union of the device ops' intervals over the traced window,
averaged over devices. Percent."""

from benchmark.reducers import trace as tr


def reduce(args: dict, ev: dict):
    trace = ev.get("trace")
    if not trace or not trace["devices"]:
        return None
    busy, window = tr.busy_and_window(trace)
    if window <= 0:
        return None
    return 100.0 * (1.0 - busy / window)
