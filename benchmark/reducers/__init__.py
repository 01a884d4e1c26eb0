"""Reducers from evidence (device trace, host spans, counters) to one
number. layer_metrics/<metric>.json names a reducer `kind` and its `args`;
`reduce(kind, args, ev)` finds benchmark/reducers/<kind>.py and calls its
`reduce(args, ev)`. A reducer that finds nothing to read returns None and
the metric is left out of the line.
"""

import importlib


def reduce(kind: str, args: dict, ev: dict):
    return importlib.import_module(f"benchmark.reducers.{kind}").reduce(args, ev)
