"""One module per traffic kind; `load(kind)` finds it by name. A driver has
`run(cell, seed, seconds, trace, ctx)` for a measured run and
`check(cell, seed, control, ctx)` for the numbers of the first steps alone.
"""

import importlib


def load(kind: str):
    return importlib.import_module(f"benchmark.drivers.{kind}")
