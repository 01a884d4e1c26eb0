"""Plain float32 references, one module per configuration, named by the
configuration file's `reference` key. They import nothing of the program
and take nothing it has made: weights and inputs come from the seed through
benchmark/weights.py.
"""

import importlib


def load(name: str):
    return importlib.import_module(f"benchmark.reference.{name}")
