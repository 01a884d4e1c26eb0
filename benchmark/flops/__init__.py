"""Operations and bytes the algorithms need, from shapes alone: one module
per architecture, found by the name a configuration's file gives under
`"flops"` (configs/<config>.json), so a driver carries no model's name. A
later PR cannot change a module that is here, so a roofline share or a
model-FLOP utilization means the same thing in every PR; a new architecture
brings a module of its own. Every function takes (config, traffic) and is
checked against a hand count in tests/test_flops.py. A multiply-add is two
operations; recomputation is never counted.

A module that stands for a model has `train_flops_per_item(config, traffic)`:
the operations one item (an image, a token) costs in a training step,
forward and backward. A kernel's work for a roofline share is a function
named by the metric's file (`work`, and `module` where it is not the
configuration's own) returning {"flops", "bytes", "peak"}.
"""

import importlib


def load(name: str):
    return importlib.import_module(f"benchmark.flops.{name}")
