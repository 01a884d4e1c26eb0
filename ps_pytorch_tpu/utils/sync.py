"""Honest device synchronization for timing code.

Two traps this helper exists to avoid, on any backend:

1. Per-buffer readiness: waiting on (or reading) a step's *loss* does not
   serialize the same step's parameter update, because in every train
   step here the metrics outputs are produced by the forward/backward
   pass while the gradient aggregation + optimizer apply feed only the
   params outputs. A timing loop that waits on the loss stops the clock
   before the step has finished.
2. Trusting a readiness signal for the barrier a measurement rests on. A
   HOST read (`float(...)`) of a value that depends on every buffer needs
   no such trust: the value must exist.

`host_sync(*trees)` dispatches one tiny jitted reduction that consumes one
element of EVERY array leaf of every tree passed, then host-reads the
scalar — so it returns only after every buffer in those trees has retired.
Cost: one element per leaf + one scalar transfer.
"""

from __future__ import annotations

from functools import reduce

import jax
import jax.numpy as jnp


@jax.jit
def _probe(leaves):
    return reduce(
        lambda acc, x: acc + x.ravel()[0].astype(jnp.float32),
        leaves,
        jnp.float32(0.0),
    )


def host_sync(*trees) -> float:
    """Block until every array leaf of every tree has actually been
    computed, via a host read that depends on all of them. Returns the
    (meaningless) probe scalar so callers can keep a data dependency."""
    leaves = [
        x
        for t in trees
        for x in jax.tree_util.tree_leaves(t)
        if hasattr(x, "dtype") and getattr(x, "size", 0)
    ]
    if not leaves:
        return 0.0
    return float(_probe(leaves))
