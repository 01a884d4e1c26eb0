"""Resilience layer: failure is normal, so defend and *prove* the defense.

The reference PS design already treats failure as a first-class input —
a straggler kill-threshold on workers and an evaluator that survives on
checkpoints alone. This package gives the TPU-native reproduction the
matching machinery, in four parts:

- ``guard``:  the device-side non-finite gradient guard fused into the PS
  train step (parallel/ps.py) — a skipped step is the identity update,
  counted on device, with optional dynamic loss scaling for the int8
  compression schemes.
- ``retry``:  bounded exponential-backoff retry for checkpoint I/O (the
  reference's shared-NFS evaluator is exactly where transient EIO lives).
- ``faults``: a deterministic, env/flag-driven fault-injection plan so
  every defense is chaos-tested end-to-end (inject -> skip/fallback/
  resume -> converge) instead of trusted.
- ``elastic``: membership is an input too — resume-reshape lets a
  checkpoint written on an N-worker mesh continue on an M-worker mesh
  (shrink/grow, replicated<->ZeRO-1), and the adaptive aggregation
  controller turns the static backup-worker mask into a per-window
  response to observed stragglers.
"""

from .elastic import (
    AdaptiveMaskController,
    MeshGeometry,
    geometry_of,
    load_geometry,
    needs_reshape,
    reshape_raw_state,
    save_geometry,
)
from .faults import FaultPlan, resolve_fault_plan
from .guard import GuardState, init_guard_state, tree_all_finite
from .retry import retry_io

__all__ = [
    "AdaptiveMaskController",
    "FaultPlan",
    "GuardState",
    "MeshGeometry",
    "geometry_of",
    "init_guard_state",
    "load_geometry",
    "needs_reshape",
    "reshape_raw_state",
    "resolve_fault_plan",
    "retry_io",
    "save_geometry",
    "tree_all_finite",
]
