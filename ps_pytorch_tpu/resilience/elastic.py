"""Elastic membership: resume-reshape across mesh geometries + adaptive
partial aggregation.

The reference PS is married twice over to the cluster it started on: the
mpirun hostfile fixes the worker count for the life of the run, and the
``--num-aggregate`` backup-worker knob is a constant chosen before the
first straggler ever shows up. This module removes both bindings:

1. **Resume-reshape** (``MeshGeometry`` / ``reshape_raw_state``): a
   checkpoint written on an N-worker mesh restores onto an M-worker mesh
   — shrink or grow, replicated or ZeRO-1-sharded optimizer placement,
   any ``bucket_bytes``/``quant_block_size`` carving. The interchange
   format is the replicated TREE shape (exactly what checkpoints already
   store for params, PR 5's layout-portability rule); everything
   worker-count-dependent is canonicalized into it on load and
   re-specialized into the target geometry:

   - **params**: tree-shaped in the file already (``FlatVector``
     serialization handlers) — untouched, bit-exact by construction.
   - **optimizer moments**, ZeRO-1: the stacked ``[N, shard]`` rows are
     the workers' per-bucket regions of one padded flat vector
     (``ps._worker_region``); inverting that layout and re-carving under
     the target's ``BucketPlan`` is a pure rearrangement of the same f32
     bits, so moments are BIT-EXACT across N→M and across
     replicated↔sharded switches (the padding tails are zeros on both
     sides).
   - **error-feedback residuals**: per-worker state with no meaningful
     identity on a different mesh. Re-distributed SUM-PRESERVINGLY: the
     total residual mass (what EF will eventually add back to the
     update) is conserved — each of the M workers gets total/M — but
     the per-worker rows are NOT bit-preserved. Exact conservation when
     M is a power of two (f32 division by 2^k is lossless); otherwise
     conserved to f32 rounding. This is the documented exception.
   - **BatchNorm stats**, ``bn_mode="local"``: per-worker stacked stats
     are averaged and broadcast to the new mesh — the same "stats are
     statistics, not math" stance the reference takes by never syncing
     them. Documented exception: not bit-preserved under N≠M.
   - **guard counters / step**: mesh-size-free, pass through (the
     RESETTABLE merge in checkpoint.py still applies afterwards).

   The source geometry comes from a tiny ``elastic.json`` manifest the
   trainer drops next to its checkpoints (`save_geometry`, per-step
   entries — an elastically-resumed dir holds MIXED-geometry files); a
   dir without one (pre-elastic runs) resumes fine on the SAME geometry
   and fails with an actionable error on a changed one — except the one
   change shapes cannot catch, a ZeRO-1 bucket/quant re-carving (same
   stacked shapes, permuted worker→region mapping), for which the
   trainer warns that the carving is unverifiable.

2. **Adaptive partial aggregation** (``AdaptiveMaskController``): the
   static pre-psum mask generalized to ACE-Sync-style adaptive sync.
   With ``PSConfig.num_aggregate_min/max`` set, the compiled train step
   takes a traced int32 count (no retrace on change) and this host-side
   controller picks next window's count from the straggler watchdog's
   per-step walltimes: a window containing slow steps shrinks the count
   (one per slow step, floored at min — stop waiting for stragglers),
   a clean window grows it back by one (ceilinged at max). Every change
   emits a ``mask_adapt`` JSONL event. Full-count windows are bit-exact
   against the static ``num_aggregate=None`` path (mask of exactly 1.0,
   denominator exactly N); partial counts that are not powers of two
   may differ from the equivalent static config by 1 ULP (XLA
   strength-reduces division by a static constant; the traced
   denominator is a true divide).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
from typing import Optional

import numpy as np

logger = logging.getLogger("ps_pytorch_tpu")

GEOMETRY_FILE = "elastic.json"
GEOMETRY_VERSION = 1

# the MeshGeometry fields that decide state SHAPES/LAYOUT (needs_reshape
# reads these; dcn_hosts is recorded for the record but collective
# routing never changes what a checkpoint stores)
_SHAPE_FIELDS = (
    "num_workers", "opt_placement", "bucket_bytes", "quant_block_size",
    "compress", "error_feedback", "bn_mode",
)


@dataclasses.dataclass(frozen=True)
class MeshGeometry:
    """Everything about a run's mesh/placement that decides the SHAPES
    of its checkpointed state (the trainer's ``elastic.json`` manifest).
    A manifest an earlier version wrote may carry more keys (``from_json``
    drops them): checkpoints are tree-shaped at the boundary."""

    num_workers: int
    opt_placement: str = "replicated"
    bucket_bytes: Optional[int] = None
    quant_block_size: int = 0
    compress: Optional[str] = None
    error_feedback: bool = False
    bn_mode: str = "pmean"
    dcn_hosts: int = 1

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["version"] = GEOMETRY_VERSION
        return d

    @classmethod
    def from_json(cls, d: dict) -> "MeshGeometry":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})


def geometry_of(cfg) -> MeshGeometry:
    """The manifest entry for a live PSConfig."""
    return MeshGeometry(
        num_workers=cfg.num_workers,
        opt_placement=cfg.opt_placement,
        bucket_bytes=cfg.bucket_bytes,
        quant_block_size=cfg.quant_block_size,
        compress=None if cfg.compress in (None, "none") else cfg.compress,
        error_feedback=cfg.error_feedback,
        bn_mode=cfg.bn_mode,
        dcn_hosts=cfg.dcn_hosts,
    )


def save_geometry(model_dir: str, geom: MeshGeometry,
                  step: Optional[int] = None) -> str:
    """Atomically write/merge the manifest (call from the writer process
    only; the trainer gates on process_index() == 0 like checkpoint
    writes).

    The top-level fields describe the dir's LATEST writer; ``step``
    additionally records the geometry under ``steps[str(step)]``. The
    per-step map matters because an elastically-resumed dir holds
    checkpoints from MIXED geometries (step 3 written on 8 workers,
    step 6 on 4): a corrupt-newest fallback that restores the older
    file must reshape by the geometry that wrote THAT file — the
    latest-writer entry would mislabel it, loudly for shape-changing
    differences, silently for a ZeRO-1 bucket-carving-only change."""
    os.makedirs(model_dir, exist_ok=True)
    path = os.path.join(model_dir, GEOMETRY_FILE)
    data = geom.to_json()
    steps = {}
    if os.path.exists(path):
        try:
            with open(path, "r", encoding="utf-8") as f:
                steps = json.load(f).get("steps", {}) or {}
        except (OSError, ValueError):
            steps = {}  # a torn manifest must not fail the save
    if step is not None:
        steps[str(step)] = geom.to_json()
    if steps:
        data["steps"] = steps
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


def load_geometry(model_dir: str,
                  step: Optional[int] = None) -> Optional[MeshGeometry]:
    """The geometry that wrote checkpoint ``step`` (``step=None``: the
    dir's latest writer), or None when it cannot be known.

    None in two honest cases: no manifest (a pre-elastic dir), and a
    ``step`` with no per-step record — such a step was written BEFORE
    per-step tracking, so the latest-writer entry would be a guess, and
    guessing wrong on a ZeRO-1 carving is silent moment-scrambling; the
    caller's manifest-less path (restore unreshaped + warn) is strictly
    safer. A torn/unreadable manifest also returns None: resume's
    contract is quarantine-and-fall-back, and the manifest must never be
    the file that bricks it (the checkpoint CRC still guards the state
    itself)."""
    path = os.path.join(model_dir, GEOMETRY_FILE)
    if not os.path.exists(path):
        return None
    try:
        with open(path, "r", encoding="utf-8") as f:
            data = json.load(f)
        if step is not None:
            entry = (data.get("steps") or {}).get(str(step))
            return None if entry is None else MeshGeometry.from_json(entry)
        return MeshGeometry.from_json(data)
    except (OSError, ValueError, TypeError) as e:
        logger.warning(
            "elastic manifest %s is unreadable (%s); treating the dir "
            "as manifest-less", path, e,
        )
        return None


def needs_reshape(src: MeshGeometry, dst: MeshGeometry) -> bool:
    """Would a checkpoint written under ``src`` mis-load (wrong shapes OR
    silently wrong region mapping) into a ``dst``-geometry state?

    The subtle case: the ZeRO-1 stacked ``[n, shard]`` moment SHAPE does
    not depend on ``bucket_bytes`` (carving never changes the padded
    total), but the worker→region MAPPING does — a bucket_bytes change
    under the sharded placement loads cleanly and trains on scrambled
    moments. Reshape routes on the mapping, not just the shape."""
    if src.opt_placement != dst.opt_placement:
        return True
    n_changed = src.num_workers != dst.num_workers
    if src.opt_placement == "sharded":
        if n_changed:
            return True
        if (src.bucket_bytes or 0) != (dst.bucket_bytes or 0):
            return True
        if _quant_block(src) != _quant_block(dst):
            return True
    if n_changed and (src.error_feedback or dst.error_feedback):
        return True
    src_local = src.bn_mode == "local"
    dst_local = dst.bn_mode == "local"
    if src_local != dst_local or (n_changed and src_local):
        return True
    return False


# ------------------------------------------------------------ geometry math

def _quant_block(geom: MeshGeometry) -> int:
    if geom.compress in ("int8", "int8_2round") and geom.quant_block_size:
        return geom.quant_block_size
    return 1


def _ps_config(geom: MeshGeometry):
    """A PSConfig carrying this geometry, so the bucket plans come from
    THE engine's own ``_sharded_plan``/``wire_align`` — the reshape can
    never desync from the carving the live run used. Lazy import:
    parallel.ps imports resilience.guard, so a module-level import here
    would cycle through the package __init__."""
    from ..parallel.ps import PSConfig

    return PSConfig(
        num_workers=geom.num_workers,
        opt_placement=geom.opt_placement,
        bucket_bytes=geom.bucket_bytes,
        quant_block_size=geom.quant_block_size,
        compress=geom.compress,
        error_feedback=geom.error_feedback,
        bn_mode=geom.bn_mode,
    )


def _sharded_plan(geom: MeshGeometry, total: int):
    from ..parallel.ps import _sharded_plan as plan

    return plan(_ps_config(geom), total)


def _regions_to_flat(stacked: np.ndarray, plan, n: int) -> np.ndarray:
    """Invert ``ps._worker_region``: stacked per-worker rows (each row =
    that worker's 1/n slice of every bucket, concatenated in bucket
    order) back into the one padded flat vector. Pure bit rearrangement."""
    flat = np.zeros((plan.padded_total,), np.asarray(stacked).dtype)
    off = 0
    for start, size in zip(plan.starts, plan.sizes):
        s = size // n
        for w in range(n):
            flat[start + w * s:start + (w + 1) * s] = stacked[w, off:off + s]
        off += s
    return flat


def _flat_to_regions(flat: np.ndarray, plan, n: int) -> np.ndarray:
    """``ps._worker_region`` for all workers at once, host-side."""
    out = np.empty((n, plan.padded_total // n), np.asarray(flat).dtype)
    off = 0
    for start, size in zip(plan.starts, plan.sizes):
        s = size // n
        for w in range(n):
            out[w, off:off + s] = flat[start + w * s:start + (w + 1) * s]
        off += s
    return out


def _tree_template(layout, length: int):
    from ..parallel.buckets import _np_flat_to_tree

    return _np_flat_to_tree(layout, np.zeros((length,), np.float32))


def _dict_to_flat(state_dict, layout, plan) -> np.ndarray:
    """Tree-shaped nested dict (the canonical interchange form) -> one
    padded flat vector in ``plan``'s geometry."""
    from flax import serialization

    from ..parallel.buckets import _np_tree_to_flat

    tree = serialization.from_state_dict(
        _tree_template(layout, plan.padded_total), state_dict
    )
    return _np_tree_to_flat(layout, plan, tree)


def _flat_to_dict(flat: np.ndarray, layout):
    """Padded (or exactly-total) flat vector -> tree-shaped nested dict."""
    from flax import serialization

    from ..parallel.buckets import _np_flat_to_tree

    return serialization.to_state_dict(_np_flat_to_tree(layout, flat))


# ------------------------------------------------------- opt_state reshape

def _opt_to_canonical(node, src_plan, n: int, layout):
    """Walk a stored ZeRO-1 opt_state dict: every stacked ``[n, shard]``
    moment becomes a tree-shaped dict (bit-exact region inversion), every
    stacked ``[n]`` scalar (optax step counts — identical on every
    worker by construction) collapses to row 0."""
    if node is None:
        return None
    if isinstance(node, dict):
        return {
            k: _opt_to_canonical(v, src_plan, n, layout)
            for k, v in node.items()
        }
    arr = np.asarray(node)
    shard = src_plan.padded_total // n
    if arr.ndim == 2 and arr.shape == (n, shard):
        return _flat_to_dict(_regions_to_flat(arr, src_plan, n), layout)
    if arr.ndim == 1 and arr.shape[0] == n:
        return arr[0]
    return node


def _opt_from_canonical(canon, tgt_node, dst_plan, m: int, layout):
    """Walk the TARGET's (fresh ZeRO-1) opt_state dict in parallel with
    the canonical form: tree-shaped moments are flattened and carved
    into the target's stacked regions, scalars broadcast to ``[m]``."""
    if tgt_node is None:
        return None
    if isinstance(tgt_node, dict):
        if not isinstance(canon, dict) or set(tgt_node) - set(canon):
            # same loud error for a non-dict AND for missing keys (e.g.
            # an sgd checkpoint resumed onto an adam target lacks
            # mu/nu): letting None fall through would surface as an
            # obscure flax structure crash or an object-dtype array
            raise ValueError(
                "elastic reshape: checkpointed optimizer state does not "
                "match the target optimizer's structure — resume with the "
                "same --optimizer the checkpoint was written with"
            )
        return {
            k: _opt_from_canonical(canon[k], tgt_node[k], dst_plan, m,
                                   layout)
            for k in tgt_node
        }
    tarr = np.asarray(tgt_node)
    shard = dst_plan.padded_total // m
    if tarr.ndim == 2 and tarr.shape == (m, shard):
        return _flat_to_regions(_dict_to_flat(canon, layout, dst_plan),
                                dst_plan, m)
    if tarr.ndim == 1 and tarr.shape[0] == m:
        return np.broadcast_to(np.asarray(canon), (m,)).copy()
    return canon


# ------------------------------------------------------ EF residual reshape

def _ef_to_canonical(raw_comm, src: MeshGeometry, layout):
    """Per-worker residual state -> ONE tree-shaped total-residual dict
    (sum over workers: the mass EF owes the next update)."""
    if src.opt_placement == "sharded":
        arr = np.asarray(raw_comm, np.float32)  # [n, padded_total_src]
        return _flat_to_dict(arr.sum(axis=0), layout)

    def leaf_sum(node):
        if isinstance(node, dict):
            return {k: leaf_sum(v) for k, v in node.items()}
        return np.asarray(node, np.float32).sum(axis=0)

    return leaf_sum(raw_comm)


def _ef_from_canonical(canon, dst: MeshGeometry, layout):
    """Total residual -> per-worker rows of total/M (sum-preserving; the
    per-worker split is NOT bit-preserved — documented exception)."""
    m = dst.num_workers
    if dst.opt_placement == "sharded":
        total = layout.total
        plan = _sharded_plan(dst, total)
        flat = _dict_to_flat(canon, layout, plan) / np.float32(m)
        return np.tile(flat[None, :], (m, 1))

    def leaf_rows(node):
        if isinstance(node, dict):
            return {k: leaf_rows(v) for k, v in node.items()}
        leaf = np.asarray(node, np.float32) / np.float32(m)
        return np.broadcast_to(leaf, (m,) + leaf.shape).copy()

    return leaf_rows(canon)


# ---------------------------------------------------------- bn-stats reshape

def _bn_to_canonical(raw_bs, local: bool):
    if not local:
        return raw_bs

    def leaf_mean(node):
        if isinstance(node, dict):
            return {k: leaf_mean(v) for k, v in node.items()}
        return np.asarray(node).mean(axis=0)

    return leaf_mean(raw_bs)


def _bn_from_canonical(canon, local: bool, m: int):
    if not local:
        return canon

    def leaf_stack(node):
        if isinstance(node, dict):
            return {k: leaf_stack(v) for k, v in node.items()}
        arr = np.asarray(node)
        return np.broadcast_to(arr, (m,) + arr.shape).copy()

    return leaf_stack(canon)


# --------------------------------------------------------------- entry point

def reshape_raw_state(raw: dict, src: MeshGeometry, dst_cfg, target) -> dict:
    """Transform a raw checkpoint state dict written under ``src`` into
    one loadable by ``checkpoint.restore_from_raw(target, ...)`` for a
    run configured as ``dst_cfg`` (a PSConfig), where ``target`` is the
    freshly-initialized host-side PSTrainState for the NEW geometry.

    params/step/guard_state pass through untouched (tree-shaped and
    mesh-size-free respectively); opt_state moments are bit-exact
    rearrangements; EF residuals and local BN stats are re-distributed
    (see module docstring for exactly what is and is not bit-preserved).
    """
    from flax import serialization

    from ..parallel.buckets import FlatVector, tree_layout

    dst = geometry_of(dst_cfg)
    if isinstance(target.params, FlatVector):
        layout = target.params.layout
    else:
        layout = tree_layout(target.params)
    out = dict(raw)

    # ---- optimizer moments (bit-exact across every geometry change)
    opt_raw = raw.get("opt_state")
    if opt_raw is not None:
        canon = opt_raw
        if src.opt_placement == "sharded":
            src_plan = _sharded_plan(src, layout.total)
            canon = _opt_to_canonical(
                opt_raw, src_plan, src.num_workers, layout
            )
        if dst.opt_placement == "sharded":
            dst_plan = _sharded_plan(dst, layout.total)
            tgt_opt = serialization.to_state_dict(target.opt_state)
            canon = _opt_from_canonical(
                canon, tgt_opt, dst_plan, dst.num_workers, layout
            )
        out["opt_state"] = canon

    # ---- error-feedback residuals (sum-preserving re-distribution);
    # present-vs-disabled mismatches are left for restore_from_raw's
    # existing loud config errors. Redistribute ONLY when worker
    # identity is actually lost (N or placement changed): the residual
    # rows are indexed by worker × flat position — replicated rows are
    # per-leaf and the sharded rows are FULL padded vectors, never
    # region-carved — so a bucket-carving-only (or bn-only) reshape
    # keeps every worker's accumulated residual bit-exact for free.
    comm = raw.get("comm_state")
    if comm is not None and target.comm_state is not None:
        identity_kept = (
            src.num_workers == dst.num_workers
            and src.opt_placement == dst.opt_placement
            and (
                src.opt_placement != "sharded"
                or _sharded_plan(src, layout.total).padded_total
                == _sharded_plan(dst, layout.total).padded_total
            )
        )
        if not identity_kept:
            out["comm_state"] = _ef_from_canonical(
                _ef_to_canonical(comm, src, layout), dst, layout
            )

    # ---- BatchNorm stats (mean/broadcast for the local mode) — same
    # identity rule as EF: per-worker stacked stats survive any reshape
    # that keeps N and locality (e.g. a ZeRO-1 carving-only change);
    # averaging them there would discard accumulated running stats for
    # nothing
    bs = raw.get("batch_stats")
    if bs is not None:
        src_local = src.bn_mode == "local"
        dst_local = dst.bn_mode == "local"
        bn_identity_kept = src_local == dst_local and (
            not src_local or src.num_workers == dst.num_workers
        )
        if not bn_identity_kept:
            out["batch_stats"] = _bn_from_canonical(
                _bn_to_canonical(bs, src_local), dst_local, dst.num_workers
            )

    return out


# ----------------------------------------------------- adaptive aggregation

class AdaptiveMaskController:
    """Host half of adaptive partial aggregation: windowed step-time
    statistics (the straggler watchdog's walltimes — the trainer arms
    its per-step barrier whenever this controller exists) pick the next
    window's aggregation count inside [num_aggregate_min, max].

    Policy — deliberately simple and deterministic (the chaos suite
    drives it through FaultPlan.slow_steps):

    - a window containing slow steps (walltime > ``threshold_s``, the
      watchdog's own threshold) shrinks the count by the number of slow
      steps, floored at min: stop waiting for that many stragglers
      within one window of first seeing them;
    - a clean window grows the count by one, ceilinged at max: recover
      gradually so a transient storm does not leave the run degraded.

    Every change emits one ``mask_adapt`` JSONL event through
    ``event_sink``; the traced count itself is clipped again on device,
    so the PSC108 envelope holds even against a buggy controller.

    Multi-host: hosts observe DIFFERENT local walltimes (the straggling
    host sees the stall; a fast host may not), but every host must pass
    the SAME traced count into the global psum — divergent counts make
    the masked aggregate mathematically wrong and silently diverge
    replicated params. ``consensus`` (trainer-provided on multi-host:
    min over hosts of the proposed count, one int32 DCN allgather) is
    applied at each window close — window boundaries are step-counted
    and therefore already identical across hosts. Min semantics: a
    straggler seen by ANY host shrinks everyone; recovery happens only
    when every host's window was clean. The ``slow_steps`` field of the
    mask_adapt event stays the LOCAL observation (hosts' events may
    differ there; step/from/to are identical by construction).

    This consensus hookup is CONTRACT, not convention: the registry's
    adaptive specs declare it as ``AdaptivePolicy.consensus =
    "trainer.Trainer._count_consensus"`` and PSC110 statically verifies
    the named function exists and is consensus-shaped (its return passes
    through a consensus collective — lint/diverge.py's inventory), while
    PSL007 flags any new path that feeds a process-divergent count to
    the traced step without laundering it first."""

    def __init__(self, cfg, threshold_s: float, window: int,
                 event_sink=None, consensus=None):
        if not cfg.adaptive_aggregate:
            raise ValueError(
                "AdaptiveMaskController needs num_aggregate_min/max set"
            )
        if window < 1:
            raise ValueError(f"adapt window must be >= 1, got {window}")
        if threshold_s is None or threshold_s <= 0:
            raise ValueError(
                "adaptive aggregation needs the straggler watchdog's "
                "threshold (arm it with --mode/--kill-threshold): the "
                "controller consumes its per-step walltimes"
            )
        self.lo = cfg.num_aggregate_min
        self.hi = cfg.num_aggregate_max
        self.count = int(cfg.initial_aggregate)
        self.threshold_s = float(threshold_s)
        self.window = int(window)
        self.adaptations = 0
        self._sink = event_sink
        self._consensus = consensus
        self._steps = 0
        self._slow = 0
        self._win_start: Optional[int] = None

    def record(self, step_no: int, seconds: float) -> int:
        """Feed one step's walltime; returns the count the NEXT step
        should use (changes only at window boundaries)."""
        if self._win_start is None:
            self._win_start = step_no
        self._steps += 1
        if seconds > self.threshold_s:
            self._slow += 1
        if self._steps >= self.window:
            self._close_window(step_no)
        return self.count

    def _close_window(self, step_no: int) -> None:
        old = self.count
        if self._slow:
            new = max(self.lo, old - self._slow)
        else:
            new = min(self.hi, old + 1)
        if self._consensus is not None:
            # every host calls this at the same (step-counted) boundary;
            # the adopted count is identical everywhere by construction
            new = min(max(int(self._consensus(new)), self.lo), self.hi)
        if new != old:
            self.adaptations += 1
            logger.info(
                "mask_adapt: aggregation count %d -> %d after window "
                "%d-%d (%d/%d slow steps)",
                old, new, self._win_start, step_no, self._slow, self._steps,
            )
            if self._sink is not None:
                self._sink({
                    "kind": "mask_adapt",
                    "step": step_no,
                    "window_start": self._win_start,
                    "from": old,
                    "to": new,
                    "slow_steps": self._slow,
                    "window_steps": self._steps,
                })
        self.count = new
        self._steps = 0
        self._slow = 0
        self._win_start = None
