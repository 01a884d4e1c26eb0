"""Trace-only cost model: a modeled step time for any candidate config.

Everything here is computable on CPU in seconds with NOTHING executing —
the inputs are the same jaxpr-level measurements the contract checker
already takes (check/walker.py collective accounting, check/opcount.py
update-path ops, parallel/overlap.py schedule freedom), priced by a
DECLARED hardware profile (link bandwidths + per-collective launch cost
+ per-op update cost + a per-model compute floor).

The step-time estimate follows the analytical model of "On the Utility
of Gradient Compression in Distributed Training Systems" (PAPERS.md):
communication only costs walltime where it cannot hide behind compute,
so

    modeled_step_s = compute_s
                   + update_path_ops * op_cost_s
                   + comm_s * (1 - overlap_headroom)

where ``comm_s`` is the alpha-beta collective time (per-row: algorithm
factor x bytes / link bandwidth + count x launch cost — the same factor
table tools/predicted_scaling.py uses) and ``overlap_headroom`` is the
jaxpr schedule-freedom probe's mean independent fraction (what a
latency-hiding scheduler MAY run beside the wire). A measured probe can
substitute its span-derived dispatch fraction for the jaxpr headroom
(``modeled_step_seconds`` is the one formula both paths share).

This is a RANKING model, not a simulator: absolute seconds inherit every
caveat of runs/predicted_scaling.json's alpha-beta pricing, but the
orderings it produces are pinned against evidence the repo has already
banked (tests/test_tune.py: per-leaf vs bucketed collective counts from
runs/comm_contract.json, serial vs pipelined headroom from
runs/overlap_ab.json, and the homomorphic wire ranking <= its dequant
twin on the ResNet18 int8 leg).

The ``wire_domain`` knob (§6h) needs no special term: a homomorphic
candidate's narrowed accumulator psum (int16 vs int32), dropped round-2
scale rows, and int8 hierarchical reassembly all land in its OWN traced
byte rows, so ``comm_seconds_from_rows`` prices the compressed-domain
wire exactly the way PSC104 accounts it.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Optional, Sequence

# the committed alpha-beta model whose link numbers the default profile
# inherits (tools/predicted_scaling.py wrote it; tests pin the format)
DEFAULT_SCALING_MODEL = "runs/predicted_scaling.json"

# per-device single-step compute floor, seconds, by network — a
# single-chip step time divided across the mesh. Both values are
# unverified (no chip record in the repository backs them; ResNet18
# b1024 matches runs/predicted_scaling.json model.t1_seconds, LeNet is
# b8192): re-measure under ROADMAP D7.
# Used only when the scaling-model file is absent or names no t1 for
# the network — the profile always records which source it used.
_T1_FALLBACK_S = {"ResNet18": 6.693e-2, "LeNet": 7.083e-3}

# collective algorithm factors over a group of size g (ring schedules;
# the same table tools/predicted_scaling.py prices HLO ops with):
# all-reduce moves 2(g-1)/g of the payload per link, one-shot
# gather/scatter/all_to_all (g-1)/g, permute 1.
_ALL_REDUCE_KINDS = ("psum", "pmax", "pmin", "pmean")
_ONE_SHOT_KINDS = ("psum_scatter", "all_gather", "all_to_all")


def _kind_factor(kind: str, g: int) -> float:
    if g <= 1:
        return 0.0
    if kind in _ALL_REDUCE_KINDS:
        return 2.0 * (g - 1) / g
    if kind in _ONE_SHOT_KINDS:
        return (g - 1) / g
    return 1.0  # ppermute and anything exotic: one payload per link


@dataclasses.dataclass(frozen=True)
class HardwareProfile:
    """The declared hardware a candidate is priced for.

    ``collective_launch_s`` is the fixed cost of ONE collective
    (dispatch + rendezvous latency) — the term that separates a
    62-collective per-leaf wire from an 11-bucket fused one even when
    both move the same bytes. ``op_cost_s`` prices one update-path
    jaxpr equation (the term flat state holds at 120 on ResNet18, where
    a per-leaf state read 386). ``compute_s`` is the per-step
    forward+backward floor communication hides behind."""

    name: str = "tpu_v5e_defaults"
    ici_gbs: float = 45.0           # one-way per-link GB/s
    dcn_gbs: float = 12.5           # per-host GB/s
    collective_launch_s: float = 2e-5
    op_cost_s: float = 2e-7
    compute_s: float = 0.0
    source: str = "builtin defaults"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def load_hardware_profile(
    network: str,
    num_workers: int,
    path: Optional[str] = None,
    ici_gbs: Optional[float] = None,
    dcn_gbs: Optional[float] = None,
) -> HardwareProfile:
    """Profile with link numbers from the committed scaling model
    (runs/predicted_scaling.json "model" block) when present, builtin
    fallbacks otherwise; explicit ``ici_gbs``/``dcn_gbs`` always win.
    ``compute_s`` = the network's single-chip step time / num_workers
    (perfect compute scaling is assumed — the error is common to every
    candidate of one search, so rankings are unaffected)."""
    path = path or DEFAULT_SCALING_MODEL
    base = HardwareProfile()
    ici, dcn = base.ici_gbs, base.dcn_gbs
    t1 = _T1_FALLBACK_S.get(network)
    source = f"builtin defaults (no {path})"
    if os.path.exists(path):
        try:
            with open(path, "r", encoding="utf-8") as f:
                model = json.load(f).get("model", {})
            ici = float(model.get("ici_gbs_one_way", ici))
            dcn = float(model.get("dcn_gbs_per_host", dcn))
            if network == "ResNet18" and "t1_seconds" in model:
                t1 = float(model["t1_seconds"])
                source = path
            else:
                # the file priced only the links; say where t1 came from
                # instead of claiming the whole profile came from it
                source = f"{path} (links); builtin t1 for {network}"
        except (OSError, ValueError):
            source = f"builtin defaults (unreadable {path})"
    if t1 is None:
        # an unknown network still ranks: wire/schedule/op terms are the
        # candidate-dependent part, the floor just offsets them all
        t1 = 0.0
    return HardwareProfile(
        ici_gbs=ici_gbs if ici_gbs is not None else ici,
        dcn_gbs=dcn_gbs if dcn_gbs is not None else dcn,
        compute_s=t1 / max(num_workers, 1),
        source=source,
    )


def comm_seconds_from_rows(
    rows: Sequence[dict],
    axis_sizes: Dict[str, int],
    profile: HardwareProfile,
) -> float:
    """Alpha-beta collective time for accounting rows shaped like the
    pscheck artifact's (``{kind, axes, dtype, count, bytes}`` — bytes
    TOTAL across the row's count). Rows riding a DCN axis are priced on
    the per-host NIC; pure-ICI rows on the ICI link."""
    total = 0.0
    for row in rows:
        g = 1
        for ax in row.get("axes", ()):
            g *= int(axis_sizes.get(ax, 1))
        gbs = (
            profile.dcn_gbs
            if any(ax == "dcn" for ax in row.get("axes", ()))
            else profile.ici_gbs
        )
        total += _kind_factor(row["kind"], g) * row["bytes"] / (gbs * 1e9)
        total += int(row["count"]) * profile.collective_launch_s
    return total


def modeled_step_seconds(
    comm_s: float,
    overlap_headroom: Optional[float],
    update_path_ops: int,
    profile: HardwareProfile,
) -> float:
    """THE step-time formula (module docstring). Shared by the
    trace-only path (jaxpr headroom) and the probe-calibrated path
    (measured dispatch fraction) so the two can never drift."""
    exposed = comm_s * (1.0 - (overlap_headroom or 0.0))
    return profile.compute_s + update_path_ops * profile.op_cost_s + exposed


@dataclasses.dataclass(frozen=True)
class CandidateCost:
    """One candidate's modeled cost plus every input that produced it —
    the record stores the inputs so the regression gate can re-derive
    ``modeled_step_s`` through the live formula and catch the model and
    the banked artifact drifting apart."""

    comm_rows: List[dict]           # full per-(kind,axes,dtype) accounting
    wire_bytes: int                 # gradient-path reduce bytes (PSC102 set)
    n_collectives: int              # every collective eqn in the step
    n_grad_reduces: int             # reduce-kind eqns feeding the params
    update_path_ops: int            # jaxpr eqns downstream of the reduce
    overlap_headroom: Optional[float]   # mean independent fraction
    mean_dispatch_prefix: Optional[float]
    comm_s: float
    exposed_comm_s: float
    compute_s: float
    update_s: float
    modeled_step_s: float

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        for k in ("comm_s", "exposed_comm_s", "compute_s", "update_s",
                  "modeled_step_s"):
            d[k] = round(d[k], 9)
        return d


def model_cost(
    result,
    profile: HardwareProfile,
    axis_sizes: Dict[str, int],
) -> CandidateCost:
    """Cost one traced candidate (a check/core.TraceResult carrying its
    ClosedJaxpr via ``trace_spec(keep_jaxpr=True)``)."""
    from ..check.opcount import update_path_ops_from
    from ..check.walker import REDUCE_KINDS
    from ..parallel.overlap import overlap_headroom_from

    if result.closed is None:
        raise ValueError(
            "model_cost needs the candidate's traced jaxpr — trace with "
            "trace_spec(spec, keep_jaxpr=True)"
        )
    comm_s = comm_seconds_from_rows(result.summary, axis_sizes, profile)
    wire_bytes = sum(
        c.bytes for c in result.collectives
        if c.feeds_params and c.kind in REDUCE_KINDS
    )
    n_grad = sum(
        1 for c in result.collectives
        if c.feeds_params and c.kind in REDUCE_KINDS
    )
    headrep = overlap_headroom_from(result.closed)
    headroom = headrep.get("overlap_headroom")
    ops = update_path_ops_from(result.closed)
    exposed = comm_s * (1.0 - (headroom or 0.0))
    update_s = ops * profile.op_cost_s
    return CandidateCost(
        comm_rows=list(result.summary),
        wire_bytes=wire_bytes,
        n_collectives=sum(int(r["count"]) for r in result.summary),
        n_grad_reduces=n_grad,
        update_path_ops=ops,
        overlap_headroom=headroom,
        mean_dispatch_prefix=headrep.get("mean_dispatch_prefix"),
        comm_s=comm_s,
        exposed_comm_s=exposed,
        compute_s=profile.compute_s,
        update_s=update_s,
        modeled_step_s=modeled_step_seconds(comm_s, headroom, ops, profile),
    )
