"""The comparison that decides `correct`: the program's numbers against the
plain reference's, each with a limit of its own (workloads/<cell>.json).

Norms are compared leaf by leaf and the worst leaf counts: the gap between
the program's norm and the reference's (not the norm of their difference),
against the reference's norm of that leaf or of the median leaf, whichever
is larger, because some gradients are all but zero.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, Sequence


def worst_leaf_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    if len(prog) != len(ref) or not ref:
        return math.inf
    floor = statistics.median(ref)
    worst = 0.0
    for p, r in zip(prog, ref):
        if not (math.isfinite(p) and math.isfinite(r)):
            return math.inf
        den = max(r, floor)
        worst = max(worst, abs(p - r) / den if den > 0 else math.inf)
    return worst


def worst_leaves(prog, ref, names, k: int = 3):
    """The k leaves with the widest gap, as [name, program, reference]: for
    the line that explains a comparison, not for the decision."""
    floor = statistics.median(ref)
    gaps = sorted(((abs(p - r) / max(r, floor, 1e-30), n, p, r)
                   for p, r, n in zip(prog, ref, names)), reverse=True)
    return [[n, p, r] for _, n, p, r in gaps[:k]]


def rel_gap(p: float, r: float) -> float:
    if not (math.isfinite(p) and math.isfinite(r)) or r == 0:
        return math.inf
    return abs(p - r) / abs(r)


def training_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """prog / ref: {"loss": [l1, l2, l3], "grad_norms": [...per leaf],
    "dparam_norms": [...per leaf]} -> the numbers held to a limit."""
    out = {
        f"loss_step{i + 1}_rel": rel_gap(p, r)
        for i, (p, r) in enumerate(zip(prog["loss"], ref["loss"]))
    }
    if len(prog["loss"]) != len(ref["loss"]):
        out["loss_step1_rel"] = math.inf
    out["grad_norm_worst_leaf"] = worst_leaf_gap(
        prog["grad_norms"], ref["grad_norms"])
    out["dparam_norm_worst_leaf"] = worst_leaf_gap(
        prog["dparam_norms"], ref["dparam_norms"])
    return out


def decide(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, rows): every number beside its limit. A number with no
    limit, or a limit with no number, is not correct."""
    rows, ok = [], True
    for name in sorted(set(numbers) | set(limits)):
        value, limit = numbers.get(name), limits.get(name)
        passed = (
            value is not None and limit is not None
            and math.isfinite(value) and value <= limit
        )
        ok = ok and passed
        rows.append({"number": name, "value": value, "limit": limit,
                     "ok": passed})
    return ok, rows
