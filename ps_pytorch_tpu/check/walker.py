"""Jaxpr collective walker: the measurement half of pscheck.

Walks a traced step function's jaxpr (recursing through jit/shard_map/
scan/while/cond/custom_* sub-jaxprs) and returns every collective
equation with its axes, per-device payload shape/dtype, and byte count —
the ground truth the contract rules (rules.py) check against. A reverse
liveness pass simultaneously marks which collectives feed the updated
parameters (as opposed to, say, the metrics pmean), which is what lets
PSC102 say "psummed over that axis BEFORE the optimizer" instead of
"psummed somewhere".

Liveness is exact through jit / shard_map / custom_{jvp,vjp} / remat
call boundaries (1:1 invar/outvar mapping) and conservative inside
scan / while / cond bodies (any live output marks the whole body live —
an over-approximation that can only add ancestors, never lose one).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

# primitive name -> canonical collective kind reported in contracts.
# psum_scatter lowers to the reduce_scatter primitive; both spellings are
# mapped so the walker is robust across jax versions.
COLLECTIVE_PRIMS: Dict[str, str] = {
    "psum": "psum",
    "pmax": "pmax",
    "pmin": "pmin",
    "ppermute": "ppermute",
    "pshuffle": "ppermute",
    "all_gather": "all_gather",
    "all_to_all": "all_to_all",
    "reduce_scatter": "psum_scatter",
    "psum_scatter": "psum_scatter",
}

# reduce-style kinds that consume (sum over) an axis — the family PSC102
# accepts as "the gradient reduction"
REDUCE_KINDS = ("psum", "psum_scatter", "all_to_all")


@dataclasses.dataclass(frozen=True)
class Collective:
    """One collective equation from the traced step."""

    kind: str                 # canonical kind (COLLECTIVE_PRIMS values)
    axes: Tuple[str, ...]     # mesh axis names it rides
    dtype: str                # payload dtype (first operand)
    shapes: Tuple[Tuple[int, ...], ...]  # per-operand payload shapes
    bytes: int                # per-device payload bytes (sum of operands)
    feeds_params: bool        # reverse-reachable from the params outputs

    def to_json(self) -> dict:
        return {
            "kind": self.kind,
            "axes": list(self.axes),
            "dtype": self.dtype,
            "shapes": [list(s) for s in self.shapes],
            "bytes": self.bytes,
            "feeds_params": self.feeds_params,
        }


def _axes_of(eqn) -> Tuple[str, ...]:
    ax = eqn.params.get("axes", None)
    if ax is None:
        ax = eqn.params.get("axis_name", None)
    if ax is None:
        return ()
    if isinstance(ax, (tuple, list)):
        return tuple(str(a) for a in ax)
    return (str(ax),)


def _payload_by_dtype(eqn) -> List[Tuple[str, Tuple[Tuple[int, ...], ...], int]]:
    """(dtype, shapes, bytes) PER OPERAND DTYPE. jax batches a whole-tree
    psum into one eqn with every leaf as an operand; splitting by dtype
    here means a single f32 leaf smuggled into an otherwise-int8
    collective still surfaces as its own f32 record for PSC103 instead of
    hiding behind the first operand's dtype."""
    groups: Dict[str, List] = {}
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        if aval is None or not hasattr(aval, "shape"):
            continue
        dtype = str(aval.dtype)
        g = groups.setdefault(dtype, [[], 0])
        g[0].append(tuple(int(d) for d in aval.shape))
        numel = 1
        for d in aval.shape:
            numel *= int(d)
        g[1] += numel * aval.dtype.itemsize
    return [
        (dtype, tuple(shapes), nbytes)
        for dtype, (shapes, nbytes) in sorted(groups.items())
    ]


# call-like primitives whose invars/outvars map 1:1 onto their sub-jaxpr's,
# by the names the installed jax gives them (jax.jit traces to `jit`, the
# old `pjit`; jax.checkpoint to `remat2`). Shared with check/numerics.py: a
# name missing here makes BOTH walkers treat the call as opaque, and the
# gradient-path taint then leaks onto every psum behind it.
EXACT_CALLS = frozenset({
    "jit", "closed_call", "call", "remat2", "custom_jvp_call",
    "custom_jvp_call_jaxpr", "custom_vjp_call", "shard_map", "custom_lin",
})


def _subjaxprs(eqn) -> List[Tuple[Any, bool]]:
    """(jaxpr-like, exact_io_mapping) pairs under one equation.

    exact=True means eqn invars/outvars map 1:1 onto the sub-jaxpr's —
    true for the call-like primitives; loops and branches get the
    conservative treatment.
    """
    name = eqn.primitive.name
    out: List[Tuple[Any, bool]] = []
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr", "cond_jaxpr",
                "body_jaxpr"):
        sub = eqn.params.get(key)
        if sub is not None:
            exact = name in EXACT_CALLS and key in ("jaxpr", "call_jaxpr",
                                                    "fun_jaxpr")
            out.append((sub, exact))
    for br in eqn.params.get("branches", ()) or ():
        out.append((br, False))
    return out


def _open(jaxpr_like):
    """ClosedJaxpr | Jaxpr -> Jaxpr."""
    return getattr(jaxpr_like, "jaxpr", jaxpr_like)


def _is_var(v) -> bool:
    return hasattr(v, "aval") and not hasattr(v, "val")  # Var, not Literal


def _walk(
    jaxpr,
    live: Set[Any],
    all_live: bool,
    out: List[Collective],
) -> Set[Any]:
    """Reverse pass over one (open) jaxpr.

    `live` holds vars of THIS jaxpr known to feed the params outputs;
    returns the subset of this jaxpr's invars that feed them. Collects
    every collective eqn into `out`, marking feeds_params.
    """
    needed: Set[Any] = set(live)
    for eqn in reversed(jaxpr.eqns):
        eqn_live = all_live or any(
            v in needed for v in eqn.outvars if _is_var(v)
        )
        subs = _subjaxprs(eqn)
        name = eqn.primitive.name
        if subs:
            for sub, exact in subs:
                inner = _open(sub)
                if exact and not all_live:
                    sub_live = {
                        iv
                        for ov, iv in zip(eqn.outvars, inner.outvars)
                        if _is_var(ov) and ov in needed and _is_var(iv)
                    }
                    sub_needed = _walk(inner, sub_live, False, out)
                    # eqn invars map 1:1 onto sub invars for call-likes;
                    # zip from the END so leading const-vars (remat-style
                    # open jaxprs) stay aligned
                    n = min(len(eqn.invars), len(inner.invars))
                    for ov, iv in zip(eqn.invars[-n:], inner.invars[-n:]):
                        if iv in sub_needed and _is_var(ov):
                            needed.add(ov)
                    # constvars feeding params conservatively mark all
                    if any(cv in sub_needed for cv in inner.constvars):
                        for v in eqn.invars:
                            if _is_var(v):
                                needed.add(v)
                else:
                    _walk(inner, set(), eqn_live, out)
                    if eqn_live:
                        for v in eqn.invars:
                            if _is_var(v):
                                needed.add(v)
            continue
        if name in COLLECTIVE_PRIMS:
            for dtype, shapes, nbytes in _payload_by_dtype(eqn):
                out.append(
                    Collective(
                        kind=COLLECTIVE_PRIMS[name],
                        axes=_axes_of(eqn),
                        dtype=dtype,
                        shapes=shapes,
                        bytes=nbytes,
                        feeds_params=bool(eqn_live),
                    )
                )
        if eqn_live:
            for v in eqn.invars:
                if _is_var(v):
                    needed.add(v)
    return needed


def collect_collectives(
    closed_jaxpr,
    param_out_indices: Optional[Sequence[int]] = None,
) -> List[Collective]:
    """All collectives in a ClosedJaxpr, in reverse traversal order.

    `param_out_indices` are flat output positions (into jaxpr.outvars)
    holding the updated parameters; collectives that reach them get
    feeds_params=True. With None, every collective is (conservatively)
    marked as feeding params.
    """
    jaxpr = _open(closed_jaxpr)
    out: List[Collective] = []
    if param_out_indices is None:
        _walk(jaxpr, set(), True, out)
    else:
        live = {
            jaxpr.outvars[i]
            for i in param_out_indices
            if _is_var(jaxpr.outvars[i])
        }
        _walk(jaxpr, live, False, out)
    out.reverse()
    return out


def summarize(collectives: Sequence[Collective]) -> List[dict]:
    """Aggregate per (kind, axes, dtype): the stable accounting rows the
    committed contract artifact pins (PSC104)."""
    acc: Dict[Tuple[str, Tuple[str, ...], str], dict] = {}
    for c in collectives:
        key = (c.kind, c.axes, c.dtype)
        row = acc.setdefault(
            key,
            {
                "kind": c.kind,
                "axes": list(c.axes),
                "dtype": c.dtype,
                "count": 0,
                "bytes": 0,
            },
        )
        row["count"] += 1
        row["bytes"] += c.bytes
    return [
        acc[k]
        for k in sorted(acc, key=lambda k: (k[0], k[1], k[2]))
    ]
