"""Op-count probe: how much of the traced step is UPDATE path
(everything downstream of the gradient reduce)?

``update_path_op_count`` walks the traced jaxpr FORWARD from the
outputs of every reduce-kind collective (walker.REDUCE_KINDS — the
gradient psum / psum_scatter / all_to_all family) and counts the
equations that consume them, directly or transitively. This is the
number flat state keeps small: one fused vector update where a per-leaf
state would run a per-leaf scatter -> optimizer -> apply chain, with the
forward/backward half of the program untouched (tests/test_flat_state.py
pins it). Deterministic, CPU-only, nothing executes. The few post-reduce
metrics ops (loss pmean consumers) are counted too.

Sub-jaxpr handling mirrors walker.py: exact through the call-like
primitives (jit / shard_map / remat / custom_*), conservative inside
scan / while / cond (a tainted input taints every output and the WHOLE
body counts, nested sub-jaxprs included) — an over-approximation that
can only raise the count, never hide de-fusion.
"""

from __future__ import annotations

from typing import Any, Set, Tuple

from .walker import COLLECTIVE_PRIMS, REDUCE_KINDS, _is_var, _open, _subjaxprs


def _total_eqns(jaxpr) -> int:
    """Every equation under a jaxpr, nested sub-jaxprs included — the
    conservative 'all of it is update path' count for a tainted loop or
    branch body."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for sub, _ in _subjaxprs(eqn):
            n += _total_eqns(_open(sub))
    return n


def _forward_count(jaxpr, tainted: Set[Any]) -> Tuple[int, Set[Any]]:
    """One forward pass over an open jaxpr: seed taint at reduce-kind
    collective outputs, propagate through dataflow, count tainted eqns.
    Returns (count, tainted outvars of this jaxpr)."""
    count = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        in_tainted = any(v in tainted for v in eqn.invars if _is_var(v))
        subs = _subjaxprs(eqn)
        if subs:
            for sub, exact in subs:
                inner = _open(sub)
                if exact:
                    n = min(len(eqn.invars), len(inner.invars))
                    sub_taint = {
                        iv
                        for ov, iv in zip(eqn.invars[-n:], inner.invars[-n:])
                        if _is_var(ov) and ov in tainted and _is_var(iv)
                    }
                    c, sub_out = _forward_count(inner, sub_taint)
                    count += c
                    for ov, iv in zip(eqn.outvars, inner.outvars):
                        if _is_var(ov) and _is_var(iv) and iv in sub_out:
                            tainted.add(ov)
                else:
                    if in_tainted:
                        # loop/branch fed by the reduce: the WHOLE body
                        # is conservatively update path (a de-fused
                        # per-leaf update hidden inside a scan must
                        # raise the count, never collapse to 1)
                        count += _total_eqns(inner)
                        for v in eqn.outvars:
                            if _is_var(v):
                                tainted.add(v)
                    else:
                        # not fed by an outer reduce: count only its own
                        # internal post-reduce ops — and if the body
                        # CONTAINS a reduce, its outputs carry taint out
                        # of the loop (conservatively all of them; the
                        # in/out mapping is not exact here)
                        c, sub_out = _forward_count(inner, set())
                        count += c
                        if sub_out:
                            for v in eqn.outvars:
                                if _is_var(v):
                                    tainted.add(v)
            continue
        is_reduce = (
            name in COLLECTIVE_PRIMS
            and COLLECTIVE_PRIMS[name] in REDUCE_KINDS
        )
        if in_tainted:
            count += 1
        if in_tainted or is_reduce:
            # the reduce itself seeds taint but is not a post-reduce op
            for v in eqn.outvars:
                if _is_var(v):
                    tainted.add(v)
    return count, {v for v in jaxpr.outvars if _is_var(v) and v in tainted}


def update_path_op_count(fn, *args) -> int:
    """Number of jaxpr equations downstream of the gradient reduce in
    ``fn(*args)`` — the update-path size flat state keeps small.
    Traces only (ShapeDtypeStruct args are fine); nothing executes."""
    import jax

    return update_path_ops_from(jax.make_jaxpr(fn)(*args))


def update_path_ops_from(closed) -> int:
    """``update_path_op_count`` over an already-traced ClosedJaxpr (the
    tune/ cost model reuses the trace pscheck's rules ran on)."""
    count, _ = _forward_count(_open(closed), set())
    return count
