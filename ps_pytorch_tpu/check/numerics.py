"""psnumerics — precision-flow analysis over traced jaxprs (PSC111-114).

The walker (walker.py) measures WHERE the collectives are; this module
proves WHAT the quantized wire's numbers can be. A forward abstract
interpretation over the same traced jaxpr tracks, per variable,

  * an interval bound (``lo``/``hi``) — the worst-case value range on
    the integer lattice (int8 payloads enter at +-127 via the traced
    clamp; collectives and reductions multiply it by their traced
    summand counts),
  * scale provenance (``roots``) — the set of max-abs reductions
    (an ``abs`` feeding a ``reduce_max``) this value's scale chain
    descends from,
  * payload provenance (``sites``) — the set of quantization sites
    (bounded float->int converts) this value descends from, and
  * residual provenance (``deqs``) — the dequantization events it
    descends from (the error-feedback closure check, PSC112).

Call-likes (jit / shard_map / remat / custom_{jvp,vjp}) are entered
exactly, mirroring the walker's 1:1 invar/outvar mapping. ``cond``
branches are joined exactly (one branch runs). ``scan``/``while`` carry
state is ITERATED to a provenance fixpoint with bounds dropped to
unknown — a value routed through a loop carry can never prove a bound,
so a numerics rule over it degrades to "cannot prove", never to a
vacuous pass; chains confined to a single iteration stay exact.

Quantization sites are keyed by their cumulative element offset on the
gradient path (``start_offset``) — the same flat-buffer coordinates the
bucketed wire uses — so per-bucket format decisions (ROADMAP item 1)
land on lattice state the analyzer already tracks per bucket.

Everything here is pure data over ``jax.core`` jaxprs: nothing
executes, no device is touched.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, FrozenSet, List, Optional, Sequence, Tuple

import numpy as np

from .walker import EXACT_CALLS as _EXACT_CALLS, _is_var, _open

# reduce-kind collectives (the walker's REDUCE_KINDS, by primitive name):
# outputs are "downstream of the gradient reduce" for PSC114
_REDUCE_PRIMS = {"psum", "psum_scatter", "reduce_scatter", "all_to_all"}

_EMPTY: FrozenSet[int] = frozenset()


def _finfo_mant(dtype) -> Optional[int]:
    try:
        return int(np.finfo(np.dtype(dtype)).nmant) + 1  # + implicit bit
    except Exception:
        pass
    # np.finfo refuses extension floats (bfloat16, fp8) — those live in
    # ml_dtypes, which ships with jax and has its own finfo
    try:
        import ml_dtypes

        return int(ml_dtypes.finfo(np.dtype(dtype)).nmant) + 1
    except Exception:
        return None


def _int_cap(dtype) -> Optional[int]:
    try:
        if np.issubdtype(dtype, np.integer):
            return int(np.iinfo(dtype).max)
    except Exception:
        pass
    return None


def _is_int(dtype) -> bool:
    return bool(np.issubdtype(dtype, np.integer))


def _is_float(dtype) -> bool:
    return bool(np.issubdtype(dtype, np.inexact)) or (
        _finfo_mant(dtype) is not None)


def _narrows(src, dst) -> bool:
    """True when a convert src->dst can silently lose precision."""
    if np.issubdtype(dst, np.bool_) or np.issubdtype(src, np.bool_):
        return False
    if _is_int(dst) and _is_float(src):
        return True  # drops fractions; only a quantize site may do this
    if _is_int(src) and _is_int(dst):
        si, di = np.iinfo(src), np.iinfo(dst)
        return di.max < si.max or di.min > si.min
    if _is_float(src) and _is_float(dst):
        ms, md = _finfo_mant(src), _finfo_mant(dst)
        return md is not None and ms is not None and md < ms
    return False  # int -> float: lattice-aware check handled separately


# ------------------------------------------------------------------ events


@dataclasses.dataclass(frozen=True)
class QuantSite:
    """A bounded float->int (or narrowing int->int) convert: the traced
    truth of one quantization point on the wire lattice."""

    sid: int
    dtype: str                     # target integer dtype
    shape: Tuple[int, ...]
    size: int
    start_offset: int              # cumulative grad-path element offset
                                   # (the bucketed wire's flat coords)
    peak: Optional[float]          # clamp bound carried into the convert
    pre_peak: Optional[float]      # worst-case |value| BEFORE the clamp
                                   # (None: unbounded / unknown)
    roots: FrozenSet[int]          # max-abs reductions its scale chain saw
    primary: bool                  # quantizes fresh float (not a requant
                                   # of lattice payload: EF tracks these)
    conservative: bool             # inside a loop body
    feeds_params: bool = False


@dataclasses.dataclass(frozen=True)
class DequantEvent:
    """A multiply (or divide) of lattice payload by a scale, leaving the
    integer lattice: the point PSC111 audits for scale provenance."""

    did: int
    payload_sites: FrozenSet[int]
    scale_roots: FrozenSet[int]
    scale_literal: bool            # scale is a static constant
    conservative: bool
    feeds_params: bool = False


@dataclasses.dataclass(frozen=True)
class AccumEvent:
    """One integer accumulation (psum / psum_scatter / reduce_sum /
    narrowing convert / int->float mantissa exit) with its traced
    worst-case |sum| against the dtype's capacity."""

    kind: str                      # psum|psum_scatter|reduce_sum|convert
                                   # |mantissa
    dtype: str                     # accumulator / target dtype
    axes: Tuple[str, ...]          # collective axes ('' ops: empty)
    multiplier: Optional[int]      # summand count (None: unknown axis)
    peak_in: Optional[float]
    peak_out: Optional[float]
    capacity: Optional[int]
    lattice: bool                  # payload descends from a quant site
    conservative: bool
    feeds_params: bool = False


@dataclasses.dataclass(frozen=True)
class NarrowEvent:
    """A precision-narrowing convert_element_type (PSC114 raw material:
    the rule flags the ones downstream of the gradient reduce, on the
    update path, that are not declared quantize sites or allowances)."""

    src: str
    dst: str
    is_quant_site: bool
    downstream_of_reduce: bool
    conservative: bool
    feeds_params: bool = False


@dataclasses.dataclass(frozen=True)
class ResidualEvent:
    """A subtract whose subtrahend descends from a dequantization —
    the grad - dequant(quant(grad)) error-feedback residual shape."""

    rid: int
    covered_sites: FrozenSet[int]  # primary quant sites this closes
                                   # (minuend proven an ancestor-sharer)
    feeds_carry: bool              # reaches a non-param step output
    feeds_params: bool             # double-count hazard when True
    conservative: bool


@dataclasses.dataclass
class NumericsReport:
    """The full precision-flow record for one traced step."""

    sites: Tuple[QuantSite, ...]
    dequants: Tuple[DequantEvent, ...]
    accums: Tuple[AccumEvent, ...]
    narrows: Tuple[NarrowEvent, ...]
    residuals: Tuple[ResidualEvent, ...]
    axis_sizes: Dict[str, int]

    def grad_sites(self) -> List[QuantSite]:
        return [s for s in self.sites if s.feeds_params]


# ------------------------------------------------------------------- state


class _St:
    """Abstract value: interval + provenance. Mutated never; copied via
    ``_evolve``."""

    __slots__ = ("lo", "hi", "roots", "sites", "deqs", "is_abs", "pre",
                 "post", "tainted")

    def __init__(self, lo=None, hi=None, roots=_EMPTY, sites=_EMPTY,
                 deqs=_EMPTY, is_abs=False, pre=None, post=False,
                 tainted=False):
        self.lo = lo
        self.hi = hi
        self.roots = roots
        self.sites = sites
        self.deqs = deqs
        self.is_abs = is_abs
        self.pre = pre
        self.post = post
        self.tainted = tainted

    def peak(self) -> Optional[float]:
        if self.lo is None or self.hi is None:
            return None
        return max(abs(self.lo), abs(self.hi))


def _union(ins: Sequence[_St], lo=None, hi=None, is_abs=False,
           pre=None) -> _St:
    roots = _EMPTY
    sites = _EMPTY
    deqs = _EMPTY
    post = False
    tainted = False
    for s in ins:
        roots |= s.roots
        sites |= s.sites
        deqs |= s.deqs
        post = post or s.post
        tainted = tainted or s.tainted
    return _St(lo=lo, hi=hi, roots=roots, sites=sites, deqs=deqs,
               is_abs=is_abs, pre=pre, post=post, tainted=tainted)


def _join(a: _St, b: _St) -> _St:
    """Least upper bound: interval hull + provenance union."""
    lo = None if (a.lo is None or b.lo is None) else min(a.lo, b.lo)
    hi = None if (a.hi is None or b.hi is None) else max(a.hi, b.hi)
    pre = None if (a.pre is None or b.pre is None) else max(a.pre, b.pre)
    return _St(lo=lo, hi=hi, roots=a.roots | b.roots,
               sites=a.sites | b.sites, deqs=a.deqs | b.deqs,
               is_abs=a.is_abs and b.is_abs, pre=pre,
               post=a.post or b.post, tainted=a.tainted or b.tainted)


def _taint(s: _St) -> _St:
    """Loop-carry widening: keep provenance, drop every proven bound."""
    return _St(lo=None, hi=None, roots=s.roots, sites=s.sites,
               deqs=s.deqs, is_abs=False, pre=None, post=s.post,
               tainted=True)


def _prov_eq(a: _St, b: _St) -> bool:
    return (a.roots == b.roots and a.sites == b.sites and a.deqs == b.deqs
            and a.post == b.post)


def _scalar_of(s: _St) -> Optional[float]:
    """The statically-known scalar value, when the interval is a point."""
    if s.lo is not None and s.lo == s.hi:
        return s.lo
    return None


# ---------------------------------------------------------------- analyzer


class _Analyzer:
    def __init__(self, axis_sizes: Optional[Dict[str, int]] = None):
        self.axis_sizes: Dict[str, int] = dict(axis_sizes or {})
        self._forced_axes = frozenset(self.axis_sizes)
        self._preds: List[List[int]] = [[]]  # node 0: external constants
        self._sid = itertools.count()
        self._did = itertools.count()
        self._rid = itertools.count()
        self.sites: List[QuantSite] = []
        self._site_node: Dict[int, int] = {}
        self.dequants: List[DequantEvent] = []
        self._deq_node: Dict[int, int] = {}
        self._deq_payload: Dict[int, FrozenSet[int]] = {}
        self.accums: List[AccumEvent] = []
        self._accum_node: List[int] = []
        self.narrows: List[NarrowEvent] = []
        self._narrow_node: List[int] = []
        self.residuals: List[dict] = []   # resolved in finalize()
        self._loop_depth = 0
        self._anc_cache: Dict[int, FrozenSet[int]] = {}

    # -- graph ----------------------------------------------------------

    def _new_node(self, preds: Sequence[int]) -> int:
        self._preds.append(list(dict.fromkeys(preds)))
        return len(self._preds) - 1

    def _ancestors(self, starts: Sequence[int]) -> FrozenSet[int]:
        seen: set = set()
        stack = list(starts)
        while stack:
            n = stack.pop()
            if n in seen:
                continue
            seen.add(n)
            stack.extend(self._preds[n])
        return frozenset(seen)

    def _anc_of(self, node: int) -> FrozenSet[int]:
        got = self._anc_cache.get(node)
        if got is None:
            got = self._ancestors([node])
            self._anc_cache[node] = got
        return got

    # -- literal / const states ----------------------------------------

    def _const_state(self, val) -> _St:
        try:
            arr = np.asarray(val)
            if arr.size and arr.size <= 4096 and (
                np.issubdtype(arr.dtype, np.number)
                or np.issubdtype(arr.dtype, np.bool_)
            ):
                a = arr.astype(np.float64)
                if np.all(np.isfinite(a)):
                    return _St(lo=float(a.min()), hi=float(a.max()))
        except Exception:
            pass
        return _St()

    def _get(self, env, v) -> Tuple[_St, int]:
        if _is_var(v):
            got = env.get(v)
            if got is None:
                return _St(), 0  # untracked (e.g. dropvar reuse): unknown
            return got
        return self._const_state(v.val), 0

    # -- main recursion -------------------------------------------------

    def run_closed(self, closed) -> List[Tuple[_St, int]]:
        jaxpr = _open(closed)
        env: Dict[Any, Tuple[_St, int]] = {}
        for cv, cval in zip(jaxpr.constvars,
                            getattr(closed, "consts", ()) or ()):
            env[cv] = (self._const_state(cval), self._new_node([]))
        for cv in jaxpr.constvars:
            if cv not in env:
                env[cv] = (_St(), self._new_node([]))
        for iv in jaxpr.invars:
            env[iv] = (_St(), self._new_node([]))
        self._run(jaxpr, env, record=True)
        return [self._get(env, ov) for ov in jaxpr.outvars]

    def _bind_closed(self, sub, env: Dict[Any, Tuple[_St, int]]) -> Any:
        """Bind a ClosedJaxpr's constvars into env; return the open
        jaxpr."""
        inner = _open(sub)
        for cv, cval in zip(inner.constvars,
                            getattr(sub, "consts", ()) or ()):
            env[cv] = (self._const_state(cval), 0)
        for cv in inner.constvars:
            if cv not in env:
                env[cv] = (_St(), 0)
        return inner

    def _run(self, jaxpr, env: Dict[Any, Tuple[_St, int]],
             record: bool) -> None:
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in _EXACT_CALLS:
                self._exact_call(eqn, env, record)
            elif name == "scan":
                self._scan(eqn, env, record)
            elif name == "while":
                self._while(eqn, env, record)
            elif name == "cond":
                self._cond(eqn, env, record)
            else:
                self._eqn(eqn, env, record)

    def _exact_call(self, eqn, env, record: bool) -> None:
        name = eqn.primitive.name
        sub = None
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            sub = eqn.params.get(key)
            if sub is not None:
                break
        if sub is None:
            self._eqn(eqn, env, record)
            return
        if name == "shard_map":
            mesh = eqn.params.get("mesh")
            shape = getattr(mesh, "shape", None)
            if shape:
                for ax, size in dict(shape).items():
                    if str(ax) not in self._forced_axes:
                        self.axis_sizes[str(ax)] = int(size)
        inner_env: Dict[Any, Tuple[_St, int]] = {}
        inner = self._bind_closed(sub, inner_env)
        # walker convention: invars map 1:1, zipped from the END so
        # leading const-style invars of open jaxprs stay aligned
        n = min(len(eqn.invars), len(inner.invars))
        if n:
            for iv in inner.invars[:-n]:
                inner_env[iv] = (_St(), 0)
            for ov, iv in zip(eqn.invars[-n:], inner.invars[-n:]):
                inner_env[iv] = self._get(env, ov)
        else:
            for iv in inner.invars:
                inner_env[iv] = (_St(), 0)
        self._run(inner, inner_env, record)
        for ov, sv in zip(eqn.outvars, inner.outvars):
            if _is_var(ov):
                env[ov] = self._get(inner_env, sv)

    def _loop_body(self, body_closed, const_in, carry_in, xs_in, record):
        """Fixpoint a loop body: provenance grows to a fixed point with
        carry bounds dropped; events are recorded on the final pass."""
        inner_env: Dict[Any, Tuple[_St, int]] = {}
        body = self._bind_closed(body_closed, inner_env)
        carry = [_taint(s) for s, _ in carry_in]
        region = self._new_node(
            [n for _, n in list(const_in) + list(carry_in) + list(xs_in)]
        )
        ncarry = len(carry_in)
        for _ in range(4):
            env_i = dict(inner_env)
            vals = (list(const_in)
                    + [(c, region) for c in carry]
                    + [(s, n) for s, n in xs_in])
            for iv, v in zip(body.invars, vals):
                env_i[iv] = v
            self._run(body, env_i, record=False)
            outs = [self._get(env_i, ov) for ov in body.outvars]
            new_carry = [_join(c, _taint(o)) for c, (o, _) in
                         zip(carry, outs[:ncarry])]
            if all(_prov_eq(c, n2) for c, n2 in zip(carry, new_carry)):
                carry = new_carry
                break
            carry = new_carry
        # final recording pass
        self._loop_depth += 1
        env_f = dict(inner_env)
        vals = (list(const_in)
                + [(c, region) for c in carry]
                + [(s, n) for s, n in xs_in])
        for iv, v in zip(body.invars, vals):
            env_f[iv] = v
        self._run(body, env_f, record=record)
        self._loop_depth -= 1
        outs = [self._get(env_f, ov) for ov in body.outvars]
        # close the cycle: carry outputs feed the region node
        self._preds[region].extend(n for _, n in outs[:ncarry])
        return outs, region

    def _scan(self, eqn, env, record: bool) -> None:
        nconsts = eqn.params.get("num_consts", 0)
        ncarry = eqn.params.get("num_carry", 0)
        ins = [self._get(env, v) for v in eqn.invars]
        const_in = ins[:nconsts]
        carry_in = ins[nconsts:nconsts + ncarry]
        xs_in = ins[nconsts + ncarry:]
        outs, region = self._loop_body(
            eqn.params["jaxpr"], const_in, carry_in, xs_in, record
        )
        for i, ov in enumerate(eqn.outvars):
            if not _is_var(ov):
                continue
            if i < len(outs):
                st, node = outs[i]
                if i < ncarry:
                    st = _taint(st)  # the carried-out iterate
                env[ov] = (st, node)
            else:
                env[ov] = (_St(tainted=True), region)

    def _while(self, eqn, env, record: bool) -> None:
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        ins = [self._get(env, v) for v in eqn.invars]
        cond_consts = ins[:cn]
        body_consts = ins[cn:cn + bn]
        carry_in = ins[cn + bn:]
        outs, region = self._loop_body(
            eqn.params["body_jaxpr"], body_consts, carry_in, [], record
        )
        # run the cond once for event coverage (tainted carry)
        cond_env: Dict[Any, Tuple[_St, int]] = {}
        cond = self._bind_closed(eqn.params["cond_jaxpr"], cond_env)
        vals = list(cond_consts) + [(_taint(s), region)
                                    for s, _ in carry_in]
        self._loop_depth += 1
        for iv, v in zip(cond.invars, vals):
            cond_env[iv] = v
        self._run(cond, cond_env, record=record)
        self._loop_depth -= 1
        for i, ov in enumerate(eqn.outvars):
            if not _is_var(ov):
                continue
            if i < len(outs):
                st, node = outs[i]
                env[ov] = (_taint(st), node)
            else:
                env[ov] = (_St(tainted=True), region)

    def _cond(self, eqn, env, record: bool) -> None:
        branches = eqn.params.get("branches", ()) or ()
        operands = [self._get(env, v) for v in eqn.invars[1:]]
        joined: List[Optional[Tuple[_St, List[int]]]] = None
        for br in branches:
            br_env: Dict[Any, Tuple[_St, int]] = {}
            inner = self._bind_closed(br, br_env)
            for iv, v in zip(inner.invars, operands):
                br_env[iv] = v
            self._run(inner, br_env, record)
            outs = [self._get(br_env, ov) for ov in inner.outvars]
            if joined is None:
                joined = [(st, [node]) for st, node in outs]
            else:
                joined = [
                    (_join(a, st), nodes + [node])
                    for (a, nodes), (st, node) in zip(joined, outs)
                ]
        for i, ov in enumerate(eqn.outvars):
            if not _is_var(ov):
                continue
            if joined is not None and i < len(joined):
                st, nodes = joined[i]
                env[ov] = (st, self._new_node(nodes))
            else:
                env[ov] = (_St(), 0)

    # -- per-primitive transfer ----------------------------------------

    def _axis_mult(self, eqn) -> Optional[int]:
        ax = eqn.params.get("axes", None)
        if ax is None:
            ax = eqn.params.get("axis_name", None)
        if ax is None:
            return None
        if not isinstance(ax, (tuple, list)):
            ax = (ax,)
        mult = 1
        for a in ax:
            size = self.axis_sizes.get(str(a))
            if size is None:
                return None
            mult *= size
        return mult

    def _eqn_axes(self, eqn) -> Tuple[str, ...]:
        ax = eqn.params.get("axes", None)
        if ax is None:
            ax = eqn.params.get("axis_name", None)
        if ax is None:
            return ()
        if not isinstance(ax, (tuple, list)):
            ax = (ax,)
        return tuple(str(a) for a in ax)

    def _eqn(self, eqn, env, record: bool) -> None:
        name = eqn.primitive.name
        ins = [self._get(env, v) for v in eqn.invars]
        sts = [s for s, _ in ins]
        node = self._new_node([n for _, n in ins])
        self._in_nodes = [n for _, n in ins]
        conservative = self._loop_depth > 0
        out_dtype = None
        if eqn.outvars and hasattr(eqn.outvars[0], "aval"):
            aval = eqn.outvars[0].aval
            out_dtype = getattr(aval, "dtype", None)

        st = self._transfer(name, eqn, sts, out_dtype, node, record,
                            conservative)

        outs = eqn.outvars
        if name == "optimization_barrier" and len(outs) == len(sts):
            for ov, s in zip(outs, sts):
                if _is_var(ov):
                    env[ov] = (s, node)
            return
        for ov in outs:
            if _is_var(ov):
                env[ov] = (st, node)

    def _transfer(self, name, eqn, sts, out_dtype, node, record,
                  conservative) -> _St:
        s0 = sts[0] if sts else _St()

        if name == "convert_element_type":
            return self._convert(eqn, s0, out_dtype, node, record,
                                 conservative)

        if name in ("add", "add_any"):
            a, b = sts[0], sts[1]
            lo = None if (a.lo is None or b.lo is None) else a.lo + b.lo
            hi = None if (a.hi is None or b.hi is None) else a.hi + b.hi
            out = _union(sts, lo=lo, hi=hi)
            if (record and out_dtype is not None and _is_int(out_dtype)
                    and out.sites):
                cap = _int_cap(out_dtype)
                self.accums.append(AccumEvent(
                    kind="add", dtype=str(out_dtype), axes=(),
                    multiplier=2,
                    peak_in=max(p for p in (a.peak(), b.peak())
                                if p is not None)
                    if (a.peak() is not None or b.peak() is not None)
                    else None,
                    peak_out=out.peak(), capacity=cap,
                    lattice=True, conservative=conservative))
                self._accum_node.append(node)
            return out

        if name == "sub":
            a, b = sts[0], sts[1]
            lo = None if (a.lo is None or b.hi is None) else a.lo - b.hi
            hi = None if (a.hi is None or b.lo is None) else a.hi - b.lo
            out = _union(sts, lo=lo, hi=hi)
            if record and b.deqs:
                # the error-feedback residual shape: minuend - dequant(...)
                cand = _EMPTY
                for d in b.deqs:
                    cand |= self._deq_payload.get(d, _EMPTY)
                self.residuals.append({
                    "rid": next(self._rid),
                    "cand": cand,
                    "minuend_node": self._in_nodes[0],
                    "node": node,
                    "conservative": conservative,
                })
            return out

        if name == "mul":
            return self._mul(sts, out_dtype, node, record, conservative)

        if name == "div":
            return self._div(sts, out_dtype, node, record, conservative)

        if name == "neg":
            lo = None if s0.hi is None else -s0.hi
            hi = None if s0.lo is None else -s0.lo
            return _union(sts, lo=lo, hi=hi)

        if name in ("abs", "sign"):
            if name == "sign":
                return _union(sts, lo=-1.0, hi=1.0)
            p = s0.peak()
            return _union(sts, lo=0.0, hi=p, is_abs=True)

        if name in ("max", "min"):
            a, b = sts[0], sts[1]
            ka, kb = _scalar_of(a), _scalar_of(b)
            if name == "max":
                lo = (max(x for x in (a.lo, b.lo) if x is not None)
                      if (a.lo is not None or b.lo is not None) else None)
                hi = (None if (a.hi is None or b.hi is None)
                      else max(a.hi, b.hi))
            else:
                lo = (None if (a.lo is None or b.lo is None)
                      else min(a.lo, b.lo))
                hi = (min(x for x in (a.hi, b.hi) if x is not None)
                      if (a.hi is not None or b.hi is not None) else None)
            # clamp: remember the unclamped operand's peak for the
            # saturation check at the eventual requant convert
            pre = None
            if ka is not None and kb is None:
                pre = b.pre if b.pre is not None else b.peak()
            elif kb is not None and ka is None:
                pre = a.pre if a.pre is not None else a.peak()
            out = _union(sts, lo=lo, hi=hi, pre=pre)
            out.is_abs = any(s.is_abs for s in sts)
            return out

        if name == "clamp":
            lo_b, x, hi_b = sts[0], sts[1], sts[2]
            klo, khi = _scalar_of(lo_b), _scalar_of(hi_b)
            pre = x.pre if x.pre is not None else x.peak()
            return _union([x], lo=klo, hi=khi, pre=pre)

        if name in ("round", "floor", "ceil", "nearbyint"):
            out = _union(sts, lo=s0.lo, hi=s0.hi, pre=s0.pre)
            out.is_abs = s0.is_abs
            return out

        if name in ("reduce_max", "pmax"):
            out = _union(sts, lo=s0.lo, hi=s0.hi)
            out.is_abs = s0.is_abs
            if name == "reduce_max" and s0.is_abs:
                # a max-abs reduction: mint a scale-provenance root
                # (-1 on fixpoint passes keeps the iterate stable)
                out.roots = out.roots | {node if record else -1}
            return out

        if name in ("reduce_min", "pmin"):
            out = _union(sts, lo=s0.lo, hi=s0.hi)
            out.is_abs = s0.is_abs
            return out

        if name in ("reduce_sum", "cumsum"):
            axes = eqn.params.get("axes", ())
            in_aval = getattr(eqn.invars[0], "aval", None)
            mult = 1
            if name == "cumsum":
                ax = eqn.params.get("axis", 0)
                axes = (ax,)
            if in_aval is not None and hasattr(in_aval, "shape"):
                for a in axes:
                    mult *= int(in_aval.shape[a])
            else:
                mult = None
            return self._summed(sts, s0, mult, (), "reduce_sum",
                                out_dtype, node, record, conservative)

        if name in ("psum", "psum_scatter", "reduce_scatter"):
            mult = self._axis_mult(eqn)
            out = self._summed(
                sts, s0, mult, self._eqn_axes(eqn),
                "psum" if name == "psum" else "psum_scatter",
                out_dtype, node, record, conservative)
            out.post = True
            return out

        if name in ("all_gather", "all_to_all", "ppermute", "pshuffle"):
            out = _union(sts, lo=s0.lo, hi=s0.hi)
            if name == "all_to_all":
                out.post = True
            return out

        if name in ("reshape", "squeeze", "expand_dims",
                    "broadcast_in_dim", "transpose", "rev", "slice",
                    "dynamic_slice", "gather", "copy", "stop_gradient"):
            out = _union(sts[:1], lo=s0.lo, hi=s0.hi, pre=s0.pre)
            out.is_abs = s0.is_abs
            return out

        if name == "concatenate":
            out = sts[0]
            for s in sts[1:]:
                out = _join(out, s)
            return out

        if name == "pad":
            return _join(sts[0], sts[1])

        if name == "dynamic_update_slice":
            return _join(sts[0], sts[1])

        if name == "select_n":
            cases = sts[1:] if len(sts) > 1 else sts
            out = cases[0]
            for s in cases[1:]:
                out = _join(out, s)
            return out

        if name in ("gt", "lt", "ge", "le", "eq", "ne", "and", "or",
                    "not", "xor", "is_finite", "reduce_and", "reduce_or"):
            return _union(sts, lo=0.0, hi=1.0)

        if name == "integer_pow":
            y = eqn.params.get("y", None)
            p = s0.peak()
            if y is not None and p is not None and y >= 0:
                hi = float(p) ** int(y)
                lo = 0.0 if int(y) % 2 == 0 else -hi
                return _union(sts, lo=lo, hi=hi)
            return _union(sts)

        if name in ("iota", "rng_bit_generator", "random_bits",
                    "random_seed", "random_wrap", "random_fold_in"):
            return _St()

        if name in ("dot_general", "conv_general_dilated"):
            # fold-style dequantization (serve attention): a float
            # contraction of int-lattice payload against an operand that
            # already carries the scale row (root provenance) IS the
            # point where the payload leaves the lattice — audit it as a
            # dequant; with no scale in sight the payload flows on and a
            # later elementwise scale multiply is the dequant
            a, b = sts[0], sts[1]
            payload = other = None
            if a.sites and not b.sites:
                payload, other = a, b
            elif b.sites and not a.sites:
                payload, other = b, a
            if (payload is not None and other.roots
                    and out_dtype is not None and _is_float(out_dtype)):
                did = next(self._did) if record else -1
                if record:
                    self.dequants.append(DequantEvent(
                        did=did, payload_sites=payload.sites,
                        scale_roots=other.roots, scale_literal=False,
                        conservative=conservative))
                    self._deq_node[did] = node
                    self._deq_payload[did] = payload.sites
                out = _union(sts)
                out.sites = _EMPTY
                out.deqs = out.deqs | {did}
                out.lo = out.hi = None
                return out
            return _union(sts)

        # default: provenance union, bounds unknown
        return _union(sts)

    def _summed(self, sts, s0, mult, axes, kind, out_dtype, node, record,
                conservative) -> _St:
        if mult is not None and s0.lo is not None and s0.hi is not None:
            lo = min(s0.lo * mult, s0.hi * mult)
            hi = max(s0.lo * mult, s0.hi * mult)
        else:
            lo = hi = None
        out = _union(sts, lo=lo, hi=hi)
        if record and out_dtype is not None and _is_int(out_dtype):
            self.accums.append(AccumEvent(
                kind=kind, dtype=str(out_dtype), axes=tuple(axes),
                multiplier=mult, peak_in=s0.peak(),
                peak_out=(None if hi is None else max(abs(lo), abs(hi))),
                capacity=_int_cap(out_dtype),
                lattice=bool(s0.sites), conservative=conservative))
            self._accum_node.append(node)
        elif (record and out_dtype is not None and _is_float(out_dtype)
              and s0.sites):
            # float psum of lattice payload: mantissa capacity applies
            mant = _finfo_mant(out_dtype)
            cap = (1 << mant) if mant else None
            self.accums.append(AccumEvent(
                kind=kind, dtype=str(out_dtype), axes=tuple(axes),
                multiplier=mult, peak_in=s0.peak(),
                peak_out=(None if hi is None else max(abs(lo), abs(hi))),
                capacity=cap, lattice=True, conservative=conservative))
            self._accum_node.append(node)
        return out

    def _mul(self, sts, out_dtype, node, record, conservative) -> _St:
        a, b = sts[0], sts[1]
        # dequantization: lattice payload x scale, leaving the lattice
        payload = None
        other = None
        if a.sites and not b.sites:
            payload, other = a, b
        elif b.sites and not a.sites:
            payload, other = b, a
        if payload is not None and _scalar_of(other) is not None:
            # multiply by a STATIC scalar: an exact rescale (softmax
            # temperature, gain) — the payload stays on the lattice;
            # only a traced (data-dependent) scale can dequantize
            k = _scalar_of(other)
            lo = hi = None
            if payload.lo is not None and payload.hi is not None:
                lo, hi = sorted((payload.lo * k, payload.hi * k))
            out = _union(sts, lo=lo, hi=hi,
                         pre=(None if payload.pre is None
                              else payload.pre * abs(k)))
            out.is_abs = payload.is_abs and k > 0
            return out
        if (payload is not None and out_dtype is not None
                and _is_float(out_dtype)
                and _scalar_of(other) is None):
            did = next(self._did) if record else -1
            if record:
                self.dequants.append(DequantEvent(
                    did=did, payload_sites=payload.sites,
                    scale_roots=other.roots,
                    scale_literal=(_scalar_of(other) is not None
                                   and not other.roots),
                    conservative=conservative))
                self._deq_node[did] = node
                self._deq_payload[did] = payload.sites
            out = _union(sts)
            out.sites = _EMPTY
            out.deqs = out.deqs | {did}
            out.lo = out.hi = None
            return out
        # interval product
        lo = hi = None
        if (a.lo is not None and a.hi is not None and b.lo is not None
                and b.hi is not None):
            prods = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
            lo, hi = min(prods), max(prods)
        out = _union(sts, lo=lo, hi=hi)
        if (record and out_dtype is not None and _is_int(out_dtype)
                and out.sites and hi is None):
            # integer lattice product with unknown bound: capacity
            # becomes unprovable downstream; surface it here
            self.accums.append(AccumEvent(
                kind="mul", dtype=str(out_dtype), axes=(),
                multiplier=None, peak_in=None, peak_out=None,
                capacity=_int_cap(out_dtype), lattice=True,
                conservative=conservative))
            self._accum_node.append(node)
        return out

    def _div(self, sts, out_dtype, node, record, conservative) -> _St:
        a, b = sts[0], sts[1]
        k = _scalar_of(b)
        if k is not None and k != 0.0:
            lo = hi = None
            if a.lo is not None and a.hi is not None:
                q = sorted((a.lo / k, a.hi / k))
                lo, hi = q
            out = _union([a], lo=lo, hi=hi)
            out.is_abs = a.is_abs
            out.roots = a.roots | b.roots
            return out
        if (a.sites and not b.sites and out_dtype is not None
                and _is_float(out_dtype)):
            # dequant spelled as payload / inv_scale
            did = next(self._did) if record else -1
            if record:
                self.dequants.append(DequantEvent(
                    did=did, payload_sites=a.sites, scale_roots=b.roots,
                    scale_literal=False, conservative=conservative))
                self._deq_node[did] = node
                self._deq_payload[did] = a.sites
            out = _union(sts)
            out.sites = _EMPTY
            out.deqs = out.deqs | {did}
            out.lo = out.hi = None
            return out
        return _union(sts)

    def _convert(self, eqn, s0, out_dtype, node, record,
                 conservative) -> _St:
        in_aval = getattr(eqn.invars[0], "aval", None)
        src = getattr(in_aval, "dtype", None)
        if src is None or out_dtype is None:
            return _union([s0])
        out = _union([s0], lo=s0.lo, hi=s0.hi, pre=s0.pre)
        out.is_abs = s0.is_abs
        narrowing = _narrows(src, out_dtype)
        peak = s0.peak()
        if peak is None and _is_int(src):
            # an integer source has intrinsic dtype bounds even when the
            # dataflow bound is unknown (external int8 pool args)
            ii = np.iinfo(np.dtype(src))
            out.lo, out.hi = float(ii.min), float(ii.max)
            peak = float(max(abs(ii.min), ii.max))

        if _is_int(out_dtype) and (_is_float(src) or
                                   (_is_int(src) and narrowing)):
            cap = _int_cap(out_dtype)
            lattice_dtype = np.dtype(out_dtype).itemsize <= 2
            if peak is not None and cap is not None and peak <= cap:
                if not lattice_dtype:
                    # bounded cast into a wide int (index math, counters)
                    # — provably exact, not a quantization event
                    return out
                if (_scalar_of(s0) is not None and not s0.roots
                        and not s0.sites):
                    # a STATIC constant cast onto the lattice (zero
                    # init, padding) — provably exact, not a site
                    return out
                # a bounded narrowing convert onto the wire lattice:
                # a quantization site
                if record:
                    sid = next(self._sid)
                    shape = tuple(
                        int(d) for d in getattr(in_aval, "shape", ())
                    )
                    size = 1
                    for d in shape:
                        size *= d
                    self.sites.append(QuantSite(
                        sid=sid, dtype=str(out_dtype), shape=shape,
                        size=size, start_offset=0,  # set in finalize
                        peak=peak,
                        pre_peak=s0.pre,
                        roots=s0.roots,
                        primary=not s0.sites,
                        conservative=conservative))
                    self._site_node[sid] = node
                    out.sites = out.sites | {sid}
                else:
                    out.sites = out.sites | {-1}
            else:
                if record:
                    self.narrows.append(NarrowEvent(
                        src=str(src), dst=str(out_dtype),
                        is_quant_site=False,
                        downstream_of_reduce=s0.post,
                        conservative=conservative))
                    self._narrow_node.append(node)
                if (record and peak is not None and cap is not None
                        and peak > cap):
                    self.accums.append(AccumEvent(
                        kind="convert", dtype=str(out_dtype),
                        axes=(), multiplier=1, peak_in=peak,
                        peak_out=peak, capacity=cap,
                        lattice=bool(s0.sites),
                        conservative=conservative))
                    self._accum_node.append(node)
                out.lo = out.hi = None
            return out

        if _is_int(src) and _is_float(out_dtype) and s0.sites:
            # lattice value entering float: exactness needs the mantissa
            mant = _finfo_mant(out_dtype)
            cap = (1 << mant) if mant else None
            if record and (peak is None or (cap is not None
                                            and peak > cap)):
                self.accums.append(AccumEvent(
                    kind="mantissa", dtype=str(out_dtype), axes=(),
                    multiplier=1, peak_in=peak, peak_out=peak,
                    capacity=cap, lattice=True,
                    conservative=conservative))
                self._accum_node.append(node)
            return out

        if narrowing:
            if record:
                self.narrows.append(NarrowEvent(
                    src=str(src), dst=str(out_dtype),
                    is_quant_site=False,
                    downstream_of_reduce=s0.post,
                    conservative=conservative))
                self._narrow_node.append(node)
        return out

    # -- finalize -------------------------------------------------------

    def finalize(self, out_states: List[Tuple[_St, int]],
                 param_out_indices: Optional[Sequence[int]]
                 ) -> NumericsReport:
        n_out = len(out_states)
        param_set = set(param_out_indices or range(n_out))
        param_nodes = [node for i, (_, node) in enumerate(out_states)
                       if i in param_set]
        nonparam_nodes = [node for i, (_, node) in enumerate(out_states)
                          if i not in param_set]
        anc_params = self._ancestors(param_nodes)
        anc_nonparams = self._ancestors(nonparam_nodes)

        sites: List[QuantSite] = []
        offset = 0
        for s in self.sites:
            feeds = self._site_node[s.sid] in anc_params
            s = dataclasses.replace(s, feeds_params=feeds,
                                    start_offset=offset)
            if feeds and s.primary:
                offset += s.size
            sites.append(s)
        dequants = [
            dataclasses.replace(
                d, feeds_params=self._deq_node[d.did] in anc_params)
            for d in self.dequants
        ]
        accums = [
            dataclasses.replace(a, feeds_params=node in anc_params)
            for a, node in zip(self.accums, self._accum_node)
        ]
        narrows = [
            dataclasses.replace(nv, feeds_params=node in anc_params)
            for nv, node in zip(self.narrows, self._narrow_node)
        ]
        residuals: List[ResidualEvent] = []
        for r in self.residuals:
            covered = {
                sid for sid in r["cand"]
                if sid in self._site_node
                and r["minuend_node"] in self._anc_of(
                    self._site_node[sid])
            }
            if covered:
                # recomputed-transform EF (collectives.
                # local_quantized_contribution): the residual round-trips
                # a RE-quantization of the value the wire quantized —
                # bit-identical by construction but a separate set of
                # eqns, so the wire's own site is not in the subtrahend.
                # Extend coverage to sites quantizing the SAME minuend
                # with the SAME geometry: the minuend-ancestry check ties
                # both to one source value, the (dtype, shape) match ties
                # them to one transform.
                geom = {(self.sites[sid].dtype, self.sites[sid].shape)
                        for sid in covered}
                covered |= {
                    s.sid for s in self.sites
                    if s.sid not in covered
                    and (s.dtype, s.shape) in geom
                    and r["minuend_node"] in self._anc_of(
                        self._site_node[s.sid])
                }
            residuals.append(ResidualEvent(
                rid=r["rid"], covered_sites=frozenset(covered),
                feeds_carry=r["node"] in anc_nonparams,
                feeds_params=r["node"] in anc_params,
                conservative=r["conservative"]))
        return NumericsReport(
            sites=tuple(sites), dequants=tuple(dequants),
            accums=tuple(accums), narrows=tuple(narrows),
            residuals=tuple(residuals),
            axis_sizes=dict(self.axis_sizes))


def analyze_numerics(
    closed_jaxpr,
    param_out_indices: Optional[Sequence[int]] = None,
    axis_sizes: Optional[Dict[str, int]] = None,
) -> NumericsReport:
    """Run the precision-flow analysis over a traced ClosedJaxpr.

    ``param_out_indices``: flat output positions of the updated params
    (None: every output counts as params — fully conservative).
    ``axis_sizes``: mesh-axis sizes for collectives traced OUTSIDE a
    shard_map (e.g. a ``jax.make_jaxpr(..., axis_env=...)`` trace);
    sizes discovered from shard_map eqns are merged in automatically,
    with the explicit entries winning.
    """
    an = _Analyzer(axis_sizes=axis_sizes)
    outs = an.run_closed(closed_jaxpr)
    return an.finalize(outs, param_out_indices)
