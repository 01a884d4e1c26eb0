"""pscheck rules PSC101-PSC105: contract checks over a traced step.

| rule   | guards against                                                  |
|--------|-----------------------------------------------------------------|
| PSC101 | a declared mesh axis no collective consumes (dead parallelism — |
|        | e.g. a dropped dp reduction), or a collective riding an axis    |
|        | the scheme never declared                                       |
| PSC102 | a gradient reduction that no longer feeds the optimizer: for    |
|        | each axis with replicated gradient leaves, a reduce of the      |
|        | declared kind must be an ancestor of the updated params (the    |
|        | ARCHITECTURE §2 recipe, checked by jaxpr dataflow — a metrics   |
|        | pmean over the same axis does NOT count)                        |
| PSC103 | wire-dtype regressions on compressed paths: with an int8 wire   |
|        | declared, every collective on those axes must carry int8 except |
|        | the explicitly-allowed scale rows / metrics / update gathers    |
| PSC104 | silent wire-byte drift: the full per-collective accounting      |
|        | (kind, axes, dtype, count, bytes) must round-trip against the   |
|        | committed runs/comm_contract.json                               |
| PSC105 | dropped donation: every donated input must survive lowering as  |
|        | a donor/alias mark, and its output partner must match in        |
|        | structure/shape/dtype (mismatch = XLA silently un-donates)      |
| PSC106 | silent de-fusion on a bucketed wire: a scheme declaring a       |
|        | FusionSpec may emit at most per_bucket * ceil(payload_bytes /   |
|        | bucket_bytes) + slack reduce-kind collectives feeding the       |
|        | updated params — a refactor quietly going back to one           |
|        | collective per pytree leaf fails the gate                       |
| PSC107 | serving hot-path regressions: a step declaring a ServePolicy    |
|        | (the slot-parallel decode step, serve/engine.py) must emit ZERO |
|        | collectives, and its KV pool must honor the declared storage    |
|        | dtype (int8 payload + f32 block scales when quantized; the      |
|        | compute dtype otherwise) — an f32 leaf in a declared-int8 pool  |
|        | is the serving analogue of a PSC103 wire regression             |
| PSC108 | adaptive-mask regressions: a config declaring an AdaptivePolicy |
|        | (traced aggregation count, PSConfig.num_aggregate_min/max) must |
|        | still declare its grad-reduce requirement — so PSC102's         |
|        | dataflow rule keeps pinning the masked reduce — and its         |
|        | gradient-path reduce bytes must stay inside the declared        |
|        | envelope: adaptation reshapes values, never wire bytes          |
| PSC109 | schedule-variance on the pipelined wire: a config declaring an  |
|        | OverlapPolicy (PSConfig.overlap="pipelined") must move EXACTLY  |
|        | the gradient-path reduce bytes of its named serial twin (same   |
|        | bytes, different schedule — pipelining may reorder and split,   |
|        | never grow or shrink the wire), and must really dispatch per    |
|        | bucket: at least n_buckets x per_bucket reduce-kind             |
|        | collectives each feeding the updated params, so the PSC102      |
|        | dataflow guarantee holds PER BUCKET — a "pipelined" config      |
|        | whose wire quietly re-fused into one barrier eqn fails          |
| PSC110 | undeclared host-consensus for adaptive configs: a config        |
|        | declaring an AdaptivePolicy must NAME the host-consensus point  |
|        | (``.consensus``, a package-relative dotted path) that agrees    |
|        | the traced count across processes, and that must resolve in     |
|        | pslint's consensus inventory (lint/diverge.py: a function whose |
|        | return passes through broadcast_one_to_all / process_allgather) |
|        | — an adaptive knob with no consensus point is PR 7's per-host   |
|        | agg_count tear waiting to recur                                 |
| PSC111 | fresh or mismatched scale rows: every dequantize's scale must   |
|        | be a dataflow descendant of the SAME max-abs reduction that     |
|        | produced its quantize's scale, across every hop of the 2round   |
|        | and hier wires (check/numerics.py provenance roots) — a scale   |
|        | minted from a constant or a different reduction would decode    |
|        | the lattice against the wrong dynamic range                     |
| PSC112 | broken error-feedback closure: with error_feedback declared,    |
|        | every primary quantization site on the gradient path must have  |
|        | a residual consumer of the form grad - dequant(quant) whose     |
|        | result feeds the next step's carry (and NOT the updated params  |
|        | too — that double-counts the correction); a dropped residual    |
|        | silently degrades EF-SGD back to biased quantized SGD           |
| PSC113 | integer-accumulation overflow proven from the trace: worst-case |
|        | |sum| bound = clamp peak x product of the traced collective     |
|        | axis sizes (hier = DCN x ICI product) must fit the payload      |
|        | dtype, replacing trust in the config-time ACCUM_CAPACITY table; |
|        | also refuses lattice reductions whose traced dtype is not the   |
|        | declared accumulator (PR 12's widened-payload regression) and   |
|        | homomorphic_rescale divisors that saturate the requant clamp    |
| PSC114 | silent downcast on the update path: every convert_element_type  |
|        | downstream of the gradient reduce that narrows precision and    |
|        | feeds the updated params must be a detected quantization site   |
|        | or a declared NarrowingAllowance — extends PSC103 from policing |
|        | wire dtypes to proving WHERE narrowing may happen at all        |

PSC111-114 read the NumericsReport that check/numerics.py distills from
the same traced jaxpr (TraceResult.numerics, present whenever the spec
declares a NumericsPolicy). Events flagged ``conservative`` crossed a
scan/while carry, where the analyzer widens to unknown — the rules turn
those into explicit "cannot prove" findings rather than passing
vacuously inside a loop body.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

from .core import CheckFinding, TraceResult
from .walker import REDUCE_KINDS

RULE_IDS = ("PSC101", "PSC102", "PSC103", "PSC104", "PSC105", "PSC106",
            "PSC107", "PSC108", "PSC109", "PSC110", "PSC111", "PSC112",
            "PSC113", "PSC114")


def psc101_axes(r: TraceResult) -> List[CheckFinding]:
    declared = set(r.spec.axes)
    used = set()
    for c in r.collectives:
        used.update(c.axes)
    out = []
    for ax in sorted(declared - used):
        out.append(CheckFinding(
            "PSC101", r.spec.name,
            f"declared mesh axis '{ax}' is consumed by no collective "
            f"(dead parallel axis — dropped reduction?)",
        ))
    for ax in sorted(used - declared):
        out.append(CheckFinding(
            "PSC101", r.spec.name,
            f"collective rides undeclared axis '{ax}' "
            f"(declared: {sorted(declared)})",
        ))
    return out


def psc102_grad_reduce(r: TraceResult) -> List[CheckFinding]:
    out = []
    for req in r.spec.grad_reduce:
        hit = any(
            c.feeds_params and req.axis in c.axes and c.kind in req.kinds
            for c in r.collectives
        )
        if not hit:
            near_misses = sorted({
                c.kind for c in r.collectives
                if req.axis in c.axes and c.kind in req.kinds
            })
            hint = (
                " (a matching reduce exists but feeds only non-param "
                "outputs, e.g. metrics)" if near_misses else ""
            )
            out.append(CheckFinding(
                "PSC102", r.spec.name,
                f"no {'/'.join(req.kinds)} over axis '{req.axis}' feeds "
                f"the updated params — replicated gradient leaves are "
                f"not reduced before the optimizer{hint}",
            ))
    return out


def psc103_wire(r: TraceResult) -> List[CheckFinding]:
    wire = r.spec.wire
    if wire is None:
        return []
    out = []
    for c in r.collectives:
        if not set(c.axes) & set(wire.axes):
            continue
        if c.dtype == wire.payload_dtype:
            continue
        allowed = False
        for a in wire.allow:
            if a.kind != c.kind or a.dtype != c.dtype:
                continue
            if a.axes is not None and not set(c.axes) <= set(a.axes):
                continue
            if a.max_bytes is not None and c.bytes > a.max_bytes:
                continue
            allowed = True
            break
        if not allowed:
            out.append(CheckFinding(
                "PSC103", r.spec.name,
                f"{c.kind} over {list(c.axes)} carries {c.dtype} "
                f"({c.bytes} B) on a declared {wire.payload_dtype} wire "
                f"— compression regression (no allowance covers it)",
            ))
    return out


def psc106_fusion(r: TraceResult) -> List[CheckFinding]:
    """Count the reduce-kind collectives on the gradient path (the
    payload-carrying psum / psum_scatter / all_to_all eqns that feed the
    updated params — scale pmax rows, the guard pmin, gathers, and the
    metrics pmean are out of scope) against the declared bucket budget."""
    fu = r.spec.fusion
    if fu is None:
        return []
    got = _grad_reduce_count(r)
    if got <= fu.max_collectives:
        return []
    granularity = (
        "one fused buffer"
        if not fu.bucket_bytes
        else f"{fu.n_buckets} bucket(s) of ~{fu.bucket_bytes} B"
    )
    return [CheckFinding(
        "PSC106", r.spec.name,
        f"{got} gradient-path reduce collectives, but the declared "
        f"bucket plan ({granularity} over {fu.payload_bytes} B payload, "
        f"per_bucket={fu.per_bucket}, slack={fu.slack}) allows at most "
        f"{fu.max_collectives} — the wire has silently de-fused "
        f"(per-leaf collectives crept back in?)",
    )]


def psc107_serve(r: TraceResult) -> List[CheckFinding]:
    """The serving hot path: zero collectives + KV storage dtype policy.

    Collectives are checked at the jaxpr level (named-axis ops): the
    decode step is slot-parallel by construction — weights replicated,
    pool sharded over slots — so ANY collective means training-style
    communication crept into the request loop. The dtype policy walks
    the KV pool arg's leaves by path: ``*_q`` payload / ``*_s`` scale
    rows for a quantized pool, plain K/V in the declared compute dtype
    otherwise."""
    sp = r.spec.serve
    if sp is None:
        return []
    out = []
    for c in r.collectives:
        out.append(CheckFinding(
            "PSC107", r.spec.name,
            f"{c.kind} over {list(c.axes)} [{c.dtype}, {c.bytes} B] on "
            f"the serving hot path — the decode step is slot-parallel "
            f"and must emit zero collectives",
        ))
    for path, dtype in r.kv_leaves:
        if sp.quantized:
            if path.endswith("_q']"):
                want = "int8"
            elif path.endswith("_s']"):
                want = "float32"
            else:
                out.append(CheckFinding(
                    "PSC107", r.spec.name,
                    f"KV pool leaf {path} [{dtype}] on a declared int8 "
                    f"pool is neither payload (*_q) nor scale row (*_s) "
                    f"— unquantized storage crept in",
                ))
                continue
        else:
            want = sp.kv_dtype
        if dtype != want:
            out.append(CheckFinding(
                "PSC107", r.spec.name,
                f"KV pool leaf {path} carries {dtype}, declared storage "
                f"dtype is {want} — serving cache dtype regression",
            ))
    return out


def psc108_adaptive(r: TraceResult) -> List[CheckFinding]:
    """The adaptive-mask contract: (a) the spec must keep a grad_reduce
    declaration — the traced count is a pre-reduce multiply, so PSC102's
    "masked reduce feeds the updated params" check is the dataflow rule
    and PSC108 refuses the opt-out of it; (b) the gradient-path reduce
    collectives must fit the declared byte envelope — a mask count is
    VALUES (which workers contribute, what divides the sum), so any
    per-count growth of the wire (mask gathers, resized payloads) is a
    regression."""
    ap = r.spec.adaptive
    if ap is None:
        return []
    out = []
    if not r.spec.grad_reduce:
        out.append(CheckFinding(
            "PSC108", r.spec.name,
            "adaptive aggregation declared but no grad_reduce "
            "requirement — without it PSC102 cannot pin the masked "
            "reduce's dataflow to the updated params",
        ))
    got = _grad_reduce_bytes(r)
    if got > ap.envelope_bytes:
        out.append(CheckFinding(
            "PSC108", r.spec.name,
            f"gradient-path reduce collectives move {got} B, but the "
            f"adaptive envelope (counts {ap.min_aggregate}-"
            f"{ap.max_aggregate}) declares at most {ap.envelope_bytes} B "
            f"— the traced mask must reshape values, not add wire bytes",
        ))
    return out


def _grad_reduce_bytes(r: TraceResult) -> int:
    return sum(
        c.bytes
        for c in r.collectives
        if c.feeds_params and c.kind in REDUCE_KINDS
    )


def _grad_reduce_count(r: TraceResult) -> int:
    return sum(
        1
        for c in r.collectives
        if c.feeds_params and c.kind in REDUCE_KINDS
    )


def psc109_schedule(results: Sequence[TraceResult]) -> List[CheckFinding]:
    """Schedule invariance for pipelined configs (cross-result rule,
    like PSC104): byte-equality against the serial twin when the twin
    was traced in the same batch, and per-bucket dispatch — the
    pipelined wire must emit one reduce chain per bucket (x the
    scheme's per-bucket collective cost), each a dataflow ancestor of
    the updated params."""
    out: List[CheckFinding] = []
    by_name = {r.spec.name: r for r in results}
    for r in results:
        ov = r.spec.overlap
        if ov is None or ov.mode != "pipelined":
            continue
        fu = r.spec.fusion
        if fu is None:
            out.append(CheckFinding(
                "PSC109", r.spec.name,
                "pipelined overlap declared without a FusionSpec — the "
                "per-bucket dispatch requirement needs the bucket plan "
                "to know how many reduce chains to demand",
            ))
        else:
            want = fu.per_bucket * fu.n_buckets
            got = _grad_reduce_count(r)
            if got < want:
                out.append(CheckFinding(
                    "PSC109", r.spec.name,
                    f"only {got} gradient-path reduce collectives for a "
                    f"pipelined plan of {fu.n_buckets} bucket(s) "
                    f"(x{fu.per_bucket} per bucket = {want} expected) — "
                    f"the wire has re-fused into a barrier; the "
                    f"schedule is serial no matter what the config "
                    f"declares",
                ))
        twin = by_name.get(ov.serial_twin) if ov.serial_twin else None
        if twin is None:
            # the twin wasn't traced in this batch (e.g. --only) — the
            # byte pin still holds transitively via PSC104 on both
            continue
        mine, theirs = _grad_reduce_bytes(r), _grad_reduce_bytes(twin)
        if mine != theirs:
            out.append(CheckFinding(
                "PSC109", r.spec.name,
                f"gradient-path reduce collectives move {mine} B but the "
                f"serial twin '{twin.spec.name}' moves {theirs} B — "
                f"pipelining must reorder the schedule, never change "
                f"the bytes",
            ))
    return out


def psc110_consensus(results: Sequence[TraceResult]) -> List[CheckFinding]:
    """Adaptive configs must declare a REAL host-consensus point.

    The traced aggregation count is a jitted-step input that must be
    bit-identical on every process (a torn count = different masked
    reduces = divergent replicated params, PR 7's bug). The dynamic half
    of that guarantee is pslint's PSL007; this is the static registry
    half: every AdaptivePolicy names where consensus happens, and the
    name must resolve to a consensus-shaped function (its return value
    passes through broadcast_one_to_all/process_allgather) in the
    package — found by the same AST walker the divergence lint uses
    (lint/diverge.py:consensus_inventory), so a renamed or de-consensused
    helper breaks this gate, not a pod run."""
    from ..lint.diverge import consensus_inventory

    out: List[CheckFinding] = []
    inventory = None
    for r in results:
        pol = r.spec.adaptive
        if pol is None:
            continue
        if not pol.consensus:
            out.append(CheckFinding(
                "PSC110", r.spec.name,
                "AdaptivePolicy declares a traced aggregation count but "
                "no host-consensus point — each process would adapt on "
                "its own telemetry and feed the step torn values; name "
                "the function that agrees them (e.g. "
                "'trainer.Trainer._count_consensus')",
            ))
            continue
        if inventory is None:
            inventory = consensus_inventory()
        if pol.consensus not in inventory:
            known = ", ".join(sorted(inventory)) or "none found"
            out.append(CheckFinding(
                "PSC110", r.spec.name,
                f"declared host-consensus point '{pol.consensus}' "
                f"is not in the package's consensus inventory "
                f"(functions whose return passes through "
                f"broadcast_one_to_all/process_allgather; known: "
                f"{known}) — renamed, or no longer consensus-shaped",
            ))
    return out


def psc105_donation(r: TraceResult) -> List[CheckFinding]:
    if r.spec.donation is None:
        return []
    out = []
    if r.donor_marks < r.donated_leaves:
        out.append(CheckFinding(
            "PSC105", r.spec.name,
            f"only {r.donor_marks} of {r.donated_leaves} donated input "
            f"buffers survive lowering with a donor/alias mark — "
            f"donation was dropped (donate_argnums missing or overridden)",
        ))
    for msg in r.donation_mismatches:
        out.append(CheckFinding("PSC105", r.spec.name, msg))
    return out


def _numerics(r: TraceResult):
    """The (policy, report) pair the PSC111-114 rules read, or (None,
    None) when the spec declares no NumericsPolicy (old fixtures)."""
    pol = getattr(r.spec, "numerics", None)
    rep = r.numerics
    if pol is None or rep is None:
        return None, None
    return pol, rep


def psc111_scale_provenance(r: TraceResult) -> List[CheckFinding]:
    """Every dequantize's scale must descend from the SAME max-abs
    reduction that produced its quantize's scale (shared provenance
    root), across every hop of the 2round / hier wires."""
    pol, rep = _numerics(r)
    if rep is None:
        return []
    out = []
    by_sid = {s.sid: s for s in rep.sites}
    for d in rep.dequants:
        for sid in sorted(d.payload_sites):
            s = by_sid.get(sid)
            if s is None or s.roots & d.scale_roots:
                continue
            origin = ("a static constant" if d.scale_literal
                      else "a different dataflow origin" if d.scale_roots
                      else "no max-abs reduction at all")
            verb = ("cannot be proven to descend"
                    if (d.conservative or s.conservative)
                    else "does not descend")
            out.append(CheckFinding(
                "PSC111", r.spec.name,
                f"dequantize of the {s.dtype} payload at offset "
                f"{s.start_offset} takes its scale from {origin}: the "
                f"scale {verb} from the max-abs reduction behind the "
                f"quantize's scale — the lattice decodes against the "
                f"wrong dynamic range",
            ))
    if pol.quantized:
        for s in rep.sites:
            if s.primary and s.feeds_params and not s.roots:
                out.append(CheckFinding(
                    "PSC111", r.spec.name,
                    f"quantization site at offset {s.start_offset} "
                    f"({s.dtype}, {s.size} elem) on the gradient path "
                    f"has no max-abs reduction in its scale chain — its "
                    f"clamp bound was minted from a constant, not from "
                    f"the data's dynamic range",
                ))
    return out


def psc112_error_feedback(r: TraceResult) -> List[CheckFinding]:
    """With error_feedback declared, every primary quantization site on
    the gradient path needs a grad - dequant(quant) residual that feeds
    the next step's carry — and only the carry (feeding the params too
    double-counts the correction)."""
    pol, rep = _numerics(r)
    if rep is None or not pol.error_feedback:
        return []
    primary = [s for s in rep.sites if s.primary and s.feeds_params]
    if not primary:
        return [CheckFinding(
            "PSC112", r.spec.name,
            "error_feedback declared but the trace has no primary "
            "quantization site on the gradient path — there is no "
            "quantization error for a residual to close over",
        )]
    out = []
    live = [e for e in rep.residuals if e.feeds_carry]
    for s in primary:
        cov = [e for e in live if s.sid in e.covered_sites]
        if not cov:
            out.append(CheckFinding(
                "PSC112", r.spec.name,
                f"quantization site at offset {s.start_offset} "
                f"({s.dtype}, {s.size} elem) has no residual consumer "
                f"grad - dequant(quant) feeding the next step's carry — "
                f"the quantization error is dropped and EF-SGD silently "
                f"degrades to biased quantized SGD",
            ))
        elif s.conservative or all(e.conservative for e in cov):
            out.append(CheckFinding(
                "PSC112", r.spec.name,
                f"cannot prove error-feedback closure for the "
                f"quantization site at offset {s.start_offset}: the "
                f"residual chain crosses a scan/while carry, where "
                f"bounds and dataflow widen to unknown",
            ))
    for e in rep.residuals:
        if e.covered_sites and e.feeds_carry and e.feeds_params:
            out.append(CheckFinding(
                "PSC112", r.spec.name,
                f"the error-feedback residual covering site(s) "
                f"{sorted(e.covered_sites)} feeds BOTH the carried "
                f"residual and the updated params — the correction is "
                f"applied this step AND replayed next step "
                f"(double-counted)",
            ))
    return out


def psc113_capacity(r: TraceResult) -> List[CheckFinding]:
    """Integer-accumulation capacity proven from the trace: worst-case
    |sum| = clamp peak x the traced summand count (collective axis
    sizes, reduce dims) must fit the payload dtype — plus the declared-
    accumulator dtype pin (PR 12's widened-payload shape) and the
    homomorphic_rescale saturation check."""
    pol, rep = _numerics(r)
    if rep is None:
        return []
    out = []
    for a in rep.accums:
        where = f"{a.kind} over {list(a.axes)}" if a.axes else a.kind
        if (a.peak_out is not None and a.capacity is not None
                and a.peak_out > a.capacity):
            summands = (
                f" ({a.multiplier} summands x |payload| <= {a.peak_in:g})"
                if a.multiplier is not None and a.peak_in is not None
                else ""
            )
            cap_kind = ("exact-mantissa capacity"
                        if a.kind == "mantissa" or not a.dtype.startswith(
                            "int")
                        else "dtype capacity")
            out.append(CheckFinding(
                "PSC113", r.spec.name,
                f"{where} in {a.dtype} reaches worst-case |sum| = "
                f"{a.peak_out:g}{summands}, over the {cap_kind} "
                f"{a.capacity} — the traced accumulation overflows",
            ))
        elif a.lattice and a.peak_out is None:
            reason = (
                "the bound crosses a scan/while carry"
                if a.conservative
                else "unknown axis size"
                if a.multiplier is None and a.kind in ("psum",
                                                       "psum_scatter")
                else "the payload bound is unknown"
            )
            out.append(CheckFinding(
                "PSC113", r.spec.name,
                f"cannot prove {where} in {a.dtype} fits: lattice "
                f"payload with no provable |sum| bound ({reason}) — "
                f"quantized accumulation must be proven from the trace, "
                f"not assumed",
            ))
        elif (pol.quantized and a.kind in ("psum", "psum_scatter")
              and a.dtype in ("int8", "int16") and a.feeds_params
              and a.peak_out is None):
            out.append(CheckFinding(
                "PSC113", r.spec.name,
                f"cannot prove {where} fits {a.dtype}: the wire payload "
                f"carries no provable clamp bound into the reduce — an "
                f"unclamped cast is on the quantized wire",
            ))
        if (pol.accum_dtype is not None and a.lattice
                and a.kind in ("psum", "psum_scatter")
                and a.dtype.startswith("int")
                and a.dtype != pol.accum_dtype):
            out.append(CheckFinding(
                "PSC113", r.spec.name,
                f"lattice {where} carries {a.dtype} on a declared "
                f"{pol.accum_dtype} accumulator — the widened payload "
                f"crept back onto the wire (the PR 12 regression shape)",
            ))
    for s in rep.sites:
        if s.primary or not s.feeds_params:
            # primary quantizes divide by their own max-abs: in-range by
            # construction; only lattice REQUANTS (homomorphic_rescale)
            # carry a divisor that can saturate the clamp
            continue
        if s.pre_peak is None:
            out.append(CheckFinding(
                "PSC113", r.spec.name,
                f"cannot prove the lattice requantize at offset "
                f"{s.start_offset} ({s.dtype}) stays in range: the "
                f"pre-clamp |value| bound is unknown, so the "
                f"homomorphic_rescale divisor cannot be proven to "
                f"prevent saturation",
            ))
        elif s.peak is not None and s.pre_peak > s.peak + 1e-6:
            out.append(CheckFinding(
                "PSC113", r.spec.name,
                f"lattice requantize at offset {s.start_offset} "
                f"saturates: |value| reaches {s.pre_peak:g} before the "
                f"+-{s.peak:g} clamp — the homomorphic_rescale divisor "
                f"is too small and the wire clips",
            ))
    return out


def psc114_downcast(r: TraceResult) -> List[CheckFinding]:
    """No silent downcast on the update path: a precision-narrowing
    convert downstream of the gradient reduce that feeds the updated
    params must be a detected quantization site (those never land in
    ``narrows``) or a declared NarrowingAllowance."""
    pol, rep = _numerics(r)
    if rep is None:
        return []
    allowed = {(a.src, a.dst) for a in pol.allow_narrowing}
    out = []
    for n in rep.narrows:
        if not n.downstream_of_reduce or not n.feeds_params:
            continue
        if (n.src, n.dst) in allowed:
            continue
        out.append(CheckFinding(
            "PSC114", r.spec.name,
            f"convert {n.src}->{n.dst} downstream of the gradient "
            f"reduce feeds the updated params but is neither a "
            f"quantization site (no provable clamp bound) nor a "
            f"declared NarrowingAllowance — precision drops silently "
            f"on the update path",
        ))
    return out


def check_result(r: TraceResult) -> List[CheckFinding]:
    return (
        psc101_axes(r)
        + psc102_grad_reduce(r)
        + psc103_wire(r)
        + psc105_donation(r)
        + psc106_fusion(r)
        + psc107_serve(r)
        + psc108_adaptive(r)
        + psc111_scale_provenance(r)
        + psc112_error_feedback(r)
        + psc113_capacity(r)
        + psc114_downcast(r)
    )


def _row_key(row: dict) -> tuple:
    return (row["kind"], tuple(row["axes"]), row["dtype"])


def psc104_roundtrip(
    results: Sequence[TraceResult],
    contract: dict,
    check_stale: bool = True,
) -> List[CheckFinding]:
    """Diff the measured accounting against the committed artifact."""
    out: List[CheckFinding] = []
    configs: Dict[str, dict] = contract.get("configs", {})
    for r in results:
        pinned = configs.get(r.spec.name)
        if pinned is None:
            out.append(CheckFinding(
                "PSC104", r.spec.name,
                "config missing from the contract artifact — refresh with "
                "--write-contract",
            ))
            continue
        want = {_row_key(row): row for row in pinned.get("collectives", [])}
        got = {_row_key(row): row for row in r.summary}
        for key in sorted(set(want) | set(got)):
            kind, axes, dtype = key
            label = f"{kind} over {list(axes)} [{dtype}]"
            if key not in want:
                out.append(CheckFinding(
                    "PSC104", r.spec.name,
                    f"unpinned collective appeared: {label} "
                    f"(count={got[key]['count']}, bytes={got[key]['bytes']})",
                ))
            elif key not in got:
                out.append(CheckFinding(
                    "PSC104", r.spec.name,
                    f"pinned collective vanished: {label} "
                    f"(was count={want[key]['count']}, "
                    f"bytes={want[key]['bytes']})",
                ))
            elif (want[key]["count"] != got[key]["count"]
                  or want[key]["bytes"] != got[key]["bytes"]):
                out.append(CheckFinding(
                    "PSC104", r.spec.name,
                    f"wire accounting drift for {label}: pinned "
                    f"count={want[key]['count']} bytes={want[key]['bytes']}"
                    f", measured count={got[key]['count']} "
                    f"bytes={got[key]['bytes']}",
                ))
    if check_stale:
        traced = {r.spec.name for r in results}
        for name in sorted(set(configs) - traced):
            out.append(CheckFinding(
                "PSC104", name,
                "stale contract entry: config no longer in the registry — "
                "refresh with --write-contract",
            ))
    return out
