"""Comm/compute-overlap evidence for the PS engine (SURVEY component #12).

The reference hand-pipelines per-layer gradient sends so communication of
layer k+1's gradient overlaps backprop of layer k
(/root/reference/src/model_ops/resnet_split.py:262-363). The TPU re-design
deletes that machinery and relies on XLA: the gradient psum lowers to async
`all-reduce-start`/`all-reduce-done` pairs and the latency-hiding scheduler
places backward compute between them. This tool produces the evidence, three
ways (most → least direct):

  trace     read a `--profile-dir` capture (the `XLA Ops` / `Async XLA Ops`
            lines of its xplane.pb, through jax.profiler.ProfileData) from a
            real run and measure wall-clock overlap between collective and
            compute events on the device timeline. Needs a device that emits
            an op-level timeline (TPU; the CPU backend logs host events only).
            A device event is named by its INSTRUCTION (`fusion.525`), never
            by a scope: which bucket of the pipelined wire an instruction
            belongs to comes from the step's census beside the capture
            (`step_scopes.json`: the `grad_reduce/bucket_reduce_o<offset>` /
            `update/bucket_update_o<offset>` scopes, obs/scopes.py), and the
            per-bucket overlap breakdown is reported when they appear.
  topology  AOT-compile the SPMD train step for an N-chip TPU topology via
            `jax.experimental.topologies` (no chips needed — the compiler
            does the scheduling) and analyze the compiled schedule.
  hlo       compile for the attached backend (e.g. the 8-device virtual CPU
            mesh) and analyze the compiled schedule. NOTE the CPU backend
            combines the whole gradient tree into ONE synchronous all-reduce
            scheduled after backward — a property of XLA:CPU, not of the
            engine; this mode exists to exercise the analyzer and to show
            the HLO the partitioner emits.
  jaxpr     trace the step (nothing compiles or executes) and measure the
            SCHEDULE FREEDOM the program's dataflow grants, per gradient
            reduce: `independent_frac` (equation weight that is neither
            ancestor nor descendant — what a latency-hiding scheduler MAY
            place beside the collective; `overlap_fraction` is its mean)
            and `prefix_frac` (ancestor weight — what MUST retire before
            the collective can launch). The pipelined wire (--overlap on)
            raises the former and collapses the latter: serially, the
            global flatten makes every bucket wait for the whole
            backward; pipelined, the first readiness-ordered bucket
            launches after its own leaves' chain alone. Deterministic and
            backend-independent — the number to bank from a CPU container.

Schedule analysis: in a scheduled HLO module the textual instruction order
of the entry computation IS the execution order. For every async collective
pair we count the compute instructions (fusion/convolution/dot/...) placed
between -start and -done: >0 means the scheduler hid (part of) the
collective behind compute. Sync collectives are reported with their position
in the schedule instead.

Usage:
  python tools/overlap_report.py hlo --workers 8 --network ResNet18
  python tools/overlap_report.py trace --profile-dir runs/profile/...
  python tools/overlap_report.py topology --topology v5e:2x4 --workers 8

Folded into the observability front end as a subcommand — prefer
``python tools/trace_report.py overlap <mode> [...]`` (same flags; this
module remains the implementation).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

COLLECTIVE_OPS = (
    "all-reduce-start", "all-reduce-done", "all-reduce",
    "all-gather-start", "all-gather-done", "all-gather",
    "reduce-scatter", "collective-permute-start",
    "collective-permute-done", "collective-permute", "all-to-all",
)
COMPUTE_OPS = (
    "fusion", "convolution", "dot", "reduce", "scatter", "select-and-scatter",
    "custom-call", "sort", "cholesky", "triangular-solve",
)
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def _shape_bytes(type_str: str) -> int:
    """Total bytes of every array shape mentioned in an HLO type string
    (handles tuples): 'f32[3,3,64,64]{...}' -> 147456."""
    total = 0
    for dt, dims in re.findall(r"([a-z]\w*)\[([\d,]*)\]", type_str):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def _opcode(line: str):
    """Opcode of an HLO instruction line ('%name = <type> opcode(...)').
    Tuple types contain parens-free tokens like f32[8]{0}, so the first
    lowercase identifier directly followed by '(' is the opcode."""
    line = re.sub(r"/\*.*?\*/", "", line)
    if "=" not in line:
        return None, line
    rhs = line.split("=", 1)[1]
    m = re.search(r"([a-z][a-z0-9-]*)\(", rhs)
    return (m.group(1) if m else None), rhs


def _replica_groups(rhs: str):
    """Parse a collective's replica_groups attribute into a list of device-id
    lists, or None if absent. Handles both syntaxes XLA prints:
      explicit  replica_groups={{0,1,2,3},{4,5,6,7}}
      iota      replica_groups=[4,8]<=[32]          (reshape of iota)
                replica_groups=[8,4]<=[4,8]T(1,0)   (transposed reshape)
    The iota form [G,S]<=[dims](T(perm))? means: take iota(prod(dims)),
    reshape to dims, optionally transpose by perm, then reshape to G rows
    of S — the rows are the groups."""
    m = re.search(r"replica_groups=\{\{([\d,{}\s]*)\}\}", rhs)
    if m:
        return [
            [int(x) for x in grp.split(",") if x.strip()]
            for grp in re.split(r"\}\s*,\s*\{", m.group(1))
            if grp.strip()
        ]
    m = re.search(
        r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?",
        rhs,
    )
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        n = 1
        for d in dims:
            n *= d
        if n != g * s:
            return None
        ids = list(range(n))
        if m.group(4):  # transpose: walk the reshaped iota in perm order
            perm = [int(p) for p in m.group(4).split(",")]
            # strides of the original dims layout (row-major)
            strides = [1] * len(dims)
            for i in range(len(dims) - 2, -1, -1):
                strides[i] = strides[i + 1] * dims[i + 1]
            out = []
            def walk(depth, off):
                if depth == len(perm):
                    out.append(off)
                    return
                d = perm[depth]
                for i in range(dims[d]):
                    walk(depth + 1, off + i * strides[d])
            walk(0, 0)
            ids = out
        return [ids[i * s:(i + 1) * s] for i in range(g)]
    return None


def _wrapped_groups(rhs: str, comp_groups: dict):
    """Groups of an async wrapper's wrapped collective: resolve the
    calls=%target against the computation->groups map."""
    m = re.search(r"calls=(%[\w.\-]+)", rhs)
    return comp_groups.get(m.group(1)) if m else None


def analyze_hlo_schedule(hlo_text: str) -> dict:
    """Walk the scheduled entry computation; report every collective with
    the compute placed between its start/done pair (async) or its schedule
    position (sync)."""
    lines = hlo_text.splitlines()
    # replica_groups of collectives hidden inside non-entry computations:
    # XLA's generic async wrappers (`async-start ..., calls=%wrapped_x`)
    # print the groups attribute on the WRAPPED instruction in its own
    # computation, not on the -start line — map computation name -> groups
    # so the wrapper's collective still gets classified
    comp_groups: dict = {}
    current_comp = None
    for l in lines:
        m = re.match(r"\s*(%[\w.\-]+)\s*(?:\([^)]*\))?\s*.*\{\s*$", l)
        if m and "=" not in l.split("{")[0]:
            current_comp = m.group(1)
            continue
        if l.startswith("}") or l.strip() == "}":
            current_comp = None
            continue
        if current_comp and "replica_groups=" in l:
            g = _replica_groups(l)
            if g is not None and current_comp not in comp_groups:
                comp_groups[current_comp] = g
    # entry computation: from 'ENTRY' to the closing brace at depth 0
    try:
        start = next(i for i, l in enumerate(lines) if l.startswith("ENTRY"))
    except StopIteration:
        return {"error": "no ENTRY computation found"}
    body = []
    for line in lines[start + 1:]:
        if line.startswith("}"):
            break
        if re.match(r"\s*(%|ROOT)", line):
            body.append(line)

    ops = []
    for i, line in enumerate(body):
        op, rhs = _opcode(line)
        if op is None:
            continue
        name_m = re.match(r"\s*(?:ROOT\s+)?(%[\w.\-]+)", line)
        ops.append({
            "i": i,
            "name": name_m.group(1) if name_m else f"<{i}>",
            "op": op,
            "bytes": _shape_bytes(rhs.split(op + "(", 1)[0]),
            "rhs": rhs,  # untruncated, for operand parsing
        })

    compute_idx = [o["i"] for o in ops if o["op"] in COMPUTE_OPS]
    collectives = []
    starts = {}
    unmatched_done = 0
    collective_kinds = {k for k in COLLECTIVE_OPS if not k.endswith(("-start", "-done"))}

    def _async_kind(o):
        """Collective kind of an async -start/-done instruction, or None.
        Handles both dedicated ops (all-reduce-start) and XLA's generic
        wrappers (async-start ... calls=%wrapped_reduce_scatter), where the
        wrapped collective's name appears in the instruction text. Plain
        async copies etc. return None — they move no collective traffic."""
        base = o["op"].rsplit("-", 1)[0]
        if base in collective_kinds:
            return base
        if base == "async":
            # only the calls= target names the wrapped op — operand names
            # and metadata can mention collectives without being one
            called = re.search(r"calls=(%[\w.\-]+)", o["rhs"])
            if called:
                tok = called.group(1)
                for k in sorted(collective_kinds, key=len, reverse=True):
                    if k in tok or k.replace("-", "_") in tok:
                        return k
        return None

    for o in ops:
        if o["op"].endswith("-start"):
            if _async_kind(o) is not None:
                starts[o["name"]] = o
        elif o["op"].endswith("-done"):
            # operand of -done is the matching -start instruction
            operand = re.search(r"\((%[\w.\-]+)", o["rhs"])
            s = starts.get(operand.group(1)) if operand else None
            if s is None:
                if _async_kind(o) is not None:
                    unmatched_done += 1
                continue
            between = [i for i in compute_idx if s["i"] < i < o["i"]]
            collectives.append({
                "kind": _async_kind(s) or s["op"],
                # the -start type tuple holds input AND output buffers;
                # the -done type is the result alone = the payload
                "bytes": o["bytes"],
                "async": True,
                "start_pos": s["i"],
                "done_pos": o["i"],
                "compute_ops_between": len(between),
                "overlapped": len(between) > 0,
                # dedicated -start ops carry replica_groups inline; generic
                # async wrappers keep it on the wrapped computation
                "groups": _replica_groups(s["rhs"])
                or _wrapped_groups(s["rhs"], comp_groups),
            })
        elif o["op"] in COLLECTIVE_OPS:
            after = [i for i in compute_idx if i > o["i"]]
            collectives.append({
                "kind": o["op"],
                "bytes": o["bytes"],
                "async": False,
                "pos": o["i"],
                "schedule_len": len(body),
                "compute_ops_after": len(after),
                "groups": _replica_groups(o["rhs"]),
            })

    return {
        "instructions": len(body),
        "compute_instructions": len(compute_idx),
        "collectives": collectives,
        "n_async": sum(1 for c in collectives if c["async"]),
        "n_async_overlapped": sum(
            1 for c in collectives if c.get("overlapped")
        ),
        "n_sync": sum(1 for c in collectives if not c["async"]),
        "unmatched_done": unmatched_done,
    }


# ---------------------------------------------------------------- build step

def _build_step(args, mesh, dcn_hosts: int = 1):
    import jax
    import jax.numpy as jnp

    from ps_pytorch_tpu.data import make_preprocessor
    from ps_pytorch_tpu.models import build_model, input_shape_for
    from ps_pytorch_tpu.optim import sgd
    from ps_pytorch_tpu.parallel.ps import (
        PSConfig,
        init_ps_state,
        make_ps_train_step,
    )

    cfg = PSConfig(
        num_workers=args.workers,
        compress=args.compress,
        num_aggregate=args.num_aggregate,
        dcn_hosts=dcn_hosts,  # >1 needs a make_hybrid_mesh-shaped mesh
        bucket_bytes=(
            None if args.bucket_bytes < 0 else args.bucket_bytes
        ),
        overlap="pipelined" if args.overlap == "on" else "serial",
    )
    net = build_model(args.network, num_classes=10)
    tx = sgd(0.1, momentum=0.9)
    state = init_ps_state(
        net, tx, cfg, jax.random.key(0), input_shape_for(args.network)
    )
    pre = make_preprocessor(args.dataset, train=True)
    step = make_ps_train_step(net, tx, cfg, mesh, preprocess=pre)
    h, w, c = input_shape_for(args.network)
    batch = {
        "image": jnp.zeros((args.batch, h, w, c), jnp.uint8),
        "label": jnp.zeros((args.batch,), jnp.int32),
    }
    return step, state, batch


def run_hlo(args) -> dict:
    import jax

    from ps_pytorch_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(num_workers=args.workers)
    step, state, batch = _build_step(args, mesh)
    txt = step.lower(state, batch, jax.random.key(1)).compile().as_text()
    rep = analyze_hlo_schedule(txt)
    rep["mode"] = "hlo"
    rep["backend"] = jax.default_backend()
    rep["workers"] = args.workers
    return rep


def run_jaxpr(args) -> dict:
    """Schedule-freedom from the traced step's dataflow (trace-only, no
    compile): parallel/overlap.jaxpr_overlap_headroom over the real
    train step built with this CLI's config (--overlap selects the
    schedule). overlap_headroom ~0 = every collective is a barrier."""
    import jax

    from ps_pytorch_tpu.parallel.mesh import make_mesh
    from ps_pytorch_tpu.parallel.overlap import jaxpr_overlap_headroom

    mesh = make_mesh(num_workers=args.workers)
    step, state, batch = _build_step(args, mesh)
    rep = jaxpr_overlap_headroom(step, state, batch, jax.random.key(1))
    # keep the report compact: per-collective rows collapse to stats
    fracs = sorted(
        p["independent_frac"] for p in rep.pop("per_collective")
    )
    rep["overlap_fraction"] = rep["overlap_headroom"]  # the headline
    rep.update({
        "mode": "jaxpr",
        "workers": args.workers,
        "network": args.network,
        "compress": args.compress,
        "overlap": args.overlap,
        "bucket_bytes": args.bucket_bytes,
        "independent_frac_min": fracs[0] if fracs else None,
        "independent_frac_max": fracs[-1] if fracs else None,
    })
    return rep


def run_topology(args) -> dict:
    """AOT-compile the N-chip TPU program via a PJRT topology description —
    the TPU compiler does the real scheduling, no chips needed."""
    import jax
    from jax.experimental import topologies

    last_err = None
    for name in ([args.topology] if args.topology else
                 [f"v5e:{args.workers}", f"v5litepod-{args.workers}",
                  f"v5e:2x{args.workers // 2}"]):
        try:
            topo = topologies.get_topology_desc(name, "tpu")
            break
        except Exception as e:  # try the next naming convention
            last_err = e
            topo = None
    if topo is None:
        return {"mode": "topology", "error": f"{type(last_err).__name__}: {last_err}"}

    from ps_pytorch_tpu.parallel.mesh import WORKER_AXIS

    mesh = topologies.make_mesh(topo, (args.workers,), (WORKER_AXIS,))
    step, state, batch = _build_step(args, mesh)
    state_sds = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state
    )
    batch_sds = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch
    )
    k = jax.random.key(1)
    key_sds = jax.ShapeDtypeStruct(k.shape, k.dtype)  # typed PRNG key
    try:
        lowered = step.lower(state, batch, k)
    except Exception:
        lowered = step.lower(state_sds, batch_sds, key_sds)
    txt = lowered.compile().as_text()
    rep = analyze_hlo_schedule(txt)
    rep["mode"] = "topology"
    rep["topology"] = str(topo)
    rep["workers"] = args.workers
    return rep


def capture_spans(profile_dir: str):
    """(spans, file): the device ops of the newest capture under
    `profile_dir` (`plugins/profile/*/*.xplane.pb`, read with
    jax.profiler.ProfileData: the `XLA Ops` and `Async XLA Ops` lines of
    every device plane) as {"name", "pid", "ts", "dur"} in microseconds. On
    the TPU an event's name is its whole HLO line; `name` keeps the
    instruction's."""
    from ps_pytorch_tpu.obs.hlo import hlo_line_name
    from ps_pytorch_tpu.obs.profiler import device_planes

    capture, planes = device_planes(profile_dir)
    spans = [{"name": hlo_line_name(e.name) or e.name, "pid": plane.name,
              "ts": e.start_ns * 1e-3, "dur": e.duration_ns * 1e-3}
             for plane in planes for line in plane.lines
             if line.name in ("XLA Ops", "Async XLA Ops") for e in line.events]
    return spans, capture


def run_trace(args) -> dict:
    """Wall-clock overlap from a --profile-dir run: fraction of
    collective-event time that coincides with compute events on the device
    timeline. The capture names an op by its instruction; WHICH bucket of
    the pipelined wire an instruction belongs to is read from the step's
    census beside the capture (`step_scopes.json`, which `--profile-dir`
    writes: ps_pytorch_tpu/obs/scopes.py), by the
    `grad_reduce/bucket_reduce_o<offset>` / `update/bucket_update_o<offset>`
    scopes."""
    if not args.profile_dir:
        return {"mode": "trace", "error": "--profile-dir is required"}
    spans, capture = capture_spans(args.profile_dir)
    # every chip runs the same program: the first device's timeline is read
    first = min((e["pid"] for e in spans), default=None)
    spans = [e for e in spans if e["pid"] == first]
    if not spans:
        return {"mode": "trace", "error": f"no device ops in a capture "
                f"(plugins/profile/*/*.xplane.pb) under {args.profile_dir}"}
    table = {}
    census = os.path.join(args.profile_dir, "step_scopes.json")
    if os.path.exists(census):
        with open(census) as f:
            table = json.load(f)["instructions"]
    rep = analyze_trace(spans, table)
    rep["trace_file"] = capture
    rep["census"] = census if table else None
    return rep


def analyze_trace(spans, table) -> dict:
    """`spans`: device ops {"name" (the instruction's), "pid", "ts", "dur"};
    `table`: the census's {instruction: [phase, scope, work, mixed, via]}
    ({} without one: no per-bucket rows then)."""
    scope_of = lambda e: (table.get(e["name"]) or ["", ""])[1]
    is_coll = lambda n: any(
        k in n.lower()
        for k in ("all-reduce", "all_reduce", "allreduce", "all-gather",
                  "all_gather", "reduce-scatter", "reduce_scatter",
                  "collective", "all-to-all", "psum")
    )
    # compute = real op events only (fusion/conv/dot/elementwise families),
    # NOT every non-collective span: infra/marker events (barriers, infeed,
    # trace bookkeeping) would otherwise count as overlapped compute and
    # inflate the fraction quoted as component-#12 evidence.
    # Classification is anchored to the HLO op-name PREFIX (the token before
    # the first '.', '%' stripped) matched EXACTLY against an op set — free
    # substring search would let copy-start/copy-done DMA bookkeeping or
    # address-computation thunks ride in on 'copy'/'dynamic'/'while'
    # substrings (advisor r04). 'copy' the exact op is real data movement;
    # 'copy-start'/'copy-done' are distinct prefixes and stay unclassified.
    # Anything unmatched lands in the skipped audit list, not in a bucket.
    _COMP_OPS = frozenset((
        "fusion", "convolution", "dot", "transpose", "copy", "reduce",
        "reduce-window", "scatter", "gather", "select", "broadcast",
        "add", "multiply", "subtract", "divide", "negate", "iota",
        "slice", "dynamic-slice", "dynamic-update-slice", "concatenate",
        "pad", "reshape", "bitcast", "convert", "compare", "rsqrt",
        # XLA spells these exponential/logistic; keep the short forms too
        "sqrt", "exp", "exponential", "log", "power", "abs", "maximum",
        "minimum", "tanh", "sigmoid", "logistic", "clamp",
        "select-and-scatter",
        # Pallas/custom kernels and compiled loop bodies are real compute
        "custom-call", "while",
    ))

    def _base_op(n: str) -> str:
        return n.lower().lstrip("%").split(".")[0]

    def is_comp(n: str) -> bool:
        if is_coll(n):
            return False
        base = _base_op(n)
        # fusion kinds surface as loop_fusion/input_fusion/output_fusion;
        # Pallas kernels keep their kernel name but are tagged custom-call
        return (base in _COMP_OPS or base.endswith("fusion")
                or "flash" in base or "kernel" in base)
    coll = [(e["ts"], e["ts"] + e["dur"]) for e in spans if is_coll(e["name"])]
    comp_events = [e for e in spans if is_comp(e["name"])]
    comp = [(e["ts"], e["ts"] + e["dur"]) for e in comp_events]
    skipped = [e for e in spans if not is_coll(e["name"]) and not is_comp(e["name"])]

    def _top_names(events, k=12):
        tot = {}
        for e in events:
            tot[e["name"]] = tot.get(e["name"], 0.0) + e["dur"]
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [{"name": n, "total_ms": round(d / 1e3, 3)} for n, d in ranked]

    def _merge(iv):
        out = []
        for s, t in sorted(iv):
            if out and s <= out[-1][1]:
                out[-1] = (out[-1][0], max(out[-1][1], t))
            else:
                out.append((s, t))
        return out

    def _inter(a, b):
        i = j = 0
        tot = 0.0
        while i < len(a) and j < len(b):
            s = max(a[i][0], b[j][0])
            t = min(a[i][1], b[j][1])
            if s < t:
                tot += t - s
            if a[i][1] < b[j][1]:
                i += 1
            else:
                j += 1
        return tot

    cm, pm = _merge(coll), _merge(comp)
    coll_time = sum(t - s for s, t in cm)
    overlap = _inter(cm, pm)
    # per-bucket breakdown when the pipelined wire's named scopes appear
    # on the device timeline: each bucket's own overlapped fraction.
    # ONLY the reduce scopes define a bucket's comm interval, and only
    # the SAME bucket's reduce/update spans are excluded from the
    # compute set it intersects — a bucket's own optimizer ops must not
    # count as phantom self-overlap, but ANOTHER bucket's update running
    # during this bucket's reduce is exactly the overlap the per-bucket
    # update path exists to create and must be counted.
    bucket_any_re = re.compile(r"bucket_(?:reduce|update)_o(\d+)")
    bucket_reduce_re = re.compile(r"bucket_reduce_o(\d+)")
    per_bucket = {}
    for e in spans:
        m = bucket_reduce_re.search(scope_of(e))
        if not m:
            continue
        per_bucket.setdefault(int(m.group(1)), []).append(
            (e["ts"], e["ts"] + e["dur"])
        )

    def _comp_offset(e):
        m = bucket_any_re.search(scope_of(e))
        return int(m.group(1)) if m else None

    comp_tagged = [(e, _comp_offset(e)) for e in comp_events]
    bucket_rows = []
    for off in sorted(per_bucket):
        bm = _merge(per_bucket[off])
        bt = sum(t - s for s, t in bm)
        pm_other = _merge([
            (e["ts"], e["ts"] + e["dur"])
            for e, tag in comp_tagged if tag != off
        ])
        ov = _inter(bm, pm_other)
        bucket_rows.append({
            "bucket_offset": off,
            "ms": round(bt / 1e3, 3),
            "overlapped_ms": round(ov / 1e3, 3),
            "overlap_fraction": round(ov / bt, 4) if bt else None,
        })
    return {
        "mode": "trace",
        "device_pids": sorted({e["pid"] for e in spans}),
        "n_collective_events": len(coll),
        "n_compute_events": len(comp),
        "n_skipped_events": len(skipped),
        # a large skipped share means the keyword filter missed real work
        # (or the trace is mostly infra) — audit top_skipped_events then
        "skipped_ms": round(sum(e["dur"] for e in skipped) / 1e3, 3),
        "compute_ms": round(sum(e["dur"] for e in comp_events) / 1e3, 3),
        "collective_ms": round(coll_time / 1e3, 3),
        "overlapped_ms": round(overlap / 1e3, 3),
        "overlap_fraction": round(overlap / coll_time, 4) if coll_time else None,
        # the pipelined wire's per-bucket spans, when present
        "per_bucket": bucket_rows or None,
        # name breakdowns so the fraction is auditable: what counted as
        # compute, and what was excluded as infra/markers
        "top_compute_events": _top_names(comp_events),
        "top_skipped_events": _top_names(skipped),
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("mode", choices=["hlo", "trace", "topology", "jaxpr"])
    p.add_argument("--workers", type=int, default=8)
    p.add_argument("--network", default="ResNet18")
    p.add_argument("--dataset", default="Cifar10")
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--compress", default=None)
    p.add_argument("--num-aggregate", type=int, default=None)
    p.add_argument("--bucket-bytes", type=int, default=-1,
                   help="gradient wire granularity (-1 = per-leaf, 0 = "
                        "one fused buffer, N = ~N-byte buckets)")
    p.add_argument("--overlap", choices=["on", "off"], default="off",
                   help="build the step with the pipelined bucket "
                        "schedule (PSConfig.overlap)")
    p.add_argument("--profile-dir", default=None)
    p.add_argument("--topology", default=None)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)

    rep = {"hlo": run_hlo, "trace": run_trace, "topology": run_topology,
           "jaxpr": run_jaxpr}[args.mode](args)
    print(json.dumps(rep, indent=2))
    if args.out:
        if os.path.dirname(args.out):
            os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rep, f, indent=2)
    return rep


if __name__ == "__main__":
    main()
