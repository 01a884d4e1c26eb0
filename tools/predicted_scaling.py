"""Predicted multi-chip scaling from AOT-partitioned HLO (no hardware).

The reference's headline artifact is a measured speedup table at
1/2/4/8/16/32 workers (analysis/Speedup_Comparisons_LeNet.ipynb cell 6,
BASELINE.md). Real multi-chip is unavailable in this environment, so this
tool produces the committed stand-in round-3 VERDICT asked for (missing #3):
for each (worker count, compression mode) it partitions the REAL PS train
step for an N-device mesh, reads the collective operations XLA actually
emitted — kind, count, and exact on-wire payload bytes — and folds them
through a standard, clearly-labeled alpha-beta ring model to predict
per-step collective cost and scaling efficiency on v5e ICI.

What is measured vs modeled:
  measured  collective kinds/counts/payload bytes AND replica groups,
            from the compiled SPMD program (the same
            `analyze_hlo_schedule` used by overlap_report.py). Gradient
            payloads do not depend on batch size, so the tiny per-worker
            batch used here changes nothing.
  modeled   link time per collective, PER AXIS (r04 VERDICT item 4). The
            physical layout is hosts of 8 chips (a v5e host); each
            collective's replica groups are classified by the hosts they
            span. Ring factors are applied at the GROUP size g (not total
            chip count): all-reduce 2(g-1)/g * S, gather/scatter/a2a
            (g-1)/g * S, permute S.
              intra-host group (h=1):  t = S*factor(g) / --ici-gbs
              cross-host group (h>1):  every ring edge carries
                S*factor(g); a host's NIC carries one outgoing cut edge
                per group present on it (per_host/c groups, c = g/h chips
                of each group per host), so
                  t_dcn = (per_host/c) * S*factor(g) / --dcn-gbs
                and the intra-host segments (absent when c=1) still cost
                  t_ici = S*factor(g) / --ici-gbs;
                the ring pipelines, so t = max(t_ici, t_dcn).
            Defaults: --ici-gbs 45 (public one-way per-ICI-link v5e
            figure), --dcn-gbs 12.5 (order-of-100-Gbps per-host NIC —
            set your fabric's real figure). Compute time at n workers =
            t1 / n (fixed global batch, the reference's own
            normalization), t1 an assumed single-chip ResNet18 b=1024
            step time unless --t1 gives a measured one.

Efficiency bounds: "no overlap" serializes compute + comm; "full overlap"
takes max(compute, comm) — the XLA latency-hiding scheduler lands between
them (component #12 evidence: tools/overlap_report.py).

The partitioner runs on the CPU backend here. Payload sizes and collective
choices come from SPMD partitioning, which is backend-independent; the
*schedule* is not, so this tool reports bytes/counts only and leaves
schedule claims to overlap_report.py.

Usage:
  python tools/predicted_scaling.py --out runs/predicted_scaling.json
  python tools/predicted_scaling.py --workers 8 16 32 --modes none int8
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# mode name -> PSConfig knobs. "hier" is the hierarchical DCN x ICI
# composition (ps.py dcn_hosts>1): ICI reduce-scatter -> one int8 DCN
# crossing -> ICI all-gather; hosts chosen so each host holds 8 chips
# (a v5e host), min 2 hosts.
MODES = {
    "none": dict(compress=None),
    "int8": dict(compress="int8"),
    "int8_2round": dict(compress="int8_2round"),
    "hier_2round": dict(compress="int8_2round", hier=True),
}

# pscheck cross-check: each mode's HLO collectives must agree in KIND
# with the jaxpr-level accounting pscheck pins for the matching contract
# config (runs/comm_contract.json, rule PSC104's artifact). Bytes are
# not compared — the contract traces LeNet on the 8-chip test mesh, this
# tool partitions ResNet at each worker count — but a kind appearing on
# one side only means the two measurements no longer describe the same
# wire protocol, which is exactly the drift PSC104 exists to catch.
MODE_CONTRACT_CONFIG = {
    "none": "ps_none_replicated",
    "int8": "ps_int8_replicated",
    "int8_2round": "ps_int8_2round_replicated",
    "hier_2round": "ps_hier_int8_2round_replicated",
}

# jaxpr collective kind (pscheck walker) -> compiled HLO op kind
_JAXPR_TO_HLO_KIND = {
    "psum": "all-reduce",
    "pmax": "all-reduce",
    "pmin": "all-reduce",
    "all_gather": "all-gather",
    "psum_scatter": "reduce-scatter",
    "all_to_all": "all-to-all",
    "ppermute": "collective-permute",
}


def contract_cross_check(rows: list, contract: dict) -> dict:
    """Compare each measured row's HLO collective-kind set against the
    pscheck contract entry for its mode. Returns a report block with one
    result per row; ok=None marks rows with no contract entry."""
    results = []
    for row in rows:
        cfg_name = MODE_CONTRACT_CONFIG.get(row["mode"])
        cfg = contract.get("configs", {}).get(cfg_name) if cfg_name else None
        if cfg is None:
            results.append({
                "workers": row["workers"], "mode": row["mode"],
                "config": cfg_name, "ok": None,
                "error": "no pscheck contract entry for this mode",
            })
            continue
        expected = sorted({
            _JAXPR_TO_HLO_KIND.get(c["kind"], c["kind"])
            for c in cfg.get("collectives", [])
        })
        measured = sorted(row.get("by_kind", {}))
        results.append({
            "workers": row["workers"], "mode": row["mode"],
            "config": cfg_name, "expected_kinds": expected,
            "measured_kinds": measured, "ok": expected == measured,
        })
    return {
        "ok": all(r["ok"] is not False for r in results),
        "results": results,
    }


# ring/torus step-count factors per collective kind (alpha-beta model,
# bytes multiplier applied to the payload): all-reduce moves every byte
# twice minus the 1/n it keeps; one-shot redistributions move (n-1)/n.
_RING_FACTOR = {
    "all-reduce": lambda n: 2 * (n - 1) / n,
    "all-gather": lambda n: (n - 1) / n,
    "reduce-scatter": lambda n: (n - 1) / n,
    "all-to-all": lambda n: (n - 1) / n,
    "collective-permute": lambda n: 1.0,
}


def child(args) -> None:
    """Partition the PS step for the CURRENT process's device count and
    emit one JSON line of collective stats (spawned by main with
    XLA_FLAGS=--xla_force_host_platform_device_count=N)."""
    import jax

    from ps_pytorch_tpu.parallel.mesh import make_hybrid_mesh, make_mesh
    from tools.overlap_report import analyze_hlo_schedule, _build_step

    n = args.one_workers
    mode = MODES[args.one_mode]
    hosts = max(2, n // 8) if mode.get("hier") else 1
    dataset = "MNIST" if args.network == "LeNet" else "Cifar10"
    ns = argparse.Namespace(
        workers=n, network=args.network, dataset=dataset,
        batch=args.batch * n, compress=mode["compress"],
        num_aggregate=None,
    )
    if hosts > 1:
        mesh = make_hybrid_mesh(hosts, n // hosts)
    else:
        mesh = make_mesh(num_workers=n)
    step, state, batch = _build_step(ns, mesh, dcn_hosts=hosts)

    txt = step.lower(state, batch, jax.random.key(1)).compile().as_text()
    rep = analyze_hlo_schedule(txt)
    # physical layout for axis classification: a v5e host is 8 chips, so a
    # FLAT n-chip mesh still spans ceil(n/8) physical hosts — its full-pool
    # collectives cross DCN at n>8 even though the mesh has one axis. The
    # hier mode's mesh is (hosts, n//hosts) with row-major device ids, so
    # id // per_host is the host index in both cases.
    per_host = (n // hosts) if hosts > 1 else min(n, 8)
    by_kind: dict = {}
    by_class: dict = {}
    for c in rep["collectives"]:
        k = by_kind.setdefault(c["kind"], {"count": 0, "bytes": 0})
        k["count"] += 1
        k["bytes"] += c["bytes"]
        groups = c.get("groups") or [list(range(n))]
        g = max(len(grp) for grp in groups)
        h = max(len({d // per_host for d in grp}) for grp in groups)
        cls = by_class.setdefault(f"{c['kind']}|g{g}|h{h}", {
            "kind": c["kind"], "g": g, "h": h, "count": 0, "bytes": 0,
        })
        cls["count"] += 1
        cls["bytes"] += c["bytes"]
    print(json.dumps({
        "workers": n, "mode": args.one_mode, "hosts": hosts,
        "per_host_model": per_host,
        "by_kind": by_kind,
        "by_class": by_class,
        "total_collective_bytes": sum(k["bytes"] for k in by_kind.values()),
        "n_collectives": sum(k["count"] for k in by_kind.values()),
    }))


# per-step seconds of single-chip ResNet18 b=1024 f32, the t_compute
# anchor when --t1 is not given: unverified (no chip record backs it),
# re-measure under ROADMAP D7 and pass the result as --t1
_T1_ASSUMED_S = 0.067


def predict(row: dict, t1: float, bw: float, dcn_bw: float | None = None) -> dict:
    """Fold one child measurement through the alpha-beta model.

    With per-group axis classes (row["by_class"], carrying group size g and
    hosts-spanned h per collective), the per-axis model applies: factors at
    g, intra-host classes on the ICI bandwidth, cross-host classes on the
    per-host DCN NIC with (per_host / c) groups sharing it (c = g/h chips
    of each group per host), pipelined-ring bottleneck max(ici, dcn).
    Without by_class (legacy rows / unit tests) it falls back to the flat
    single-bandwidth model at total chip count."""
    n = row["workers"]
    ici_s = dcn_s = 0.0
    if row.get("by_class") and dcn_bw:
        per_host = row.get("per_host_model") or min(n, 8)
        comm = 0.0
        for cls in row["by_class"].values():
            g, h = cls["g"], cls["h"]
            factor = _RING_FACTOR.get(cls["kind"], lambda k: 2 * (k - 1) / k)(g)
            link_bytes = cls["bytes"] * factor
            if h <= 1:
                t = link_bytes / bw
                ici_s += t
            else:
                c = max(1, g // h)
                t_dcn = (per_host / c) * link_bytes / dcn_bw
                # c == 1: every ring edge crosses hosts, no ICI segment
                t_ici = link_bytes / bw if c > 1 else 0.0
                t = max(t_ici, t_dcn)
                # attribute to the BOTTLENECK leg: on a fast fabric the
                # cross-host ring can be ICI-bound, and the per-axis split
                # must tell the reader which link to buy
                if t_dcn >= t_ici:
                    dcn_s += t
                else:
                    ici_s += t
            comm += t
    else:
        comm = 0.0
        for kind, st in row["by_kind"].items():
            factor = _RING_FACTOR.get(kind, lambda k: 2 * (k - 1) / k)(n)
            comm += st["bytes"] * factor / bw
        ici_s = comm
    compute = t1 / n
    return {
        **row,
        "modeled_comm_s": round(comm, 6),
        "modeled_comm_ici_s": round(ici_s, 6),
        "modeled_comm_dcn_s": round(dcn_s, 6),
        "modeled_compute_s": round(compute, 6),
        "speedup_no_overlap": round(t1 / (compute + comm), 2),
        "speedup_full_overlap": round(t1 / max(compute, comm), 2),
        "efficiency_no_overlap": round(t1 / (compute + comm) / n, 4),
        "efficiency_full_overlap": round(t1 / max(compute, comm) / n, 4),
    }


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workers", type=int, nargs="+", default=[8, 16, 32])
    p.add_argument("--modes", nargs="+", default=list(MODES),
                   choices=list(MODES))
    p.add_argument("--network", default="ResNet18")
    p.add_argument("--batch", type=int, default=8,
                   help="per-worker batch (payloads are batch-independent)")
    p.add_argument("--ici-gbs", type=float, default=45.0,
                   help="one-way per-link ICI GB/s (public v5e figure)")
    p.add_argument("--dcn-gbs", type=float, default=12.5,
                   help="per-host one-way DCN GB/s (default 12.5 = 100 "
                        "Gbps NIC; set your fabric's real figure)")
    p.add_argument("--t1", type=float, default=None,
                   help="single-chip step seconds measured on a chip; "
                        "default: an unverified assumption")
    p.add_argument("--timeout", type=int, default=900)
    p.add_argument("--out", default=None)
    p.add_argument("--contract", default=None,
                   help="pscheck contract artifact to cross-check "
                        "collective kinds against (default: "
                        "runs/comm_contract.json if present)")
    p.add_argument("--one-workers", type=int, default=None,
                   help=argparse.SUPPRESS)  # child mode
    p.add_argument("--one-mode", default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.one_workers:
        child(args)
        return {}

    from tpu_env import clean_cpu_env

    t1, t1_src = (
        (args.t1, "--t1") if args.t1
        else (_T1_ASSUMED_S, "assumed (not measured on a chip)")
    )
    bw = args.ici_gbs * 1e9

    rows, failures = [], []
    for n in args.workers:
        for mode in args.modes:
            if MODES[mode].get("hier") and n < 4:
                failures.append({
                    "workers": n, "mode": mode,
                    "error": "skipped: hier needs >=4 chips (2 hosts x 2)",
                })
                continue
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--one-workers", str(n), "--one-mode", mode,
                   "--network", args.network, "--batch", str(args.batch)]
            try:
                proc = subprocess.run(
                    cmd, env=clean_cpu_env(n_devices=n), cwd=REPO,
                    capture_output=True, text=True, timeout=args.timeout,
                )
            except subprocess.TimeoutExpired:
                failures.append({"workers": n, "mode": mode,
                                 "error": f"timeout {args.timeout}s"})
                continue
            if proc.returncode != 0:
                failures.append({"workers": n, "mode": mode,
                                 "error": proc.stderr.strip()[-500:]})
                continue
            row = json.loads(proc.stdout.strip().splitlines()[-1])
            rows.append(predict(row, t1, bw, dcn_bw=args.dcn_gbs * 1e9))
            print(f"# {n} workers / {mode}: "
                  f"{row['total_collective_bytes']/1e6:.2f} MB wire, "
                  f"{rows[-1]['speedup_no_overlap']}x-"
                  f"{rows[-1]['speedup_full_overlap']}x", file=sys.stderr)

    contract_path = args.contract or os.path.join(
        REPO, "runs", "comm_contract.json"
    )
    contract_block = None
    if os.path.exists(contract_path):
        with open(contract_path) as f:
            contract_block = contract_cross_check(rows, json.load(f))
        contract_block["path"] = os.path.relpath(contract_path, REPO)
        if not contract_block["ok"]:
            bad = [r for r in contract_block["results"]
                   if r["ok"] is False]
            for r in bad:
                print(
                    f"# CONTRACT MISMATCH {r['workers']} workers / "
                    f"{r['mode']}: HLO kinds {r['measured_kinds']} != "
                    f"pscheck contract kinds {r['expected_kinds']} "
                    f"({r['config']})", file=sys.stderr,
                )
    elif args.contract:
        print(f"# contract {args.contract} not found; cross-check skipped",
              file=sys.stderr)

    report = {
        "contract_check": contract_block,
        "model": {
            "t1_seconds": t1, "t1_source": t1_src,
            "ici_gbs_one_way": args.ici_gbs,
            "dcn_gbs_per_host": args.dcn_gbs,
            "factors": (
                "per collective GROUP of size g spanning h hosts: "
                "all-reduce 2(g-1)/g; gather/scatter/a2a (g-1)/g; permute "
                "1. h=1 -> ICI link time; h>1 -> per-host NIC time "
                "(per_host/c groups share the NIC, c=g/h), pipelined-ring "
                "bottleneck max(ici, dcn)"
            ),
            "caveat": (
                "bytes/counts/groups measured from the SPMD-partitioned "
                "HLO; link time is an alpha-beta MODEL, not a measurement. "
                "Physical layout assumed: hosts of 8 chips, device ids "
                "host-contiguous — so FLAT modes' full-pool collectives "
                "are DCN-priced beyond 8 chips, which is exactly the "
                "regime the hierarchical scheme exists for"
            ),
            "hier_note": (
                "hier rows at n>=16 model the real (n/8 hosts x 8 chips) "
                "layout. The n=8 hier row models a HYPOTHETICAL 2-host x "
                "4-chip pod (a physical 8-chip v5e pod is one host, where "
                "hier degenerates to the flat scheme); it exists so the "
                "table has no silently-missing cell"
            ),
        },
        "rows": rows,
        "failures": failures,
    }
    hdr = (f"{'n':>4} {'mode':>12} {'wire MB':>9} {'colls':>6} "
           f"{'comm ms':>9} {'ici ms':>8} {'dcn ms':>8} "
           f"{'eff (no ov)':>11} {'eff (full ov)':>13}")
    print(hdr)
    for r in rows:
        print(f"{r['workers']:>4} {r['mode']:>12} "
              f"{r['total_collective_bytes']/1e6:>9.2f} "
              f"{r['n_collectives']:>6} {r['modeled_comm_s']*1e3:>9.3f} "
              f"{r['modeled_comm_ici_s']*1e3:>8.3f} "
              f"{r['modeled_comm_dcn_s']*1e3:>8.3f} "
              f"{r['efficiency_no_overlap']:>11.3f} "
              f"{r['efficiency_full_overlap']:>13.3f}")
    if args.out:
        if os.path.dirname(args.out):
            os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
        print(f"report -> {args.out}", file=sys.stderr)
    return report


if __name__ == "__main__":
    _report = main()
    _cc = _report.get("contract_check")
    # a kind-level mismatch against the pscheck artifact is a wire
    # regression — fail the process so scripted runs can't commit it
    sys.exit(1 if (_cc and not _cc["ok"]) else 0)
