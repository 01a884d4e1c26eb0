"""Autotune CLI: one command instead of ten flags.

Searches the declared knob grid for a model (compress x bucket_bytes x
overlap x opt_placement x quant block x wire domain), pruning invalid
points with the PSC101-109 contract rules BEFORE costing them, ranking
the survivors with the trace-only cost model, and (optionally) running
short measured probes on the top-K. Writes a ranked, schema-validated
evidence record and prints the winning flag line.

  python tools/autotune.py --model resnet18 --trace-only
      -> runs/autotune_resnet18.json (CPU-only, nothing executes)
  python tools/autotune.py --model lenet --probe-top 3
      -> the top 3 modeled candidates also run 4 real steps each on the
         live backend; span-derived overlap fractions land in the record

Apply the result directly:

  python -m ps_pytorch_tpu.cli.train --config-json runs/autotune_resnet18.json

Tracing needs the deterministic 8-device CPU mesh; launched from a shell
that does not already say so, this re-execs itself under
tpu_env.clean_cpu_env first, exactly like ``python -m ps_pytorch_tpu.check``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _reexec_clean_env() -> None:
    try:
        from tpu_env import clean_cpu_env, env_is_clean
    except ImportError:
        return  # outside the repo: trust the caller's env
    from ps_pytorch_tpu.check.contracts import MESH_DEVICES

    if env_is_clean(n_devices=MESH_DEVICES):
        return
    os.execve(
        sys.executable,
        [sys.executable, os.path.abspath(__file__), *sys.argv[1:]],
        clean_cpu_env(n_devices=MESH_DEVICES),
    )


def main(argv=None) -> int:
    from ps_pytorch_tpu.tune import load_hardware_profile, run_search
    from ps_pytorch_tpu.tune.search import MODELS

    p = argparse.ArgumentParser(
        "tools/autotune.py",
        description="contract-guarded knob search; see module docstring",
    )
    p.add_argument("--model", required=True, choices=sorted(MODELS))
    p.add_argument("--grid", default="default",
                   choices=("default", "smoke", "tiny"),
                   help="knob grid preset (smoke/tiny are the trimmed "
                        "CI grids)")
    p.add_argument("--trace-only", action="store_true",
                   help="cost-model ranking only: trace + rules + model "
                        "on CPU, no step ever executes")
    p.add_argument("--probe-top", type=int, default=0,
                   help="run short measured probes on the top-K modeled "
                        "candidates (0 = none)")
    p.add_argument("--probe-steps", type=int, default=4,
                   help="measured steps per probe")
    p.add_argument("--ici-gbs", type=float, default=None,
                   help="override the profile's ICI GB/s")
    p.add_argument("--dcn-gbs", type=float, default=None,
                   help="override the profile's DCN GB/s")
    p.add_argument("--out", default=None,
                   help="evidence record path (default: "
                        "runs/autotune_<model>.json)")
    p.add_argument("--top", type=int, default=10,
                   help="ranked rows to print")
    args = p.parse_args(argv)

    if args.trace_only and args.probe_top > 0:
        print("autotune: --trace-only and --probe-top are mutually "
              "exclusive", file=sys.stderr)
        return 2
    if args.probe_top < 0 or args.probe_steps < 1:
        print("autotune: --probe-top must be >= 0 and --probe-steps >= 1",
              file=sys.stderr)
        return 2

    from ps_pytorch_tpu.check.contracts import MESH_DEVICES

    preset = MODELS[args.model]
    profile = load_hardware_profile(
        preset["network"], MESH_DEVICES,
        path=os.path.join(REPO, "runs", "predicted_scaling.json"),
        ici_gbs=args.ici_gbs, dcn_gbs=args.dcn_gbs,
    )
    rec = run_search(
        args.model, grid=args.grid, profile=profile,
        probe_top=args.probe_top, probe_steps=args.probe_steps,
        progress=lambda msg: print(f"# {msg}", file=sys.stderr),
    )

    out = args.out or os.path.join(
        REPO, "runs", f"autotune_{args.model}.json"
    )
    d = os.path.dirname(out)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(out, "w", encoding="utf-8") as f:
        json.dump(rec, f, indent=2, sort_keys=False)
        f.write("\n")

    print(f"# {rec['n_candidates']} candidate(s) ranked, "
          f"{rec['n_pruned']} pruned, {rec['elapsed_s']}s -> {out}",
          file=sys.stderr)
    width = max(
        (len(c["name"]) for c in rec["candidates"][:args.top]), default=4
    )
    print(f"{'rank':>4}  {'config':<{width}}  {'modeled_ms':>10}  "
          f"{'comm_ms':>8}  {'headroom':>8}  {'upd_ops':>7}")
    for c in rec["candidates"][:args.top]:
        cost = c["cost"]
        print(f"{c['rank']:>4}  {c['name']:<{width}}  "
              f"{cost['modeled_step_s'] * 1e3:>10.4f}  "
              f"{cost['comm_s'] * 1e3:>8.4f}  "
              f"{(cost['overlap_headroom'] or 0.0):>8.4f}  "
              f"{cost['update_path_ops']:>7}")
    if rec["best"] is not None:
        speed = rec["gate"]["modeled_speedup"]
        vs = f" ({speed}x the default's modeled cost)" if speed else ""
        print(f"# best: {rec['best']['name']}{vs}")
        print(f"# flags: {rec['best']['flag_line']}")
        print(f"# apply: python -m ps_pytorch_tpu.cli.train "
              f"--config-json {out}")
    return 0 if rec["n_candidates"] else 1


if __name__ == "__main__":
    _reexec_clean_env()
    sys.exit(main())
