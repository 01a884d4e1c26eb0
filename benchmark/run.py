"""One run of one cell:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (build, seeded weights and data, the three steps the reference
follows, warm-up until the block time has settled) ends where the measured
window starts. The last line of stdout is the result; the lines before it
explain it. No accelerator is an error, never a CPU run.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(tag: str, obj) -> None:
    print(f"[bench] {tag} {json.dumps(obj)}", flush=True)


def setup_jax():
    """One cache rule: JAX_COMPILATION_CACHE_DIR if set, else the checkout's
    .jax_cache; every program is kept, however quickly it compiled."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def find_chips(jax, need: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no accelerator: jax sees {devices[0].platform} devices; "
                         "the benchmark never runs on the CPU")
    if len(devices) < need:
        raise SystemExit(f"the cell needs {need} chips and jax sees {len(devices)}")
    return devices[:need]


def memory_peak(devices) -> int:
    """Peak bytes on the fullest chip. The TPU runtime counts live buffers
    (`bytes_in_use`) apart from what a running program reserves for its
    temporaries (`bytes_reserved`), so the peak of a step is the buffers
    live now, in steady state, plus the largest reservation; the peak of
    live buffers alone can be higher during set-up."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(max(st.get("peak_bytes_in_use", 0),
                         st.get("bytes_in_use", 0) + st.get("peak_bytes_reserved", 0)))
    return int(max(peaks))


def read_spans(path: str):
    """The program's span stream with each span's time on the host clock."""
    if not path or not os.path.exists(path):
        return []
    base, out = 0.0, []
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("kind") == "run_header":
                base = rec["t_mono"]
            elif rec.get("kind") == "span":
                out.append({**rec, "t_abs": rec["t"] + base})
    return out


def per_layer_metrics(cell, res, devices, peaks, setup_compile_s, record_to=None):
    from benchmark import reducers
    from benchmark.reducers import trace as tr

    evd = res["evidence"]
    k = int(cell.traffic["block_steps"])
    capture = tr.load(evd["profile_dir"])
    if record_to:  # tools/record_trace.py: keep the whole capture
        os.makedirs(record_to, exist_ok=True)
        tr.save_json(capture, os.path.join(record_to, cell.name + ".trace.json.gz"))
        with open(os.path.join(record_to, cell.name + ".planes.json"), "w") as f:
            json.dump(tr.describe(evd["profile_dir"]), f, indent=1)
    trace, steps = tr.trim(capture, evd["trace_blocks"], k)
    ev = {
        "trace": trace, "cell": cell, "peaks": peaks,
        "steps_traced": steps, "window_t0": evd["window_t0"],
        "spans": read_spans(evd.get("spans_file")),
        "memory_peak_bytes": res["memory_peak_bytes"],
        "counters": {**evd.get("counters", {}), "compile_s": setup_compile_s},
    }
    metrics = {}
    for m in cell.per_layer:
        value = reducers.reduce(m["kind"], m.get("args", {}), ev)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    busy, window = tr.busy_and_window(trace)
    # beside the traced stretch, the same share from this run's last untraced
    # (warm-up) block on the host clock: they agree unless the profiler
    # disturbs the host path it watches
    block_s = evd["warmup_block_s"][-1]
    say("traced", {"steps_read": steps, "busy_s": busy, "window_s": window,
                   "traced_step_ms": 1e3 * window / steps if steps else None,
                   "untraced_step_ms": 1e3 * block_s / k,
                   "idle_pct_traced_stretch": 100 * (1 - busy / window) if window else None,
                   "idle_pct_by_untraced_block": (100 * (1 - busy / steps * k / block_s)
                                                  if steps else None),
                   "gaps_between_runs_ms": tr.run_gaps_ms(capture)})
    return metrics, {"busy_s": busy, "window_s": window}, tr.breakdown(trace)


def model_flop_utilization_pct(cell, items_per_s: float, chips: int, peaks: dict) -> float:
    """The throughput in other units: the operations the forward and
    backward passes need (the configuration's own module under flops/) over
    the chips' bf16 peak."""
    from benchmark import flops

    per_item = flops.load(cell.config["flops"]).train_flops_per_item(cell.config, cell.traffic)
    return 100.0 * items_per_s * per_item / (chips * peaks["bf16_flops_per_s"])


def run_cell(cell, seed, seconds, trace, devices, peaks, record_to=None) -> int:
    from benchmark import compare, drivers
    from benchmark.compiles import CompileWatch

    watch = CompileWatch().install()
    out_dir = tempfile.mkdtemp(prefix="bench_")
    try:
        ctx = {"out_dir": out_dir, "profile_dir": os.path.join(out_dir, "profile"),
               "compiles": watch, "devices": devices}
        res = drivers.load(cell.kind).run(cell, seed, seconds, bool(trace), ctx)
        setup_s = res["setup_end"] - T_START
        res["memory_peak_bytes"] = memory_peak(devices)
        say("memory_stats", devices[0].memory_stats())
        compile_s = watch.backend_compile_s  # the window compiled nothing
        say("setup_compiles", watch.counts())
        say("setup_marks_s", {k: v - T_START for k, v in res["evidence"]["marks"].items()})
        say("warmup_block_s", res["evidence"]["warmup_block_s"])
        say("blocks", res["blocks"])
        if res["window_compiles"]:
            say("error", {"compiled_inside_window": watch.programs[-res["window_compiles"]:]})
            return 4
        device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
                  "count": len(devices), "memory_peak_bytes": res["memory_peak_bytes"]}
        if trace:
            metrics, traced, breakdown = per_layer_metrics(
                cell, res, devices, peaks, compile_s, record_to)
            device.update(traced)
        else:
            metrics = {m["name"]: {"value": float(res["end_to_end"][m["name"]]),
                                   "unit": m["unit"]}
                       for m in cell.end_to_end if m["name"] in res["end_to_end"]}
            metrics["setup_s"] = {"value": setup_s, "unit": "s"}
            breakdown = None
            say("model_flop_utilization_pct", model_flop_utilization_pct(
                cell, res["blocks"]["window_rate"], len(devices), peaks))
        # the reference runs after the window and after the peak was read,
        # with the program's state freed: its time is in no metric
        t_ref = time.perf_counter()
        ref = res["reference"]()
        numbers = compare.training_numbers(res["prog"], ref)
        for which in ("grad_norms", "dparam_norms"):
            say("worst_leaves_" + which, compare.worst_leaves(
                res["prog"][which], ref[which], ref["leaf_names"]))
        correct, rows = compare.decide(numbers, cell.limits)
        for row in rows:
            say("compared", row)
        say("reference_s", time.perf_counter() - t_ref)
        line = {"correct": bool(correct), "attempted": int(res["attempted"]),
                "failed": int(res["failed"]), "metrics": metrics, "device": device}
        if breakdown is not None:
            line["breakdown"] = breakdown
        print(json.dumps(line), flush=True)
        return 0
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmark import spec

    cell = spec.load_cell(args.workload)
    import ps_pytorch_tpu  # noqa: F401  (absent: not a checkout of the program)

    jax = setup_jax()
    devices = find_chips(jax, cell.chips)
    peaks = spec.load_peaks(devices[0].device_kind)
    return run_cell(cell, args.seed, args.seconds, args.trace, devices, peaks)


if __name__ == "__main__":
    sys.exit(main())
