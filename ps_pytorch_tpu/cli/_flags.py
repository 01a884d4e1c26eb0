"""Shared argparse surface, mirroring the reference flag names and defaults
(/root/reference/src/distributed_nn.py:24-68, distributed_evaluator.py:39-56,
single_machine.py:24-51) plus the TPU-native extensions.

Deliberate mappings (documented divergences):
- --compress-grad compress|none  -> int8-quantized collectives (Blosc is a
  host-byte codec; on an ICI reduce path the bandwidth lever is quantization.
  The C++ host codec used for checkpoints lives in native/, see ops/codec.py).
- --enable-gpu                    -> accepted, ignored (accelerator selection
  is JAX_PLATFORMS; the reference's type=bool flag was itself broken — any
  non-empty string was True, distributed_nn.py:66).
- --mode/--kill-threshold         -> accepted; straggler kill is meaningless
  under synchronous SPMD dispatch (no stragglers intra-slice); the capability
  it bought — stepping on a subset of gradients — is --num-aggregate.
- --comm-type Bcast|Async         -> accepted, ignored (weights live
  replicated on the mesh; there is nothing to fetch).
"""

from __future__ import annotations

import argparse
import json
import logging

from ..parallel import PSConfig
from ..trainer import TrainConfig

logger = logging.getLogger("ps_pytorch_tpu")


def add_train_flags(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    d = TrainConfig()
    parser.add_argument("--batch-size", type=int, default=d.batch_size,
                        help="per-worker training batch size")
    parser.add_argument("--test-batch-size", type=int, default=d.test_batch_size)
    parser.add_argument("--epochs", type=int, default=d.epochs)
    parser.add_argument("--max-steps", type=int, default=d.max_steps)
    parser.add_argument("--lr", type=float, default=d.lr)
    parser.add_argument("--momentum", type=float, default=d.momentum)
    parser.add_argument("--weight-decay", type=float, default=d.weight_decay)
    parser.add_argument("--optimizer", type=str, default=d.optimizer,
                        choices=("sgd", "adam", "amsgrad"))
    parser.add_argument("--seed", type=int, default=d.seed)
    parser.add_argument("--log-interval", type=int, default=d.log_interval)
    parser.add_argument("--network", type=str, default=d.network)
    parser.add_argument("--dataset", type=str, default=d.dataset)
    parser.add_argument("--eval-freq", type=int, default=d.eval_freq)
    parser.add_argument("--train-dir", type=str, default=d.train_dir)
    parser.add_argument("--data-root", type=str, default=None)
    parser.add_argument("--no-synthetic", action="store_true",
                        help="fail instead of falling back to synthetic data")
    parser.add_argument("--resume", action="store_true",
                        help="resume from the newest checkpoint in --train-dir")
    parser.add_argument("--no-checkpoints", action="store_true")
    parser.add_argument("--compress-checkpoints", action="store_true",
                        help="write checkpoints through the native C++ codec")
    parser.add_argument("--shard-mode", type=str, default=d.shard_mode,
                        choices=("reshuffle", "disjoint"))
    parser.add_argument("--dtype", type=str, default=d.dtype,
                        choices=("float32", "bfloat16"),
                        help="compute dtype (bfloat16 = MXU-native; params stay f32)")
    parser.add_argument("--profile-dir", type=str, default=None,
                        help="write a jax.profiler trace of a bounded "
                             "step window here (see --profile-start/"
                             "--profile-steps)")
    parser.add_argument("--profile-start", type=int, default=None,
                        help="first profiled step (default: one warmup "
                             "step after the run's first step, so "
                             "compilation stays out of the capture)")
    parser.add_argument("--profile-steps", type=int, default=d.profile_steps,
                        help="profiled window length in steps: captures "
                             "[start, start+N)")
    parser.add_argument("--trace", type=str, default=None, metavar="DIR",
                        help="host-phase span tracing (obs/trace.py): "
                             "write this process's span stream "
                             "(trace_train_p<i>.jsonl) into DIR; merge "
                             "and summarize with tools/trace_report.py")
    parser.add_argument("--remat", action="store_true",
                        help="rematerialize ResNet blocks in backward (saves memory)")
    parser.add_argument("--metrics-file", type=str, default=None,
                        help="append machine-readable metrics (one JSON/line)")
    # parity flags: --mode != normal arms the straggler watchdog with
    # --kill-threshold seconds (detection/warning; nothing to kill in SPMD)
    parser.add_argument("--mode", type=str, default="normal")
    parser.add_argument("--kill-threshold", type=float, default=7.0)
    parser.add_argument("--comm-type", type=str, default="Bcast")
    parser.add_argument("--enable-gpu", type=str, default="")
    # resilience (host side)
    parser.add_argument("--straggler-storm-n", type=int,
                        default=d.straggler_storm_n,
                        help="consecutive straggler steps that collapse "
                             "into one straggler_storm event")
    parser.add_argument("--max-consecutive-skips", type=int,
                        default=d.max_consecutive_skips,
                        help="abort after this many consecutive non-finite "
                             "(skipped) steps; 0 = never abort")
    parser.add_argument("--fault-plan", type=str, default=None,
                        help="deterministic fault injection: a JSON "
                             "FaultPlan object or @path to one (also via "
                             "PS_TPU_FAULTS env); see resilience/faults.py")
    parser.add_argument("--adapt-window", type=int, default=d.adapt_window,
                        help="adaptive aggregation window (steps): how often "
                             "the mask count is re-picked from step-time "
                             "stats (with --num-aggregate-min/max)")
    return parser


def _num_aggregate(val: str) -> int:
    # the reference accepted any int here and the engine silently treated
    # out-of-range values as "all workers"; a negative is always a typo
    n = int(val)
    if n < 0:
        raise argparse.ArgumentTypeError(
            f"--num-aggregate must be >= 0 (0 = aggregate all workers), "
            f"got {n}"
        )
    return n


def _bucket_bytes(val: str) -> int:
    # -1 is the only negative with a meaning (legacy per-leaf wire); any
    # other negative is a typo that would otherwise silently select it
    n = int(val)
    if n < -1:
        raise argparse.ArgumentTypeError(
            f"--bucket-bytes must be -1 (per-leaf), 0 (one fused buffer) "
            f"or a positive byte budget, got {n}"
        )
    return n


def add_ps_flags(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    parser.add_argument("--num-workers", type=int, default=0,
                        help="mesh size (0 = all visible devices)")
    parser.add_argument("--num-aggregate", type=_num_aggregate, default=0,
                        help="aggregate only K of N worker gradients per step "
                             "(0 = all; values > num_workers warn and clamp "
                             "to all; reference --num-aggregate)")
    parser.add_argument("--num-aggregate-min", type=int, default=0,
                        help="adaptive partial aggregation lower bound: with "
                             "BOTH bounds set the aggregation count adapts "
                             "per --adapt-window from straggler-watchdog "
                             "step times (needs --mode/--kill-threshold to "
                             "arm the watchdog); 0 = static mask")
    parser.add_argument("--num-aggregate-max", type=int, default=0,
                        help="adaptive partial aggregation upper bound "
                             "(0 = static mask; see --num-aggregate-min)")
    parser.add_argument("--mask-mode", type=str, default="random_k",
                        choices=("random_k", "first_k"))
    parser.add_argument("--compress-grad", type=str, default="none",
                        choices=("compress", "none", "2round"),
                        help="compress -> int8-quantized psum (exact int32 "
                             "sum); 2round -> all_to_all+all_gather whose "
                             "WIRE is int8 (true 4x bandwidth cut, one extra "
                             "bounded quantization on the partial sums)")
    parser.add_argument("--error-feedback", action="store_true",
                        help="EF-SGD: carry each worker's compression "
                             "residual into the next step (needs a "
                             "--compress-grad mode; works with both "
                             "--opt-placement modes)")
    parser.add_argument("--quant-block-size", type=int, default=0,
                        help="per-block quantization scale granularity (0 = per-tensor)")
    parser.add_argument("--bucket-bytes", type=_bucket_bytes, default=-1,
                        help="gradient wire granularity: -1 = legacy "
                             "message-per-leaf collectives, 0 = ONE fused "
                             "flat buffer, N = ~N-byte contiguous buckets "
                             "aligned to the quantization block "
                             "(O(n_buckets) collectives instead of "
                             "O(n_leaves); parallel/buckets.py)")
    parser.add_argument("--overlap", type=str, default="off",
                        choices=("on", "off"),
                        help="pipelined bucket reduction: launch each "
                             "bucket's collective as soon as its leaves' "
                             "gradients are ready (readiness-ordered "
                             "dispatch + per-bucket optimizer updates, "
                             "parallel/buckets.py §6g). Same bytes as the "
                             "serial schedule (PSC109 pins it); off = the "
                             "committed-contract baseline. Default off: "
                             "the CPU A/B shows parity (XLA:CPU runs "
                             "collectives synchronously) — the "
                             "latency-hiding win needs a TPU run to bank")
    parser.add_argument("--quant-rounding", type=str, default="nearest",
                        choices=("nearest", "stochastic"),
                        help="stochastic = unbiased gradient quantization")
    parser.add_argument("--wire-domain", type=str, default="dequant",
                        choices=("dequant", "homomorphic"),
                        help="what the aggregation sums (§6h): dequant = "
                             "widen each quantized hop to f32 to add; "
                             "homomorphic = sum in the compressed domain "
                             "(shared per-bucket scales, exact integer "
                             "accumulation, one deferred scale-multiply "
                             "per bucket at the consumer — the int8 psum "
                             "narrows to int16, the 2round wire drops its "
                             "round-2 scale rows, the hier DCN x ICI "
                             "reassembly ships int8 instead of f32). "
                             "Needs a --compress-grad mode and nearest "
                             "rounding")
    parser.add_argument("--opt-placement", type=str, default="replicated",
                        choices=("replicated", "sharded"),
                        help="where optimizer state lives (sharded = ZeRO-1 PS)")
    parser.add_argument("--bn-mode", type=str, default="pmean",
                        choices=("local", "pmean", "synced"))
    parser.add_argument("--grad-accum-steps", type=int, default=1,
                        help="microbatches accumulated per step (scales the "
                             "effective per-worker batch beyond HBM)")
    parser.add_argument("--dcn-hosts", type=int, default=1,
                        help=">1 = hierarchical dp over a (hosts x chips) "
                             "hybrid mesh (ICI reduce first, one DCN hop)")
    # resilience (device side)
    parser.add_argument("--no-nonfinite-guard", action="store_true",
                        help="disable the device-side non-finite gradient "
                             "guard (skip-step on NaN/Inf; default on)")
    parser.add_argument("--dynamic-loss-scale", action="store_true",
                        help="grow-on-success/back-off-on-overflow loss "
                             "scaling (needs a --compress-grad mode)")
    parser.add_argument("--loss-scale-init", type=float, default=2.0 ** 15)
    parser.add_argument("--loss-scale-growth-interval", type=int,
                        default=2000,
                        help="consecutive good steps before the loss "
                             "scale doubles")
    parser.add_argument("--coordinator-address", type=str, default=None,
                        help="host:port for multi-host DCN rendezvous")
    parser.add_argument("--num-processes", type=int, default=None)
    parser.add_argument("--process-id", type=int, default=None)
    return parser


def _config_json_flags(data) -> dict:
    """Extract the flag dict from a --config-json file: a full autotune
    evidence record (tools/autotune.py output — the best candidate's
    flags apply), one candidate entry, or a bare {flag: value} object."""
    if not isinstance(data, dict):
        raise SystemExit(
            "--config-json: expected a JSON object (an autotune record "
            f"or a flag dict), got {type(data).__name__}"
        )
    if data.get("kind") == "autotune":
        best = data.get("best")
        if not best or "flags" not in best:
            raise SystemExit(
                "--config-json: autotune record has no best candidate "
                "to apply (every point was pruned?)"
            )
        return dict(best["flags"])
    if "flags" in data and isinstance(data["flags"], dict):
        return dict(data["flags"])
    return dict(data)


def expand_config_json(
    parser: argparse.ArgumentParser, argv: list
) -> list:
    """Apply ``--config-json FILE`` by expanding the file's flags into
    the argv BEFORE parsing, so every value still goes through the
    parser's own types and choices.

    Rejections (SystemExit with the reason; exit code 1):
    - an unknown key: the file names a flag this CLI does not define;
    - a flag conflict: a flag set by the file ALSO appears explicitly
      on the command line (argparse prefix abbreviations included — an
      explicit ``--compress-g`` conflicts with a configured
      ``--compress-grad``) — the tuned record and the operator disagree
      about who owns the knob, so neither silently wins.
    Flags NOT set by the file pass through untouched."""
    path = None
    rest: list = []
    i = 0
    while i < len(argv):
        tok = argv[i]
        if tok == "--config-json":
            if i + 1 >= len(argv):
                raise SystemExit("--config-json: missing FILE argument")
            path = argv[i + 1]
            i += 2
            continue
        if tok.startswith("--config-json="):
            path = tok.split("=", 1)[1]
            i += 1
            continue
        rest.append(tok)
        i += 1
    if path is None:
        return argv
    try:
        with open(path, "r", encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        raise SystemExit(f"--config-json: cannot read {path}: {e}")
    flags = _config_json_flags(data)

    by_option = {
        s: a for a in parser._actions for s in a.option_strings
    }
    unknown = sorted(k for k in flags if k not in by_option)
    if unknown:
        raise SystemExit(
            f"--config-json: unknown flag(s) {unknown} in {path} — not "
            f"part of this CLI (typo, or a record from a different tool?)"
        )
    explicit = set()
    for t in rest:
        if not t.startswith("--"):
            continue
        tok = t.split("=", 1)[0]
        # resolve argparse's prefix abbreviations, or an abbreviated
        # explicit flag (--compress-g) would dodge the conflict check
        # and then silently last-wins over the configured value
        matches = [o for o in by_option if o.startswith(tok)]
        explicit.add(matches[0] if len(matches) == 1 else tok)
    conflicts = sorted(k for k in flags if k in explicit)
    if conflicts:
        raise SystemExit(
            f"--config-json: flag(s) {conflicts} are set by {path} AND "
            f"passed explicitly — drop one side (the config file owns "
            f"the tuned knobs; explicit flags own everything else)"
        )
    expanded: list = []
    for k, v in flags.items():
        action = by_option[k]
        if action.nargs == 0:  # store_true/store_false style
            if not isinstance(v, bool):
                raise SystemExit(
                    f"--config-json: {k} takes no value; expected a "
                    f"JSON boolean, got {v!r}"
                )
            if v:
                expanded.append(k)
        else:
            expanded.extend([k, str(v)])
    return expanded + rest


def train_config_from(args: argparse.Namespace) -> TrainConfig:
    return TrainConfig(
        network=args.network,
        dataset=args.dataset,
        batch_size=args.batch_size,
        test_batch_size=args.test_batch_size,
        epochs=args.epochs,
        max_steps=args.max_steps,
        lr=args.lr,
        momentum=args.momentum,
        weight_decay=args.weight_decay,
        optimizer=args.optimizer,
        seed=args.seed,
        log_interval=args.log_interval,
        eval_freq=args.eval_freq,
        train_dir=args.train_dir,
        save_checkpoints=not args.no_checkpoints,
        compress_checkpoints=args.compress_checkpoints,
        resume=args.resume,
        data_root=args.data_root,
        allow_synthetic=not args.no_synthetic,
        shard_mode=args.shard_mode,
        dtype=args.dtype,
        profile_dir=args.profile_dir,
        profile_start=args.profile_start,
        profile_steps=args.profile_steps,
        trace_dir=args.trace,
        remat=args.remat,
        metrics_file=args.metrics_file,
        straggler_threshold_s=(
            args.kill_threshold if args.mode != "normal" else None
        ),
        straggler_storm_n=args.straggler_storm_n,
        max_consecutive_skips=args.max_consecutive_skips,
        fault_plan=args.fault_plan,
        adapt_window=args.adapt_window,
    )


def ps_config_from(args: argparse.Namespace, num_workers: int) -> PSConfig:
    num_aggregate = args.num_aggregate
    if num_aggregate > num_workers:
        # out-of-range used to SILENTLY mean "all workers" — keep the
        # semantics (clamping to N is exactly that) but say so once
        logger.warning(
            "--num-aggregate %d exceeds num_workers %d: clamping to %d "
            "(aggregate all workers)",
            num_aggregate, num_workers, num_workers,
        )
        num_aggregate = num_workers
    return PSConfig(
        num_workers=num_workers,
        num_aggregate=num_aggregate or None,
        num_aggregate_min=args.num_aggregate_min or None,
        num_aggregate_max=args.num_aggregate_max or None,
        mask_mode=args.mask_mode,
        compress={
            "compress": "int8",
            "2round": "int8_2round",
            "none": None,
        }[args.compress_grad],
        quant_block_size=args.quant_block_size,
        quant_rounding=args.quant_rounding,
        wire_domain=args.wire_domain,
        bucket_bytes=(
            None if args.bucket_bytes < 0 else args.bucket_bytes
        ),
        overlap="pipelined" if args.overlap == "on" else "serial",
        error_feedback=args.error_feedback,
        opt_placement=args.opt_placement,
        bn_mode=args.bn_mode,
        grad_accum_steps=args.grad_accum_steps,
        dcn_hosts=args.dcn_hosts,
        nonfinite_guard=not args.no_nonfinite_guard,
        dynamic_loss_scale=args.dynamic_loss_scale,
        loss_scale_init=args.loss_scale_init,
        loss_scale_growth_interval=args.loss_scale_growth_interval,
    )
