"""LR sweep harness (parity: /root/reference/src/tune.sh — a grid of
learning rates each launched as a full mpirun job — plus
tiny_tuning_parser.py:14-27, which regex-parses the worker logs and averages
the reported loss).

Here the sweep runs in-process (one mesh, sequential short runs) and the
scoring path is deliberately the same as the reference's: each run's
iteration log lines are captured and fed through utils.parse_iter_line, and
the candidate's score is the mean loss over its final --score-window steps.
Prints a ranking and returns {lr: score}.
"""

from __future__ import annotations

import argparse
import logging

import jax

from ..data import prepare_data
from ..trainer import Trainer
from ..utils import (
    enable_persistent_compile_cache,
    get_logger,
    parse_iter_line,
)
from ._flags import add_ps_flags, add_train_flags, ps_config_from, train_config_from

logger = get_logger()

DEFAULT_GRID = (0.1, 0.05, 0.02, 0.01, 0.005, 0.002, 0.001)  # tune.sh's 7 LRs


class _LineCapture(logging.Handler):
    def __init__(self):
        super().__init__()
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


def score_lines(lines, window: int) -> float:
    """Mean loss over the last `window` parsed iteration lines
    (tiny_tuning_parser semantics: scrape logs, average loss). A run that
    ever reported a non-finite loss is scored inf — a diverged lr must not
    win on its pre-divergence prefix."""
    import math

    losses = [d["loss"] for d in map(parse_iter_line, lines) if d]
    if not losses or any(not math.isfinite(x) for x in losses):
        return float("inf")
    return sum(losses[-window:]) / len(losses[-window:])


def _sweep(run_one, lr_grid, window) -> dict:
    """Shared grid loop: capture each run's iteration log lines, score
    through the reference's log-parsing semantics, print the ranking."""
    results = {}
    for lr in lr_grid:
        capture = _LineCapture()
        logger.addHandler(capture)
        try:
            run_one(lr)
        finally:
            logger.removeHandler(capture)
        results[lr] = score_lines(capture.lines, window)
        logger.info("lr %g -> mean loss %.4f", lr, results[lr])
    ranking = sorted(results.items(), key=lambda kv: kv[1])
    logger.info("best lr: %g (mean loss %.4f)", *ranking[0])
    return results


def tune_lm(args) -> dict:
    """LR sweep over cli.train_lm (any --parallelism scheme): each grid
    point is a fresh short run scored through the same log-parsing path
    the CNN sweep (and the reference's tiny_tuning_parser) uses. The
    shared training flags (optimizer, weight decay, dtype) forward."""
    from .train_lm import main as lm_main

    def run_one(lr):
        lm_main(
            [
                "--parallelism", args.lm_parallelism,
                "--seq-len", str(args.lm_seq_len),
                "--dim", str(args.lm_dim),
                "--depth", str(args.lm_depth),
                "--heads", str(args.lm_heads),
                "--vocab-size", str(args.lm_vocab_size),
                "--max-steps", str(args.max_steps),
                "--batch-size", str(args.batch_size),
                "--log-interval", "1",
                "--lr", str(lr),
                "--seed", str(args.seed),
                "--optimizer", args.optimizer,
                "--momentum", str(args.momentum),
                "--weight-decay", str(args.weight_decay),
                "--dtype", args.dtype,
            ]
        )

    return _sweep(run_one, args.lr_grid, args.score_window)


def main(argv=None) -> dict:
    # sweep candidates re-jit the same step; the persistent cache makes a
    # re-run of the sweep (and any HLO-identical candidate) compile-free
    enable_persistent_compile_cache()
    parser = argparse.ArgumentParser("ps_pytorch_tpu.cli.tune")
    add_train_flags(parser)
    add_ps_flags(parser)
    parser.add_argument("--lr-grid", type=float, nargs="+",
                        default=list(DEFAULT_GRID))
    parser.add_argument("--score-window", type=int, default=10,
                        help="average the loss over the final N logged steps")
    parser.add_argument("--workload", default="ps", choices=["ps", "lm"],
                        help="ps: CNN PS trainer; lm: train_lm sweep")
    parser.add_argument("--lm-parallelism", default="dp_sp")
    parser.add_argument("--lm-seq-len", type=int, default=128)
    parser.add_argument("--lm-dim", type=int, default=128)
    parser.add_argument("--lm-depth", type=int, default=2)
    parser.add_argument("--lm-heads", type=int, default=4)
    parser.add_argument("--lm-vocab-size", type=int, default=64)
    args = parser.parse_args(argv)

    if args.workload == "lm":
        return tune_lm(args)

    num_workers = args.num_workers or len(jax.devices())
    base = train_config_from(args)
    dataset = prepare_data(
        base.dataset, root=base.data_root, allow_synthetic=base.allow_synthetic
    )  # load once; each grid point reuses it

    def run_one(lr):
        tcfg = train_config_from(args)
        tcfg.lr = lr
        tcfg.log_interval = 1  # score every step
        tcfg.save_checkpoints = False
        tcfg.resume = False  # every candidate must start from scratch
        pcfg = ps_config_from(args, num_workers)
        Trainer(tcfg, pcfg, dataset=dataset).train()

    return _sweep(run_one, args.lr_grid, args.score_window)


if __name__ == "__main__":
    main()
