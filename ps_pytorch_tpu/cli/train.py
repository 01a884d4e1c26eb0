"""Distributed PS training entry (parity: /root/reference/src/distributed_nn.py
+ run_pytorch.sh). One process per host drives the whole mesh — the mpirun
rank dispatch (distributed_nn.py:109-126) has no TPU equivalent; SPMD jit
replaces the master/worker split.

Canonical invocation (reference run_pytorch.sh semantics):
  python -m ps_pytorch_tpu.cli.train --network ResNet18 --dataset Cifar10 \
      --batch-size 128 --lr 0.1 --momentum 0.9 --num-aggregate 5 \
      --compress-grad compress --train-dir output/models/

Multi-device smoke (8 virtual CPU devices):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m ps_pytorch_tpu.cli.train --num-workers 8 --max-steps 5
"""

from __future__ import annotations

import argparse
import sys

import jax

from ..obs import setup_tracer
from ..parallel import initialize_multihost
from ..trainer import Trainer
from ..utils import enable_persistent_compile_cache, get_logger
from ._flags import (
    add_ps_flags,
    add_train_flags,
    expand_config_json,
    ps_config_from,
    train_config_from,
)

logger = get_logger()


def main(argv=None) -> dict:
    enable_persistent_compile_cache()
    parser = argparse.ArgumentParser("ps_pytorch_tpu.cli.train")
    add_train_flags(parser)
    add_ps_flags(parser)
    parser.add_argument(
        "--config-json", metavar="FILE",
        help="apply a tuned knob set from an autotune evidence record "
             "(tools/autotune.py output; the best candidate's flags) or "
             "a bare {flag: value} JSON object. Unknown keys and flags "
             "that also appear explicitly on the command line are "
             "rejected (see cli/_flags.expand_config_json)",
    )
    # --config-json expands into real argv tokens BEFORE parsing, so the
    # file's values ride the parser's own types/choices validation
    argv = expand_config_json(
        parser, list(sys.argv[1:] if argv is None else argv)
    )
    args = parser.parse_args(argv)

    # the process's set-up record opens here; the Trainer's first log step
    # prints where the time to the first step went (obs/trace.setup_summary)
    with setup_tracer().span("setup.devices"):
        initialize_multihost(
            coordinator_address=args.coordinator_address,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
        num_workers = args.num_workers or len(jax.devices())
    tcfg = train_config_from(args)
    pcfg = ps_config_from(args, num_workers)
    trainer = Trainer(tcfg, pcfg)
    # SIGTERM/SIGINT -> checkpoint + clean exit; rerun with --resume
    trainer.install_signal_handlers()
    metrics = trainer.train()
    logger.info("training done: %s", metrics)
    # past the loop the handlers' flag is no longer read: put the previous
    # handlers back so Ctrl-C during validation (or in an embedding app)
    # behaves normally again
    trainer.restore_signal_handlers()
    if trainer.stop_requested:
        # preemption path: the checkpoint is written — exit before the
        # grace window closes instead of starting a full validation pass
        logger.warning("stopped by signal: skipping validation")
        return {"train": metrics, "val": None}
    val = trainer.validate()
    return {"train": metrics, "val": val}


if __name__ == "__main__":
    main()
