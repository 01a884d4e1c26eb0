"""Single-device baseline entry (parity: /root/reference/src/single_machine.py,
nn_ops.py:29-106 — the "measure scalability against this" oracle, README.md:38).

Identical math to cli.train with a 1-device mesh; exists as a separate entry
point so the scalability-baseline workflow carries over name-for-name.
"""

from __future__ import annotations

import argparse

from ..parallel import PSConfig
from ..trainer import Trainer
from ..utils import enable_persistent_compile_cache, get_logger
from ._flags import add_train_flags, train_config_from

logger = get_logger()


def main(argv=None) -> dict:
    enable_persistent_compile_cache()
    parser = argparse.ArgumentParser("ps_pytorch_tpu.cli.single_machine")
    add_train_flags(parser)
    args = parser.parse_args(argv)
    tcfg = train_config_from(args)
    pcfg = PSConfig(num_workers=1)
    trainer = Trainer(tcfg, pcfg)
    metrics = trainer.train()
    logger.info("training done: %s", metrics)
    val = trainer.validate()
    return {"train": metrics, "val": val}


if __name__ == "__main__":
    main()
