"""Out-of-band polling evaluator (parity: /root/reference/src/
distributed_evaluator.py + evaluate_pytorch.sh).

A separate process that shares only a filesystem with the trainer: it polls
--model-dir for new `model_step_{N}` checkpoints (every --poll-interval
seconds, reference default 10s — distributed_evaluator.py:88), loads each,
and reports test loss / Prec@1 / Prec@5 (distributed_evaluator.py:90-106).
`--once` evaluates the newest checkpoint and exits; `--timeout` stops after
that many idle seconds.

Checkpoints are loaded structure-free (checkpoint.load_checkpoint_raw), so
the evaluator needs only --network/--dataset — never the trainer's
optimizer, placement, or BN-mode configuration. Per-worker ("local") BN
stats saved with a stacked leading worker axis are averaged for evaluation.
"""

from __future__ import annotations

import argparse
from typing import Optional

import jax
import jax.numpy as jnp

from .. import checkpoint as ckpt
from ..data import (
    BatchIterator,
    make_preprocessor,
    prefetch_to_device,
    prepare_data,
)
from ..models import apply_model, build_model, init_model, input_shape_for
from ..ops.metrics import accuracy, cross_entropy_loss
from ..trainer import average_metrics
from ..utils import (
    enable_persistent_compile_cache,
    format_eval_line,
    get_logger,
)

logger = get_logger()


class Evaluator:
    """Loads step-tagged checkpoints and runs the test split on one device."""

    def __init__(
        self,
        network: str,
        dataset_name: str,
        model_dir: str,
        eval_batch_size: int = 1000,
        data_root: Optional[str] = None,
        allow_synthetic: bool = True,
    ):
        self.model_dir = model_dir
        self.dataset = prepare_data(
            dataset_name, root=data_root, allow_synthetic=allow_synthetic
        )
        self.model = build_model(network, num_classes=self.dataset.num_classes)
        # only used to recognize the expected batch_stats leaf ranks
        _, self._bn_template = init_model(
            self.model, jax.random.key(0), input_shape_for(network)
        )
        pre = make_preprocessor(dataset_name, train=False)

        def eval_fn(params, batch_stats, images, labels):
            x = pre(None, images)
            logits, _ = apply_model(self.model, params, batch_stats, x, train=False)
            loss = cross_entropy_loss(logits, labels)
            prec1, prec5 = accuracy(logits, labels, (1, 5))
            return {"loss": loss, "prec1": prec1, "prec5": prec5}

        self._eval_fn = jax.jit(eval_fn)
        self.eval_batch_size = eval_batch_size

    def _extract(self, raw: dict):
        """Pull params/batch_stats out of a raw checkpoint dict; average
        stacked per-worker BN stats (bn_mode='local' trainer runs)."""
        params = raw["params"]
        batch_stats = raw.get("batch_stats") or {}
        expected = jax.tree_util.tree_leaves(self._bn_template)
        got = jax.tree_util.tree_leaves(batch_stats)
        if expected and got and got[0].ndim == expected[0].ndim + 1:
            batch_stats = jax.tree_util.tree_map(
                lambda x: jnp.mean(x, axis=0), batch_stats
            )
        return params, batch_stats

    def evaluate_step(self, step: int) -> dict:
        params, batch_stats = self._extract(
            ckpt.load_checkpoint_raw(self.model_dir, step)
        )
        it = BatchIterator(
            self.dataset.test_images,
            self.dataset.test_labels,
            self.eval_batch_size,
            shuffle=False,
        )
        # same prefetch path as the trainer (data.prefetch_to_device):
        # batch k+1's host->device transfer overlaps eval on batch k.
        # This evaluator runs the model on ONE device, so the default
        # placement is the sharding here; a mesh consumer passes
        # parallel.batch_sharding instead (trainer.validate does).
        prefetched = prefetch_to_device(iter(it), size=2)
        out = average_metrics(
            lambda b: self._eval_fn(
                params, batch_stats, b["image"], b["label"]
            ),
            prefetched,
        )
        logger.info(format_eval_line(step, out["loss"], out["prec1"], out["prec5"]))
        return out

    def run(
        self,
        poll_interval: float = 10.0,
        timeout: Optional[float] = None,
        once: bool = False,
    ) -> dict:
        results = {}
        if once:
            # newest VALID step: a corrupt/truncated latest file must not
            # kill the one-shot evaluation when an older good one exists
            step = ckpt.latest_valid_step(self.model_dir)
            if step is None:
                logger.info("no checkpoints in %s", self.model_dir)
                return results
            results[step] = self.evaluate_step(step)
            return results
        for step in ckpt.poll_checkpoints(
            self.model_dir, interval_s=poll_interval, timeout_s=timeout
        ):
            results[step] = self.evaluate_step(step)
        return results


def main(argv=None) -> dict:
    enable_persistent_compile_cache()
    parser = argparse.ArgumentParser("ps_pytorch_tpu.cli.evaluate")
    parser.add_argument("--eval-batch-size", type=int, default=1000)
    parser.add_argument("--model-dir", type=str, default="output/models/")
    parser.add_argument("--dataset", type=str, default="MNIST")
    parser.add_argument("--network", type=str, default="LeNet")
    parser.add_argument("--data-root", type=str, default=None)
    parser.add_argument("--no-synthetic", action="store_true")
    parser.add_argument("--poll-interval", type=float, default=10.0)
    parser.add_argument("--timeout", type=float, default=None,
                        help="stop after this many idle seconds (default: poll forever)")
    parser.add_argument("--once", action="store_true",
                        help="evaluate the newest checkpoint and exit")
    args = parser.parse_args(argv)
    ev = Evaluator(
        args.network,
        args.dataset,
        args.model_dir,
        eval_batch_size=args.eval_batch_size,
        data_root=args.data_root,
        allow_synthetic=not args.no_synthetic,
    )
    return ev.run(
        poll_interval=args.poll_interval, timeout=args.timeout, once=args.once
    )


if __name__ == "__main__":
    main()
