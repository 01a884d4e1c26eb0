"""Traffic kind `ps_train`: the PS trainer as `cli.train` builds it.

The trainer's own loop is the timed path. Its log interval is the block:
`Trainer.train()` drains the device once per interval and logs one line,
and a logging handler of the benchmark reads the host clock at each such
line. The first call runs the three steps the reference follows (interval
1, so the state after step 1 can be read), the second runs warm-up and the
window on the same trainer, state and compiled step, and is ended with
`request_stop()` at the first block boundary at or after --seconds.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import time

import numpy as np

from benchmark import blocks, reference, weights
from benchmark.reducers import trace as tr


def _trainer_args(cell, seed, ctx, trace):
    from ps_pytorch_tpu.cli._flags import (
        add_ps_flags, add_train_flags, ps_config_from, train_config_from)

    t = cell.traffic
    argv = list(t["argv"]) + ["--seed", str(seed % (2 ** 31 - 1)),
                              "--metrics-file", os.path.join(ctx["out_dir"], "metrics.jsonl")]
    if trace:
        argv += ["--trace", os.path.join(ctx["out_dir"], "spans")]
    parser = argparse.ArgumentParser()
    add_train_flags(parser)
    add_ps_flags(parser)
    args = parser.parse_args(argv)
    return train_config_from(args), ps_config_from(args, cell.chips)


def _feed_rows(tcfg, pcfg, n_rows, steps):
    """Which rows each worker is fed in the first steps: the trainer's own
    iterator run over row numbers in place of pixels."""
    from ps_pytorch_tpu.data import BatchIterator, shard_for_worker

    ids = np.arange(n_rows, dtype=np.int32)
    rows = np.empty((steps, pcfg.num_workers, tcfg.batch_size), np.int64)
    for w in range(pcfg.num_workers):
        a, b, s = shard_for_worker(ids, ids, w, pcfg.num_workers,
                                   mode=tcfg.shard_mode, seed=tcfg.seed)
        it = BatchIterator(a, b, tcfg.batch_size, seed=s).epoch()
        for k in range(steps):
            rows[k, w] = next(it)["label"]
    return rows


class _Session:
    """The trainer with seeded data and weights, and the clock on its log."""

    def __init__(self, cell, seed, ctx, trace=False):
        import jax
        import jax.numpy as jnp
        from ps_pytorch_tpu.data import Dataset
        from ps_pytorch_tpu.parallel import FlatVector, shard_state, tree_view
        from ps_pytorch_tpu.parallel.buckets import to_flat_vector
        from ps_pytorch_tpu.trainer import Trainer

        self.cell, self.seed, self.ctx = cell, seed, ctx
        t = cell.traffic
        self.tcfg, self.pcfg = _trainer_args(cell, seed, ctx, trace)
        images, labels = weights.cifar_like(seed, int(t["train_rows"]),
                                            int(cell.config["num_classes"]))
        self.images, self.labels = images, labels
        ds = Dataset(cell.config["dataset"], images, labels,
                     images[:64], labels[:64], synthetic=True)
        self.trainer = tr = Trainer(self.tcfg, self.pcfg, dataset=ds)
        self.ref = reference.load(cell.config["reference"])
        self.shapes = self.ref.param_shapes(cell.config)
        if not weights.same_tree(tree_view(tr.state.params), self.shapes):
            raise SystemExit("the trainer's parameter tree is not the one "
                             f"configs/{cell.config_name}.json describes")
        self.w0 = weights.make_weights(self.shapes, seed)
        params = tr.state.params
        new = (to_flat_vector(self.w0, params.plan)
               if isinstance(params, FlatVector) else self.w0)
        tr.state = shard_state(tr.state.replace(params=new), tr.mesh, self.pcfg)
        self._norms = jax.jit(lambda p: weights.leaf_norms(tree_view(p)))
        self._change = jax.jit(lambda p, w0: weights.leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, tree_view(p), w0)))
        self.on_block = None
        self._handler = _LogClock(self)
        logging.getLogger("ps_pytorch_tpu").addHandler(self._handler)

    def close(self):
        logging.getLogger("ps_pytorch_tpu").removeHandler(self._handler)

    def records(self):
        with open(self.tcfg.metrics_file) as f:
            recs = [json.loads(line) for line in f]
        return {r["step"]: r for r in recs if r.get("kind") == "train"}

    def first_steps(self):
        """Drives the first steps through the trainer's loop; returns what
        the reference is compared with."""
        n = int(self.cell.traffic["check_steps"])
        got = {}

        def on_block(step, now):
            st = self.trainer.state
            if step == 1:
                got["grad"] = self._norms(st.opt_state.momentum_buffer)
            if step == n:
                got["dparam"] = self._change(st.params, self.w0)

        self.on_block = on_block
        self.tcfg.log_interval, self.tcfg.max_steps = 1, n
        self.trainer.train()
        recs = self.records()
        return {
            "loss": [recs[s]["loss"] for s in range(1, n + 1)],
            "grad_norms": np.asarray(got["grad"]).tolist(),
            "dparam_norms": np.asarray(got["dparam"]).tolist(),
        }

    def reference_numbers(self, operand=None):
        t = self.cell.traffic
        n = int(t["check_steps"])
        feed = {
            "images": self.images, "labels": self.labels,
            "rows": _feed_rows(self.tcfg, self.pcfg, len(self.images), n),
            "feed_seed": self.tcfg.seed,
        }
        return self.ref.train_steps(
            self.cell.config, t, lambda: weights.make_weights(self.shapes, self.seed),
            feed, n, devices=self.ctx.get("devices"), operand=operand)


class _LogClock(logging.Handler):
    """Reads the host clock at each of the trainer's per-window log lines,
    which it writes right after draining the device."""

    def __init__(self, session):
        super().__init__(logging.INFO)
        self.s = session

    def emit(self, record):
        from ps_pytorch_tpu.utils import parse_iter_line

        now = time.perf_counter()
        line = parse_iter_line(record.getMessage())
        if line is not None and self.s.on_block is not None:
            self.s.on_block(int(line["step"]), now)


def check(cell, seed, control, ctx):
    """The first steps' numbers of the program and of the reference; with
    `control`, the reference at the control's operand precision stands in
    the program's place."""
    s = _Session(cell, seed, ctx)
    try:
        if control:
            prog = s.reference_numbers(operand=cell.traffic["control_operand"])
        else:
            prog = s.first_steps()
    finally:
        s.close()
    s.trainer = None
    return prog, s.reference_numbers()


def run(cell, seed, seconds, trace, ctx):
    import jax

    t = cell.traffic
    k = int(t["block_steps"])
    stamps = {"driver_start": time.perf_counter()}
    s = _Session(cell, seed, ctx, trace=trace)
    stamps["built"] = time.perf_counter()
    watch = ctx["compiles"]
    prog = s.first_steps()
    stamps["first_steps"] = time.perf_counter()
    per_block = k * s.tcfg.batch_size * s.pcfg.num_workers
    warm, window, marks = [], [], {}
    trace_blocks = 2  # read from the first's third run to the second's first

    def on_block(step, now):
        last, count = marks.get("last"), marks.get("count")
        marks["last"], marks["count"] = now, watch.count
        if last is None or step % k:
            return  # the partial block after the first steps
        if "t0" not in marks:
            clean = count == watch.count  # nothing compiled inside this block
            marks["since"] = marks.get("since", 0) + 1 if clean else 0
            warm.append(now - last)
            if blocks.settled(warm, marks["since"]):
                marks.update(t0=now, step0=step, compiles0=watch.count)
                if trace:
                    tr.start(ctx["profile_dir"])
                    marks["tracing"] = True
            return
        window.append(now - last)
        if trace and len(window) == trace_blocks:
            jax.profiler.stop_trace()
            marks["tracing"] = False
        if now - marks["t0"] >= seconds and not marks.get("tracing"):
            s.trainer.request_stop()

    s.on_block = on_block
    s.tcfg.log_interval, s.tcfg.max_steps = k, 10 ** 9
    try:
        s.trainer.train()
    finally:
        s.close()
        if marks.get("tracing"):
            jax.profiler.stop_trace()
    recs = s.records()
    first, last = recs[marks["step0"]], recs[max(recs)]
    skipped = int(last.get("skipped_steps", 0) - first.get("skipped_steps", 0))
    evidence = {"warmup_block_s": warm, "marks": stamps, "counters": {
        "window_compiles": watch.count - marks["compiles0"]}}
    if trace:
        evidence.update(
            spans_file=os.path.join(ctx["out_dir"], "spans", "trace_train_p0.jsonl"),
            window_t0=marks["t0"], trace_blocks=trace_blocks,
            profile_dir=ctx["profile_dir"])
    s.trainer = None
    return {
        "setup_end": marks["t0"],
        "end_to_end": {"train_images_per_s": blocks.window_rate(per_block, window)},
        "blocks": blocks.summary(per_block, "images/s", window),
        "attempted": len(window) * k,
        "failed": skipped,
        "window_compiles": watch.count - marks["compiles0"],
        "prog": prog,
        "reference": s.reference_numbers,
        "evidence": evidence,
    }
