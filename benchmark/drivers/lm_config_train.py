"""Traffic kind `lm_config_train`: the LM step of `cli.train_lm
--parallelism dp_sp --lm-config <file>`, for a configuration that carries a
model's published keys (`model_type` and the rest). The family is whatever
the program's own `models.lm.load_lm_config` makes of the file; no model is
named here. The loop, the blocks and their close (a host read of the loss,
then `host_sync(params)`, then the step's counters) are those of the
`lm_train` kind, whose session this one extends. In a traced run the
counters of every traced step are kept on the device, and their means over
the steps that `reducers/trace.trim` reads go into the evidence as
`<counter>_traced`: a kernel whose work is sized at run time is held
against the work of the steps its time was read in.

A reference may describe a group of alike leaves one entry each (a list
under a key its `GROUPS` names, so that benchmark/weights.py draws each at
its own fan-in) where the program holds them stacked: `stacked` /
`unstacked` turn one form into the other, and every norm is taken leaf by
leaf in the reference's form.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import blocks, reference, weights
from benchmark.drivers import lm_train
from benchmark.reducers import trace as tr


def _walk(tree, groups, fn):
    if isinstance(tree, dict):
        return {k: fn(v) if k in groups else _walk(v, groups, fn) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return [_walk(v, groups, fn) for v in tree]
    return tree


def stacked(tree, groups):
    """Lists under a key of `groups` -> one tree of leaves with a leading axis."""
    import jax
    import jax.numpy as jnp

    return _walk(tree, groups,
                 lambda group: jax.tree_util.tree_map(lambda *a: jnp.stack(a), *group))


def unstacked(tree, groups):
    import jax

    def split(group):
        n = jax.tree_util.tree_leaves(group)[0].shape[0]
        return [jax.tree_util.tree_map(lambda a: a[i], group) for i in range(n)]

    return _walk(tree, groups, split)


class _Session(lm_train._Session):
    def __init__(self, cell, seed, ctx):
        import jax
        import jax.numpy as jnp
        from ps_pytorch_tpu.models.lm import load_lm_config
        from ps_pytorch_tpu.optim import build_optimizer
        from ps_pytorch_tpu.parallel.dp_sp import (
            init_lm_state, make_lm_train_step, make_mesh_2d, shard_tokens_2d)
        from ps_pytorch_tpu.parallel.mesh import replicated_sharding

        c, t = cell.config, cell.traffic
        self.cell, self.seed = cell, seed
        self.cfg = load_lm_config(
            c, attention_impl=t["attention_impl"], remat=bool(t["remat"]),
            compute_dtype=jnp.bfloat16 if t["dtype"] == "bfloat16" else None)
        tx = build_optimizer(t["optimizer"], float(t["lr"]), b1=float(t["b1"]),
                             b2=float(t["b2"]), eps=float(t["eps"]))
        devices = jax.devices()[: int(t["num_dp"]) * int(t["num_sp"])]
        self.mesh = make_mesh_2d(int(t["num_dp"]), int(t["num_sp"]), devices=devices)
        params, self.opt = init_lm_state(
            self.cfg, tx, jax.random.key(seed % (2 ** 31 - 1)), self.mesh)
        self.ref = reference.load(c["reference"])
        self.shapes = self.ref.param_shapes(c)
        groups = tuple(getattr(self.ref, "GROUPS", ()))
        split = lambda tree: unstacked(tree, groups)
        if not weights.same_tree(jax.eval_shape(split, params), self.shapes):
            raise SystemExit("the LM's parameter tree is not the one "
                             f"configs/{cell.config_name}.json describes")
        del params
        self.params = jax.device_put(jax.jit(lambda tree: stacked(tree, groups))(self.make_params()),
                                     replicated_sharding(self.mesh))
        self._step = make_lm_train_step(self.cfg, tx, self.mesh)
        self._put = lambda tok: shard_tokens_2d(jnp.asarray(tok), self.mesh)
        self.tokens = weights.token_rows(seed, int(t["corpus_rows"]),
                                         int(t["seq_len"]), int(c["vocab_size"]))
        self._order = np.random.default_rng(seed + 1)
        self.rows = []
        self.counters = {}      # the step's scalar counters, read at a block's close
        self._pending = {}
        self.traced = None      # a list while a capture runs: every step's counters
        b1 = float(t["b1"])
        self._grad_norms = jax.jit(
            lambda m: weights.leaf_norms(split(m)) / (1.0 - b1))
        self._change = jax.jit(lambda p, w0: weights.leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, split(p), w0)))

    def step(self):
        rows = self.draw_rows()
        self.params, self.opt, loss, *rest = self._step(
            self.params, self.opt, self._put(self.tokens[rows]))
        self._pending = {k: v for k, v in (rest[0] if rest else {}).items() if v.ndim == 0}
        if self.traced is not None:
            self.traced.append(self._pending)
        return loss

    def close_block(self, loss):
        import jax

        value, _ = super().close_block(loss)
        self.counters = {k: float(v) for k, v in jax.device_get(self._pending).items()}
        return value, time.perf_counter()

    def traced_means(self, blocks, block_steps):
        """`<counter>_traced`: each counter's mean over the traced steps
        that `reducers/trace.trim` reads (the same slice of the capture's
        `blocks` x `block_steps` runs)."""
        import jax

        read = jax.device_get(self.traced)[tr.BOUNDARY_RUNS:(blocks - 1) * block_steps + 1]
        return {f"{name}_traced": float(np.mean([step[name] for step in read]))
                for name in (read[0] if read else {})}


def check(cell, seed, control, ctx):
    """The program's first steps against the reference; with `control`, the
    reference at the control's operand precision in the program's place."""
    s = _Session(cell, seed, ctx)
    if control:
        for _ in range(int(cell.traffic["check_steps"])):
            s.draw_rows()
        s.free()
        prog = s.reference_numbers(operand=cell.traffic["control_operand"])
    else:
        prog = s.first_steps()
        s.free()
    return prog, s.reference_numbers()


def run(cell, seed, seconds, trace, ctx):
    import jax

    t = cell.traffic
    k = int(t["block_steps"])
    per_block = k * int(t["batch_rows"]) * int(t["seq_len"])
    watch = ctx["compiles"]
    stamps = {"driver_start": time.perf_counter()}
    s = _Session(cell, seed, ctx)
    stamps["built"] = time.perf_counter()
    prog = s.first_steps()
    stamps["first_steps"] = time.perf_counter()
    trace_blocks = 4  # of two steps: read from the second.s first run to the fourth.s first

    def block():
        loss = None
        for _ in range(k):
            loss = s.step()
        return s.close_block(loss)

    _, last = s.close_block(s.step())  # a step of its own: drains the first ones
    warm, since = [], 0
    while True:
        count = watch.count
        _, now = block()
        since = since + 1 if count == watch.count else 0
        warm.append(now - last)
        last = now
        if blocks.settled(warm, since):
            break
    t0, compiles0 = last, watch.count
    window, tracing = [], bool(trace)
    if trace:
        tr.start(ctx["profile_dir"])
        s.traced = []
    while last - t0 < seconds or tracing:
        _, now = block()
        window.append(now - last)
        last = now
        if tracing and len(window) == trace_blocks:
            jax.profiler.stop_trace()
            tracing = False
            traced, s.traced = s.traced_means(trace_blocks, k), None
    window_compiles = watch.count - compiles0
    evidence = {"warmup_block_s": warm, "marks": stamps,
                "counters": {**s.counters, "window_compiles": window_compiles}}
    if trace:
        evidence["counters"].update(traced)
        evidence.update(window_t0=t0, trace_blocks=trace_blocks,
                        profile_dir=ctx["profile_dir"])
    s.free()
    return {
        "setup_end": t0,
        "end_to_end": {"train_tokens_per_s": blocks.window_rate(per_block, window)},
        "blocks": blocks.summary(per_block, "tokens/s", window),
        "attempted": len(window) * k,
        "failed": 0,
        "window_compiles": window_compiles,
        "prog": prog,
        "reference": s.reference_numbers,
        "evidence": evidence,
    }
