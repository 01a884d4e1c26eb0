"""Traffic kind `lm_train`: the LM step as `cli.train_lm` builds it for
`--parallelism dp_sp` (TransformerConfig, build_optimizer, make_mesh_2d,
init_lm_state, make_lm_train_step, shard_tokens_2d), driven by a loop of
the benchmark's own that closes blocks the way that CLI's log steps do:
a host read of the loss, then `host_sync(params)`.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import blocks, reference, weights
from benchmark.reducers import trace as tr


class _Session:
    def __init__(self, cell, seed, ctx):
        import jax
        import jax.numpy as jnp
        from ps_pytorch_tpu.models.transformer import TransformerConfig
        from ps_pytorch_tpu.optim import build_optimizer
        from ps_pytorch_tpu.parallel.dp_sp import (
            init_lm_state, make_lm_train_step, make_mesh_2d, shard_tokens_2d)
        from ps_pytorch_tpu.parallel.mesh import replicated_sharding

        c, t = cell.config, cell.traffic
        self.cell, self.seed = cell, seed
        self.cfg = TransformerConfig(
            vocab_size=int(c["vocab_size"]), dim=int(c["n_embd"]),
            depth=int(c["n_layer"]), heads=int(c["n_head"]),
            mlp_ratio=int(c["mlp_ratio"]), max_seq_len=int(t["seq_len"]),
            attention_impl=t["attention_impl"],
            compute_dtype=jnp.bfloat16 if t["dtype"] == "bfloat16" else None,
        )
        tx = build_optimizer(t["optimizer"], float(t["lr"]), b1=float(t["b1"]),
                             b2=float(t["b2"]), eps=float(t["eps"]))
        devices = jax.devices()[: int(t["num_dp"]) * int(t["num_sp"])]
        self.mesh = make_mesh_2d(int(t["num_dp"]), int(t["num_sp"]), devices=devices)
        params, self.opt = init_lm_state(
            self.cfg, tx, jax.random.key(seed % (2 ** 31 - 1)), self.mesh)
        self.ref = reference.load(c["reference"])
        self.shapes = self.ref.param_shapes(c)
        if not weights.same_tree(params, self.shapes):
            raise SystemExit("the LM's parameter tree is not the one "
                             f"configs/{cell.config_name}.json describes")
        del params
        self.params = jax.device_put(self.make_params(), replicated_sharding(self.mesh))
        self._step = make_lm_train_step(self.cfg, tx, self.mesh)
        self._put = lambda tok: shard_tokens_2d(jnp.asarray(tok), self.mesh)
        self.tokens = weights.token_rows(seed, int(t["corpus_rows"]),
                                         int(t["seq_len"]), int(c["vocab_size"]))
        self._order = np.random.default_rng(seed + 1)
        self.rows = []          # the rows of every batch fed so far
        b1 = float(t["b1"])
        self._grad_norms = jax.jit(lambda m: weights.leaf_norms(m) / (1.0 - b1))
        self._change = jax.jit(lambda p, w0: weights.leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, p, w0)))

    def make_params(self):
        return weights.make_weights(self.shapes, self.seed)

    def draw_rows(self):
        rows = self._order.integers(0, len(self.tokens), int(self.cell.traffic["batch_rows"]))
        self.rows.append(rows)
        return rows

    def step(self):
        """One step through the window's own call and feed."""
        rows = self.draw_rows()
        self.params, self.opt, loss = self._step(
            self.params, self.opt, self._put(self.tokens[rows]))
        return loss

    def close_block(self, loss):
        from ps_pytorch_tpu.utils import host_sync

        value = float(loss)
        host_sync(self.params)
        return value, time.perf_counter()

    def first_steps(self):
        n = int(self.cell.traffic["check_steps"])
        losses, grad = [], None
        for s in range(n):
            losses.append(self.step())
            if s == 0:
                grad = self._grad_norms(self.opt.exp_avg)
        dparam = self._change(self.params, self.make_params())
        return {
            "loss": [float(x) for x in losses],
            "grad_norms": np.asarray(grad).tolist(),
            "dparam_norms": np.asarray(dparam).tolist(),
        }

    def reference_numbers(self, operand=None):
        n = int(self.cell.traffic["check_steps"])
        feed = {"tokens": self.tokens, "rows": np.stack(self.rows[:n])}
        return self.ref.train_steps(self.cell.config, self.cell.traffic,
                                    self.make_params, feed, n, operand=operand)

    def free(self):
        self.params = self.opt = self._step = None


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
    trace_blocks = 3  # read from the first's third run to the third's first

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
    while last - t0 < seconds or tracing:
        _, now = block()
        window.append(now - last)
        last = now
        if tracing and len(window) == trace_blocks:
            jax.profiler.stop_trace()
            tracing = False
    window_compiles = watch.count - compiles0
    evidence = {"warmup_block_s": warm, "marks": stamps,
                "counters": {"window_compiles": window_compiles}}
    if trace:
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
