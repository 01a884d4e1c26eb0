"""2-D parallelism: PS data parallelism x ring-attention sequence parallelism.

The composition argument made executable: because the PS engine keeps params
replicated (mesh.py docstring) and the sequence-parallel transformer keeps
them replicated too (models/transformer.py), the two axes compose on one
2-D mesh ("workers", "seq") with no weight re-sharding — batch shards ride
the dp axis, sequence shards the sp axis, gradients meet in one
pmean-over-dp + psum-over-sp.

Gradient math: each (dp, sp) device differentiates only its LOCAL slice of
the objective — loss_sum_local / count_global, with the global count a
constant — and the gradients are psum'd over sp exactly once afterwards.
Differentiating a psum'd loss inside shard_map would seed a cotangent on
every sp device and overcount each term n_sp times (the ring's ppermute
transpose already routes cross-device contributions back to the device
owning the parameters' activation path). Averaging over dp is the PS
aggregation (sync_replicas_master_nn.py:204-208 semantics, batch-mean form).

Next-token targets cross sequence-shard boundaries: the target of a shard's
last token is the NEXT shard's first token, fetched with one ppermute; the
final global position is masked out of the loss.

A sequence axis with one member (sp 1: both LM benchmark cells) has no
ring: the model takes the within-chip attention
(models/transformer.attention_path) and the "next shard" is this one, so
the target of the last token is read from the shard itself and no
collective over sp moves anything.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.lm import lm_family
from ..obs.scopes import GRAD_REDUCE, HEAD_LOSS, UPDATE, ScopedStep, scope, stamped
from ..obs.trace import setup_span
from .mesh import WORKER_AXIS, replicated_sharding
from .ring_attention import SEQ_AXIS


# the name obs/scopes.last_step finds this module's step under
LM_TRAIN_STEP = "lm_train_step"


def make_mesh_2d(
    num_dp: int,
    num_sp: int,
    devices: Optional[Sequence[jax.Device]] = None,
    dp_axis: str = WORKER_AXIS,
    sp_axis: str = SEQ_AXIS,
) -> Mesh:
    """(num_dp x num_sp) mesh; dp outer so batch shards stay on neighboring
    devices (the sp ring is the inner, highest-bandwidth dimension)."""
    devs = list(devices if devices is not None else jax.devices())
    need = num_dp * num_sp
    if need > len(devs):
        raise ValueError(f"need {need} devices, have {len(devs)}")
    grid = np.array(devs[:need]).reshape(num_dp, num_sp)
    return Mesh(grid, (dp_axis, sp_axis))


def shard_tokens_2d(
    tokens, mesh: Mesh, dp_axis: str = WORKER_AXIS, sp_axis: str = SEQ_AXIS
):
    """[B_global, T_global] -> B over dp, T over sp."""
    return jax.device_put(tokens, NamedSharding(mesh, P(dp_axis, sp_axis)))


def lm_loss_local(
    cfg,
    params,
    tokens: jax.Array,
    sp_axis: str = SEQ_AXIS,
):
    """LOCAL slice of the global-mean next-token loss for one (dp, sp) shard
    of tokens [b_local, t_local], for any family models/lm.lm_family knows.

    Returns (loss_sum_local / count_global, aux): the global loss is the
    psum of the first over sp — do that OUTSIDE the differentiated
    function (see module docstring: differentiating through the psum
    overcounts gradients); aux is what the family counted on these tokens
    ({} for the dense family)."""
    b_loc, t_loc = tokens.shape
    n_sp = lax.axis_size(sp_axis)
    s = lax.axis_index(sp_axis)
    logits, aux = lm_family(cfg).apply(cfg, params, tokens, seq_axis_name=sp_axis)
    if logits.ndim == 4:
        return _offsets_loss(logits, tokens, n_sp), aux
    # target of my last token = next shard's first token (ring shift left);
    # with one member that shard is this one (the position is masked below)
    with scope(HEAD_LOSS):
        nxt_first = tokens[:, :1]
        if n_sp > 1:
            nxt_first = lax.ppermute(
                nxt_first, sp_axis, [(j, (j - 1) % n_sp) for j in range(n_sp)]
            )
        tgt = jnp.concatenate([tokens[:, 1:], nxt_first], axis=1)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        pos = s * t_loc + jnp.arange(t_loc)
        valid = (pos < n_sp * t_loc - 1).astype(jnp.float32)  # drop final position
        loss_sum = jnp.sum(nll * valid[None, :])
        count = jnp.float32(b_loc) * jnp.sum(valid)
        return loss_sum / lax.psum(count, sp_axis), aux


def _offsets_loss(logits, tokens, n_sp: int):
    """The loss of a family with several prediction heads: logits [b, t, P,
    vocab], head p predicting the token at i + 1 + p; the plain mean of the
    NLL over every head and every position whose target lies in the row (i
    + 1 + p < t). One sequence shard only: such a family refuses more."""
    if n_sp != 1:
        raise NotImplementedError(
            "several prediction heads read targets up to num_pred_heads tokens ahead, "
            "which lm_loss_local fetches from this shard only: run --num-sp 1")
    b, t, heads, _ = logits.shape
    with scope(HEAD_LOSS):
        ahead = jnp.pad(tokens, [(0, 0), (0, heads)])
        tgt = jnp.stack([ahead[:, 1 + p:1 + p + t] for p in range(heads)], axis=-1)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        valid = (jnp.arange(t)[:, None] + 1 + jnp.arange(heads)[None, :] < t).astype(jnp.float32)
        return jnp.sum(nll * valid[None]) / (jnp.float32(b) * jnp.sum(valid))


@setup_span("setup.init_state")
def init_lm_state(
    cfg,
    tx: optax.GradientTransformation,
    key: jax.Array,
    mesh: Mesh,
):
    """Init (params, opt_state) replicated ON THE MESH, like every other
    scheme's init_*_state: state left uncommitted on device 0 makes the
    train step compile twice — once for device-0 inputs, again for its
    own mesh-sharded outputs."""
    params = lm_family(cfg).init(cfg, key)
    return jax.device_put((params, tx.init(params)), replicated_sharding(mesh))


# plan_update's cut, in elements of a product's smaller operand: 7/8 of a
# v5e's 128 MiB of VMEM at the blocks' two bytes an element. Up to it the
# TPU compiler keeps that operand of a weight-gradient product resident and
# the product's cost does not depend on its output tile; past it the
# contraction is walked in pieces, the output tile is what bounds the
# operands' re-reads, and an epilogue of Adam's six float32 streams leaves
# VMEM for a quarter of it (PERF.md, "where the update stands": compiled
# for a described v5e the switch falls between 3,584 and 3,712 columns at
# 16,384 rows, 7,168 and 7,424 at 8,192, 896 and 1,024 at 65,536)
UPDATE_APART_OPERAND = 7 * 128 * 2 ** 20 // (8 * 2)


def plan_update(leaf_shape, rows: int) -> bool:
    """Whether a leaf's update stands apart from the product that makes its
    gradient (the gradient is materialised and Adam reads it as an
    elementwise pass of its own) or is left for XLA to fold into that
    product as its epilogue. Pure: a leaf's shape and the rows a step
    contracts over on one chip (b_loc * t_loc) are all it sees.

    A matrix [m, n] is the result of ONE product [rows, m]^T x [rows, n].
    Folding pays while the smaller operand, rows x min(m, n), stays in VMEM
    (Adam's traffic hides under the MXUs: every block leaf of a 1024-wide
    model at 8,192 rows); it costs twice the product's time where that
    operand does not fit (4096-wide leaves at 16,384 rows). Anything else
    is no product's result, or not one XLA makes: norm gains, biases and
    conv taps; the experts' stacked matrices, whose gradient is a Pallas
    kernel's and whose Adam stands alone already."""
    return len(leaf_shape) == 2 and rows * min(leaf_shape) > UPDATE_APART_OPERAND


def update_plan(params, rows: int) -> dict:
    """What plan_update answers over a tree of parameters (arrays or their
    shapes) at `rows` a step: how often the mechanism engages, for
    `cli.train_lm`'s log line and its `update_plan` trace event."""
    shapes = [tuple(leaf.shape) for leaf in jax.tree_util.tree_leaves(params)]
    apart = [s for s in shapes if plan_update(s, rows)]
    return {"rows": rows, "leaves": len(shapes), "leaves_apart": len(apart),
            "params": sum(math.prod(s) for s in shapes),
            "params_apart": sum(math.prod(s) for s in apart)}


@setup_span("setup.make_step")
def make_lm_train_step(
    cfg,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    dp_axis: str = WORKER_AXIS,
    sp_axis: str = SEQ_AXIS,
    donate: bool = True,
):
    """Jitted 2-D train step: (params, opt_state, tokens) ->
    (params, opt_state, loss). params/opt_state replicated; tokens sharded
    [B over dp, T over sp]. A family that counts (models/lm.LMFamily.
    counters: the expert layers' routing) returns a fourth value, the dict
    of its counters over the step's global batch."""
    counters = lm_family(cfg).counters

    def worker_fn(params, opt_state, tokens):
        (loss_local, aux), grads = jax.value_and_grad(
            lambda p: lm_loss_local(cfg, p, tokens, sp_axis), has_aux=True
        )(params)
        # exact sequence gradient: sum local partials over sp exactly once;
        # PS aggregation: mean over dp (each dp shard saw a disjoint slice)
        with scope(GRAD_REDUCE):
            grads = lax.pmean(lax.psum(grads, sp_axis), dp_axis)
            loss = lax.pmean(lax.psum(loss_local, sp_axis), dp_axis)
            # leaf by leaf, never the tree as one: a barrier over the tree
            # would hold every float32 gradient live at once
            grads = jax.tree_util.tree_map(
                lambda g: lax.optimization_barrier(g)
                if plan_update(g.shape, tokens.size) else g, grads)
        with scope(UPDATE):
            updates, new_opt = tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
        if counters is None:
            return new_params, new_opt, loss
        with scope(GRAD_REDUCE):
            return new_params, new_opt, loss, counters(lax.psum(aux, (dp_axis, sp_axis)))

    mapped = jax.shard_map(
        worker_fn,
        mesh=mesh,
        in_specs=(P(), P(), P(dp_axis, sp_axis)),
        out_specs=(P(), P(), P()) + (() if counters is None else (P(),)),
        check_vma=False,
    )
    return ScopedStep(
        LM_TRAIN_STEP, jax.jit(stamped(mapped), donate_argnums=(0, 1) if donate else ()))
