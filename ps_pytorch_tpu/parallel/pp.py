"""Pipeline (stage) parallelism for the transformer family.

Absent from the reference (SURVEY.md section 2: "TP / PP / SP / EP / CP ...
absent"); built here so model depth scales across the mesh. The schedule is
GPipe mapped onto SPMD collectives:

- the transformer's blocks are STACKED into [depth, ...] leaves and the
  depth axis is sharded over the `stage` mesh axis — each device owns
  depth/n_stages contiguous blocks and runs them with a local `lax.scan`;
- the global batch is cut into M microbatches; one jitted `lax.scan` over
  M + S - 1 ticks runs the pipeline: each tick every stage `ppermute`s its
  previous activation to the next stage, stage 0 injects the next
  microbatch's embedding, the last stage collects finished microbatches;
- embeddings / norms / unembedding are replicated (stage 0 embeds, the
  last stage projects to logits; psum completes the loss on all stages).

Bubble fraction is the usual (S-1)/(M+S-1) — choose M >= S. All ticks are
one compiled loop body (uniform control flow; `jnp.where` does the
schedule gating), so XLA overlaps each tick's ppermute with the next
tick's block compute where the hardware allows.

Gradient correctness uses the same rule as parallel/tp.py: under
shard_map(check_vma=False), AD computes exact gradients of the SUM over
shards of the per-shard outputs, so the train step differentiates loss/S
and psums the replicated leaves' gradients afterwards.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Optional, Sequence, TYPE_CHECKING

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.metrics import next_token_nll
from .tp import opt_state_specs

if TYPE_CHECKING:  # pragma: no cover
    from ..models.transformer import TransformerConfig

PP_AXIS = "stage"


def make_pp_mesh(
    num_stages: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """1-D pipeline mesh (axis 'stage')."""
    from .mesh import make_mesh

    return make_mesh(num_workers=num_stages, devices=devices, axis_name=PP_AXIS)


def to_pp_layout(cfg: "TransformerConfig", params: Dict) -> Dict:
    """Stack the per-block param dicts into [depth, ...] leaves so the
    depth axis can be mesh-sharded and scanned."""
    out = {k: v for k, v in params.items() if k != "blocks"}
    out["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs), *params["blocks"])
    return out


def from_pp_layout(cfg: "TransformerConfig", params_pp: Dict) -> Dict:
    """Inverse of `to_pp_layout` (checkpoint interchange)."""
    out = {k: v for k, v in params_pp.items() if k != "blocks"}
    out["blocks"] = [
        jax.tree.map(lambda x: x[i], params_pp["blocks"])
        for i in range(cfg.depth)
    ]
    return out


def pp_param_specs(cfg: "TransformerConfig", axis: str = PP_AXIS) -> Dict:
    """Stacked blocks shard their leading (depth) dim over the stage axis;
    everything else is replicated."""
    blk = {
        "ln1": P(axis),
        "wqkv": P(axis),
        "wo": P(axis),
        "ln2": P(axis),
        "w_up": P(axis),
        "w_down": P(axis),
    }
    return {"embed": P(), "pos_embed": P(), "out_norm": P(), "blocks": blk}


def shard_params_pp(
    cfg: "TransformerConfig", params_pp: Dict, mesh: Mesh, axis: str = PP_AXIS
) -> Dict:
    n = mesh.shape[axis]
    if cfg.depth % n:
        raise ValueError(f"depth {cfg.depth} not divisible by {n} stages")
    from .mesh import place_on_mesh

    return place_on_mesh(params_pp, mesh, pp_param_specs(cfg, axis))


def _block(cfg: "TransformerConfig", x, blk):
    """One transformer block — the same function the oracle runs."""
    from ..models.transformer import local_attention, transformer_block

    return transformer_block(cfg, x, blk, local_attention(cfg))


def gpipe_fold(
    axis_name: str,
    tokens: jax.Array,  # int32 [M, B_mb, T] microbatched (this column's)
    dim: int,
    cd,
    embed: Callable,  # mb_idx -> [B_mb, T, dim] activations (stage 0)
    run_local: Callable,  # x -> (y, aux_scalar) through this stage's blocks
    mb_loss: Callable,  # (y, tok_mb) -> scalar loss for one microbatch
):
    """THE GPipe tick schedule — the single implementation shared by the
    dense pipeline (here), MoE-in-PP (parallel/pp_moe.py), and the 3-D
    dp x pp x tp composition (parallel/dp_tp_pp.py); only the per-stage
    block body, embedding, and loss head differ.

    One `lax.scan` over M + S - 1 ticks: every tick each stage ppermutes
    its previous activation to the next stage, stage 0 injects the next
    microbatch's embedding, and the loss head is folded INTO the tick per
    finished microbatch — so the largest activation ever live is one
    microbatch's [B_mb, T, V] logits, never [M, B_mb, T, V] (a PP stage's
    memory must scale with the microbatch, not the global batch). The
    loss value is computed uniformly on every stage (SPMD control flow);
    only the last stage's survives the mask+psum. `run_local`'s aux
    output (e.g. MoE load-balance) is accumulated over VALID ticks only —
    warmup/drain ticks process garbage activations whose statistics must
    not leak.

    Returns (task_loss, aux_sum): task replicated within the column via
    the stage psum-mask and already divided by M (mean of equal-size
    per-microbatch means == global mean); aux_sum is the raw valid-tick
    sum (normalize at the caller).
    """
    n = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    m, b_mb, t = tokens.shape
    perm = [(j, (j + 1) % n) for j in range(n)]
    y0 = jnp.zeros((b_mb, t, dim), cd)

    def tick(carry, tk):
        y, loss_sum, aux_sum = carry
        inbound = lax.ppermute(y, axis_name, perm)
        x_in = jnp.where(stage == 0, embed(tk), inbound)
        y_new, aux_tick = run_local(x_in)
        mine = tk - stage  # the microbatch THIS stage processed this tick
        aux_sum = aux_sum + jnp.where(
            (mine >= 0) & (mine < m), aux_tick, 0.0
        )
        done = tk - (n - 1)
        tok_mb = lax.dynamic_index_in_dim(
            tokens, jnp.clip(done, 0, m - 1), 0, keepdims=False
        )
        loss_sum = loss_sum + jnp.where(
            (done >= 0) & (done < m), mb_loss(y_new, tok_mb), 0.0
        )
        return (y_new, loss_sum, aux_sum), None

    zero = jnp.zeros((), jnp.float32)
    (_, loss_sum, aux_sum), _ = lax.scan(
        tick, (y0, zero, zero), jnp.arange(m + n - 1)
    )
    task = lax.psum(jnp.where(stage == n - 1, loss_sum / m, 0.0), axis_name)
    return task, aux_sum


def _pp_logits_and_loss(
    cfg: "TransformerConfig",
    params: Dict,  # PP layout, LOCAL shards (inside shard_map)
    tokens: jax.Array,  # int32 [M, B_mb, T] microbatched, replicated
    axis_name: str,
):
    """Run the pipeline schedule; returns the scalar mean next-token loss
    (identical on every stage, via psum of the last stage's value)."""
    from ..models.transformer import _rms_norm

    m = tokens.shape[0]
    pos = jnp.arange(tokens.shape[2])
    cd = cfg.effective_compute_dtype  # blocks emit compute_dtype activations

    def local_blocks(x):
        body = lambda x, blk: (_block(cfg, x, blk), None)
        if cfg.remat:
            body = jax.checkpoint(body)
        x, _ = lax.scan(body, x, params["blocks"])
        return x, jnp.zeros((), jnp.float32)

    def embed(mb_idx):
        tok = lax.dynamic_index_in_dim(
            tokens, jnp.clip(mb_idx, 0, m - 1), 0, keepdims=False
        )
        return (params["embed"][tok] + params["pos_embed"][pos][None]).astype(cd)

    def mb_loss(y, tok_mb):
        xf = _rms_norm(y, params["out_norm"].astype(cd))
        logits = xf @ params["embed"].T.astype(cd)  # [B_mb, T, V]
        return next_token_nll(logits, tok_mb)

    task, _ = gpipe_fold(
        axis_name, tokens, cfg.dim, cd, embed, local_blocks, mb_loss
    )
    return task


def make_pp_train_step(
    cfg: "TransformerConfig",
    tx: optax.GradientTransformation,
    mesh: Mesh,
    num_microbatches: int,
    axis_name: str = PP_AXIS,
    donate: bool = True,
):
    """Jitted PP LM train step: (params_pp, opt_state, tokens [B, T]) ->
    (params_pp, opt_state, loss). Block params/opt state sharded over the
    stage axis; tokens replicated and cut into `num_microbatches` equal
    microbatches inside the step."""
    from ..models.lm import require_dense

    require_dense(cfg, "pipeline parallelism (parallel/pp.py)")
    specs_tree = pp_param_specs(cfg, axis_name)

    def shard_fn(params, opt_state, tokens):
        n = lax.axis_size(axis_name)
        bsz, t = tokens.shape
        if bsz % num_microbatches:  # static shape: raises at trace time
            raise ValueError(
                f"batch {bsz} not divisible by {num_microbatches} microbatches"
            )
        mb = tokens.reshape(num_microbatches, bsz // num_microbatches, t)

        # same AD rule as tp.py: grads of sum-over-shards => scale by 1/n,
        # then psum the replicated leaves' partial grads
        loss, grads = jax.value_and_grad(
            lambda p: _pp_logits_and_loss(cfg, p, mb, axis_name) / n
        )(params)
        grads = jax.tree.map(
            lambda g, s: lax.psum(g, axis_name) if s == P() else g,
            grads,
            specs_tree,
            is_leaf=lambda x: isinstance(x, P),
        )
        updates, new_opt = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        return new_params, new_opt, loss * n

    shapes = _pp_param_shapes(cfg)
    opt_specs = opt_state_specs(jax.eval_shape(tx.init, shapes), shapes, specs_tree)
    mapped = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(specs_tree, opt_specs, P()),
        out_specs=(specs_tree, opt_specs, P()),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(0, 1) if donate else ())


def _pp_param_shapes(cfg: "TransformerConfig") -> Dict:
    from ..models.transformer import init_transformer

    shapes = jax.eval_shape(lambda: init_transformer(cfg, jax.random.key(0)))
    return jax.eval_shape(partial(to_pp_layout, cfg), shapes)


def init_pp_state(
    cfg: "TransformerConfig",
    tx: optax.GradientTransformation,
    key: jax.Array,
    mesh: Mesh,
    axis_name: str = PP_AXIS,
):
    """Init (params_pp, opt_state) placed with PP shardings."""
    from ..models.lm import require_dense

    require_dense(cfg, "pipeline parallelism (parallel/pp.py)")
    from ..models.transformer import init_transformer

    params_pp = shard_params_pp(
        cfg, to_pp_layout(cfg, init_transformer(cfg, key)), mesh, axis_name
    )
    from .mesh import place_on_mesh

    opt_state = tx.init(params_pp)
    specs = opt_state_specs(opt_state, params_pp, pp_param_specs(cfg, axis_name))
    return params_pp, place_on_mesh(opt_state, mesh, specs)
