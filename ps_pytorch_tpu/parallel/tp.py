"""Tensor (model) parallelism for the transformer family.

The reference has no tensor parallelism (SURVEY.md section 2: "TP / PP / SP /
EP / CP ... absent"); this module is part of making the mesh design
future-proof beyond the reference's data-parallel-only scope. The layout is
the standard Megatron split mapped onto XLA collectives:

- attention: heads sharded over the `model` axis — `wqkv` is stored
  [D, 3, H, hd] and sharded on H, so every device computes full attention
  for its own heads with ZERO communication; `wo` is stored [H, hd, D]
  (row-parallel) and the output projection ends in one `psum`.
- MLP: `w_up` column-sharded [D, M/n] (independent GELUs), `w_down`
  row-sharded [M/n, D], one `psum` after the down-projection.
- embeddings: replicated by default; `shard_vocab=True` shards the
  embedding matrix [V, D] over the model axis (vocab-parallel): the
  lookup masks out-of-range ids and psums partial embeddings, and the
  unembedding keeps logits LOCAL [B, T, V/n] — the cross-entropy runs
  vocab-parallel (gathered row max + psum'd exp-sum plus the owner
  shard's target logit) so the full [B, T, V] tensor never exists on
  any device. Norms stay replicated.

Two psums per block per token — both ride ICI, both fused by XLA into the
surrounding matmuls. Gradients w.r.t. sharded weights are naturally local
(shard_map transposes the psum to a broadcast of the cotangent), so the
optimizer runs shard-wise with no extra collectives: tensor-parallel
training is `value_and_grad` + local optax update, exactly like the PS
engine but with sharded instead of replicated state.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.metrics import next_token_nll

# NOTE: ..models.transformer imports from this package (ring_attention), so
# importing it at module top would be circular; TransformerConfig appears
# only in (string) annotations and _rms_norm/init_transformer are imported
# lazily inside the functions that use them.
from typing import TYPE_CHECKING

if TYPE_CHECKING:  # pragma: no cover
    from ..models.transformer import TransformerConfig

TP_AXIS = "model"


def make_tp_mesh(
    num_shards: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """1-D tensor-parallel mesh (axis 'model')."""
    from .mesh import make_mesh

    return make_mesh(num_workers=num_shards, devices=devices, axis_name=TP_AXIS)


def to_tp_layout(cfg: TransformerConfig, params: Dict) -> Dict:
    """Re-layout replicated transformer params for head/column sharding.

    wqkv [D, 3D] -> [D, 3, H, hd]  (shard dim 2)
    wo   [D, D]  -> [H, hd, D]     (shard dim 0)
    w_up [D, M] stays               (shard dim 1)
    w_down [M, D] stays             (shard dim 0)
    """
    h, hd = cfg.heads, cfg.head_dim
    out = {k: v for k, v in params.items() if k != "blocks"}
    out["blocks"] = []
    for blk in params["blocks"]:
        b = dict(blk)
        b["wqkv"] = blk["wqkv"].reshape(cfg.dim, 3, h, hd)
        b["wo"] = blk["wo"].reshape(h, hd, cfg.dim)
        out["blocks"].append(b)
    return out


def from_tp_layout(cfg: TransformerConfig, params_tp: Dict) -> Dict:
    """Inverse of `to_tp_layout` (for checkpoint interchange)."""
    out = {k: v for k, v in params_tp.items() if k != "blocks"}
    out["blocks"] = []
    for blk in params_tp["blocks"]:
        b = dict(blk)
        b["wqkv"] = blk["wqkv"].reshape(cfg.dim, 3 * cfg.dim)
        b["wo"] = blk["wo"].reshape(cfg.dim, cfg.dim)
        out["blocks"].append(b)
    return out


def tp_param_specs(
    cfg: TransformerConfig, axis: str = TP_AXIS, shard_vocab: bool = False
) -> Dict:
    """PartitionSpec pytree matching `to_tp_layout` output."""
    blk = {
        "ln1": P(),
        "wqkv": P(None, None, axis, None),
        "wo": P(axis, None, None),
        "ln2": P(),
        "w_up": P(None, axis),
        "w_down": P(axis, None),
    }
    return {
        "embed": P(axis, None) if shard_vocab else P(),
        "pos_embed": P(),
        "out_norm": P(),
        "blocks": [dict(blk) for _ in range(cfg.depth)],
    }


def shard_params_tp(
    cfg: TransformerConfig, params_tp: Dict, mesh: Mesh, axis: str = TP_AXIS,
    shard_vocab: bool = False,
) -> Dict:
    """Place a TP-layout param tree on the mesh with the TP shardings."""
    n = mesh.shape[axis]
    if cfg.heads % n:
        raise ValueError(f"heads {cfg.heads} not divisible by {n} model shards")
    if (cfg.dim * cfg.mlp_ratio) % n:
        raise ValueError(
            f"mlp dim {cfg.dim * cfg.mlp_ratio} not divisible by {n} model shards"
        )
    if shard_vocab and cfg.vocab_size % n:
        raise ValueError(
            f"vocab {cfg.vocab_size} not divisible by {n} model shards"
        )
    from .mesh import place_on_mesh

    return place_on_mesh(params_tp, mesh, tp_param_specs(cfg, axis, shard_vocab))


def apply_transformer_tp(
    cfg: TransformerConfig,
    params: Dict,  # TP layout, LOCAL shards (inside shard_map)
    tokens: jax.Array,  # int32 [B, T] (replicated)
    axis_name: str = TP_AXIS,
    shard_vocab: bool = False,
) -> jax.Array:
    """Forward on one model shard.

    Returns replicated logits [B, T, vocab] (shard_vocab=False), or the
    LOCAL logits shard [B, T, vocab/n] (shard_vocab=True — feed to
    vocab_parallel_nll; the full logits tensor never materializes).

    Mirrors models/transformer.py:apply_transformer with the Megatron
    split; every activation entering/leaving a block is replicated, so the
    result is bit-identical (up to reduction order) to the single-device
    model.
    """
    from ..models.transformer import _rms_norm, local_attention

    attend_local = local_attention(cfg)
    b, t = tokens.shape
    pos = jnp.arange(t)
    if shard_vocab:
        # vocab-parallel lookup: my shard owns ids [off, off + v_loc);
        # out-of-range rows contribute zero, psum completes the embedding
        v_loc = params["embed"].shape[0]
        off = lax.axis_index(axis_name) * v_loc
        local_ids = jnp.clip(tokens - off, 0, v_loc - 1)
        mine = (tokens >= off) & (tokens < off + v_loc)
        emb = jnp.where(mine[..., None], params["embed"][local_ids], 0.0)
        x = lax.psum(emb, axis_name) + params["pos_embed"][pos][None]
    else:
        x = params["embed"][tokens] + params["pos_embed"][pos][None]

    cd = cfg.effective_compute_dtype

    def block(x, blk):
        x = x.astype(cd)
        blk = {k: v.astype(cd) for k, v in blk.items()}  # cast at use
        h = _rms_norm(x, blk["ln1"])
        qkv = jnp.einsum("btd,dchk->btchk", h, blk["wqkv"])  # [B,T,3,Hloc,hd]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        o = attend_local(q, k, v)  # local heads only
        proj = jnp.einsum("bthk,hkd->btd", o, blk["wo"])
        x = x + lax.psum(proj, axis_name)
        h = _rms_norm(x, blk["ln2"])
        down = jax.nn.gelu(h @ blk["w_up"]) @ blk["w_down"]
        return x + lax.psum(down, axis_name)

    if cfg.remat:
        block = jax.checkpoint(block)
    for blk in params["blocks"]:
        x = block(x, blk)
    xf = _rms_norm(x.astype(cd), params["out_norm"].astype(cd))
    # tied unembedding: local vocab columns only when sharded
    return xf @ params["embed"].T.astype(cd)


def vocab_parallel_nll(
    logits_local: jax.Array,  # [B, T, V/n] — this shard's vocab columns
    tokens: jax.Array,  # int32 [B, T] (replicated)
    axis_name: str = TP_AXIS,
) -> jax.Array:
    """Mean next-token NLL over vocab-sharded logits (Megatron-style).

    softmax statistics cross the mesh per position as the row max (an
    all_gather of n scalars + max — pmax has no JVP rule) and a psum'd
    exp-sum, plus the owner shard's target logit — the full [B, T, V]
    logits tensor never exists on any device.
    Matches ops/metrics.next_token_nll on gathered logits exactly (up to
    reduction order); tested in tests/test_tp.py.
    """
    lg = logits_local[:, :-1].astype(jnp.float32)  # positions predicting t+1
    tgt = tokens[:, 1:]
    v_loc = lg.shape[-1]
    off = lax.axis_index(axis_name) * v_loc

    # global row max, for stability only: its gradient cancels analytically
    # in m + log(sum exp(lg - m)), so stop_gradient is EXACT. pmax has no
    # JVP rule at all (even under stop_gradient the trace hits it), so the
    # max crosses the mesh as all_gather + max, which differentiates fine.
    m = lax.stop_gradient(
        jnp.max(lax.all_gather(jnp.max(lg, axis=-1), axis_name), axis=0)
    )
    z = lax.psum(jnp.sum(jnp.exp(lg - m[..., None]), axis=-1), axis_name)

    local_tgt = jnp.clip(tgt - off, 0, v_loc - 1)
    mine = (tgt >= off) & (tgt < off + v_loc)
    picked = jnp.take_along_axis(lg, local_tgt[..., None], axis=-1)[..., 0]
    tgt_logit = lax.psum(jnp.where(mine, picked, 0.0), axis_name)

    # log softmax(target) = tgt_logit - m - log z
    return jnp.mean(m + jnp.log(z) - tgt_logit)


def make_tp_forward(
    cfg: TransformerConfig, mesh: Mesh, axis_name: str = TP_AXIS, jit: bool = True,
    shard_vocab: bool = False,
):
    """Tensor-parallel forward: params in TP layout (sharded per
    `tp_param_specs`), tokens replicated -> logits. Replicated [B, T, V]
    by default; with shard_vocab the logits come back as a GLOBAL array
    sharded on the vocab dim (the full tensor still never lives on one
    device)."""
    mapped = jax.shard_map(
        partial(
            apply_transformer_tp, cfg, axis_name=axis_name,
            shard_vocab=shard_vocab,
        ),
        mesh=mesh,
        in_specs=(tp_param_specs(cfg, axis_name, shard_vocab), P()),
        out_specs=P(None, None, axis_name) if shard_vocab else P(),
        check_vma=False,
    )
    return jax.jit(mapped) if jit else mapped


def _is_replicated(spec: P) -> bool:
    return all(a is None for a in spec)


def opt_state_specs(opt_state, params, param_specs):
    """Spec tree for an optax state: every sub-tree that structurally
    matches the param tree (momentum/first/second-moment buffers) takes the
    param specs; every other leaf (step counters, scalars) is replicated.

    `opt_state` may be concrete arrays or `jax.eval_shape` output — only
    the structure is used.
    """
    params_treedef = jax.tree.structure(params)

    def walk(node):
        try:
            if jax.tree.structure(node) == params_treedef:
                return param_specs
        except Exception:
            pass
        if isinstance(node, tuple) and hasattr(node, "_fields"):  # NamedTuple
            return type(node)(*(walk(c) for c in node))
        if isinstance(node, (list, tuple)):
            return type(node)(walk(c) for c in node)
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return P()  # array leaf or None

    return walk(opt_state)


def _tp_param_shapes(cfg: TransformerConfig) -> Dict:
    from ..models.transformer import init_transformer

    shapes = jax.eval_shape(lambda: init_transformer(cfg, jax.random.key(0)))
    return jax.eval_shape(partial(to_tp_layout, cfg), shapes)


def init_tp_state(
    cfg: TransformerConfig,
    tx: optax.GradientTransformation,
    key: jax.Array,
    mesh: Mesh,
    axis_name: str = TP_AXIS,
    shard_vocab: bool = False,
):
    """Init (params_tp, opt_state) already placed with TP shardings —
    momentum buffers shard exactly like their parameters."""
    from ..models.lm import require_dense

    require_dense(cfg, "tensor parallelism (parallel/tp.py)")
    from ..models.transformer import init_transformer

    params_tp = shard_params_tp(
        cfg, to_tp_layout(cfg, init_transformer(cfg, key)), mesh, axis_name,
        shard_vocab=shard_vocab,
    )
    from .mesh import place_on_mesh

    opt_state = tx.init(params_tp)
    specs = opt_state_specs(
        opt_state, params_tp, tp_param_specs(cfg, axis_name, shard_vocab)
    )
    return params_tp, place_on_mesh(opt_state, mesh, specs)


def make_tp_train_step(
    cfg: TransformerConfig,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis_name: str = TP_AXIS,
    donate: bool = True,
    shard_vocab: bool = False,
):
    """Jitted TP LM train step: (params_tp, opt_state, tokens) ->
    (params_tp, opt_state, loss). Params/opt state sharded over the model
    axis; tokens replicated. Gradients for sharded weights are local, so
    the optimizer update is shard-wise — no gradient collective at all
    (the two in-block psums are the only communication). With
    shard_vocab=True the embedding/logits run vocab-parallel (see
    vocab_parallel_nll)."""
    from ..models.lm import require_dense

    require_dense(cfg, "tensor parallelism (parallel/tp.py)")

    specs_tree = tp_param_specs(cfg, axis_name, shard_vocab)

    def shard_fn(params, opt_state, tokens):
        n = lax.axis_size(axis_name)

        def loss_fn(p):
            logits = apply_transformer_tp(
                cfg, p, tokens, axis_name, shard_vocab=shard_vocab
            )
            # With check_vma=False, shard_map AD computes exact grads of the
            # SUM over shards of the per-shard outputs (psum transposes to
            # psum — the correct transpose of that global function). Every
            # shard computes the identical loss, so differentiate loss/n:
            # sharded leaves' grads come out exact; replicated leaves' grads
            # come out as per-shard partials whose psum is exact (below).
            if shard_vocab:
                return vocab_parallel_nll(logits, tokens, axis_name) / n
            return next_token_nll(logits, tokens) / n

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.tree.map(
            lambda g, s: lax.psum(g, axis_name) if _is_replicated(s) else g,
            grads,
            specs_tree,
            is_leaf=lambda x: isinstance(x, P),
        )
        updates, new_opt = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        return new_params, new_opt, loss * n

    shapes = _tp_param_shapes(cfg)
    opt_specs = opt_state_specs(jax.eval_shape(tx.init, shapes), shapes, specs_tree)
    mapped = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(specs_tree, opt_specs, P()),
        out_specs=(specs_tree, opt_specs, P()),
        check_vma=False,
    )
    # donate params+opt state: the update writes in place in HBM instead of
    # double-buffering the model (same convention as ps.make_ps_train_step)
    return jax.jit(mapped, donate_argnums=(0, 1) if donate else ())
