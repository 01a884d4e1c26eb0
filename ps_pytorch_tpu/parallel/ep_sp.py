"""2-D parallelism: expert parallelism x ring-attention sequence parallelism.

The last composition gap from round 1 (NOTES gap #4 / VERDICT item 9): MoE
models with long contexts. One (expert x seq) mesh:

- batch sharded over the expert axis (it doubles as data parallelism, as
  in parallel/moe.py), sequence sharded over the seq axis;
- attention: ring (or ring-flash / Ulysses, via TransformerConfig) over
  `seq` — K/V blocks rotate within each expert row (a `seq` axis of one
  member takes the within-chip attention: models/transformer.
  attention_path);
- MoE MLP: two all_to_alls over `expert` — token routing within each seq
  column. The two collectives touch ORTHOGONAL mesh dimensions, so the
  composition needs no new communication primitive at all: exactly the
  scaling-book recipe of assigning independent parallelism forms to
  independent mesh axes.

Gradient rule (the same sum-over-shards discipline as dp_sp.py + moe.py):
each (ep, sp) shard differentiates its LOCAL objective slice
  lm_local + aux_w * aux_local / n_sp      (lm_local sums its nll slice
                                            over count psum'd over sp)
Replicated leaves then need psum over sp and pmean over ep (PS-mean over
the batch axis); expert-sharded leaves already carry their ep-routed
contributions (all_to_all transposes to all_to_all) and need only
psum over sp and the 1/n_ep mean scale.

No reference counterpart (SURVEY.md section 2: every parallelism axis
beyond DP is absent there).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .moe import (
    EP_AXIS,
    MoEConfig,
    apply_moe_transformer,
    init_moe_params,
    moe_param_specs,
)
from .ring_attention import SEQ_AXIS
from .tp import opt_state_specs

from ..models.transformer import TransformerConfig


def make_mesh_ep_sp(
    num_ep: int,
    num_sp: int,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """(num_ep x num_sp) mesh; expert outer, seq inner (the ring is the
    latency-critical dimension — keep it on neighboring devices)."""
    devs = list(devices if devices is not None else jax.devices())
    need = num_ep * num_sp
    if need > len(devs):
        raise ValueError(f"need {need} devices, have {len(devs)}")
    grid = np.array(devs[:need]).reshape(num_ep, num_sp)
    return Mesh(grid, (EP_AXIS, SEQ_AXIS))


def shard_tokens_ep_sp(tokens, mesh: Mesh):
    """[B_global, T_global] -> B over expert, T over seq."""
    return jax.device_put(tokens, NamedSharding(mesh, P(EP_AXIS, SEQ_AXIS)))


def moe_lm_loss_local(
    cfg: TransformerConfig,
    moe: MoEConfig,
    params,
    tokens: jax.Array,  # [b_local, t_local]
    ep_axis: str = EP_AXIS,
    sp_axis: str = SEQ_AXIS,
):
    """LOCAL slice of the global-mean next-token loss + aux, for one
    (ep, sp) shard. Mirrors dp_sp.lm_loss_local (boundary target fetched
    with one ppermute; final global position masked), plus the MoE aux
    scaled so the sp-sum + ep-mean of the slices is the global mean aux."""
    b_loc, t_loc = tokens.shape
    n_sp = lax.axis_size(sp_axis)
    s = lax.axis_index(sp_axis)
    logits, aux = apply_moe_transformer(
        cfg, moe, params, tokens, axis_name=ep_axis, seq_axis_name=sp_axis
    )
    nxt_first = lax.ppermute(
        tokens[:, :1], sp_axis, [(j, (j - 1) % n_sp) for j in range(n_sp)]
    )
    tgt = jnp.concatenate([tokens[:, 1:], nxt_first], axis=1)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    pos = s * t_loc + jnp.arange(t_loc)
    valid = (pos < n_sp * t_loc - 1).astype(jnp.float32)
    loss_sum = jnp.sum(nll * valid[None, :])
    count = jnp.float32(b_loc) * jnp.sum(valid)
    lm_local = loss_sum / lax.psum(count, sp_axis)
    return lm_local, aux


def make_ep_sp_train_step(
    cfg: TransformerConfig,
    moe: MoEConfig,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    donate: bool = True,
):
    """Jitted 2-D MoE train step: (params, opt_state, tokens) ->
    (params, opt_state, task_loss, aux). Expert weights sharded over
    `expert` (replicated over `seq`); tokens [B over expert, T over seq];
    everything else replicated."""
    specs_tree = moe_param_specs(cfg, EP_AXIS)

    def shard_fn(params, opt_state, tokens):
        n_ep = lax.axis_size(EP_AXIS)
        n_sp = lax.axis_size(SEQ_AXIS)

        def local_obj(p):
            lm_local, aux = moe_lm_loss_local(cfg, moe, p, tokens)
            # aux_local/n_sp: sp-sum + ep-mean of slices == mean over shards
            return lm_local + moe.aux_loss_weight * aux / n_sp, (lm_local, aux)

        (_, (lm_local, aux)), grads = jax.value_and_grad(
            local_obj, has_aux=True
        )(params)
        grads = jax.tree.map(
            lambda g, s: (
                lax.pmean(lax.psum(g, SEQ_AXIS), EP_AXIS)
                if s == P()
                # expert-sharded: ep contributions already routed home by
                # the all_to_all transpose; sum the sp replicas, mean
                # over the ep (data) axis
                else lax.psum(g, SEQ_AXIS) / n_ep
            ),
            grads,
            specs_tree,
            is_leaf=lambda x: isinstance(x, P),
        )
        updates, new_opt = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        task = lax.pmean(lax.psum(lm_local, SEQ_AXIS), EP_AXIS)
        return new_params, new_opt, task, lax.pmean(aux, (EP_AXIS, SEQ_AXIS))

    shapes = jax.eval_shape(lambda: init_moe_params(cfg, moe, jax.random.key(0)))
    opt_specs = opt_state_specs(jax.eval_shape(tx.init, shapes), shapes, specs_tree)
    mapped = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(specs_tree, opt_specs, P(EP_AXIS, SEQ_AXIS)),
        out_specs=(specs_tree, opt_specs, P(), P()),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(0, 1) if donate else ())


def init_ep_sp_state(
    cfg: TransformerConfig,
    moe: MoEConfig,
    tx: optax.GradientTransformation,
    key: jax.Array,
    mesh: Mesh,
):
    """Init (params, opt_state) placed for the 2-D mesh: P(expert) leaves
    shard over the expert axis and replicate over seq automatically."""
    from .mesh import place_on_mesh
    from .moe import shard_params_moe

    params = shard_params_moe(cfg, init_moe_params(cfg, moe, key), mesh)
    opt_state = tx.init(params)
    specs = opt_state_specs(opt_state, params, moe_param_specs(cfg, EP_AXIS))
    return params, place_on_mesh(opt_state, mesh, specs)
