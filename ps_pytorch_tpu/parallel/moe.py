"""Mixture-of-Experts with expert parallelism (all_to_all dispatch).

Absent from the reference (SURVEY.md section 2: "TP / PP / SP / EP / CP ...
absent"); built here to complete the mesh's parallelism axes. The design is
the Switch-Transformer / Mesh-TensorFlow formulation mapped onto XLA
collectives:

- every block's dense MLP is replaced by E experts (stacked [E, D, M] /
  [E, M, D] weights, sharded over the `expert` mesh axis — each device owns
  E/n experts);
- the batch is sharded over the SAME axis (the expert axis doubles as data
  parallelism outside the MoE region);
- top-1 gating with capacity C = ceil(tokens_local * capacity_factor / E):
  per (token, expert) dispatch/combine tensors built with a one-hot cumsum
  rank (overflowing tokens are dropped — they ride the residual only, the
  standard Switch behavior);
- dispatch: einsum to [E, C, D] -> `lax.all_to_all` (split E over devices,
  concatenate senders) -> [E/n, n*C, D] expert compute -> all_to_all back
  -> combine-weighted sum. Two all_to_alls per MoE layer, both on ICI.
- a Switch-style load-balance auxiliary loss (E * sum f_e p_e) is returned
  alongside the task loss.

Beside that capacity layer stands the DROPLESS layer of the
top-k-of-many families (`DroplessSpec`, `moe_dropless_local`; the spec
says how the router scores, whose input it reads and what the experts'
gate is): no
capacity, no token dropped at any imbalance, the (token, expert)
assignments sorted by expert, laid out for the worst case and walked in
passes of a buffer sized to twice the uniform load (`pass_rows`), as many
as the rows really routed here need, each multiplied by
ops/grouped_matmul.py. The layer is TOLD which experts it holds (`experts_held`,
`expert_offset`): it routes over all of them and computes the part of the
result its own experts give, which is what one chip of an expert-parallel
deployment does. With axis_name=None it runs without its exchange; nothing
stands in for the absent chips.

Gradients: same shard_map AD rule as tp.py/pp.py — each shard returns its
LOCAL loss; AD computes exact grads of the sum over shards; differentiate
local/n, then psum the replicated leaves (all_to_all's transpose is
all_to_all, which is exact under this convention).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, NamedTuple, Optional, Sequence, TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.scopes import MOE_COMBINE, MOE_DISPATCH, MOE_EXPERTS, MOE_ROUTE, scope
from ..ops.grouped_matmul import (TILE_M, GroupLayout, buffer_rows, group_layout,
                                   grouped_matmul, layout_pass)
from ..ops.metrics import next_token_nll
from ..ops.moe_rows_sum import rows_sum, rows_sum_path
from .tp import opt_state_specs

if TYPE_CHECKING:  # pragma: no cover
    from ..models.transformer import TransformerConfig

EP_AXIS = "expert"


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """MoE knobs layered on top of a TransformerConfig."""

    num_experts: int = 8
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    # 1 = Switch routing; 2 = GShard-style top-2 (renormalized gates,
    # second choices queue behind first choices for capacity slots)
    top_k: int = 1

    def __post_init__(self):
        if self.top_k not in (1, 2):
            raise ValueError(f"top_k must be 1 or 2, got {self.top_k}")


SCORES = ("sigmoid", "softmax_topk")
ROUTER_INPUTS = ("ffn_norm", "attention_norm")
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


@dataclasses.dataclass(frozen=True)
class DroplessSpec:
    """A dropless expert layer and this chip's share of it. Three static
    choices say which layer: how the router scores (`dropless_route`),
    whose rows it reads, and the experts' gate."""

    num_experts: int            # the router's outputs, all of them
    top_k: int
    experts_held: int           # experts whose weights live here ...
    expert_offset: int = 0      # ... ids offset .. offset + held - 1
    routed_scale: float = 1.0   # `sigmoid` scores only
    norm_topk_prob: bool = True
    scores: str = "sigmoid"
    # `ffn_norm`: the router reads the rows the experts read, inside
    # moe_dropless_local. `attention_norm`: it read the block's first norm
    # where the block began (`route_tokens`) and the layer is handed the route
    router_input: str = "ffn_norm"
    activation: str = "silu"    # down(activation(gate) * up)

    def __post_init__(self):
        for name, known in (("scores", SCORES), ("router_input", ROUTER_INPUTS),
                            ("activation", tuple(ACTIVATIONS))):
            if getattr(self, name) not in known:
                raise ValueError(f"{name}={getattr(self, name)!r}: one of {' | '.join(known)}")
        if not 0 < self.top_k <= self.num_experts:
            raise ValueError(f"top_k {self.top_k} of {self.num_experts} experts")
        if not (0 <= self.expert_offset
                and self.expert_offset + self.experts_held <= self.num_experts
                and self.experts_held > 0):
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset + self.experts_held - 1} "
                f"are not a share of {self.num_experts}")


def make_ep_mesh(
    num_shards: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """1-D expert-parallel mesh (axis 'expert')."""
    from .mesh import make_mesh

    return make_mesh(num_workers=num_shards, devices=devices, axis_name=EP_AXIS)


def init_moe_params(
    cfg: "TransformerConfig", moe: MoEConfig, key: jax.Array
) -> Dict:
    """Transformer params with every block's dense MLP replaced by a gate
    + stacked expert weights."""
    from ..models.transformer import init_transformer

    params = init_transformer(cfg, key)
    mlp_dim = cfg.dim * cfg.mlp_ratio
    e = moe.num_experts
    for i, blk in enumerate(params["blocks"]):
        bk = jax.random.split(jax.random.fold_in(key, 1000 + i), 3)
        del blk["w_up"], blk["w_down"]
        scale = 1.0 / (cfg.dim ** 0.5)
        blk["wg"] = (jax.random.normal(bk[0], (cfg.dim, e)) * scale).astype(
            cfg.dtype
        )
        blk["w_up_e"] = (
            jax.random.normal(bk[1], (e, cfg.dim, mlp_dim)) * scale
        ).astype(cfg.dtype)
        blk["w_down_e"] = (
            jax.random.normal(bk[2], (e, mlp_dim, cfg.dim)) * (1.0 / mlp_dim ** 0.5)
        ).astype(cfg.dtype)
    return params


def moe_param_specs(cfg: "TransformerConfig", axis: str = EP_AXIS) -> Dict:
    blk = {
        "ln1": P(),
        "wqkv": P(),
        "wo": P(),
        "ln2": P(),
        "wg": P(),
        "w_up_e": P(axis),
        "w_down_e": P(axis),
    }
    return {
        "embed": P(),
        "pos_embed": P(),
        "out_norm": P(),
        "blocks": [dict(blk) for _ in range(cfg.depth)],
    }


def shard_params_moe(
    cfg: "TransformerConfig", params: Dict, mesh: Mesh, axis: str = EP_AXIS
) -> Dict:
    n = mesh.shape[axis]
    e = params["blocks"][0]["w_up_e"].shape[0]
    if e % n:
        raise ValueError(f"{e} experts not divisible by {n} expert shards")
    from .mesh import place_on_mesh

    return place_on_mesh(params, mesh, moe_param_specs(cfg, axis))


def _choice_dispatch(onehot, capacity, offset):
    """Queue one routing choice into capacity slots.

    onehot [N, E]; offset [E] = slots already taken per expert by earlier
    (higher-priority) choices. Returns the [N, E, C] dispatch tensor
    (1.0 where a token owns a slot; overflow rows are all-zero).
    """
    rank = jnp.cumsum(onehot, axis=0) * onehot - onehot  # [N, E] within-choice
    rank = rank + offset[None, :] * onehot
    kept = (rank < capacity) * onehot
    pos = jax.nn.one_hot(
        jnp.sum(rank * onehot, axis=-1), capacity, dtype=jnp.float32
    )  # [N, C]
    return kept[:, :, None] * pos[:, None, :]


def _gate_and_dispatch(x2d, wg, capacity, top_k: int = 1):
    """Top-1 (Switch) or top-2 (GShard) gating over flat tokens [N, D].

    Returns (dispatch [N, E, C] float {0,1}, combine [N, E, C], aux scalar).
    For top-2, gates are renormalized over the two choices and second
    choices queue behind ALL first choices for an expert's capacity slots.
    """
    logits = x2d @ wg  # [N, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    e = wg.shape[-1]

    expert1 = jnp.argmax(probs, axis=-1)  # [N]
    gate1 = jnp.take_along_axis(probs, expert1[:, None], axis=-1)[:, 0]
    onehot1 = jax.nn.one_hot(expert1, e, dtype=jnp.float32)  # [N, E]
    dispatch = _choice_dispatch(onehot1, capacity, jnp.zeros((e,)))  # [N,E,C]

    if top_k == 2:
        probs2 = probs * (1.0 - onehot1)  # mask the first choice
        expert2 = jnp.argmax(probs2, axis=-1)
        gate2 = jnp.take_along_axis(probs, expert2[:, None], axis=-1)[:, 0]
        onehot2 = jax.nn.one_hot(expert2, e, dtype=jnp.float32)
        # second choices queue behind every first choice (capped at C)
        taken = jnp.minimum(jnp.sum(onehot1, axis=0), capacity)
        dispatch2 = _choice_dispatch(onehot2, capacity, taken)
        # renormalize over the two choices (dropped choices contribute 0)
        denom = gate1 + gate2 + 1e-9
        combine = (
            dispatch * (gate1 / denom)[:, None, None]
            + dispatch2 * (gate2 / denom)[:, None, None]
        )
        dispatch = dispatch + dispatch2
    else:
        combine = dispatch * gate1[:, None, None]

    # aux load-balance loss on first-choice assignment (Switch form):
    # E * sum_e (fraction routed to e) * (mean prob of e)
    f = jnp.mean(onehot1, axis=0)
    p = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f * p)
    return dispatch, combine, aux


def moe_mlp_local(h, blk, moe: MoEConfig, axis_name: Optional[str]):
    """MoE MLP on local tokens h [B, T, D]; returns ([B, T, D], aux).

    With axis_name=None this is the single-device (all experts local)
    oracle; inside shard_map the two all_to_alls route tokens to the
    devices owning their experts and back.
    """
    b, t, d = h.shape
    x2d = h.reshape(b * t, d)
    e = moe.num_experts
    capacity = int(np.ceil(b * t * moe.top_k * moe.capacity_factor / e))
    # cast at use: params may be stored f32 while activations run bf16
    dispatch, combine, aux = _gate_and_dispatch(
        x2d, blk["wg"].astype(h.dtype), capacity, top_k=moe.top_k
    )
    # gating runs in f32; the dispatch/combine one-hots drop back to the
    # activation dtype so the expert matmuls stay on the bf16 path
    dispatch = dispatch.astype(h.dtype)
    combine = combine.astype(h.dtype)
    expert_in = jnp.einsum("nec,nd->ecd", dispatch, x2d)  # [E, C, D]

    if axis_name is not None:
        # to expert owners: split E, concat senders' capacity slots
        expert_in = lax.all_to_all(
            expert_in, axis_name, split_axis=0, concat_axis=1, tiled=True
        )  # [E/n, n*C, D]
    w_up = blk["w_up_e"].astype(h.dtype)  # local experts, compute dtype
    w_down = blk["w_down_e"].astype(h.dtype)
    expert_out = jnp.einsum(
        "ecm,emd->ecd",
        jax.nn.gelu(jnp.einsum("ecd,edm->ecm", expert_in, w_up)),
        w_down,
    )
    if axis_name is not None:
        # back to token owners
        expert_out = lax.all_to_all(
            expert_out, axis_name, split_axis=1, concat_axis=0, tiled=True
        )  # [E, C, D]

    out = jnp.einsum("nec,ecd->nd", combine, expert_out)
    return out.reshape(b, t, d).astype(h.dtype), aux


def dropless_route(n32, router, router_bias, spec: DroplessSpec):
    """Top-k routing of float32 rows n32 [N, D] over ALL experts: (idx int32
    [N, k], weights float32 [N, k]). `sigmoid`: the choice is by score plus
    `router_bias` (the aux-loss-free correction: a buffer, no gradient),
    the weights are the scores themselves, normalised over the k chosen,
    held here or not, and scaled. `softmax_topk`: the k largest LOGITS, the
    weights a softmax over those k alone (a softmax over all of them
    renormalised over the chosen is the same number); no bias, no scale.
    The products are float32 (`highest`): a bfloat16 router picks other
    experts."""
    logits = jnp.dot(n32.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if spec.scores == "softmax_topk":
        top, idx = lax.top_k(logits, spec.top_k)
        return idx.astype(jnp.int32), jax.nn.softmax(top, axis=-1)
    s = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(s + lax.stop_gradient(router_bias.astype(jnp.float32)), spec.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if spec.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * spec.routed_scale


# The dropless layer moves rows three ways, each a gather whose transpose
# is written as the inverse gather (a scatter of 98,304 rows is what the
# device is worst at). `route` = (row_assign, row_live, pos, held) of ONE
# PASS (`_pass_route`): row r of the pass's buffer holds assignment
# row_assign[r] = n * k + j when row_live[r]; assignment (n, j) lies in row
# pos[n, j] of it when held[n, j] (held HERE and in THIS pass).


def _gather_rows(v, route, per_token: int):
    row_assign, row_live = route[0], route[1]
    return jnp.where(row_live[:, None], v[row_assign // per_token], 0)


def _gather_assignments(v, route):
    """[M, ...] -> [k, N, ...]: k leads, so a [N, D] slab keeps whole tiles
    (as [N, k, D] the chip pads k = 10 to 16 and relays 480 MiB out as 768)."""
    pos, held = route[2].T, route[3].T
    return jnp.where(held[..., None], v[pos], 0)


@jax.custom_vjp
def _rows_from_tokens(x, route):
    """[N, D] -> [M, D]: each live row its token's x, the others zero."""
    return _gather_rows(x, route, route[2].shape[1])


@jax.custom_vjp
def _tokens_from_rows(y, route):
    """[M, D] -> [N, D]: each token the sum of its held assignments' rows:
    ONE kernel that fetches the held rows alone where `rows_sum_path` says
    so (the forward's combine and, as `_rows_from_tokens`' transpose, the
    tokens' gradient), the gather over all N x k and its sum elsewhere."""
    return rows_sum(y, route[2], route[3],
                    lambda v: jnp.sum(_gather_assignments(v, route), axis=0))


@jax.custom_vjp
def _rows_from_assignments(w, route):
    """[N, k] -> [M]: each live row its assignment's scalar."""
    return _gather_rows(w.reshape(-1, 1), route, 1)[:, 0]


_rows_from_tokens.defvjp(
    lambda x, route: (_rows_from_tokens(x, route), route),
    lambda route, g: (_tokens_from_rows(g, route), None))
_tokens_from_rows.defvjp(
    lambda y, route: (_tokens_from_rows(y, route), route),
    lambda route, g: (_rows_from_tokens(g, route), None))
_rows_from_assignments.defvjp(
    lambda w, route: (_rows_from_assignments(w, route), route),
    lambda route, g: (_gather_assignments(g[:, None], route)[..., 0].T, None))


def pass_rows(n: int, spec: DroplessSpec) -> int:
    """Rows of the buffer one pass of the dropless layer fills, from n
    tokens: twice what uniform routing sends to the experts held here (and
    a tile an expert), never more than the worst case, which is one pass."""
    uniform = -(-n * spec.top_k * spec.experts_held // spec.num_experts)
    return min(buffer_rows(2 * uniform, spec.experts_held),
               buffer_rows(n * spec.top_k, spec.experts_held))


def route_tokens(n32, blk, spec: DroplessSpec):
    """`dropless_route` of n32 [B, T, D] under the router's scope: the
    (idx, weights) [B * T, k] that moe_dropless_local is handed where the
    router reads other rows than the experts (`spec.router_input`); from
    its own rows it computes the same itself."""
    with scope(MOE_ROUTE):
        return dropless_route(n32.reshape(-1, n32.shape[-1]), blk["router"],
                              blk.get("router_bias"), spec)


def moe_dropless_local(n32, blk, spec: DroplessSpec, compute_dtype,
                       axis_name: Optional[str] = None, rows: Optional[int] = None,
                       route=None):
    """The routed experts' part of a dropless layer on local rows.

    n32 [B, T, D]: the float32 normed hidden. blk: "router" [D, E_all],
    "router_bias" [E_all] (`sigmoid` scores), "experts": {"w_gate", "w_up"
    [held, D, F], "w_down" [held, F, D]} (experts gated by
    `spec.activation`). `route`: `route_tokens`' pair where
    `spec.router_input` is not the layer's own rows, None otherwise.
    Returns (y [B, T, D] in
    compute_dtype, stats): the weighted sum over the chosen experts HELD
    HERE, and int32 counters of this call: "counts" [held], the rows each
    of them got; "unserved", the tokens none of whose experts is held (they
    get zeros: the caller adds what every chip computes alike, such as a
    shared expert); "passes" and "buffer_rows", below; under a `relu` gate
    also "gate_active", the gate's pre-activations above zero over the rows
    routed here, and "gate_entries", how many those are.

    Every assignment held here gets a row. The rows are laid out for the
    worst case and WALKED IN PASSES of a `rows`-row buffer (`pass_rows`:
    twice the uniform load; any multiple of a tile can be asked for, and
    the worst case is the layer in one pass whatever the routing), as many
    as the live tiles need, counted on the device: one compiled body, so a
    layer past its buffer pays for one more pass and nothing else.
    ops/grouped_matmul skips what is empty inside a pass. axis_name is the
    expert axis of a deployment whose exchange this repo does not build
    yet: only None (this chip's share, no exchange) is accepted."""
    if axis_name is not None:
        raise NotImplementedError(
            "the dropless layer runs one chip's share without its exchange; "
            "an all_to_all over an expert axis is not built (ROADMAP M3)")
    if (route is None) != (spec.router_input == "ffn_norm"):
        raise ValueError(f"router_input={spec.router_input!r}: the layer routes its own rows "
                         "(route=None) or is handed route_tokens' pair, as the spec says")
    b, t, d = n32.shape
    n = b * t
    rows = pass_rows(n, spec) if rows is None else rows
    x32 = n32.reshape(n, d)
    if route is None:
        with scope(MOE_ROUTE):
            route = dropless_route(x32, blk["router"], blk.get("router_bias"), spec)
    idx, w = route
    with scope(MOE_DISPATCH):
        plan = _dispatch_plan(idx, spec, n, rows)
    y, active = _routed(x32.astype(compute_dtype), w, blk["experts"], plan, rows,
                        spec.activation)
    stats = {"counts": plan.counts,
             "unserved": jnp.sum(~jnp.any(plan.held, axis=-1), dtype=jnp.int32),
             "passes": _passes(plan, rows), "buffer_rows": jnp.int32(rows)}
    if active is not None:
        width = blk["experts"]["w_gate"].shape[-1]
        stats.update(gate_active=active, gate_entries=jnp.sum(plan.counts) * width)
    return y.reshape(b, t, d), stats


def combine_rows_read(stats: Dict, assignments: int, d: int, dtype):
    """int32: the rows of [*, d] `dtype` that `_tokens_from_rows` reads as the
    forward's combine of the layer whose counters `stats` are, over its
    passes. The kernel fetches an assignment's row in the one pass that holds
    it: the rows held here. The plain form reads a row for each of the
    `assignments` (N x k) in every pass. The layer's caller adds it to the
    counters under `combine_rows_read` (models/mla_moe.ffn_half)."""
    if rows_sum_path(d, dtype) == "pallas":
        return jnp.sum(stats["counts"], dtype=jnp.int32)
    return stats["passes"] * jnp.int32(assignments)


def no_routing(held: int) -> Dict:
    """A layer that routes nothing: moe_dropless_local's counters and its
    caller's `combine_rows_read`."""
    return {"counts": jnp.zeros((held,), jnp.int32), "unserved": jnp.int32(0),
            "passes": jnp.int32(0), "buffer_rows": jnp.int32(0),
            "combine_rows_read": jnp.int32(0)}


class _Plan(NamedTuple):
    """Where the N x k assignments lie in the worst case's rows: sorted by
    expert, each expert on tile boundaries, live rows packed from row 0.
    Nothing here is sized by the worst case but `layout.tile_expert`."""

    counts: jax.Array   # int32 [held]: the rows each held expert got
    first: jax.Array    # int32 [held]: its first slot in `order`
    order: jax.Array    # int32 [N * k]: sorted slot -> assignment n * k + j
    pos: jax.Array      # int32 [N, k]: an assignment's row, where `held`
    held: jax.Array     # bool [N, k]: its expert lives here
    layout: GroupLayout


def _dispatch_plan(idx, spec: DroplessSpec, n: int, rows: int) -> _Plan:
    """The plan from the chosen experts idx [N, k]; `layout.tile_expert`
    reaches to the end of the last `rows`-row pass the worst case needs."""
    k, held_n = spec.top_k, spec.experts_held
    local = idx - spec.expert_offset
    held = (local >= 0) & (local < held_n)                       # [N, k]
    key = jnp.where(held, local, held_n).reshape(-1)             # [A]
    counts = jnp.sum(key[None] == jnp.arange(held_n, dtype=jnp.int32)[:, None],
                     axis=1, dtype=jnp.int32)                    # [held]
    worst = buffer_rows(n * k, held_n, TILE_M)
    layout = group_layout(counts, -(-worst // rows) * rows, TILE_M)
    # one stable sort by expert orders the assignments; both maps are read
    # off it: `order` (sorted slot -> assignment) and its inverse `slot`
    a = jnp.arange(n * k, dtype=jnp.int32)
    _, order = lax.sort((key, a), num_keys=1, is_stable=True)
    _, slot = lax.sort((order, a), num_keys=1)
    first = jnp.cumsum(counts) - counts                          # [held]
    # an assignment's row: its expert's first row plus its rank among that
    # expert's assignments
    e_a = jnp.minimum(key, held_n - 1)
    pos = jnp.where(held.reshape(-1), layout.starts[e_a] + slot - first[e_a], 0).reshape(n, k)
    return _Plan(counts, first, order, pos, held, layout)


def _passes(plan: _Plan, rows: int):
    """int32: the passes of `rows` rows that hold the live tiles; at least 1."""
    return -(-plan.layout.n_live[0] * TILE_M // rows)


def _pass_route(plan: _Plan, p, rows: int):
    """`route` (see above) of pass p, the layout's rows p * rows .. (p + 1)
    * rows - 1: a row's assignment is the sorted slot of the same rank."""
    lay = plan.layout
    r = p * rows + jnp.arange(rows, dtype=jnp.int32)
    e_r = lay.tile_expert[r // TILE_M]
    in_e = r - lay.starts[e_r]
    row_live = (in_e < plan.counts[e_r]) & (r // TILE_M < lay.n_live[0])
    last = plan.order.shape[0] - 1
    row_assign = jnp.where(row_live, plan.order[jnp.minimum(plan.first[e_r] + in_e, last)], 0)
    here = plan.held & (plan.pos >= p * rows) & (plan.pos < (p + 1) * rows)
    return row_assign, row_live, jnp.where(here, plan.pos - p * rows, 0), here


def _pass(x, w, experts, plan: _Plan, p, rows: int, activation: str):
    """What the rows of pass p add to y [N, D]: their tokens gathered into
    a [rows, D] buffer, the three grouped products over the pass's part of
    the layout, the rows' weights, the gather back over N x k. Beside it,
    under a `relu` gate, int32: the gate's pre-activations above zero in
    the pass's live rows (None under `silu`, whose step counts nothing)."""
    with scope(MOE_DISPATCH):
        route = _pass_route(plan, p, rows)
        layout = layout_pass(plan.layout, p, rows)
        xs = _rows_from_tokens(x, route)
    with scope(MOE_EXPERTS):
        gate = grouped_matmul(xs, experts["w_gate"], layout)
        up = grouped_matmul(xs, experts["w_up"], layout)
        ys = grouped_matmul(ACTIVATIONS[activation](gate) * up, experts["w_down"], layout)
        active = (jnp.sum((gate > 0) & route[1][:, None], dtype=jnp.int32)
                  if activation == "relu" else None)
    with scope(MOE_COMBINE):
        # weighted on the row side, so no [N, k, D] tensor exists in either pass
        ys = ys * _rows_from_assignments(w, route)[:, None].astype(ys.dtype)
        return _tokens_from_rows(ys, route), active


def _sum_over_passes(plan: _Plan, rows: int, like, term):
    """sum over the passes p of term(p), a tree shaped as `like`, in
    float32 (a count in its own integers): a `while` whose count the device
    decides (no reverse rule of its own, hence `_routed`'s). One pass adds
    its term to zeros: the bits of the term."""
    wide = lambda v: jnp.float32 if jnp.issubdtype(v.dtype, jnp.floating) else v.dtype
    zero = jax.tree.map(lambda v: jnp.zeros(v.shape, wide(v)), like)
    add = lambda acc, v: acc + v.astype(acc.dtype)
    total = lax.fori_loop(0, _passes(plan, rows),
                          lambda p, acc: jax.tree.map(add, acc, term(p)), zero)
    return jax.tree.map(lambda s, v: s.astype(v.dtype), total, like)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _routed(x, w, experts, plan: _Plan, rows: int, activation: str):
    """x [N, D], the router's weights w [N, k] and the held experts' stacked
    matrices -> (y [N, D], `_pass`'s count or None): `_pass` summed over the
    passes. The backward is a
    second loop of the same count that runs a pass again and takes its
    `jax.vjp`, so a pass's rows live for one turn of one loop (under `remat`
    the half-block is run again anyway; without it the re-run is the
    layer's forward a second time)."""
    count = jax.ShapeDtypeStruct((), jnp.int32) if activation == "relu" else None
    return _sum_over_passes(plan, rows, (x, count),
                            lambda p: _pass(x, w, experts, plan, p, rows, activation))


def _routed_fwd(x, w, experts, plan, rows, activation):
    return _routed(x, w, experts, plan, rows, activation), (x, w, experts, plan)


def _routed_bwd(rows, activation, res, g):
    x, w, experts, plan = res

    def grads(p):
        y_of = lambda *a: _pass(*a, plan, p, rows, activation)[0]
        return jax.vjp(y_of, x, w, experts)[1](g[0])

    return (*_sum_over_passes(plan, rows, (x, w, experts), grads), None)


_routed.defvjp(_routed_fwd, _routed_bwd)


def routing_counters(stats):
    """The step's routing counters from the expert layers' stacked
    `moe_dropless_local` counters (already summed over the mesh): counts
    [L, held], unserved, passes, buffer_rows [L]. rows_here,
    max_expert_rows, min_expert_rows, tokens_unserved, passes (L when every
    layer fits one pass) and buffer_rows (what a pass holds), each summed
    over layers under `moe_<name>` and per layer under
    `moe_<name>_per_layer`; and `moe_rows_max_over_mean`: the fullest
    expert's rows over the mean expert's, layers summed. Where the layers'
    caller counted it (`combine_rows_read` [L]) also `moe_combine_rows_read`,
    the rows the forward's combine fetched: rows_here through the kernel of
    ops/moe_rows_sum.py, N x k x passes through the plain form. Where the layers
    count their `relu` gate (gate_active, gate_entries [L]) also
    `moe_gate_active`, the share of the gate's pre-activations above zero
    over the rows routed here (a half at fresh weights; a SiLU in its place
    has no such share), and `moe_gate_active_per_layer`."""
    counts = stats["counts"]
    per = {"rows_here": jnp.sum(counts, axis=1), "max_expert_rows": jnp.max(counts, axis=1),
           "min_expert_rows": jnp.min(counts, axis=1), "tokens_unserved": stats["unserved"],
           "passes": stats["passes"], "buffer_rows": stats["buffer_rows"]}
    if "combine_rows_read" in stats:
        per["combine_rows_read"] = stats["combine_rows_read"]
    out = {}
    for name, v in per.items():
        out[f"moe_{name}"] = jnp.sum(v)
        out[f"moe_{name}_per_layer"] = v
    mean = jnp.maximum(out["moe_rows_here"], 1).astype(jnp.float32) / counts.shape[1]
    out["moe_rows_max_over_mean"] = out["moe_max_expert_rows"].astype(jnp.float32) / mean
    if "gate_active" in stats:
        share = lambda a, n: a.astype(jnp.float32) / jnp.maximum(n, 1).astype(jnp.float32)
        out["moe_gate_active"] = share(jnp.sum(stats["gate_active"]),
                                       jnp.sum(stats["gate_entries"]))
        out["moe_gate_active_per_layer"] = share(stats["gate_active"], stats["gate_entries"])
    return out


def stack_layers(stats):
    """The expert layers' counters, one dict a layer -> one dict of [L, ...]."""
    return jax.tree.map(lambda *v: jnp.stack(v), *stats)


def apply_moe_transformer(
    cfg: "TransformerConfig",
    moe: MoEConfig,
    params: Dict,
    tokens: jax.Array,  # int32 [B_local, T_local]
    axis_name: Optional[str] = None,
    seq_axis_name: Optional[str] = None,
) -> tuple:
    """Forward -> (logits [B_local, T_local, vocab], mean aux loss).

    `seq_axis_name` composes expert parallelism with sequence parallelism
    (parallel/ep_sp.py): attention runs on the ring/Ulysses over that axis
    and positions index globally, while the MoE dispatch all_to_alls stay
    on the expert axis — the two collectives touch orthogonal mesh
    dimensions, so neither needs to know about the other."""
    from ..models.transformer import (
        _rms_norm,
        select_attention,
        transformer_block,
    )

    b, t = tokens.shape
    if seq_axis_name is not None:
        pos = lax.axis_index(seq_axis_name) * t + jnp.arange(t)
    else:
        pos = jnp.arange(t)
    x = params["embed"][tokens] + params["pos_embed"][pos][None]
    attend = select_attention(cfg, seq_axis_name)

    def block_fn(x, blk):
        # transformer_block calls mlp(h) exactly once; the cell carries the
        # aux loss out of the callback and returns it as a proper output
        # (so jax.checkpoint can wrap the whole block)
        aux_cell = []

        def mlp(h):
            out, aux = moe_mlp_local(h, blk, moe, axis_name)
            aux_cell.append(aux)
            return out

        x = transformer_block(cfg, x, blk, attend, mlp=mlp)
        return x, aux_cell[0]

    if cfg.remat:
        block_fn = jax.checkpoint(block_fn)

    aux_total = 0.0
    for blk in params["blocks"]:
        x, aux = block_fn(x, blk)
        aux_total = aux_total + aux

    cd = cfg.effective_compute_dtype
    xf = _rms_norm(x.astype(cd), params["out_norm"].astype(cd))
    logits = xf @ params["embed"].T.astype(cd)
    return logits, aux_total / cfg.depth


def make_moe_train_step(
    cfg: "TransformerConfig",
    moe: MoEConfig,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis_name: str = EP_AXIS,
    donate: bool = True,
):
    """Jitted MoE LM train step: (params, opt_state, tokens [B, T]) ->
    (params, opt_state, loss, aux). Expert weights + batch sharded over the
    expert axis; everything else replicated (the axis is simultaneously the
    data-parallel axis)."""
    specs_tree = moe_param_specs(cfg, axis_name)

    def shard_fn(params, opt_state, tokens):
        n = lax.axis_size(axis_name)

        def loss_fn(p):
            logits, aux = apply_moe_transformer(cfg, moe, p, tokens, axis_name)
            task = next_token_nll(logits, tokens)
            local = task + moe.aux_loss_weight * aux
            # sum-over-shards AD rule (see module docstring): local/n
            return local / n, (task, aux)

        (_, (task_loss, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        grads = jax.tree.map(
            lambda g, s: lax.psum(g, axis_name) if s == P() else g,
            grads,
            specs_tree,
            is_leaf=lambda x: isinstance(x, P),
        )
        updates, new_opt = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        return (
            new_params,
            new_opt,
            lax.pmean(task_loss, axis_name),
            lax.pmean(aux, axis_name),
        )

    shapes = _moe_param_shapes(cfg, moe)
    opt_specs = opt_state_specs(jax.eval_shape(tx.init, shapes), shapes, specs_tree)
    mapped = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(specs_tree, opt_specs, P(axis_name)),
        out_specs=(specs_tree, opt_specs, P(), P()),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(0, 1) if donate else ())


def _moe_param_shapes(cfg: "TransformerConfig", moe: MoEConfig) -> Dict:
    return jax.eval_shape(
        lambda: init_moe_params(cfg, moe, jax.random.key(0))
    )


def init_moe_state(
    cfg: "TransformerConfig",
    moe: MoEConfig,
    tx: optax.GradientTransformation,
    key: jax.Array,
    mesh: Mesh,
    axis_name: str = EP_AXIS,
):
    """Init (params, opt_state) placed with EP shardings."""
    params = shard_params_moe(
        cfg, init_moe_params(cfg, moe, key), mesh, axis_name
    )
    from .mesh import place_on_mesh

    opt_state = tx.init(params)
    specs = opt_state_specs(opt_state, params, moe_param_specs(cfg, axis_name))
    return params, place_on_mesh(opt_state, mesh, specs)


def shard_moe_batch(tokens, mesh: Mesh, axis_name: str = EP_AXIS):
    """[B_global, T] -> B sharded over the expert axis."""
    return jax.device_put(tokens, NamedSharding(mesh, P(axis_name)))
