"""Sequence-parallel transformer LM — the long-context model family.

The reference has no attention workloads (SURVEY.md section 5), so this
family has no counterpart to cite; it exists because long-context is a
first-class capability of this framework. The design splits the sequence
axis across the mesh (parallel/ring_attention.py): every non-attention op
(embed, norms, MLP) is pointwise over sequence and runs on local shards
with zero communication; attention is the ring. Params stay replicated, so
the PS data-parallel engine and the sequence axis compose on a 2-D mesh
(dp x sp) without re-sharding weights.

Pure init/apply (no flax.linen) so the module works identically inside and
outside shard_map.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..obs.scopes import EMBED, FFN, HEAD_LOSS, MIXER_ATTENTION, MLP, scope
from ..parallel.ring_attention import SEQ_AXIS, full_attention, ring_attention


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256
    dim: int = 128
    depth: int = 2
    heads: int = 4
    mlp_ratio: int = 4
    max_seq_len: int = 2048
    causal: bool = True
    dtype: Any = jnp.float32
    # rematerialize each block in backward (remat_block): a block keeps its
    # input and, where the within-chip flash kernels attend, their o and lse
    # (B*H*T*(d_v*itemsize + 4) bytes a layer: o is as large as the input in
    # compute_dtype here, where H*d_v == dim) and, where the device has the
    # room (remat_plan), their folded q, k, v; it runs everything else again.
    # ~1/3 more FLOPs for O(depth) -> O(1) of a block's other ~30 activations,
    # the standard lever for long-context training
    remat: bool = False
    # rotate K/V both ways on the sequence ring (half the sequential hops,
    # both ICI directions of a physical ring) — see parallel/ring_attention
    bidirectional_ring: bool = False
    # sequence-parallel attention scheme: "ring" (K/V rotation, any head
    # count) or "ulysses" (two all_to_alls, heads % axis_size == 0) — see
    # parallel/ulysses.py for the trade-off
    sp_attention: str = "ring"
    # within-chip attention: "naive" (materializes [T, T]) or "flash"
    # (Pallas blockwise kernel, ops/flash_attention.py). Applies to ALL
    # paths: single-device/tp/pp/moe use it directly; sp "ring" switches
    # to ring_flash_attention (partial-triple kernel per hop, never
    # [T_loc, T_loc]; one-way or bidirectional) and sp "ulysses" runs it
    # on the gathered full-seq/local-heads layout
    attention_impl: str = "naive"
    # mixed precision: params/optimizer state stay `dtype` (keep f32 —
    # bf16 Adam moments are broken: bf16(0.999) == 1.0), while block
    # matmuls/attention run in `compute_dtype` (None = same as dtype).
    # Same convention as the CNN trainer's --dtype bfloat16.
    compute_dtype: Any = None

    @property
    def effective_compute_dtype(self):
        return self.compute_dtype if self.compute_dtype is not None else self.dtype

    @property
    def head_dim(self) -> int:
        assert self.dim % self.heads == 0
        return self.dim // self.heads


def _dense_init(key, shape, dtype, scale=None):
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / (fan_in ** 0.5)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def init_transformer(cfg: TransformerConfig, key: jax.Array) -> Dict:
    keys = jax.random.split(key, 2 + cfg.depth)
    params = {
        "embed": (
            jax.random.normal(keys[0], (cfg.vocab_size, cfg.dim)) * 0.02
        ).astype(cfg.dtype),
        "pos_embed": (
            jax.random.normal(keys[1], (cfg.max_seq_len, cfg.dim)) * 0.02
        ).astype(cfg.dtype),
        "blocks": [],
        "out_norm": jnp.ones((cfg.dim,), cfg.dtype),
    }
    for i in range(cfg.depth):
        bk = jax.random.split(keys[2 + i], 6)
        mlp_dim = cfg.dim * cfg.mlp_ratio
        params["blocks"].append(
            {
                "ln1": jnp.ones((cfg.dim,), cfg.dtype),
                "wqkv": _dense_init(bk[0], (cfg.dim, 3 * cfg.dim), cfg.dtype),
                "wo": _dense_init(bk[1], (cfg.dim, cfg.dim), cfg.dtype),
                "ln2": jnp.ones((cfg.dim,), cfg.dtype),
                "w_up": _dense_init(bk[2], (cfg.dim, mlp_dim), cfg.dtype),
                "w_down": _dense_init(bk[3], (mlp_dim, cfg.dim), cfg.dtype),
            }
        )
    return params


def _rms_norm(x, gamma, eps=1e-6):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gamma


def local_attention(cfg: TransformerConfig):
    """The within-chip attention callable for this config: the Pallas
    flash kernel or the naive jnp reference. Shared by the single-device,
    tensor-, pipeline-, and expert-parallel paths."""
    if cfg.attention_impl == "flash":
        from ..ops.flash_attention import flash_attention

        return partial(flash_attention, causal=cfg.causal)
    if cfg.attention_impl == "naive":
        return partial(full_attention, causal=cfg.causal)
    raise ValueError(f"unknown attention_impl {cfg.attention_impl!r}")


def attention_path(cfg, n_seq: int) -> str:
    """Which attention a model runs over a sequence axis of `n_seq`
    members: "local" | "ring" | "ulysses". A sequence axis with one member
    has no ring: K and V would be sent to the chip they are on and every
    partial merged with nothing, so one member takes the within-chip
    attention whatever cfg.sp_attention names. The ONE decision, read by
    select_attention and by cli/train_lm.py's log line and `flash_plan`
    instant."""
    if cfg.sp_attention not in ("ring", "ulysses"):
        raise ValueError(f"unknown sp_attention {cfg.sp_attention!r}")
    return "local" if n_seq == 1 else cfg.sp_attention


def select_attention(cfg: TransformerConfig, seq_axis_name: Optional[str] = None):
    """The attention callable for this config — the ONE selection point.

    seq_axis_name=None, or a mesh axis with one member (attention_path):
    within-chip (naive jnp or Pallas flash). Two or more members: the
    sequence-parallel scheme (cfg.sp_attention) over that mesh axis — ring
    (jnp, or flash-per-hop under attention_impl="flash") or Ulysses (a2a
    re-shard, local attention in cfg.attention_impl). Call it with an axis
    name where the axis is bound (inside the mapped function, as every
    model apply does): its size is a Python int at trace time.
    Shared by the dense transformer (apply_transformer), the MLA/MoE one
    (models/mla_moe.apply_mla_moe) and the MoE transformer
    (parallel/moe.apply_moe_transformer) so no family can diverge in
    attention math."""
    if seq_axis_name is None:
        return local_attention(cfg)
    path = attention_path(cfg, jax.lax.axis_size(seq_axis_name))
    if path == "local":
        return local_attention(cfg)
    if path == "ulysses":
        from ..parallel.ulysses import ulysses_attention

        return partial(
            ulysses_attention, axis_name=seq_axis_name, causal=cfg.causal,
            impl=cfg.attention_impl,
        )
    if cfg.attention_impl == "flash":
        # flash INSIDE each ring hop: no [T_loc, T_loc] block ever
        # materializes (ops/flash_attention partial-triple kernels);
        # bidirectional_ring rotates K/V both ways, two triples/hop
        from ..parallel.ring_attention import ring_flash_attention

        return partial(
            ring_flash_attention,
            axis_name=seq_axis_name,
            causal=cfg.causal,
            bidirectional=cfg.bidirectional_ring,
        )
    return partial(
        ring_attention,
        axis_name=seq_axis_name,
        causal=cfg.causal,
        bidirectional=cfg.bidirectional_ring,
    )


def flash_layers(cfg, batch: int, seq_len: int, heads: int, d: int, d_v: int, layers: int,
                 causal=None):
    """[ops/flash_attention.flash_saves] of `layers` attention layers that
    attend with q, k [batch, seq_len, heads, d] and v [..., d_v] under any
    family's `cfg` and the mask kind `causal` (cfg.causal where None); []
    where the flash kernels do not run."""
    from ..ops.flash_attention import flash_saves

    if cfg.attention_impl != "flash":
        return []
    return [flash_saves(batch, seq_len, heads, d, d_v, cfg.effective_compute_dtype,
                        cfg.causal if causal is None else causal, layers)]


def flash_plans(cfg, seq_len: int, seq_shards: int, d_qk: int, d_v: int, causal=None):
    """The `flash_plan` entry of a family's plans (models/lm.LMFamily.plans)
    under any family's `cfg`: the kernels' tile plan is static, so how many
    tiles of the rectangle the grids never enter (tiles_total - grid_steps)
    is known from the shapes every attention call will have (ops/
    flash_attention.plan_flash says what each field means), and so is the
    path select_attention takes (attention_path decides both; a ring's hops
    attend a shard's length and build their walk from their offsets). []
    where the flash kernels do not run. A family whose layers differ in
    mask gives each kind's as `causal` and gets `mask` (ops/flash_attention.
    mask_name), `window` and `tile_fill` (mask_fill: the share of the live
    tiles' entries the mask keeps) beside the fields every plan has."""
    from ..ops.flash_attention import SlidingWindow, mask_fill, mask_name, plan_flash

    if cfg.attention_impl != "flash":
        return []
    path = attention_path(cfg, seq_shards)
    t_att = seq_len // seq_shards if path == "ring" else seq_len
    own = causal is not None
    causal = causal if own else cfg.causal
    plan = plan_flash(t_att, t_att, d_qk, cfg.effective_compute_dtype, causal, d_v=d_v)
    fields = {f: getattr(plan, f) for f in (
        "block_q", "block_k", "grid_steps", "tiles_run", "tiles_total", "bwd", "dq_acc_bytes")}
    fields.update(d_qk=d_qk, d_v=d_v, attention_path=path, seq_shards=seq_shards)
    if own:
        fields.update(mask=mask_name(causal), tile_fill=mask_fill(plan, t_att, t_att, causal),
                      window=causal.window if isinstance(causal, SlidingWindow) else 0)
    return [("flash_plan", "ps_flash_", fields)]


def saved_layers(cfg: TransformerConfig, batch: int, seq_len: int):
    """What this family's blocks name for `remat` at tokens [batch,
    seq_len] (ops/flash_attention.SavedLayers, one a kind of layer): the
    flash kernels' values where they run. Every family has one; cli/
    train_lm.py reads them through models/lm.LMFamily."""
    return flash_layers(cfg, batch, seq_len, cfg.heads, cfg.head_dim, cfg.head_dim, cfg.depth)


def remat_plan(kinds, params):
    """ops/flash_attention.plan_remat_saves for a model of `kinds` beside
    the training state of `params`, on this process's device."""
    from ..ops.flash_attention import device_bytes_limit, plan_remat_saves

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    return plan_remat_saves(kinds, n_params, device_bytes_limit())


def remat_block(block, kinds, params):
    """`block` under jax.checkpoint as every LM family's `remat` means it:
    the backward keeps the block's input and whatever inside it carries a
    name of remat_plan's (the flash forward kernel's o and lse, the delta
    rule's inverses; the kernels' operands q, k, v where memory allows),
    and recomputes the rest. So the block runs twice, ps_flash_fwd once,
    and of an attention layer's second run what makes q, k and v is dead.
    Where no value has those names (the jnp attention, the ring's hops, a
    state-space block) this is jax.checkpoint(block)."""
    names = remat_plan(kinds, params).names
    return jax.checkpoint(block, policy=jax.checkpoint_policies.save_only_these_names(*names))


_MIXER_LEAVES = ("ln1", "wqkv", "wo")


def _cast(v, dtype, name):
    with scope(name):
        return v.astype(dtype)


def transformer_block(cfg: TransformerConfig, x, blk, attend, mlp=None):
    """One pre-norm block: attention + GELU MLP, both residual.

    The single source of the block math — apply_transformer (below), the
    pipeline-parallel schedule (parallel/pp.py), and the MoE transformer
    (parallel/moe.py, via `mlp`) all run exactly this, so no parallel path
    can desynchronize from the oracle it is tested against.
    `attend` maps ([B,T,H,hd],)*3 -> [B,T,H,hd]; `mlp` (optional) replaces
    the dense GELU MLP, mapping the normed hidden [B,T,D] -> [B,T,D].
    """
    cd = cfg.effective_compute_dtype
    x = _cast(x, cd, MIXER_ATTENTION)
    # cast weights at use, not at init: params (and grads/moments) keep
    # their storage dtype; only the block math runs in compute_dtype (each
    # cast under the scope of the half that reads it, in the leaves' order)
    blk = {k: _cast(v, cd, MIXER_ATTENTION if k in _MIXER_LEAVES else FFN)
           for k, v in blk.items()}
    with scope(MIXER_ATTENTION):
        b, t = x.shape[0], x.shape[1]
        h = _rms_norm(x, blk["ln1"])
        qkv = h @ blk["wqkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        split_heads = lambda a: a.reshape(b, t, cfg.heads, cfg.head_dim)
        o = attend(split_heads(q), split_heads(k), split_heads(v))
        x = x + o.reshape(b, t, cfg.dim) @ blk["wo"]
    with scope(FFN):
        h = _rms_norm(x, blk["ln2"])
        if mlp is not None:
            return x + mlp(h)
        with scope(MLP):
            return x + jax.nn.gelu(h @ blk["w_up"]) @ blk["w_down"]


def apply_transformer(
    cfg: TransformerConfig,
    params: Dict,
    tokens: jax.Array,  # int32 [B, T_local]
    seq_axis_name: Optional[str] = None,
    pos_offset: Optional[jax.Array] = None,
) -> jax.Array:
    """Forward -> logits [B, T_local, vocab].

    Under shard_map pass seq_axis_name: attention runs over that axis
    (select_attention: the sequence-parallel scheme, or the within-chip
    attention where the axis has one member) and positional embeddings
    index by GLOBAL position (shard offset). Outside shard_map
    (seq_axis_name=None) this is the plain single-device model.
    """
    b, t_loc = tokens.shape
    if seq_axis_name is not None:
        shard = jax.lax.axis_index(seq_axis_name) * t_loc
    else:
        shard = 0
    attend = select_attention(cfg, seq_axis_name)
    if pos_offset is not None:
        shard = shard + pos_offset
    pos = shard + jnp.arange(t_loc)
    with scope(EMBED):
        x = params["embed"][tokens] + params["pos_embed"][pos][None]

    def block(x, blk):
        return transformer_block(cfg, x, blk, attend)

    if cfg.remat:
        block = remat_block(block, saved_layers(cfg, b, t_loc), params)
    for blk in params["blocks"]:
        x = block(x, blk)

    cd = cfg.effective_compute_dtype
    with scope(HEAD_LOSS):
        xf = _rms_norm(x.astype(cd), params["out_norm"].astype(cd))
        return xf @ params["embed"].T.astype(cd)


def apply_dense(cfg, params, tokens, seq_axis_name=None, pos_offset=None):
    """apply_transformer as models/lm.LMFamily.apply: nothing is counted."""
    return apply_transformer(cfg, params, tokens, seq_axis_name, pos_offset), {}


def plans(cfg: TransformerConfig, seq_len: int, seq_shards: int):
    return flash_plans(cfg, seq_len, seq_shards, cfg.head_dim, cfg.head_dim)


# what models/lm.py reads of a family's module; this one is built from
# sizes, so it has no published config to refuse
CONFIG = TransformerConfig


def family(cfg: TransformerConfig):
    from .lm import LMFamily  # models/lm.py imports this module

    return LMFamily(init_transformer, apply_dense, None, saved_layers, plans)


def make_sp_forward(
    cfg: TransformerConfig,
    mesh: Mesh,
    axis_name: str = SEQ_AXIS,
    jit: bool = True,
):
    """Sequence-parallel forward: params replicated, tokens/logits sharded
    [B, T] / [B, T, V] along the sequence axis. This is the ONE place the
    sp sharding contract lives — pass jit=False to compose the mapped fn
    inside a larger jitted computation (e.g. a loss)."""
    mapped = jax.shard_map(
        lambda p, tok: apply_transformer(cfg, p, tok, seq_axis_name=axis_name),
        mesh=mesh,
        in_specs=(P(), P(None, axis_name)),
        out_specs=P(None, axis_name),
        check_vma=False,
    )
    return jax.jit(mapped) if jit else mapped
