"""Latent-attention, sparse-expert decoder LM (the DeepSeek-V3 block).

The second LM family beside models/transformer.py, for public models whose
config.json says `model_type: deepseek_v3`. Same shape of module: pure
init/apply, the call `apply_mla_moe(cfg, params, tokens, seq_axis_name,
pos_offset)` of apply_transformer, the attention picked by the one
selection point models/transformer.select_attention (naive, flash, ring,
Ulysses), so the flash kernels serve both families.

Per token row x (every norm RMS with gain, statistics in float32):

- block: h = x + Attn(norm(x)); y = h + FFN(norm(h)). No position
  embedding; a final norm; an untied head [D, V].
- Attn (multi-head latent attention, no query compression): q = n W_q,
  per head (q_nope, q_rope); (c, k_rope) = n W_kva with ONE k_rope head
  shared by all; c = norm(c); per head (k_nope, v) = c W_kvb; rotary on
  q_rope and k_rope (pairs (2i, 2i+1) as `rope_interleave` stores them,
  angle pos * theta^(-2i/d_rope), global positions under a sequence
  axis); k = [k_nope, k_rope]; causal softmax(q k^T / sqrt(d_nope +
  d_rope)) v; W_o. The query/key width (192 at the published sizes)
  differs from the value width (128): ops/flash_attention takes both.
- FFN of the first `first_k_dense_replace` layers: (silu(n W_g) * n W_u) W_d.
- FFN of the others: the routed experts held here (parallel/moe.
  moe_dropless_local: sigmoid scores over ALL experts, top-k by score plus
  the aux-loss-free bias, weights normalised over the k and scaled, no
  token dropped) plus the shared experts (one gated MLP n_shared x wide).

The chip's share is the configuration's, not the mesh's: `experts_held`
and `expert_offset` say which routed experts' weights exist here, and
`vocab_size` is the slice of the vocabulary held (embedding, head, loss).
What the absent experts would add is left out and that partial result
goes on, as on one chip of an expert-parallel deployment.

The aux-loss-free bias (`router_bias`) is a leaf with no gradient; its
update rule is not part of the step.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..obs.scopes import EMBED, FFN, HEAD_LOSS, MIXER_MLA, MLP, MOE, scope
from ..parallel.moe import (DroplessSpec, combine_rows_read, moe_dropless_local, no_routing,
                            routing_counters, stack_layers)
from .lm import LMFamily
from .transformer import flash_layers, flash_plans, remat_block, select_attention

# config.json keys this family reads; every other key is carried by the
# benchmark's file and ignored here
_PUBLISHED = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "intermediate_size", "moe_intermediate_size", "n_routed_experts",
    "n_shared_experts", "num_experts_per_tok", "first_k_dense_replace",
    "routed_scaling_factor", "norm_topk_prob", "rope_theta", "rms_norm_eps",
)
# what from_published turns down, for models/lm.require_dense's message
REFUSES = "query compression, rope scaling, grouped routing, a tied head"


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    # the published keys, under their published names
    vocab_size: int = 256
    hidden_size: int = 64
    num_hidden_layers: int = 3
    num_attention_heads: int = 4
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    intermediate_size: int = 128
    moe_intermediate_size: int = 32
    n_routed_experts: int = 16
    n_shared_experts: int = 2
    num_experts_per_tok: int = 3
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.448
    norm_topk_prob: bool = True
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    # no rotation of the 64 "rope" channels: no deepseek_v3 config sets it;
    # models/kda_hybrid.py's latent attention layers are this one with it on
    mla_use_nope: bool = False
    # this chip's share of the routed experts (all of them by default)
    experts_held: Optional[int] = None
    expert_offset: int = 0
    # how it is run: the same options, with the same meaning, as
    # TransformerConfig (select_attention reads them off either)
    causal: bool = True
    dtype: Any = jnp.float32
    remat: bool = False
    bidirectional_ring: bool = False
    sp_attention: str = "ring"
    attention_impl: str = "naive"
    compute_dtype: Any = None

    def __post_init__(self):
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.n_routed_experts)
        self.routing  # a share that is none raises here, not in the step

    @classmethod
    def from_published(cls, published: Dict, **run) -> "MlaMoeConfig":
        """From a config.json-shaped dict (plus `experts_held` /
        `expert_offset`). What the family cannot express is an error, not
        a silent departure."""
        refuse = {
            "q_lora_rank": (None,), "rope_scaling": (None,), "n_group": (1, None),
            "topk_group": (1, None), "scoring_func": ("sigmoid",),
            "topk_method": ("noaux_tc", None), "hidden_act": ("silu", None),
            "attention_bias": (False, None), "rope_interleave": (True,),
            "moe_layer_freq": (1, None), "tie_word_embeddings": (False, None),
        }
        for key, allowed in refuse.items():
            if published.get(key, allowed[0]) not in allowed:
                raise ValueError(
                    f"{key}={published[key]!r}: models/mla_moe.py supports {allowed[0]!r} only")
        missing = [k for k in _PUBLISHED if k not in published]
        if missing:
            raise ValueError(f"config lacks {missing}")
        share = {k: published[k] for k in ("experts_held", "expert_offset") if k in published}
        return cls(**{k: published[k] for k in _PUBLISHED}, **share, **run)

    @property
    def effective_compute_dtype(self):
        return self.compute_dtype if self.compute_dtype is not None else self.dtype

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def routing(self) -> DroplessSpec:
        return DroplessSpec(
            num_experts=self.n_routed_experts, top_k=self.num_experts_per_tok,
            experts_held=self.experts_held, expert_offset=self.expert_offset,
            routed_scale=self.routed_scaling_factor, norm_topk_prob=self.norm_topk_prob)

    @property
    def moe_layers(self) -> int:
        return max(self.num_hidden_layers - self.first_k_dense_replace, 0)


def _gated_init(key, d, width, dtype, stack=None):
    kg, ku, kd = jax.random.split(key, 3)
    lead = () if stack is None else (stack,)
    n = lambda k, shape, fan: (jax.random.normal(k, lead + shape) / fan ** 0.5).astype(dtype)
    return {"w_gate": n(kg, (d, width), d), "w_up": n(ku, (d, width), d),
            "w_down": n(kd, (width, d), width)}


def init_mla_moe(cfg: MlaMoeConfig, key: jax.Array) -> Dict:
    d, h, dt = cfg.hidden_size, cfg.num_attention_heads, cfg.dtype
    keys = jax.random.split(key, 2 + cfg.num_hidden_layers)
    dense = lambda k, shape: (jax.random.normal(k, shape) / shape[0] ** 0.5).astype(dt)
    blocks = []
    for i in range(cfg.num_hidden_layers):
        bk = jax.random.split(keys[2 + i], 8)
        blk = {
            "ln1": jnp.ones((d,), dt),
            "wq": dense(bk[0], (d, h * cfg.qk_head_dim)),
            "wkv_a": dense(bk[1], (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim)),
            "kv_norm": {"scale": jnp.ones((cfg.kv_lora_rank,), dt)},
            "wkv_b": dense(bk[2], (cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim))),
            "wo": dense(bk[3], (h * cfg.v_head_dim, d)),
            "ln2": jnp.ones((d,), dt),
        }
        if i < cfg.first_k_dense_replace:
            blk["mlp"] = _gated_init(bk[4], d, cfg.intermediate_size, dt)
        else:
            blk["router"] = dense(bk[5], (d, cfg.n_routed_experts)).astype(jnp.float32)
            blk["router_bias"] = jnp.zeros((cfg.n_routed_experts,), jnp.float32)
            blk["shared"] = _gated_init(
                bk[6], d, cfg.n_shared_experts * cfg.moe_intermediate_size, dt)
            blk["experts"] = _gated_init(
                bk[7], d, cfg.moe_intermediate_size, dt, stack=cfg.experts_held)
        blocks.append(blk)
    return {
        "embed": (jax.random.normal(keys[0], (cfg.vocab_size, d)) * 0.02).astype(dt),
        "blocks": blocks,
        "out_norm": jnp.ones((d,), dt),
        "head": dense(keys[1], (d, cfg.vocab_size)),
    }


def _rms32(x, gain, eps):
    """RMS norm with float32 statistics; the float32 result."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) \
        * gain.astype(jnp.float32)


def _rope(x, pos, theta: float):
    """Rotate the pairs (2i, 2i+1) of x [B, T, H, d] by pos[t] * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]                # [T, d/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    pair = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pair[..., 0], pair[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _gated_mlp(n, w, cd):
    return (jax.nn.silu(n @ w["w_gate"].astype(cd)) * (n @ w["w_up"].astype(cd))) \
        @ w["w_down"].astype(cd)


def mla_attention(cfg, n, blk, attend, pos):
    """n [B, T, D] in the compute dtype -> the attention branch [B, T, D].
    `cfg.mla_use_nope` leaves both rotations out: the shared 64 channels
    then are plain key channels and `pos` is unused."""
    cd = n.dtype
    b, t, _ = n.shape
    h, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    q = (n @ blk["wq"].astype(cd)).reshape(b, t, h, dn + dr)
    kva = n @ blk["wkv_a"].astype(cd)
    c = _rms32(kva[..., :cfg.kv_lora_rank], blk["kv_norm"]["scale"], cfg.rms_norm_eps).astype(cd)
    kvb = (c @ blk["wkv_b"].astype(cd)).reshape(b, t, h, dn + dv)
    if cfg.mla_use_nope:
        k_rope = kva[..., None, cfg.kv_lora_rank:]
    else:
        q_rope = _rope(q[..., dn:], pos, cfg.rope_theta)
        k_rope = _rope(kva[..., None, cfg.kv_lora_rank:], pos, cfg.rope_theta)
        q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_rope, (b, t, h, dr))], axis=-1)
    o = attend(q, k, kvb[..., dn:])                                    # [B, T, H, dv]
    return o.reshape(b, t, h * dv) @ blk["wo"].astype(cd)


def mla_mixer_half(cfg, x, blk, attend, pos):
    """x [B, T, D] in the compute dtype -> x + Attn(norm(x))."""
    cd = x.dtype
    with scope(MIXER_MLA):
        return x + mla_attention(cfg, _rms32(x, blk["ln1"], cfg.rms_norm_eps).astype(cd),
                                 blk, attend, pos)


def ffn_half(cfg, x, blk, route=None):
    """x [B, T, D] in the compute dtype -> (x + FFN(norm(x)), the layer's
    routing counters as parallel/moe.moe_dropless_local gives them): the
    dense MLP where the block holds `mlp` (zero counters), else the routed
    experts held here plus the shared expert where the block holds one
    (`shared`). `route`: parallel/moe.route_tokens' pair where the router
    read the block's first norm and not this half's (`cfg.routing.
    router_input`). The half every block of this family, of models/
    kda_hybrid.py, models/swa_moe.py and models/prerouted_moe.py ends in,
    whatever its mixer."""
    cd = x.dtype
    with scope(FFN):
        n32 = _rms32(x, blk["ln2"], cfg.rms_norm_eps)
        if "mlp" in blk:
            with scope(MLP):
                y = x + _gated_mlp(n32.astype(cd), blk["mlp"], cd)
            return y, no_routing(cfg.experts_held)
        with scope(MOE):
            routed, stats = moe_dropless_local(n32, blk, cfg.routing, cd, route=route)
            stats["combine_rows_read"] = combine_rows_read(
                stats, n32.shape[0] * n32.shape[1] * cfg.routing.top_k, n32.shape[2], cd)
        y = x + routed.astype(cd)
        if "shared" not in blk:
            return y, stats
        with scope(MLP):
            return y + _gated_mlp(n32.astype(cd), blk["shared"], cd), stats


def mla_moe_block(cfg: MlaMoeConfig, x, blk, attend, pos):
    """One block -> (x, the layer's routing counters); the counters are
    zeros for a dense layer."""
    x = mla_mixer_half(cfg, x.astype(cfg.effective_compute_dtype), blk, attend, pos)
    return ffn_half(cfg, x, blk)


def saved_layers(cfg: MlaMoeConfig, batch: int, seq_len: int):
    """models/transformer.saved_layers for this family: every layer's
    latent attention through the flash kernels."""
    return flash_layers(cfg, batch, seq_len, cfg.num_attention_heads, cfg.qk_head_dim,
                        cfg.v_head_dim, cfg.num_hidden_layers)


def apply_mla_moe(
    cfg: MlaMoeConfig,
    params: Dict,
    tokens: jax.Array,  # int32 [B, T_local], ids of the vocabulary slice
    seq_axis_name: Optional[str] = None,
    pos_offset: Optional[jax.Array] = None,
):
    """Forward -> (logits [B, T_local, vocab], routing): routing["counts"]
    int32 [expert layers, held] are the rows each expert held here got from
    these tokens, routing["unserved"] int32 [expert layers] the tokens none
    of whose experts is held, routing["passes"] and ["buffer_rows"] the
    passes each layer ran and the rows a pass holds (parallel/moe.
    moe_dropless_local's counters, stacked). Under shard_map pass
    seq_axis_name, as for apply_transformer: attention runs over the axis
    and the rotary angles take GLOBAL positions."""
    b, t_loc = tokens.shape
    shard = jax.lax.axis_index(seq_axis_name) * t_loc if seq_axis_name is not None else 0
    if pos_offset is not None:
        shard = shard + pos_offset
    pos = shard + jnp.arange(t_loc)
    attend = select_attention(cfg, seq_axis_name)
    cd = cfg.effective_compute_dtype

    def block(x, blk):
        return mla_moe_block(cfg, x, blk, attend, pos)

    if cfg.remat:
        block = remat_block(block, saved_layers(cfg, b, t_loc), params)
    with scope(EMBED):
        x = params["embed"][tokens].astype(cd)
    routed = []
    for blk in params["blocks"]:
        x, stats = block(x, blk)
        if "mlp" not in blk:
            routed.append(stats)
    with scope(HEAD_LOSS):
        n = _rms32(x, params["out_norm"], cfg.rms_norm_eps).astype(cd)
    routing = stack_layers(routed) if routed else {}
    with scope(HEAD_LOSS):
        return n @ params["head"].astype(cd), routing


def plans(cfg: MlaMoeConfig, seq_len: int, seq_shards: int):
    return flash_plans(cfg, seq_len, seq_shards, cfg.qk_head_dim, cfg.v_head_dim)


def moe_counters(aux) -> Dict:
    return routing_counters(aux)


CONFIG = MlaMoeConfig


def family(cfg: MlaMoeConfig) -> LMFamily:
    """models/lm.LMFamily of such a config: the expert layers' routing
    (parallel/moe.routing_counters) is its one group of counters."""
    return LMFamily(init_mla_moe, apply_mla_moe, moe_counters if cfg.moe_layers else None,
                    saved_layers, plans, (("moe_route", "moe_"),))
