"""Sliding-window / global grouped-query attention over experts routed from
the ATTENTION's input (the SmallThinker block).

The seventh LM family beside models/transformer.py, mla_moe.py,
ssm_hybrid.py, kda_hybrid.py, eva_dense.py and swa_moe.py, for public models
whose config says `model_type: smallthinker`. Same shape of module: pure
init/apply, the call `apply_prerouted_moe(cfg, params, tokens, seq_axis_name,
pos_offset)`. Two 0/1 lists over depth decide a layer: `sliding_window_layout
[l]` its mask, `rope_layout[l]` whether anything rotates. Every layer is an
expert layer; there is no dense layer and no shared expert.

Per token row x (every norm RMS with gain, statistics in float32; `cd` the
compute dtype; `num_attention_heads` query heads over `num_key_value_heads`
key/value heads of `head_dim`, the same in every layer):

- n = norm_1(x). ROUTE, from n: logits n W_r over all
  `moe_num_primary_experts` (float32 at `highest`), the
  `moe_num_active_primary_experts` largest, weights a float32 softmax over
  those alone (parallel/moe.dropless_route, `softmax_topk`); no bias, no
  scale. The router reads the block's FIRST norm, not the FFN's own.
- h = x + Attn(n): q = n W_q, k = n W_k, v = n W_v, no bias. Where
  `rope_layout[l]` is 1, rotary on q and k over the whole head (rotate-half,
  `rope_theta`); where 0, nothing (no pass is run). Query head h reads
  key/value head h // (heads / kv) (models/swa_moe.gqa_attention, without its
  gate). Keys seen: j <= i, or i - `sliding_window_size` < j <= i where
  `sliding_window_layout[l]` is 1 (ops/flash_attention.SlidingWindow). W_o.
- y = h + sum over the chosen e HELD HERE of w_e (relu(m W_gate,e) * m
  W_up,e) W_down,e, m = norm_2(h), `moe_ffn_hidden_size` wide
  (models/mla_moe.ffn_half with the route handed in, through
  parallel/moe.moe_dropless_local under a `relu` gate).
- a final norm; an untied head [D, V]. No position embedding.

The chip's share is the configuration's, as in models/mla_moe.py:
`experts_held` / `expert_offset` and the `vocab_size` slice. A sequence axis
of more than one member is refused, as models/swa_moe.py refuses it and for
its reason: the ring's hops do not know the window (ROADMAP M5).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..obs.scopes import EMBED, FFN, HEAD_LOSS, MIXER_ATTENTION, MIXER_SWA, MOE, scope
from ..ops.flash_attention import SlidingWindow
from ..parallel.moe import DroplessSpec, pass_rows, route_tokens, routing_counters, stack_layers
from .lm import LMFamily
from .mla_moe import _gated_init, _rms32, ffn_half
from .swa_moe import Rope, gqa_attention, rope_fields
from .transformer import flash_layers, flash_plans, remat_block, select_attention

# config keys this family reads; every other key is carried by the
# benchmark's file and ignored here
_PUBLISHED = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "rms_norm_eps", "moe_num_primary_experts",
    "moe_num_active_primary_experts", "moe_ffn_hidden_size", "sliding_window_size",
    "sliding_window_layout", "rope_layout", "rope_theta",
)
# what from_published turns down, for models/lm.require_dense's message
REFUSES = (
    "moe_primary_router_apply_softmax false, norm_topk_prob false, a rope_scaling, a tied "
    "head, a sequence axis of more than one member")


@dataclasses.dataclass(frozen=True)
class PreroutedMoeConfig:
    # the published keys, under their published names
    vocab_size: int = 256
    hidden_size: int = 64
    num_hidden_layers: int = 4
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    rms_norm_eps: float = 1e-6
    moe_num_primary_experts: int = 8
    moe_num_active_primary_experts: int = 3
    moe_ffn_hidden_size: int = 32
    sliding_window_size: int = 8
    sliding_window_layout: Tuple[int, ...] = (0, 1, 1, 1)
    rope_layout: Tuple[int, ...] = (0, 1, 1, 1)
    rope_theta: float = 10000.0
    # this chip's share of the routed experts (all of them by default)
    experts_held: Optional[int] = None
    expert_offset: int = 0
    # how it is run: the same options, with the same meaning, as
    # TransformerConfig (select_attention reads them off either)
    causal: Any = True
    dtype: Any = jnp.float32
    remat: bool = False
    bidirectional_ring: bool = False
    sp_attention: str = "ring"
    attention_impl: str = "naive"
    compute_dtype: Any = None

    def __post_init__(self):
        for name in ("sliding_window_layout", "rope_layout"):
            layout = tuple(int(v) for v in getattr(self, name))
            object.__setattr__(self, name, layout)
            if len(layout) != self.num_hidden_layers or set(layout) - {0, 1}:
                raise ValueError(f"{name} names {len(layout)} layers of kinds "
                                 f"{sorted(set(layout))} for num_hidden_layers="
                                 f"{self.num_hidden_layers}: one 0 or 1 a layer")
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.moe_num_primary_experts)
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"num_key_value_heads={self.num_key_value_heads} has to divide "
                             f"num_attention_heads={self.num_attention_heads}")
        if self.head_dim % 2:
            raise ValueError("a rotated head is an even width (the rotation's halves)")
        self.routing  # a share that is none raises here, not in the step

    @classmethod
    def from_published(cls, published: Dict, **run) -> "PreroutedMoeConfig":
        """From a config.json-shaped dict (plus `experts_held` /
        `expert_offset`). What the family cannot express is an error that
        names the key, not a silent departure."""
        refuse = {
            "moe_primary_router_apply_softmax": (True,), "norm_topk_prob": (True,),
            "rope_scaling": (None,), "tie_word_embeddings": (False, None),
        }
        for key, allowed in refuse.items():
            if published.get(key, allowed[0]) not in allowed:
                raise ValueError(f"{key}={published[key]!r}: models/prerouted_moe.py supports "
                                 f"{allowed[0]!r} only")
        missing = [k for k in _PUBLISHED if k not in published]
        if missing:
            raise ValueError(f"config lacks {missing}")
        share = {k: published[k] for k in ("experts_held", "expert_offset") if k in published}
        return cls(**{k: published[k] for k in _PUBLISHED}, **share, **run)

    @property
    def effective_compute_dtype(self):
        return self.compute_dtype if self.compute_dtype is not None else self.dtype

    @property
    def routing(self) -> DroplessSpec:
        return DroplessSpec(
            num_experts=self.moe_num_primary_experts,
            top_k=self.moe_num_active_primary_experts, experts_held=self.experts_held,
            expert_offset=self.expert_offset, scores="softmax_topk",
            router_input="attention_norm", activation="relu")

    @property
    def rope(self) -> Rope:
        return Rope(rope_theta=self.rope_theta)

    def mask(self, sliding: int):
        """The mask kind of a layer, as the attention calls take it."""
        return SlidingWindow(self.sliding_window_size) if sliding else self.causal

    def layer_kinds(self):
        """((sliding, rotary, layers), ...): the distinct attention layers,
        sliding first, in the order saved_layers and plans list them."""
        pairs = list(zip(self.sliding_window_layout, self.rope_layout))
        kinds = sorted(set(pairs), reverse=True)
        return tuple((sliding, rotary, pairs.count((sliding, rotary))) for sliding, rotary in kinds)


def init_prerouted_moe(cfg: PreroutedMoeConfig, key: jax.Array) -> Dict:
    d, hd, dt = cfg.hidden_size, cfg.head_dim, cfg.dtype
    heads, kv = cfg.num_attention_heads, cfg.num_key_value_heads
    keys = jax.random.split(key, 2 + cfg.num_hidden_layers)
    dense = lambda k, shape: (jax.random.normal(k, shape) / shape[0] ** 0.5).astype(dt)
    blocks = []
    for i in range(cfg.num_hidden_layers):
        bk = jax.random.split(keys[2 + i], 6)
        blocks.append({
            "ln1": jnp.ones((d,), dt),
            "wq": dense(bk[0], (d, heads * hd)), "wk": dense(bk[1], (d, kv * hd)),
            "wv": dense(bk[2], (d, kv * hd)), "wo": dense(bk[3], (heads * hd, d)),
            "ln2": jnp.ones((d,), dt),
            "router": dense(bk[4], (d, cfg.moe_num_primary_experts)).astype(jnp.float32),
            "experts": _gated_init(bk[5], d, cfg.moe_ffn_hidden_size, dt, stack=cfg.experts_held),
        })
    return {
        "embed": (jax.random.normal(keys[0], (cfg.vocab_size, d)) * 0.02).astype(dt),
        "blocks": blocks,
        "out_norm": jnp.ones((d,), dt),
        "head": dense(keys[1], (d, cfg.vocab_size)),
    }


def prerouted_block(cfg: PreroutedMoeConfig, sliding: int, rotary: int, x, blk, attend, pos):
    """One block of the layer kind (sliding, rotary) -> (x, the layer's
    routing counters). The first norm feeds the router and the attention."""
    cd = cfg.effective_compute_dtype
    mixer = MIXER_SWA if sliding else MIXER_ATTENTION
    with scope(mixer):
        x = x.astype(cd)
        n32 = _rms32(x, blk["ln1"], cfg.rms_norm_eps)
    with scope(FFN), scope(MOE):
        route = route_tokens(n32, blk, cfg.routing)
    with scope(mixer):
        mixed, _ = gqa_attention(cfg, n32.astype(cd), blk, attend, pos,
                                 cfg.rope if rotary else None)
        x = x + mixed
    return ffn_half(cfg, x, blk, route=route)


def saved_layers(cfg: PreroutedMoeConfig, batch: int, seq_len: int):
    """models/transformer.saved_layers for this family: one entry a kind of
    attention layer (cfg.layer_kinds), keys and values already repeated to
    the query heads."""
    return [kept for sliding, _, layers in cfg.layer_kinds()
            for kept in flash_layers(cfg, batch, seq_len, cfg.num_attention_heads, cfg.head_dim,
                                     cfg.head_dim, layers, causal=cfg.mask(sliding))]


def apply_prerouted_moe(
    cfg: PreroutedMoeConfig,
    params: Dict,
    tokens: jax.Array,  # int32 [B, T], ids of the vocabulary slice
    seq_axis_name: Optional[str] = None,
    pos_offset: Optional[jax.Array] = None,
):
    """Forward -> (logits [B, T, vocab], aux): parallel/moe.
    moe_dropless_local's counters stacked over the layers, as
    models/mla_moe.apply_mla_moe gives them, with the `relu` gate's two."""
    if seq_axis_name is not None and jax.lax.axis_size(seq_axis_name) > 1:
        raise NotImplementedError(
            "models/prerouted_moe.py: a sequence axis of "
            f"{jax.lax.axis_size(seq_axis_name)} members needs the ring's hops to know the "
            "sliding window, which parallel/ring_attention.py does not yet (ROADMAP M5): "
            "run --num-sp 1")
    b, t = tokens.shape
    pos = jnp.arange(t) + (0 if pos_offset is None else pos_offset)
    cd = cfg.effective_compute_dtype
    attends = {sliding: select_attention(dataclasses.replace(cfg, causal=cfg.mask(sliding)),
                                         seq_axis_name)
               for sliding in set(cfg.sliding_window_layout)}
    kept = saved_layers(cfg, b, t) if cfg.remat else None

    def block_of(sliding, rotary):
        def block(x, blk):
            return prerouted_block(cfg, sliding, rotary, x, blk, attends[sliding], pos)

        return remat_block(block, kept, params) if cfg.remat else block

    layout = list(zip(cfg.sliding_window_layout, cfg.rope_layout))
    blocks = {kind: block_of(*kind) for kind in set(layout)}
    with scope(EMBED):
        x = params["embed"][tokens].astype(cd)
    routed = []
    for kind, blk in zip(layout, params["blocks"]):
        x, stats = blocks[kind](x, blk)
        routed.append(stats)
    with scope(HEAD_LOSS):
        n = _rms32(x, params["out_norm"], cfg.rms_norm_eps).astype(cd)
        return n @ params["head"].astype(cd), stack_layers(routed)


def plans(cfg: PreroutedMoeConfig, seq_len: int, seq_shards: int):
    """One `flash_plan` a kind of attention layer (models/transformer.
    flash_plans' fields under that kind's mask, with `layer_kind` its place
    in saved_layers, `heads`, `kv_heads`, `layers`, `rotary`: `default` |
    `none`, and models/swa_moe.rope_fields), then `moe_plan`: which dropless
    layer every block runs (parallel/moe.DroplessSpec's choices; `pass_rows`
    at one row of seq_len tokens a chip)."""
    out = []
    for i, (sliding, rotary, layers) in enumerate(cfg.layer_kinds()):
        for name, kernels, fields in flash_plans(cfg, seq_len, seq_shards, cfg.head_dim,
                                                 cfg.head_dim, causal=cfg.mask(sliding)):
            out.append((name, kernels, {
                **fields, "layer_kind": i, "heads": cfg.num_attention_heads,
                "kv_heads": cfg.num_key_value_heads, "layers": layers,
                "rotary": "default" if rotary else "none",
                **rope_fields(cfg, cfg.num_attention_heads, cfg.rope if rotary else None,
                              seq_len // seq_shards)}))
    spec = cfg.routing
    out.append(("moe_plan", None, {
        "scores": spec.scores, "router_input": spec.router_input, "activation": spec.activation,
        "experts": spec.num_experts, "top_k": spec.top_k, "experts_held": spec.experts_held,
        "pass_rows": pass_rows(seq_len // seq_shards, spec), "shared_expert": False}))
    return out


CONFIG = PreroutedMoeConfig


def family(cfg: PreroutedMoeConfig) -> LMFamily:
    return LMFamily(init_prerouted_moe, apply_prerouted_moe, routing_counters, saved_layers,
                    plans, (("moe_route", "moe_"),))
