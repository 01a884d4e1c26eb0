"""Sliding-window / global grouped-query attention over sparse experts (the
Laguna block).

The sixth LM family beside models/transformer.py, mla_moe.py, ssm_hybrid.py,
kda_hybrid.py and eva_dense.py, for public models whose config.json says
`model_type: laguna`. Same shape of module: pure init/apply, the call
`apply_swa_moe(cfg, params, tokens, seq_axis_name, pos_offset)`. The config
is NOT uniform over depth: layer l has a kind `layer_types[l]`
(`full_attention` | `sliding_attention`), its own number of query heads
`num_attention_heads_per_layer[l]`, a rotary by its kind
(`rope_parameters[kind]`) and an FFN by `mlp_layer_types[l]` (`dense` |
`sparse`), and each decides that layer's parameter shapes.

Per token row x (every norm RMS with gain, statistics in float32; `cd` is
the compute dtype; H_l the layer's query heads over `num_key_value_heads`
key/value heads of `head_dim`):

- block: h = x + Attn(norm(x)); y = h + FFN(norm(h)); a final norm; an
  untied head [D, V]. No position embedding.
- Attn: q = n W_q [H_l heads], k = n W_k, v = n W_v [kv heads], no bias.
  Rotary on q and k in the halves layout (rotate_half), the rotated dims
  FIRST: `partial_rotary_factor` x head_dim of them, the rest pass through.
  `rope_type: default`: angle pos * theta^(-2j/r). `rope_type: yarn`: the
  frequencies f_j = theta^(-2j/r) blended with f_j / factor by the linear
  ramp between the correction dims of `beta_fast` and `beta_slow` turns over
  `original_max_position_embeddings` (a dim that turns more often than
  beta_fast keeps f_j, one that turns less than beta_slow takes f_j /
  factor), cos and sin times `attention_factor`. The blend is static.
  Query head h reads key/value head h // (H_l / kv): keys and values are
  repeated to the query heads before the attention call (their gradients
  sum back through the repeat). softmax(q k^T / sqrt(head_dim)) v over the
  keys j <= i (full_attention) or i - `sliding_window` < j <= i
  (sliding_attention: ops/flash_attention.SlidingWindow). Then the gate a
  head and token: o_h <- sigmoid(n W_g)_h o_h, W_g [D, H_l] (`gating:
  per-head`), and W_o.
- FFN of a `dense` layer: (silu(n W_gate) * n W_up) W_down,
  `intermediate_size` wide. Of a `sparse` one: models/mla_moe.ffn_half, the
  half-block the expert families share: sigmoid scores over all
  `num_experts` (float32), the `num_experts_per_tok` largest, weights
  renormalised (`norm_topk_prob`) and scaled `moe_routed_scaling_factor`,
  the experts HELD HERE through parallel/moe.moe_dropless_local, plus one
  shared expert `shared_expert_intermediate_size` wide, unweighted. The
  router's correction bias is a zero buffer no gradient reaches (the
  config names none).

The chip's share is the configuration's, as in models/mla_moe.py:
`experts_held` / `expert_offset` and the `vocab_size` slice. A sequence
axis of more than one member is refused: the ring's hops do not know the
window (ROADMAP M5).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.scopes import (ATTN_GATE, EMBED, HEAD_LOSS, KV_REPEAT, MIXER_ATTENTION, MIXER_SWA,
                          ROPE, scope)
from ..ops.flash_attention import SlidingWindow
from ..ops.rope import plan_rope, rope_path, rotate_leading
from ..parallel.moe import DroplessSpec, routing_counters, stack_layers
from .lm import LMFamily
from .mla_moe import _gated_init, _rms32, ffn_half
from .transformer import flash_layers, flash_plans, remat_block, select_attention

FULL, SLIDING = "full_attention", "sliding_attention"

# config.json keys this family reads (`rope_parameters` is a group a layer
# kind); every other key is carried by the benchmark's file and ignored here
_PUBLISHED = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
    "num_experts", "num_experts_per_tok", "moe_intermediate_size",
    "shared_expert_intermediate_size", "norm_topk_prob", "moe_routed_scaling_factor",
    "sliding_window", "layer_types", "num_attention_heads_per_layer", "mlp_layer_types",
    "rope_parameters",
)
# what from_published turns down, for models/lm.require_dense's message
REFUSES = (
    "a rope_type other than default and yarn, a gating other than per-head, a nonzero "
    "moe_router_logit_softcapping, moe_apply_router_weight_on_input, a tied head, a "
    "sequence axis of more than one member")


@dataclasses.dataclass(frozen=True)
class Rope:
    """One layer kind's rotary, from its `rope_parameters` group."""

    rope_theta: float = 10000.0
    rope_type: str = "default"
    partial_rotary_factor: float = 1.0
    # `yarn` only
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def __post_init__(self):
        if self.rope_type not in ("default", "yarn"):
            raise ValueError(f"rope_type={self.rope_type!r} in a rope_parameters group: "
                             "models/swa_moe.py supports 'default' and 'yarn' only")

    def frequencies(self, head_dim: int) -> Tuple[np.ndarray, float]:
        """(float32 [r / 2] angles a position, what cos and sin are
        multiplied by) for the r = partial_rotary_factor x head_dim rotated
        dims. Static: no sequence length enters."""
        r = int(head_dim * self.partial_rotary_factor)
        f = float(self.rope_theta) ** (-np.arange(0, r, 2, dtype=np.float64) / r)
        if self.rope_type == "default":
            return f.astype(np.float32), 1.0

        def correction_dim(turns: float) -> float:
            return r * math.log(self.original_max_position_embeddings / (turns * 2 * math.pi)) \
                / (2 * math.log(self.rope_theta))

        low = max(math.floor(correction_dim(self.beta_fast)), 0)
        high = min(math.ceil(correction_dim(self.beta_slow)), r - 1)
        ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
        blended = f * (1 - ramp) + f / self.factor * ramp
        return blended.astype(np.float32), float(self.attention_factor)


@dataclasses.dataclass(frozen=True)
class SwaMoeConfig:
    # the published keys, under their published names
    vocab_size: int = 256
    hidden_size: int = 64
    intermediate_size: int = 128
    num_hidden_layers: int = 3
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    rms_norm_eps: float = 1e-6
    num_experts: int = 16
    num_experts_per_tok: int = 3
    moe_intermediate_size: int = 32
    shared_expert_intermediate_size: int = 32
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    sliding_window: int = 8
    layer_types: Tuple[str, ...] = (FULL, SLIDING, SLIDING)
    num_attention_heads_per_layer: Tuple[int, ...] = (4, 6, 6)
    mlp_layer_types: Tuple[str, ...] = ("dense", "sparse", "sparse")
    # ((layer kind, Rope), ...): the published group a kind
    rope_parameters: Any = ((FULL, Rope()), (SLIDING, Rope()))
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
        for name in ("layer_types", "num_attention_heads_per_layer", "mlp_layer_types"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        ropes = self.rope_parameters
        ropes = tuple((kind, group if isinstance(group, Rope) else Rope(**group))
                      for kind, group in (ropes.items() if isinstance(ropes, dict) else ropes))
        object.__setattr__(self, "rope_parameters", ropes)
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.num_experts)
        n = self.num_hidden_layers
        if not (len(self.layer_types) == len(self.num_attention_heads_per_layer)
                == len(self.mlp_layer_types) == n):
            raise ValueError(
                f"layer_types, num_attention_heads_per_layer and mlp_layer_types name "
                f"{len(self.layer_types)}, {len(self.num_attention_heads_per_layer)} and "
                f"{len(self.mlp_layer_types)} layers for num_hidden_layers={n}")
        unknown = (sorted(set(self.layer_types) - {FULL, SLIDING})
                   + sorted(set(self.mlp_layer_types) - {"dense", "sparse"}))
        if unknown or set(self.layer_types) - set(dict(ropes)):
            raise ValueError(
                f"layer kinds {sorted(set(self.layer_types))} / {sorted(set(self.mlp_layer_types))}"
                f": one of {FULL} | {SLIDING} with its rope_parameters group, dense | sparse")
        for heads in self.num_attention_heads_per_layer:
            if heads % self.num_key_value_heads:
                raise ValueError(
                    f"num_key_value_heads={self.num_key_value_heads} has to divide every "
                    f"layer's query heads, not {heads}")
        if self.head_dim % 2 or any(int(self.head_dim * r.partial_rotary_factor) % 2
                                    for _, r in ropes):
            raise ValueError("the rotated part of a head is an even width (the rotation's halves)")
        self.routing  # a share that is none raises here, not in the step

    @classmethod
    def from_published(cls, published: Dict, **run) -> "SwaMoeConfig":
        """From a config.json-shaped dict (plus `experts_held` /
        `expert_offset`). What the family cannot express is an error that
        names the key, not a silent departure."""
        refuse = {
            "gating": ("per-head",), "moe_router_logit_softcapping": (0, None),
            "moe_apply_router_weight_on_input": (False, None),
            "tie_word_embeddings": (False, None), "attention_bias": (False, None),
            "decoder_sparse_step": (1, None), "hidden_act": ("silu", None),
        }
        for key, allowed in refuse.items():
            if published.get(key, allowed[0]) not in allowed:
                raise ValueError(
                    f"{key}={published[key]!r}: models/swa_moe.py supports {allowed[0]!r} only")
        if set(published.get("gating_types", ())) - {"per_head"}:
            raise ValueError(
                f"gating_types={sorted(set(published['gating_types']))}: models/swa_moe.py "
                "supports 'per_head' only")
        missing = [k for k in _PUBLISHED if k not in published]
        if missing:
            raise ValueError(f"config lacks {missing}")
        known = {f.name for f in dataclasses.fields(Rope)}
        ropes = {kind: {k: v for k, v in group.items() if k in known}
                 for kind, group in published["rope_parameters"].items()
                 if isinstance(group, dict)}
        share = {k: published[k] for k in ("experts_held", "expert_offset") if k in published}
        fields = {k: published[k] for k in _PUBLISHED}
        return cls(**{**fields, "rope_parameters": ropes}, **share, **run)

    @property
    def effective_compute_dtype(self):
        return self.compute_dtype if self.compute_dtype is not None else self.dtype

    @property
    def routing(self) -> DroplessSpec:
        return DroplessSpec(
            num_experts=self.num_experts, top_k=self.num_experts_per_tok,
            experts_held=self.experts_held, expert_offset=self.expert_offset,
            routed_scale=self.moe_routed_scaling_factor, norm_topk_prob=self.norm_topk_prob)

    @property
    def moe_layers(self) -> int:
        return self.mlp_layer_types.count("sparse")

    def rope(self, kind: str) -> Rope:
        return dict(self.rope_parameters)[kind]

    def mask(self, kind: str):
        """The mask kind of a layer kind, as the attention calls take it."""
        return SlidingWindow(self.sliding_window) if kind == SLIDING else self.causal

    def layer_kinds(self):
        """((kind, query heads, layers), ...): the distinct attention layers,
        sliding first, in the order saved_layers and plans list them."""
        pairs = list(zip(self.layer_types, self.num_attention_heads_per_layer))
        kinds = sorted(set(pairs), key=lambda p: (p[0] != SLIDING, p[1]))
        return tuple((kind, heads, pairs.count((kind, heads))) for kind, heads in kinds)


def init_swa_moe(cfg: SwaMoeConfig, key: jax.Array) -> Dict:
    d, hd, kv, dt = cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads, cfg.dtype
    keys = jax.random.split(key, 2 + cfg.num_hidden_layers)
    dense = lambda k, shape: (jax.random.normal(k, shape) / shape[0] ** 0.5).astype(dt)
    blocks = []
    for i, (heads, ffn) in enumerate(zip(cfg.num_attention_heads_per_layer, cfg.mlp_layer_types)):
        bk = jax.random.split(keys[2 + i], 9)
        blk = {
            "ln1": jnp.ones((d,), dt),
            "wq": dense(bk[0], (d, heads * hd)), "wk": dense(bk[1], (d, kv * hd)),
            "wv": dense(bk[2], (d, kv * hd)), "wg": dense(bk[3], (d, heads)),
            "wo": dense(bk[4], (heads * hd, d)),
            "ln2": jnp.ones((d,), dt),
        }
        if ffn == "dense":
            blk["mlp"] = _gated_init(bk[5], d, cfg.intermediate_size, dt)
        else:
            blk["router"] = dense(bk[6], (d, cfg.num_experts)).astype(jnp.float32)
            blk["router_bias"] = jnp.zeros((cfg.num_experts,), jnp.float32)
            blk["shared"] = _gated_init(bk[7], d, cfg.shared_expert_intermediate_size, dt)
            blk["experts"] = _gated_init(
                bk[8], d, cfg.moe_intermediate_size, dt, stack=cfg.experts_held)
        blocks.append(blk)
    return {
        "embed": (jax.random.normal(keys[0], (cfg.vocab_size, d)) * 0.02).astype(dt),
        "blocks": blocks,
        "out_norm": jnp.ones((d,), dt),
        "head": dense(keys[1], (d, cfg.vocab_size)),
    }


def _rope_leading(x, pos, rope: Rope):
    """Rotate the first r dims of x [B, T, H, d] in the halves layout (pairs
    (j, j + r/2)) by pos[t] * f_j, cos and sin scaled as `rope` says; the
    other d - r dims pass through. float32 inside."""
    freqs, scale = rope.frequencies(x.shape[-1])
    r = 2 * freqs.shape[0]
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freqs)[None]     # [T, r/2]
    cos, sin = (jnp.cos(ang) * scale)[None, :, None], (jnp.sin(ang) * scale)[None, :, None]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :r // 2], x32[..., r // 2:r]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x32[..., r:]], axis=-1).astype(x.dtype)


def rope_fields(cfg, heads: int, rope: Optional[Rope], t: int) -> Dict:
    """What a layer kind's `flash_plan` says of its rotation at rows of t
    tokens: `rope_path` (ops/rope.rope_path; "none" for a layer without
    positions), `rope_dims` (r) and, where the kernel runs, `ps_rope`'s tile
    for q and the block of channels for k. Shared with
    models/prerouted_moe.py."""
    if rope is None:
        return {"rope_path": "none"}
    hd, dt = cfg.head_dim, cfg.effective_compute_dtype
    r = 2 * len(rope.frequencies(hd)[0])
    out = {"rope_path": rope_path(hd, r), "rope_dims": r}
    if out["rope_path"] == "pallas":
        q, k = plan_rope(t, heads * hd, dt), plan_rope(t, cfg.num_key_value_heads * hd, dt)
        out.update(rope_block_t=q.block_t, rope_block_c=q.block_c, rope_block_c_kv=k.block_c)
    return out


def gqa_attention(cfg, n, blk, attend, pos, rope: Optional[Rope]):
    """n [B, T, D] in the compute dtype -> (the attention branch [B, T, D],
    the sum of its output gate over tokens and heads). `cfg` gives
    `num_key_value_heads` and `head_dim`, the block its query heads (W_q's
    width). `rope` None: nothing rotates and no pass is run (a layer without
    positions). A block without `wg` has no gate, and the sum is None.
    Shared with models/prerouted_moe.py."""
    cd = n.dtype
    b, t, _ = n.shape
    kv, hd = cfg.num_key_value_heads, cfg.head_dim
    heads = blk["wq"].shape[1] // hd
    q = (n @ blk["wq"].astype(cd)).reshape(b, t, heads, hd)
    k = (n @ blk["wk"].astype(cd)).reshape(b, t, kv, hd)
    v = (n @ blk["wv"].astype(cd)).reshape(b, t, kv, hd)
    if rope is not None:
        with scope(ROPE):
            q, k = rotate_leading((q, k), pos, *rope.frequencies(hd),
                                  twin=lambda x: _rope_leading(x, pos, rope))
    with scope(KV_REPEAT):
        k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))
    o = attend(q, k, v)                                                  # [B, T, H, hd]
    opened = None
    if "wg" in blk:
        with scope(ATTN_GATE):
            gate = jax.nn.sigmoid((n @ blk["wg"].astype(cd)).astype(jnp.float32))   # [B, T, H]
            o = o * gate[..., None].astype(cd)
            opened = jnp.sum(gate)
    return o.reshape(b, t, heads * hd) @ blk["wo"].astype(cd), opened


def swa_moe_block(cfg: SwaMoeConfig, kind: str, x, blk, attend, pos):
    """One block of the layer kind `kind` -> (x, the layer's routing
    counters, the gate's sum); the routing counters are zeros for a dense
    layer."""
    cd = cfg.effective_compute_dtype
    with scope(MIXER_SWA if kind == SLIDING else MIXER_ATTENTION):
        x = x.astype(cd)
        mixed, opened = gqa_attention(
            cfg, _rms32(x, blk["ln1"], cfg.rms_norm_eps).astype(cd), blk, attend, pos,
            cfg.rope(kind))
        x = x + mixed
    return (*ffn_half(cfg, x, blk), opened)


def saved_layers(cfg: SwaMoeConfig, batch: int, seq_len: int):
    """models/transformer.saved_layers for this family: one entry a kind of
    attention layer (cfg.layer_kinds: they differ in heads and mask), keys
    and values already repeated to the query heads."""
    return [kept for kind, heads, layers in cfg.layer_kinds()
            for kept in flash_layers(cfg, batch, seq_len, heads, cfg.head_dim, cfg.head_dim,
                                     layers, causal=cfg.mask(kind))]


def apply_swa_moe(
    cfg: SwaMoeConfig,
    params: Dict,
    tokens: jax.Array,  # int32 [B, T], ids of the vocabulary slice
    seq_axis_name: Optional[str] = None,
    pos_offset: Optional[jax.Array] = None,
):
    """Forward -> (logits [B, T, vocab], aux): aux["counts"] int32 [expert
    layers, held] and aux["unserved"] int32 [expert layers] as
    models/mla_moe.apply_mla_moe gives them; aux["attn_gate_sum"] float32
    [layers], the output gate summed over tokens and heads, and
    aux["attn_gate_count"], how many entries that is."""
    if seq_axis_name is not None and jax.lax.axis_size(seq_axis_name) > 1:
        raise NotImplementedError(
            "models/swa_moe.py: a sequence axis of "
            f"{jax.lax.axis_size(seq_axis_name)} members needs the ring's hops to know the "
            "sliding window, which parallel/ring_attention.py does not yet (ROADMAP M5): "
            "run --num-sp 1")
    b, t = tokens.shape
    pos = jnp.arange(t) + (0 if pos_offset is None else pos_offset)
    cd = cfg.effective_compute_dtype
    attends = {kind: select_attention(dataclasses.replace(cfg, causal=cfg.mask(kind)),
                                      seq_axis_name) for kind in set(cfg.layer_types)}

    kept = saved_layers(cfg, b, t) if cfg.remat else None

    def block_of(kind):
        def block(x, blk):
            return swa_moe_block(cfg, kind, x, blk, attends[kind], pos)

        return remat_block(block, kept, params) if cfg.remat else block

    blocks = {kind: block_of(kind) for kind in attends}
    with scope(EMBED):
        x = params["embed"][tokens].astype(cd)
    routed, opened = [], []
    for kind, blk in zip(cfg.layer_types, params["blocks"]):
        x, stats, g = blocks[kind](x, blk)
        opened.append(g)
        if "mlp" not in blk:
            routed.append(stats)
    with scope(HEAD_LOSS):
        n = _rms32(x, params["out_norm"], cfg.rms_norm_eps).astype(cd)
    entries = jnp.asarray([b * t * h for h in cfg.num_attention_heads_per_layer], jnp.float32)
    aux = {"attn_gate_sum": jnp.stack(opened), "attn_gate_count": entries}
    if routed:
        aux.update(stack_layers(routed))
    with scope(HEAD_LOSS):
        return n @ params["head"].astype(cd), aux


def swa_counters(aux) -> Dict:
    """What the step returns beside the loss, from the aux summed over the
    mesh: the expert layers' routing (parallel/moe.routing_counters) and
    `attn_gate_open`, the mean of the attention output's sigmoid gate over
    tokens, heads and layers (a half at a fresh gate; a gate stuck shut or
    open reads 0 or 1), and the same per layer."""
    out = {"attn_gate_open": jnp.sum(aux["attn_gate_sum"]) / jnp.sum(aux["attn_gate_count"]),
           "attn_gate_open_per_layer": aux["attn_gate_sum"] / aux["attn_gate_count"]}
    if "counts" in aux:
        out.update(routing_counters(aux))
    return out


def plans(cfg: SwaMoeConfig, seq_len: int, seq_shards: int):
    """One `flash_plan` a kind of attention layer: models/transformer.
    flash_plans' fields under that kind's mask, with `layer_kind` its place
    in saved_layers, `layer_type`, `heads`, `kv_heads`, `layers` and how the
    kind's q and k are rotated (`rope_fields`)."""
    out = []
    for i, (kind, heads, layers) in enumerate(cfg.layer_kinds()):
        for name, kernels, fields in flash_plans(cfg, seq_len, seq_shards, cfg.head_dim,
                                                 cfg.head_dim, causal=cfg.mask(kind)):
            out.append((name, kernels, {
                **fields, "layer_kind": i, "layer_type": kind, "heads": heads,
                "kv_heads": cfg.num_key_value_heads, "layers": layers,
                **rope_fields(cfg, heads, cfg.rope(kind), seq_len // seq_shards)}))
    return out


CONFIG = SwaMoeConfig


def family(cfg: SwaMoeConfig) -> LMFamily:
    return LMFamily(init_swa_moe, apply_swa_moe, swa_counters, saved_layers, plans,
                    (("moe_route", "moe_"), ("attn_state", "attn_")))
