"""Hybrid delta-rule / latent-attention, sparse-expert decoder LM (the
Kimi-Linear block).

The fourth LM family beside models/transformer.py, models/mla_moe.py and
models/ssm_hybrid.py, for public models whose config.json says `model_type:
kimi_linear`. Same shape of module: pure init/apply, the call
`apply_kda_hybrid(cfg, params, tokens, seq_axis_name, pos_offset)`, the
attention picked by models/transformer.select_attention. Its blocks have one
of TWO mixers, by the published 1-indexed lists `linear_attn_config.
kda_layers` and `full_attn_layers`, and every block ends in the FFN half of
models/mla_moe.py (`ffn_half`: the dense MLP in the first
`first_k_dense_replace` layers, routed plus shared experts after them).

Per token row (every norm RMS with gain, statistics in float32; `cd` is the
compute dtype):

- block: h = x + Mixer(norm(x)); y = h + FFN(norm(h)); a final norm; an
  untied head [D, V]. No position embedding.
- KDA mixer (Kimi Delta Attention; H heads of `head_dim` for keys and
  values alike): q~, k~, v = silu(conv(n W_q | W_k | W_v)), the conv causal
  and depthwise over `short_conv_kernel_size` taps, one filter a channel,
  no bias; a head at a time q = q~ / |q~| * head_dim^-1/2, k = k~ / |k~|
  (ops/kda.l2_normalize, eps 1e-6 under the root); the log-decay A KEY
  CHANNEL g = -exp(A_log[h]) * softplus((n W_fa) W_fb + dt_bias); beta =
  sigmoid(n W_beta), one a head; per head the state S [keys, values], S_0
  = 0: S' = diag(exp(g_t)) S_(t-1), S_t = S' + beta_t k_t (v_t - S'^T
  k_t)^T, o_t = S_t^T q_t (ops/kda.kda_chunked at `kda_chunk_size`); y =
  norm_over_head_dim(o; gain [head_dim]) * sigmoid((n W_ga) W_gb); W_o.
  g, its sums, the decays and the carried state are float32.
- MLA mixer: models/mla_moe.mla_attention, told by `mla_use_nope` to leave
  both rotations out (the 64 shared channels are plain key channels).
- FFN: models/mla_moe.ffn_half with this family's DroplessSpec (sigmoid
  scores over `num_experts`, `num_experts_per_token` chosen by score plus
  the correction bias, weights renormalised and scaled, one shared expert).

The chip's share is the configuration's: `experts_held` / `expert_offset`
and the `vocab_size` slice, as in models/mla_moe.py. The delta rule's state
is not handed from one sequence shard to the next: a sequence axis of more
than one member is refused. The width of the two low-rank pairs (W_fa, W_ga)
is `head_dim`: the published config has no key for it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..obs.scopes import EMBED, HEAD_LOSS, MIXER_KDA, scope
from ..ops.causal_conv import causal_conv_silu, conv_path
from ..ops.kda import KDA_OPERANDS, kda_chunked, kda_saves
from ..parallel.moe import DroplessSpec, routing_counters, stack_layers
from .lm import LMFamily
from .mla_moe import _gated_init, _rms32, ffn_half, mla_mixer_half
from .ssm_hybrid import _causal_conv
from .transformer import flash_layers, flash_plans, remat_block, select_attention

# config.json keys this family reads (`linear_attn_config` is a group);
# every other key is carried by the benchmark's file and ignored here
_PUBLISHED = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "intermediate_size", "moe_intermediate_size", "num_experts",
    "num_experts_per_token", "num_shared_experts", "first_k_dense_replace",
    "routed_scaling_factor", "moe_renormalize", "mla_use_nope", "rope_theta",
    "rms_norm_eps",
)
# what from_published turns down, for models/lm.require_dense's message
REFUSES = (
    "a router activation other than sigmoid, expert groups, query compression, rope "
    "scaling, a tied head, next-token-prediction layers, a sequence axis of more than "
    "one member")


@dataclasses.dataclass(frozen=True)
class KdaHybridConfig:
    # the published keys, under their published names
    vocab_size: int = 256
    hidden_size: int = 64
    num_hidden_layers: int = 4
    num_attention_heads: int = 4
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    intermediate_size: int = 128
    moe_intermediate_size: int = 32
    num_experts: int = 16
    num_experts_per_token: int = 3
    num_shared_experts: int = 1
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.446
    moe_renormalize: bool = True
    mla_use_nope: bool = True
    rope_theta: float = 1e4
    rms_norm_eps: float = 1e-5
    # `linear_attn_config`, flattened; the lists stay 1-indexed
    kda_layers: Tuple[int, ...] = (1, 2, 4)
    full_attn_layers: Tuple[int, ...] = (3,)
    kda_heads: int = 4
    kda_head_dim: int = 16
    short_conv_kernel_size: int = 4
    # the chunk of ops/kda.kda_chunked: no published key, 64 unless given
    kda_chunk_size: int = 64
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
        for name in ("kda_layers", "full_attn_layers"):
            object.__setattr__(self, name, tuple(int(i) for i in getattr(self, name)))
        if sorted(self.kda_layers + self.full_attn_layers) != list(
                range(1, self.num_hidden_layers + 1)):
            raise ValueError(
                f"linear_attn_config: kda_layers {list(self.kda_layers)} and full_attn_layers "
                f"{list(self.full_attn_layers)} do not name each of layers 1.."
                f"{self.num_hidden_layers} once")
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.num_experts)
        self.routing  # a share that is none raises here, not in the step

    @classmethod
    def from_published(cls, published: Dict, **run) -> "KdaHybridConfig":
        """From a config.json-shaped dict (plus `experts_held` /
        `expert_offset` / `kda_chunk_size`). What the family cannot express
        is an error that names the key, not a silent departure."""
        refuse = {
            "moe_router_activation_func": ("sigmoid",), "num_expert_group": (1, None),
            "topk_group": (1, None), "q_lora_rank": (None,), "rope_scaling": (None,),
            "tie_word_embeddings": (False, None), "num_nextn_predict_layers": (0, None),
            "hidden_act": ("silu", None), "moe_layer_freq": (1, None),
        }
        for key, allowed in refuse.items():
            if published.get(key, allowed[0]) not in allowed:
                raise ValueError(
                    f"{key}={published[key]!r}: models/kda_hybrid.py supports {allowed[0]!r} only")
        missing = [k for k in _PUBLISHED + ("linear_attn_config",) if k not in published]
        if missing:
            raise ValueError(f"config lacks {missing}")
        lin = published["linear_attn_config"]
        share = {k: published[k] for k in ("experts_held", "expert_offset", "kda_chunk_size")
                 if k in published}
        return cls(**{k: published[k] for k in _PUBLISHED},
                   kda_layers=lin["kda_layers"], full_attn_layers=lin["full_attn_layers"],
                   kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
                   short_conv_kernel_size=lin["short_conv_kernel_size"], **share, **run)

    @property
    def effective_compute_dtype(self):
        return self.compute_dtype if self.compute_dtype is not None else self.dtype

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def kda_inner(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def routing(self) -> DroplessSpec:
        return DroplessSpec(
            num_experts=self.num_experts, top_k=self.num_experts_per_token,
            experts_held=self.experts_held, expert_offset=self.expert_offset,
            routed_scale=self.routed_scaling_factor, norm_topk_prob=self.moe_renormalize)

    @property
    def moe_layers(self) -> int:
        return max(self.num_hidden_layers - self.first_k_dense_replace, 0)


def init_kda_hybrid(cfg: KdaHybridConfig, key: jax.Array) -> Dict:
    d, h, dt = cfg.hidden_size, cfg.num_attention_heads, cfg.dtype
    inner, rank, taps = cfg.kda_inner, cfg.kda_head_dim, cfg.short_conv_kernel_size
    keys = jax.random.split(key, 2 + cfg.num_hidden_layers)
    dense = lambda k, shape: (jax.random.normal(k, shape) / shape[0] ** 0.5).astype(dt)
    blocks = []
    for i in range(cfg.num_hidden_layers):
        bk = jax.random.split(keys[2 + i], 20)
        blk = {"ln1": jnp.ones((d,), dt)}
        if i + 1 in cfg.kda_layers:
            # decay parameters as the source initialises them: A in [1, 16],
            # dt log-uniform in [1e-3, 1e-1] through the inverse softplus
            step = jnp.exp(jax.random.uniform(
                bk[8], (inner,), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
            blk.update(
                wq=dense(bk[0], (d, inner)), wk=dense(bk[1], (d, inner)),
                wv=dense(bk[2], (d, inner)),
                conv_q=dense(bk[9], (taps, inner)), conv_k=dense(bk[10], (taps, inner)),
                conv_v=dense(bk[11], (taps, inner)),
                f_a=dense(bk[12], (d, rank)), f_b=dense(bk[13], (rank, inner)),
                a_log=jnp.log(jax.random.uniform(bk[14], (cfg.kda_heads,), minval=1.0, maxval=16.0)),
                dt_bias=(step + jnp.log(-jnp.expm1(-step))).astype(jnp.float32),
                w_beta=dense(bk[15], (d, cfg.kda_heads)),
                g_a=dense(bk[16], (d, rank)), g_b=dense(bk[17], (rank, inner)),
                o_norm={"scale": jnp.ones((cfg.kda_head_dim,), dt)},
                wo=dense(bk[3], (inner, d)))
        else:
            blk.update(
                wq=dense(bk[0], (d, h * cfg.qk_head_dim)),
                wkv_a=dense(bk[1], (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim)),
                kv_norm={"scale": jnp.ones((cfg.kv_lora_rank,), dt)},
                wkv_b=dense(bk[2], (cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim))),
                wo=dense(bk[3], (h * cfg.v_head_dim, d)))
        blk["ln2"] = jnp.ones((d,), dt)
        if i < cfg.first_k_dense_replace:
            blk["mlp"] = _gated_init(bk[4], d, cfg.intermediate_size, dt)
        else:
            blk["router"] = dense(bk[5], (d, cfg.num_experts)).astype(jnp.float32)
            blk["router_bias"] = jnp.zeros((cfg.num_experts,), jnp.float32)
            blk["shared"] = _gated_init(
                bk[6], d, cfg.num_shared_experts * cfg.moe_intermediate_size, dt)
            blk["experts"] = _gated_init(
                bk[7], d, cfg.moe_intermediate_size, dt, stack=cfg.experts_held)
        blocks.append(blk)
    return {
        "embed": (jax.random.normal(keys[0], (cfg.vocab_size, d)) * 0.02).astype(dt),
        "blocks": blocks,
        "out_norm": jnp.ones((d,), dt),
        "head": dense(keys[1], (d, cfg.vocab_size)),
    }


def _into32(a, w):
    return jnp.einsum("btd,de->bte", a, w.astype(a.dtype), preferred_element_type=jnp.float32)


def _short_branch(n, w, taps, heads: int, scale: Optional[float]):
    """silu(conv(n W)) a head, L2-normalised times `scale` where one is
    given: [B, T, H, d] in n's dtype; the conv, its silu and the norm in
    float32 (ops/causal_conv.py)."""
    x = causal_conv_silu(_into32(n, w), taps, None, n.dtype, _causal_conv,
                         heads=None if scale is None else heads, head_scale=scale)
    return x.reshape(x.shape[:2] + (heads, -1))


def _log_decay(n, blk, heads: int):
    """g = -exp(A_log[h]) * softplus((n W_fa) W_fb + dt_bias): float32
    [B, T, H, d], never positive."""
    f32 = jnp.float32
    x = jax.nn.softplus(_into32(n @ blk["f_a"].astype(n.dtype), blk["f_b"])
                        + blk["dt_bias"].astype(f32))
    return -jnp.exp(blk["a_log"].astype(f32))[:, None] * x.reshape(x.shape[:2] + (heads, -1))


def _gated_norm(n, o, blk, eps: float):
    """norm_over_head_dim(o) * sigmoid((n W_ga) W_gb) -> [B, T, H * d] in
    n's dtype."""
    gate = jax.nn.sigmoid(_into32(n @ blk["g_a"].astype(n.dtype), blk["g_b"]))
    y = _rms32(o, blk["o_norm"]["scale"], eps) * gate.reshape(o.shape)
    return y.reshape(gate.shape).astype(n.dtype)


def kda_mixer(cfg: KdaHybridConfig, n, blk):
    """n [B, T, D] in the compute dtype -> (the branch [B, T, D], the
    recurrence's cut-off count). Each elementwise stretch (a conv branch,
    the decay, the gated norm) keeps its inputs only for the backward pass
    (jax.checkpoint): their float32 passes over [B, T, H * d] would else
    all be alive beside the recurrence's own."""
    cd = n.dtype
    h, dk = cfg.kda_heads, cfg.kda_head_dim
    short = jax.checkpoint(_short_branch, static_argnums=(3, 4))
    q = short(n, blk["wq"], blk["conv_q"], h, dk ** -0.5)
    k = short(n, blk["wk"], blk["conv_k"], h, 1.0)
    v = short(n, blk["wv"], blk["conv_v"], h, None)
    # where the blocks' policy keeps them (KDA_OPERANDS), the backward does
    # not run the three branches again to run the op again; each branch's
    # own checkpoint still does, for the conv's and the norm's gradients
    q, k, v = map(checkpoint_name, (q, k, v), KDA_OPERANDS)
    decay = {name: blk[name] for name in ("f_a", "f_b", "a_log", "dt_bias")}
    g = jax.checkpoint(_log_decay, static_argnums=(2,))(n, decay, h)
    beta = jax.nn.sigmoid(_into32(n, blk["w_beta"]))
    o, cut_off = kda_chunked(q, k, v, g, beta, cfg.kda_chunk_size)
    gate = {name: blk[name] for name in ("g_a", "g_b", "o_norm")}
    y = jax.checkpoint(_gated_norm, static_argnums=(3,))(n, o, gate, cfg.rms_norm_eps)
    return y @ blk["wo"].astype(cd), cut_off


def mixer_half(cfg: KdaHybridConfig, x, blk, attend, pos):
    """x + Mixer(norm(x)) for a block of either mixer (by the leaves it
    holds) -> (x in the compute dtype, the recurrence's cut-off count: zero
    for an MLA block)."""
    cd = cfg.effective_compute_dtype
    if "a_log" not in blk:
        return mla_mixer_half(cfg, x.astype(cd), blk, attend, pos), jnp.int32(0)
    with scope(MIXER_KDA):
        x = x.astype(cd)
        mixed, cut_off = kda_mixer(cfg, _rms32(x, blk["ln1"], cfg.rms_norm_eps).astype(cd), blk)
        return x + mixed, cut_off


def apply_kda_hybrid(
    cfg: KdaHybridConfig,
    params: Dict,
    tokens: jax.Array,  # int32 [B, T], ids of the vocabulary slice
    seq_axis_name: Optional[str] = None,
    pos_offset: Optional[jax.Array] = None,
):
    """Forward -> (logits [B, T, vocab], aux): aux["counts"] int32 [expert
    layers, held] and aux["unserved"] int32 [expert layers] as
    models/mla_moe.apply_mla_moe gives them, aux["kda_cut_off"] int32 [KDA
    layers]: per layer, the (row, chunk, head) whose slowest channel's decay
    over the whole chunk is under 2^-24 (ops/kda.CUT_OFF_LOG)."""
    if seq_axis_name is not None and jax.lax.axis_size(seq_axis_name) > 1:
        raise NotImplementedError(
            "models/kda_hybrid.py: a sequence axis of "
            f"{jax.lax.axis_size(seq_axis_name)} members needs the delta rule's "
            "carried state handed from one sequence shard to the next, "
            "which parallel/dp_sp.py does not do yet (ROADMAP M6): run --num-sp 1")
    pos = jnp.arange(tokens.shape[1]) + (0 if pos_offset is None else pos_offset)
    attend = select_attention(cfg, seq_axis_name)
    cd = cfg.effective_compute_dtype

    def mixer(x, blk):
        return mixer_half(cfg, x, blk, attend, pos)

    def ffn(x, blk):
        return ffn_half(cfg, x, blk)

    if cfg.remat:
        # the two halves apart: the backward holds what one half
        # recomputes, never the delta rule's temporaries beside the expert
        # buffer's
        kinds = saved_layers(cfg, *tokens.shape)
        mixer, ffn = remat_block(mixer, kinds, params), remat_block(ffn, kinds, params)
    with scope(EMBED):
        x = params["embed"][tokens].astype(cd)
    routed, cut_off = [], []
    for blk in params["blocks"]:
        x, cut = mixer(x, blk)
        x, stats = ffn(x, blk)
        if "mlp" not in blk:
            routed.append(stats)
        if "a_log" in blk:
            cut_off.append(cut)
    with scope(HEAD_LOSS):
        n = _rms32(x, params["out_norm"], cfg.rms_norm_eps).astype(cd)
    aux = {}
    if routed:
        aux.update(stack_layers(routed))
    if cut_off:
        aux["kda_cut_off"] = jnp.stack(cut_off)
    with scope(HEAD_LOSS):
        return n @ params["head"].astype(cd), aux


def saved_layers(cfg: KdaHybridConfig, batch: int, seq_len: int):
    """models/transformer.saved_layers for this family: the latent-attention
    layers' flash kernels, then the delta-rule layers' inverses and q, k, v."""
    attention = flash_layers(cfg, batch, seq_len, cfg.num_attention_heads, cfg.qk_head_dim,
                             cfg.v_head_dim, len(cfg.full_attn_layers))
    return attention + [kda_saves(batch, seq_len, cfg.kda_heads, cfg.kda_head_dim,
                                  cfg.effective_compute_dtype, cfg.kda_chunk_size,
                                  len(cfg.kda_layers))]


def kda_plan(cfg: KdaHybridConfig, seq_len: int) -> Dict:
    """What every call of the recurrence will look like, from the shapes
    alone (cli/train_lm.py logs it and records it as the `kda_plan`
    instant). `sub_block` is the smallest block the pair scores and the
    triangular inverse are built up from."""
    from ..ops.kda import padded_len, scan_path

    chunk, d = cfg.kda_chunk_size, cfg.kda_head_dim
    padded = padded_len(seq_len, chunk, d, d)
    return {"chunk": chunk, "sub_block": 1, "n_chunks": padded // chunk,
            "padded_len": padded, "heads": cfg.kda_heads,
            "d_head": cfg.kda_head_dim, "kda_layers": len(cfg.kda_layers),
            "attention_layers": len(cfg.full_attn_layers), "scan_path": scan_path(chunk, d, d),
            "conv_path": conv_path(cfg.kda_inner, cfg.short_conv_kernel_size)}


def kda_counters(aux) -> Dict:
    """What the step returns beside the loss, from the aux summed over the
    mesh: the routing counters of the expert layers (parallel/moe.
    routing_counters) and `kda_chunks_cut_off` over all KDA layers, and per
    layer."""
    out = {}
    if "counts" in aux:
        out.update(routing_counters(aux))
    if "kda_cut_off" in aux:
        out.update(kda_chunks_cut_off=jnp.sum(aux["kda_cut_off"]),
                   kda_chunks_cut_off_per_layer=aux["kda_cut_off"])
    return out


def plans(cfg: KdaHybridConfig, seq_len: int, seq_shards: int):
    out = flash_plans(cfg, seq_len, seq_shards, cfg.qk_head_dim, cfg.v_head_dim)
    if cfg.kda_layers:
        out.append(("kda_plan", "ps_kda_", kda_plan(cfg, seq_len)))
    return out


CONFIG = KdaHybridConfig


def family(cfg: KdaHybridConfig) -> LMFamily:
    return LMFamily(init_kda_hybrid, apply_kda_hybrid,
                    kda_counters if cfg.moe_layers or cfg.kda_layers else None,
                    saved_layers, plans, (("kda_state", "kda_"), ("moe_route", "moe_")))
