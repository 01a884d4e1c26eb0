"""Hybrid state-space / attention decoder LM (the Granite-4.0-H block).

The third LM family beside models/transformer.py and models/mla_moe.py,
for public models whose config.json says `model_type: granitemoehybrid`
with no routed experts. Same shape of module: pure init/apply, the call
`apply_ssm_hybrid(cfg, params, tokens, seq_axis_name, pos_offset)`, the
attention picked by models/transformer.select_attention. Its blocks are of
TWO kinds, in the order the published `layer_types` gives.

Per token row (every norm RMS with gain, statistics in float32; `cd` is
the compute dtype):

- model: x = embed[tokens] * embedding_multiplier; per layer
  x = x + residual_multiplier * mixer(norm(x)), then
  x = x + residual_multiplier * mlp(norm(x));
  logits = norm(x) embed^T / logits_scaling (tied head). No positional
  term anywhere (`position_embedding_type: nope`).
- mlp(n) = (silu(n W_in[:, :F]) * (n W_in[:, F:])) W_out, F =
  `shared_intermediate_size`.
- attention mixer: q, k, v without bias, `num_attention_heads` query heads
  over `num_key_value_heads` key/value heads, causal softmax(q k^T *
  attention_multiplier) v, W_o. Keys and values are repeated to the query
  heads before the attention call (their gradients sum back through the
  repeat); the flash kernels take the scale.
- mamba mixer (Mamba-2): [z | xBC | dt] = n W_in; xBC = silu(causal
  depthwise conv over `mamba_d_conv` taps, each channel its own, + bias);
  [x | B | C] = xBC, B and C shared by the heads of a group; dt =
  softplus(dt + dt_bias); A = -exp(A_log); per head S_t = exp(dt_t A)
  S_(t-1) + dt_t x_t B_t^T, y_t = S_t C_t + D x_t (ops/ssd.ssd_chunked at
  `mamba_chunk_size`; D is the leaf `skip/scale`, the scale of the skip
  from x to y, one a head, 1 at init as in the source); y = norm_over_d_inner(y * silu(z)) * g (the gate
  inside the norm); W_out. dt, A, the decays and the carried state are
  float32; no clamp of dt (`time_step_limit` (0, inf)).

`vocab_size` is the slice of the vocabulary held here (embedding, head,
loss). The recurrent state is not handed from one sequence shard to the
next: a sequence axis of more than one member is refused.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..obs.scopes import EMBED, FFN, HEAD_LOSS, MIXER_ATTENTION, MIXER_SSD, MLP, scope
from ..ops.causal_conv import causal_conv_silu, conv_path
from ..ops.ssd import SCAN_PATH, ssd_chunked
from .lm import LMFamily
from .mla_moe import _rms32
from .transformer import flash_layers, flash_plans, remat_block, select_attention

# config.json keys this family reads; every other key is carried by the
# benchmark's file and ignored here
_PUBLISHED = (
    "vocab_size", "hidden_size", "num_hidden_layers", "layer_types",
    "num_attention_heads", "num_key_value_heads", "shared_intermediate_size",
    "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
    "mamba_d_conv", "mamba_chunk_size", "attention_multiplier",
    "embedding_multiplier", "residual_multiplier", "logits_scaling", "rms_norm_eps",
)
# what from_published turns down, for models/lm.require_dense's message
REFUSES = "routed experts, a positional term, a sequence axis of more than one member"


@dataclasses.dataclass(frozen=True)
class SsmHybridConfig:
    # the published keys, under their published names
    vocab_size: int = 256
    hidden_size: int = 64
    num_hidden_layers: int = 4
    layer_types: Tuple[str, ...] = ("mamba", "mamba", "attention", "mamba")
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    shared_intermediate_size: int = 128
    mamba_n_heads: int = 4
    mamba_d_head: int = 16
    mamba_d_state: int = 16
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 8
    attention_multiplier: float = 0.0625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
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
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unknown = sorted(set(self.layer_types) - {"mamba", "attention"})
        if unknown or len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers of kinds "
                f"{sorted(set(self.layer_types))} for num_hidden_layers="
                f"{self.num_hidden_layers}: one of mamba | attention a layer")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(
                f"mamba_n_groups={self.mamba_n_groups} does not divide "
                f"mamba_n_heads={self.mamba_n_heads}")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.hidden_size % self.num_attention_heads:
            raise ValueError(
                f"num_key_value_heads={self.num_key_value_heads} has to divide "
                f"num_attention_heads={self.num_attention_heads}, and that hidden_size")

    @classmethod
    def from_published(cls, published: Dict, **run) -> "SsmHybridConfig":
        """From a config.json-shaped dict. What the family cannot express
        is an error that names the key, not a silent departure."""
        refuse = {
            "num_local_experts": (0, None), "num_experts_per_tok": (0, None),
            "position_embedding_type": ("nope",), "hidden_act": ("silu", None),
            "attention_bias": (False, None), "mamba_proj_bias": (False, None),
            "mamba_conv_bias": (True,), "tie_word_embeddings": (True,),
            "normalization_function": ("rmsnorm", None),
        }
        for key, allowed in refuse.items():
            if published.get(key, allowed[0]) not in allowed:
                raise ValueError(
                    f"{key}={published[key]!r}: models/ssm_hybrid.py supports {allowed[0]!r} only")
        missing = [k for k in _PUBLISHED if k not in published]
        if missing:
            raise ValueError(f"config lacks {missing}")
        inner = published["mamba_n_heads"] * published["mamba_d_head"]
        if published.get("mamba_expand") not in (None, inner / published["hidden_size"]):
            raise ValueError(
                f"mamba_expand={published['mamba_expand']!r} x hidden_size is not "
                f"mamba_n_heads x mamba_d_head = {inner}")
        return cls(**{k: published[k] for k in _PUBLISHED}, **run)

    @property
    def effective_compute_dtype(self):
        return self.compute_dtype if self.compute_dtype is not None else self.dtype

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def mamba_layers(self) -> int:
        return self.layer_types.count("mamba")


def init_ssm_hybrid(cfg: SsmHybridConfig, key: jax.Array) -> Dict:
    d, dt = cfg.hidden_size, cfg.dtype
    keys = jax.random.split(key, 1 + cfg.num_hidden_layers)
    dense = lambda k, shape: (jax.random.normal(k, shape) / shape[0] ** 0.5).astype(dt)
    hd, nh = cfg.head_dim, cfg.mamba_n_heads
    blocks = []
    for i, kind in enumerate(cfg.layer_types):
        bk = jax.random.split(keys[1 + i], 8)
        blk = {"ln1": jnp.ones((d,), dt)}
        if kind == "mamba":
            # decay parameters as the source initialises them: A in [1, 16],
            # dt log-uniform in [1e-3, 1e-1] through the inverse softplus
            step = jnp.exp(jax.random.uniform(
                bk[6], (nh,), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
            blk.update(
                in_proj=dense(bk[0], (d, cfg.d_inner + cfg.conv_dim + nh)),
                conv_w=dense(bk[1], (cfg.mamba_d_conv, cfg.conv_dim)),
                conv_b=jnp.zeros((cfg.conv_dim,), dt),
                dt_bias=(step + jnp.log(-jnp.expm1(-step))).astype(jnp.float32),
                a_log=jnp.log(jax.random.uniform(bk[7], (nh,), minval=1.0, maxval=16.0)),
                skip={"scale": jnp.ones((nh,), jnp.float32)},       # D
                norm={"scale": jnp.ones((cfg.d_inner,), dt)},
                out_proj=dense(bk[2], (cfg.d_inner, d)))
        else:
            blk.update(
                wq=dense(bk[0], (d, cfg.num_attention_heads * hd)),
                wk=dense(bk[1], (d, cfg.num_key_value_heads * hd)),
                wv=dense(bk[2], (d, cfg.num_key_value_heads * hd)),
                wo=dense(bk[3], (cfg.num_attention_heads * hd, d)))
        blk["ln2"] = jnp.ones((d,), dt)
        blk["mlp"] = {"w_in": dense(bk[4], (d, 2 * cfg.shared_intermediate_size)),
                      "w_out": dense(bk[5], (cfg.shared_intermediate_size, d))}
        blocks.append(blk)
    return {
        "embed": (jax.random.normal(keys[0], (cfg.vocab_size, d)) * 0.02).astype(dt),
        "blocks": blocks,
        "out_norm": jnp.ones((d,), dt),
    }


def _causal_conv(x, w, bias):
    """Depthwise causal conv over time: x [B, T, C] float32, w [K, C] (tap
    K-1 meets the current token), bias [C]. The plain form of
    ops/causal_conv.py's kernels, which `causal_conv_silu` takes wherever
    `conv_path` says "xla"."""
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, [(0, 0), (k - 1, 0), (0, 0)])
    return sum(padded[:, i:i + t] * w[i] for i in range(k)) + bias


def mamba_mixer(cfg: SsmHybridConfig, n, blk):
    """n [B, T, D] in the compute dtype -> (the branch [B, T, D], the
    scan's cut-off count)."""
    cd, f32 = n.dtype, jnp.float32
    b, t, _ = n.shape
    h, p, g, s = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups, cfg.mamba_d_state
    proj = n @ blk["in_proj"].astype(cd)
    z, xbc, dt = jnp.split(proj, [cfg.d_inner, cfg.d_inner + cfg.conv_dim], axis=-1)
    xbc = causal_conv_silu(xbc, blk["conv_w"], blk["conv_b"], cd, _causal_conv)
    x, bmat, cmat = jnp.split(xbc, [cfg.d_inner, cfg.d_inner + g * s], axis=-1)
    dt = jax.nn.softplus(dt.astype(f32) + blk["dt_bias"].astype(f32))
    y, cut_off = ssd_chunked(
        x.reshape(b, t, h, p), dt, -jnp.exp(blk["a_log"].astype(f32)),
        bmat.reshape(b, t, g, s), cmat.reshape(b, t, g, s), blk["skip"]["scale"], cfg.mamba_chunk_size)
    gated = y.reshape(b, t, cfg.d_inner) * jax.nn.silu(z.astype(f32))
    y = _rms32(gated, blk["norm"]["scale"], cfg.rms_norm_eps).astype(cd)
    return y @ blk["out_proj"].astype(cd), cut_off


def gqa_attention(cfg: SsmHybridConfig, n, blk, attend):
    """n [B, T, D] -> the attention branch [B, T, D]; no positional term."""
    cd = n.dtype
    b, t, _ = n.shape
    hq, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = (n @ blk["wq"].astype(cd)).reshape(b, t, hq, hd)
    k = (n @ blk["wk"].astype(cd)).reshape(b, t, hkv, hd)
    v = (n @ blk["wv"].astype(cd)).reshape(b, t, hkv, hd)
    spread = lambda a: jnp.repeat(a, hq // hkv, axis=2)
    o = attend(q, spread(k), spread(v), scale=cfg.attention_multiplier)
    return o.reshape(b, t, hq * hd) @ blk["wo"].astype(cd)


def _gated_mlp(n, w, cd):
    gate, up = jnp.split(n @ w["w_in"].astype(cd), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w["w_out"].astype(cd)


def ssm_hybrid_block(cfg: SsmHybridConfig, x, blk, attend):
    """One block of either kind (by the leaves it holds) -> (x, the scan's
    cut-off count: zero for an attention block)."""
    cd = cfg.effective_compute_dtype
    with scope(MIXER_SSD if "in_proj" in blk else MIXER_ATTENTION):
        x = x.astype(cd)
        n = _rms32(x, blk["ln1"], cfg.rms_norm_eps).astype(cd)
        if "in_proj" in blk:
            mixed, cut_off = mamba_mixer(cfg, n, blk)
        else:
            mixed, cut_off = gqa_attention(cfg, n, blk, attend), jnp.int32(0)
        x = x + (cfg.residual_multiplier * mixed).astype(cd)
    with scope(FFN):
        n = _rms32(x, blk["ln2"], cfg.rms_norm_eps).astype(cd)
        with scope(MLP):
            return x + (cfg.residual_multiplier * _gated_mlp(n, blk["mlp"], cd)).astype(cd), cut_off


def saved_layers(cfg: SsmHybridConfig, batch: int, seq_len: int):
    """models/transformer.saved_layers for this family: the attention
    layers' flash kernels, keys and values already repeated to the query
    heads; a state-space layer names nothing."""
    return flash_layers(cfg, batch, seq_len, cfg.num_attention_heads, cfg.head_dim,
                        cfg.head_dim, cfg.num_hidden_layers - cfg.mamba_layers)


def apply_ssm_hybrid(
    cfg: SsmHybridConfig,
    params: Dict,
    tokens: jax.Array,  # int32 [B, T], ids of the vocabulary slice
    seq_axis_name: Optional[str] = None,
    pos_offset: Optional[jax.Array] = None,
):
    """Forward -> (logits [B, T, vocab], {"ssd_cut_off": int32 [mamba
    layers]}): per state-space layer, the (row, chunk, head) whose log-decay
    over the whole chunk is under ops/ssd.CUT_OFF_LOG. `pos_offset` is
    taken and unused: the model has no positional term."""
    del pos_offset
    if seq_axis_name is not None and jax.lax.axis_size(seq_axis_name) > 1:
        raise NotImplementedError(
            "models/ssm_hybrid.py: a sequence axis of "
            f"{jax.lax.axis_size(seq_axis_name)} members needs the state-space "
            "layers' carried state handed from one sequence shard to the next, "
            "which parallel/dp_sp.py does not do yet (ROADMAP M6): run --num-sp 1")
    attend = select_attention(cfg, seq_axis_name)
    cd = cfg.effective_compute_dtype

    def block(x, blk):
        return ssm_hybrid_block(cfg, x, blk, attend)

    if cfg.remat:
        block = remat_block(block, saved_layers(cfg, *tokens.shape), params)
    with scope(EMBED):
        x = (params["embed"][tokens] * cfg.embedding_multiplier).astype(cd)
    cut_off = []
    for blk in params["blocks"]:
        x, c = block(x, blk)
        if "in_proj" in blk:
            cut_off.append(c)
    with scope(HEAD_LOSS):
        n = _rms32(x, params["out_norm"], cfg.rms_norm_eps).astype(cd)
        logits = (n @ params["embed"].T.astype(cd)) / cfg.logits_scaling
    return logits, ({"ssd_cut_off": jnp.stack(cut_off)} if cut_off else {})


def ssd_plan(cfg: SsmHybridConfig, seq_len: int) -> Dict:
    """What every call of the scan will look like, from the shapes alone
    (the `ssd_plan` instant, through `plans`); `scan_path` is a constant,
    `conv_path` the form the short conv takes in this process."""
    return {"chunk": cfg.mamba_chunk_size, "n_chunks": -(-seq_len // cfg.mamba_chunk_size),
            "heads": cfg.mamba_n_heads, "d_head": cfg.mamba_d_head,
            "d_state": cfg.mamba_d_state, "groups": cfg.mamba_n_groups,
            "mamba_layers": cfg.mamba_layers,
            "attention_layers": cfg.num_hidden_layers - cfg.mamba_layers,
            "scan_path": SCAN_PATH, "conv_path": conv_path(cfg.conv_dim, cfg.mamba_d_conv)}


def ssd_counters(aux) -> Dict:
    """What the step returns beside the loss, from the aux summed over the
    mesh: `ssd_chunks_cut_off` over all state-space layers, and per layer."""
    return {"ssd_chunks_cut_off": jnp.sum(aux["ssd_cut_off"]),
            "ssd_chunks_cut_off_per_layer": aux["ssd_cut_off"]}


def plans(cfg: SsmHybridConfig, seq_len: int, seq_shards: int):
    out = flash_plans(cfg, seq_len, seq_shards, cfg.head_dim, cfg.head_dim)
    if cfg.mamba_layers:
        out.append(("ssd_plan", None, ssd_plan(cfg, seq_len)))
    return out


CONFIG = SsmHybridConfig


def family(cfg: SsmHybridConfig) -> LMFamily:
    return LMFamily(init_ssm_hybrid, apply_ssm_hybrid, ssd_counters if cfg.mamba_layers else None,
                    saved_layers, plans, (("ssd_state", "ssd_"),))
