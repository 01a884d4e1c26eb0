"""Dense byte-level decoder with EVA attention (the EvaByte block).

The fifth LM family beside models/transformer.py, mla_moe.py, ssm_hybrid.py
and kda_hybrid.py, for public models whose config.json says `model_type:
evabyte`. Same shape of module: pure init/apply, the call
`apply_eva_dense(cfg, params, tokens, seq_axis_name, pos_offset)`.

Per token row (s = head_dim^-0.5; every norm's statistics float32; `cd` is
the compute dtype; the residual stream x is float32, `fp32_skip_add`):

- model: x = embed[bytes]; per layer x = x + attn(norm(x)) W_o, then
  x = x + mlp(norm(x)); logits[i, p] = norm(x)[i] W_head[:, p], p = 0 ..
  `num_pred_heads` - 1, float32 (`fp32_logits`): head p predicts the byte
  at i + 1 + p, and the loss is the plain mean over heads and positions
  (parallel/dp_sp.lm_loss_local).
- norm(x) = x / sqrt(mean(x^2) + eps) * g. The source holds w with g = 1 +
  w (`norm_add_unit_offset`) and starts w at 0; the leaf here holds g and
  starts at 1: the same function of the same Adam steps.
- attn: q, k, v = n W_q, n W_k, n W_v, `num_attention_heads` heads each, no
  bias, no grouping; q and k rotated over the whole head in the halves
  layout (rotate_half) at the absolute position, theta `rope_theta`; then
  ops/eva.eva_attention at `window_size` and `chunk_size` with the layer's
  learned `phi` and `mu` [heads, head_dim].
- mlp(n) = (silu(n W_gate) * (n W_up)) W_down, `intermediate_size` wide.

`fp32_ln: false` notwithstanding, norm statistics are float32 as in every
family here. What the family cannot express is refused by name
(from_published); the summaries of one sequence shard are not handed to the
next, so a sequence axis of more than one member is refused too.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..obs.scopes import EMBED, FFN, HEAD_LOSS, MIXER_EVA, MLP, scope
from ..ops.eva import eva_attention, eva_saves, plan_eva
from .lm import LMFamily
from .mla_moe import _gated_mlp, _rms32
from .transformer import remat_block

# config.json keys this family reads; every other key is carried by the
# benchmark's file and ignored here
_PUBLISHED = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "intermediate_size", "window_size", "chunk_size", "num_pred_heads", "rope_theta",
    "rms_norm_eps",
)
# what from_published turns down, for models/lm.require_dense's message
REFUSES = (
    "an attention_class other than eva, a fixed num_chunks, rope scaling, grouped "
    "key/value heads, a tied head, a chunk_size that does not divide window_size, a "
    "sequence axis of more than one member")


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    # the published keys, under their published names
    vocab_size: int = 320
    hidden_size: int = 64
    num_hidden_layers: int = 2
    num_attention_heads: int = 4
    intermediate_size: int = 176
    window_size: int = 64
    chunk_size: int = 8
    num_pred_heads: int = 8
    rope_theta: float = 100000.0
    rms_norm_eps: float = 1e-5
    # how it is run: the same options, with the same meaning, as
    # TransformerConfig
    causal: bool = True
    dtype: Any = jnp.float32
    remat: bool = False
    bidirectional_ring: bool = False
    sp_attention: str = "ring"
    attention_impl: str = "naive"
    compute_dtype: Any = None

    def __post_init__(self):
        if self.hidden_size % self.num_attention_heads or self.head_dim % 2:
            raise ValueError(
                f"num_attention_heads={self.num_attention_heads} has to divide hidden_size="
                f"{self.hidden_size} into heads of an even width (the rotation's halves)")
        if self.window_size % self.chunk_size:
            raise ValueError(
                f"chunk_size={self.chunk_size} does not divide window_size={self.window_size}")

    @classmethod
    def from_published(cls, published: Dict, **run) -> "EvaByteConfig":
        """From a config.json-shaped dict. What the family cannot express
        is an error that names the key, not a silent departure."""
        refuse = {
            "attention_class": ("eva",), "num_chunks": (None,), "rope_scaling": (None,),
            "tie_word_embeddings": (False,), "attention_bias": (False, None),
            "hidden_act": ("silu", None), "norm_add_unit_offset": (True,),
            "fp32_skip_add": (True,), "fp32_logits": (True,),
        }
        for key, allowed in refuse.items():
            if published.get(key, allowed[0]) not in allowed:
                raise ValueError(
                    f"{key}={published[key]!r}: models/eva_dense.py supports {allowed[0]!r} only")
        missing = [k for k in _PUBLISHED if k not in published]
        if missing:
            raise ValueError(f"config lacks {missing}")
        heads = published["num_attention_heads"]
        if published.get("num_key_value_heads", heads) != heads:
            raise ValueError(
                f"num_key_value_heads={published['num_key_value_heads']!r}: models/eva_dense.py "
                f"pools one key and value a head, so it takes num_attention_heads={heads} of "
                "them (no grouped key/value heads)")
        return cls(**{k: published[k] for k in _PUBLISHED}, **run)

    @property
    def effective_compute_dtype(self):
        return self.compute_dtype if self.compute_dtype is not None else self.dtype

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def eva_layers(self) -> int:
        """Layers whose mixer is EVA attention: every one."""
        return self.num_hidden_layers


def init_eva_dense(cfg: EvaByteConfig, key: jax.Array) -> Dict:
    d, f, dt = cfg.hidden_size, cfg.intermediate_size, cfg.dtype
    h, hd = cfg.num_attention_heads, cfg.head_dim
    keys = jax.random.split(key, 2 + cfg.num_hidden_layers)
    dense = lambda k, shape: (jax.random.normal(k, shape) / shape[0] ** 0.5).astype(dt)
    blocks = []
    for i in range(cfg.num_hidden_layers):
        bk = jax.random.split(keys[2 + i], 9)
        blocks.append({
            "ln1": jnp.ones((d,), dt),
            "wq": dense(bk[0], (d, d)), "wk": dense(bk[1], (d, d)),
            "wv": dense(bk[2], (d, d)), "wo": dense(bk[3], (d, d)),
            # one row a head; drawn (not zero) so that the pooling is no plain mean
            "phi": dense(bk[4], (h, hd)), "mu": dense(bk[5], (h, hd)),
            "ln2": jnp.ones((d,), dt),
            "mlp": {"w_gate": dense(bk[6], (d, f)), "w_up": dense(bk[7], (d, f)),
                    "w_down": dense(bk[8], (f, d))},
        })
    return {
        "embed": (jax.random.normal(keys[0], (cfg.vocab_size, d)) * 0.02).astype(dt),
        "blocks": blocks,
        "out_norm": jnp.ones((d,), dt),
        "head": dense(keys[1], (d, cfg.num_pred_heads * cfg.vocab_size)),
    }


def _rope_halves(x, pos, theta: float):
    """Rotate the pairs (i, i + d/2) of x [B, T, H, d] by pos[t] *
    theta^(-2i/d): x * cos + rotate_half(x) * sin, in float32."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]                # [T, d/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


def eva_mixer(cfg: EvaByteConfig, n, blk, pos):
    """n [B, T, D] in the compute dtype -> (the branch [B, T, D], the
    attention's counts)."""
    cd = n.dtype
    b, t, _ = n.shape
    heads = lambda w: (n @ w.astype(cd)).reshape(b, t, cfg.num_attention_heads, cfg.head_dim)
    q = _rope_halves(heads(blk["wq"]), pos, cfg.rope_theta)
    k = _rope_halves(heads(blk["wk"]), pos, cfg.rope_theta)
    o, counts = eva_attention(q, k, heads(blk["wv"]), blk["phi"], blk["mu"], cfg.window_size,
                              cfg.chunk_size, impl=cfg.attention_impl)
    return o.reshape(b, t, cfg.hidden_size) @ blk["wo"].astype(cd), counts


def eva_dense_block(cfg: EvaByteConfig, x, blk, pos):
    """One block on the float32 stream x -> (x, the attention's counts)."""
    cd, f32 = cfg.effective_compute_dtype, jnp.float32
    with scope(MIXER_EVA):
        n = _rms32(x, blk["ln1"], cfg.rms_norm_eps).astype(cd)
        mixed, counts = eva_mixer(cfg, n, blk, pos)
        x = x + mixed.astype(f32)
    with scope(FFN):
        n = _rms32(x, blk["ln2"], cfg.rms_norm_eps).astype(cd)
        with scope(MLP):
            return x + _gated_mlp(n, blk["mlp"], cd).astype(f32), counts


def saved_layers(cfg: EvaByteConfig, batch: int, seq_len: int):
    """models/transformer.saved_layers for this family: what ops/eva.py
    names where its kernels run (models/transformer.flash_layers' rule)."""
    if cfg.attention_impl != "flash":
        return []
    return [eva_saves(batch, seq_len, cfg.num_attention_heads, cfg.head_dim,
                      cfg.effective_compute_dtype, cfg.window_size, cfg.chunk_size,
                      cfg.num_hidden_layers)]


def apply_eva_dense(
    cfg: EvaByteConfig,
    params: Dict,
    tokens: jax.Array,  # int32 [B, T], bytes and the special ids
    seq_axis_name: Optional[str] = None,
    pos_offset: Optional[jax.Array] = None,
):
    """Forward -> (logits float32 [B, T, num_pred_heads, vocab], the layers'
    attention counts stacked: ops/eva.eva_attention's, [layers] each)."""
    if seq_axis_name is not None and jax.lax.axis_size(seq_axis_name) > 1:
        raise NotImplementedError(
            "models/eva_dense.py: a sequence axis of "
            f"{jax.lax.axis_size(seq_axis_name)} members needs the earlier shards' chunk "
            "summaries handed to the later ones, which parallel/dp_sp.py does not do yet "
            "(ROADMAP M6): run --num-sp 1")
    b, t = tokens.shape
    pos = jnp.arange(t) + (0 if pos_offset is None else pos_offset)
    cd = cfg.effective_compute_dtype

    def block(x, blk):
        return eva_dense_block(cfg, x, blk, pos)

    if cfg.remat:
        block = remat_block(block, saved_layers(cfg, b, t), params)
    with scope(EMBED):
        x = params["embed"][tokens].astype(jnp.float32)
    counts = []
    for blk in params["blocks"]:
        x, c = block(x, blk)
        counts.append(c)
    with scope(HEAD_LOSS):
        n = _rms32(x, params["out_norm"], cfg.rms_norm_eps).astype(cd)
        logits = jnp.dot(n, params["head"].astype(cd), preferred_element_type=jnp.float32)
    aux = {"eva_" + name: jnp.stack([c[name] for c in counts]) for name in counts[0]}
    return logits.reshape(b, t, cfg.num_pred_heads, cfg.vocab_size), aux


def eva_plan(cfg: EvaByteConfig, seq_len: int) -> Dict:
    """What every call of the attention will look like, from the shapes
    alone (cli/train_lm.py logs it and records it as the `eva_plan`
    instant): windows and summaries a row, the two passes' tiles a head
    (`tiles_local`, `tiles_remote`: the live ones; the local pass walks
    exactly those, the remote pass `remote_grid_steps` of its rectangle's
    `remote_tiles_total`: its live tiles and a dead entry for each q block
    of window 0)."""
    plan = plan_eva(seq_len, cfg.head_dim, cfg.effective_compute_dtype,
                    cfg.window_size, cfg.chunk_size)
    local, remote = plan.tiles()
    out = {"window": plan.window, "chunk": cfg.chunk_size, "windows": plan.windows,
           "padded_len": plan.t_pad, "summaries": plan.summaries,
           "heads": cfg.num_attention_heads, "d_head": cfg.head_dim,
           "eva_layers": cfg.eva_layers,
           "block_q": plan.local.block_q, "block_k": plan.local.block_k,
           "tiles_local": local, "tiles_remote": remote, "bwd": plan.local.bwd}
    if plan.remote:
        out.update(remote_block_q=plan.remote.block_q, remote_block_k=plan.remote.block_k,
                   remote_grid_steps=plan.remote.grid_steps,
                   remote_tiles_total=plan.remote.tiles_total)
    return out


def eva_counters(aux) -> Dict:
    """What the step returns beside the loss, from the aux summed over the
    mesh: `eva_remote_mass`, the mean share of its softmax that a query past
    window 0 puts on summaries (0 where no row is longer than a window),
    and the same per layer."""
    queries = jnp.maximum(aux["eva_mass_queries"], 1.0)
    return {"eva_remote_mass": jnp.sum(aux["eva_mass_sum"]) / jnp.sum(queries),
            "eva_remote_mass_per_layer": aux["eva_mass_sum"] / queries}


def plans(cfg: EvaByteConfig, seq_len: int, seq_shards: int):
    """EVA attention runs the flash kernels twice a layer, over windows and
    over pooled keys: its own plan says both, in place of a `flash_plan`."""
    del seq_shards  # one member only (apply_eva_dense)
    return [("eva_plan", "ps_eva_",
             {**eva_plan(cfg, seq_len), "attention_impl": cfg.attention_impl})]


CONFIG = EvaByteConfig


def family(cfg: EvaByteConfig) -> LMFamily:
    return LMFamily(init_eva_dense, apply_eva_dense, eva_counters, saved_layers, plans,
                    (("eva_state", "eva_"),))
