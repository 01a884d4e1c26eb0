"""Plain reference of `kanana2_30b_a3b_ep8`: the DeepSeek-V3 block
(`model_type: deepseek_v3`) as kakaocorp/kanana-2-30b-a3b-instruct-2601
configures it, on one chip's share of an 8-chip expert-parallel layer.
Float32 jax.numpy, every product at precision "highest", nothing of the
program imported.

Per token row x, all norms RMS with gain (eps `rms_norm_eps`):

    h = x + Attn(norm(x));  y = h + FFN(norm(h));  logits = norm(x_L) W_head

Attn (latent attention, no query compression): q = n W_q -> heads of
(q_nope 128, q_rope 64); (c 512, k_rope 64) = n W_kva, k_rope ONE head for
all; c = norm(c); per head (k_nope 128, v 128) = c W_kvb; rotary on q_rope
and k_rope: the pairs (2i, 2i+1), as `rope_interleave` stores them, turned
by pos * theta^(-2i/64); k = [k_nope, k_rope]; causal softmax(q k^T /
sqrt(192)) v; W_o. FFN of layer 0: (silu(n W_g) * n W_u) W_d, 6144 wide.
FFN of the others: s = sigmoid(n W_r) over all 128 experts; the 6 largest
of s + b chosen (b: the aux-loss-free correction, a leaf that no gradient
reaches; zero here, its update rule not part of the step); weights
s[chosen] / (sum + 1e-20) * 2.448 over all six; the sum over the chosen
experts HELD HERE (ids expert_offset .. +experts_held-1) of weight *
Expert_e(n), each a gated MLP 768 wide, plus the shared experts (one gated
MLP 1536 wide). What the absent experts would add is left out and the
partial result goes on, as on one chip of the deployment. Every held expert
is applied to every token and weighted (zero where not chosen): plain, and
eight times the routed work. Loss: mean next-token NLL over the vocabulary
slice held.

Sized to run beside its own state: one row of `seq_len` tokens at a time
with the gradients summed in place, Adam's moments on the host meanwhile, each layer rematerialised, attention a
block of queries at a time, the held experts one at a time.

`operand` is the control's switch: "float8_e4m3fn" rounds both operands of
every product to 8-bit floats first, the nearest precision below the
bfloat16 the configuration computes in.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
QUERY_BLOCK = 512
GROUPS = ("experts",)   # keys of `param_shapes` that hold a list of alike entries


def _sizes(cfg: dict):
    return (int(cfg["hidden_size"]), int(cfg["num_attention_heads"]),
            int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
            int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"]))


def param_shapes(cfg: dict) -> dict:
    """One 2-D entry per expert (benchmark/weights.py draws a leaf at
    1/sqrt(prod(shape[:-1])): each expert at its own fan-in); the program
    holds them stacked, and so does `_expert_ffn` below."""
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    d, h, dn, dr, dv, r = _sizes(cfg)
    v = int(cfg["vocab_size"])
    gated = lambda width: {"w_gate": S(d, width), "w_up": S(d, width), "w_down": S(width, d)}
    blocks = []
    for i in range(int(cfg["num_hidden_layers"])):
        blk = {"ln1": S(d), "wq": S(d, h * (dn + dr)), "wkv_a": S(d, r + dr),
               "kv_norm": {"scale": S(r)}, "wkv_b": S(r, h * (dn + dv)),
               "wo": S(h * dv, d), "ln2": S(d)}
        if i < int(cfg["first_k_dense_replace"]):
            blk["mlp"] = gated(int(cfg["intermediate_size"]))
        else:
            f = int(cfg["moe_intermediate_size"])
            blk.update(router=S(d, int(cfg["n_routed_experts"])),
                       router_bias=S(int(cfg["n_routed_experts"])),
                       shared=gated(int(cfg["n_shared_experts"]) * f),
                       experts=[gated(f) for _ in range(int(cfg["experts_held"]))])
        blocks.append(blk)
    return {"embed": S(v, d), "blocks": blocks, "out_norm": S(d), "head": S(d, v)}


def _mm(operand):
    def cast(a):
        return a if operand is None else a.astype(operand).astype(jnp.float32)

    def mm(a, b, spec):
        return jnp.einsum(spec, cast(a), cast(b), precision=HI)

    return mm


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [T, H, d]: the pairs (2i, 2i+1) turned by t * theta^(-2i/d)."""
    t, _, d = x.shape
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * theta ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def _attention(cfg, n, p, mm):
    d, h, dn, dr, dv, r = _sizes(cfg)
    t = n.shape[0]
    theta = float(cfg["rope_theta"])
    q = mm(n, p["wq"], "td,de->te").reshape(t, h, dn + dr)
    kva = mm(n, p["wkv_a"], "td,de->te")
    c = _rms(kva[:, :r], p["kv_norm"]["scale"], float(cfg["rms_norm_eps"]))
    kvb = mm(c, p["wkv_b"], "tr,re->te").reshape(t, h, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], axis=-1)
    k_rope = _rope(kva[:, None, r:], theta)
    k = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(k_rope, (t, h, dr))], axis=-1)
    v = kvb[..., dn:]
    scale = (dn + dr) ** -0.5
    bq = min(QUERY_BLOCK, t)
    assert t % bq == 0

    @jax.checkpoint
    def block(q_blk, start):
        s = mm(q_blk, k, "qhd,khd->hqk") * scale
        keep = jnp.arange(t)[None, :] <= (start + jnp.arange(bq))[:, None]
        s = jnp.where(keep[None], s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), v, "hqk,khd->qhd")

    o = lax.map(lambda a: block(*a), (q.reshape(t // bq, bq, h, dn + dr),
                                     jnp.arange(0, t, bq)))
    return mm(o.reshape(t, h * dv), p["wo"], "te,ed->td")


def _gated(n, w, mm):
    return mm(jax.nn.silu(mm(n, w["w_gate"], "td,df->tf")) * mm(n, w["w_up"], "td,df->tf"),
              w["w_down"], "tf,fd->td")


def _expert_ffn(cfg, n, p, mm):
    k, held, off = (int(cfg["num_experts_per_tok"]), int(cfg["experts_held"]),
                    int(cfg.get("expert_offset", 0)))
    s = jax.nn.sigmoid(mm(n, p["router"], "td,de->te"))
    _, idx = lax.top_k(s + p["router_bias"], k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * float(cfg["routed_scaling_factor"])
    # weight of each held expert for each token: zero where it was not chosen
    here = jnp.sum(jnp.where(idx[:, :, None] == off + jnp.arange(held)[None, None],
                             w[:, :, None], 0.0), axis=1)                  # [T, held]
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *p["experts"])

    def one(acc, ew):
        weights, col = ew
        return acc + col[:, None] * _gated(n, weights, mm), None

    routed, _ = lax.scan(jax.checkpoint(one), jnp.zeros_like(n), (stacked, here.T))
    return routed + _gated(n, p["shared"], mm)


def _layer(cfg, x, p, mm):
    eps = float(cfg["rms_norm_eps"])
    x = x + _attention(cfg, _rms(x, p["ln1"], eps), p, mm)
    n = _rms(x, p["ln2"], eps)
    return x + (_gated(n, p["mlp"], mm) if "mlp" in p else _expert_ffn(cfg, n, p, mm))


def logits_fn(cfg: dict, params, tokens, operand=None):
    """tokens int32 [T] (one row) -> logits [T, vocab held]."""
    mm = _mm(operand)
    x = params["embed"][tokens]
    for p in params["blocks"]:
        x = jax.checkpoint(lambda x, p: _layer(cfg, x, p, mm))(x, p)
    n = _rms(x, params["out_norm"], float(cfg["rms_norm_eps"]))
    return mm(n, params["head"], "td,dv->tv")


def nll_sum(cfg: dict, params, tokens, operand=None):
    """Sum over positions 0..T-2 of one row of the next token's -log p."""
    logp = jax.nn.log_softmax(logits_fn(cfg, params, tokens, operand)[:-1], axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


def train_steps(cfg: dict, traffic: dict, make_params, feed: dict,
                n_steps: int = 3, operand=None):
    """Follows the first `n_steps` steps on feed["tokens"][feed["rows"][s]]
    from the weights `make_params()` gives (PyTorch-form Adam at the
    constant rate `lr`). Returns losses, the first gradient's norm per leaf and the norm of the
    parameters' change per leaf."""
    from benchmark.weights import leaf_names, leaf_norms

    lr, b1, b2, eps = (float(traffic[k]) for k in ("lr", "b1", "b2", "eps"))
    operand = None if operand is None else jnp.dtype(operand)

    @partial(jax.jit, donate_argnums=(0,))
    def accumulate(gsum, p, row):
        loss, g = jax.value_and_grad(lambda p: nll_sum(cfg, p, row, operand))(p)
        return jax.tree_util.tree_map(jnp.add, gsum, g), loss

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(p, m, v, gsum, count, step):
        g = jax.tree_util.tree_map(lambda t: t / count, gsum)
        m = jax.tree_util.tree_map(lambda a, t: b1 * a + (1 - b1) * t, m, g)
        v = jax.tree_util.tree_map(lambda a, t: b2 * a + (1 - b2) * t * t, v, g)
        size = lr * jnp.sqrt(1 - b2 ** step) / (1 - b1 ** step)
        p = jax.tree_util.tree_map(
            lambda a, mm_, vv: a - size * mm_ / (jnp.sqrt(vv) + eps), p, m, v)
        return p, m, v, leaf_norms(g)

    @jax.jit
    def change(p, q):
        return leaf_norms(jax.tree_util.tree_map(jnp.subtract, p, q))

    tokens, rows = np.asarray(feed["tokens"]), np.asarray(feed["rows"])
    p = make_params()
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    # Adam's moments wait on the host between updates: the gradient pass of
    # a row needs their 4.3 GiB (the control's more than the reference's)
    m = v = None
    losses, grad_norms = [], None
    for s in range(n_steps):
        batch = tokens[rows[s]]
        count = batch.shape[0] * (batch.shape[1] - 1)
        gsum, lsum = zeros(p), 0.0
        for row in batch:
            gsum, l = accumulate(gsum, p, jnp.asarray(row))
            lsum = lsum + float(l)
        losses.append(lsum / count)
        m, v = (zeros(p), zeros(p)) if m is None else jax.device_put((m, v))
        p, m, v, gn = update(p, m, v, gsum, jnp.float32(count), jnp.float32(s + 1))
        if s == 0:
            grad_norms = np.asarray(gn).tolist()
        if s + 1 < n_steps:
            m, v = jax.device_get((m, v))
    del m, v, gsum
    dparam = np.asarray(change(p, make_params())).tolist()
    return {"loss": losses, "grad_norms": grad_norms, "dparam_norms": dparam,
            "leaf_names": leaf_names(p)}
