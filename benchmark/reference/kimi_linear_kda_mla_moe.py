"""Plain reference of `kimi_linear_48b_a3b_ep32`: the hybrid delta-rule /
latent-attention expert decoder (`model_type: kimi_linear`) as
moonshotai/Kimi-Linear-48B-A3B-Instruct configures it, on one chip's share
of a 32-chip expert-parallel layer. Float32 jax.numpy, every product at
precision "highest", nothing of the program imported.

Per token row x, all norms RMS with gain (eps `rms_norm_eps`):

    h = x + Mixer(norm(x));  y = h + FFN(norm(h));  logits = norm(x_L) W_head

The mixer of published layer i (1-indexed) is KDA where `linear_attn_config.
kda_layers` names i and latent attention where `full_attn_layers` does.

KDA (H heads of `head_dim` d for keys and values alike): q~, k~, v =
silu(conv(n W_q | W_k | W_v)), the conv causal and depthwise over
`short_conv_kernel_size` taps (stored [taps, channels], the last tap meets
the current token), no bias; per head q = q~ / sqrt(|q~|^2 + 1e-6) * d^-1/2,
k = k~ / sqrt(|k~|^2 + 1e-6); the log-decay a key channel g = -exp(A_log[h])
* softplus((n W_fa) W_fb + dt_bias); beta = sigmoid(n W_beta), one a head;
per head a state S [d keys, d values], S_0 = 0, TOKEN BY TOKEN:

    S' = diag(exp(g_t)) S_(t-1)
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T          o_t = S_t^T q_t

y = norm_over_d(o; gain [d]) * sigmoid((n W_ga) W_gb); W_o. The recurrence
is computed by this definition, never by chunks: a lax.scan over t, in
segments of SEGMENT tokens under jax.checkpoint so that its backward keeps
one state a segment and one segment's states (64 x 2 MiB and 128 x 2 MiB at
8,192 tokens and 32 heads of 128 x 128).

Latent attention (no query compression, NO rotation: `mla_use_nope`): q = n
W_q -> heads of 128 + 64; (c 512, k_shared 64) = n W_kva, k_shared one head
for all and unrotated; c = norm(c); per head (k 128, v 128) = c W_kvb; k =
[k, k_shared]; causal softmax(q k^T / sqrt(192)) v; W_o.

FFN of layer 1: (silu(n W_g) * n W_u) W_d, 9216 wide. FFN of the others: s =
sigmoid(n W_r) over all 256 experts; the 8 largest of s + b chosen (b: the
correction bias, a leaf no gradient reaches; zero here, its update rule not
part of the step; one expert group, so the grouped top-k is the plain one);
weights s[chosen] / (sum + 1e-20) * 2.446; the sum over the chosen experts
HELD HERE (ids expert_offset .. +experts_held-1) of weight * Expert_e(n),
each a gated MLP 1024 wide, plus the shared expert (one gated MLP 1024
wide). What the absent experts would add is left out and the partial result
goes on, as on one chip of the deployment. Every held expert is applied to
every token and weighted (zero where not chosen). Loss: mean next-token NLL
over the vocabulary slice held.

Sized to run beside its own state (602 M parameters: 2.4 GB a copy): one
row of `seq_len` tokens at a time through ONE gradient program (the rows'
gradients are summed by a second, small one), Adam's moments wait on the
host between updates, each layer rematerialised, attention a block of
queries at a time, the held experts one at a time.

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
SEGMENT = 128
GROUPS = ("experts",)   # keys of `param_shapes` that hold a list of alike entries


def _kda_sizes(cfg: dict):
    lin = cfg["linear_attn_config"]
    return int(lin["num_heads"]), int(lin["head_dim"]), int(lin["short_conv_kernel_size"])


def _mla_sizes(cfg: dict):
    return (int(cfg["num_attention_heads"]), int(cfg["qk_nope_head_dim"]),
            int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"]))


def param_shapes(cfg: dict) -> dict:
    """One 2-D entry per expert (benchmark/weights.py draws a leaf at
    1/sqrt(prod(shape[:-1])): each expert at its own fan-in); the program
    holds them stacked, and so does `_expert_ffn` below. The two low-rank
    pairs are `head_dim` wide (the configuration file's `assumed`)."""
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    hk, dk, taps = _kda_sizes(cfg)
    h, dn, dr, dv, r = _mla_sizes(cfg)
    inner = hk * dk
    lin = cfg["linear_attn_config"]
    gated = lambda width: {"w_gate": S(d, width), "w_up": S(d, width), "w_down": S(width, d)}
    blocks = []
    for i in range(int(cfg["num_hidden_layers"])):
        blk = {"ln1": S(d)}
        if i + 1 in lin["kda_layers"]:
            blk.update(wq=S(d, inner), wk=S(d, inner), wv=S(d, inner),
                       conv_q=S(taps, inner), conv_k=S(taps, inner), conv_v=S(taps, inner),
                       f_a=S(d, dk), f_b=S(dk, inner), a_log=S(hk), dt_bias=S(inner),
                       w_beta=S(d, hk), g_a=S(d, dk), g_b=S(dk, inner),
                       o_norm={"scale": S(dk)}, wo=S(inner, d))
        else:
            assert i + 1 in lin["full_attn_layers"], i + 1
            blk.update(wq=S(d, h * (dn + dr)), wkv_a=S(d, r + dr), kv_norm={"scale": S(r)},
                       wkv_b=S(r, h * (dn + dv)), wo=S(h * dv, d))
        blk["ln2"] = S(d)
        if i < int(cfg["first_k_dense_replace"]):
            blk["mlp"] = gated(int(cfg["intermediate_size"]))
        else:
            f = int(cfg["moe_intermediate_size"])
            blk.update(router=S(d, int(cfg["num_experts"])),
                       router_bias=S(int(cfg["num_experts"])),
                       shared=gated(int(cfg["num_shared_experts"]) * f),
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


def _delta_rule(q, k, v, g, beta, mm):
    """q, k, g [T, H, K]; v [T, H, V]; beta [T, H] -> o [T, H, V], token by
    token from S = 0."""
    t = q.shape[0]
    seg = SEGMENT if t % SEGMENT == 0 else t

    def turn(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = jnp.exp(g_t)[..., None] * s
        u = b_t[:, None] * (v_t - mm(s, k_t, "hkv,hk->hv"))
        s = s + mm(k_t, u, "hk,hv->hkv")
        return s, mm(s, q_t, "hkv,hk->hv")

    @jax.checkpoint
    def segment(s, inp):
        return lax.scan(turn, s, inp)

    xs = tuple(a.reshape((t // seg, seg) + a.shape[1:]) for a in (q, k, v, g, beta))
    s0 = jnp.zeros(q.shape[1:] + (v.shape[-1],), jnp.float32)
    _, o = lax.scan(segment, s0, xs)
    return o.reshape(v.shape)


def _kda(cfg, n, p, mm):
    h, dk, taps = _kda_sizes(cfg)
    t = n.shape[0]

    def short(w, filt):
        padded = jnp.pad(mm(n, w, "td,de->te"), [(taps - 1, 0), (0, 0)])
        return jax.nn.silu(sum(padded[i:i + t] * filt[i] for i in range(taps))).reshape(t, h, dk)

    unit = lambda a: a * lax.rsqrt(jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6)
    low_rank = lambda a, b: mm(mm(n, a, "td,dr->tr"), b, "tr,re->te")
    q = unit(short(p["wq"], p["conv_q"])) * dk ** -0.5
    k = unit(short(p["wk"], p["conv_k"]))
    v = short(p["wv"], p["conv_v"])
    g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(
        low_rank(p["f_a"], p["f_b"]) + p["dt_bias"]).reshape(t, h, dk)
    beta = jax.nn.sigmoid(mm(n, p["w_beta"], "td,dh->th"))
    o = _delta_rule(q, k, v, g, beta, mm)
    gate = jax.nn.sigmoid(low_rank(p["g_a"], p["g_b"])).reshape(t, h, dk)
    y = _rms(o, p["o_norm"]["scale"], float(cfg["rms_norm_eps"])) * gate
    return mm(y.reshape(t, h * dk), p["wo"], "te,ed->td")


def _attention(cfg, n, p, mm):
    h, dn, dr, dv, r = _mla_sizes(cfg)
    assert cfg["mla_use_nope"] is True, "the reference computes no rotation"
    t = n.shape[0]
    q = mm(n, p["wq"], "td,de->te").reshape(t, h, dn + dr)
    kva = mm(n, p["wkv_a"], "td,de->te")
    c = _rms(kva[:, :r], p["kv_norm"]["scale"], float(cfg["rms_norm_eps"]))
    kvb = mm(c, p["wkv_b"], "tr,re->te").reshape(t, h, dn + dv)
    k = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(kva[:, None, r:], (t, h, dr))], axis=-1)
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
    k, held, off = (int(cfg["num_experts_per_token"]), int(cfg["experts_held"]),
                    int(cfg.get("expert_offset", 0)))
    s = jax.nn.sigmoid(mm(n, p["router"], "td,de->te"))
    _, idx = lax.top_k(s + p["router_bias"], k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["moe_renormalize"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * float(cfg["routed_scaling_factor"])
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
    mixer = _kda if "a_log" in p else _attention
    x = x + mixer(cfg, _rms(x, p["ln1"], eps), p, mm)
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
    constant rate `lr`). Returns losses, the first gradient's norm per leaf
    and the norm of the parameters' change per leaf."""
    from benchmark.weights import leaf_names, leaf_norms

    lr, b1, b2, eps = (float(traffic[k]) for k in ("lr", "b1", "b2", "eps"))
    operand = None if operand is None else jnp.dtype(operand)

    @jax.jit
    def first(p, row):
        loss, g = jax.value_and_grad(lambda p: nll_sum(cfg, p, row, operand))(p)
        return g, loss

    # the sum is a program of its own: a second program around `first` would
    # compile the whole backward pass again (a minute and a half on the chip's
    # host, and too large for the compile cache to keep)
    @partial(jax.jit, donate_argnums=(0,))
    def add(gsum, g):
        return jax.tree_util.tree_map(jnp.add, gsum, g)

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
    # Adam's moments wait on the host between updates: a row's gradient
    # pass needs their 4.8 GB
    m = v = None
    losses, grad_norms = [], None
    for s in range(n_steps):
        batch = tokens[rows[s]]
        count = batch.shape[0] * (batch.shape[1] - 1)
        gsum, lsum = None, 0.0
        for row in batch:
            g, l = first(p, jnp.asarray(row))
            gsum = g if gsum is None else add(gsum, g)
            lsum = lsum + float(l)
        del g
        losses.append(lsum / count)
        m, v = (zeros(p), zeros(p)) if m is None else jax.device_put((m, v))
        p, m, v, gn = update(p, m, v, gsum, jnp.float32(count), jnp.float32(s + 1))
        del gsum
        if s == 0:
            grad_norms = np.asarray(gn).tolist()
        if s + 1 < n_steps:
            m, v = jax.device_get((m, v))
    del m, v
    dparam = np.asarray(change(p, make_params())).tolist()
    return {"loss": losses, "grad_norms": grad_norms, "dparam_norms": dparam,
            "leaf_names": leaf_names(p)}
