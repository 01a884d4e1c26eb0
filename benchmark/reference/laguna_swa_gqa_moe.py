"""Plain reference of `laguna_s_2_1_ep32`: the sliding-window / global
grouped-query attention expert decoder (`model_type: laguna`) as
poolside/Laguna-S-2.1 configures it, on one chip's share of a 32-chip
expert-parallel layer. Float32 jax.numpy, every product at precision
"highest", nothing of the program imported.

Per token row x [T, D], all norms RMS with gain (eps `rms_norm_eps`); layer l
has the kind `layer_types[l]`, H = `num_attention_heads_per_layer[l]` query
heads over `num_key_value_heads` key/value heads of `head_dim` d, and the FFN
`mlp_layer_types[l]` names:

    h = x + Attn(norm(x));  y = h + FFN(norm(h));  logits = norm(x_L) W_head

Attn: q = n W_q [T, H, d]; k = n W_k, v = n W_v [T, kv, d]. Rotary on q and k
by the kind's `rope_parameters` group, rotate-half layout with the rotated
dims FIRST: r = `partial_rotary_factor` x d dims rotate, pair (j, j + r/2) by
the angle pos * f_j, the other d - r pass through. `rope_type: default`: f_j =
theta^(-2j/r). `rope_type: yarn`: base f_j = theta^(-2j/r), interpolated f_j /
`factor`; the correction dim of n turns over L = `original_max_position_
embeddings` positions is c(n) = r ln(L / (2 pi n)) / (2 ln theta); low =
floor(c(beta_fast)), high = ceil(c(beta_slow)), clipped to [0, r - 1]; ramp_j =
clip((j - low) / (high - low), 0, 1); f = f_j (1 - ramp_j) + f_j / factor *
ramp_j; cos and sin are multiplied by `attention_factor`. Nothing of it
depends on the row's length. Query head h reads key/value head h // (H / kv).
s_ij = q_i . k_j / sqrt(d); the keys seen are j <= i (full_attention) or i -
`sliding_window` < j <= i (sliding_attention), a dense [queries, T] comparison
a block of queries at a time; softmax over the seen keys; o_h = sum_j p_ij
v_j. The gate a head and token: o_h <- sigmoid(n W_g)_h o_h, W_g [D, H]; W_o.

FFN of a `dense` layer: (silu(n W_gate) * n W_up) W_down, `intermediate_size`
wide. FFN of a `sparse` one: s = sigmoid(n W_r) over all `num_experts`; the
`num_experts_per_tok` largest of s + b chosen (b: a correction bias the
config has no key for: a zero buffer no gradient reaches); weights s[chosen] /
(sum + 1e-20) * `moe_routed_scaling_factor`; the sum over the chosen experts
HELD HERE (ids expert_offset .. +experts_held-1) of weight * Expert_e(n), each
a gated MLP `moe_intermediate_size` wide, plus the shared expert (one gated
MLP `shared_expert_intermediate_size` wide), unweighted. What the absent
experts would add is left out and the partial result goes on, as on one chip
of the deployment. Every held expert is applied to every token and weighted
(zero where not chosen). Loss: mean next-token NLL over the vocabulary slice
held.

Sized to run beside its own state (811 M parameters: 3.2 GB a copy): one row
of `seq_len` tokens at a time through ONE gradient program, Adam's moments
wait on the host between updates, each layer rematerialised, attention a block
of queries at a time, the held experts one at a time.

`operand` is the control's switch: "float8_e4m3fn" rounds both operands of
every product to 8-bit floats first, the nearest precision below the bfloat16
the configuration computes in.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
QUERY_BLOCK = 256
GROUPS = ("experts",)   # keys of `param_shapes` that hold a list of alike entries


def param_shapes(cfg: dict) -> dict:
    """One 2-D entry per expert (benchmark/weights.py draws a leaf at
    1/sqrt(prod(shape[:-1])): each expert at its own fan-in); the program
    holds them stacked, and so does `_expert_ffn` below."""
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    kv, hd = int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    gated = lambda width: {"w_gate": S(d, width), "w_up": S(d, width), "w_down": S(width, d)}
    blocks = []
    for i in range(int(cfg["num_hidden_layers"])):
        h = int(cfg["num_attention_heads_per_layer"][i])
        blk = {"ln1": S(d), "wq": S(d, h * hd), "wk": S(d, kv * hd), "wv": S(d, kv * hd),
               "wg": S(d, h), "wo": S(h * hd, d), "ln2": S(d)}
        if cfg["mlp_layer_types"][i] == "dense":
            blk["mlp"] = gated(int(cfg["intermediate_size"]))
        else:
            assert cfg["mlp_layer_types"][i] == "sparse", cfg["mlp_layer_types"][i]
            blk.update(router=S(d, int(cfg["num_experts"])),
                       router_bias=S(int(cfg["num_experts"])),
                       shared=gated(int(cfg["shared_expert_intermediate_size"])),
                       experts=[gated(int(cfg["moe_intermediate_size"]))
                                for _ in range(int(cfg["experts_held"]))])
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


def rotary_table(group: dict, head_dim: int, t: int):
    """(cos, sin) float32 [T, r / 2] of one `rope_parameters` group, scaled
    as the group says; r the rotated dims."""
    r = int(head_dim * float(group.get("partial_rotary_factor", 1)))
    theta = float(group["rope_theta"])
    f = np.array([theta ** (-2.0 * j / r) for j in range(r // 2)])
    scale = 1.0
    kind = group.get("rope_type", "default")
    if kind == "yarn":
        span = float(group["original_max_position_embeddings"])
        c = lambda turns: r * math.log(span / (2 * math.pi * turns)) / (2 * math.log(theta))
        low = max(math.floor(c(float(group["beta_fast"]))), 0)
        high = min(math.ceil(c(float(group["beta_slow"]))), r - 1)
        ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
        f = f * (1.0 - ramp) + f / float(group["factor"]) * ramp
        scale = float(group["attention_factor"])
    else:
        assert kind == "default", kind
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(f, jnp.float32)[None]
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def _rotate(x, cos, sin):
    """x [T, H, d]: the pairs (j, j + r/2) of the first r dims turned, the
    rest as they are."""
    half = cos.shape[1]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    cos, sin = cos[:, None], sin[:, None]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def _attention(cfg, kind, n, p, mm):
    kv, d = int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    t = n.shape[0]
    h = p["wg"].shape[1]
    group = h // kv
    cos, sin = rotary_table(cfg["rope_parameters"][kind], d, t)
    q = _rotate(mm(n, p["wq"], "td,de->te").reshape(t, h, d), cos, sin)
    k = _rotate(mm(n, p["wk"], "td,de->te").reshape(t, kv, d), cos, sin)
    v = mm(n, p["wv"], "td,de->te").reshape(t, kv, d)
    window = int(cfg["sliding_window"]) if kind == "sliding_attention" else None
    assert kind in ("sliding_attention", "full_attention"), kind
    bq = min(QUERY_BLOCK, t)
    assert t % bq == 0

    @jax.checkpoint
    def block(q_blk, start):
        # query head kv_i * group + g reads key/value head kv_i
        s = mm(q_blk.reshape(bq, kv, group, d), k, "qcgd,kcd->cgqk") * d ** -0.5
        i, j = (start + jnp.arange(bq))[:, None], jnp.arange(t)[None, :]
        seen = j <= i
        if window is not None:
            seen = seen & (j > i - window)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), v, "cgqk,kcd->qcgd").reshape(bq, h, d)

    o = lax.map(lambda a: block(*a), (q.reshape(t // bq, bq, h, d), jnp.arange(0, t, bq)))
    gate = jax.nn.sigmoid(mm(n, p["wg"], "td,dh->th"))
    o = o.reshape(t, h, d) * gate[..., None]
    return mm(o.reshape(t, h * d), p["wo"], "te,ed->td")


def _gated(n, w, mm):
    return mm(jax.nn.silu(mm(n, w["w_gate"], "td,df->tf")) * mm(n, w["w_up"], "td,df->tf"),
              w["w_down"], "tf,fd->td")


def route_weights(cfg, n, p, mm):
    """[T, held]: the weight of each held expert for each token, zero where
    it was not among the token's chosen."""
    k, held, off = (int(cfg["num_experts_per_tok"]), int(cfg["experts_held"]),
                    int(cfg.get("expert_offset", 0)))
    s = jax.nn.sigmoid(mm(n, p["router"], "td,de->te"))
    _, idx = lax.top_k(s + p["router_bias"], k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * float(cfg["moe_routed_scaling_factor"])
    return jnp.sum(jnp.where(idx[:, :, None] == off + jnp.arange(held)[None, None],
                             w[:, :, None], 0.0), axis=1)


def routed_experts(cfg, n, p, mm):
    """The routed part alone: the held experts' weighted sum [T, D]."""
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *p["experts"])

    def one(acc, ew):
        weights, col = ew
        return acc + col[:, None] * _gated(n, weights, mm), None

    routed, _ = lax.scan(jax.checkpoint(one), jnp.zeros_like(n),
                         (stacked, route_weights(cfg, n, p, mm).T))
    return routed


def _expert_ffn(cfg, n, p, mm):
    return routed_experts(cfg, n, p, mm) + _gated(n, p["shared"], mm)


def _layer(cfg, kind, x, p, mm):
    eps = float(cfg["rms_norm_eps"])
    x = x + _attention(cfg, kind, _rms(x, p["ln1"], eps), p, mm)
    n = _rms(x, p["ln2"], eps)
    return x + (_gated(n, p["mlp"], mm) if "mlp" in p else _expert_ffn(cfg, n, p, mm))


def logits_fn(cfg: dict, params, tokens, operand=None):
    """tokens int32 [T] (one row) -> logits [T, vocab held]."""
    mm = _mm(operand)
    x = params["embed"][tokens]
    for kind, p in zip(cfg["layer_types"], params["blocks"]):
        x = jax.checkpoint(lambda x, p, kind=kind: _layer(cfg, kind, x, p, mm))(x, p)
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
    # compile the whole backward pass again
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
    # pass needs their 6.5 GB
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
