"""Plain reference of `smallthinker_21b_a3b_ep4`: the sliding-window / global
grouped-query attention decoder whose experts are routed from the ATTENTION's
input (`model_type: smallthinker`) as PowerInfer/SmallThinker-21BA3B-Instruct
configures it, on one chip's share of a 4-chip expert-parallel layer. Float32
jax.numpy, every product at precision "highest", nothing of the program
imported.

Per token row x [T, D], all norms RMS with gain (eps `rms_norm_eps`); H =
`num_attention_heads` query heads over `num_key_value_heads` key/value heads
of `head_dim` d in every layer; layer l is sliding where
`sliding_window_layout[l]` is 1 and rotates where `rope_layout[l]` is 1:

    n = norm_1(x);  route from n;  h = x + Attn(n)
    y = h + Experts(norm_2(h), route);  logits = norm(x_L) W_head

Route: r = n W_r over all `moe_num_primary_experts`; the
`moe_num_active_primary_experts` largest LOGITS chosen; weights softmax over
those chosen logits alone. No bias, no scaling. The router reads the block's
first norm (the attention's input), the experts the second.

Attn: q = n W_q [T, H, d]; k = n W_k, v = n W_v [T, kv, d]. Where the layer
rotates: rotary on q and k over all d dims, rotate-half layout (pairs (j, j +
d/2)), angle pos * `rope_theta`^(-2j/d); where it does not, q and k are used
as they are (no positions). Query head h reads key/value head h // (H / kv).
s_ij = q_i . k_j / sqrt(d); the keys seen are j <= i, or i -
`sliding_window_size` < j <= i in a sliding layer, a dense [queries, T]
comparison a block of queries at a time; softmax over the seen keys; W_o. No
gate, no q/k norm, no bias.

Experts: the sum over the chosen experts HELD HERE (ids expert_offset ..
+experts_held-1) of weight * (relu(m W_gate,e) * m W_up,e) W_down,e,
`moe_ffn_hidden_size` wide. There is no shared expert and no dense layer.
What the absent experts would add is left out and the partial result goes on,
as on one chip of the deployment. Every held expert is applied to every token
and weighted (zero where not chosen). Loss: mean next-token NLL over the
vocabulary slice held.

Sized to run beside its own state (559 M parameters: 2.2 GB a copy): one row
of `seq_len` tokens at a time through ONE gradient program, Adam's moments
wait on the host between updates, each layer rematerialised, attention a block
of queries at a time ([28, 16384, 16384] scores never exist), the held experts
one at a time.

`operand` is the control's switch: "float8_e4m3fn" rounds both operands of
every product to 8-bit floats first, the nearest precision below the bfloat16
the configuration computes in.
"""

from __future__ import annotations

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
    holds them stacked, and so does `routed_experts` below."""
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    h, kv, hd = (int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
                 int(cfg["head_dim"]))
    f = int(cfg["moe_ffn_hidden_size"])
    blocks = [{"ln1": S(d), "wq": S(d, h * hd), "wk": S(d, kv * hd), "wv": S(d, kv * hd),
               "wo": S(h * hd, d), "ln2": S(d),
               "router": S(d, int(cfg["moe_num_primary_experts"])),
               "experts": [{"w_gate": S(d, f), "w_up": S(d, f), "w_down": S(f, d)}
                           for _ in range(int(cfg["experts_held"]))]}
              for _ in range(int(cfg["num_hidden_layers"]))]
    return {"embed": S(v, d), "blocks": blocks, "out_norm": S(d), "head": S(d, v)}


def _mm(operand):
    def cast(a):
        return a if operand is None else a.astype(operand).astype(jnp.float32)

    def mm(a, b, spec):
        return jnp.einsum(spec, cast(a), cast(b), precision=HI)

    return mm


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _rotate(x, theta: float):
    """x [T, H, d]: the pairs (j, j + d/2) turned by pos * theta^(-2j/d)."""
    t, _, d = x.shape
    f = np.array([theta ** (-2.0 * j / d) for j in range(d // 2)])
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(f, jnp.float32)[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(cfg, sliding: int, rotary: int, n, p, mm):
    h, kv, d = (int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
                int(cfg["head_dim"]))
    t = n.shape[0]
    group = h // kv
    q = mm(n, p["wq"], "td,de->te").reshape(t, h, d)
    k = mm(n, p["wk"], "td,de->te").reshape(t, kv, d)
    v = mm(n, p["wv"], "td,de->te").reshape(t, kv, d)
    if rotary:
        q, k = _rotate(q, float(cfg["rope_theta"])), _rotate(k, float(cfg["rope_theta"]))
    window = int(cfg["sliding_window_size"]) if sliding else None
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
    return mm(o.reshape(t, h * d), p["wo"], "te,ed->td")


def route_weights(cfg, n, p, mm):
    """[T, held]: the weight of each held expert for each token, zero where
    it was not among the token's chosen. n: the rows the ROUTER reads."""
    k, held, off = (int(cfg["moe_num_active_primary_experts"]), int(cfg["experts_held"]),
                    int(cfg.get("expert_offset", 0)))
    top, idx = lax.top_k(mm(n, p["router"], "td,de->te"), k)
    w = jax.nn.softmax(top, axis=-1)
    return jnp.sum(jnp.where(idx[:, :, None] == off + jnp.arange(held)[None, None],
                             w[:, :, None], 0.0), axis=1)


def _reglu(m, w, mm):
    return mm(jax.nn.relu(mm(m, w["w_gate"], "td,df->tf")) * mm(m, w["w_up"], "td,df->tf"),
              w["w_down"], "tf,fd->td")


def routed_experts(m, weights, p, mm):
    """The held experts' weighted sum [T, D] on the rows m the EXPERTS read,
    by `route_weights`' [T, held]."""
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *p["experts"])

    def one(acc, ew):
        expert, col = ew
        return acc + col[:, None] * _reglu(m, expert, mm), None

    routed, _ = lax.scan(jax.checkpoint(one), jnp.zeros_like(m), (stacked, weights.T))
    return routed


def _layer(cfg, sliding, rotary, x, p, mm):
    eps = float(cfg["rms_norm_eps"])
    n = _rms(x, p["ln1"], eps)
    weights = route_weights(cfg, n, p, mm)
    x = x + _attention(cfg, sliding, rotary, n, p, mm)
    return x + routed_experts(_rms(x, p["ln2"], eps), weights, p, mm)


def logits_fn(cfg: dict, params, tokens, operand=None):
    """tokens int32 [T] (one row) -> logits [T, vocab held]."""
    mm = _mm(operand)
    x = params["embed"][tokens]
    for sliding, rotary, p in zip(cfg["sliding_window_layout"], cfg["rope_layout"],
                                  params["blocks"]):
        x = jax.checkpoint(lambda x, p, s=int(sliding), r=int(rotary): _layer(cfg, s, r, x, p, mm))(
            x, p)
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
    # pass needs their 4.5 GB
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
