"""Plain reference of `granite4_h_micro_1period`: the hybrid state-space /
attention decoder (`model_type: granitemoehybrid`, no routed experts) as
ibm-granite/granite-4.0-h-micro configures it, one period of its layer
pattern and an eighth of its tied vocabulary. Float32 jax.numpy, every
product at precision "highest", nothing of the program imported.

Per token row (all norms RMS with gain, eps `rms_norm_eps`):

    x = embed[tokens] * embedding_multiplier
    per layer, in `layer_types` order:
        x = x + residual_multiplier * mixer(norm(x))
        x = x + residual_multiplier * mlp(norm(x))
    logits = norm(x) embed^T / logits_scaling

mlp(n) = (silu(n W_in[:, :F]) * (n W_in[:, F:])) W_out. Attention mixer: q
(32 heads of 64), k and v (8 heads of 64, each repeated to four query
heads), NO positional term, causal softmax(q k^T * attention_multiplier) v,
W_o. Mamba-2 mixer: [z | xBC | dt] = n W_in; xBC = silu(causal depthwise
conv, 4 taps a channel, + bias); [x | B | C] = xBC (B, C shared by the heads
of a group); dt = softplus(dt + dt_bias); A = -exp(A_log); per head

    S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T        y_t = S_t C_t + D x_t

y = norm_over_d_inner(y * silu(z)) * g; W_out. D is the leaf `skip/scale`
(the scale of the skip from x to y), so that benchmark/weights.py, whose
rule knows a vector by its name, makes it 1 as the source initialises it.
Loss: mean next-token NLL over the vocabulary slice held.

The state-space layer is computed BY ITS DEFINITION, never by chunks:
`_ssm_recurrence` is the recurrence token by token (a lax.scan over t; up
to RECURRENCE_UP_TO tokens, the tests' sizes), `_ssm_dual` the same
definition unrolled over the whole sequence,

    y_i = sum_(j<=i) exp(sum_(j<k<=i) dt_k A) (C_i . B_j) dt_j x_j + D x_i,

a block of QUERY_BLOCK rows i at a time against every j (a [rows, T]
float32 matrix a head), for the chip's 8,192 tokens where the recurrence's
backward pass would keep 8,192 states. tests/test_ssm_hybrid.py holds the
two forms to each other.

Departures from the published description, all of evaluation and none of
the mathematics: (1) in the dual form the exponent sum_(j<k<=i) dt_k A is
the difference of two sums that both START AT THE BLOCK'S FIRST ROW (one
running forwards, one backwards), not of two cumulative sums from the
sequence's start: those reach -6,500 at these weights and their float32
difference would carry an error of 1e-3 into every decay; (2) the taps of
the conv are stored [taps, channels]; (3) no clamp of dt (`time_step_limit`
is (0, inf) in the source).

Sized to run beside its own state (772 M parameters: 2.9 GiB a copy): one
row of `seq_len` tokens at a time, the first row's gradient is the sum's
first term (no zero tree), Adam's moments wait on the host between
updates, each layer rematerialised, attention and the dual form a block of
queries at a time.

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
QUERY_BLOCK = 256
RECURRENCE_UP_TO = 512


def _sizes(cfg: dict):
    h, p = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    g, n = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    return h, p, g, n, h * p, h * p + 2 * g * n     # ..., d_inner, conv_dim


def param_shapes(cfg: dict) -> dict:
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    d, v, f = int(cfg["hidden_size"]), int(cfg["vocab_size"]), int(cfg["shared_intermediate_size"])
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    hd = d // hq
    h, _, _, _, inner, conv = _sizes(cfg)
    blocks = []
    for kind in cfg["layer_types"]:
        blk = {"ln1": S(d)}
        if kind == "mamba":
            blk.update(in_proj=S(d, inner + conv + h), conv_w=S(int(cfg["mamba_d_conv"]), conv),
                       conv_b=S(conv), dt_bias=S(h), a_log=S(h), skip={"scale": S(h)},
                       norm={"scale": S(inner)}, out_proj=S(inner, d))
        else:
            blk.update(wq=S(d, hq * hd), wk=S(d, hkv * hd), wv=S(d, hkv * hd), wo=S(hq * hd, d))
        blk.update(ln2=S(d), mlp={"w_in": S(d, 2 * f), "w_out": S(f, d)})
        blocks.append(blk)
    assert len(blocks) == int(cfg["num_hidden_layers"])
    return {"embed": S(v, d), "blocks": blocks, "out_norm": S(d)}


def _mm(operand):
    def cast(a):
        return a if operand is None else a.astype(operand).astype(jnp.float32)

    def mm(a, b, spec):
        return jnp.einsum(spec, cast(a), cast(b), precision=HI)

    return mm


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _ssm_recurrence(x, dt, a, b, c, mm):
    """x [T, G, R, P], dt [T, G, R], a [G, R], b and c [T, G, N] -> S_t C_t."""
    def turn(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t * a)[..., None, None] * s + mm(x_t * dt_t[..., None], b_t, "grp,gn->grpn")
        return s, mm(s, c_t, "grpn,gn->grp")

    _, y = lax.scan(turn, jnp.zeros(x.shape[1:] + (b.shape[-1],), jnp.float32), (x, dt, b, c))
    return y


def _ssm_dual(x, dt, a, b, c, mm):
    """The same, unrolled over the sequence: rows [start, start + bq) of
    y_i = sum_(j<=i) exp(sum_(j<k<=i) dt_k A) (C_i . B_j) dt_j x_j."""
    t = x.shape[0]
    bq = min(QUERY_BLOCK, t)
    assert t % bq == 0
    log_decay = dt * a                                                # [T, G, R]
    xdt = x * dt[..., None]
    pos = jnp.arange(t)

    @jax.checkpoint
    def rows(start):
        # signed[k] = sum_(start<k'<=k) for k >= start, -sum_(k<k'<=start) before it
        after = jnp.cumsum(jnp.where((pos > start)[:, None, None], log_decay, 0.0), axis=0)
        upto = jnp.where((pos <= start)[:, None, None], log_decay, 0.0)
        before = jnp.flip(jnp.cumsum(jnp.flip(upto, 0), axis=0), 0) - upto
        signed = after - before
        i = start + jnp.arange(bq)
        seg = lax.dynamic_slice_in_dim(signed, start, bq)[:, None] - signed[None]   # [bq, T, G, R]
        keep = (pos[None, :] <= i[:, None])[:, :, None, None]
        weight = jnp.exp(jnp.where(keep, seg, -jnp.inf))
        cb = mm(lax.dynamic_slice_in_dim(c, start, bq), b, "qgn,kgn->qkg")
        return mm(cb[..., None] * weight, xdt, "qkgr,kgrp->qgrp")

    return lax.map(rows, jnp.arange(0, t, bq)).reshape(x.shape)


def _mamba(cfg, n, p, mm):
    h, hp, g, ns, inner, conv = _sizes(cfg)
    t = n.shape[0]
    proj = mm(n, p["in_proj"], "td,de->te")
    z, xbc, dt = proj[:, :inner], proj[:, inner:inner + conv], proj[:, inner + conv:]
    taps = p["conv_w"].shape[0]
    padded = jnp.pad(xbc, [(taps - 1, 0), (0, 0)])
    xbc = jax.nn.silu(sum(padded[i:i + t] * p["conv_w"][i] for i in range(taps)) + p["conv_b"])
    x = xbc[:, :inner].reshape(t, g, h // g, hp)
    b = xbc[:, inner:inner + g * ns].reshape(t, g, ns)
    c = xbc[:, inner + g * ns:].reshape(t, g, ns)
    dt = jax.nn.softplus(dt + p["dt_bias"]).reshape(t, g, h // g)
    a = -jnp.exp(p["a_log"]).reshape(g, h // g)
    ssm = _ssm_recurrence if t <= RECURRENCE_UP_TO else _ssm_dual
    y = ssm(x, dt, a, b, c, mm) + p["skip"]["scale"].reshape(g, h // g)[:, :, None] * x
    y = _rms(y.reshape(t, inner) * jax.nn.silu(z), p["norm"]["scale"], float(cfg["rms_norm_eps"]))
    return mm(y, p["out_proj"], "te,ed->td")


def _attention(cfg, n, p, mm):
    t, d = n.shape
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    hd = d // hq
    q = mm(n, p["wq"], "td,de->te").reshape(t, hq, hd)
    k = jnp.repeat(mm(n, p["wk"], "td,de->te").reshape(t, hkv, hd), hq // hkv, axis=1)
    v = jnp.repeat(mm(n, p["wv"], "td,de->te").reshape(t, hkv, hd), hq // hkv, axis=1)
    scale = float(cfg["attention_multiplier"])
    bq = min(QUERY_BLOCK, t)
    assert t % bq == 0

    @jax.checkpoint
    def block(q_blk, start):
        s = mm(q_blk, k, "qhd,khd->hqk") * scale
        keep = jnp.arange(t)[None, :] <= (start + jnp.arange(bq))[:, None]
        s = jnp.where(keep[None], s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), v, "hqk,khd->qhd")

    o = lax.map(lambda a: block(*a), (q.reshape(t // bq, bq, hq, hd), jnp.arange(0, t, bq)))
    return mm(o.reshape(t, hq * hd), p["wo"], "te,ed->td")


def _mlp(n, w, mm):
    hidden = mm(n, w["w_in"], "td,df->tf")
    f = hidden.shape[-1] // 2
    return mm(jax.nn.silu(hidden[:, :f]) * hidden[:, f:], w["w_out"], "tf,fd->td")


def _layer(cfg, x, p, mm):
    eps, res = float(cfg["rms_norm_eps"]), float(cfg["residual_multiplier"])
    mixer = _mamba if "in_proj" in p else _attention
    x = x + res * mixer(cfg, _rms(x, p["ln1"], eps), p, mm)
    return x + res * _mlp(_rms(x, p["ln2"], eps), p["mlp"], mm)


def logits_fn(cfg: dict, params, tokens, operand=None):
    """tokens int32 [T] (one row) -> logits [T, vocab held]."""
    mm = _mm(operand)
    x = params["embed"][tokens] * float(cfg["embedding_multiplier"])
    for p in params["blocks"]:
        x = jax.checkpoint(lambda x, p: _layer(cfg, x, p, mm))(x, p)
    n = _rms(x, params["out_norm"], float(cfg["rms_norm_eps"]))
    return mm(n, params["embed"], "td,vd->tv") / float(cfg["logits_scaling"])


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

    @partial(jax.jit, donate_argnums=(0,))
    def accumulate(gsum, p, row):
        g, loss = first(p, row)
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
    # Adam's moments wait on the host between updates: a row's gradient
    # pass needs their 5.8 GiB
    m = v = None
    losses, grad_norms = [], None
    for s in range(n_steps):
        batch = tokens[rows[s]]
        count = batch.shape[0] * (batch.shape[1] - 1)
        gsum, lsum = None, 0.0
        for row in batch:
            gsum, l = first(p, jnp.asarray(row)) if gsum is None \
                else accumulate(gsum, p, jnp.asarray(row))
            lsum = lsum + float(l)
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
