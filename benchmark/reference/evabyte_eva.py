"""Plain reference of `evabyte_6b5_4layers`: the byte-level decoder with
EVA attention (`model_type: evabyte`) as EvaByte/EvaByte configures it, the
first four of its 32 layers with the embedding, the final norm and the
eight byte-prediction heads. Float32 jax.numpy, every product at precision
"highest", nothing of the program imported.

Per row of T bytes (s = 128^-0.5; every norm rms(x) * g with g = 1 + w, the
leaf holding g; eps `rms_norm_eps`):

    x = embed[bytes]
    per layer:
        n = norm(x);  q, k, v = n W_q, n W_k, n W_v       32 heads of 128 each
        q, k = rope(q), rope(k)        whole head, halves layout, theta 1e5
        chunk j = bytes 16j .. 16j+15:
            a[j, m] = softmax_m( s * k[16j+m] . phi_h )
            k~[j] = sum_m a[j, m] k[16j+m] + mu_h     v~[j] = sum_m a[j, m] v[16j+m]
        query i, window w = i // 2048:
            keys { k[t] : 2048 w <= t <= i }  U  { k~[j] : j // 128 < w }
            o[i] = softmax over that union of (s * q[i] . key) applied to
                   the matching { v[t] } U { v~[j] }
        x = x + o W_o
        x = x + ( silu(norm(x) W_gate) * (norm(x) W_up) ) W_down
    logits[i, p] = norm(x)[i] W_head[:, p]                 p = 0..7, 320 each
    loss = mean over p and the i with i + 1 + p < T of -log softmax(logits[i, p])[byte[i+1+p]]

The attention is computed BY ITS MEANING: for a block of QUERY_BLOCK
queries of one window, the scores against that window's own keys and
against every summary are laid side by side, whatever the definition does
not let the query see is set to -inf, and ONE jax.nn.softmax runs over the
union; a block at a time so that it fits at 16,384 bytes ([32, 512, 2048 +
1024] float32 scores). The row is padded on the right to whole windows: a
padded key lies after every real query of its window, a part-filled chunk
pools its real bytes only, and the padded queries are dropped.

Assumed where the config is silent (the configuration's file lists each
with its reason): the pooling's form, which summaries a query sees, the
rotation's layout, the heads' targets and the plain mean.

Sized to run beside its own state (821 M parameters: 3.1 GiB a copy): one
row at a time, Adam's moments wait on the host between updates, each layer
rematerialised.

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


def _sizes(cfg: dict):
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return d, h, d // h, int(cfg["window_size"]), int(cfg["chunk_size"])


def param_shapes(cfg: dict) -> dict:
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    d, h, hd, _, _ = _sizes(cfg)
    f, v, heads = int(cfg["intermediate_size"]), int(cfg["vocab_size"]), int(cfg["num_pred_heads"])
    block = lambda: {
        "ln1": S(d), "wq": S(d, d), "wk": S(d, d), "wv": S(d, d), "wo": S(d, d),
        "phi": S(h, hd), "mu": S(h, hd), "ln2": S(d),
        "mlp": {"w_gate": S(d, f), "w_up": S(d, f), "w_down": S(f, d)}}
    return {"embed": S(v, d), "blocks": [block() for _ in range(int(cfg["num_hidden_layers"]))],
            "out_norm": S(d), "head": S(d, heads * v)}


def _mm(operand):
    def cast(a):
        return a if operand is None else a.astype(operand).astype(jnp.float32)

    def mm(a, b, spec):
        return jnp.einsum(spec, cast(a), cast(b), precision=HI)

    return mm


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _rope(x, theta: float):
    """x [T, H, d] at positions 0..T-1: x * cos + rotate_half(x) * sin."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + half * sin


def _pool(k, v, phi, mu, chunk, scale, t_real, mm):
    """k, v [T, H, d] (T a multiple of chunk; bytes from t_real on are
    padding) -> the summaries k~, v~ [T / chunk, H, d]."""
    t, h, d = k.shape
    kc, vc = k.reshape(t // chunk, chunk, h, d), v.reshape(t // chunk, chunk, h, d)
    real = (jnp.arange(t) < t_real).reshape(t // chunk, chunk, 1)
    weight = scale * mm(kc, phi, "jmhd,hd->jmh")
    # a chunk of padding alone gets finite weights and a zero sum: nothing sees it
    a = jax.nn.softmax(jnp.where(real, weight, -1e30), axis=1) * real
    return mm(a, kc, "jmh,jmhd->jhd") + mu, mm(a, vc, "jmh,jmhd->jhd")


def _attention(cfg, n, p, mm):
    t_real, d = n.shape
    _, h, hd, window, chunk = _sizes(cfg)
    scale, theta = hd ** -0.5, float(cfg["rope_theta"])
    t = -(-t_real // window) * window
    n = jnp.pad(n, [(0, t - t_real), (0, 0)])
    q = _rope(mm(n, p["wq"], "td,de->te").reshape(t, h, hd), theta)
    k = _rope(mm(n, p["wk"], "td,de->te").reshape(t, h, hd), theta)
    v = mm(n, p["wv"], "td,de->te").reshape(t, h, hd)
    kp, vp = _pool(k, v, p["phi"], p["mu"], chunk, scale, t_real, mm)
    bq = min(QUERY_BLOCK, window)
    assert window % bq == 0 and window % chunk == 0
    summary_window = jnp.arange(t // chunk) // (window // chunk)

    @jax.checkpoint
    def block(q_blk, start):
        first = (start // window) * window                 # the block's window starts here
        own_k, own_v = (lax.dynamic_slice_in_dim(a, first, window) for a in (k, v))
        i = start + jnp.arange(bq)
        own = first + jnp.arange(window)[None, :] <= i[:, None]             # causal, in the window
        far = summary_window[None, :] < (i // window)[:, None]              # whole earlier windows
        s = scale * jnp.concatenate([mm(q_blk, own_k, "qhd,khd->hqk"),
                                     mm(q_blk, kp, "qhd,jhd->hqj")], axis=-1)
        s = jnp.where(jnp.concatenate([own, far], axis=-1)[None], s, -jnp.inf)
        prob = jax.nn.softmax(s, axis=-1)                                   # ONE softmax over both
        return mm(prob[..., :window], own_v, "hqk,khd->qhd") + mm(prob[..., window:], vp, "hqj,jhd->qhd")

    o = lax.map(lambda a: block(*a), (q.reshape(t // bq, bq, h, hd), jnp.arange(0, t, bq)))
    return mm(o.reshape(t, h * hd)[:t_real], p["wo"], "te,ed->td")


def _mlp(n, w, mm):
    gate, up = mm(n, w["w_gate"], "td,df->tf"), mm(n, w["w_up"], "td,df->tf")
    return mm(jax.nn.silu(gate) * up, w["w_down"], "tf,fd->td")


def _layer(cfg, x, p, mm):
    eps = float(cfg["rms_norm_eps"])
    x = x + _attention(cfg, _rms(x, p["ln1"], eps), p, mm)
    return x + _mlp(_rms(x, p["ln2"], eps), p["mlp"], mm)


def logits_fn(cfg: dict, params, tokens, operand=None):
    """tokens int32 [T] (one row) -> logits [T, num_pred_heads, vocab]."""
    mm = _mm(operand)
    x = params["embed"][tokens]
    for p in params["blocks"]:
        x = jax.checkpoint(lambda x, p: _layer(cfg, x, p, mm))(x, p)
    n = _rms(x, params["out_norm"], float(cfg["rms_norm_eps"]))
    return mm(n, params["head"], "td,dv->tv").reshape(
        tokens.shape[0], int(cfg["num_pred_heads"]), int(cfg["vocab_size"]))


def targets(tokens, heads: int):
    """(the byte at i + 1 + p for every i and head p [T, heads], whether it
    lies in the row)."""
    t = tokens.shape[0]
    at = jnp.arange(t)[:, None] + 1 + jnp.arange(heads)[None, :]
    return tokens[jnp.minimum(at, t - 1)], at < t


def nll_sum(cfg: dict, params, tokens, operand=None):
    """Sum over the heads p and the positions i with i + 1 + p < T of one
    row's -log p(byte[i + 1 + p])."""
    logp = jax.nn.log_softmax(logits_fn(cfg, params, tokens, operand), axis=-1)
    tgt, valid = targets(tokens, int(cfg["num_pred_heads"]))
    return -jnp.sum(jnp.where(valid, jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0], 0.0))


def train_steps(cfg: dict, traffic: dict, make_params, feed: dict,
                n_steps: int = 3, operand=None):
    """Follows the first `n_steps` steps on feed["tokens"][feed["rows"][s]]
    from the weights `make_params()` gives (PyTorch-form Adam at the
    constant rate `lr`). Returns losses, the first gradient's norm per leaf
    and the norm of the parameters' change per leaf."""
    from benchmark.weights import leaf_names, leaf_norms

    lr, b1, b2, eps = (float(traffic[k]) for k in ("lr", "b1", "b2", "eps"))
    operand = None if operand is None else jnp.dtype(operand)
    heads = int(cfg["num_pred_heads"])

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
    # pass needs their 6.1 GiB
    m = v = None
    losses, grad_norms = [], None
    for s in range(n_steps):
        batch = tokens[rows[s]]
        t = batch.shape[1]
        count = batch.shape[0] * sum(max(t - 1 - h, 0) for h in range(heads))
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
