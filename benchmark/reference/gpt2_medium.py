"""Plain reference of `gpt2_medium`: a decoder-only transformer at the sizes
of openai-community/gpt2-medium with the departures the configuration file
lists (RMS norm without bias, no linear biases, tanh-approximated GELU as
jax.nn.gelu gives it, output head tied to the token embedding). Forward,
next-token loss over positions 0..T-2, gradients and the PyTorch-form Adam,
in float32 jax.numpy with every product at precision "highest". Rows are
independent, so the batch is taken in blocks of rows and the gradients
summed; each layer is rematerialised, so a block fits beside the optimizer,
and the layers run as one scan over their stacked weights.

`operand` is the control's switch: "float8_e4m3fn" rounds both operands of
every product to 8-bit floats first, which is the nearest precision below
the bfloat16 the configuration computes in.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST


def param_shapes(cfg: dict) -> dict:
    S = jax.ShapeDtypeStruct
    f32 = jnp.float32
    d, v = int(cfg["n_embd"]), int(cfg["vocab_size"])
    m = d * int(cfg["mlp_ratio"])
    blk = lambda: {
        "ln1": S((d,), f32), "wqkv": S((d, 3 * d), f32), "wo": S((d, d), f32),
        "ln2": S((d,), f32), "w_up": S((d, m), f32), "w_down": S((m, d), f32),
    }
    return {
        "embed": S((v, d), f32),
        "pos_embed": S((int(cfg["n_positions"]), d), f32),
        "blocks": [blk() for _ in range(int(cfg["n_layer"]))],
        "out_norm": S((d,), f32),
    }


def _mm(operand):
    def cast(a):
        return a if operand is None else a.astype(operand).astype(jnp.float32)

    def mm(a, b, spec):
        return jnp.einsum(spec, cast(a), cast(b), precision=HI)

    return mm


def _rms(x, g, eps=1e-6):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _layer(x, p, heads: int, mm):
    b, t, d = x.shape
    hd = d // heads
    h = _rms(x, p["ln1"])
    qkv = mm(h, p["wqkv"], "btd,de->bte")
    q, k, v = (a.reshape(b, t, heads, hd) for a in jnp.split(qkv, 3, axis=-1))
    s = mm(q, k, "bqhd,bkhd->bhqk") / jnp.sqrt(jnp.float32(hd))
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask[None, None], s, -jnp.inf)
    o = mm(jax.nn.softmax(s, axis=-1), v, "bhqk,bkhd->bqhd").reshape(b, t, d)
    x = x + mm(o, p["wo"], "btd,de->bte")
    h = _rms(x, p["ln2"])
    return x + mm(jax.nn.gelu(mm(h, p["w_up"], "btd,dm->btm")), p["w_down"], "btm,md->btd")


def logits_fn(cfg: dict, params, tokens, operand=None):
    mm = _mm(operand)
    t = tokens.shape[1]
    x = params["embed"][tokens] + params["pos_embed"][:t][None]
    # the layers are alike, so one scan over their stacked weights: the
    # compiler sees one layer, not twenty-four (minutes and megabytes less)
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *params["blocks"])
    layer = jax.checkpoint(lambda x, p: _layer(x, p, int(cfg["n_head"]), mm))
    x, _ = lax.scan(lambda x, p: (layer(x, p), None), x, stacked)
    return mm(_rms(x, params["out_norm"]), params["embed"], "btd,vd->btv")


def nll_sum(cfg: dict, params, tokens, operand=None):
    """Sum over rows and positions 0..T-2 of the next token's -log p."""
    logp = jax.nn.log_softmax(logits_fn(cfg, params, tokens, operand)[:, :-1], axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def train_steps(cfg: dict, traffic: dict, make_params, feed: dict,
                n_steps: int = 3, operand=None, block_rows: int = 2):
    """Follows the first `n_steps` steps on feed["tokens"][feed["rows"][s]]
    from the weights `make_params()` gives. Returns losses, the first
    gradient's norm per leaf and the norm of the parameters' change per
    leaf."""
    from benchmark.weights import leaf_names, leaf_norms

    lr, b1, b2, eps = (float(traffic[k]) for k in ("lr", "b1", "b2", "eps"))
    operand = None if operand is None else jnp.dtype(operand)
    grad_block = jax.jit(jax.value_and_grad(
        lambda p, tok: nll_sum(cfg, p, tok, operand)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))

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
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, grad_norms = [], None
    for s in range(n_steps):
        batch = tokens[rows[s]]
        count = batch.shape[0] * (batch.shape[1] - 1)
        gsum, lsum = None, 0.0
        for r in range(0, batch.shape[0], block_rows):
            l, g = grad_block(p, jnp.asarray(batch[r:r + block_rows]))
            lsum = lsum + l
            gsum = g if gsum is None else add(gsum, g)
        losses.append(float(lsum) / count)
        p, m, v, gn = update(p, m, v, gsum, jnp.float32(count), jnp.float32(s + 1))
        if s == 0:
            grad_norms = np.asarray(gn).tolist()
    del m, v, gsum
    dparam = np.asarray(change(p, make_params())).tolist()
    return {"loss": losses, "grad_norms": grad_norms, "dparam_norms": dparam,
            "leaf_names": leaf_names(p)}
