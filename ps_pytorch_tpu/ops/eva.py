"""EVA attention: exact softmax inside a window, one learned summary a chunk
of every window before it, ONE softmax over both.

The mixer of `model_type: evabyte` configs (models/eva_dense.py), after EVA
(Zheng et al., ICLR 2023, arXiv:2302.04542: a chunk of keys stands before a
far query as one pooled key, its values as their softmax-pooled mean) with
the learned per-head `phi` and `mu` of the EvaByte release in the place of
the sampled feature and the chunk's mean. For queries, keys and values
[B, T, H, D], a window of `window` positions and chunks of `chunk` (which
divides it), with s the score scale:

    chunk j = positions chunk*j .. chunk*j + chunk - 1
      a[j, m] = softmax_m( s * k[chunk*j + m] . phi_h )       float32
      k~[j] = sum_m a[j, m] k[chunk*j + m] + mu_h
      v~[j] = sum_m a[j, m] v[chunk*j + m]
    query i, of window w = i // window:
      keys { k[t] : window*w <= t <= i }  U  { k~[j] : j // (window/chunk) < w }
      o[i] = softmax over that union of (s * q[i] . key), applied to the
             matching { v[t] } U { v~[j] }

A query of window 0 sees no summary; a summary never stands for a position
of the query's own window or of a later one. T that is no multiple of the
window pads on the right: a part-filled last chunk pools its real positions
only, and nothing sees a padded key (the causal mask inside the window; the
last window's summaries are seen by no query).

On the chip the two key sets are two passes of the flash kernels
(ops/flash_attention.py) and no [T, T] or [T, T / chunk] array reaches HBM
in either direction:

- `pool` (eva_pool): one XLA fusion that reads k and v once and writes a
  `chunk`-th of them, differentiated by jax;
- `local`: the causal partial-triple kernel on the windows folded into the
  leading axis, [B*H*W, window, D];
- `remote`: the same kernel over [B*H, T, D] queries and [B*H, T / chunk, D]
  pooled keys under the mask kind EarlierWindows, live tiles only;
- `merge`: the two triples (pv, m, l) joined by their statistics as a ring
  joins its hops (parallel/ring_attention._merge_triple), then normalized
  once. The backward runs flash_grads_partial twice with the MERGED lse
  and delta, so the two passes' gradients sum to the one softmax's.

Under `remat` the merged o and lse are residuals by name (EVA_SAVED) and q,
k, v, k~, v~ operands by name (EVA_OPERANDS): models/transformer.remat_block
decides what is kept as for the flash and delta-rule kernels, and the
forward run again holds neither kernel pass where o and lse were kept.
Off-TPU without PS_TPU_PALLAS_INTERPRET (ops/pallas_mode.py), and for
`impl="naive"`, `eva_attention` takes its jnp twin, window by window.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..obs.scopes import EVA_LOCAL, EVA_MERGE, EVA_POOL, EVA_REMOTE, scope
from .flash_attention import (
    NEG_INF, EarlierWindows, FlashPlan, SavedLayers, _pad_t, flash_grads_partial, flash_partial,
    plan_flash)
from .pallas_mode import pallas_mode

# what only the two kernel passes and their merge can make
EVA_SAVED = ("ps_eva_o", "ps_eva_lse")
# the passes' operands, folded to [B*H, T, D]: q, k, v and the pooled pair
EVA_OPERANDS = ("ps_eva_q", "ps_eva_k", "ps_eva_v", "ps_eva_kp", "ps_eva_vp")


class EvaPlan(NamedTuple):
    """How one call lays out its T positions (pure, from shapes): `windows`
    of `window` positions each over `t_pad`, `summaries` pooled keys, and
    the two passes' tile plans (`remote` None where there is one window)."""

    window: int       # min(the configured window, T): one window holds a shorter row whole
    windows: int
    t_pad: int
    summaries: int    # t_pad / chunk, rounded up
    per_window: int   # summaries a window
    local: FlashPlan               # one window's causal square
    remote: Optional[FlashPlan]    # [t_pad, summaries] under `mask`
    mask: Optional[EarlierWindows]

    def tiles(self):
        """(local, remote) tiles that do work, a head: plan_flash's counts."""
        return (self.windows * self.local.tiles_run,
                self.remote.tiles_run if self.remote else 0)


def plan_eva(t: int, d: int, dtype, window: int, chunk: int) -> EvaPlan:
    if window % chunk:
        raise ValueError(f"chunk {chunk} does not divide window {window}")
    if t <= window:
        # one window: no summary is seen; the row need not fill the window
        return EvaPlan(t, 1, t, -(-t // chunk), window // chunk,
                       plan_flash(t, t, d, dtype, True), None, None)
    windows = -(-t // window)
    t_pad, per_window = windows * window, window // chunk
    mask = EarlierWindows(window, per_window)
    return EvaPlan(window, windows, t_pad, t_pad // chunk, per_window,
                   plan_flash(window, window, d, dtype, True),
                   plan_flash(t_pad, t_pad // chunk, d, dtype, mask), mask)


def eva_saves(b: int, t: int, h: int, d: int, dtype, window: int, chunk: int,
              layers: int) -> SavedLayers:
    """What _eva_vjp_fwd names in `layers` layers that call eva_attention
    with q, k, v [b, t, h, d]."""
    plan, sds, bh = plan_eva(t, d, dtype, window, chunk), jax.ShapeDtypeStruct, b * h
    full, pooled = sds((bh, plan.t_pad, d), dtype), sds((bh, plan.summaries, d), dtype)
    return SavedLayers(
        layers,
        dict(zip(EVA_SAVED, (full, sds((bh, plan.t_pad), jnp.float32)))),
        dict(zip(EVA_OPERANDS, (full, full, full, pooled, pooled))))


# ------------------------------------------------------------------ pooling


def eva_pool(k3, v3, phi, mu, chunk: int, scale: float, t_real: int):
    """Chunks of k3, v3 [BH, T, D] (positions from t_real on are padding)
    -> (k~, v~) [BH, ceil(T / chunk), D] in their dtype; phi, mu [BH, D].
    The weights, their softmax and the two sums are float32."""
    f32 = jnp.float32
    bh, t, d = k3.shape
    n = -(-t // chunk)
    chunks = lambda x: jnp.pad(x, [(0, 0), (0, n * chunk - t), (0, 0)]).astype(f32).reshape(
        bh, n, chunk, x.shape[-1])
    kc, vc = chunks(k3), chunks(v3)
    logits = scale * jnp.sum(kc * phi.astype(f32)[:, None, None, :], axis=-1)   # [BH, n, chunk]
    real = (jnp.arange(n * chunk) < t_real).reshape(n, chunk)
    # a wholly padded chunk comes out as mu and 0: finite, and seen by no query
    a = jax.nn.softmax(jnp.where(real, logits, NEG_INF), axis=-1) * real
    kp = jnp.sum(a[..., None] * kc, axis=2) + mu.astype(f32)[:, None, :]
    vp = jnp.sum(a[..., None] * vc, axis=2)
    return kp.astype(k3.dtype), vp.astype(v3.dtype)


# ------------------------------------------- the two passes and their merge


def _by_window(x, windows: int):
    """[BH, W * n, ...] -> [BH * W, n, ...]: each window a row of its own."""
    return x.reshape((x.shape[0] * windows, x.shape[1] // windows) + x.shape[2:])


def _whole(x, windows: int):
    return x.reshape((x.shape[0] // windows, x.shape[1] * windows) + x.shape[2:])


def _forward(q3, k3, v3, kp3, vp3, scale, plan: EvaPlan):
    """(o [BH, T, D] in q3's dtype, lse f32 [BH, T], mass f32 [BH, T]): the
    merged output, its logsumexp, and the share of each query's softmax
    that lies on summaries."""
    from ..parallel.ring_attention import _merge_triple

    w = plan.windows
    with scope(EVA_LOCAL):
        local = tuple(_whole(x, w) for x in flash_partial(
            _by_window(q3, w), _by_window(k3, w), _by_window(v3, w), scale, True, 0, 0))
    if plan.remote is None:
        pv, m, l = local
        remote_l = jnp.zeros_like(l)
    else:
        with scope(EVA_REMOTE):
            remote = flash_partial(q3, kp3, vp3, scale, plan.mask, 0, 0)
        with scope(EVA_MERGE):
            pv, m, l = _merge_triple(local, remote)
            remote_l = remote[2] * jnp.exp(remote[1] - m)
    with scope(EVA_MERGE):
        # every query sees itself, so l > 0
        return (pv / l[..., None]).astype(q3.dtype), m + jnp.log(l), remote_l / l


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _eva(q3, k3, v3, kp3, vp3, scale, plan):
    o, _, mass = _forward(q3, k3, v3, kp3, vp3, scale, plan)
    return o, mass


def _eva_vjp_fwd(q3, k3, v3, kp3, vp3, scale, plan):
    o, lse, mass = _forward(q3, k3, v3, kp3, vp3, scale, plan)
    o, lse = map(checkpoint_name, (o, lse), EVA_SAVED)
    operands = tuple(map(checkpoint_name, (q3, k3, v3, kp3, vp3), EVA_OPERANDS))
    return (o, mass), (*operands, o, lse)


def _eva_vjp_bwd(scale, plan, res, cts):
    q3, k3, v3, kp3, vp3, o3, lse = res
    do3, _ = cts            # the mass is a count, not a value of the model
    f32, w = jnp.float32, plan.windows
    with scope(EVA_MERGE):
        delta = jnp.sum(do3.astype(f32) * o3.astype(f32), axis=-1)
    with scope(EVA_LOCAL):
        dq, dk, dv = (_whole(x, w) for x in flash_grads_partial(
            *(_by_window(x, w) for x in (q3, k3, v3, do3, lse, delta)), scale, True, 0, 0))
    if plan.remote is None:
        dkp, dvp = jnp.zeros_like(kp3), jnp.zeros_like(vp3)
    else:
        with scope(EVA_REMOTE):
            dq_r, dkp, dvp = flash_grads_partial(
                q3, kp3, vp3, do3, lse, delta, scale, plan.mask, 0, 0)
        with scope(EVA_MERGE):
            dq = dq + dq_r
    return (dq.astype(q3.dtype), dk.astype(k3.dtype), dv.astype(v3.dtype),
            dkp.astype(kp3.dtype), dvp.astype(vp3.dtype))


_eva.defvjp(_eva_vjp_fwd, _eva_vjp_bwd)


def _eva_jnp(q3, k3, v3, kp3, vp3, scale, plan: EvaPlan):
    """The twin: per window, the explicit union of its own keys and the
    summaries before it under one softmax ([W, window, window + summaries]
    scores, so for small T only)."""
    f32, w, n = jnp.float32, plan.windows, plan.window
    bh, t, _ = q3.shape
    qw, kw, vw = (x.reshape(bh, w, n, x.shape[-1]) for x in (q3, k3, v3))
    s_local = jnp.einsum("bwqd,bwkd->bwqk", qw, kw, preferred_element_type=f32) * scale
    s_local = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s_local, NEG_INF)
    s_remote = jnp.einsum("bwqd,bjd->bwqj", qw, kp3, preferred_element_type=f32) * scale
    seen = (jnp.arange(kp3.shape[1])[None, :] // plan.per_window) < jnp.arange(w)[:, None]
    s_remote = jnp.where(seen[None, :, None, :], s_remote, NEG_INF)
    p = jax.nn.softmax(jnp.concatenate([s_local, s_remote], axis=-1), axis=-1)
    o = (jnp.einsum("bwqk,bwkd->bwqd", p[..., :n].astype(v3.dtype), vw,
                    preferred_element_type=f32)
         + jnp.einsum("bwqj,bjd->bwqd", p[..., n:].astype(v3.dtype), vp3,
                      preferred_element_type=f32))
    return o.reshape(bh, t, -1).astype(q3.dtype), jnp.sum(p[..., n:], axis=-1).reshape(bh, t)


# --------------------------------------------------------------- public API


def eva_attention(
    q: jax.Array,    # [B, T, H, D]
    k: jax.Array,
    v: jax.Array,
    phi: jax.Array,  # [H, D]
    mu: jax.Array,   # [H, D]
    window: int,
    chunk: int,
    scale: Optional[float] = None,
    impl: str = "flash",
):
    """-> (o [B, T, H, D], counts): the attention of the module docstring,
    differentiable in all five arrays. `counts` holds what the step counts
    of it: `mass_sum` (float32: the softmax mass on summaries, summed over
    the queries past window 0) beside their number `mass_queries`."""
    from ..parallel.ring_attention import _fold_heads, _unfold_heads

    b, t, h, d = q.shape
    scale = float(d ** -0.5 if scale is None else scale)
    plan = plan_eva(t, d, q.dtype, window, chunk)
    q3, k3, v3 = (_pad_t(_fold_heads(x), plan.t_pad) for x in (q, k, v))
    with scope(EVA_POOL):
        a_head = lambda x: jnp.tile(x, (b, 1))        # row b * H + h is head h
        kp3, vp3 = eva_pool(k3, v3, a_head(phi), a_head(mu), chunk, scale, t)
    if impl == "flash" and pallas_mode() is not None:
        o3, mass = _eva(q3, k3, v3, kp3, vp3, scale, plan)
    elif impl in ("flash", "naive"):
        with jax.named_scope("ps_eva_jnp"):
            o3, mass = _eva_jnp(q3, k3, v3, kp3, vp3, scale, plan)
    else:
        raise ValueError(f"unknown attention_impl {impl!r}")
    far = max(t - plan.window, 0)                     # queries past window 0
    counts = {
        "mass_sum": jnp.sum(jax.lax.stop_gradient(mass)[:, plan.window:t], dtype=jnp.float32),
        "mass_queries": jnp.float32(b * h * far),
    }
    return _unfold_heads(o3[:, :t], b, h), counts
