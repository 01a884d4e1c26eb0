"""The state-space scan of a Mamba-2 layer, in its chunked (state-space
duality) form: the recurrence over the sequence as matrix products.

Per head h, with x_t in R^P, B_t and C_t in R^N (shared by the heads of a
group), dt_t > 0, A_h < 0 and a state S in R^(P x N):

    S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T        y_t = S_t C_t + D x_t

`ssd_recurrence` is that definition, token by token (a lax.scan of T turns;
the tests' twin). `ssd_chunked` computes the same y in chunks of Q tokens:

- within a chunk, Y_diag = (L o (C B^T)) (dt x), L[i, j] = exp(sum_(j<k<=i)
  dt_k A) for j <= i and 0 above the diagonal;
- each chunk's end state from its own inputs, sum_s exp(sum_(s<k<=Q) dt_k A)
  dt_s x_s B_s^T;
- the T/Q chunk states carried from chunk to chunk (`_carry`: a lax.scan
  of T/Q turns), giving the state each chunk starts from;
- Y_off[i] = exp(sum_(k<=i) dt_k A) S_start C_i.

Every decay is exp of a DIFFERENCE of cumulative sums of dt A inside one
chunk, in float32, masked before the exp; never exp(+cumsum) * exp(-cumsum)
(a chunk's log-decay reaches -180 at the widths trained here, and float32
exp underflows at -87). dt, A, the decays and the carried state stay
float32; the products take their operands in x's dtype (the blocks' compute
dtype) and accumulate in float32. Differentiable by jax.grad through the
products. A T that is no multiple of Q is padded with dt = 0: a padded
token leaves the state as it is, adds nothing to it, and its y is cut off.

Plain XLA (`SCAN_PATH`); no kernel yet (PERF.md section 7).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.scopes import SCAN, scope

# A constant, not a selection: there is one scan, in plain XLA, and nothing
# branches on this. models/ssm_hybrid.ssd_plan reports it so that its
# instant reads like kda_plan's, whose `scan_path` (ops/kda.scan_path) IS
# chosen from the shapes.
SCAN_PATH = "xla"
# float32 exp(x) is 0 below this: a chunk whose whole log-decay is under it
# hands nothing of the state it was given to the next chunk
CUT_OFF_LOG = -87.0


def _grouped(a, groups: int):
    """[B, T, H, ...] -> [B, T, G, H/G, ...]: the heads by their group."""
    return a.reshape(a.shape[:2] + (groups, a.shape[2] // groups) + a.shape[3:])


def ssd_recurrence(x, dt, a, b, c, d):
    """The definition, token by token, in float32.

    x [B, T, H, P]; dt [B, T, H] (after softplus); a [H] (negative);
    b, c [B, T, G, N] with G dividing H; d [H]. Returns y [B, T, H, P]."""
    f32 = jnp.float32
    g = b.shape[2]
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    xg, dtg = _grouped(x, g), _grouped(dt, g)                       # [B,T,G,R,P], [B,T,G,R]
    ag = a.astype(f32).reshape(g, -1)
    bsz, _, _, r, p = xg.shape

    def turn(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t * ag)[..., None, None] * s + jnp.einsum(
            "bgrp,bgn->bgrpn", x_t * dt_t[..., None], b_t, precision=lax.Precision.HIGHEST)
        return s, jnp.einsum("bgrpn,bgn->bgrp", s, c_t, precision=lax.Precision.HIGHEST)

    s0 = jnp.zeros((bsz, g, r, p, b.shape[-1]), f32)
    _, y = lax.scan(turn, s0, tuple(jnp.moveaxis(v, 1, 0) for v in (xg, dtg, b, c)))
    y = jnp.moveaxis(y, 0, 1).reshape(x.shape)
    return y + d.astype(f32)[:, None] * x


def _carry(states, total):
    """The state each chunk starts from: states [B, C, G, R, P, N] are the
    chunks' own end states, total [B, C, G, R] their whole log-decays;
    S_start[0] = 0, S_start[c+1] = exp(total[c]) S_start[c] + states[c]."""
    def turn(s, inp):
        own, tot = inp
        return jnp.exp(tot)[..., None, None] * s + own, s

    _, before = lax.scan(turn, jnp.zeros_like(states[:, 0]),
                         (jnp.moveaxis(states, 1, 0), jnp.moveaxis(total, 1, 0)))
    return jnp.moveaxis(before, 0, 1)


@scope(SCAN)
def ssd_chunked(x, dt, a, b, c, d, chunk: int):
    """The chunked scan -> (y float32 [B, T, H, P], cut_off int32): shapes
    as `ssd_recurrence`; the products run in x.dtype. `cut_off` counts the
    (row, chunk, head) whose log-decay over the whole chunk is under
    CUT_OFF_LOG: there the carried state does no work."""
    f32 = jnp.float32
    cd = x.dtype
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    pad = -t % chunk
    if pad:
        widen = lambda v: jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        x, dt, b, c = widen(x), widen(dt), widen(b), widen(c)
    nc = (t + pad) // chunk
    dt = dt.astype(f32)
    chunks = lambda v: v.reshape((bsz, nc, chunk) + v.shape[2:])
    dtc = chunks(_grouped(dt, g))                                    # [B,C,Q,G,R]
    xdt32 = chunks(_grouped(x.astype(f32), g)) * dtc[..., None]      # dt x, [B,C,Q,G,R,P]
    cum = jnp.cumsum(dtc * a.astype(f32).reshape(g, -1), axis=2)     # log-decay from the chunk's start
    cum = jnp.moveaxis(cum, 2, -1)                                   # [B,C,G,R,Q]
    total = cum[..., -1]
    bc, cc = chunks(b), chunks(c)                                    # [B,C,Q,G,N]
    xdt = xdt32.astype(cd)

    # within the chunk: (L o C B^T) (dt x)
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc, preferred_element_type=f32)
    seg = cum[..., :, None] - cum[..., None, :]                      # [B,C,G,R,Q,Q]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    scores = (cb[:, :, :, None] * decay).astype(cd)
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", scores, xdt, preferred_element_type=f32)

    # each chunk's end state from its own inputs, then the carry
    to_end = jnp.moveaxis(jnp.exp(total[..., None] - cum), -1, 2)    # [B,C,Q,G,R]
    weighted = (xdt32 * to_end[..., None]).astype(cd)
    states = jnp.einsum("bcsgrp,bcsgn->bcgrpn", weighted, bc, preferred_element_type=f32)
    before = _carry(states, total)

    # what the state a chunk starts from adds to its tokens
    from_start = jnp.moveaxis(jnp.exp(cum), -1, 2)                   # [B,C,Q,G,R]
    y = y + from_start[..., None] * jnp.einsum(
        "bclgn,bcgrpn->bclgrp", cc, before.astype(cd), preferred_element_type=f32)

    y = y.reshape(bsz, nc * chunk, h, p)[:, :t]
    y = y + d.astype(f32)[:, None] * x[:, :t].astype(f32)
    return y, jnp.sum(total < CUT_OFF_LOG).astype(jnp.int32)
