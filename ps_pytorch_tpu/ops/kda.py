"""Kimi Delta Attention's recurrence: a gated delta rule whose decay is one
number a KEY CHANNEL, token by token and in its chunked form.

Per head, with q_t, k_t in R^K, v_t in R^V, a log-decay g_t in R^K (<= 0),
beta_t in (0, 1) and a state S in R^(K x V), S_0 = 0:

    S' = diag(exp(g_t)) S_(t-1)
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T          o_t = S_t^T q_t

(the paper's S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_(t-1) + beta_t
k_t v_t^T). `kda_recurrence` is that definition (a lax.scan of T turns; the
tests' twin). `kda_chunked` computes the same o in chunks of C tokens. With
G_i the sum of g over the chunk's tokens up to and including i, and S_0 the
state the chunk starts from, the corrections u_j = beta_j (v_j - S'_j^T k_j)
of one chunk solve a unit lower-triangular system,

    (I + A) U = diag(beta) (V - (K o exp(G)) S_0),
    A_ij = beta_i sum_d k_id k_jd exp(G_id - G_jd)   for j < i,

and then o_i = (q_i o exp(G_i)) S_0 + sum_(j<=i) B_ij u_j with B_ij =
sum_d q_id k_jd exp(G_id - G_jd), and the chunk ends in diag(exp(G_C)) S_0
+ sum_j (k_j o exp(G_C - G_j)) u_j^T. What a chunk needs of itself alone
(both score matrices, the system's inverse, its right-hand sides) is products
over many chunks at once (`_within_chunks`, a block of chunks at a time); the
state then goes from chunk to chunk in a lax.scan of T/C turns
(`_across_chunks`), each turn four small products.

**No exponent of a positive number anywhere.** exp(G_i - G_j) does not
factor out of the sum over d, and exp(G_i) * exp(-G_j) overflows float32
once a chunk's summed log-decay passes -88. `_pair_scores` splits the pairs
j < i by the highest bit in which i and j differ: at level l the chunk falls
into blocks of 2^l tokens, a pair is made of an odd block (i) and the even
block before it (j), and with X the cumulative sum at the odd block's first
token G_i <= X <= G_j, so exp(G_i - X) and exp(X - G_j) are both at most 1.
One product a level over the whole chunk, masked to that level's pairs:
log2(C) products of [C, K] x [K, C] and no temporary of C^2 x K. A factor
that underflows to 0 stands where the true product is smaller still.

**The triangular system** is solved by the same blocks (`unit_lower_inverse`):
the inverse of a block of 2^(l+1) from its two halves' inverses X_J, X_I and
the block A_IJ between them, [[X_J, 0], [-X_I A_IJ X_J, X_I]], as two
products over the whole chunk a level: log2(C) levels, no step a token. Its
backward is the inverse's own, dA = -T^T dT T^T.

g, its cumulative sums, every decay, the system's matrix, its inverse (at
precision "highest") and the carried state stay float32; the other
products take their operands in q's dtype (the blocks' compute dtype) and
accumulate in float32, as ops/ssd.py does. `_within_chunks` keeps its
inputs and the system's inverse (`KDA_SAVED`) for the backward pass and
nothing else (jax.checkpoint): the per-level operands of `_pair_scores`
would be 6 x 2 tensors the size of q. A T that is no multiple of C is padded
with g = 0, beta = 0: a padded token leaves the state as it is and its o is
cut off. C is a power of two.

Plain XLA (`SCAN_PATH`); no kernel yet (PERF.md section 7).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

SCAN_PATH = "xla"
# the one value of a chunk that `remat` is worth keeping (models/kda_hybrid.py
# adds the name to its blocks' policy, as ops/flash_attention.FLASH_SAVED is
# in every family's): the triangular system's inverse, 16 KiB a chunk and
# head in float32, whose backward needs nothing else and whose forward is
# ten products at "highest"
KDA_SAVED = ("ps_kda_inverse",)
HI = lax.Precision.HIGHEST
# a chunk whose SLOWEST-decaying channel keeps less than this of the state
# it was given hands the next chunk nothing a float32 sum would notice
CUT_OFF_LOG = -24.0 * math.log(2.0)
# chunks of one row that `_within_chunks` takes at a time and that make one
# segment of `_across_chunks` (fewer where the row's chunks have no such
# divisor)
CHUNKS_A_BLOCK = 16


def l2_normalize(x, scale: float = 1.0, eps: float = 1e-6):
    """x / |x|_2 over the last axis (eps under the root), times `scale`;
    float32."""
    x = x.astype(jnp.float32)
    return x * (lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps) * scale)


def kda_recurrence(q, k, v, g, beta):
    """The definition, token by token, in float32.

    q, k [B, T, H, K]; v [B, T, H, V]; g [B, T, H, K] (<= 0); beta
    [B, T, H]. Returns o [B, T, H, V]."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))

    def turn(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = jnp.exp(g_t)[..., None] * s
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=HI))
        s = s + k_t[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=HI)

    s0 = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]), f32)
    _, o = lax.scan(turn, s0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _levels(chunk: int) -> int:
    return chunk.bit_length() - 1


def _sibling_mask(chunk: int, level: int):
    """[C, C] bool: i in an odd block of 2^level tokens, j in the even block
    just before it."""
    pos = jnp.arange(chunk)
    parent = pos >> (level + 1)
    odd = ((pos >> level) & 1) == 1
    return (parent[:, None] == parent[None, :]) & odd[:, None] & ~odd[None, :]


def _mm(a, b, spec):
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _pair_scores(q, k, cum):
    """(B, A'): B_ij = sum_d q_id k_jd exp(cum_id - cum_jd) for j <= i,
    A'_ij the same of k and k for j < i; zero elsewhere. q, k [..., C, K]
    in the compute dtype, cum [..., C, K] float32 (non-increasing along C).
    Float32 [..., C, C]."""
    f32 = jnp.float32
    cd = q.dtype
    c, width = q.shape[-2], q.shape[-1]
    q32, k32 = q.astype(f32), k.astype(f32)
    qk = jnp.zeros(q.shape[:-1] + (c,), f32)
    kk = jnp.zeros_like(qk)
    lead = cum.shape[:-2]
    sign = jnp.array([-1.0, 1.0], f32).reshape(2, 1, 1)
    for level in range(_levels(c)):
        s = 1 << level
        blocks = cum.reshape(lead + (c // (2 * s), 2, s, width))
        ref = blocks[..., 1:2, 0:1, :]                       # the odd block's first token
        # even half: exp(ref - cum) (j before ref); odd half: exp(cum - ref)
        fac = jnp.exp((blocks - ref) * sign).reshape(cum.shape)
        kf = (k32 * fac).astype(cd)
        # the whole square is multiplied and this level's pairs kept: every
        # factor is at most 1, so what the mask throws away is finite
        mask = _sibling_mask(c, level)
        qk = qk + jnp.where(mask, _mm((q32 * fac).astype(cd), kf, "...ik,...jk->...ij"), 0.0)
        kk = kk + jnp.where(mask, _mm(kf, kf, "...ik,...jk->...ij"), 0.0)
    # j = i: no decay between a token and itself
    diag = jnp.sum(q32 * k32, axis=-1)
    return qk + diag[..., None] * jnp.eye(c, dtype=f32), kk


@jax.custom_vjp
def unit_lower_inverse(a):
    """(I + a)^-1 for a strictly lower-triangular a [..., C, C], float32, by
    halves: log2(C) levels of two products, none of them a step a row."""
    c = a.shape[-1]
    mm = lambda x, y: jnp.einsum("...ij,...jk->...ik", x, y, precision=HI)
    x = jnp.eye(c, dtype=a.dtype) - jnp.where(_sibling_mask(c, 0), a, 0.0)
    for level in range(1, _levels(c)):
        off = jnp.where(_sibling_mask(c, level), a, 0.0)
        x = x - mm(mm(x, off), x)
    return x


def _inverse_fwd(a):
    # named, and flat: [..., C * C] has no minor dimension of 64 for the
    # TPU's (8, 128) tiles to pad to 128, so what `remat` keeps is its size
    c = a.shape[-1]
    flat = checkpoint_name(unit_lower_inverse(a).reshape(a.shape[:-2] + (c * c,)), KDA_SAVED[0])
    return flat.reshape(a.shape), flat


def _inverse_bwd(flat, dt):
    t = flat.reshape(dt.shape)
    mm = lambda x, y, spec: jnp.einsum(spec, x, y, precision=HI)
    da = -mm(mm(t, dt, "...ji,...jk->...ik"), t, "...ij,...kj->...ik")
    c = t.shape[-1]
    return (jnp.where(jnp.tril(jnp.ones((c, c), bool), -1), da, 0.0),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _within_chunks(args):
    """What a chunk needs of itself alone, for chunks [..., C, *]: the
    decayed q k^T scores, W = T (K o exp(G)) and U_0 = T V (float32) with T
    = (I + A)^-1 diag(beta), the keys decayed to the chunk's end and the
    queries decayed from its start."""
    q, k, v, cum, beta = args
    f32, cd = jnp.float32, q.dtype
    qk, kk = _pair_scores(q, k, cum)
    solve = (unit_lower_inverse(beta[..., None] * kk) * beta[..., None, :]).astype(cd)
    k32 = k.astype(f32)
    w = _mm(solve, (k32 * jnp.exp(cum)).astype(cd), "...ij,...jk->...ik").astype(cd)
    u0 = _mm(solve, v, "...ij,...jv->...iv")
    kend = (k32 * jnp.exp(cum[..., -1:, :] - cum)).astype(cd)
    return qk.astype(cd), w, u0, kend, (q.astype(f32) * jnp.exp(cum)).astype(cd)


def _across_chunks(qk, w, u0, kend, qg, total, zero_carried: bool = False):
    """o [B, NC, H, C, V] float32, chunk by chunk from S = 0 (a lax.scan of
    NC turns): u = u0 - w S, o = qg S + qk u, S' = exp(total) S + kend^T u.
    qk [B, NC, H, C, C], w, kend, qg [B, NC, H, C, K] in the compute dtype,
    u0 [B, NC, H, C, V] and total [B, NC, H, K] float32; the state S [B, H,
    K, V] float32. The backward keeps one state a segment of
    CHUNKS_A_BLOCK turns and runs a segment's turns again: no stack of NC
    states exists in either pass. `zero_carried` is the tests' switch:
    every chunk then starts from 0."""
    cd = qk.dtype

    def turn(s, inp):
        qk_c, w_c, u0_c, kend_c, qg_c, tot_c = inp
        sc = s.astype(cd)
        u = (u0_c - _mm(w_c, sc, "bhck,bhkv->bhcv")).astype(cd)
        o = _mm(qg_c, sc, "bhck,bhkv->bhcv") + _mm(qk_c, u, "bhij,bhjv->bhiv")
        nxt = jnp.exp(tot_c)[..., None] * s + _mm(kend_c, u, "bhck,bhcv->bhkv")
        return (jnp.zeros_like(nxt) if zero_carried else nxt), o

    @jax.checkpoint
    def segment(s, inp):
        return lax.scan(turn, s, inp)

    b, nc, h, _, width = w.shape
    per = math.gcd(nc, CHUNKS_A_BLOCK)
    xs = tuple(jnp.moveaxis(a, 1, 0).reshape((nc // per, per) + a.shape[:1] + a.shape[2:])
               for a in (qk, w, u0, kend, qg, total))
    s0 = jnp.zeros((b, h, width, u0.shape[-1]), jnp.float32)
    _, o = lax.scan(segment, s0, xs)
    return jnp.moveaxis(o.reshape((nc,) + o.shape[2:]), 0, 1)


def kda_chunked(q, k, v, g, beta, chunk: int = 64, zero_carried: bool = False):
    """The chunked form -> (o float32 [B, T, H, V], cut_off int32): shapes
    as `kda_recurrence`; the products run in q.dtype. `cut_off` counts the
    (row, chunk, head) in which even the slowest-decaying channel's decay
    over the whole chunk is under 2^-24: there the carried state does no
    work a float32 sum would notice."""
    if chunk < 2 or chunk & (chunk - 1):
        raise ValueError(f"kda_chunked: chunk={chunk} is not a power of two (2 or more)")
    f32 = jnp.float32
    cd = q.dtype
    bsz, t, h, _ = q.shape
    pad = -t % chunk
    if pad:
        widen = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        q, k, v, g, beta = (widen(a) for a in (q, k, v, g, beta))
    nc = (t + pad) // chunk
    # [B, T, H, X] -> [B, NC, H, C, X]
    chunks = lambda a: jnp.moveaxis(a.reshape((bsz, nc, chunk) + a.shape[2:]), 3, 2)
    q, k, v = chunks(q.astype(cd)), chunks(k.astype(cd)), chunks(v.astype(cd))
    beta = jnp.moveaxis(beta.astype(f32).reshape(bsz, nc, chunk, h), 3, 2)   # [B,NC,H,C]
    cum = jnp.cumsum(chunks(g.astype(f32)), axis=3)              # log-decay from the chunk's start
    total = cum[..., -1, :]                                      # [B,NC,H,K]

    # the chunk's own part, a block of chunks at a time: what `_pair_scores`
    # and the system's solve keep for their backward is a block's, not T's
    per = math.gcd(nc, CHUNKS_A_BLOCK)
    blocked = lambda a: jnp.moveaxis(a.reshape((bsz, nc // per, per) + a.shape[2:]), 1, 0)
    whole = lambda a: jnp.moveaxis(a, 0, 1).reshape((bsz, nc) + a.shape[3:])
    within = jax.checkpoint(
        _within_chunks, policy=jax.checkpoint_policies.save_only_these_names(*KDA_SAVED))
    own = (whole(a) for a in lax.map(within, tuple(blocked(a) for a in (q, k, v, cum, beta))))
    o = _across_chunks(*own, total, zero_carried)
    o = jnp.moveaxis(o, 2, 3).reshape(bsz, nc * chunk, h, -1)[:, :t]
    cut_off = jnp.sum(jnp.max(total, axis=-1) < CUT_OFF_LOG).astype(jnp.int32)
    return o, cut_off
