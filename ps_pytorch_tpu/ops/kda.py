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
accumulate in float32, as ops/ssd.py does. A T that is no multiple of C is
padded with g = 0, beta = 0: a padded token leaves the state as it is and
its o is cut off. C is a power of two.

**Two forms of the chunk's own part, one algorithm.** On a TPU (or under
PS_TPU_PALLAS_INTERPRET), for heads of whole 128-lane tiles, three Pallas
kernels whose grid step is a (row, head, group of chunks) and whose
intermediates never leave VMEM: `ps_kda_inverse`, `ps_kda_within_fwd`,
`ps_kda_within_bwd`, tied by a `jax.custom_vjp` (`_within_kernels`).
Anywhere else their jnp twin `_within_blocks` (`ps_kda_within_jnp`):
`_within_chunks` under `lax.map`, a block of chunks at a time, keeping its
inputs and the inverse for the backward pass and nothing else
(jax.checkpoint): the per-level operands of `_pair_scores` would be 6 x 2
tensors the size of q. Either way the system's inverse is the one value
`remat` keeps (`KDA_SAVED`): the forward it runs again does not solve the
system again. `scan_path` says which form a call takes; the scan across
chunks is plain XLA in both (PERF.md section 7).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..obs.scopes import DELTA_RULE, scope
from .flash_attention import SavedLayers
from .pallas_mode import kernel_mode, pallas_mode

# the one value of a chunk that `remat` is worth keeping (models/kda_hybrid.py
# adds the name to its blocks' policy, as ops/flash_attention.FLASH_SAVED is
# in every family's): the triangular system's inverse, 16 KiB a chunk and
# head in float32, whose backward needs nothing else and whose forward is
# ten products at "highest"
KDA_SAVED = ("ps_kda_inverse",)
# the op's q, k and v as models/kda_hybrid.kda_mixer's short branches leave
# them (a product, a conv, silu and an L2 norm in float32 each; bfloat16
# [B, T, H, d] to keep): kept too wherever ops/flash_attention.
# plan_remat_saves finds the room. g is float32 and a low-rank pair and a
# softplus to make again; beta is one number a head.
KDA_OPERANDS = ("ps_kda_q", "ps_kda_k", "ps_kda_v")
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


def _within_blocks(args):
    """`_within_chunks` over all of [B, NC, H, C, *], a block of
    CHUNKS_A_BLOCK chunks at a time: what `_pair_scores` and the system's
    solve keep for their backward is a block's, not T's. The kernels' jnp
    twin."""
    bsz, nc = args[0].shape[:2]
    per = math.gcd(nc, CHUNKS_A_BLOCK)
    blocked = lambda a: jnp.moveaxis(a.reshape((bsz, nc // per, per) + a.shape[2:]), 1, 0)
    whole = lambda a: jnp.moveaxis(a, 0, 1).reshape((bsz, nc) + a.shape[3:])
    within = jax.checkpoint(
        _within_chunks, policy=jax.checkpoint_policies.save_only_these_names(*KDA_SAVED))
    with jax.named_scope("ps_kda_within_jnp"):
        return tuple(whole(a) for a in lax.map(within, tuple(blocked(a) for a in args)))


# ------------------------------------------------- the chunk's own part, in VMEM
#
# Three Pallas kernels, one grid step a (row, head, group of chunks), every
# intermediate of a chunk in VMEM: `ps_kda_inverse` (the k k^T scores and the
# system's inverse: the one value `remat` keeps, so the forward it runs again
# does not hold this kernel), `ps_kda_within_fwd` (the q k^T scores and, from
# the inverse handed in, w, u0, kend, qg) and `ps_kda_within_bwd` (the levels
# again, the inverse's backward -T^T dT T^T, all five gradients). The
# mathematics is `_within_chunks`': the same levels, masks, dtypes and
# precisions. A level's reference row is picked by sublane rotations and
# selects (a reshape into blocks of 1, 2, 4 rows has no Mosaic lowering), and
# its gradient goes back by the transposed rotations.
#
# Two chunks go through as ONE matrix of 2C rows, for C = 64 a whole 128 x
# 128 tile of the MXU and whole 128-lane registers: the level masks never
# pair tokens of two chunks (levels stop at log2(C)), so every [2C, 2C]
# square is block-diagonal and what lies off its blocks is masked away as
# the other levels' pairs are. The inverse leaves `ps_kda_inverse` and enters
# the other two PACKED, its two blocks side by side as [C, 2C] (no minor
# dimension of 64 for the (8, 128) tiles to pad): what `remat` keeps is its
# size, as `_inverse_fwd`'s flat form is. The q k^T scores leave and their
# gradient enters a chunk a block [C, C], the layout `_across_chunks` takes.

PAIRS_A_STEP = 4      # pairs of chunks a grid step at C = 64 and heads of 128:
#                       independent work for the MXUs' pipelines (a chunk is a
#                       chain of dependent products), and what the 16 MiB of
#                       VMEM hold of their unrolled temporaries
_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b.T


def _pairs_a_step(chunk: int, d_key: int, d_value: int) -> int:
    """Pairs of chunks a grid step: PAIRS_A_STEP, fewer where a pair's
    squares [2C, 2C] and rows [2C, K] are larger than at C = 64 and heads
    of 128 or 256, none where one pair is too much for VMEM."""
    return min(PAIRS_A_STEP,
               PAIRS_A_STEP * 64 * 64 * 512 // (chunk * chunk * max(512, d_key + d_value)))


def kernels_fit(chunk: int, d_key: int, d_value: int) -> bool:
    """The shapes the kernels take: whole (8, 128) tiles, and a pair of
    chunks' temporaries inside VMEM."""
    return (chunk % 8 == 0 and d_key % 128 == 0 and d_value % 128 == 0
            and _pairs_a_step(chunk, d_key, d_value) >= 1)


def scan_path(chunk: int, d_key: int, d_value: int) -> str:
    """Which form the chunk's own part and the scan across chunks take in
    this process at these shapes (models/kda_hybrid.kda_plan records it)."""
    if pallas_mode() is None or not kernels_fit(chunk, d_key, d_value):
        return "xla"
    return "pallas_within+xla_scan"


def padded_len(t: int, chunk: int, d_key: int, d_value: int) -> int:
    """T as `kda_chunked` pads it: whole chunks, and for the kernels, which
    take chunks in pairs, an even number of them."""
    return t + -t % (chunk if scan_path(chunk, d_key, d_value) == "xla" else 2 * chunk)


def kda_saves(b: int, t: int, h: int, d: int, dtype, chunk: int, layers: int) -> SavedLayers:
    """What `layers` delta-rule layers name for a `remat` policy at q, k, v
    [b, t, h, d]: the triangular inverses as the form that runs shapes them,
    and the op's q, k, v."""
    sds = jax.ShapeDtypeStruct
    nc = padded_len(t, chunk, d, d) // chunk
    inverse = sds((b, nc, h, chunk * chunk) if scan_path(chunk, d, d) == "xla"
                  else (b, nc // 2, h, chunk, 2 * chunk), jnp.float32)
    return SavedLayers(layers, {KDA_SAVED[0]: inverse},
                       {name: sds((b, t, h, d), dtype) for name in KDA_OPERANDS})


def _dot(a, b, dims, precision=None):
    return lax.dot_general(a, b, dims, precision=precision,
                           preferred_element_type=jnp.float32)


def _iotas(rows: int):
    """Row numbers [R, 1], and row and column numbers [R, R]."""
    return (lax.broadcasted_iota(jnp.int32, (rows, 1), 0),
            lax.broadcasted_iota(jnp.int32, (rows, rows), 0),
            lax.broadcasted_iota(jnp.int32, (rows, rows), 1))


def _bit(x, level: int):
    return ((x >> level) & 1) == 1


def _pair_bits(ri, ci):
    """i xor j below the diagonal, 0 elsewhere: a pair j < i is of the
    level of its highest set bit."""
    return jnp.where(ri > ci, ri ^ ci, 0)


def _level_mask(bits, level: int):
    """`_sibling_mask` from `_pair_bits`."""
    return (bits >> level) == 1


def _level_factors(cum, row, levels: int):
    """[fac_l]: exp(-|cum_i - cum_ref(i)|) a level, ref(i) the first token of
    the odd block of 2^l in i's block of 2^(l+1): `_pair_scores`' `fac`.
    `a` holds cum at the start of each token's block of 2^l."""
    from jax.experimental.pallas import tpu as pltpu

    rows = cum.shape[0]
    a, out = cum, []
    for level in range(levels):
        s = 1 << level
        odd = _bit(row, level)
        ref = jnp.where(odd, a, pltpu.roll(a, rows - s, 0))          # a_(i+s) in the even block
        out.append(jnp.exp(jnp.where(odd, cum - ref, ref - cum)))
        if level + 1 < levels:
            a = jnp.where(odd, pltpu.roll(a, s, 0), a)
    return out


def _inverse_body(a, ri, ci, levels: int):
    """`unit_lower_inverse` of a [R, R] whose blocks of 2^levels rows are
    systems of their own, from iotas."""
    bits = _pair_bits(ri, ci)
    x = jnp.where(ri == ci, 1.0, 0.0) - jnp.where(_level_mask(bits, 0), a, 0.0)
    for level in range(1, levels):
        off = jnp.where(_level_mask(bits, level), a, 0.0)
        x = x - _dot(_dot(x, off, _NN, HI), x, _NN, HI)
    return x


def _column(row_vec, ri, ci):
    """[1, R] -> [R, 1] without a transpose."""
    return jnp.sum(jnp.where(ri == ci, row_vec, 0.0), axis=1, keepdims=True)


def _pair(ref, p):
    """Chunks 2p and 2p + 1 of a block, one under the other: [2C, X]."""
    return jnp.concatenate([ref[2 * p], ref[2 * p + 1]], axis=0)


def _unpair(ref, p, value):
    c = value.shape[0] // 2
    ref[2 * p] = value[:c]
    ref[2 * p + 1] = value[c:]


def _packed(square):
    """A block-diagonal [2C, 2C] as [C, 2C], its blocks side by side."""
    c = square.shape[0] // 2
    return square[:c] + square[c:]


def _on_the_blocks(square, ri, ci):
    """What a [2C, 2C] holds on its two diagonal blocks, 0 off them."""
    c = square.shape[0] // 2
    return jnp.where((ri < c) == (ci < c), square, 0.0)


def _unpacked(packed, ri, ci):
    return _on_the_blocks(jnp.concatenate([packed, packed], axis=0), ri, ci)


def _block_diagonal(first, second, ri, ci):
    """The [2C, 2C] of two [C, C]."""
    twice = lambda a: jnp.concatenate([a, a], axis=1)
    return _on_the_blocks(jnp.concatenate([twice(first), twice(second)], axis=0), ri, ci)


def _chunk_ends(cum, row, c: int):
    """Each row's own chunk's last row of cum [2C, K]."""
    return jnp.where(row < c, cum[c - 1:c, :], cum[2 * c - 1:2 * c, :])


def _each_pair(pairs: int, body):
    """body(p) for every pair of a grid step: traced once, unrolled into
    straight-line code where the kernel is lowered (independent chains for
    the scheduler to interleave)."""
    lax.fori_loop(0, pairs, lambda p, carry: body(p), None, unroll=True)


def _inverse_kernel(k_ref, cum_ref, beta_ref, t_ref):
    f32, cd = jnp.float32, k_ref.dtype
    c = k_ref.shape[1]
    row, ri, ci = _iotas(2 * c)
    bits = _pair_bits(ri, ci)
    def pair(p):
        k32, cum = _pair(k_ref, p).astype(f32), _pair(cum_ref, p)
        kk = jnp.zeros((2 * c, 2 * c), f32)
        for level, fac in enumerate(_level_factors(cum, row, _levels(c))):
            kf = (k32 * fac).astype(cd)
            kk = jnp.where(_level_mask(bits, level), _dot(kf, kf, _NT), kk)   # a pair has one level
        t_ref[p] = _packed(_inverse_body(_column(beta_ref[p], ri, ci) * kk, ri, ci, _levels(c)))

    _each_pair(k_ref.shape[0] // 2, pair)


def _fwd_kernel(q_ref, k_ref, v_ref, cum_ref, beta_ref, t_ref,
                qk_ref, w_ref, u0_ref, kend_ref, qg_ref):
    f32, cd = jnp.float32, q_ref.dtype
    c = q_ref.shape[1]
    row, ri, ci = _iotas(2 * c)
    bits = _pair_bits(ri, ci)
    def pair(p):
        q32, k32, cum = _pair(q_ref, p).astype(f32), _pair(k_ref, p).astype(f32), _pair(cum_ref, p)
        qk = jnp.where(ri == ci, jnp.sum(q32 * k32, axis=1, keepdims=True), 0.0)
        for level, fac in enumerate(_level_factors(cum, row, _levels(c))):
            scores = _dot((q32 * fac).astype(cd), (k32 * fac).astype(cd), _NT)
            qk = jnp.where(_level_mask(bits, level), scores, qk)             # a pair has one level
        decay = jnp.exp(cum)
        solve = (_unpacked(t_ref[p], ri, ci) * beta_ref[p]).astype(cd)
        qk_ref[2 * p] = qk[:c, :c].astype(cd)
        qk_ref[2 * p + 1] = qk[c:, c:].astype(cd)
        _unpair(w_ref, p, _dot(solve, (k32 * decay).astype(cd), _NN).astype(cd))
        _unpair(u0_ref, p, _dot(solve, _pair(v_ref, p), _NN))
        _unpair(kend_ref, p, (k32 * jnp.exp(_chunk_ends(cum, row, c) - cum)).astype(cd))
        _unpair(qg_ref, p, (q32 * decay).astype(cd))

    _each_pair(q_ref.shape[0] // 2, pair)


def _bwd_kernel(q_ref, k_ref, v_ref, cum_ref, beta_ref, t_ref,
                gqk_ref, gw_ref, gu0_ref, gkend_ref, gqg_ref,
                dq_ref, dk_ref, dv_ref, dcum_ref, dbeta_ref):
    from jax.experimental.pallas import tpu as pltpu

    f32, cd = jnp.float32, q_ref.dtype
    c = q_ref.shape[1]
    rows, levels = 2 * c, _levels(c)
    row, ri, ci = _iotas(rows)
    bits, bits_t = _pair_bits(ri, ci), _pair_bits(ci, ri)
    eye, first = ri == ci, row < c
    def pair(p):
        q32, k32, cum = _pair(q_ref, p).astype(f32), _pair(k_ref, p).astype(f32), _pair(cum_ref, p)
        t, beta_row = _unpacked(t_ref[p], ri, ci), beta_ref[p]
        beta_col = _column(beta_row, ri, ci)
        t_t = t.T                        # every transposed product below takes its left side turned once
        decay = jnp.exp(cum)
        to_end = jnp.exp(_chunk_ends(cum, row, c) - cum)
        solve, solve_t = (t * beta_row).astype(cd), (t_t * beta_col).astype(cd)
        kg = (k32 * decay).astype(cd)
        gw, gu0 = _pair(gw_ref, p), _pair(gu0_ref, p).astype(cd)
        # w = solve kg, u0 = solve v
        dsolve = _dot(gu0, _pair(v_ref, p), _NT) + _dot(gw, kg, _NT)
        _unpair(dv_ref, p, _dot(solve_t, gu0, _NN).astype(cd))
        dkg = _dot(solve_t, gw, _NN)
        # kend = k exp(cum_C - cum), qg = q exp(cum)
        dq = _pair(gqg_ref, p).astype(f32) * decay
        gkend = _pair(gkend_ref, p).astype(f32) * to_end
        ended = gkend * k32
        dk = dkg * decay + gkend
        dcum = (dkg * (k32 * decay) + dq * q32 - ended
                + jnp.where(row == c - 1, jnp.sum(jnp.where(first, ended, 0.0), axis=0, keepdims=True), 0.0)
                + jnp.where(row == rows - 1, jnp.sum(jnp.where(first, 0.0, ended), axis=0, keepdims=True), 0.0))
        # solve = T diag(beta), T = (I + diag(beta) kk)^-1: dA = -T^T dT T^T
        dbeta = jnp.sum(dsolve * t, axis=0, keepdims=True)
        da = -_dot(_dot(t_t, dsolve * beta_row, _NN, HI), t_t, _NN, HI)
        da = jnp.where((bits > 0) & (bits < c), da, 0.0)       # below the diagonal, one chunk
        dkk = beta_col * da
        dkk_t = dkk.T
        gqk = _block_diagonal(gqk_ref[2 * p].astype(f32), gqk_ref[2 * p + 1].astype(f32), ri, ci)
        gqk_t = gqk.T
        # the diagonal of qk: sum_d q k
        on_diag = jnp.sum(jnp.where(eye, gqk, 0.0), axis=1, keepdims=True)
        dq = dq + on_diag * k32
        dk = dk + on_diag * q32
        dbeta_col = jnp.zeros((rows, 1), f32)
        facs = _level_factors(cum, row, levels)
        back = None                      # d(cum at each block's start), level by level
        for level in range(levels - 1, -1, -1):
            s, fac, odd = 1 << level, facs[level], _bit(row, level)
            mask, mask_t = _level_mask(bits, level), _level_mask(bits_t, level)
            qf, kf = (q32 * fac).astype(cd), (k32 * fac).astype(cd)
            dbeta_col = dbeta_col + jnp.sum(
                jnp.where(mask, da * _dot(kf, kf, _NT), 0.0), axis=1, keepdims=True)
            # qk += mask o (qf kf^T), kk += mask o (kf kf^T)
            dqf = _dot(jnp.where(mask, gqk, 0.0).astype(cd), kf, _NN)
            dkf = (_dot(jnp.where(mask_t, gqk_t, 0.0).astype(cd), qf, _NN)
                   + _dot(jnp.where(mask, dkk, jnp.where(mask_t, dkk_t, 0.0)).astype(cd), kf, _NN))
            dq = dq + dqf * fac
            dk = dk + dkf * fac
            dexp = (dqf * q32 + dkf * k32) * fac
            diff = jnp.where(odd, dexp, -dexp)          # d(cum - ref)
            dcum = dcum + diff
            here = jnp.where(odd, -diff - pltpu.roll(diff, s, 0), 0.0)
            if back is not None:     # stage level + 1 -> stage level
                back = jnp.where(odd, 0.0, back + pltpu.roll(back, rows - s, 0))
            back = here if back is None else back + here
        _unpair(dq_ref, p, dq.astype(cd))
        _unpair(dk_ref, p, dk.astype(cd))
        _unpair(dcum_ref, p, dcum + back)
        dbeta_ref[p] = dbeta + jnp.sum(jnp.where(eye, dbeta_col, 0.0), axis=0, keepdims=True)

    _each_pair(q_ref.shape[0] // 2, pair)


def _chunk_call(kernel, name: str, mode: dict, pairs: int, ins, outs):
    """One grid step a (row, head, `pairs` pairs of chunks, fewer where NC /
    2 has no such divisor) over arrays [B, NC, H, rows, lanes] (a chunk) and
    [B, NC / 2, H, rows, lanes] (a pair of chunks); `outs` are
    ShapeDtypeStructs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, nc, h = ins[0].shape[:3]
    per = 2 * math.gcd(nc // 2, pairs)
    spec = lambda a: pl.BlockSpec((None, per * a.shape[1] // nc, None) + a.shape[3:],
                                  lambda i, j, n: (i, n, j, 0, 0))
    return pl.pallas_call(
        kernel,
        name=name,
        grid=(b, h, nc // per),
        in_specs=[spec(a) for a in ins],
        out_specs=[spec(a) for a in outs],
        out_shape=outs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        **mode,
    )(*ins)


def _beta_rows(beta):
    """[B, NC, H, C] -> [B, NC / 2, H, 1, 2 C]: a pair of chunks' beta as
    the row a kernel scales a pair's columns by."""
    b, nc, h, c = beta.shape
    return jnp.moveaxis(beta.reshape(b, nc // 2, 2, h, c), 2, 3).reshape(b, nc // 2, h, 1, 2 * c)


def _within_forward(args):
    q, k, v, cum, beta = args
    mode = kernel_mode("kda_within")
    f32, cd = jnp.float32, q.dtype
    b, nc, h, c, width = q.shape
    like = lambda a, dtype: jax.ShapeDtypeStruct(a.shape, dtype)
    square = lambda dtype: jax.ShapeDtypeStruct((b, nc // 2, h, c, 2 * c), dtype)
    beta_rows = _beta_rows(beta)
    pairs = _pairs_a_step(c, width, v.shape[4])
    inverse, = _chunk_call(_inverse_kernel, "ps_kda_inverse", mode, pairs,
                           (k, cum, beta_rows), [square(f32)])
    inverse = checkpoint_name(inverse, KDA_SAVED[0])
    own = _chunk_call(
        _fwd_kernel, "ps_kda_within_fwd", mode, pairs, (q, k, v, cum, beta_rows, inverse),
        [jax.ShapeDtypeStruct((b, nc, h, c, c), cd), like(k, cd), like(v, f32), like(k, cd),
         like(q, cd)])
    return tuple(own), inverse


@jax.custom_vjp
def _within_kernels(args):
    """`_within_chunks` over all of [B, NC, H, C, *] (NC even) by the kernels."""
    return _within_forward(args)[0]


def _within_kernels_fwd(args):
    own, inverse = _within_forward(args)
    return own, (args, inverse)


def _within_kernels_bwd(saved, grads):
    (q, k, v, cum, beta), inverse = saved
    b, nc, h, c, _ = q.shape
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    dq, dk, dv, dcum, dbeta = _chunk_call(
        _bwd_kernel, "ps_kda_within_bwd", kernel_mode("kda_within"),
        _pairs_a_step(c, q.shape[4], v.shape[4]),
        (q, k, v, cum, _beta_rows(beta), inverse, *grads),
        [like(q), like(k), like(v), like(cum),
         jax.ShapeDtypeStruct((b, nc // 2, h, 1, 2 * c), jnp.float32)])
    dbeta = jnp.moveaxis(dbeta.reshape(b, nc // 2, h, 2, c), 3, 2).reshape(beta.shape)
    return ((dq, dk, dv, dcum, dbeta),)


_within_kernels.defvjp(_within_kernels_fwd, _within_kernels_bwd)


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


@scope(DELTA_RULE)
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
    kernels = scan_path(chunk, q.shape[-1], v.shape[-1]) != "xla"
    pad = padded_len(t, chunk, q.shape[-1], v.shape[-1]) - t
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

    own = (_within_kernels if kernels else _within_blocks)((q, k, v, cum, beta))
    o = _across_chunks(*own, total, zero_carried)
    o = jnp.moveaxis(o, 2, 3).reshape(bsz, nc * chunk, h, -1)[:, :t]
    cut_off = jnp.sum(jnp.max(total, axis=-1) < CUT_OFF_LOG).astype(jnp.int32)
    return o, cut_off
