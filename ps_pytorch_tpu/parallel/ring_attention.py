"""Ring attention — sequence/context parallelism over a mesh axis.

Long-context support is out of the reference's scope (SURVEY.md section 5:
CNN image workloads only, "no attention, no sequence dimension anywhere"),
but it is first-class here: the same ICI ring that carries the PS gradient
collectives carries blockwise attention, so sequences scale with the mesh
instead of with one chip's HBM.

Algorithm (blockwise online softmax, flash-attention style accumulation):
each of the N devices holds a [B, T/N, H, D] shard of Q/K/V. K/V blocks
rotate around the ring with `lax.ppermute` (neighbor exchange over ICI —
N-1 hops total, each overlapped by XLA with the local QK^T/PV compute);
every hop updates a running (max m, denominator l, numerator o) triple, so
softmax is exact without ever materializing the [T, T] score matrix.
Causality is enforced per (query-block, key-block) pair from the devices'
ring positions — fully-masked pairs contribute nothing and skip no hops
(uniform control flow keeps the loop compilable).

The N=1 degenerate case is exact full attention; tests check the sharded
result against it bit-for-tolerance on the virtual CPU mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SEQ_AXIS = "seq"

_NEG_BIG = -1e30  # mask value; avoids -inf - -inf = nan in the max trick


def _block_attend(q, k, v, mask, scale):
    """One (query-block x key-block) contribution.

    q: [B, Tq, H, D]; k/v: [B, Tk, H, D]; mask: [Tq, Tk] bool or None.
    Returns (m_blk [B, H, Tq], p_sum [B, H, Tq], pv [B, Tq, H, D]).

    Softmax statistics and accumulators are f32 regardless of input dtype
    (bf16 stats lose the max-trick's cancellation; matmuls still run on
    the inputs' dtype through the MXU with f32 accumulation).
    """
    scores = (
        jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
        * scale
    )
    if mask is not None:
        scores = jnp.where(mask[None, None], scores, _NEG_BIG)
    m_blk = jnp.max(scores, axis=-1)  # [B, H, Tq]
    p = jnp.exp(scores - m_blk[..., None])
    if mask is not None:
        # rows with no valid key: m_blk == _NEG_BIG and p would be exp(0)=1
        p = jnp.where(mask[None, None], p, 0.0)
    p_sum = jnp.sum(p, axis=-1)
    # PV runs on the inputs' dtype (bf16 MXU path) with f32 accumulation;
    # only the stats (m, l) and the running output stay f32
    pv = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return m_blk, p_sum, pv


def _accumulate(acc, m_blk, p_sum, pv):
    """Fold one block's (max, sum, numerator) into the running triple."""
    o, m, l = acc
    m_new = jnp.maximum(m, m_blk)
    alpha = jnp.exp(m - m_new)  # rescale old accumulators
    beta = jnp.exp(m_blk - m_new)  # rescale this block
    l_new = l * alpha + p_sum * beta
    o_new = (
        o * alpha.transpose(0, 2, 1)[..., None]
        + pv * beta.transpose(0, 2, 1)[..., None]
    )
    return o_new, m_new, l_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = SEQ_AXIS,
    causal: bool = False,
    scale: Optional[float] = None,
    bidirectional: bool = False,
) -> jax.Array:
    """Exact attention over sequence shards rotating on a ring.

    Call inside shard_map with q/k/v sharded [B, T_local, H, D] along the
    sequence axis `axis_name`. Returns the local output shard.

    `bidirectional=True` rotates K/V both ways simultaneously and processes
    two blocks per hop: same total traffic, half the sequential hops, and
    both ICI directions of a physical ring in use. Falls back to the
    one-way ring for n <= 2 (nothing to overlap).
    """
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    b, t_loc, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    q_pos = me * t_loc + jnp.arange(t_loc)  # global query positions

    def block_mask(k_blk):
        if not causal:
            return None
        k_pos = k_blk * t_loc + jnp.arange(t_loc)
        return k_pos[None, :] <= q_pos[:, None]  # [Tq, Tk]

    # f32 accumulators (see _block_attend), as wide as the values
    o0 = jnp.zeros(q.shape[:-1] + v.shape[-1:], jnp.float32)
    m0 = jnp.full((b, h, t_loc), _NEG_BIG, jnp.float32)
    l0 = jnp.zeros((b, h, t_loc), jnp.float32)

    # send my k/v block to the PREVIOUS device each hop: after s hops,
    # device i holds key block (i + s) mod n
    perm_fwd = [(j, (j - 1) % n) for j in range(n)]

    if not bidirectional or n <= 2:

        def hop(carry, s):
            o, m, l, k_cur, v_cur = carry
            m_blk, p_sum, pv = _block_attend(
                q, k_cur, v_cur, block_mask((me + s) % n), scale
            )
            acc = _accumulate((o, m, l), m_blk, p_sum, pv)
            # uniform rotation every hop keeps the loop body identical for
            # XLA (the final hop's permute returns k/v home)
            k_nxt = lax.ppermute(k_cur, axis_name, perm_fwd)
            v_nxt = lax.ppermute(v_cur, axis_name, perm_fwd)
            return (*acc, k_nxt, v_nxt), None

        # scan (not fori_loop): reverse-mode AD must flow through the ring
        # for training; ppermute transposes to the inverse rotation
        (o, m, l, _, _), _ = lax.scan(hop, (o0, m0, l0, k, v), jnp.arange(n))
    else:
        perm_bwd = [(j, (j + 1) % n) for j in range(n)]
        # own block first (no comm), then ceil((n-1)/2) two-block hops
        acc = _accumulate(
            (o0, m0, l0), *_block_attend(q, k, v, block_mask(me), scale)
        )
        n_hops = (n - 1 + 1) // 2
        # offsets +s (fwd) and -s (bwd) cover 1..n-1; for even n the offset
        # n/2 arrives on both streams — drop the bwd duplicate at s = n/2
        use_bwd = np.ones(n_hops, bool)
        if n % 2 == 0:
            use_bwd[-1] = False

        def hop2(carry, xs):
            s, bwd_ok = xs
            o, m, l, k_f, v_f, k_b, v_b = carry
            k_f = lax.ppermute(k_f, axis_name, perm_fwd)
            v_f = lax.ppermute(v_f, axis_name, perm_fwd)
            k_b = lax.ppermute(k_b, axis_name, perm_bwd)
            v_b = lax.ppermute(v_b, axis_name, perm_bwd)
            acc = _accumulate(
                (o, m, l),
                *_block_attend(q, k_f, v_f, block_mask((me + s) % n), scale),
            )
            m_blk, p_sum, pv = _block_attend(
                q, k_b, v_b, block_mask((me - s) % n), scale
            )
            # mask the duplicate block to a no-op contribution
            m_blk = jnp.where(bwd_ok, m_blk, _NEG_BIG)
            p_sum = jnp.where(bwd_ok, p_sum, 0.0)
            pv = jnp.where(bwd_ok, pv, 0.0)
            acc = _accumulate(acc, m_blk, p_sum, pv)
            return (*acc, k_f, v_f, k_b, v_b), None

        (o, m, l, *_), _ = lax.scan(
            hop2,
            (*acc, k, v, k, v),
            (jnp.arange(1, n_hops + 1), jnp.asarray(use_bwd)),
        )
    # causal guarantees >= 1 valid key per query (its own position), so l > 0
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


# ---------------------------------------------------------- ring + flash
# Flash WITHIN each hop: the jnp ring above materializes a [T_loc, T_loc]
# score block per hop; here each hop runs the Pallas partial-triple kernel
# (ops/flash_attention.flash_partial), so per-hop memory is O(block) and
# the full attention over N shards never builds a T_loc^2 tensor anywhere.
# Gradients are a custom VJP: a second ring pass in which dk/dv
# accumulators TRAVEL WITH their k/v shards (n rotations = home), each hop
# adding its exact contribution computed from the globally-merged
# (lse, delta) stats — summing to the exact flash backward.


def _fold_heads(x):  # [B, T, H, D] -> [B*H, T, D] (kernel layout)
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _unfold_heads(x3, b, h):  # inverse of _fold_heads
    bh, t, d = x3.shape
    return x3.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _merge_triple(acc, hop):
    """Online-softmax merge of two (pv [BH,T,D], m [BH,T], l [BH,T])."""
    pv, m, l = acc
    pv_h, m_h, l_h = hop
    m_new = jnp.maximum(m, m_h)
    # guard fully-masked-so-far rows: exp(_NEG_BIG - _NEG_BIG) = 1 is fine
    # (l contributions are 0 there), but exp below must not overflow
    alpha = jnp.exp(m - m_new)
    beta = jnp.exp(m_h - m_new)
    return (
        pv * alpha[..., None] + pv_h * beta[..., None],
        m_new,
        l * alpha + l_h * beta,
    )


_NOOP_M = _NEG_BIG  # a masked hop contributes (pv=0, m=_NEG_BIG, l=0)


def _mask_triple(ok, triple):
    """Reduce a (pv, m, l) hop contribution to a no-op when not ok."""
    pv, m, l = triple
    return (
        jnp.where(ok, pv, 0.0),
        jnp.where(ok, m, _NOOP_M),
        jnp.where(ok, l, 0.0),
    )


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = SEQ_AXIS,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    bidirectional: bool = False,
) -> jax.Array:
    """ring_attention with the Pallas flash kernel inside each hop.

    Call inside shard_map with q/k/v sharded [B, T_local, H, D] along
    `axis_name`. Exact (same math as ring_attention/full_attention); falls
    back to kernel interpret mode off-TPU. Memory per hop is O(block_q x
    block_k) VMEM scratch + the O(T_loc) (pv, m, l) running triple.

    bidirectional=True rotates K/V both ways and merges two partial
    triples per hop — same total traffic, half the sequential hops, both
    ICI directions in use (the flash analogue of ring_attention's
    bidirectional mode; falls back to one-way for n <= 2)."""
    o, _ = _ring_flash_fwd(
        q, k, v, axis_name, causal, scale, block_q, block_k, bidirectional
    )
    return o


def _bidir_plan(n):
    """Offsets 1..n-1 covered by +s (fwd) and -s (bwd) streams; for even n
    the offset n/2 arrives on both — drop the bwd duplicate."""
    n_hops = (n - 1 + 1) // 2
    use_bwd = np.ones(n_hops, bool)
    if n % 2 == 0 and n_hops:
        use_bwd[-1] = False
    return n_hops, use_bwd


def _ring_flash_fwd(q, k, v, axis_name, causal, scale, block_q, block_k,
                    bidirectional):
    from ..ops.flash_attention import flash_partial

    n = lax.axis_size(axis_name)
    # global positions are only consumed by the causal mask; without it,
    # deriving the shard offsets from lax.axis_index would strand a
    # partition-id op on the kernel's (then-unused) SMEM offsets operand,
    # which XLA's SPMD partitioner refuses to place (the ring_flash-bidir
    # CPU failure) — so the non-causal ring simply doesn't ask where it is
    me = lax.axis_index(axis_name) if causal else 0
    b, t_loc, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    q3, k3, v3 = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    q_off = me * t_loc

    pv0 = jnp.zeros(q3.shape[:2] + v3.shape[2:], jnp.float32)
    m0 = jnp.full(q3.shape[:2], _NEG_BIG, jnp.float32)
    l0 = jnp.zeros(q3.shape[:2], jnp.float32)
    perm_fwd = [(j, (j - 1) % n) for j in range(n)]

    def partial_at(k_c, v_c, blk_idx):
        return flash_partial(
            q3, k_c, v_c, scale, causal, q_off,
            blk_idx * t_loc if causal else 0,
            block_q, block_k,
        )

    if not bidirectional or n <= 2:

        def hop(carry, s):
            pv, m, l, k_c, v_c = carry
            triple = partial_at(k_c, v_c, (me + s) % n)
            pv, m, l = _merge_triple((pv, m, l), triple)
            k_c = lax.ppermute(k_c, axis_name, perm_fwd)
            v_c = lax.ppermute(v_c, axis_name, perm_fwd)
            return (pv, m, l, k_c, v_c), None

        # k/v come home after n rotations; scan keeps one hop's buffers live
        (pv, m, l, k3, v3), _ = lax.scan(
            hop, (pv0, m0, l0, k3, v3), jnp.arange(n)
        )
    else:
        perm_bwd = [(j, (j + 1) % n) for j in range(n)]
        acc = _merge_triple((pv0, m0, l0), partial_at(k3, v3, me))
        n_hops, use_bwd = _bidir_plan(n)

        def hop2(carry, xs):
            s, bwd_ok = xs
            pv, m, l, k_f, v_f, k_b, v_b = carry
            k_f = lax.ppermute(k_f, axis_name, perm_fwd)
            v_f = lax.ppermute(v_f, axis_name, perm_fwd)
            k_b = lax.ppermute(k_b, axis_name, perm_bwd)
            v_b = lax.ppermute(v_b, axis_name, perm_bwd)
            acc = _merge_triple(
                (pv, m, l), partial_at(k_f, v_f, (me + s) % n)
            )
            tb = _mask_triple(bwd_ok, partial_at(k_b, v_b, (me - s) % n))
            acc = _merge_triple(acc, tb)
            return (*acc, k_f, v_f, k_b, v_b), None

        (pv, m, l, *_), _ = lax.scan(
            hop2,
            (*acc, k3, v3, k3, v3),
            (jnp.arange(1, n_hops + 1), jnp.asarray(use_bwd)),
        )
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o3 = pv / l_safe[..., None]
    lse = m + jnp.log(l_safe)
    o = _unfold_heads(o3, b, h).astype(q.dtype)
    return o, (q3, k3, v3, o3, lse)


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal, scale, block_q, block_k,
                        bidirectional):
    return _ring_flash_fwd(
        q, k, v, axis_name, causal, scale, block_q, block_k, bidirectional
    )


def _ring_flash_vjp_bwd(axis_name, causal, scale, block_q, block_k,
                        bidirectional, res, do):
    from ..ops.flash_attention import flash_grads_partial

    q3, k3, v3, o3, lse = res
    b, t_loc, h, _ = do.shape  # static shape/dtype info rides on the cotangent
    in_dtype = do.dtype
    n = lax.axis_size(axis_name)
    # same rule as _ring_flash_fwd: only the causal mask consumes global
    # positions, and a dead axis_index strands an unplaceable partition-id
    me = lax.axis_index(axis_name) if causal else 0
    if scale is None:
        scale = 1.0 / (q3.shape[-1] ** 0.5)  # the query/key width, not do's
    do3 = _fold_heads(do).astype(q3.dtype)
    delta = jnp.sum(do3.astype(jnp.float32) * o3, axis=-1)  # [BH, T_loc]
    q_off = me * t_loc
    perm_fwd = [(j, (j - 1) % n) for j in range(n)]

    dq0 = jnp.zeros(q3.shape, jnp.float32)
    dk0 = jnp.zeros(k3.shape, jnp.float32)
    dv0 = jnp.zeros(v3.shape, jnp.float32)

    def grads_at(k_c, v_c, blk_idx):
        return flash_grads_partial(
            q3, k_c, v_c, do3, lse, delta, scale, causal,
            q_off, blk_idx * t_loc if causal else 0, block_q, block_k,
        )

    if not bidirectional or n <= 2:

        def hop(carry, s):
            dq, k_c, v_c, dk_c, dv_c = carry
            dq_h, dk_h, dv_h = grads_at(k_c, v_c, (me + s) % n)
            dq = dq + dq_h
            dk_c = dk_c + dk_h
            dv_c = dv_c + dv_h
            # dk/dv accumulators travel WITH their k/v shard; after n
            # rotations every shard (and its gradient) is home
            k_c = lax.ppermute(k_c, axis_name, perm_fwd)
            v_c = lax.ppermute(v_c, axis_name, perm_fwd)
            dk_c = lax.ppermute(dk_c, axis_name, perm_fwd)
            dv_c = lax.ppermute(dv_c, axis_name, perm_fwd)
            return (dq, k_c, v_c, dk_c, dv_c), None

        (dq, _, _, dk, dv), _ = lax.scan(
            hop, (dq0, k3, v3, dk0, dv0), jnp.arange(n)
        )
    else:
        perm_bwd = [(j, (j + 1) % n) for j in range(n)]
        dq, dk_own, dv_own = grads_at(k3, v3, me)  # own block, no comm
        n_hops, use_bwd = _bidir_plan(n)

        def hop2(carry, xs):
            s, bwd_ok = xs
            dq, k_f, v_f, dk_f, dv_f, k_b, v_b, dk_b, dv_b = carry
            k_f = lax.ppermute(k_f, axis_name, perm_fwd)
            v_f = lax.ppermute(v_f, axis_name, perm_fwd)
            dk_f = lax.ppermute(dk_f, axis_name, perm_fwd)
            dv_f = lax.ppermute(dv_f, axis_name, perm_fwd)
            k_b = lax.ppermute(k_b, axis_name, perm_bwd)
            v_b = lax.ppermute(v_b, axis_name, perm_bwd)
            dk_b = lax.ppermute(dk_b, axis_name, perm_bwd)
            dv_b = lax.ppermute(dv_b, axis_name, perm_bwd)
            dq_f, dkh_f, dvh_f = grads_at(k_f, v_f, (me + s) % n)
            dq_b, dkh_b, dvh_b = grads_at(k_b, v_b, (me - s) % n)
            dq = dq + dq_f + jnp.where(bwd_ok, dq_b, 0.0)
            dk_f = dk_f + dkh_f
            dv_f = dv_f + dvh_f
            dk_b = dk_b + jnp.where(bwd_ok, dkh_b, 0.0)
            dv_b = dv_b + jnp.where(bwd_ok, dvh_b, 0.0)
            return (dq, k_f, v_f, dk_f, dv_f, k_b, v_b, dk_b, dv_b), None

        (dq, _, _, dk_f, dv_f, _, _, dk_b, dv_b), _ = lax.scan(
            hop2,
            (dq, k3, v3, dk0, dv0, k3, v3, dk0, dv0),
            (jnp.arange(1, n_hops + 1), jnp.asarray(use_bwd)),
        )
        # deliver the traveling accumulators home in ONE rotation each:
        # after n_hops fwd rotations, device j's fwd accumulator describes
        # block (j + n_hops) % n -> send to that device; mirror for bwd
        home_f = [(j, (j + n_hops) % n) for j in range(n)]
        home_b = [(j, (j - n_hops) % n) for j in range(n)]
        dk = (
            dk_own
            + lax.ppermute(dk_f, axis_name, home_f)
            + lax.ppermute(dk_b, axis_name, home_b)
        )
        dv = (
            dv_own
            + lax.ppermute(dv_f, axis_name, home_f)
            + lax.ppermute(dv_b, axis_name, home_b)
        )

    unfold = lambda x3: _unfold_heads(x3, b, h).astype(in_dtype)
    return unfold(dq), unfold(dk), unfold(dv)


ring_flash_attention.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def full_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Single-device reference: exact softmax attention, [B, T, H, D].
    `causal` is False | True | an ops/flash_attention.SlidingWindow (the
    mask kinds flash_attention takes, whose jnp twin this is)."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    # f32 softmax regardless of input dtype (matches the ring/flash paths)
    scores = (
        jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
        * scale
    )
    if isinstance(causal, tuple):  # a mask kind of ops/flash_attention.py
        from ..ops.flash_attention import dense_mask

        mask = dense_mask(causal, q.shape[1], k.shape[1])
        scores = jnp.where(mask[None, None], scores, _NEG_BIG)
    elif causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        scores = jnp.where(mask[None, None], scores, _NEG_BIG)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def make_seq_mesh(num_shards: Optional[int] = None) -> Mesh:
    """1-D sequence-parallel mesh (axis 'seq')."""
    from .mesh import make_mesh

    return make_mesh(num_workers=num_shards, axis_name=SEQ_AXIS)


def make_ring_attention(
    mesh: Mesh,
    axis_name: str = SEQ_AXIS,
    causal: bool = False,
    bidirectional: bool = False,
    impl: str = "naive",
):
    """Jitted sequence-sharded attention: (q, k, v) [B, T, H, D] global ->
    [B, T, H, D] global, T sharded over the mesh axis.

    impl="flash" uses the Pallas partial-triple kernel per hop
    (ring_flash_attention), one-way or bidirectional."""
    if impl == "flash":
        fn = partial(
            ring_flash_attention, axis_name=axis_name, causal=causal,
            bidirectional=bidirectional,
        )
    else:
        fn = partial(
            ring_attention,
            axis_name=axis_name,
            causal=causal,
            bidirectional=bidirectional,
        )
    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(None, axis_name), P(None, axis_name), P(None, axis_name)),
        out_specs=P(None, axis_name),
        check_vma=False,
    )
    return jax.jit(mapped)


def shard_sequence(x: jax.Array, mesh: Mesh, axis_name: str = SEQ_AXIS):
    """Place [B, T, ...] with T sharded along the mesh axis."""
    return jax.device_put(x, NamedSharding(mesh, P(None, axis_name)))
