"""Gradient-aggregation collectives: the TPU-native replacement for the
reference master's Irecv/waitany gather loop and Blosc codec.

Reference semantics being reproduced (see SURVEY.md section 3.2):
- plain aggregation: sum of per-worker gradients divided by num_aggregate
  (sync_replicas_master_nn.py:204-208) -> `psum_mean`
- partial ("backup-worker") aggregation: only the first K of N gradients per
  layer are added, but the step is still synchronous
  (sync_replicas_master_nn.py:179-186,207) -> `aggregation_mask`, applied
  before the psum. `random_k` models "first K to *arrive*" (arrival order is
  nondeterministic in the reference); `first_k` is the deterministic variant.
- compressed communication: Blosc/snappy byte compression of each gradient
  (compression.py:18-31) -> int8 uniform quantization on the reduce path
  (`quantized_psum`): quantize with a global per-tensor scale, sum in int32,
  dequantize. Same capability (bandwidth reduction), hardware-native form.
  The Pallas TPU kernels for the quantize/dequantize hot path live in
  ops/quantize.py; this module wires them into the collective.
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.quantize import (
    accum_dtype,
    accumulate_rescale_int8,
    dequantize_int8,
    quantize_int8,
)
from .buckets import piece_stream


def aggregation_mask(
    axis_name: str,
    num_workers: int,
    num_aggregate,
    key: Optional[jax.Array] = None,
    mode: str = "random_k",
) -> jax.Array:
    """Per-worker {0,1} scalar: does this worker's gradient enter the sum?

    Must be called inside shard_map/pmap over `axis_name`. With
    num_aggregate None or >= num_workers, every worker participates.

    ``num_aggregate`` may be a TRACED int32 scalar (the adaptive partial
    aggregation path, resilience/elastic.py): the selection is then
    computed with dynamic-k arithmetic — ``random_k`` via the rank of
    each worker in the shared permutation (worker w is selected iff
    argsort(perm)[w] < k, exactly the set perm[:k] the static spelling
    builds), ``first_k`` via the same ``w < k`` compare. A traced k equal
    to num_workers yields a mask of exactly 1.0 everywhere, so the
    full-mask adaptive step multiplies by 1.0 — bit-exact against the
    static no-mask path."""
    dynamic = isinstance(num_aggregate, jax.Array)
    if not dynamic and (
        num_aggregate is None or num_aggregate >= num_workers
    ):
        return jnp.float32(1.0)
    w = lax.axis_index(axis_name)
    if mode == "first_k":
        return (w < num_aggregate).astype(jnp.float32)
    if mode == "random_k":
        if key is None:
            raise ValueError("random_k masking needs a (replicated) PRNG key")
        perm = jax.random.permutation(key, num_workers)
        if dynamic:
            # rank[w] = position of worker w in perm; rank < k <=> w is in
            # perm[:k] — same selected set as the static scatter below,
            # but expressible with a traced k
            rank = jnp.argsort(perm)
            return (rank[w] < num_aggregate).astype(jnp.float32)
        selected = jnp.zeros((num_workers,), jnp.float32).at[perm[:num_aggregate]].set(1.0)
        return selected[w]
    raise ValueError(f"unknown aggregation mode {mode!r}")


def _bucket_scope(pipelined: bool, key_id):
    """Named scope for one bucket's reduce chain (pipelined mode only):
    the per-bucket span names (``bucket_reduce_o<start offset>``) that
    profiler timelines and tools/trace_report.py's overlap analysis key
    on. Serial mode stays scope-free so its lowering is untouched."""
    if not pipelined:
        return contextlib.nullcontext()
    return jax.named_scope(f"bucket_reduce_o{int(key_id)}")


def psum_mean(tree, axis_name: str, denominator: float,
              bucket_bytes: Optional[int] = None,
              flat_output: bool = False, pipelined: bool = False,
              bucket_output: bool = False):
    """Sum over workers / denominator (parity: _model_update divides the
    aggregate buffer by num_aggregate, sync_replicas_master_nn.py:204-207).

    ``bucket_bytes`` (buckets.piece_stream) ships the fused flat f32
    buckets instead of the raw leaves — bit-exact for f32 gradients
    (same values, same elementwise sum/divide), and the collective
    operands become a few contiguous buffers instead of one per leaf.
    ``flat_output`` (what the PS step asks for: its state is flat)
    returns the aggregate as one padded flat vector instead of
    scattering it back into the tree; the collectives themselves are
    identical (jax batches a whole-tree psum into one eqn either way).

    ``pipelined`` (PSConfig.overlap) emits ONE psum eqn per bucket, in
    readiness order, over buckets assembled from their own leaves — same
    buckets, same bytes, bit-identical values, but each bucket's reduce
    is dataflow-independent of the rest of the backward so a
    latency-hiding scheduler can overlap them (serial's fused psum over
    the global concat cannot start until every gradient exists).
    ``bucket_output`` returns the canonical-order list of per-bucket
    aggregates for the per-bucket vector update."""
    if bucket_bytes is None and not flat_output:
        summed = lax.psum(tree, axis_name)
        return jax.tree_util.tree_map(lambda g: g / denominator, summed)
    pieces, key_ids, rebuild = piece_stream(
        tree, bucket_bytes, flat_output=flat_output, pipelined=pipelined,
        bucket_output=bucket_output,
    )
    if pipelined:
        outs = []
        for i, g in zip(key_ids, pieces):
            with _bucket_scope(True, i):
                outs.append(lax.psum(g, axis_name) / denominator)
        return rebuild(outs)
    summed = lax.psum(pieces, axis_name)  # one fused eqn over the buckets
    return rebuild([s / denominator for s in summed])


def quantized_psum(
    tree,
    axis_name: str,
    denominator: float,
    block_size: int = 0,
    rounding: str = "nearest",
    key: Optional[jax.Array] = None,
    bucket_bytes: Optional[int] = None,
    flat_output: bool = False,
    pipelined: bool = False,
    bucket_output: bool = False,
    wire_domain: str = "dequant",
    num_workers: Optional[int] = None,
):
    """int8-quantized gradient all-reduce.

    Per piece: global absmax (pmax) -> symmetric int8 quantize -> int32 psum
    -> dequantize / denominator. Deterministic (same scale on all workers) and
    exact-sum in int32 (no overflow below 2^23 workers). `block_size` > 0
    switches to per-block scales for tighter quantization error; `rounding=
    "stochastic"` makes each worker's quantization unbiased with independent
    noise (key folded by worker index and piece id), so rounding error
    averages out across the psum instead of accumulating (capabilities beyond
    the reference's lossless-but-slow Blosc path).

    ``wire_domain="homomorphic"`` (PSConfig.wire_domain) is the THC-style
    compressed-domain spelling of the same sum: the scales are already
    shared (the pmax), so the psum rides the MINIMAL exact accumulator
    dtype for ``num_workers`` summands (ops/quantize.accum_dtype — int16
    through 258 workers, halving the dequant path's int32 wire) and the
    division by ``denominator`` folds into the single deferred
    scale-multiply at the consumer. The accumulation itself is bit-exact
    either way (integer sums); only the wire bytes and the final
    multiply's association differ.

    A piece is one pytree leaf (``bucket_bytes=None``, the reference's
    message-per-layer shape) or one fused flat bucket (buckets.py) — the
    latter collapses O(n_leaves) pmax+psum pairs into O(n_buckets), with
    bucket boundaries aligned to ``block_size`` so no scale row straddles
    buckets and PRNG keys folded by bucket start offset (position-stable).
    """
    if wire_domain == "homomorphic":
        if num_workers is None:
            raise ValueError(
                "homomorphic quantized_psum needs num_workers (it sizes "
                "the exact accumulator dtype)"
            )
        if rounding == "stochastic":
            raise ValueError(
                "homomorphic wire needs rounding='nearest' (per-worker "
                "stochastic noise is incoherent on a shared lattice)"
            )
    if rounding == "stochastic":
        if key is None:
            raise ValueError("stochastic rounding needs a key")
        key = jax.random.fold_in(key, lax.axis_index(axis_name))

    def one(i, g):
        g32 = g.astype(jnp.float32)
        leaf_key = jax.random.fold_in(key, i) if key is not None else None
        q, scale = quantize_int8(
            g32,
            axis_name=axis_name,
            block_size=block_size,
            rounding=rounding,
            key=leaf_key,
        )
        if wire_domain == "homomorphic":
            # compressed-domain sum: narrow exact accumulator on the
            # wire, ONE deferred scale-multiply (the denominator folds
            # into the shared scale) at the consumer
            s = lax.psum(q.astype(accum_dtype(num_workers)), axis_name)
            return dequantize_int8(
                s, scale / denominator, block_size=block_size, shape=g.shape
            )
        s = lax.psum(q.astype(jnp.int32), axis_name)
        deq = dequantize_int8(s, scale, block_size=block_size, shape=g.shape)
        return deq / denominator

    pieces, key_ids, rebuild = piece_stream(
        tree, bucket_bytes, align=block_size or 1, flat_output=flat_output,
        pipelined=pipelined, bucket_output=bucket_output,
    )
    outs = []
    for i, g in zip(key_ids, pieces):
        with _bucket_scope(pipelined, i):
            outs.append(one(i, g))
    return rebuild(outs)


def _slice_len(total: int, n: int, block_size: int) -> int:
    """Per-worker region length: ceil(total/n) rounded up to whole
    quantization blocks."""
    bs = block_size or 1
    return (-(-total // n) + bs - 1) // bs * bs


def _q2r_scatter_stage(g32, axis_name, n, s, block_size, rounding, leaf_key):
    """Round 1 of the 2-round scheme for one flat padded [n*s] leaf:
    shared-scale int8 quantize -> all_to_all int8 -> local int32 sum ->
    dequantize MY region. Returns the f32 partial sum [s] — an int8-wire
    reduce_scatter."""
    q1, scale1 = quantize_int8(
        g32,
        axis_name=axis_name,  # shared (pmax) scales: replicated rows
        block_size=block_size,
        rounding=rounding,
        key=leaf_key,
    )
    q1 = q1.reshape(n, s).astype(jnp.int8)
    # row j of the a2a result = device j's slice of MY region
    recv = lax.all_to_all(q1, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)
    partial = jnp.sum(recv.astype(jnp.int32), axis=0)  # [s]
    w = lax.axis_index(axis_name)
    if block_size:
        nb_loc = s // block_size
        my_scales = lax.dynamic_slice(scale1, (w * nb_loc, 0), (nb_loc, 1))
        partial = (
            partial.reshape(nb_loc, block_size).astype(jnp.float32)
            * my_scales
        ).reshape(-1)
    else:
        partial = partial.astype(jnp.float32) * scale1
    return partial


def _q2r_scatter_stage_hom(g32, wire_axis, scale_axes, n, s, block_size):
    """Homomorphic round 1 for one flat padded [n*s] piece: SHARED-scale
    (pmax over ``scale_axes`` — the whole reducing axis set, so one scale
    row set serves every worker) int8 quantize -> all_to_all int8 over
    ``wire_axis``. Returns ``(recv [n, s] int8, scale)`` — the received
    worker rows of MY region, un-accumulated so the caller can fuse the
    exact integer accumulation with its lattice rescale
    (ops/quantize.accumulate_rescale_int8, one Pallas VPU pass on TPU).
    The scale rows cover the WHOLE padded vector and are replicated on
    every worker by the pmax, so any consumer can dequantize any region
    with zero scale traffic."""
    q1, scale1 = quantize_int8(
        g32, axis_name=scale_axes, block_size=block_size
    )
    q1 = q1.reshape(n, s).astype(jnp.int8)
    recv = lax.all_to_all(q1, wire_axis, split_axis=0, concat_axis=0,
                          tiled=True)
    return recv, scale1


def _deq_shared(full, scale, gain, block_size):
    """THE single deferred scale-multiply of the homomorphic wire: int8
    payload x (shared scale x gain) -> f32, per block row or per tensor.
    ``gain`` folds the lattice-rescale factors and the aggregation
    denominator back in (it may be traced)."""
    if block_size:
        return (
            full.reshape(-1, block_size).astype(jnp.float32)
            * (scale * gain)
        ).reshape(-1)
    return full.astype(jnp.float32) * (scale * gain)


def _q2r_gather_stage(partial, axis_name, n, s, block_size, rounding, key2):
    """Round 2: requantize the [s] partial sum with LOCAL scales (regions
    are disjoint, so no cross-worker scale agreement is needed) and
    all_gather int8 (+ tiny f32 scale rows) -> dequantized full [n*s]."""
    q2, scale2 = quantize_int8(
        partial, block_size=block_size, rounding=rounding, key=key2
    )
    q2 = q2.reshape(-1).astype(jnp.int8)
    full = lax.all_gather(q2, axis_name, tiled=True)  # int8 on the wire
    if block_size:
        scales2 = lax.all_gather(scale2, axis_name, tiled=True)  # [nb,1]
        deq = (
            full.reshape(-1, block_size).astype(jnp.float32) * scales2
        ).reshape(-1)
    else:
        scales2 = lax.all_gather(scale2.reshape(1), axis_name, tiled=True)
        deq = (
            full.reshape(n, s).astype(jnp.float32) * scales2[:, None]
        ).reshape(-1)
    return deq


def quantized_allreduce_2round(
    tree,
    axis_name: str,
    denominator: float,
    num_workers: int,
    block_size: int = 0,
    rounding: str = "nearest",
    key: Optional[jax.Array] = None,
    bucket_bytes: Optional[int] = None,
    flat_output: bool = False,
    pipelined: bool = False,
    bucket_output: bool = False,
    wire_domain: str = "dequant",
):
    """Two-round int8 all-reduce whose WIRE traffic is actually int8.

    `quantized_psum` sums int8 payloads in an int32 psum — exact, but the
    bytes on the interconnect are int32, so it compresses compute, not
    bandwidth. This is the bandwidth-honest scheme (the compressed
    multi-hop all-reduce family — THC/DynamiQ, PAPERS.md): per leaf,

        flatten -> pad to [n, s] -> int8 quantize (round 1, shared
        per-block scales via pmax) -> all_to_all int8 (each worker
        receives everyone's slice of ITS region) -> local int32 sum ->
        requantize the partial sum (round 2, local scales) -> all_gather
        int8 (+ tiny f32 scale rows) -> dequantize / denominator.

    ~2 int8 bytes/element on the wire per device vs ~8 for an f32 ring
    psum — a true 4x reduction, at the cost of a second (per-block-scaled)
    quantization on the partial sums. That round-2 noise is NOT tracked by
    the EF residual (which mirrors round 1 only); measured on real LeNet
    gradients it is ~1.5e-2 of the aggregate's norm with per-tensor scales
    and ~8e-3 with block-128 scales
    (tests/test_compression.py::test_ef_untracked_round2_noise_measured).
    The result is identical on every worker by construction (it is
    all_gathered).

    ``wire_domain="homomorphic"``: round 2's widen -> requantize (and its
    f32 scale-row gather) disappears entirely — the exact int32
    accumulation of MY region is lattice-rescaled by the aggregation
    denominator (``ops/quantize.homomorphic_rescale``: round(acc / k)
    provably fits int8, since |acc| <= k * 127 on the shared lattice),
    all_gathered as int8, and dequantized by ONE deferred scale-multiply
    with the round-1 scale rows every worker already holds from the
    pmax. The only lossy step beyond round 1 is that single deterministic
    rounding at the shared scale's granularity (vs the dequant path's
    adaptively-rescaled round-2 requantization — comparable envelope,
    zero extra wire rows). Requires ``rounding="nearest"`` (PSConfig
    enforces it: per-worker stochastic noise has no coherent meaning on
    a shared lattice rescale).
    """
    n = num_workers
    if wire_domain == "homomorphic" and rounding == "stochastic":
        raise ValueError(
            "homomorphic wire needs rounding='nearest' (per-worker "
            "stochastic noise is incoherent on a shared lattice)"
        )
    # same key discipline as quantized_psum / local_quantized_contribution
    # (fold worker first, leaf second) so error-feedback residuals mirror
    # the transmitted values exactly
    if rounding == "stochastic":
        if key is None:
            raise ValueError("stochastic rounding needs a key")
        key = jax.random.fold_in(key, lax.axis_index(axis_name))

    def one(i, g):
        g32 = g.astype(jnp.float32).reshape(-1)
        total = g32.shape[0]
        s = _slice_len(total, n, block_size)
        g32 = jnp.pad(g32, (0, n * s - total))
        if wire_domain == "homomorphic":
            recv, scale1 = _q2r_scatter_stage_hom(
                g32, axis_name, axis_name, n, s, block_size
            )
            q2 = accumulate_rescale_int8(recv, denominator)
            full = lax.all_gather(q2, axis_name, tiled=True)  # int8, no
            # scale rows: every worker holds the shared rows already
            deq = _deq_shared(full, scale1, 1.0, block_size)
            return deq[:total].reshape(g.shape)  # denominator folded in
        leaf_key = jax.random.fold_in(key, i) if key is not None else None
        partial = _q2r_scatter_stage(
            g32, axis_name, n, s, block_size, rounding, leaf_key
        )
        k2 = jax.random.fold_in(leaf_key, 1) if leaf_key is not None else None
        deq = _q2r_gather_stage(
            partial, axis_name, n, s, block_size, rounding, k2
        )
        return (deq[:total] / denominator).reshape(g.shape)

    pieces, key_ids, rebuild = piece_stream(
        tree, bucket_bytes, align=block_size or 1, flat_output=flat_output,
        pipelined=pipelined, bucket_output=bucket_output,
    )
    outs = []
    for i, g in zip(key_ids, pieces):
        with _bucket_scope(pipelined, i):
            outs.append(one(i, g))
    return rebuild(outs)


def quantized_allreduce_2round_hier(
    tree,
    axis_names: tuple,
    denominator: float,
    axis_sizes: tuple,
    block_size: int = 0,
    rounding: str = "nearest",
    key: Optional[jax.Array] = None,
    bucket_bytes: Optional[int] = None,
    flat_output: bool = False,
    pipelined: bool = False,
    bucket_output: bool = False,
    wire_domain: str = "dequant",
):
    """Hierarchical (DCN x ICI) bandwidth-honest int8 all-reduce that
    crosses DCN exactly ONCE per gradient element.

    Naively composing two flat 2-round all-reduces would end the inner
    (ICI) round with an all_gather, leaving every ICI column holding the
    identical full host-sum — and then per_host redundant int8 copies of
    the whole gradient would cross the DCN bottleneck. Instead, per leaf:

      1. inner int8-wire reduce_scatter over ICI (round-1 stage only):
         each chip ends with the f32 partial sum of ITS 1/per_host
         region of the host total;
      2. a full 2-round int8 all-reduce over the DCN axis on that region
         alone — the ICI columns carry DISJOINT regions, so total DCN
         traffic is ~1 int8 byte/element regardless of per_host;
      3. one f32 all_gather over ICI reassembles the globally-summed
         vector (ICI bandwidth is an order of magnitude above DCN; the
         scheme spends bytes on the link that has them).

    axis_names = (dcn_axis, ici_axis); axis_sizes = (hosts, per_host).
    Round-1 quantization (the EF contribution transform) is shared-scale
    over the ICI axis with the key pre-folded by DCN index — mirror it
    with local_quantized_contribution(axis_names[1], key=dcn_folded_key).

    ``wire_domain="homomorphic"``: round-1 scales are shared GLOBALLY
    (one pmax over BOTH axes — one scale row set serves every chip on
    the mesh), so the accumulated payload stays on one lattice across
    every hop and NOTHING ever widens to f32 on the wire: the ICI
    partial sums lattice-rescale (/per_host) to int8 and cross DCN as
    int8, the DCN sums rescale (/hosts) and gather back as int8, and —
    the headline row — the ICI reassembly all_gather carries int8
    instead of the dequant path's f32 (4x smaller; the PSC103 hier
    reassembly allowance disappears). The consumer applies ONE deferred
    scale-multiply with gain (per_host * hosts) / denominator folding
    the exact aggregation count back in. Mirror the EF contribution
    with local_quantized_contribution over the FULL axis tuple."""
    dcn_axis, ici_axis = axis_names
    hosts, per_host = axis_sizes
    if wire_domain == "homomorphic" and rounding == "stochastic":
        raise ValueError(
            "homomorphic wire needs rounding='nearest' (per-worker "
            "stochastic noise is incoherent on a shared lattice)"
        )
    if rounding == "stochastic":
        if key is None:
            raise ValueError("stochastic rounding needs a key")
        # decorrelate across hosts FIRST (same-ICI-index chips on
        # different hosts must not draw identical noise), then per chip
        key = jax.random.fold_in(key, lax.axis_index(dcn_axis))
        key = jax.random.fold_in(key, lax.axis_index(ici_axis))

    def one_hom(i, g):
        g32 = g.astype(jnp.float32).reshape(-1)
        total = g32.shape[0]
        s1 = _slice_len(total, per_host, block_size)
        g32 = jnp.pad(g32, (0, per_host * s1 - total))
        # 1. ICI: shared-GLOBAL-scale quantize, int8 a2a, exact int sum
        recv1, scale1 = _q2r_scatter_stage_hom(
            g32, ici_axis, axis_names, per_host, s1, block_size
        )
        # 2. DCN hop forwards the accumulated payload on the SAME
        # lattice: fused accumulate+rescale /per_host back into int8
        # range (|acc| <= per_host * 127), a2a int8, fused
        # accumulate+rescale /hosts
        q_mid = accumulate_rescale_int8(recv1, float(per_host))
        s2 = _slice_len(s1, hosts, block_size)
        q_mid = jnp.pad(q_mid, (0, hosts * s2 - s1))
        recv2 = lax.all_to_all(
            q_mid.reshape(hosts, s2), dcn_axis, split_axis=0,
            concat_axis=0, tiled=True,
        )
        q2 = accumulate_rescale_int8(recv2, float(hosts))
        region = lax.all_gather(q2, dcn_axis, tiled=True)[:s1]
        # 3. reassemble over ICI — int8, the hop the dequant path pays
        # f32 for; then the single deferred scale-multiply, with the
        # rescale factors and the true denominator folded into the gain
        full = lax.all_gather(region, ici_axis, tiled=True)
        gain = (per_host * hosts) / denominator
        deq = _deq_shared(full, scale1, gain, block_size)
        return deq[:total].reshape(g.shape)

    def one(i, g):
        if wire_domain == "homomorphic":
            return one_hom(i, g)
        g32 = g.astype(jnp.float32).reshape(-1)
        total = g32.shape[0]
        s1 = _slice_len(total, per_host, block_size)
        g32 = jnp.pad(g32, (0, per_host * s1 - total))
        leaf_key = jax.random.fold_in(key, i) if key is not None else None
        # 1. ICI reduce_scatter: my [s1] region of the host sum (the
        # EF-mirrored transform; the DCN hop's requantization is
        # untracked round-2-style noise, same as the flat scheme's)
        partial = _q2r_scatter_stage(
            g32, ici_axis, per_host, s1, block_size, rounding, leaf_key
        )
        # 2. full 2-round over DCN on the region only
        s2 = _slice_len(s1, hosts, block_size)
        partial = jnp.pad(partial, (0, hosts * s2 - s1))
        k_dcn = (
            jax.random.fold_in(leaf_key, 2) if leaf_key is not None else None
        )
        p2 = _q2r_scatter_stage(
            partial, dcn_axis, hosts, s2, block_size, rounding, k_dcn
        )
        k2 = jax.random.fold_in(k_dcn, 1) if k_dcn is not None else None
        region = _q2r_gather_stage(
            p2, dcn_axis, hosts, s2, block_size, rounding, k2
        )[:s1]
        # 3. reassemble over ICI (f32; ICI is the cheap link)
        full = lax.all_gather(region, ici_axis, tiled=True)
        return (full[:total] / denominator).reshape(g.shape)

    pieces, key_ids, rebuild = piece_stream(
        tree, bucket_bytes, align=block_size or 1, flat_output=flat_output,
        pipelined=pipelined, bucket_output=bucket_output,
    )
    outs = []
    for i, g in zip(key_ids, pieces):
        with _bucket_scope(pipelined, i):
            outs.append(one(i, g))
    return rebuild(outs)


def local_quantized_contribution(
    grads,
    axis_name: str,
    block_size: int = 0,
    rounding: str = "nearest",
    key: Optional[jax.Array] = None,
    bucket_bytes: Optional[int] = None,
    pipelined: bool = False,
):
    """What THIS worker's gradient becomes after its (shared-scale) int8
    round trip — the transmitted value whose difference from the true
    gradient is the error-feedback residual. Mirrors quantized_psum /
    round 1 of the 2-round scheme exactly (same scales, same rounding
    keys, same bucketing and key-fold discipline), so `residual = g -
    contribution` is the real on-wire error."""
    if rounding == "stochastic":
        if key is None:
            raise ValueError("stochastic rounding needs a key")
        key = jax.random.fold_in(key, lax.axis_index(axis_name))

    def one(i, g):
        g32 = g.astype(jnp.float32)
        leaf_key = jax.random.fold_in(key, i) if key is not None else None
        q, scale = quantize_int8(
            g32,
            axis_name=axis_name,
            block_size=block_size,
            rounding=rounding,
            key=leaf_key,
        )
        return dequantize_int8(
            q.astype(jnp.int32), scale, block_size=block_size, shape=g.shape
        )

    pieces, key_ids, rebuild = piece_stream(
        grads, bucket_bytes, align=block_size or 1, pipelined=pipelined
    )
    return rebuild([one(i, g) for i, g in zip(key_ids, pieces)])


def aggregate_gradients(
    grads,
    axis_name: str,
    num_workers: int,
    num_aggregate=None,
    mask_key: Optional[jax.Array] = None,
    mask_mode: str = "random_k",
    compress: Optional[str] = None,
    quant_block_size: int = 0,
    quant_rounding: str = "nearest",
    quant_key: Optional[jax.Array] = None,
    return_contribution: bool = False,
    axis_sizes: Optional[tuple] = None,
    bucket_bytes: Optional[int] = None,
    flat_output: bool = False,
    pipelined: bool = False,
    bucket_output: bool = False,
    wire_domain: str = "dequant",
):
    """The full PS aggregation: mask -> (bucket) -> (quantized) reduce -> / K.

    ``bucket_bytes`` selects the wire granularity (PSConfig.bucket_bytes):
    ``None`` = the legacy message-per-leaf shape, ``0`` = one fused flat
    buffer, ``N`` = ~N-byte buckets. Every scheme and the EF contribution
    share the same piece stream (buckets.piece_stream), so residuals
    mirror the transmitted values exactly in either granularity.

    ``flat_output`` (what the PS step asks for: its state is flat)
    returns the AGGREGATE as one padded flat f32 vector — the shape the
    fused vector update consumes — instead of scattering it back into
    the gradient tree. It is compute-side only: the masking,
    quantization, and every collective are byte-identical to the tree
    output, and the EF contribution (when requested) stays TREE-shaped
    because the per-worker residual state is per-leaf
    (checkpoint-portable across bucket settings).

    return_contribution=True additionally returns THIS worker's
    transmitted (post-mask, post-quantization-round-trip) value — what
    error feedback subtracts from the pre-aggregation gradient to get the
    true on-wire residual. The masking and compress dispatch live HERE
    only; the EF path in ps.py must not re-implement them.

    A TUPLE axis_name (hierarchical DCN x ICI data parallelism) with
    compress="int8_2round" runs the HIERARCHICAL 2-round scheme:
    bandwidth-honest int8 all-reduce over ICI within each host first
    (denominator 1), then the same scheme across the DCN axis on the
    host-local sums — every wire crossing, intra- and inter-host, carries
    int8. Requires `axis_sizes` = (hosts, workers_per_host). The EF
    contribution mirrors the INNER ring's round-1 transform; the DCN
    round's requantization noise is not residual-tracked — measured at
    ~1e-2 of the aggregate's norm (halved by block-128 scales) for the
    flat scheme's round 2, the same transform
    (tests/test_compression.py::test_ef_untracked_round2_noise_measured).

    ``num_aggregate`` may be a TRACED int32 scalar (adaptive partial
    aggregation): the mask is then always applied (1.0 everywhere when
    the traced count equals num_workers — bit-exact against the static
    no-mask path on power-of-two meshes) and the denominator is the
    traced count itself, so the aggregate stays an average over the
    selected set at every count without retracing."""
    if wire_domain not in ("dequant", "homomorphic"):
        raise ValueError(f"bad wire_domain {wire_domain!r}")
    if wire_domain == "homomorphic":
        if compress in (None, "none"):
            raise ValueError(
                "wire_domain='homomorphic' needs a compress mode — an "
                "uncompressed f32 psum has no compressed domain to sum in"
            )
        if quant_rounding == "stochastic":
            raise ValueError(
                "wire_domain='homomorphic' needs quant_rounding='nearest'"
            )
    dynamic = isinstance(num_aggregate, jax.Array)
    if dynamic:
        k = num_aggregate.astype(jnp.float32)
    else:
        k = (
            num_aggregate
            if (num_aggregate is not None and num_aggregate < num_workers)
            else num_workers
        )
    hier_2round = compress == "int8_2round" and isinstance(
        axis_name, (tuple, list)
    )
    if dynamic or k != num_workers:
        sel = aggregation_mask(axis_name, num_workers, num_aggregate, mask_key, mask_mode)
        grads = jax.tree_util.tree_map(lambda g: g * sel.astype(g.dtype), grads)
    denom = k if dynamic else float(k)
    if compress in (None, "none"):
        agg = psum_mean(grads, axis_name, denom,
                        bucket_bytes=bucket_bytes, flat_output=flat_output,
                        pipelined=pipelined, bucket_output=bucket_output)
        contribution = grads  # lossless transmit: residual is zero
    elif compress == "int8":
        agg = quantized_psum(
            grads,
            axis_name,
            denom,
            block_size=quant_block_size,
            rounding=quant_rounding,
            key=quant_key,
            bucket_bytes=bucket_bytes,
            flat_output=flat_output,
            pipelined=pipelined,
            bucket_output=bucket_output,
            wire_domain=wire_domain,
            num_workers=num_workers,
        )
        contribution = None
    elif hier_2round:
        if axis_sizes is None:
            raise ValueError(
                "hierarchical int8_2round needs axis_sizes=(hosts, "
                "workers_per_host)"
            )
        agg = quantized_allreduce_2round_hier(
            grads,
            tuple(axis_name),
            denom,
            tuple(axis_sizes),
            block_size=quant_block_size,
            rounding=quant_rounding,
            key=quant_key,
            bucket_bytes=bucket_bytes,
            flat_output=flat_output,
            pipelined=pipelined,
            bucket_output=bucket_output,
            wire_domain=wire_domain,
        )
        contribution = None
    elif compress == "int8_2round":
        agg = quantized_allreduce_2round(
            grads,
            axis_name,
            denom,
            num_workers,
            block_size=quant_block_size,
            rounding=quant_rounding,
            key=quant_key,
            bucket_bytes=bucket_bytes,
            flat_output=flat_output,
            pipelined=pipelined,
            bucket_output=bucket_output,
            wire_domain=wire_domain,
        )
        contribution = None
    else:
        raise ValueError(f"unknown compression {compress!r}")
    if not return_contribution:
        return agg
    if contribution is None:  # quantized modes share the round-1 transform
        contrib_key = quant_key
        if hier_2round and quant_rounding == "stochastic" and quant_key is not None:
            # mirror the hier function's own fold chain (DCN index first,
            # then local_quantized_contribution's internal ICI fold) so
            # the residual tracks the transmitted values exactly
            contrib_key = jax.random.fold_in(
                quant_key, lax.axis_index(axis_name[0])
            )
        contribution = local_quantized_contribution(
            grads,
            # hierarchical 2round quantizes round 1 with scales shared
            # over the INNER (ICI) axis only — except on the homomorphic
            # wire, whose round-1 scales are GLOBAL (pmax over the full
            # axis tuple), so the residual must mirror that
            (
                tuple(axis_name)
                if hier_2round and wire_domain == "homomorphic"
                else (axis_name[1] if hier_2round else axis_name)
            ),
            block_size=quant_block_size,
            rounding=quant_rounding,
            key=contrib_key,
            bucket_bytes=bucket_bytes,
            pipelined=pipelined,
        )
    return agg, contribution
