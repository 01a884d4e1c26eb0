"""Bucketed flat-buffer comm engine: one collective per bucket instead of
one per pytree leaf.

The reference PS sends one MPI message per layer (tag 88+l) and the
per-leaf collectives in collectives.py inherited that shape: a
ResNet/transformer gradient pytree has dozens of leaves, so every step
pays dozens of small, latency-bound collectives. The fused-buffer
all-reduce family (DynamiQ / THC, PAPERS.md) gets the wire win by
aggregating first: flatten the whole gradient into one contiguous f32
buffer, carve it into a handful of fixed-size buckets, and ship each
bucket as ONE collective — O(n_buckets) instead of O(n_leaves).

Two layers, both pure shape bookkeeping (everything here is static
Python arithmetic; the arrays never leave the traced program):

- ``TreeLayout`` — a pytree's flat geometry: per-leaf shapes/dtypes and
  element offsets into the concatenated f32 vector. ``tree_to_flat`` /
  ``flat_to_tree`` round-trip every leaf bit-exactly (dtype and shape
  preserved, empty and odd-sized leaves included). This is the engine's
  replacement for the ad-hoc ``ravel_pytree`` in the ZeRO-1 path: same
  concat order (``tree_leaves``), explicit f32 wire dtype.
- ``BucketPlan`` — a partition of the (alignment-padded) flat buffer
  into contiguous buckets. Boundaries are aligned to the int8
  quantization block size, so no quantization block ever straddles a
  bucket: each bucket quantizes with its own scale row(s) and ships
  independently.

PRNG discipline: stochastic-rounding keys are folded by each bucket's
START OFFSET in the flat buffer (``BucketPlan.starts``), not by its
enumeration index — position-stable derivation, so a bucket's noise
stream is a function of where its bytes live, not of how many buckets
precede it (collectives.py ``key_offsets``).

``FlatVector`` is the third layer, the PS state's own layout: a
param-shaped quantity — master params, an optimizer moment — stored AS
the padded flat f32 vector, with its TreeLayout/BucketPlan riding along
as static pytree metadata. The tree view exists only where the forward
pass needs it (``flat_to_tree``, slices XLA fuses away); the optimizer
update, the non-finite-guard rollback, and the wire all operate on the
whole vector. Checkpoints stay TREE-SHAPED at the save/restore boundary:
FlatVector registers flax serialization handlers that convert at the
edge, so checkpoints are bit-portable across ``bucket_bytes``, and
pre-flat-state (tree-state) checkpoints load unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
from flax import serialization


def _align_up(n: int, align: int) -> int:
    return -(-n // align) * align


@dataclasses.dataclass(frozen=True)
class TreeLayout:
    """Static geometry of a pytree flattened into one f32 vector."""

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    offsets: Tuple[int, ...]   # element offset of each leaf in the flat vec
    total: int                 # total elements (unpadded)


def tree_layout(tree) -> TreeLayout:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes, dtypes, offsets = [], [], []
    off = 0
    for leaf in leaves:
        shapes.append(tuple(int(d) for d in jnp.shape(leaf)))
        dtypes.append(jnp.result_type(leaf))
        offsets.append(off)
        off += int(jnp.size(leaf))
    return TreeLayout(
        treedef=treedef,
        shapes=tuple(shapes),
        dtypes=tuple(dtypes),
        offsets=tuple(offsets),
        total=off,
    )


def tree_to_flat(tree) -> jax.Array:
    """Concatenate every leaf (tree_leaves order) into one f32 vector."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((0,), jnp.float32)
    return jnp.concatenate(
        [leaf.astype(jnp.float32).reshape(-1) for leaf in leaves]
    )


def flat_to_tree(layout: TreeLayout, flat: jax.Array):
    """Invert ``tree_to_flat``: slice per leaf, restore shape AND dtype.

    ``flat`` may be longer than ``layout.total`` (alignment padding);
    the tail is dropped."""
    leaves = []
    for shape, dtype, off in zip(layout.shapes, layout.dtypes,
                                 layout.offsets):
        n = 1
        for d in shape:
            n *= d
        leaves.append(
            jax.lax.slice(flat, (off,), (off + n,))
            .reshape(shape)
            .astype(dtype)
        )
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """A partition of the alignment-padded flat buffer into buckets."""

    total: int          # unpadded elements
    padded_total: int   # total rounded up to `align`
    align: int          # element alignment (int8 quantization block size)
    starts: Tuple[int, ...]  # bucket start offsets (== the PRNG fold keys)
    sizes: Tuple[int, ...]   # bucket lengths — EVERY one a multiple of
                             # `align` (padded_total is too, so the last
                             # bucket is as aligned as the rest; the
                             # sharded scatter's size // n splits rely
                             # on this)

    @property
    def n_buckets(self) -> int:
        return len(self.starts)


def plan_buckets(total: int, bucket_bytes: int, align: int = 1) -> BucketPlan:
    """Carve ``total`` f32 elements into buckets of ~``bucket_bytes``.

    ``bucket_bytes == 0`` means one fused bucket covering everything.
    Bucket boundaries are multiples of ``align`` (the int8 quantization
    block size), so per-block scale rows never straddle buckets; the
    bucket element count is ``bucket_bytes // 4`` rounded DOWN to the
    alignment (floored at one block) — a bucket never exceeds the
    requested byte budget by more than one block's padding."""
    if bucket_bytes < 0:
        raise ValueError(f"bucket_bytes must be >= 0, got {bucket_bytes}")
    align = max(int(align), 1)
    padded_total = max(_align_up(total, align), align)
    if bucket_bytes == 0:
        bucket_elems = padded_total
    else:
        bucket_elems = max((bucket_bytes // 4) // align * align, align)
    starts, sizes = [], []
    off = 0
    while off < padded_total:
        size = min(bucket_elems, padded_total - off)
        starts.append(off)
        sizes.append(size)
        off += size
    return BucketPlan(
        total=total,
        padded_total=padded_total,
        align=align,
        starts=tuple(starts),
        sizes=tuple(sizes),
    )


def split_buckets(flat_padded: jax.Array, plan: BucketPlan) -> List[jax.Array]:
    """Static slices of the padded flat buffer, one per bucket."""
    return [
        jax.lax.slice(flat_padded, (s,), (s + n,))
        for s, n in zip(plan.starts, plan.sizes)
    ]


def bucket_leaf_segments(layout: TreeLayout, plan: BucketPlan):
    """Which leaf fragments make up each bucket — the static inverse of
    "concatenate everything, then slice".

    Returns one tuple per bucket of ``(leaf_index, leaf_offset, length)``
    fragments in flat-buffer order; ``leaf_index is None`` marks the
    alignment-padding tail (zeros). This is what lets the pipelined wire
    assemble bucket ``b`` from ONLY the leaves whose bytes live in it:
    the serial spelling's global ``tree_to_flat`` concat makes every
    bucket's collective a dataflow descendant of every gradient leaf, so
    no scheduler — XLA's latency-hiding one included — may start any
    reduction before the whole backward finishes."""
    leaf_spans = []
    for i, (shape, off) in enumerate(zip(layout.shapes, layout.offsets)):
        n = 1
        for d in shape:
            n *= d
        if n:
            leaf_spans.append((off, off + n, i))
    out = []
    li = 0
    for start, size in zip(plan.starts, plan.sizes):
        end = start + size
        frags = []
        cur = start
        while li < len(leaf_spans) and leaf_spans[li][1] <= cur:
            li += 1
        j = li
        while j < len(leaf_spans) and leaf_spans[j][0] < end:
            l0, l1, idx = leaf_spans[j]
            s, e = max(cur, l0), min(end, l1)
            if s < e:
                frags.append((idx, s - l0, e - s))
                cur = e
            j += 1
        if cur < end:  # padding tail past the last leaf
            frags.append((None, 0, end - cur))
        out.append(tuple(frags))
    return tuple(out)


def assemble_bucket(leaves: Sequence[jax.Array], segments) -> jax.Array:
    """Build one contiguous f32 bucket from its own leaf fragments
    (``bucket_leaf_segments`` rows). Value-identical to slicing the
    padded global concat, but the result depends ONLY on the leaves in
    this bucket — the dataflow property the pipelined schedule needs."""
    parts = []
    for idx, off, n in segments:
        if idx is None:
            parts.append(jnp.zeros((n,), jnp.float32))
            continue
        leaf = leaves[idx].astype(jnp.float32).reshape(-1)
        if off == 0 and n == leaf.shape[0]:
            parts.append(leaf)
        else:
            parts.append(jax.lax.slice(leaf, (off,), (off + n,)))
    if not parts:
        return jnp.zeros((0,), jnp.float32)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def leaves_from_buckets(layout: TreeLayout, plan: BucketPlan, outs):
    """Rebuild the tree from per-bucket results (CANONICAL bucket order)
    without concatenating the full vector first: each leaf gathers only
    the fragments of the buckets its bytes live in, so a leaf's rebuilt
    value is a dataflow descendant of ITS buckets alone (the per-leaf
    mirror of ``assemble_bucket``; the serial ``flat_to_tree(concat(...))``
    would chain every leaf behind every bucket's reduction)."""
    leaves = []
    for shape, dtype, off in zip(layout.shapes, layout.dtypes,
                                 layout.offsets):
        n = 1
        for d in shape:
            n *= d
        parts = []
        pos = off
        for b, (bs, sz) in enumerate(zip(plan.starts, plan.sizes)):
            be = bs + sz
            if be <= pos or bs >= off + n:
                continue
            s, e = max(pos, bs), min(off + n, be)
            if s < e:
                piece = outs[b]
                if s == bs and e == be:
                    parts.append(piece)
                else:
                    parts.append(jax.lax.slice(piece, (s - bs,), (e - bs,)))
        if not parts:
            flat = jnp.zeros((0,), jnp.float32)
        elif len(parts) == 1:
            flat = parts[0]
        else:
            flat = jnp.concatenate(parts)
        leaves.append(flat.reshape(shape).astype(dtype))
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)


def readiness_bucket_order(
    plan: BucketPlan,
    layout: Optional[TreeLayout] = None,
    leaf_rank: Optional[Sequence[int]] = None,
) -> Tuple[int, ...]:
    """Bucket dispatch order for the pipelined wire: the bucket whose
    LAST-ready constituent gradient becomes available earliest goes
    first.

    ``leaf_rank[i]`` is the production rank of leaf ``i``'s gradient in
    the backward pass (smaller = produced earlier). The default rank is
    REVERSE construction order — backprop produces the last-constructed
    layers' gradients first — which for the contiguous canonical layout
    reduces to reversed bucket enumeration (the last bucket holds the
    last leaves). ``parallel/overlap.grad_leaf_readiness`` extracts the
    real production order from a traced jaxpr; tests pin that the
    default rank agrees with it on the real models, and callers with an
    exotic model can pass the measured rank instead."""
    if layout is None or leaf_rank is None:
        return tuple(reversed(range(plan.n_buckets)))
    segs = bucket_leaf_segments(layout, plan)
    n_leaves = len(layout.shapes)
    ready = []
    for b, frags in enumerate(segs):
        ranks = [
            leaf_rank[idx] for idx, _, _ in frags
            if idx is not None and idx < n_leaves
        ]
        # a bucket of pure padding is ready immediately
        ready.append((max(ranks) if ranks else -1, b))
    return tuple(b for _, b in sorted(ready))


def concat_buckets(buckets: Sequence[jax.Array]) -> jax.Array:
    return jnp.concatenate(list(buckets))


def pad_flat(flat: jax.Array, plan: BucketPlan) -> jax.Array:
    return jnp.pad(flat, (0, plan.padded_total - plan.total))


@flax.struct.dataclass
class FlatVector:
    """One param-shaped quantity stored flat.

    ``flat`` is the alignment-padded f32 vector in ``plan``'s geometry
    (``plan.padded_total`` elements; the pad tail is zero and never feeds
    the tree view). ``layout``/``plan`` are static aux data — part of the
    pytree STRUCTURE, not leaves — so jit specializes on the geometry and
    ``jax.tree_util.tree_map`` over a FlatVector is a whole-vector op.
    That makes the existing optax-style transforms fused for free: a
    ``tree_map`` over a single [P] leaf IS one vector op, and the guard's
    rollback ``jnp.where`` selects the whole state in a handful of ops.

    Serialization converts at the edge (see ``_flatvector_to_state_dict``
    below): a FlatVector's state dict is the TREE-shaped nested dict of
    its leaves, so checkpoints are byte-compatible with the tree-state
    ones earlier versions wrote.
    """

    flat: jax.Array
    layout: TreeLayout = flax.struct.field(pytree_node=False)
    plan: BucketPlan = flax.struct.field(pytree_node=False)

    def tree(self):
        """Materialize the tree view (slices/reshapes XLA fuses away)."""
        return flat_to_tree(self.layout, self.flat)


def tree_view(params):
    """Tree view of a params-like object: a FlatVector's, or the pytree
    itself (the LM engines' state)."""
    if isinstance(params, FlatVector):
        return params.tree()
    return params


def to_flat_vector(tree, plan: BucketPlan) -> FlatVector:
    """Pack a pytree into a FlatVector with ``plan``'s padding."""
    return FlatVector(
        flat=pad_flat(tree_to_flat(tree), plan),
        layout=tree_layout(tree),
        plan=plan,
    )


def _np_flat_to_tree(layout: TreeLayout, flat):
    """Host-side (numpy) twin of flat_to_tree for the checkpoint edge —
    serialization must not touch a device."""
    flat = np.asarray(flat)
    leaves = []
    for shape, dtype, off in zip(layout.shapes, layout.dtypes,
                                 layout.offsets):
        n = 1
        for d in shape:
            n *= d
        leaves.append(flat[off:off + n].reshape(shape).astype(dtype))
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)


def _np_tree_to_flat(layout: TreeLayout, plan: BucketPlan, tree):
    flat = np.zeros((plan.padded_total,), np.float32)
    for leaf, off in zip(jax.tree_util.tree_leaves(tree), layout.offsets):
        arr = np.asarray(leaf)
        flat[off:off + arr.size] = arr.astype(np.float32).reshape(-1)
    return flat


def _flatvector_to_state_dict(fv: FlatVector):
    # checkpoints are tree-shaped at the boundary: store the leaves, not
    # the buffer, so the file is identical to a tree-state run's
    return serialization.to_state_dict(
        _np_flat_to_tree(fv.layout, fv.flat)
    )


def _flatvector_from_state_dict(fv: FlatVector, state) -> FlatVector:
    # the stored dict is tree-shaped (this handler wrote it, or the
    # checkpoint predates flat state); rebuild the padded vector in the
    # TARGET's geometry — portability across bucket_bytes
    # falls out, because the tree is the interchange format
    template = _np_flat_to_tree(
        fv.layout, np.zeros((fv.plan.padded_total,), np.float32)
    )
    tree = serialization.from_state_dict(template, state)
    return fv.replace(flat=_np_tree_to_flat(fv.layout, fv.plan, tree))


serialization.register_serialization_state(
    FlatVector,
    _flatvector_to_state_dict,
    _flatvector_from_state_dict,
    override=True,  # flax.struct registered field-wise handlers already
)


def piece_stream(tree, bucket_bytes, align: int = 1,
                 flat_output: bool = False, pipelined: bool = False,
                 bucket_output: bool = False):
    """The comm engine's one entry point: what a collective scheme ships.

    Returns ``(pieces, key_ids, rebuild)``:

    - ``pieces``: the arrays to quantize/reduce — the pytree's leaves
      verbatim when ``bucket_bytes is None`` (legacy per-leaf wire), or
      the contiguous f32 buckets of the flattened tree otherwise
      (``0`` = one fused bucket, ``N`` = ~N-byte buckets aligned to
      ``align`` elements);
    - ``key_ids``: the position-stable PRNG fold value for each piece —
      the enumeration index per leaf (the legacy discipline error-
      feedback residuals already mirror), the bucket's START OFFSET in
      the flat buffer per bucket (so a piece's stochastic-rounding
      stream depends on where its bytes live, not on how many pieces
      precede it);
    - ``rebuild``: maps the per-piece aggregation results (same shapes
      as ``pieces``) back to the original tree structure, restoring
      every leaf's dtype/shape and dropping alignment padding — or, with
      ``flat_output=True`` (the PS step: the consumer is the fused
      vector update, not a per-leaf optimizer), to ONE padded flat
      f32 vector in the same ``align`` geometry, skipping the per-leaf
      scatter entirely. The pieces (and therefore the wire) are
      IDENTICAL either way — flat_output changes only the rebuild.

    ``pipelined=True`` (PSConfig.overlap="pipelined", bucketed wires
    only) keeps the SAME plan, the same leaf->bucket byte assignment,
    and the same start-offset PRNG ids — so every piece's VALUES are
    bit-identical to the serial stream — but changes the dataflow and
    the enumeration:

    - each bucket is assembled from its own leaves' fragments
      (``assemble_bucket``), never by slicing a global concat, so bucket
      b's reduction depends only on the gradients whose bytes live in b;
    - pieces stream in READINESS order (``readiness_bucket_order``:
      last-constructed leaves backprop first, so the last bucket
      dispatches first) — reverse-topological bucket enumeration;
    - the tree rebuild gathers each leaf from its own buckets
      (``leaves_from_buckets``) instead of slicing the full concat.

    ``bucket_output=True`` (pipelined flat state: the consumer is the
    PER-BUCKET vector update) makes ``rebuild`` return the list of
    per-bucket f32 aggregates in CANONICAL bucket order instead of any
    concatenation — the one spelling with no whole-vector barrier at
    all. Requires a bucketed wire."""
    if bucket_output and bucket_bytes is None:
        raise ValueError("bucket_output needs a bucketed wire "
                         "(bucket_bytes is None = per-leaf)")
    if bucket_bytes is None:
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if flat_output:
            layout = tree_layout(tree)
            plan = plan_buckets(layout.total, 0, align=align)
            return (
                leaves,
                tuple(range(len(leaves))),
                lambda outs: pad_flat(
                    concat_buckets(
                        [o.astype(jnp.float32).reshape(-1) for o in outs]
                    )
                    if outs
                    else jnp.zeros((0,), jnp.float32),
                    plan,
                ),
            )
        return (
            leaves,
            tuple(range(len(leaves))),
            lambda outs: jax.tree_util.tree_unflatten(treedef, outs),
        )
    layout = tree_layout(tree)
    plan = plan_buckets(layout.total, bucket_bytes, align=align)
    if pipelined:
        order = readiness_bucket_order(plan)
        segs = bucket_leaf_segments(layout, plan)
        leaves = jax.tree_util.tree_leaves(tree)
        pieces = [assemble_bucket(leaves, segs[b]) for b in order]
        key_ids = tuple(plan.starts[b] for b in order)

        def rebuild(outs):
            canon = [None] * plan.n_buckets
            for b, o in zip(order, outs):
                canon[b] = o
            if bucket_output:
                return canon
            if flat_output:
                return concat_buckets(canon)
            return leaves_from_buckets(layout, plan, canon)

        return (pieces, key_ids, rebuild)
    pieces = split_buckets(pad_flat(tree_to_flat(tree), plan), plan)
    if bucket_output:
        rebuild = lambda outs: list(outs)
    elif flat_output:
        rebuild = concat_buckets
    else:
        rebuild = lambda outs: flat_to_tree(layout, concat_buckets(outs))
    return (pieces, plan.starts, rebuild)
