"""Flash attention as Pallas TPU kernels (forward + backward).

The single-chip attention hot path. parallel/ring_attention.py and
parallel/ulysses.py already avoid materializing the [T, T] score matrix
ACROSS chips; this kernel does the same WITHIN a chip: blockwise online
softmax in VMEM, O(T) memory instead of O(T^2) HBM traffic, MXU-shaped
[block_q, d] x [d, block_k] matmuls.

Layout: inputs [B, T, H, D] are folded to [B*H, T, D]. A kernel's grid
is (batch*head, step), and its steps are the entries of a table in SMEM
(_walk, handed over by scalar prefetch as ops/grouped_matmul.py hands
the experts' tiles): the (q block, k block) tiles of the [n_q, n_k]
rectangle that do work, in the order the kernel accumulates them, each
with flags that say what else the step does. A tile the mask blanks
whole is no entry: it costs no grid step and fetches nothing (at T 8,192
under `causal` 136 steps a head where the rectangle has 256). Every
index_map reads the step's blocks from the tables.

The forward walks q-major, k ascending within a q block, accumulating
(acc, running max m, running sum l) in VMEM scratch from the q block's
first entry and writing the normalized output plus the logsumexp
L = m + log(l) at its last. The backward pass recomputes
p = exp(q k^T * scale - L) per tile (flash-attention-2 style), seeded with
delta = rowsum(do * o) computed in plain XLA, in ONE kernel
(ps_flash_dqkv): each live tile's s, p, dp, ds are made once and feed all
three gradients. It walks k-major, q ascending within a k block: dk and
dv accumulate over a k block's entries in VMEM scratch as the forward's
output does over a q block's; dq sums over k blocks, the OUTER axis, so
the whole head's dq ([T_q, D] float32) stays in a VMEM scratch across the
walk, and a q block's dq is written once, at the last entry that holds
it (under `causal` its diagonal tile; the dq block resident in VMEM is
the one being completed, Walk.in_block). Where that scratch does not fit
(plan_flash's cap: one chip at T well past 32k) the plan takes two kernels
instead, ps_flash_dq (q-major) and ps_flash_dkv (k-major), which compute
every tile twice. Every sum runs over the same tiles in the same order as
a walk of the whole rectangle would, so results are that walk's bit for
bit.

A block of an output that no live tile touches keeps one entry, dead
(_kept): the queries of EVA's first window see no summary, a ring hop may
lie wholly in the future, and their m = NEG_INF, l = 0 and zero gradients
still have to be written. Where both mask offsets are Python ints (every
call but a ring hop) the walk is a numpy constant and the grid is exactly
its length (FlashPlan.grid_steps). A ring hop's offsets are traced: its
walk is built in jnp from them before the call, the grid keeps the
rectangle's length, and the steps past the last entry repeat its blocks
with no flag set, so no index moves, nothing is fetched and nothing runs.
One kernel body serves both.

Every kernel works on the TRANSPOSED score tile k q^T, [block_k, block_q]
with keys down the rows. The per-query statistics (m, l, lse, delta) are
then lane-major [1, block_q] rows: a handful of vregs instead of one per
eight queries, reduced over keys by plain elementwise max/add down the
rows, and the same shape in which they cross the kernel boundary
([BH, 1, T] with (1, 1, block_q) blocks, whose second-to-last block dim
equals the array's, as Mosaic wants). The forward and dq accumulators are
held transposed too ([D, block_q]; the whole head's dq as [n_q, D,
block_q], indexed by the q block on its leading dimension) and turned
once, where they are written.
Compiled calls need block sizes that are multiples of 128 or cover the
whole (padded) sequence; plan_flash gives that.

The tile plan (plan_flash) is what makes a grid step worth its fixed
cost: tiles as large as the sequence and VMEM_BUDGET allow (512 x 512 at
T = 1024, D = 64: 3 steps a head where 128-wide tiles walked 36), planned
from what the call can observe: T_q, T_k, D, the operand dtype, causal.
Any T works: it is padded up to the block grid and the padded keys are
masked (k_len), so tiles stay MXU-shaped.

Causality is enforced by masking with global positions (_mask_scores),
and a tile that the mask would blank entirely is left out of the walk
(_tile_live: the same positions, so the mask and the skip cannot part;
a ring hop whose visiting shard lies wholly in the future walks one dead
entry an output block and comes out as m = NEG_INF, l = 0, zero
gradients). Where `causal` is an EarlierWindows the same two functions
hold the second mask kind: keys of the windows before the query's own
(ops/eva.py's pass over pooled keys, through the partial-triple API
below); where it is a SlidingWindow, the third: the `window` latest keys
up to the query's own, a band along the diagonal, so a tile is blank above
the diagonal AND below the band and the walk holds the band's tiles only
(31 of 256 at T 8,192, window 512, 512-wide tiles; flash_attention takes
it where it takes True).

Precision: p and ds are cast to the dtype of the operand they multiply,
so bfloat16 inputs give the MXU bfloat16 operands in all seven products of
a training step (nine where the backward is split); scores, m, l, lse,
delta, the exponentials and every accumulator stay float32. float32
inputs are never cast.

Selection is ops/pallas_mode.py's: Mosaic-compiled on TPU backends,
interpret mode under PS_TPU_PALLAS_INTERPRET=1 (how CPU CI exercises the
kernels), pure-jnp reference otherwise (PS_TPU_DISABLE_PALLAS=1 forces
it). The jnp reference is ring_attention.full_attention — also the test
oracle.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..obs.scopes import FLASH, scope
from .pallas_mode import kernel_mode, pallas_mode

NEG_INF = -1e30

# The names _flash_vjp_fwd gives the two residuals only the forward kernel
# can make (o and lse). A jax.checkpoint whose policy saves them
# (models/transformer.remat_block) recomputes its block in the backward
# without running ps_flash_fwd again; without such a policy the names
# lower to nothing.
FLASH_SAVED = ("ps_flash_o", "ps_flash_lse")
# The kernels' operands, named where they already live: folded to [BH, T, D]
# and padded to the block grid. Dear to make again (a layer's projections,
# rotation and norms, the fold's transposes and pads) and bfloat16-small to
# keep, so a policy saves them too wherever plan_remat_saves finds the room.
FLASH_OPERANDS = ("ps_flash_q", "ps_flash_k", "ps_flash_v")

# What one grid step may hold in VMEM by plan_flash's estimate: under the
# 16 MiB a v5e kernel gets by default, with room for what the estimate
# leaves out (Mosaic's own temporaries).
VMEM_BUDGET = 12 * 2 ** 20
MAX_BLOCK = 1024
# The fused backward also holds the whole head's dq in VMEM, beyond the
# default limit at the cells' shapes (6 MiB at [8192, 192] beside 8.3 MB
# of tiles), so it asks Mosaic for its own limit (vmem_limit). The plan
# takes it while its estimate is under FUSED_BWD_CAP, half of the 128 MiB
# a v5e core has: T = 65,536 at D = 192 is fused, T = 131,072 is split.
VMEM_BYTES = 128 * 2 ** 20
FUSED_BWD_CAP = VMEM_BYTES // 2

_NT = (((1,), (1,)), ((), ()))  # a @ b.T: contract the last dim of both
_TN = (((0,), (0,)), ((), ()))  # a.T @ b: contract the first dim of both


class EarlierWindows(NamedTuple):
    """A mask kind, given where `causal` is: the query at position i sees
    the key at position j iff j // k_window < i // q_window, every key of
    the windows before the query's own and none of its own or a later one.
    Queries and keys may count in different units (ops/eva.py: queries in
    tokens, keys one a chunk of tokens, so q_window tokens hold k_window
    keys). `causal` is False | True | an EarlierWindows wherever the
    kernels, plan_flash and the partial-triple API take it."""

    q_window: int
    k_window: int


class SlidingWindow(NamedTuple):
    """A mask kind, given where `causal` is: the query at position i sees
    the key at position j iff i - window < j <= i, its own position and the
    `window` - 1 before it. flash_attention, plan_flash and the jnp twin
    (parallel/ring_attention.full_attention) take it; a ring hop does not
    (flash_partial refuses it by name: ROADMAP M5)."""

    window: int


def mask_name(causal) -> str:
    """How a plan's instant spells a mask kind."""
    if isinstance(causal, SlidingWindow):
        return "sliding_window"
    if isinstance(causal, EarlierWindows):
        return "earlier_windows"
    return "causal" if causal else "none"


def dense_mask(causal, t_q: int, t_k: int):
    """The [t_q, t_k] booleans a mask kind keeps, both offsets 0: what
    _mask_scores computes tile by tile, for the jnp twins and the tests.
    None where nothing is masked."""
    if not causal:
        return None
    i, j = jnp.arange(t_q)[:, None], jnp.arange(t_k)[None, :]
    if isinstance(causal, EarlierWindows):
        return j // causal.k_window < i // causal.q_window
    if isinstance(causal, SlidingWindow):
        return (j <= i) & (j > i - causal.window)
    return j <= i


def _window_of(pos, window: int):
    """pos // window for a position (never negative): a Python int or a
    numpy array of them (the static walk), or traced int32 scalars and
    vectors alike."""
    if isinstance(pos, (int, np.ndarray)):
        return pos // window
    return jax.lax.div(pos, jnp.int32(window))


def _mask_scores(scores, qi, ki, block_q, block_k, causal, k_len,
                 q_off=0, k_off=0):
    """Apply the causal and/or key-padding mask to one [block_k, block_q]
    score tile (keys down the rows), with positions taken
    from the grid indices plus GLOBAL offsets (q_off/k_off are 0
    single-chip; on a sequence-parallel ring they are the traced shard
    offsets of the local q block and the visiting k block). `k_len`
    (static) masks key positions >= k_len — how flash_attention supports
    sequence lengths that are not block multiples: inputs are zero-padded
    to the block grid and the padded keys are masked here. The ONE masking
    implementation shared by the forward, dq, and dkv kernels — they must
    never diverge or gradients silently stop matching the forward."""
    iota = lambda axis: jax.lax.broadcasted_iota(jnp.int32, scores.shape, axis)
    k_local = ki * block_k + iota(0)
    keep = None
    if isinstance(causal, EarlierWindows):
        # the first key of each query's own window, one [1, block_q] row
        q_row = q_off + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (1, scores.shape[1]), 1)
        keep = (k_off + k_local) < _window_of(q_row, causal.q_window) * causal.k_window
    elif isinstance(causal, SlidingWindow):
        q_pos = q_off + qi * block_q + iota(1)
        k_pos = k_off + k_local
        keep = (k_pos <= q_pos) & (k_pos > q_pos - causal.window)
    elif causal:
        q_pos = q_off + qi * block_q + iota(1)
        keep = (k_off + k_local) <= q_pos
    if k_len is not None:
        # k_len is the LOCAL (unpadded) length of this k/v operand — the
        # pad mask is in local coordinates, unlike the causal mask's
        # global ones (a visiting ring shard pads at its local tail)
        pad_keep = k_local < k_len
        keep = pad_keep if keep is None else (keep & pad_keep)
    return jnp.where(keep, scores, NEG_INF)


def _tile_live(qi, ki, block_q, block_k, causal, k_len, q_off=0, k_off=0):
    """False where _mask_scores would blank every score of tile (qi, ki):
    its first key lies past its last query (causal), or in no window
    before its last query's (EarlierWindows), or past its last query or
    its last key a window or more before its first query (SlidingWindow),
    or past k_len. None
    when no tile can be blank. Plain arithmetic on the positions
    _mask_scores uses, so it serves ints, numpy grids (the static walk
    and plan_flash's counts) and traced offsets (a ring hop's walk) alike."""
    live = None
    if isinstance(causal, EarlierWindows):
        live = (_window_of(k_off + ki * block_k, causal.k_window)
                < _window_of(q_off + qi * block_q + (block_q - 1), causal.q_window))
    elif isinstance(causal, SlidingWindow):
        q_first, k_first = q_off + qi * block_q, k_off + ki * block_k
        live = ((k_first <= q_first + (block_q - 1))
                & (k_first + (block_k - 1) > q_first - causal.window))
    elif causal:
        live = k_off + ki * block_k <= q_off + qi * block_q + (block_q - 1)
    if k_len is not None:
        in_len = ki * block_k < k_len
        live = in_len if live is None else live & in_len
    return live


def _guard_masked_rows(stat):
    """A query whose every key is masked has m (or lse) == NEG_INF, and
    exp(NEG_INF - NEG_INF) = 1 would count its masked scores. Put
    -NEG_INF in its place, so exp(scores - stat) is 0 there: the guard
    costs one pass over the [1, block_q] statistics, not over the tile."""
    return jnp.where(stat > NEG_INF / 2, stat, -NEG_INF)


def _make_scores(scale, causal, block_q, block_k, k_len):
    """scores(q, k, qi, ki, q_off, k_off) of one tile geometry, shared by
    all kernels: the masked [block_k, block_q] float32 tile k q^T * scale."""
    masked = causal or k_len is not None

    def scores(q, k, qi, ki, q_off, k_off):
        s = jax.lax.dot_general(
            k, q, _NT, preferred_element_type=jnp.float32
        ) * scale
        if masked:
            s = _mask_scores(s, qi, ki, block_q, block_k, causal, k_len,
                             q_off, k_off)
        return s

    return scores


# ------------------------------------------------------------ the live walk
# A kernel's grid is (batch*head, step) and the steps are the entries of a
# table in SMEM (scalar prefetch, as ops/grouped_matmul.py walks the
# experts' tiles): the tiles of the [n_q, n_k] rectangle that do work, in
# the order the kernel accumulates them, so a tile the mask blanks costs no
# step and no fetch.

# What an entry does, as bits of its `flags`. A sweep is the run of entries
# that share the walk's outer block (q for the forward and dq, k for dk and
# dv); the fused backward also follows the inner one, q.
LIVE = 1       # the tile does work (_tile_live)
FIRST = 2      # first entry of its sweep: the sweep's accumulators start
LAST = 4       # last entry of its sweep: they are written out
FIRST_IN = 8   # first entry of its inner block in the whole walk
LAST_IN = 16   # last one: the sum over the sweeps is complete


class Walk(NamedTuple):
    """The entries one kernel walks, one int32 table [steps] a column.
    numpy where the call knows its offsets (`steps` entries, none idle);
    traced where a ring hop's offsets are (the rectangle's length, with an
    idle tail: flags 0 and the last entry's blocks, so no index moves)."""

    qi: jax.Array
    ki: jax.Array
    flags: jax.Array
    in_block: jax.Array   # the inner block whose sum is being completed

    @property
    def steps(self) -> int:
        return self.qi.shape[0]


def _live_tiles(n_q, n_k, block_q, block_k, causal, k_len, q_off=0, k_off=0):
    """[n_q, n_k] booleans, _tile_live over the rectangle: a numpy array
    where the mask reads no offset or both are Python ints, traced where
    one is."""
    static = not causal or (isinstance(q_off, int) and isinstance(k_off, int))
    xp = np if static else jnp
    qi = xp.arange(n_q, dtype=xp.int32)[:, None]
    ki = xp.arange(n_k, dtype=xp.int32)[None, :]
    live = _tile_live(qi, ki, block_q, block_k, causal, k_len, q_off, k_off)
    if live is None:
        return np.ones((n_q, n_k), bool)
    return xp.broadcast_to(live, (n_q, n_k))


def _kept(live):
    """The tiles a walk holds: the live ones and, for a q block or a k
    block that has none, its first tile, dead. Every output block keeps
    one step that way (its init and finalize run, the products do not: a
    first window's queries see no summary, a last window's summaries are
    seen by no query, a ring hop may lie wholly in the future), as every
    expert owns a tile in ops/grouped_matmul.py."""
    n_q, n_k = live.shape
    first_k, first_q = np.arange(n_k)[None, :] == 0, np.arange(n_q)[:, None] == 0
    return (live | (~live.any(axis=1, keepdims=True) & first_k)
            | (~live.any(axis=0, keepdims=True) & first_q))


def _walk(live, k_major: bool) -> Walk:
    """The walk over _kept(live): q-major with k ascending (the forward,
    ps_flash_dq), or k-major with q ascending (ps_flash_dkv, ps_flash_dqkv).
    One body for numpy and traced `live`."""
    static = isinstance(live, np.ndarray)
    xp = np if static else jnp
    keep = _kept(live)
    if k_major:
        live, keep = live.T, keep.T
    (n_out, n_in), kept = keep.shape, keep.reshape(-1)
    count = kept.sum()
    step = xp.arange(kept.size)
    walked = step < count
    # the kept tiles first, in the rectangle's order; the idle tail stays
    # on the last of them
    order = xp.argsort(~kept, stable=True)
    at = xp.where(walked, order, order[count - 1])
    outer, inner = at // n_in, at % n_in
    first = (step == 0) | (outer != xp.roll(outer, 1))
    last = (step == count - 1) | (outer != xp.roll(outer, -1))
    first_in = outer == xp.argmax(keep, axis=0)[inner]
    last_in = outer == (n_out - 1 - xp.argmax(keep[::-1], axis=0))[inner]
    flags = walked * (live.reshape(-1)[at] * LIVE + first * FIRST + last * LAST
                      + first_in * FIRST_IN + last_in * LAST_IN)
    # the inner block being completed: that of the latest entry to complete
    # one (before the first of them, the first's), so each block is resident
    # for one run of steps and written back once, whole
    done = xp.where(walked & last_in, step, -1)
    done = np.maximum.accumulate(done) if static else jax.lax.cummax(done)
    in_block = inner[xp.where(done < 0, xp.argmax(done >= 0), done)]
    qi, ki = (inner, outer) if k_major else (outer, inner)
    tables = [x.astype(xp.int32) for x in (qi, ki, flags, in_block)]
    if static:
        tables = [x[:int(count)] for x in tables]
    return Walk(*tables)


def _entry(qi_ref, ki_ref, flag_ref, off_ref):
    """(qi, ki, has, q_off, k_off) of this grid step: its tile's blocks,
    has(bit) of its flags, and the mask's offsets."""
    from jax.experimental import pallas as pl

    step = pl.program_id(1)
    flags = flag_ref[step]
    return (qi_ref[step], ki_ref[step], lambda bit: (flags & bit) != 0,
            off_ref[0], off_ref[1])


# Where a grid step's blocks lie: every index_map reads the walk's tables
# (after the grid indices, the scalar-prefetch refs: qi, ki, flags,
# in_block, offsets).
def _q_rows(b, s, qi, *_):
    return b, qi[s], 0


def _k_rows(b, s, qi, ki, *_):
    return b, ki[s], 0


def _q_stat(b, s, qi, *_):
    return b, 0, qi[s]


def _in_rows(b, s, qi, ki, flags, in_block, *_):
    return b, in_block[s], 0


def _walk_call(kernel, walk: Walk, offsets, bh, *, name, in_specs, out_specs,
               out_shape, scratch_shapes, mode, **params):
    """pallas_call over (bh, walk.steps) with the walk and the offsets in
    SMEM ahead of the operands."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    call = pl.pallas_call(
        kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(bh, walk.steps), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        **params, **mode,
    )
    return partial(call, *walk, jnp.stack([jnp.asarray(o, jnp.int32) for o in offsets]))


# --------------------------------------------------------------- forward


def _make_fwd_kernel(scale, causal, block_q, block_k, normalize, k_len=None):
    from jax.experimental import pallas as pl

    scores = _make_scores(scale, causal, block_q, block_k, k_len)

    def kernel(qi_ref, ki_ref, flag_ref, _, off_ref, q_ref, k_ref, v_ref,
               *out_and_scratch):
        if normalize:
            o_ref, lse_ref, acc_ref, m_ref, l_ref = out_and_scratch
        else:
            pv_ref, mo_ref, lo_ref, acc_ref, m_ref, l_ref = out_and_scratch
        qi, ki, has, q_off, k_off = _entry(qi_ref, ki_ref, flag_ref, off_ref)

        @pl.when(has(FIRST))
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)

        @pl.when(has(LIVE))
        def _tile():
            v = v_ref[0]  # [Bk, D]
            s = scores(q_ref[0], k_ref[0], qi, ki, q_off, k_off)  # [Bk, Bq]
            m_prev = m_ref[:]  # [1, Bq]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - _guard_masked_rows(m_new))
            alpha = jnp.exp(m_prev - m_new)  # [1, Bq]
            l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=0, keepdims=True)
            acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
                v, p.astype(v.dtype), _TN, preferred_element_type=jnp.float32
            )  # (p v)^T, [D, Bq]
            m_ref[:] = m_new

        @pl.when(has(LAST))
        def _finalize():
            if normalize:
                l = l_ref[:]
                l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0
                o_ref[0] = (acc_ref[:] / l_safe).T.astype(o_ref.dtype)
                lse_ref[0] = m_ref[:] + jnp.log(l_safe)
            else:
                # partial triple for ring hops: UNNORMALIZED numerator plus
                # the (m, l) stats, merged across hops by the caller
                pv_ref[0] = acc_ref[:].T
                mo_ref[0] = m_ref[:]
                lo_ref[0] = l_ref[:]

    return kernel


def _flash_fwd(q3, k3, v3, scale, causal, block_q, block_k, mode,
               offsets=(0, 0), normalize=True, k_len=None):
    """q3/k3: [BH, T, D], v3: [BH, T, Dv] -> (o [BH, T, Dv], lse [BH, T])
    when normalize, else the partial triple (pv f32 [BH, T, Dv], m f32
    [BH, T], l f32 [BH, T]) for ring-hop merging. `offsets` shifts the
    causal mask's global positions; static `k_len` masks zero-padded key
    positions (see _mask_scores). The grid walks the live tiles q-major,
    k ascending within a q block."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q3.shape
    tk, dv = k3.shape[1], v3.shape[2]
    walk = _walk(_live_tiles(t // block_q, tk // block_k, block_q, block_k,
                             causal, k_len, *offsets), k_major=False)
    kernel = _make_fwd_kernel(scale, causal, block_q, block_k, normalize,
                              k_len=k_len)
    row = pl.BlockSpec((1, 1, block_q), _q_stat)
    row_shape = jax.ShapeDtypeStruct((bh, 1, t), jnp.float32)
    out_specs = [pl.BlockSpec((1, block_q, dv), _q_rows)]
    if normalize:
        out_specs += [row]
        out_shape = [jax.ShapeDtypeStruct((bh, t, dv), q3.dtype), row_shape]
    else:
        out_specs += [row, row]
        out_shape = [
            jax.ShapeDtypeStruct((bh, t, dv), jnp.float32),
            row_shape,
            row_shape,
        ]
    out, *rows = _walk_call(
        kernel, walk, offsets, bh, name="ps_flash_fwd",
        in_specs=[
            pl.BlockSpec((1, block_q, d), _q_rows),
            pl.BlockSpec((1, block_k, d), _k_rows),
            pl.BlockSpec((1, block_k, dv), _k_rows),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((dv, block_q), jnp.float32),
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((1, block_q), jnp.float32),
        ],
        mode=mode,
    )(q3, k3, v3)
    return (out, *(r.reshape(bh, t) for r in rows))


# --------------------------------------------------------------- backward


def _make_ds(scores):
    """tile(...) -> (p^T, ds^T / scale, q, do): what the dq and dkv kernels
    both recompute from the FINAL lse and delta of a [block_k, block_q]
    tile. ds lacks its factor `scale`: each kernel applies it once to
    what it accumulated."""

    def tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
             qi, ki, q_off, k_off):
        q, do = q_ref[0], do_ref[0]
        s = scores(q, k_ref[0], qi, ki, q_off, k_off)
        # fully-masked rows contributed nothing forward (lse NEG_INF)
        p = jnp.exp(s - _guard_masked_rows(lse_ref[0]))  # exact probs
        dp = jax.lax.dot_general(
            v_ref[0], do, _NT, preferred_element_type=jnp.float32
        )  # [Bk, Bq]
        return p, p * (dp - delta_ref[0]), q, do

    return tile


def _make_dqkv_kernel(scale, causal, block_q, block_k, k_len=None):
    """The fused backward: k-major, q ascending within a k block. dk and dv
    are complete when a k block's sweep ends; dq[qi] gains one term a k
    block, in ascending ki, and is written at its q block's last entry
    (under `causal` its diagonal tile, the first of a sweep)."""
    from jax.experimental import pallas as pl

    ds_tile = _make_ds(_make_scores(scale, causal, block_q, block_k, k_len))

    def kernel(qi_ref, ki_ref, flag_ref, _, off_ref, q_ref, k_ref, v_ref,
               do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
               dq_acc, dk_acc, dv_acc):
        qi, ki, has, q_off, k_off = _entry(qi_ref, ki_ref, flag_ref, off_ref)

        @pl.when(has(FIRST))
        def _init_dkv():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

        @pl.when(has(FIRST_IN))
        def _init_dq():
            dq_acc[qi] = jnp.zeros(dq_acc.shape[1:], dq_acc.dtype)

        @pl.when(has(LIVE))
        def _tile():
            p, ds, q, do = ds_tile(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                   delta_ref, qi, ki, q_off, k_off)
            k = k_ref[0]
            ds = ds.astype(q.dtype)  # q and k share their dtype
            dv_acc[:] += jnp.dot(
                p.astype(do.dtype), do, preferred_element_type=jnp.float32
            )
            dk_acc[:] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
            dq_acc[qi] += jax.lax.dot_general(
                k, ds, _TN, preferred_element_type=jnp.float32
            )  # (ds k)^T, [D, Bq]

        @pl.when(has(LAST))
        def _finalize_dkv():
            dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

        @pl.when(has(LAST_IN))
        def _finalize_dq():
            dq_ref[0] = (dq_acc[qi] * scale).T.astype(dq_ref.dtype)

    return kernel


def _make_dq_kernel(scale, causal, block_q, block_k, k_len=None):
    from jax.experimental import pallas as pl

    ds_tile = _make_ds(_make_scores(scale, causal, block_q, block_k, k_len))

    def kernel(qi_ref, ki_ref, flag_ref, _, off_ref, q_ref, k_ref, v_ref,
               do_ref, lse_ref, delta_ref, dq_ref, acc_ref):
        qi, ki, has, q_off, k_off = _entry(qi_ref, ki_ref, flag_ref, off_ref)

        @pl.when(has(FIRST))
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        @pl.when(has(LIVE))
        def _tile():
            _, ds, _, _ = ds_tile(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                  delta_ref, qi, ki, q_off, k_off)
            k = k_ref[0]
            acc_ref[:] += jax.lax.dot_general(
                k, ds.astype(k.dtype), _TN, preferred_element_type=jnp.float32
            )  # (ds k)^T, [D, Bq]

        @pl.when(has(LAST))
        def _finalize():
            dq_ref[0] = (acc_ref[:] * scale).T.astype(dq_ref.dtype)

    return kernel


def _make_dkv_kernel(scale, causal, block_q, block_k, k_len=None):
    from jax.experimental import pallas as pl

    ds_tile = _make_ds(_make_scores(scale, causal, block_q, block_k, k_len))

    def kernel(qi_ref, ki_ref, flag_ref, _, off_ref, q_ref, k_ref, v_ref,
               do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc):
        qi, ki, has, q_off, k_off = _entry(qi_ref, ki_ref, flag_ref, off_ref)

        @pl.when(has(FIRST))
        def _init():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

        @pl.when(has(LIVE))
        def _tile():
            p, ds, q, do = ds_tile(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                   delta_ref, qi, ki, q_off, k_off)
            dv_acc[:] += jnp.dot(
                p.astype(do.dtype), do, preferred_element_type=jnp.float32
            )
            dk_acc[:] += jnp.dot(
                ds.astype(q.dtype), q, preferred_element_type=jnp.float32
            )

        @pl.when(has(LAST))
        def _finalize():
            dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    return kernel


def _flash_bwd(q3, k3, v3, lse, delta, do3, scale, causal, block_q, block_k,
               mode, offsets=(0, 0), out_dtype=None, k_len=None):
    """Blockwise gradients. `lse`/`delta` are the FINAL (post-merge)
    softmax stats — single-chip they come straight from the forward; on a
    ring every hop reuses the globally-merged values, which is what makes
    per-hop contributions sum to the exact gradient. k3/v3 may have a
    different sequence length than q3 (a visiting ring shard).
    `out_dtype` overrides the gradients' dtype (the ring passes f32 so
    per-hop pieces accumulate without a per-hop rounding). v3 and do3 are
    Dv wide where q3 and k3 are D wide. One kernel or two is plan_bwd's
    choice, from the shapes here; each walks the live tiles (_walk)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q3.shape
    tk, dv = k3.shape[1], v3.shape[2]
    live = _live_tiles(t // block_q, tk // block_k, block_q, block_k, causal,
                       k_len, *offsets)
    by_k = _walk(live, k_major=True)
    lse, delta = lse.reshape(bh, 1, t), delta.reshape(bh, 1, t)
    dq_shape = jax.ShapeDtypeStruct((bh, t, d), out_dtype or q3.dtype)
    dkv_shape = [
        jax.ShapeDtypeStruct((bh, tk, d), out_dtype or k3.dtype),
        jax.ShapeDtypeStruct((bh, tk, dv), out_dtype or v3.dtype),
    ]
    in_specs = [
        pl.BlockSpec((1, block_q, d), _q_rows),
        pl.BlockSpec((1, block_k, d), _k_rows),
        pl.BlockSpec((1, block_k, dv), _k_rows),
        pl.BlockSpec((1, block_q, dv), _q_rows),
        pl.BlockSpec((1, 1, block_q), _q_stat),
        pl.BlockSpec((1, 1, block_q), _q_stat),
    ]
    dkv_specs = [
        pl.BlockSpec((1, block_k, d), _k_rows),
        pl.BlockSpec((1, block_k, dv), _k_rows),
    ]
    dkv_scratch = [
        pltpu.VMEM((block_k, d), jnp.float32),
        pltpu.VMEM((block_k, dv), jnp.float32),
    ]
    args = (q3, k3, v3, do3, lse, delta)
    bwd, _, vmem_bytes = plan_bwd(block_q, block_k, t, d, dv,
                                  q3.dtype.itemsize)

    if bwd == "fused":
        # the dq block in VMEM is the one being completed (Walk.in_block):
        # Pallas writes a block back when its index moves on, so each is
        # written once, whole
        return _walk_call(
            _make_dqkv_kernel(scale, causal, block_q, block_k, k_len=k_len),
            by_k, offsets, bh, name="ps_flash_dqkv",
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, block_q, d), _in_rows)] + dkv_specs,
            out_shape=[dq_shape] + dkv_shape,
            scratch_shapes=[pltpu.VMEM((t // block_q, d, block_q), jnp.float32)]
            + dkv_scratch,
            mode=mode,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=vmem_limit(vmem_bytes)),
        )(*args)

    by_q = _walk(live, k_major=False)
    dq = _walk_call(
        _make_dq_kernel(scale, causal, block_q, block_k, k_len=k_len),
        by_q, offsets, bh, name="ps_flash_dq",
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), _q_rows),
        out_shape=dq_shape,
        scratch_shapes=[pltpu.VMEM((d, block_q), jnp.float32)],
        mode=mode,
    )(*args)
    dk, dv = _walk_call(
        _make_dkv_kernel(scale, causal, block_q, block_k, k_len=k_len),
        by_k, offsets, bh, name="ps_flash_dkv",
        in_specs=in_specs,
        out_specs=dkv_specs,
        out_shape=dkv_shape,
        scratch_shapes=dkv_scratch,
        mode=mode,
    )(*args)
    return dq, dk, dv


# -------------------------------------------------------------- tile plan


class FlashPlan(NamedTuple):
    """How one call tiles its [T_q, T_k] score square. The counts are per
    head, with both offsets 0 (what the call can know before it runs: a
    ring hop builds its walk from the hop's offsets, _walk)."""

    block_q: int
    block_k: int
    tq_pad: int       # T_q and T_k padded up to their blocks
    tk_pad: int
    k_len: Optional[int]  # T_k where the kernels must mask a padded tail
    grid_steps: int   # steps the kernels' grids walk (_kept): tiles_run and
    #                   one for each q or k block that no live tile touches
    tiles_run: int    # tiles that do work (_tile_live)
    vmem_bytes: int   # _vmem_bytes of the backward that runs
    bwd: str          # "fused": ps_flash_dqkv; "split": ps_flash_dq + _dkv
    dq_acc_bytes: int  # the fused kernel's dq accumulator; 0 where split

    @property
    def tiles_total(self) -> int:
        """The rectangle: tiles_total - grid_steps are never entered."""
        return (self.tq_pad // self.block_q) * (self.tk_pad // self.block_k)


def _ceil_pow2(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def _floor_pow2(x: int) -> int:
    p = 1
    while p * 2 <= x:
        p *= 2
    return p


def _vmem_bytes(block_q: int, block_k: int, d: int, itemsize: int,
                d_v: Optional[int] = None, dq_acc_bytes: int = 0) -> int:
    """What a grid step of the backward holds. The tiles, as the dkv kernel
    (the largest of the split three, and what decides the blocks) holds
    them: q, do, k, v and the two row statistics double-buffered by the
    pipeline, dk and dv (float32 on a ring hop) double-buffered beside
    their two float32 accumulators, four float32 score tiles (s, p, dp,
    ds) and the two casts of p and ds that feed the MXU. q and k are `d`
    wide, v and do `d_v` (the same unless given). The fused kernel holds
    `dq_acc_bytes` more, the whole head's dq, and its dq block
    double-buffered (float32 on a ring hop)."""
    tile = block_q * block_k
    width = d + (d if d_v is None else d_v)
    operands = 2 * (block_q + block_k) * width * itemsize
    stats = 2 * 2 * block_q * 4
    results = (2 + 1) * block_k * width * 4
    tiles = operands + stats + results + 4 * tile * 4 + 2 * tile * itemsize
    if not dq_acc_bytes:
        return tiles
    return tiles + dq_acc_bytes + 2 * block_q * d * 4


def plan_bwd(block_q: int, block_k: int, tq_pad: int, d: int,
             d_v: Optional[int], itemsize: int):
    """(bwd, dq_acc_bytes, vmem_bytes) of one backward call, from its
    shapes alone: "fused" where the tiles and the whole head's float32 dq
    come under FUSED_BWD_CAP by _vmem_bytes, else "split" with no
    accumulator. plan_flash reports it and _flash_bwd obeys it."""
    dq_acc_bytes = tq_pad * d * 4
    fused = _vmem_bytes(block_q, block_k, d, itemsize, d_v, dq_acc_bytes)
    if fused <= FUSED_BWD_CAP:
        return "fused", dq_acc_bytes, fused
    return "split", 0, _vmem_bytes(block_q, block_k, d, itemsize, d_v)


def vmem_limit(vmem_bytes: int) -> int:
    """The limit the fused backward asks of Mosaic: the plan's estimate,
    and for what it leaves out (the compiler's own temporaries) the 16 MiB
    default, in which the split kernels fit whole."""
    return vmem_bytes + 16 * 2 ** 20


def _fit_block(t: int, cap: int) -> int:
    """The block for one sequence axis of length t: the largest power of
    two up to `cap` whose padding of t stays within an eighth of t as
    padded to 128 (T = 1000 takes 512-wide blocks over 1024; T = 520 takes
    128-wide blocks over 640, not 512-wide ones over 1024). A sequence
    that fits one 128-wide block is that one block, padded to a power of
    two of at least 8."""
    if t <= 128:
        return max(8, _ceil_pow2(t))
    t128 = -(-t // 128) * 128
    b = cap
    while b > 128 and -(-t // b) * b - t128 > t128 // 8:
        b //= 2
    return b


# What a grid step costs beside its tile's entries, in entries. Read on the
# chip at [72, 8192, 128] under a window of 512 (PERF.md section 6, PR 45):
# forward and backward together take 4.21 us a step at 512 x 512 tiles and
# 1.92 us at 256 x 256, so a step costs 1.15 us beside 11.7 ps an entry.
STEP_ENTRIES = 3 * 2 ** 15


def _band_blocks(t_q: int, t_k: int, bq: int, bk: int, causal: SlidingWindow):
    """The blocks for a band: from the square plan_flash would take, halved
    together down to 128, those whose live tiles cost least at tile entries
    plus STEP_ENTRIES a step. A band fills a tile as wide as its window by
    half, so smaller tiles waste fewer entries and walk more steps; at
    T 8,192 and window 512 the 512-wide tiles win (31 steps against 93 of a
    quarter the entries: 9.4 against 12.8 ms a layer of 72 heads on the
    chip), at window 128 the 256-wide ones."""
    best = None
    while True:
        live = _live_tiles(-(-t_q // bq), -(-t_k // bk), bq, bk, causal, None)
        cost = int(live.sum()) * (bq * bk + STEP_ENTRIES)
        if best is None or cost < best[0]:
            best = (cost, bq, bk)
        if min(bq, bk) <= 128:
            return best[1:]
        bq, bk = bq // 2, bk // 2


def plan_flash(t_q: int, t_k: int, d: int, dtype, causal: bool,
               block_q: Optional[int] = None,
               block_k: Optional[int] = None,
               d_v: Optional[int] = None) -> FlashPlan:
    """The tile plan of one call, from what it can observe. Pure. `d` is
    the query/key width, `d_v` the value width where it differs.

    Both blocks start at the largest square whose tiles _vmem_bytes puts
    under VMEM_BUDGET for this head size and operand dtype, then each axis
    takes what its length allows (_fit_block), so a visiting ring shard
    may be tiled otherwise than the local queries. A requested block
    (the tests) is floored to a power of two and capped at the padded
    sequence instead: a non-pow2 request must never leave grid-uncovered
    tail rows."""
    itemsize = jnp.dtype(dtype).itemsize
    cap = MAX_BLOCK
    while cap > 128 and _vmem_bytes(cap, cap, d, itemsize, d_v) > VMEM_BUDGET:
        cap //= 2

    def block(t, want):
        if want is None:
            return _fit_block(t, cap)
        return min(_floor_pow2(want), max(8, _ceil_pow2(t)))

    bq, bk = block(t_q, block_q), block(t_k, block_k)
    if isinstance(causal, SlidingWindow) and block_q is None and block_k is None:
        bq, bk = _band_blocks(t_q, t_k, bq, bk, causal)
    tq_pad, tk_pad = -(-t_q // bq) * bq, -(-t_k // bk) * bk
    k_len = t_k if tk_pad != t_k else None
    live = _live_tiles(tq_pad // bq, tk_pad // bk, bq, bk, causal, k_len)
    bwd, dq_acc_bytes, vmem_bytes = plan_bwd(bq, bk, tq_pad, d, d_v, itemsize)
    return FlashPlan(
        block_q=bq, block_k=bk, tq_pad=tq_pad, tk_pad=tk_pad, k_len=k_len,
        grid_steps=int(_kept(live).sum()), tiles_run=int(live.sum()),
        vmem_bytes=vmem_bytes, bwd=bwd, dq_acc_bytes=dq_acc_bytes,
    )


def mask_fill(plan: FlashPlan, t_q: int, t_k: int, causal) -> float:
    """The share of the live tiles' score entries that the mask keeps (both
    offsets 0): 1 where nothing is masked, about a half under `causal` at a
    few tiles a side and under a SlidingWindow as wide as its tiles. What
    the tiles' shape wastes, for the `flash_plan` instant."""
    i = np.arange(t_q, dtype=np.int64)
    if isinstance(causal, EarlierWindows):
        kept = np.minimum(i // causal.q_window * causal.k_window, t_k)
    elif isinstance(causal, SlidingWindow):
        kept = np.maximum(np.minimum(i, t_k - 1) - np.maximum(i - causal.window + 1, 0) + 1, 0)
    elif causal:
        kept = np.minimum(i + 1, t_k)
    else:
        kept = np.full_like(i, t_k)
    return float(kept.sum()) / max(plan.tiles_run * plan.block_q * plan.block_k, 1)


# ------------------------------------------------------ what `remat` keeps

# One chip's HBM as a v5e's memory_stats() states it (`bytes_limit`, 15.75
# GiB less 2 MiB): what a device that states no limit (the CPU) is taken for.
V5E_BYTES_LIMIT = 16_909_336_064
# float32 weights, gradients and Adam's two moments
STATE_BYTES_A_PARAMETER = 16
# Operands are kept while the training state and everything the policy
# saves stay under this share of the device's limit; the rest is for the
# blocks' inputs and what one block's backward holds at a time.
REMAT_SHARE = 0.8


class SavedLayers(NamedTuple):
    """What `count` layers of one kind name for a `remat` policy, each as
    {name: jax.ShapeDtypeStruct}: the `residuals` only a kernel can make
    (always kept) and the kernels' `operands` (kept where there is room)."""

    count: int
    residuals: dict
    operands: dict


class RematPlan(NamedTuple):
    """What the blocks' policy saves. `kept` has one {name: bytes a layer,
    as the program's values have them} for each kind of layer given."""

    kept: tuple
    operands_kept: bool
    saved_bytes: int   # every layer's, as the chip stores them
    state_bytes: int
    bytes_limit: int

    @property
    def names(self) -> tuple:
        return tuple(name for kind in self.kept for name in kind)


def stored_bytes(shape, dtype) -> int:
    """Bytes of one array as a TPU keeps it in HBM: the last dimension in
    whole 128-lane tiles (a 192-wide head takes 256), the one before it in
    whole tiles of 8 32-bit rows."""
    itemsize = jnp.dtype(dtype).itemsize
    dims = list(shape)
    dims[-1] = -(-dims[-1] // 128) * 128
    if len(dims) > 1:
        rows = 8 * max(4 // itemsize, 1)
        dims[-2] = -(-dims[-2] // rows) * rows
    return math.prod(dims) * itemsize


def flash_saves(b: int, t: int, h: int, d: int, d_v: int, dtype, causal: bool,
                layers: int) -> SavedLayers:
    """What _flash_vjp_fwd names in `layers` attention layers that call
    flash_attention with q, k [b, t, h, d] and v [b, t, h, d_v]."""
    plan = plan_flash(t, t, d, dtype, causal, d_v=d_v)
    sds, bh = jax.ShapeDtypeStruct, b * h
    o_lse = (sds((bh, plan.tq_pad, d_v), dtype), sds((bh, plan.tq_pad), jnp.float32))
    qkv = (sds((bh, plan.tq_pad, d), dtype), sds((bh, plan.tk_pad, d), dtype),
           sds((bh, plan.tk_pad, d_v), dtype))
    return SavedLayers(layers, dict(zip(FLASH_SAVED, o_lse)), dict(zip(FLASH_OPERANDS, qkv)))


def device_bytes_limit() -> int:
    """The first local device's memory limit; V5E_BYTES_LIMIT where it
    states none."""
    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("bytes_limit") or V5E_BYTES_LIMIT


def plan_remat_saves(kinds, n_params: int, bytes_limit: int) -> RematPlan:
    """The names a `remat` policy saves (models/transformer.remat_block),
    from what the program can observe as it is traced. Pure. `kinds` are
    the model's SavedLayers, `n_params` its parameters, `bytes_limit` the
    device's memory.

    The residuals are always kept. The operands are kept too, all of them,
    where the training state at STATE_BYTES_A_PARAMETER and everything
    saved, as the chip stores it (stored_bytes), come under REMAT_SHARE of
    the limit; a longer sequence or a larger batch falls back to the
    residuals alone."""
    def stored(group):
        return sum(kind.count * stored_bytes(a.shape, a.dtype)
                   for kind in kinds for a in getattr(kind, group).values())

    state = STATE_BYTES_A_PARAMETER * n_params
    residuals, operands = stored("residuals"), stored("operands")
    keep = state + residuals + operands <= REMAT_SHARE * bytes_limit
    nbytes = lambda a: a.size * jnp.dtype(a.dtype).itemsize
    kept = tuple({name: nbytes(a) for name, a in
                  (*kind.residuals.items(), *(kind.operands.items() if keep else ()))}
                 for kind in kinds)
    return RematPlan(kept, keep, residuals + (operands if keep else 0), state, bytes_limit)


# --------------------------------------------------------------- public API


def _pad_t(x, tp, value=0.0):
    """Pad axis 1 (sequence) of [BH, T, ...] up to tp with `value`."""
    t = x.shape[1]
    if tp == t:
        return x
    widths = [(0, 0), (0, tp - t)] + [(0, 0)] * (x.ndim - 2)
    return jnp.pad(x, widths, constant_values=value)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q3, k3, v3, scale, causal, block_q, block_k, k_len):
    o, _ = _flash_fwd(q3, k3, v3, scale, causal, block_q, block_k,
                      kernel_mode("flash_attention"), k_len=k_len)
    return o


def _flash_vjp_fwd(q3, k3, v3, scale, causal, block_q, block_k, k_len):
    mode = kernel_mode("flash_attention")
    o, lse = _flash_fwd(q3, k3, v3, scale, causal, block_q, block_k, mode,
                        k_len=k_len)
    o, lse = map(checkpoint_name, (o, lse), FLASH_SAVED)
    q3, k3, v3 = map(checkpoint_name, (q3, k3, v3), FLASH_OPERANDS)
    return o, (q3, k3, v3, o, lse)


def _flash_vjp_bwd(scale, causal, block_q, block_k, k_len, res, do3):
    q3, k3, v3, o3, lse = res
    mode = kernel_mode("flash_attention")
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32), axis=-1)
    return _flash_bwd(q3, k3, v3, lse, delta, do3, scale, causal,
                      block_q, block_k, mode, k_len=k_len)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@scope(FLASH)
def flash_attention(
    q: jax.Array,  # [B, T, H, D]
    k: jax.Array,
    v: jax.Array,  # [B, T, H, Dv]; Dv may differ from D (latent attention)
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jax.Array:
    """Drop-in replacement for ring_attention.full_attention ([B, T, H, D]
    in, [B, T, H, Dv] out), differentiable, Pallas-backed on TPU.

    Falls back to the jnp reference when Pallas is unavailable/disabled.
    Any T works: lengths that are not block multiples are zero-padded up
    to the block grid and the padded keys masked inside the kernels, so
    tiles stay MXU-shaped (no silent degradation to tiny blocks).
    block_q/block_k default to plan_flash's choice; the tests pass them.
    """
    if pallas_mode() is None:
        from ..parallel.ring_attention import full_attention

        with jax.named_scope("ps_flash_jnp"):
            return full_attention(q, k, v, causal=causal, scale=scale)

    b, t, h, d = q.shape
    dv = v.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    plan = plan_flash(t, t, d, q.dtype, causal, block_q, block_k, d_v=dv)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, x.shape[-1])
    q3 = _pad_t(fold(q), plan.tq_pad)
    k3, v3 = _pad_t(fold(k), plan.tk_pad), _pad_t(fold(v), plan.tk_pad)
    mask = causal if isinstance(causal, SlidingWindow) else bool(causal)
    o3 = _flash(q3, k3, v3, float(scale), mask, plan.block_q, plan.block_k, plan.k_len)
    o3 = o3[:, :t]
    return o3.reshape(b, h, t, dv).transpose(0, 2, 1, 3)


# ------------------------------------------- ring-hop partial-triple API
# (consumed by parallel/ring_attention.ring_flash_attention: flash WITHIN
# each ring hop, so a sequence shard never materializes [T_loc, T_loc])


def _no_band(causal, where: str) -> None:
    if isinstance(causal, SlidingWindow):
        raise NotImplementedError(
            f"{where}: a SlidingWindow over a ring's hops is not built (a hop a window or "
            "more behind the queries would be sent for nothing: ROADMAP M5); "
            "flash_attention takes it on one chip")


def flash_partial(q3, k3, v3, scale, causal, q_off, k_off,
                  block_q=None, block_k=None, mode=None):
    """One hop's UNNORMALIZED contribution: [BH, Tq, D] queries against a
    visiting K [BH, Tk, D] / V [BH, Tk, Dv] shard -> (pv f32 [BH, Tq, Dv],
    m f32 [BH, Tq], l f32 [BH, Tq]). q_off/k_off are the shards' global sequence offsets
    (traced scalars are fine: the walk over the hop's live tiles is built
    from them in jnp and rides in SMEM with them, so one compiled kernel
    serves every hop; Python ints give a constant walk and a grid of
    exactly its length). The
    caller merges triples across hops with the usual online-softmax
    rescale and normalizes once at the end.

    Shard lengths need not be block multiples: like flash_attention, odd
    lengths are padded up to the block grid (padded keys masked via
    k_len, padded query rows sliced off) so tiles stay MXU-shaped."""
    _no_band(causal, "flash_partial")
    tq, tk = q3.shape[1], k3.shape[1]
    plan = plan_flash(tq, tk, q3.shape[2], q3.dtype, causal, block_q, block_k,
                      d_v=v3.shape[2])
    q3 = _pad_t(q3, plan.tq_pad)
    k3, v3 = _pad_t(k3, plan.tk_pad), _pad_t(v3, plan.tk_pad)
    pv, m, l = _flash_fwd(
        q3, k3, v3, scale, causal, plan.block_q, plan.block_k,
        kernel_mode("flash_partial") if mode is None else mode,
        offsets=(q_off, k_off), normalize=False,
        k_len=plan.k_len,
    )
    return pv[:, :tq], m[:, :tq], l[:, :tq]


def flash_grads_partial(q3, k3, v3, do3, lse, delta, scale, causal,
                        q_off, k_off, block_q=None, block_k=None, mode=None):
    """One hop's gradient contributions (dq [BH, Tq, D], dk [BH, Tk, D],
    dv [BH, Tk, Dv], all f32) given the FINAL merged lse/delta — per-hop
    pieces sum to the exact flash backward (f32 out so cross-hop
    accumulation never rounds per hop, even under bf16 inputs). Odd shard
    lengths pad-and-mask exactly like flash_partial (padded q rows carry
    zero do/delta, so they contribute nothing to dk/dv)."""
    _no_band(causal, "flash_grads_partial")
    tq, tk = q3.shape[1], k3.shape[1]
    plan = plan_flash(tq, tk, q3.shape[2], q3.dtype, causal, block_q, block_k,
                      d_v=v3.shape[2])
    q3, do3 = _pad_t(q3, plan.tq_pad), _pad_t(do3, plan.tq_pad)
    # lse pads with +inf-ish so padded rows' p = exp(scores - lse)
    # underflows to 0 (their do/delta are zero-padded, so they'd
    # contribute nothing anyway — this just keeps exp() finite)
    lse = _pad_t(lse, plan.tq_pad, value=-NEG_INF)
    delta = _pad_t(delta, plan.tq_pad)
    k3, v3 = _pad_t(k3, plan.tk_pad), _pad_t(v3, plan.tk_pad)
    dq, dk, dv = _flash_bwd(
        q3, k3, v3, lse, delta, do3, scale, causal, plan.block_q,
        plan.block_k,
        kernel_mode("flash_grads_partial") if mode is None else mode,
        offsets=(q_off, k_off), out_dtype=jnp.float32,
        k_len=plan.k_len,
    )
    return dq[:, :tq], dk[:, :tk], dv[:, :tk]
