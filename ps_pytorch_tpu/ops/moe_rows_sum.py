"""The dropless expert layer's way back from a pass's rows to the tokens, as
one Pallas pass that fetches only the rows that are there:

    out[n] = sum over j of ys[pos[n, j]]   where held[n, j]

ys [M, D] holds a pass's rows, already weighted (parallel/moe.py); pos [N, k]
says in which row assignment (n, j) lies and held [N, k] whether its expert
is held here and its row in this pass. `rows_sum` is the entry. On a TPU (or
under PS_TPU_PALLAS_INTERPRET), for D of whole 128-lane tiles, the kernel
`ps_moe_rows_sum`; a grid step owns a tile of tokens:

- ys stays in HBM. The tile's k * tile entries ride in SMEM: a held
  assignment's word is (the top bit, its token in the tile, its row), the
  others' is 0 (made by XLA beside the call, N x k integers). The step packs
  the held words to the front of a list in SMEM: every word is written
  where the next held one belongs and only a held one moves that place on,
  so no branch; the only work that is sized by N x k, three scalar bundles
  a word.
- It starts one copy ys[row] -> VMEM for each word of the list, all in
  flight at once on one semaphore, and fetches nothing for the others.
- It then turns to the tile BEFORE its own, whose copies flew meanwhile: as
  many waits as that step counted (every copy moves the same bytes), each
  landed row added in float32 to its token's lines of an accumulator (a
  token's rows come in j's order, as the plain sum takes them), and the
  accumulator written out as [tile, D], rounded once. So the grid has one
  step more than there are tiles.

A copy may not take ONE row of a tiled [M, D] (a slice of the second minor
dim must be whole tiles). So ys is handed over in the order the chip keeps
its (8, 128) tiles in, [M / 8, D / 128, 8, 1, 128] (`_tile_order`), which
XLA compiles as a BITCAST of what the producer wrote, no pass of its own,
and in which one row is a slice on leading dims. A two-byte dtype's tile
packs two consecutive rows into each 32-bit word and a copy moves whole
words: such a ys goes as [M / 8, D / 128, 4, 2, 128], the copy brings the
row's pair, and the kernel reads the landed pair as 32-bit words and keeps
the half it wants (the even row is the low one). A landed row is `stride`
lines of 128 words (its D / 128 rounded up to whole registers), so a row is
added in two or three register operations whatever D is.

**What the call costs before it runs** (PERF.md section 6, PR 53). The body
is ROLLED: every loop over the tile's entries, the list, the tokens and a
row's 128-lane lines is a `fori_loop` the kernel runs, unrolled by
constants alone, so its jaxpr has the same equations at every N, k and D.
And the call goes through ONE module-level `jax.jit` (`_call`, keyed by the
operands' shapes and the mode): `pallas_call` traces its body anew at every
Python call and jax lowers a Pallas equation anew at every site, while a
jitted function is traced once for its avals and lowered once a module, and
XLA inlines it with the site's scope in front of the kernel's name. The
sites of a step's expert layers, which have one shape, share one trace of
the body and one Mosaic lowering a trace context.

Anywhere else the entry takes the plain form, handed in by the caller, under
`ps_moe_rows_sum_jnp`; `rows_sum_path` says which form a call takes.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .pallas_mode import COMPILED, INTERPRET, pallas_mode
from .rope import _put       # a store from inside a loop's turn, cast to the ref's dtype

LANES = 128
SUBLANES = 8
# a held entry's word: the top bit, its token's place in the tile, its row
ROW_BITS = 22
ROW_MASK = (1 << ROW_BITS) - 1
PLACE_MASK = (1 << (31 - ROW_BITS)) - 1
HELD_BIT = -1 << 31
# tokens a grid step owns: whole 128-lane tiles, and the two landing buffers
# (tile x k rows each, the worst case) stay under BUFFER_BYTES while the tile
# is above one lane tile
TILE_N = 256
BUFFER_BYTES = 32 << 20
VMEM_ROOM = 16 << 20      # the output's two tiles, the accumulator, Mosaic's own
SCAN_UNROLL = 8           # entries a turn of the packing loop takes: divides every tile
SMEM_BLOCK = 1024         # words: what a block of a one-dimensional operand is whole tiles of
ROW_UNROLL = 2            # held rows a turn of the loops over the list takes
OUT_TOKENS = 64           # tokens whose line c a turn of the way out moves: divides every tile


def rows_sum_path(d: int, dtype) -> str:
    """Which form `rows_sum` takes in this process for rows of d values of
    `dtype` (parallel/moe.combine_rows_read counts by it)."""
    if pallas_mode() is None or d % LANES or jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32):
        return "xla"
    return "pallas"


class RowsPlan(NamedTuple):
    tile: int       # tokens a grid step owns
    chunks: int     # lines of 128 words a copy lands: a row, or a two-byte dtype's pair of rows
    stride: int     # lines from one landed row to the next: whole registers
    rows: int       # tokens a turn of the way out takes through its registers

    def vmem_bytes(self, k: int) -> int:
        """The two landing buffers of a step."""
        return 2 * k * self.tile * self.stride * LANES * 4

    def entries_block(self, k: int) -> int:
        """Words of a tile's block of entries in SMEM: its k * tile, in whole SMEM_BLOCKs."""
        return -(-k * self.tile // SMEM_BLOCK) * SMEM_BLOCK


def plan_rows(n: int, k: int, d: int, dtype) -> RowsPlan:
    """The tiles at pos [n, k] over rows of d values of `dtype`, from the
    shapes alone."""
    chunks = d // LANES
    plan = RowsPlan(TILE_N, chunks, -(-chunks // SUBLANES) * SUBLANES,
                    SUBLANES * (4 // jnp.dtype(dtype).itemsize))
    while plan.tile > LANES and plan.vmem_bytes(k) > BUFFER_BYTES:
        plan = plan._replace(tile=plan.tile // 2)
    return plan


def _tile_order(y):
    """[M, D] -> [M / 8, D / 128, 8 / p, p, 128] with p rows a 32-bit word (1,
    or 2 of a two-byte dtype): the order the chip keeps the (8, 128) tiles of
    y in, so XLA reads this as a bitcast, and in it a word's rows are a slice
    on leading dims, [g, :, s]."""
    m, d = y.shape
    p = 4 // y.dtype.itemsize
    return y.reshape(m // SUBLANES, SUBLANES // p, p, d // LANES, LANES).transpose(0, 3, 1, 2, 4)


def _entries(pos, held, plan: RowsPlan):
    """int32 [tiles * block], a tile's k * tile words together (column j's
    after column j - 1's) at the front of its block (`plan.entries_block`):
    assignment (n, j)'s word is HELD_BIT | (n's place in its tile) << ROW_BITS
    | pos[n, j] where held, 0 elsewhere and past the last token (a ragged
    tile fetches nothing there). One dimension, because a word of a [k, tile]
    block in SMEM costs four scalar bundles to address."""
    (n, k), tile = pos.shape, plan.tile
    tiles, block = -(-n // tile), plan.entries_block(k)
    # plain lax, the tokens' places a constant: every jnp call here is a
    # jitted function of its own to trace, at every first trace of a process
    place = ((np.arange(n, dtype=np.int32) % tile) << ROW_BITS | HELD_BIT)[:, None]
    words = lax.select(held, lax.bitwise_or(pos, np.broadcast_to(place, pos.shape)),
                       np.zeros(pos.shape, np.int32))
    words = lax.pad(words, np.int32(0), ((0, -n % tile, 0), (0, 0, 0)))
    words = lax.reshape(lax.transpose(lax.reshape(words, (tiles, tile, k)), (0, 2, 1)), (tiles, k * tile))
    return lax.reshape(lax.pad(words, np.int32(0), ((0, 0, 0), (0, block - k * tile, 0))), (tiles * block,))


def _each(count, turn):
    """turn(i) for i below `count` (traced), ROW_UNROLL of them a turn of the
    loop so that one's scalar work hides behind another's copies and
    registers, the odd ones out one by one."""
    def some(p, carry):
        for u in range(ROW_UNROLL):
            turn(p * ROW_UNROLL + u)
        return carry

    whole = count // ROW_UNROLL
    lax.fori_loop(0, whole, some, 0)
    if ROW_UNROLL > 1:
        lax.fori_loop(whole * ROW_UNROLL, count, lambda i, carry: (turn(i), carry)[1], 0)


def _kernel(ent_ref, y_ref, o_ref, land, sem, held_list, count, acc, *, plan: RowsPlan, k: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    step, tiles = pl.program_id(0), pl.num_programs(0) - 1
    tile, chunks, stride, cap = plan.tile, plan.chunks, plan.stride, plan.tile * k
    pairs = land.dtype.itemsize == 2
    # the landed words as [lines, 128]: a fetched row is `stride` lines from
    # a whole register's boundary, `chunks` of them its own
    words = land.bitcast(jnp.uint32) if pairs else land
    lines = words.reshape(words.shape[0], LANES)

    def row_copy(slot, at, row):
        """ys' row `row` (its pair) -> the list's place `at`, on the slot's semaphore."""
        in_tile = row & (SUBLANES - 1)
        return pltpu.make_async_copy(y_ref.at[row >> 3, :, in_tile >> 1 if pairs else in_tile],
                                     land.at[pl.ds(at * stride, chunks)], sem.at[slot])

    @pl.when(step < tiles)
    def _start():
        slot = step % 2
        base = slot * cap

        def entries(g, at):
            # every word is written where the next held one belongs, and only
            # a held one (its top bit) moves that place on: no branch, one
            # shift and one addition a word
            first = g * SCAN_UNROLL
            for t in range(SCAN_UNROLL):
                word = ent_ref[first + t]
                _put(held_list, at, word)
                at = at + lax.shift_right_logical(word, 31)
            return at

        held = lax.fori_loop(0, cap // SCAN_UNROLL, entries, base) - base
        _put(count, slot, held)
        _each(held, lambda i: row_copy(slot, base + i, held_list[base + i] & ROW_MASK).start())

    @pl.when(step > 0)
    def _sum():
        slot = (step - 1) % 2
        base, held = slot * cap, count[slot]
        _each(held, lambda i: row_copy(slot, 0, 0).wait())      # any row's bytes: the copies are of one size
        _put(acc, ..., jnp.zeros(acc.shape, f32))

        def add(i):
            word = held_list[base + i]
            src = pl.ds(pl.multiple_of((base + i) * stride, stride), stride)
            dst = pl.ds(pl.multiple_of(((word >> ROW_BITS) & PLACE_MASK) * stride, stride), stride)
            got = lines[src, :]
            if pairs:       # the even row is the low half; a bfloat16 is the high half of its float32
                got = (got >> (16 * (word & 1)).astype(jnp.uint32)) << 16
            _put(acc, dst, acc[dst, :] + lax.bitcast_convert_type(got, f32))

        _each(held, add)

        def turn(g, carry):
            # token-major lines -> the [tile, D] the caller reads: eight
            # tokens' line c stand `stride` lines apart, one strided load;
            # OUT_TOKENS tokens' line c a turn, so the loops' own cost is
            # spread over a dozen registers
            t0 = pl.multiple_of(g * OUT_TOKENS, OUT_TOKENS)

            def line(c, carry):
                lanes = pl.ds(pl.multiple_of(c * LANES, LANES), LANES)
                first = t0 * stride + c
                for r in range(0, OUT_TOKENS, plan.rows):
                    part = jnp.concatenate(
                        [acc[pl.ds(first + (r + s) * stride, SUBLANES, stride=stride), :]
                         for s in range(0, plan.rows, SUBLANES)], axis=0)
                    _put(o_ref, (pl.ds(t0 + r, plan.rows), lanes), part)
                return carry

            return lax.fori_loop(0, chunks, line, carry)

        lax.fori_loop(0, tile // OUT_TOKENS, turn, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(ys, pos, held, *, interpret: bool):
    """The kernel behind ONE jit: a step's sites of one shape share its
    trace, the body's with it, and its lowering (the module's docstring)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (n, k), (_, d), out_dtype = pos.shape, ys.shape, ys.dtype
    plan = plan_rows(n, k, d, out_dtype)
    tiles, cap = pl.cdiv(n, plan.tile), plan.tile * k
    if ys.dtype.itemsize == 4:
        ys = lax.bitcast_convert_type(ys, jnp.uint32)
    return pl.pallas_call(
        functools.partial(_kernel, plan=plan, k=k),
        name="ps_moe_rows_sum",
        grid=(tiles + 1,),
        in_specs=[
            pl.BlockSpec((plan.entries_block(k),), lambda s: (jnp.minimum(s, tiles - 1),),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((plan.tile, d), lambda s: (jnp.maximum(s - 1, 0), 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), out_dtype),
        scratch_shapes=[pltpu.VMEM((2 * cap * plan.stride, 4 // ys.dtype.itemsize, LANES), ys.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((2 * cap,), jnp.int32), pltpu.SMEM((2,), jnp.int32),
                        pltpu.VMEM((plan.tile * plan.stride, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=plan.vmem_bytes(k) + VMEM_ROOM,
            # a held entry's row is `_pass_route`'s: inside the pass's rows
            disable_bounds_checks=True),
        **(INTERPRET if interpret else COMPILED),
    )(_entries(pos, held, plan), _tile_order(ys))


def rows_sum(ys, pos, held, twin):
    """out [N, D] in ys' dtype: token n the float32 sum of the rows
    ys[pos[n, j]] of ys [M, D] over the j with held[n, j] (pos int32, held
    bool, both [N, k]), rounded once; zeros where it has none. `twin(ys)` is
    the plain form, taken where `rows_sum_path` says "xla": the caller hands
    it in. M is whole (8, 128) tiles and under 2 ** ROW_BITS (a pass's rows
    are whole tiles of 256)."""
    if rows_sum_path(ys.shape[1], ys.dtype) == "xla":
        with jax.named_scope("ps_moe_rows_sum_jnp"):
            return twin(ys)
    if ys.shape[0] % SUBLANES or ys.shape[0] >> ROW_BITS:
        raise ValueError(f"{ys.shape[0]} rows: whole tiles of {SUBLANES}, under {1 << ROW_BITS}")
    return _call(ys, pos, held, interpret=pallas_mode() is INTERPRET)
