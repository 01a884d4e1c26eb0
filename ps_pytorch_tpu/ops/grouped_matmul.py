"""Grouped matrix products for dropless expert layers (Pallas TPU kernels).

An expert layer that drops no token sorts its (token, expert) assignments
by expert and multiplies each expert's rows by that expert's weights. The
rows are laid out for the worst case (every assignment routed here), so
most of that layout is empty on a chip that holds a share of the experts;
what the products cost has to follow the rows that are really there. The
caller (parallel/moe.py) walks the layout in passes of a buffer sized to
the load: `layout_pass` gives one pass's part of it as a layout of its own.

Layout (`GroupLayout`, built by `group_layout` from the per-expert counts):
each expert's rows start on a row-tile boundary, so a `tile_m`-row tile
belongs to exactly one expert. `tile_expert[i]` names it, `n_live` counts
the tiles that hold rows; both ride in SMEM (scalar prefetch). A tile past
`n_live` does no work and moves no data: its block indices are those of the
last live tile, so the pipeline fetches and writes nothing new (the same
idea as flash_attention._tile_live, decided from SMEM at run time). Every
expert owns at least one tile, also with no rows, so `n_live >= 1`. The
weight-gradient kernel zeroes the block of every expert that owns a live
tile and leaves the others' unwritten: a whole layout names every expert,
one pass of it only some, so `grouped_matmul`'s backward reads the kernel's
result through `experts_live`. The caller keeps rows that hold no
assignment ZERO (parallel/moe.py does); rows past the last live tile are
never written and hold garbage that nothing may read.

Three products make a layer's forward and backward:

- `ps_moe_gmm`:  out[rows of e] = x[rows of e] @ w[e]        (forward)
- `ps_moe_gmm` with the right side transposed: dx = dy @ w[e]^T
- `ps_moe_tgmm`: dw[e] = x[rows of e]^T @ dy[rows of e], float32

`grouped_matmul` ties them into one differentiable call. Selection is
ops/pallas_mode.py's: Mosaic on a TPU, the interpreter under
PS_TPU_PALLAS_INTERPRET=1, otherwise the jnp twin `jax.lax.ragged_dot`
over the same layout (also the tests' oracle). PERF.md (PR 27) has both
measured on the chip.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .pallas_mode import COMPILED, INTERPRET, pallas_mode

TILE_M = 256      # rows a tile: padding is half a tile an expert on average
TILE_N_GRAD = 256  # columns of a weight-gradient block (float32 in VMEM)
# ps_moe_gmm keeps one expert's whole matrix resident, double-buffered beside
# a row tile in and out. Up to here that fits the 16 MiB a v5e kernel gets
# by default with room for Mosaic's own temporaries (12.2 MiB at 2304 x 1024,
# the widest of the accepted cells); past it (16 MiB at 3072 x 1024) the call
# asks for what it holds plus that room.
GMM_VMEM_DEFAULT = 14 * 2 ** 20
GMM_VMEM_ROOM = 8 * 2 ** 20

_TN = (((0,), (0,)), ((), ()))  # a.T @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


class GroupLayout(NamedTuple):
    """Where each expert's rows lie in a [rows, ...] buffer of `tile_m`-row
    tiles. `starts[e]` is expert e's first row; `sizes[e]` its rows padded
    up to whole tiles (at least one): what ragged_dot takes as group sizes."""

    tile_expert: jax.Array  # int32 [rows / tile_m]
    n_live: jax.Array       # int32 [1]
    starts: jax.Array       # int32 [E]
    sizes: jax.Array        # int32 [E]


def buffer_rows(assignments: int, num_experts: int, tile_m: int = TILE_M) -> int:
    """Rows of the buffer that holds any routing of `assignments` rows over
    `num_experts` experts: every expert may waste all but one row of its
    last tile, and one with no rows still owns a tile."""
    return (-(-assignments // tile_m) + num_experts) * tile_m


def group_layout(counts: jax.Array, rows: int, tile_m: int = TILE_M) -> GroupLayout:
    """The layout for `counts[e]` rows an expert in a buffer of `rows` rows
    (buffer_rows gives a size that always fits)."""
    tiles = jnp.maximum(-(-counts // tile_m), 1).astype(jnp.int32)
    ends = jnp.cumsum(tiles)
    n_tiles = rows // tile_m
    # tile i belongs to the first expert whose tiles end after it; tiles
    # past the last take the last expert (they are never run)
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(n_tiles, dtype=jnp.int32), side="right"),
        counts.shape[0] - 1).astype(jnp.int32)
    return GroupLayout(tile_expert=tile_expert, n_live=ends[-1:].astype(jnp.int32),
                       starts=((ends - tiles) * tile_m).astype(jnp.int32),
                       sizes=(tiles * tile_m).astype(jnp.int32))


def layout_pass(layout: GroupLayout, p, rows: int, tile_m: int = TILE_M) -> GroupLayout:
    """Rows p * rows .. (p + 1) * rows - 1 of `layout` as the layout of a
    [rows, ...] buffer of their own (p may be traced; `layout.tile_expert`
    reaches to the end of that pass): its tiles' experts, the live tiles
    among them (at least one: a pass past the last live tile is not to be
    run) and each expert's rows INSIDE the pass, none for most. An expert
    whose rows straddle the boundary has a part in either pass."""
    tiles = rows // tile_m
    ends = jnp.clip(layout.starts + layout.sizes - p * rows, 0, rows)
    starts = jnp.clip(layout.starts - p * rows, 0, rows)
    return GroupLayout(
        tile_expert=jax.lax.dynamic_slice(layout.tile_expert, (p * tiles,), (tiles,)),
        n_live=jnp.clip(layout.n_live - p * tiles, 1, tiles).astype(jnp.int32),
        starts=starts.astype(jnp.int32), sizes=(ends - starts).astype(jnp.int32))


def experts_live(layout: GroupLayout, num_experts: int) -> jax.Array:
    """bool [E]: the experts that own a live tile of `layout`."""
    tile = jnp.arange(layout.tile_expert.shape[0], dtype=jnp.int32)
    owns = layout.tile_expert[None] == jnp.arange(num_experts, dtype=jnp.int32)[:, None]
    return jnp.any(owns & (tile < layout.n_live[0])[None], axis=1)


# ---------------------------------------------------------------- kernels


def gmm_vmem_bytes(tile_m: int, k: int, n: int, itemsize: int) -> int:
    """What a grid step of ps_moe_gmm holds: a row tile in, one expert's
    matrix and a row tile out, each double-buffered by the pipeline."""
    return 2 * (tile_m * k + k * n + tile_m * n) * itemsize


def _gmm(x, w, layout: GroupLayout, tile_m: int, transpose_rhs: bool, mode: dict):
    """x [M, K] @ w[e] ([E, K, N], or [E, N, K] with transpose_rhs) -> [M, N]
    in x's dtype, float32 accumulation; one row tile a grid step, the whole
    of one expert's matrix resident while its tiles pass."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    n = w.shape[1] if transpose_rhs else w.shape[2]
    dims = _NT if transpose_rhs else (((1,), (0,)), ((), ()))

    def kernel(te_ref, nl_ref, x_ref, w_ref, o_ref):
        @pl.when(pl.program_id(0) < nl_ref[0])
        def _():
            o_ref[...] = jax.lax.dot_general(
                x_ref[...], w_ref[0], dims, preferred_element_type=jnp.float32
            ).astype(o_ref.dtype)

    last = lambda i, nl: jnp.minimum(i, nl[0] - 1)
    held = gmm_vmem_bytes(tile_m, k, n, x.dtype.itemsize)
    limit = {} if held <= GMM_VMEM_DEFAULT else {
        "compiler_params": pltpu.CompilerParams(vmem_limit_bytes=held + GMM_VMEM_ROOM)}
    return pl.pallas_call(
        kernel,
        name="ps_moe_gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m // tile_m,),
            in_specs=[
                pl.BlockSpec((tile_m, k), lambda i, te, nl: (last(i, nl), 0)),
                pl.BlockSpec((1,) + w.shape[1:],
                             lambda i, te, nl: (te[last(i, nl)], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tile_m, n), lambda i, te, nl: (last(i, nl), 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        **limit, **mode,
    )(layout.tile_expert, layout.n_live, x, w)


def _tgmm(x, dy, layout: GroupLayout, tile_m: int, num_experts: int, mode: dict):
    """dw[e] = x[rows of e]^T @ dy[rows of e]: [E, K, N] float32. Column
    blocks outermost, row tiles innermost, so an expert's block stays in
    VMEM while its tiles add to it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    n = dy.shape[1]
    tn = TILE_N_GRAD if n % TILE_N_GRAD == 0 else n

    def kernel(te_ref, nl_ref, x_ref, dy_ref, o_ref):
        i = pl.program_id(1)
        live = i < nl_ref[0]
        first = (i == 0) | (te_ref[i] != te_ref[jnp.maximum(i - 1, 0)])

        @pl.when(live & first)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(live)
        def _():
            o_ref[0] += jax.lax.dot_general(
                x_ref[...], dy_ref[...], _TN, preferred_element_type=jnp.float32)

    last = lambda i, nl: jnp.minimum(i, nl[0] - 1)
    return pl.pallas_call(
        kernel,
        name="ps_moe_tgmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, m // tile_m),
            in_specs=[
                pl.BlockSpec((tile_m, k), lambda j, i, te, nl: (last(i, nl), 0)),
                pl.BlockSpec((tile_m, tn), lambda j, i, te, nl: (last(i, nl), j)),
            ],
            out_specs=pl.BlockSpec(
                (1, k, tn), lambda j, i, te, nl: (te[last(i, nl)], 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((num_experts, k, n), jnp.float32),
        **mode,
    )(layout.tile_expert, layout.n_live, x, dy)


# ------------------------------------------------------------- public API


def _ragged(x, w, layout):
    """The jnp twin: XLA's ragged_dot over the same padded groups."""
    with jax.named_scope("ps_moe_gmm_jnp"):
        return jax.lax.ragged_dot(x, w.astype(x.dtype), layout.sizes,
                                  preferred_element_type=jnp.float32).astype(x.dtype)


def _mode(interpret: bool) -> dict:
    return INTERPRET if interpret else COMPILED


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped(x, w, layout, tile_m, interpret):
    return _gmm(x, w.astype(x.dtype), layout, tile_m, False, _mode(interpret))


def _grouped_fwd(x, w, layout, tile_m, interpret):
    return _grouped(x, w, layout, tile_m, interpret), (x, w, layout)


def _grouped_bwd(tile_m, interpret, res, dy):
    x, w, layout = res
    mode = _mode(interpret)
    dx = _gmm(dy, w.astype(dy.dtype), layout, tile_m, True, mode)
    # the kernel leaves the block of an expert with no live tile unwritten
    dw = jnp.where(experts_live(layout, w.shape[0])[:, None, None],
                   _tgmm(x, dy, layout, tile_m, w.shape[0], mode), 0).astype(w.dtype)
    return dx, dw, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(x: jax.Array, w: jax.Array, layout: GroupLayout,
                   tile_m: int = TILE_M) -> jax.Array:
    """out[r] = x[r] @ w[expert of row r] for the rows `layout` places:
    x [M, K] (M a multiple of tile_m, empty rows zero), w [E, K, N] in any
    float dtype (cast to x's at use; its gradient comes back in its own).
    Rows past the last live tile are undefined. Differentiable in x and w."""
    mode = pallas_mode()
    if mode is None:
        return _ragged(x, w, layout)
    return _grouped(x, w, layout, tile_m, bool(mode.get("interpret")))
