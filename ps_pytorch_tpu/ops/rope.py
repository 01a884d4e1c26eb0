"""The rotation of a head's leading dims by position, as the grouped-query
attention families run it on q and k in front of the flash kernels:

    y[..., j]         = x[..., j] c_j - x[..., j + r/2] s_j      (j < r/2)
    y[..., j + r/2]   = x[..., j + r/2] c_j + x[..., j] s_j
    y[..., r:]        = x[..., r:]

with c_j = scale cos(pos f_j), s_j = scale sin(pos f_j): the halves layout,
plain or YaRN, a whole head or its leading part.

`rotate_leading` is the entry. On a TPU (or under PS_TPU_PALLAS_INTERPRET),
for heads of one 128-lane tile, ONE Pallas pass, `ps_rope`, over x in its
own dtype: lane for lane of a head

    y = x * cos + partner(x) * sin

where `partner` is the head's lanes rotated by r/2 (towards the front on
lanes below r/2, towards the back above: `pltpu.roll` of a register, never a
slice or a concatenate in HBM) and cos, sin are two float32 tables [T, 128]
made once a call in jnp (`rope_tables`): cos is 1 and sin 0 past r, sin
carries the minus of the front half. So default and YaRN, whole and half
heads are one kernel that knows the shift and nothing of the angles. The op
is linear in x: its `jax.custom_vjp` keeps the two tables and nothing of x,
and the backward is the SAME kernel handed the sines of the opposite
rotation. Float32 inside, one rounding to x's dtype.

The pass also moves the heads: it reads x as the projection wrote it, [B, T,
H * d], and writes [B, H, T, d], which is what the flash kernels' fold makes
of [B, T, H, d] (the entry hands back that array's transpose, so XLA sees
the two transposes cancel); the backward reads the gradient folded and
writes it flat. On the chip [B, T, H * d] -> [B, T, H, d] is no free
reshape: the tiles of the first hold eight tokens of a head and the tiles of
the second eight heads of a token, and XLA made the fold behind a flat
kernel in two copies of q (PERF.md section 6, PR 50).

Anywhere else the entry takes the plain rotation it is handed (`twin`:
models/swa_moe._rope_leading, float32 slices and a concatenate) under
`ps_rope_jnp`; `rope_path` says which form a call takes.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .pallas_mode import COMPILED, INTERPRET, pallas_mode

LANES = 128
# the tile a grid step moves and the rows a turn of the kernel's loop takes
# through its registers, from the sweeps on the chip (PERF.md section 6, PR
# 50): any tile of 1,024 channels or more reads within 1% of a plain pass over
# the same bytes (512 channels 10-30% more), in turns of 32 or of 64 rows
BLOCK_T = 512
BLOCK_C = 2048
ROWS = 32
VMEM_LIMIT = 48 << 20


def rope_path(head_dim: int, r: int) -> str:
    """Which form `rotate_leading` takes in this process for heads of
    `head_dim` whose first r dims turn (the families' plan instants record
    it)."""
    if pallas_mode() is None or head_dim != LANES or r % 2 or not 0 < r <= LANES:
        return "xla"
    return "pallas"


class RopePlan(NamedTuple):
    block_t: int    # rows of a grid step's tile
    block_c: int    # its channels: whole heads
    rows: int       # rows a turn of the kernel's loop takes

    def vmem_bytes(self, dtype) -> int:
        """What a grid step holds: x's tile in and out and the two float32
        tables' blocks, each twice (the pipeline's other buffer)."""
        return 2 * self.block_t * (2 * self.block_c * jnp.dtype(dtype).itemsize + 2 * LANES * 4)


def plan_rope(t: int, width: int, dtype) -> RopePlan:
    """The tiles at x [*, t, width] of `dtype`, from the shapes alone: the
    widest block of whole heads up to BLOCK_C that divides the width, time
    tiles of BLOCK_T rows (the last may be ragged), in turns of ROWS."""
    rows = max(ROWS, 8 * max(1, 4 // jnp.dtype(dtype).itemsize))   # a packed dtype's register holds more rows
    block_c = next(c for c in range(min(BLOCK_C, width), 0, -LANES) if width % c == 0)
    block_t = min(BLOCK_T, -(-t // rows) * rows)
    return RopePlan(block_t, block_c, rows)


class RopeHow(NamedTuple):
    """What a call's forward and backward share beside their operands
    (hashable: the custom VJP's static argument). `interpret` is the mode
    the forward was traced under: the backward, traced later and perhaps
    from a cached trace of the caller, takes the same."""
    shift: int          # r / 2: how far a lane's partner stands
    interpret: bool
    folded_in: bool = False     # x comes as [B, H, T, d] and leaves as [B, T, H * d]; else the reverse

    @property
    def mode(self) -> dict:
        return INTERPRET if self.interpret else COMPILED


def rope_tables(pos, freqs, scale: float, head_dim: int = LANES):
    """(cos, sin) float32 [T, head_dim] for `ps_rope`: positions `pos` [T],
    `freqs` [r / 2] angles a position, cos and sin times `scale` (what
    models/swa_moe.Rope.frequencies gives). cos is 1 and sin 0 on the lanes
    past r; sin is negative on the front half, whose partner is subtracted."""
    f32 = jnp.float32
    ang = pos.astype(f32)[:, None] * jnp.asarray(freqs)[None]            # [T, r/2]
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    rest = head_dim - 2 * ang.shape[1]
    return (jnp.concatenate([cos, cos, jnp.ones((ang.shape[0], rest), f32)], axis=1),
            jnp.concatenate([-sin, sin, jnp.zeros((ang.shape[0], rest), f32)], axis=1))


def _head(ref, g: int, rows):
    """Where head g's `rows` lie in a tile: [heads, rows, d] folded, [rows,
    heads * d] flat."""
    return (g, rows, slice(None)) if len(ref.shape) == 3 else (rows, slice(g * LANES, (g + 1) * LANES))


def _put(ref, at, value):
    """A store from inside a loop's turn (a ref is the turn's closure, and
    pslint PSL003 reads a subscript store to a closure as a side effect)."""
    ref[at] = value.astype(ref.dtype)


def _kernel(x_ref, cos_ref, sin_ref, o_ref, *, shift: int, rows: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    front = lax.broadcasted_iota(jnp.int32, (rows, LANES), 1) < shift
    block_t = cos_ref.shape[0]
    heads = x_ref.shape[0] if len(x_ref.shape) == 3 else x_ref.shape[1] // LANES

    def partner(x):
        """Lane j's partner: j + shift on the front half, j - shift behind
        it (past r the table holds 0, so what stands here does not count)."""
        back = pltpu.roll(x, shift, 1)
        if 2 * shift == LANES:             # a whole head: both rotations are the same
            return back
        return jnp.where(front, pltpu.roll(x, LANES - shift, 1), back)

    def turn(j, carry):
        at = pl.ds(pl.multiple_of(j * rows, rows), rows)
        cos, sin = cos_ref[at, :], sin_ref[at, :]
        for g in range(heads):             # a head at a time, its tables read once
            x = x_ref[_head(x_ref, g, at)].astype(f32)
            _put(o_ref, _head(o_ref, g, at), x * cos + partner(x) * sin)
        return carry

    lax.fori_loop(0, block_t // rows, turn, 0)


def _call(x, cos, sin, how: RopeHow):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if how.folded_in:
        b, h, t, _ = x.shape
    else:
        b, t, h = x.shape[0], x.shape[1], x.shape[2] // LANES
    plan = plan_rope(t, h * LANES, x.dtype)
    bt, bc = plan.block_t, plan.block_c
    flat = pl.BlockSpec((None, bt, bc), lambda bi, ti, ci: (bi, ti, ci))
    folded = pl.BlockSpec((None, bc // LANES, bt, LANES), lambda bi, ti, ci: (bi, ci, ti, 0))
    x_tile, o_tile, o_shape = ((folded, flat, (b, t, h * LANES)) if how.folded_in
                               else (flat, folded, (b, h, t, LANES)))
    table = pl.BlockSpec((bt, LANES), lambda bi, ti, ci: (ti, 0))     # the same block for every head of a time tile
    return pl.pallas_call(
        functools.partial(_kernel, shift=how.shift, rows=plan.rows),
        name="ps_rope",
        grid=(b, pl.cdiv(t, bt), h * LANES // bc),
        in_specs=[x_tile, table, table],
        out_specs=o_tile,
        out_shape=jax.ShapeDtypeStruct(o_shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        **how.mode,
    )(x, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rope_kernel(x, cos, sin, how: RopeHow):
    return _call(x, cos, sin, how)


def _rope_kernel_fwd(x, cos, sin, how):
    return _rope_kernel(x, cos, sin, how), (cos, sin)


def _rope_kernel_bwd(how, tables, dy):
    cos, sin = tables
    # the transpose of a rotation is the rotation back, and of the fold the
    # unfold; the tables come from integer positions and static angles, and
    # take no gradient
    return _rope_kernel(dy, cos, -sin, how._replace(folded_in=not how.folded_in)), None, None


_rope_kernel.defvjp(_rope_kernel_fwd, _rope_kernel_bwd)


def rotate_leading(xs, pos, freqs, scale: float, twin):
    """Each x [B, T, H, d] of `xs` (q and k: they share their positions, so
    the tables are made once) with its heads' first r = 2 len(freqs) dims
    rotated by pos[t] * freqs in the halves layout, cos and sin times
    `scale`; the same shapes and dtypes, float32 inside. `twin(x)` is the
    plain rotation of one x, taken where `rope_path` says "xla": the caller
    hands it in (models/swa_moe._rope_leading at its own pos and Rope)."""
    d, shift = xs[0].shape[-1], len(freqs)
    if rope_path(d, 2 * shift) == "xla":
        with jax.named_scope("ps_rope_jnp"):
            return tuple(twin(x) for x in xs)
    cos, sin = rope_tables(pos, freqs, scale, d)
    how = RopeHow(shift, pallas_mode() is INTERPRET)
    # [B, T, H * d] in, [B, H, T, d] out: the transpose back is the one the
    # flash kernels' fold undoes
    return tuple(_rope_kernel(x.reshape(x.shape[:2] + (-1,)), cos, sin, how).swapaxes(1, 2) for x in xs)
