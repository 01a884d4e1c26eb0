"""The short depthwise causal conv over time with its bias and silu, as the
state-space and the delta-rule mixers run it in front of their scans:

    z[b, t, c] = sum_i w[i, c] x[b, t - (K - 1 - i), c] + bias[c]     (x = 0 before t = 0)
    y = silu(z)

`causal_conv_silu` is the entry. On a TPU (or under
PS_TPU_PALLAS_INTERPRET), for channels of whole 128-lane tiles, ONE Pallas
pass forward and ONE backward under a `jax.custom_vjp`:

- `ps_causal_conv_fwd` walks a row's time tiles in order for a block of
  channels and carries the tile's last HALO rows to the next in VMEM; the K
  - 1 shifts are sublane rotations of registers, never a copy in HBM.
- `ps_causal_conv_bwd` walks them from the last to the first: z is made
  again from x (the HALO rows before the tile come by a BlockSpec of their
  own), dz = dy silu'(z), dx is the anti-causal conv of dz (the rows after
  the tile are carried from the tile walked before), and dw, db are summed
  in float32 in an output block that stays in VMEM over all of (B, T).

Nothing is kept for the backward but x, the taps and the bias. Every value
is float32 inside, whatever x's dtype; the result is rounded once, to
`out_dtype`, where the call site rounded it before the kernels existed.
Where the caller asks for it and a head is one 128-lane tile, the L2 norm a
head (ops/kda.l2_normalize) stands between the silu and that rounding, and
its gradient between dy and dz: on the chip XLA ran it as passes and
relayouts of float32 [B, T, H, 128] that cost more than the conv (PERF.md
section 6, PR 48).

Anywhere else the entry takes the plain conv it is handed (`twin`:
models/ssm_hybrid._causal_conv, a pad, K shifted slices, a sum) under
`ps_causal_conv_jnp`; `conv_path` says which form a call takes.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .kda import l2_normalize
from .pallas_mode import COMPILED, INTERPRET, pallas_mode

# rows of float32 a tile is handed of its neighbour: one register's sublanes,
# so taps - 1 <= HALO
HALO = 8
LANES = 128
# rows a kernel takes through its registers at a time, and the tile a grid
# step moves: the largest of the sweep on the chip (PERF.md section 6, PR 48;
# 512 rows cost 5-20% more, 128 channels 25%), whose backward holds three
# float32 tiles twice over, 24 MiB
ROWS = 32
BLOCK_T = 2048
BLOCK_C = 512
VMEM_LIMIT = 48 << 20
NORM_EPS = 1e-6          # ops/kda.l2_normalize's default, which the delta-rule mixers take


def conv_path(channels: int, taps: int = 4) -> str:
    """Which form `causal_conv_silu` takes in this process at this width
    (the families' plan instants record it)."""
    if pallas_mode() is None or channels % LANES or taps - 1 > HALO:
        return "xla"
    return "pallas"


class ConvPlan(NamedTuple):
    block_t: int    # rows of a grid step's tile
    block_c: int    # its channels
    rows: int       # rows a turn of the kernel's loop takes
    halo: int       # rows of x's dtype that hold HALO float32 rows' worth of sublanes


def plan_conv(t: int, channels: int, dtype) -> ConvPlan:
    """The tiles at x [*, t, channels] of `dtype`, from the shapes alone:
    the widest block of channels up to BLOCK_C that divides them, time
    tiles of BLOCK_T rows (the last may be ragged), in turns of ROWS."""
    halo = HALO * max(1, 4 // jnp.dtype(dtype).itemsize)     # a packed dtype's register holds more rows
    rows = max(ROWS, halo)
    block_c = next(c for c in range(BLOCK_C, 0, -LANES) if channels % c == 0)
    block_t = min(BLOCK_T, -(-t // rows) * rows)
    return ConvPlan(block_t, block_c, rows, halo)


class ConvHow(NamedTuple):
    """What a call's two kernels share beside their operands (hashable: the
    custom VJP's static argument). `interpret` is the mode the forward was
    traced under: the backward, traced later and perhaps from a cached
    trace of the caller, takes the same."""
    out_dtype: jnp.dtype
    interpret: bool
    head_scale: Optional[float]     # L2-normalise each 128-lane head times this; None: no norm

    @property
    def mode(self) -> dict:
        return INTERPRET if self.interpret else COMPILED


def _earlier(ext, s: int):
    """Rows t - s of a tile that stands under its HALO rows: [R, C]."""
    from jax.experimental.pallas import tpu as pltpu

    return (ext if s == 0 else pltpu.roll(ext, s, 0))[HALO:]


def _later(ext, s: int):
    """Rows t + s of a tile that stands over the HALO rows after it."""
    from jax.experimental.pallas import tpu as pltpu

    n = ext.shape[0]
    return (ext if s == 0 else pltpu.roll(ext, n - s, 0))[:n - HALO]


def _pre_activation(ext, w, bias):
    """z and the K shifted tiles it is summed from (tap i meets rows t - (K
    - 1 - i)), in the order the plain conv sums them."""
    k = w.shape[0]
    shifted = [_earlier(ext, k - 1 - i) for i in range(k)]
    z = shifted[0] * w[0:1]
    for i in range(1, k):
        z = z + shifted[i] * w[i:i + 1]
    return (z if bias is None else z + bias), shifted


def _put(ref, at, value):
    """A store from inside a loop's turn (a ref is the turn's closure, and
    pslint PSL003 reads a subscript store to a closure as a side effect)."""
    ref[at, :] = value.astype(ref.dtype)


def _heads(y):
    """y [R, C] a 128-lane head at a time."""
    return [y[:, g * LANES:(g + 1) * LANES] for g in range(y.shape[1] // LANES)]


def _head_norm(y, scale: float):
    """ops/kda.l2_normalize over each head of y [R, C]."""
    return jnp.concatenate([l2_normalize(h, scale, NORM_EPS) for h in _heads(y)], axis=1)


def _head_norm_bwd(y, dn, scale: float):
    """dy of `_head_norm`, as jax.grad of l2_normalize writes it."""
    out = []
    for h, g in zip(_heads(y), _heads(dn)):
        u = jnp.sum(jnp.square(h), axis=-1, keepdims=True) + NORM_EPS
        q = lax.rsqrt(u)
        out.append(g * (q * scale) - h * (jnp.sum(g * h, axis=-1, keepdims=True) * (q / u * scale)))
    return jnp.concatenate(out, axis=1)


def _fwd_kernel(*refs, rows: int, has_bias: bool, head_scale):
    from jax.experimental import pallas as pl

    x_ref, w_ref = refs[:2]
    b_ref = refs[2] if has_bias else None
    o_ref, carry_ref = refs[-2:]
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():                                   # nothing stands before a row's first token
        carry_ref[...] = jnp.zeros_like(carry_ref)

    w = w_ref[...]
    bias = b_ref[...] if has_bias else None

    def turn(j, before):
        at = pl.ds(pl.multiple_of(j * rows, rows), rows)
        cur = x_ref[at, :].astype(f32)
        z, _ = _pre_activation(jnp.concatenate([before, cur], axis=0), w, bias)
        y = z * jax.nn.sigmoid(z)
        _put(o_ref, at, y if head_scale is None else _head_norm(y, head_scale))
        return cur[rows - HALO:]

    carry_ref[...] = lax.fori_loop(0, x_ref.shape[0] // rows, turn, carry_ref[...])


def _bwd_kernel(*refs, rows: int, halo: int, has_bias: bool, t: int, head_scale):
    from jax.experimental import pallas as pl

    x_ref, before_ref, dy_ref, w_ref = refs[:4]
    b_ref = refs[4] if has_bias else None
    dx_ref, dwb_ref, carry_ref = refs[-3:]
    f32 = jnp.float32
    block_t, k = x_ref.shape[0], w_ref.shape[0]
    tile = pl.num_programs(2) - 1 - pl.program_id(2)          # the last tile first

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        dwb_ref[...] = jnp.zeros_like(dwb_ref)

    @pl.when(pl.program_id(2) == 0)
    def _():                                   # nothing stands after a row's last token
        carry_ref[...] = jnp.zeros_like(carry_ref)

    w = w_ref[...]
    bias = b_ref[...] if has_bias else None
    first = jnp.where(tile == 0, 0.0, before_ref[...].astype(f32))     # the rows before the tile

    def read(ref, start, n):
        """Rows [start, start + n) in float32; 0 past T, where a ragged
        tile holds whatever the block was padded with."""
        a = ref[pl.ds(pl.multiple_of(start, n), n), :].astype(f32)
        if t % block_t == 0:
            return a
        row = tile * block_t + start + lax.broadcasted_iota(jnp.int32, (n, 1), 0)
        return jnp.where(row < t, a, 0.0)

    def turn(j, after):
        j = block_t // rows - 1 - j
        start = j * rows
        at = pl.ds(pl.multiple_of(start, rows), rows)
        cur, dy = read(x_ref, start, rows), read(dy_ref, start, rows)
        own = read(x_ref, jnp.maximum(start - halo, 0), halo)
        before = jnp.where(j == 0, first, own)[halo - HALO:]
        z, shifted = _pre_activation(jnp.concatenate([before, cur], axis=0), w, bias)
        sig = jax.nn.sigmoid(z)
        if head_scale is not None:
            dy = _head_norm_bwd(z * sig, dy, head_scale)
        dz = dy * (sig * (1.0 + z * (1.0 - sig)))
        ext = jnp.concatenate([dz, after], axis=0)
        dx = _later(ext, k - 1) * w[0:1]
        for i in range(1, k):
            dx = dx + _later(ext, k - 1 - i) * w[i:i + 1]
        _put(dx_ref, at, dx)
        # dw[i] and db as HALO partial sums a channel: registers added to
        # registers; the caller adds the HALO rows
        for i, term in enumerate([dz * s for s in shifted] + ([dz] if has_bias else [])):
            part = term[:HALO]
            for r in range(HALO, rows, HALO):
                part = part + term[r:r + HALO]
            dwb_ref[i * HALO:(i + 1) * HALO, :] += part
        return dz[:HALO]

    carry_ref[...] = lax.fori_loop(0, block_t // rows, turn, carry_ref[...])


def _call_fwd(x, w, bias, how: ConvHow):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, c = x.shape
    plan = plan_conv(t, c, x.dtype)
    bt, bc = plan.block_t, plan.block_c
    tile = pl.BlockSpec((None, bt, bc), lambda ci, bi, ti: (bi, ti, ci))
    a_channel = lambda rows: pl.BlockSpec((rows, bc), lambda ci, bi, ti: (0, ci))
    ins = [x, w] + ([bias.reshape(1, c)] if bias is not None else [])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, rows=plan.rows, has_bias=bias is not None,
                          head_scale=how.head_scale),
        name="ps_causal_conv_fwd",
        grid=(c // bc, b, pl.cdiv(t, bt)),
        in_specs=[tile, a_channel(w.shape[0])] + ([a_channel(1)] if bias is not None else []),
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, how.out_dtype),
        scratch_shapes=[pltpu.VMEM((HALO, bc), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        **how.mode,
    )(*ins)


def _call_bwd(x, w, bias, dy, how: ConvHow):
    """-> (dx in x's dtype, dw [K, C], db [C] or None; float32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, c = x.shape
    k = w.shape[0]
    plan = plan_conv(t, c, x.dtype)
    bt, bc, halo = plan.block_t, plan.block_c, plan.halo
    n_t = pl.cdiv(t, bt)
    sums = k + (bias is not None)
    tile = pl.BlockSpec((None, bt, bc), lambda ci, bi, ti: (bi, n_t - 1 - ti, ci))
    before = pl.BlockSpec(
        (None, halo, bc),
        lambda ci, bi, ti: (bi, jnp.maximum((n_t - 1 - ti) * (bt // halo) - 1, 0), ci))
    a_channel = lambda rows: pl.BlockSpec((rows, bc), lambda ci, bi, ti: (0, ci))
    ins = [x, x, dy, w] + ([bias.reshape(1, c)] if bias is not None else [])
    dx, dwb = pl.pallas_call(
        functools.partial(_bwd_kernel, rows=plan.rows, halo=halo,
                          has_bias=bias is not None, t=t, head_scale=how.head_scale),
        name="ps_causal_conv_bwd",
        grid=(c // bc, b, n_t),
        in_specs=[tile, before, tile, a_channel(k)] + ([a_channel(1)] if bias is not None else []),
        out_specs=[tile, a_channel(sums * HALO)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((sums * HALO, c), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((HALO, bc), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        **how.mode,
    )(*ins)
    dwb = jnp.sum(dwb.reshape(sums, HALO, c), axis=1)
    return dx, dwb[:k], (dwb[k] if bias is not None else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv_kernels(x, w, bias, how: ConvHow):
    return _call_fwd(x, w, bias, how)


def _conv_kernels_fwd(x, w, bias, how):
    return _conv_kernels(x, w, bias, how), (x, w, bias)


def _conv_kernels_bwd(how, saved, dy):
    return _call_bwd(*saved, dy, how)


_conv_kernels.defvjp(_conv_kernels_fwd, _conv_kernels_bwd)


def causal_conv_silu(x, w, bias, out_dtype, twin, heads: Optional[int] = None,
                     head_scale: float = 1.0):
    """silu(conv(x) + bias) in `out_dtype`: x [B, T, C] in any float dtype,
    w [K, C] (tap K - 1 meets the current token), bias [C] or None; float32
    inside. With `heads`, each of the C / heads channels of a head is then
    L2-normalised times `head_scale` (ops/kda.l2_normalize) in float32 before
    the one rounding: inside the kernels where a head is one 128-lane tile.
    `twin(x32, w32, bias32)` is the plain conv, taken where `conv_path`
    says "xla": the caller hands it in by its own module's name
    (models/ssm_hybrid._causal_conv), which is where the benchmark's tests
    reach in to break it."""
    f32 = jnp.float32
    c = x.shape[-1]
    w = w.astype(f32)
    bias = None if bias is None else bias.astype(f32)
    if conv_path(c, w.shape[0]) == "pallas":
        whole = heads is None or c // heads == LANES       # the kernels make the whole result
        y = _conv_kernels(x, w, bias, ConvHow(
            jnp.dtype(out_dtype if whole else f32), pallas_mode() is INTERPRET,
            None if heads is None or not whole else head_scale))
        if whole:
            return y
    else:
        with jax.named_scope("ps_causal_conv_jnp"):
            y = jax.nn.silu(twin(x.astype(f32), w, 0.0 if bias is None else bias))
    if heads is not None:
        y = l2_normalize(y.reshape(y.shape[:2] + (heads, -1)), head_scale, NORM_EPS).reshape(x.shape)
    return y.astype(out_dtype)
