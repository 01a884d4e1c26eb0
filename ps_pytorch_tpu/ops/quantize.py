"""int8 symmetric quantize/dequantize — the TPU-native replacement for the
reference's Blosc byte-compression of gradients (/root/reference/src/
compression.py:18-31, snappy codec at :20).

A lossless byte codec is pointless inside XLA programs; the *capability* being
matched is bandwidth reduction on the gradient path (4x for int8), wired into
the collective in parallel/collectives.py. Implementations:

- a pure-jnp reference (runs anywhere; used on the virtual CPU test mesh),
- Pallas TPU kernels (per-tensor and per-block) fusing scale-multiply +
  round + clip + int8 cast on the VPU (8x128 lanes), selected automatically
  on TPU backends and exercised on CPU via PS_TPU_PALLAS_INTERPRET=1
  (pallas interpret mode).

Rounding: "nearest" (default) or "stochastic" — stochastic rounding makes
the quantizer unbiased (E[deq(q(x))] = x), which matters for gradient
aggregation: nearest-rounding bias accumulates over steps, stochastic noise
averages out across workers and time. Stochastic mode needs a PRNG key and
runs on the jnp path (XLA fuses it; the Pallas kernel covers the nearest
hot path).

Scales are symmetric absmax/127, per-tensor (block_size=0) or per-block of
the flattened tensor (block_size>0, tighter error). When `axis_name` is
given the absmax is pmax'd across that mesh axis so every worker quantizes
with the SAME scale — which is what makes the int32 psum of quantized
values an exact sum of the per-worker quantizations (determinism the
reference's per-worker Blosc streams cannot offer).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .pallas_mode import COMPILED, pallas_mode

_LANE = 128
_SUBLANE = 8


def _pallas_mode(x: jax.Array) -> Optional[dict]:
    """ops/pallas_mode.pallas_mode(), except that a compiled launch is not
    worth it below one (8, 128) vreg tile of input: None = use jnp."""
    mode = pallas_mode()
    if mode == COMPILED and x.size < _LANE * _SUBLANE:
        return None
    return mode


# ------------------------------------------------------------ pallas kernels


def _quant_kernel(x_ref, inv_ref, out_ref):
    out_ref[:] = jnp.clip(
        jnp.round(x_ref[:] * inv_ref[0, 0]), -127.0, 127.0
    ).astype(jnp.int8)


def _quant_rows_kernel(x_ref, inv_ref, out_ref):
    # per-row (= per-quantization-block) scales: inv_ref is [block_rows, 1]
    out_ref[:] = jnp.clip(
        jnp.round(x_ref[:] * inv_ref[:]), -127.0, 127.0
    ).astype(jnp.int8)


def _pallas_quantize_2d(x2: jax.Array, inv_scale: jax.Array, mode: dict) -> jax.Array:
    """x2: f32 [M, 128], M % 8 == 0; inv_scale: f32 scalar -> int8 [M, 128]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m = x2.shape[0]
    block_m = min(m, 1024)
    return pl.pallas_call(
        _quant_kernel,
        name="ps_quantize_2d",
        out_shape=jax.ShapeDtypeStruct((m, _LANE), jnp.int8),
        grid=(pl.cdiv(m, block_m),),
        in_specs=[
            pl.BlockSpec((block_m, _LANE), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(
            (block_m, _LANE), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        **mode,
    )(x2, inv_scale.reshape(1, 1))


def _pallas_quantize_rows(xb: jax.Array, inv: jax.Array, mode: dict) -> jax.Array:
    """xb: f32 [NB, BS] (BS % 128 == 0), inv: f32 [NB, 1] -> int8 [NB, BS]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nb, bs = xb.shape
    block_nb = min(nb, max(_SUBLANE, 4096 // (bs // _LANE)))
    block_nb = -(-block_nb // _SUBLANE) * _SUBLANE  # sublane-align the tile
    return pl.pallas_call(
        _quant_rows_kernel,
        name="ps_quantize_rows",
        out_shape=jax.ShapeDtypeStruct((nb, bs), jnp.int8),
        grid=(pl.cdiv(nb, block_nb),),
        in_specs=[
            pl.BlockSpec((block_nb, bs), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block_nb, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (block_nb, bs), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        **mode,
    )(xb, inv)


# ---------------------------------------------------------------- public API


def _round(x: jax.Array, rounding: str, key: Optional[jax.Array]) -> jax.Array:
    if rounding == "nearest":
        return jnp.round(x)
    if rounding == "stochastic":
        if key is None:
            raise ValueError("stochastic rounding needs a PRNG key")
        # floor(x + U[0,1)): P(round up) == frac(x) -> unbiased
        return jnp.floor(x + jax.random.uniform(key, x.shape, jnp.float32))
    raise ValueError(f"unknown rounding {rounding!r}")


def quantize_int8(
    x: jax.Array,
    axis_name: Optional[str] = None,
    block_size: int = 0,
    rounding: str = "nearest",
    key: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization.

    Returns ``(q, scale)``. Per-tensor mode: q has x's shape, scale is scalar.
    Per-block mode: q is [n_blocks, block_size] over the zero-padded flattened
    tensor, scale is [n_blocks, 1]. Pass the original shape to
    ``dequantize_int8`` to undo.
    """
    x = x.astype(jnp.float32)
    mode = _pallas_mode(x) if rounding == "nearest" else None
    if block_size:
        flat = x.reshape(-1)
        n = flat.shape[0]
        nb = -(-n // block_size)
        flat = jnp.pad(flat, (0, nb * block_size - n))
        xb = flat.reshape(nb, block_size)
        absmax = jnp.max(jnp.abs(xb), axis=1, keepdims=True)
        if axis_name is not None:
            absmax = lax.pmax(absmax, axis_name)
        scale = absmax / 127.0
        inv = jnp.where(absmax > 0, 127.0 / jnp.maximum(absmax, 1e-30), 0.0)
        # VMEM budget: an 8-sublane f32 tile of a huge block_size would not
        # fit on chip (~16MB VMEM, double-buffered) — cap the tile at 2MB
        # and fall back to jnp beyond it
        fits_vmem = _SUBLANE * block_size * 4 <= 2 * 1024 * 1024
        if (
            mode is not None
            and block_size % _LANE == 0
            and nb % _SUBLANE == 0
            and fits_vmem
        ):
            q = _pallas_quantize_rows(xb, inv, mode)
        else:
            # the scope names the jnp path in the jaxpr and the compiled
            # program, where chip_smoke.py and a profile can see it
            with jax.named_scope("ps_quantize_rows_jnp"):
                q = jnp.clip(
                    _round(xb * inv, rounding, key), -127, 127
                ).astype(jnp.int8)
        return q, scale

    absmax = jnp.max(jnp.abs(x))
    if axis_name is not None:
        absmax = lax.pmax(absmax, axis_name)
    scale = absmax / 127.0
    inv = jnp.where(absmax > 0, 127.0 / jnp.maximum(absmax, 1e-30), 0.0)
    if mode is not None:
        n = x.size
        rows = -(-n // _LANE)
        rows_pad = -(-rows // _SUBLANE) * _SUBLANE
        flat = jnp.pad(x.reshape(-1), (0, rows_pad * _LANE - n))
        q2 = _pallas_quantize_2d(flat.reshape(rows_pad, _LANE), inv, mode)
        q = q2.reshape(-1)[:n].reshape(x.shape)
    else:
        with jax.named_scope("ps_quantize_2d_jnp"):
            q = jnp.clip(
                _round(x * inv, rounding, key), -127, 127
            ).astype(jnp.int8)
    return q, scale


def dequantize_int8(
    q: jax.Array,
    scale: jax.Array,
    block_size: int = 0,
    shape: Optional[Tuple[int, ...]] = None,
) -> jax.Array:
    """Invert `quantize_int8` (q may be an int32 psum of int8 payloads)."""
    out = q.astype(jnp.float32) * scale
    if block_size:
        if shape is None:
            raise ValueError("block mode dequantization needs the original shape")
        n = int(np.prod(shape))
        out = out.reshape(-1)[:n].reshape(shape)
    return out


# ------------------------------------------- homomorphic (compressed-domain)


_INT8_PEAK = 127  # symmetric int8 payloads live in [-127, 127]


def accum_capacity(dtype_name: str) -> int:
    """Largest number of full-scale (|q| = 127) int8 payloads whose sum
    provably fits ``dtype_name``: floor(dtype_max / 127). int16 holds
    258 workers (258 * 127 = 32766 <= 32767), int32 holds 16_909_320
    (16_909_320 * 127 = 2_147_483_640 <= 2^31 - 1)."""
    bits = {"int16": 15, "int32": 31}[dtype_name]
    return (2 ** bits - 1) // _INT8_PEAK


ACCUM_CAPACITY = {
    "int16": accum_capacity("int16"),
    "int32": accum_capacity("int32"),
}


def accum_dtype(num_summands: int):
    """Smallest integer dtype whose range provably holds a sum of
    ``num_summands`` full-scale int8 payloads — the wire dtype of a
    homomorphic psum (collectives.quantized_psum with
    wire_domain="homomorphic"). The sum of n values in [-127, 127] is
    bounded by n * 127, so the choice is a static function of the mesh
    size: int16 carries 258 workers (2 bytes/element on the wire vs 4
    for the dequant path's int32). Beyond int32's capacity no supported
    accumulator is exact — raise rather than wrap."""
    if num_summands < 1:
        raise ValueError(f"accum_dtype needs >= 1 summand, got {num_summands}")
    if num_summands <= ACCUM_CAPACITY["int16"]:
        return jnp.int16
    if num_summands <= ACCUM_CAPACITY["int32"]:
        return jnp.int32
    raise ValueError(
        f"homomorphic accumulation over {num_summands} full-scale "
        f"int8 payloads can overflow int32 (capacity "
        f"{ACCUM_CAPACITY['int32']}) — use wire_domain='dequant'"
    )


def homomorphic_rescale(acc: jax.Array, divisor) -> jax.Array:
    """Integer lattice rescale: ``round(acc / divisor)`` back to int8.

    ``acc`` is an exact integer accumulation of at most ``divisor``
    int8 payloads on a SHARED quantization lattice (|acc| <= divisor *
    127), so the rounded quotient provably fits [-127, 127] — the
    compressed-domain replacement for the dequant wire's round-2
    widen -> requantize: no f32 on the wire, no new scale rows, one
    deterministic rounding at the shared scale's granularity.
    ``divisor`` may be a traced scalar (the adaptive aggregation
    count). The divide runs in f32 COMPUTE (never on the wire), which
    represents the accumulator exactly through 2^24 — every mesh the
    int16/int32 capacity table admits below ~132k workers."""
    q = jnp.round(acc.astype(jnp.float32) / divisor)
    return jnp.clip(q, -_INT8_PEAK, _INT8_PEAK).astype(jnp.int8)


def _accum_rescale_kernel(recv_ref, div_ref, out_ref):
    acc = jnp.sum(recv_ref[:].astype(jnp.int32), axis=0, keepdims=True)
    q = jnp.round(acc.astype(jnp.float32) / div_ref[0, 0])
    out_ref[:] = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)


def _pallas_accum_rescale(recv: jax.Array, divisor, mode: dict) -> jax.Array:
    """recv: int8 [n, s] with s % 128 == 0 -> int8 [s]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, s = recv.shape
    # VMEM budget: the n x block_s int8 tile (plus int32 widening) must
    # fit on chip; 16Ki lanes x n<=~258 rows stays well under it
    block_s = min(s, 16384 // _LANE * _LANE)
    out = pl.pallas_call(
        _accum_rescale_kernel,
        name="ps_accum_rescale",
        out_shape=jax.ShapeDtypeStruct((1, s), jnp.int8),
        grid=(pl.cdiv(s, block_s),),
        in_specs=[
            pl.BlockSpec((n, block_s), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, block_s), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        **mode,
    )(recv, jnp.asarray(divisor, jnp.float32).reshape(1, 1))
    return out.reshape(-1)


def accumulate_rescale_int8(recv: jax.Array, divisor) -> jax.Array:
    """The homomorphic gather hop's fused hot path: exact integer
    accumulation over the worker rows of an all_to_all'd int8 payload
    ``[n, s]`` + lattice rescale back to int8 — the compressed-domain
    replacement for the dequant wire's widen -> requantize, fused into
    ONE Pallas VPU pass on TPU (int8 load, int32 accumulate, f32
    divide/round, int8 store: no widened intermediate ever reaches HBM).
    Exercised on CPU via PS_TPU_PALLAS_INTERPRET=1 like the flash
    kernels; the pure-jnp path is bit-identical (same sum, same f32
    divide, same round-half-even). ``divisor`` may be traced (the
    adaptive aggregation count rides the SMEM scalar operand)."""
    mode = _pallas_mode(recv)
    if mode is not None and recv.shape[1] % _LANE == 0:
        return _pallas_accum_rescale(recv, divisor, mode)
    with jax.named_scope("ps_accum_rescale_jnp"):
        return homomorphic_rescale(
            jnp.sum(recv.astype(jnp.int32), axis=0), divisor
        )


def quantization_error(x: jax.Array, block_size: int = 0) -> jax.Array:
    """Max abs round-trip error — used by tests and for Msg(MB)-style
    introspection (the reference logs compressed message sizes,
    tiny_tuning_parser.py:18; for int8 the 'compression ratio' is a constant
    4x plus scale overhead, and the interesting number is this error)."""
    q, s = quantize_int8(x, block_size=block_size)
    return jnp.max(jnp.abs(dequantize_int8(q, s, block_size, x.shape) - x))
