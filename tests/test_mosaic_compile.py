"""Mosaic compiles the main path's kernels at the published widths, for a
v5e that is described and not attached (no chip time; nothing runs, so
this says nothing of results or speed). The flash kernels at a 192-wide
query/key beside a 128-wide value at T 8192, and the dropless experts'
grouped products at [16, 2048, 768]: what interpret mode cannot refuse.

All such compiles live in THIS file: one process loads the TPU compiler,
inside a fixture, after collection (see the on-chip-measurement guide)."""

from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ps_pytorch_tpu.ops import flash_attention as fa
from ps_pytorch_tpu.ops import grouped_matmul as gm


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this machine
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # and can never be read back without one
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("d_qk, d_v", [(192, 128), (64, 64)], ids=["mla", "gpt2"])
def test_flash_kernels_compile_at_the_published_widths(shape, d_qk, d_v):
    bh, t = 64, 8192
    q, k, v, do = shape((bh, t, d_qk)), shape((bh, t, d_qk)), shape((bh, t, d_v)), shape((bh, t, d_v))
    row, off = shape((bh, t), jnp.float32), shape((), jnp.int32)
    scale = d_qk ** -0.5

    def fwd(q, k, v, q_off, k_off):
        return fa.flash_partial(q, k, v, scale, True, q_off, k_off, mode={})

    def bwd(q, k, v, do, lse, delta, q_off, k_off):
        return fa.flash_grads_partial(q, k, v, do, lse, delta, scale, True, q_off, k_off, mode={})

    assert _mosaic_calls(jax.jit(fwd).lower(q, k, v, off, off).compile()) == 1
    assert _mosaic_calls(jax.jit(bwd).lower(q, k, v, do, row, row, off, off).compile()) == 2


@pytest.mark.parametrize("k, n", [(2048, 768), (768, 2048)], ids=["gate_up", "down"])
def test_grouped_products_compile_at_the_published_widths(shape, k, n):
    experts, tm = 16, gm.TILE_M
    m = gm.buffer_rows(6 * 16384, experts, tm)
    layout = gm.GroupLayout(shape((m // tm,), jnp.int32), shape((1,), jnp.int32),
                            shape((experts,), jnp.int32), shape((experts,), jnp.int32))
    x, w, dy = shape((m, k)), shape((experts, k, n), jnp.float32), shape((m, n))

    def fwd(x, w, lay):
        return gm._grouped(x, w, lay, tm, False)

    def bwd(x, w, lay, dy):
        return jax.vjp(partial(fwd, lay=lay), x, w)[1](dy)

    assert _mosaic_calls(jax.jit(fwd).lower(x, w, layout).compile()) == 1
    assert _mosaic_calls(jax.jit(bwd).lower(x, w, layout, dy).compile()) == 2
