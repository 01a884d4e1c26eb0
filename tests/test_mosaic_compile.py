"""Mosaic compiles the main path's kernels at the published widths, for a
v5e that is described and not attached (no chip time; nothing runs, so
this says nothing of results or speed). The flash kernels at a 192-wide
query/key beside a 128-wide value at T 8192, and the dropless experts'
grouped products at [16, 2048, 768]: what interpret mode cannot refuse.

All such compiles live in THIS file: one process loads the TPU compiler,
inside a fixture, after collection (see the on-chip-measurement guide)."""

import math
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ps_pytorch_tpu.ops import flash_attention as fa
from ps_pytorch_tpu.ops import grouped_matmul as gm

from .test_flash_attention import CELL_SHAPES


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


def _mosaic_lines(compiled):
    return [line for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _mosaic_calls(compiled) -> int:
    return len(_mosaic_lines(compiled))


# an attention layer in the four LM cells, and the longest head the plan
# still fuses
FLASH_SHAPES = dict(CELL_SHAPES, t65536=(1, 65536, 192, 128))


@pytest.mark.parametrize("bwd", ["fused", "split"])
@pytest.mark.parametrize("cell", sorted(FLASH_SHAPES))
def test_flash_kernels_compile_at_the_cells_shapes(shape, monkeypatch, cell, bwd):
    """Forward and backward as a ring hop drives them (traced offsets in
    SMEM, float32 results) and the backward as the custom VJP does (results
    in the operands' dtype). The fused backward is ONE Mosaic call, given
    the VMEM limit the plan asks; past the cap (put at 0 here) the pair."""
    import re

    bh, t, d_qk, d_v = FLASH_SHAPES[cell]
    if bwd == "split":
        monkeypatch.setattr(fa, "FUSED_BWD_CAP", 0)
    plan = fa.plan_flash(t, t, d_qk, jnp.bfloat16, True, d_v=d_v)
    assert (plan.block_q, plan.block_k, plan.bwd) == (512, 512, bwd)
    q, k, v, do = shape((bh, t, d_qk)), shape((bh, t, d_qk)), shape((bh, t, d_v)), shape((bh, t, d_v))
    row, off = shape((bh, t), jnp.float32), shape((), jnp.int32)
    scale = d_qk ** -0.5

    def fwd(q, k, v, q_off, k_off):
        return fa.flash_partial(q, k, v, scale, True, q_off, k_off, mode={})

    def hop(q, k, v, do, lse, delta, q_off, k_off):
        return fa.flash_grads_partial(q, k, v, do, lse, delta, scale, True, q_off, k_off, mode={})

    def local(q, k, v, do, lse, delta):
        return fa._flash_bwd(q, k, v, lse, delta, do, scale, True, plan.block_q, plan.block_k, {})

    assert _mosaic_calls(jax.jit(fwd).lower(q, k, v, off, off).compile()) == 1
    names = {"fused": ["ps_flash_dqkv"], "split": ["ps_flash_dkv", "ps_flash_dq"]}[bwd]
    for compiled in (jax.jit(hop).lower(q, k, v, do, row, row, off, off).compile(),
                     jax.jit(local).lower(q, k, v, do, row, row).compile()):
        calls = _mosaic_lines(compiled)
        assert sorted(re.search(r"%(ps_flash_[a-z]+)", c).group(1) for c in calls) == names
        if bwd == "fused":
            (limit,) = re.findall(r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"0","size":"(\d+)"', calls[0])
            assert int(limit) == fa.vmem_limit(plan.vmem_bytes) > 16 * 2 ** 20


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


# ------------------------------------------------ the delta rule's kernels


@pytest.fixture()
def as_on_a_tpu(monkeypatch):
    """ops/pallas_mode.py picks from what the process observes: say TPU, so
    the public entries take their compiled kernels."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("PS_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("PS_TPU_DISABLE_PALLAS", raising=False)


def _kda_grads(*args):
    from ps_pytorch_tpu.ops import kda

    return jax.grad(lambda *a: jnp.sum(kda.kda_chunked(*a, 64)[0]), argnums=range(5))(*args)


def test_kda_kernels_compile_at_the_cells_shapes_with_their_time_inside_kda_ms(shape, as_on_a_tpu):
    """`kda_ms` goes by the shape at the end of an op's short name (the HLO
    name and its largest result): every `ps_kda_*` call of the op lowered at
    the kimi cell's shapes must match the pattern of
    benchmark/layer_metrics/kda_ms.json, or its time falls out of the metric
    and inflates `kda_roofline` with no work saved."""
    import json
    import os
    import re

    from benchmark.reducers.trace import short_name
    from ps_pytorch_tpu.ops import kda
    from ps_pytorch_tpu.ops.pallas_mode import kernel_census

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "layer_metrics", "kda_ms.json")) as f:
        pattern = re.compile(json.load(f)["args"]["pattern"])
    b, t, h, d = 2, 8192, 32, 128
    q, g, beta = shape((b, t, h, d)), shape((b, t, h, d), jnp.float32), shape((b, t, h), jnp.float32)
    assert kda.scan_path(64, d, d) == "pallas_within+xla_scan"
    text = jax.jit(_kda_grads).lower(q, q, q, g, beta).compile().as_text()
    assert kernel_census(text) == {"jnp": {}, "mosaic": {
        "ps_kda_inverse": 1, "ps_kda_within_fwd": 1, "ps_kda_within_bwd": 1}}
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line and "ps_kda_" in line]
    assert len(calls) == 3
    for line in calls:
        assert pattern.search(short_name(line)), short_name(line)


def test_a_kda_step_under_remat_solves_the_system_once_a_layer(topo, as_on_a_tpu):
    """The small preset of chip_smoke.py's `lm_kda` leg (KDA heads of 128,
    chunks of 64; k k k a k) as `cli.train_lm` builds its step, `remat` on:
    four KDA layers hold `ps_kda_inverse` four times (the forward that
    `remat` runs again takes the kept inverse), the forward kernel eight
    times, the backward four; nothing of the XLA twin; and `kda_plan` says
    what ran."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import chip_smoke
    from ps_pytorch_tpu.models import kda_hybrid
    from ps_pytorch_tpu.models.lm import lm_family, load_lm_config
    from ps_pytorch_tpu.ops.pallas_mode import kernel_census
    from ps_pytorch_tpu.parallel.dp_sp import SEQ_AXIS, WORKER_AXIS, make_lm_train_step, make_mesh_2d

    cfg = load_lm_config(dict(chip_smoke.LM_KDA_CONFIG), attention_impl="flash", remat=True,
                         compute_dtype=jnp.bfloat16)
    assert kda_hybrid.kda_plan(cfg, 512)["scan_path"] == "pallas_within+xla_scan"
    tx = optax.adam(1e-3)
    mesh = make_mesh_2d(1, 1, devices=[topo.devices[0]])
    state = jax.eval_shape(lambda k: (lambda p: (p, tx.init(p)))(lm_family(cfg).init(cfg, k)),
                           jax.random.key(0))
    on = lambda spec: (lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                      sharding=NamedSharding(mesh, spec)))
    params, opt = jax.tree_util.tree_map(on(P()), state)
    tokens = on(P(WORKER_AXIS, SEQ_AXIS))(jax.ShapeDtypeStruct((2, 512), jnp.int32))
    text = make_lm_train_step(cfg, tx, mesh).lower(params, opt, tokens).compile().as_text()
    census = kernel_census(text)
    layers = len(cfg.kda_layers)
    assert layers == 4 and "ps_kda_within" not in census["jnp"]
    assert {k: v for k, v in census["mosaic"].items() if k.startswith("ps_kda_")} == {
        "ps_kda_inverse": layers, "ps_kda_within_fwd": 2 * layers, "ps_kda_within_bwd": layers}


def test_an_eva_step_at_the_cells_row_holds_no_score_array(topo, as_on_a_tpu):
    """benchmark/configs/evabyte_6b5_4layers.json at ONE layer (the compile's
    time, not its shapes), 1 x 16,384 bytes, bfloat16, `remat`: both passes
    of the flash kernels are Mosaic calls at [256, 2048, 128] and [32, 16384
    x 1024, 128], each once forward (not again under `remat`) and once
    backward; no array of the step is shaped like a window's or the
    summaries' scores; its largest is an 11008-wide MLP tensor."""
    import os
    import re

    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ps_pytorch_tpu.models.lm import lm_family, load_lm_config
    from ps_pytorch_tpu.ops.pallas_mode import kernel_census
    from ps_pytorch_tpu.parallel.dp_sp import SEQ_AXIS, WORKER_AXIS, make_lm_train_step, make_mesh_2d

    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "evabyte_6b5_4layers.json")) as f:
        published = {**json.load(f), "num_hidden_layers": 1}
    cfg = load_lm_config(published, attention_impl="flash", remat=True,
                         compute_dtype=jnp.bfloat16)
    tx = optax.adam(3e-4)
    mesh = make_mesh_2d(1, 1, devices=[topo.devices[0]])
    state = jax.eval_shape(lambda k: (lambda p: (p, tx.init(p)))(lm_family(cfg).init(cfg, k)),
                           jax.random.key(0))
    on = lambda spec: (lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                      sharding=NamedSharding(mesh, spec)))
    params, opt = jax.tree_util.tree_map(on(P()), state)
    tokens = on(P(WORKER_AXIS, SEQ_AXIS))(jax.ShapeDtypeStruct((1, 16384), jnp.int32))
    text = make_lm_train_step(cfg, tx, mesh).lower(params, opt, tokens).compile().as_text()
    assert kernel_census(text) == {"jnp": {}, "mosaic": {"ps_flash_fwd": 2, "ps_flash_dqkv": 2}}
    sizes = {}
    for dtype, dims in re.findall(r"= \(?(f32|bf16|s32|pred)\[([0-9,]+)\]", text):
        shape = tuple(int(d) for d in dims.split(","))
        sizes[shape] = max(sizes.get(shape, 0),
                           math.prod(shape) * {"f32": 4, "bf16": 2, "s32": 4, "pred": 1}[dtype])
    # a window's [2048, 2048], the summaries' [16384, 1024] (or a tile's worth of rows of them)
    assert not [s for s in sizes if len(s) >= 2 and s[-2:] in (
        (2048, 2048), (16384, 1024), (2048, 1024), (16384, 16384), (2048, 3072))]
    assert max(sizes, key=sizes.get) == (16384, 11008)
