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
    """Forward and backward as a ring hop drives them (traced offsets and a
    walk built from them in SMEM, float32 results) and the backward as the
    custom VJP does (a constant walk, results in the operands' dtype). The
    fused backward is ONE Mosaic call, given the VMEM limit the plan asks;
    past the cap (put at 0 here) the pair."""
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
    # the walk rides ahead of the operands as four int32 tables (scalar
    # prefetch): the rectangle's length where the hop's offsets are traced,
    # the live tiles' alone where they are known
    walks = [(jax.jit(hop).lower(q, k, v, do, row, row, off, off).compile(), plan.tiles_total),
             (jax.jit(local).lower(q, k, v, do, row, row).compile(), plan.grid_steps)]
    assert plan.grid_steps == plan.tiles_run < plan.tiles_total
    for compiled, steps in walks:
        calls = _mosaic_lines(compiled)
        assert sorted(re.search(r"%(ps_flash_[a-z]+)", c).group(1) for c in calls) == names
        assert all(c.count(f"s32[{steps}]{{0}}") == 4 for c in calls)
        if bwd == "fused":
            (limit,) = re.findall(r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"\d+","size":"(\d+)"', calls[0])
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


@pytest.mark.parametrize("bh, mask, steps, read", [
    (72, fa.SlidingWindow(512), 31, True), (48, True, 136, False)], ids=["sliding", "global"])
def test_the_band_kernels_compile_at_the_cells_shapes_and_the_metric_tells_them_apart(
        shape, bh, mask, steps, read):
    """ps_flash_fwd and the fused ps_flash_dqkv under SlidingWindow(512) at
    [72, 8192, 128], the sliding layers of the laguna cell: the walk rides as
    four tables of 31 entries (the band's tiles of the rectangle's 256), and
    `swa_flash_ms` / `swa_flash_roofline` go by the 72 that leads the largest
    result in an op's short name: they take these kernels and leave the
    global layers' (48 heads, causal) to `flash_ms`."""
    import json
    import os
    import re

    from benchmark.reducers.trace import short_name

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    patterns = set()
    for metric in ("swa_flash_ms", "swa_flash_roofline"):
        with open(os.path.join(root, "benchmark", "layer_metrics", f"{metric}.json")) as f:
            patterns.add(json.load(f)["args"]["pattern"])
    (pattern,) = patterns
    t, d = 8192, 128
    plan = fa.plan_flash(t, t, d, jnp.bfloat16, mask)
    assert (plan.block_q, plan.block_k, plan.grid_steps, plan.bwd) == (512, 512, steps, "fused")
    q, row = shape((bh, t, d)), shape((bh, t), jnp.float32)

    def fwd(q, k, v):
        return fa._flash_fwd(q, k, v, d ** -0.5, mask, plan.block_q, plan.block_k, {})

    def bwd(q, k, v, do, lse, delta):
        return fa._flash_bwd(q, k, v, lse, delta, do, d ** -0.5, mask, plan.block_q,
                             plan.block_k, {})

    compiled = {"ps_flash_fwd": jax.jit(fwd).lower(q, q, q).compile(),
                "ps_flash_dqkv": jax.jit(bwd).lower(q, q, q, q, row, row).compile()}
    for name, program in compiled.items():
        (call,) = _mosaic_lines(program)
        assert call.count(f"s32[{steps}]{{0}}") == 4
        short = short_name(call.strip().removeprefix("ROOT "))
        assert short.startswith(name) and (re.search(pattern, short) is not None) == read, short


@pytest.mark.parametrize("k, n, own_limit", [(3072, 1024, True), (1024, 3072, True),
                                             (2304, 1024, False)],
                         ids=["laguna_gate_up", "laguna_down", "kimi_gate_up"])
def test_the_grouped_products_take_their_own_vmem_limit_past_the_default(shape, k, n, own_limit):
    """ps_moe_gmm keeps one expert's matrix resident: at 3072 x 1024 (the
    laguna cell's experts, 8 held, a worst-case buffer of 10 x 8,192 rows)
    that passes the 16 MiB a kernel gets by default, so the call states its
    own limit; at the accepted cells' widths it states none, as before."""
    experts, tm = 8, gm.TILE_M
    m = gm.buffer_rows(10 * 8192, experts, tm)
    layout = gm.GroupLayout(shape((m // tm,), jnp.int32), shape((1,), jnp.int32),
                            shape((experts,), jnp.int32), shape((experts,), jnp.int32))
    assert (gm.gmm_vmem_bytes(tm, k, n, 2) > gm.GMM_VMEM_DEFAULT) == own_limit
    assert gm.gmm_vmem_bytes(tm, 2048, 768, 2) < gm.GMM_VMEM_DEFAULT     # the kanana cell's
    x, w, dy = shape((m, k)), shape((experts, k, n), jnp.float32), shape((m, n))

    def fwd(x, w, lay):
        return gm._grouped(x, w, lay, tm, False)

    def bwd(x, w, lay, dy):
        return jax.vjp(partial(fwd, lay=lay), x, w)[1](dy)

    (call,) = _mosaic_lines(jax.jit(fwd).lower(x, w, layout).compile())
    assert ('"scoped_memory_configs":[{' in call) == own_limit
    assert ('"scoped_memory_configs":[]' in call) != own_limit
    assert _mosaic_calls(jax.jit(bwd).lower(x, w, layout, dy).compile()) == 2


# ------------------------------------------------ the delta rule's kernels


def _say_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("PS_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("PS_TPU_DISABLE_PALLAS", raising=False)


@pytest.fixture()
def as_on_a_tpu(monkeypatch):
    """ops/pallas_mode.py picks from what the process observes: say TPU, so
    the public entries take their compiled kernels."""
    _say_tpu(monkeypatch)


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


def _lm_step_compiled(topo, cfg, batch, seq):
    """make_lm_train_step for `cfg` on the described chip, compiled from
    shapes alone, as `cli.train_lm` and the benchmark's drivers build it."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ps_pytorch_tpu.models.lm import lm_family
    from ps_pytorch_tpu.optim import build_optimizer
    from ps_pytorch_tpu.parallel.dp_sp import SEQ_AXIS, WORKER_AXIS, make_lm_train_step, make_mesh_2d

    tx = build_optimizer("adam", 3e-4, b1=0.9, b2=0.999, eps=1e-8)
    mesh = make_mesh_2d(1, 1, devices=[topo.devices[0]])
    state = jax.eval_shape(lambda k: (lambda p: (p, tx.init(p)))(lm_family(cfg).init(cfg, k)),
                           jax.random.key(0))
    on = lambda spec: (lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                      sharding=NamedSharding(mesh, spec)))
    params, opt = jax.tree_util.tree_map(on(P()), state)
    tokens = on(P(WORKER_AXIS, SEQ_AXIS))(jax.ShapeDtypeStruct((batch, seq), jnp.int32))
    return make_lm_train_step(cfg, tx, mesh).lower(params, opt, tokens).compile()


def test_a_kda_step_under_remat_solves_the_system_once_a_layer(topo, as_on_a_tpu):
    """The small preset of chip_smoke.py's `lm_kda` leg (KDA heads of 128,
    chunks of 64; k k k a k) as `cli.train_lm` builds its step, `remat` on:
    four KDA layers hold `ps_kda_inverse` four times (the forward that
    `remat` runs again takes the kept inverse), the forward kernel eight
    times, the backward four; nothing of the XLA twin; and `kda_plan` says
    what ran."""
    import chip_smoke
    from ps_pytorch_tpu.models import kda_hybrid
    from ps_pytorch_tpu.models.lm import load_lm_config
    from ps_pytorch_tpu.ops.pallas_mode import kernel_census

    cfg = load_lm_config(dict(chip_smoke.LM_KDA_CONFIG), attention_impl="flash", remat=True,
                         compute_dtype=jnp.bfloat16)
    # two chunks a row: one that starts from nothing, one from a carried
    # state; the census does not depend on how many follow (the compile's
    # time does)
    assert kda_hybrid.kda_plan(cfg, 128)["scan_path"] == "pallas_within+xla_scan"
    text = _lm_step_compiled(topo, cfg, 2, 128).as_text()
    census = kernel_census(text)
    layers = len(cfg.kda_layers)
    assert layers == 4 and "ps_kda_within" not in census["jnp"]
    assert {k: v for k, v in census["mosaic"].items() if k.startswith("ps_kda_")} == {
        "ps_kda_inverse": layers, "ps_kda_within_fwd": 2 * layers, "ps_kda_within_bwd": layers}


# ------------------------------------------------ the short conv's kernels


def _pattern(metric: str):
    import json
    import os
    import re

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "layer_metrics", metric + ".json")) as f:
        return re.compile(json.load(f)["args"]["pattern"])


@pytest.mark.parametrize("dims, dtype, out_dtype, bias, heads", [
    ((1, 8192, 4352), jnp.bfloat16, jnp.bfloat16, True, None),     # granite: x, B and C of a Mamba-2 layer
    ((2, 8192, 4096), jnp.float32, jnp.bfloat16, False, 32),       # kimi: q and k, L2-normalised a head
    ((2, 8192, 4096), jnp.float32, jnp.bfloat16, False, None),     # kimi: v
], ids=["granite", "kimi_qk", "kimi_v"])
def test_conv_kernels_compile_at_the_cells_shapes_with_their_time_in_short_conv_ms_alone(
        shape, as_on_a_tpu, dims, dtype, out_dtype, bias, heads):
    """`ps_causal_conv_fwd` / `_bwd` at the two cells' shapes, each ONE
    Mosaic call whose tiles twice over stay inside the VMEM it asks for.
    `short_conv_ms` reads them by name; `kda_ms` and `ssd_ms` go by the shape
    at the end of an op's short name and must NOT take them in, or
    `kda_roofline` and `ssd_roofline` would divide the work they count by
    more time than before."""
    from benchmark.reducers.trace import short_name
    from ps_pytorch_tpu.models.ssm_hybrid import _causal_conv
    from ps_pytorch_tpu.ops import causal_conv as cc
    from ps_pytorch_tpu.ops.pallas_mode import kernel_census

    c = dims[-1]
    assert cc.conv_path(c) == "pallas"
    plan = cc.plan_conv(dims[1], c, dtype)
    tile = plan.block_t * plan.block_c
    assert 2 * tile * (2 * jnp.dtype(dtype).itemsize + jnp.dtype(out_dtype).itemsize) <= cc.VMEM_LIMIT // 2
    assert cc.VMEM_LIMIT <= 64 << 20                 # half of a v5e core's 128 MiB

    def both(x, w, b, dy):
        y, vjp = jax.vjp(lambda x, w, b: cc.causal_conv_silu(
            x, w, b, out_dtype, _causal_conv, heads=heads, head_scale=128 ** -0.5), x, w, b)
        return y, vjp(dy)

    text = jax.jit(both).lower(
        shape(dims, dtype), shape((4, c), jnp.float32), shape((c,), jnp.float32) if bias else None,
        shape(dims, out_dtype)).compile().as_text()
    assert kernel_census(text) == {"jnp": {}, "mosaic": {
        "ps_causal_conv_fwd": 1, "ps_causal_conv_bwd": 1}}
    calls = [short_name(line.strip()) for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    for name in calls:
        assert _pattern("short_conv_ms").search(name), name
        assert not _pattern("kda_ms").search(name) and not _pattern("ssd_ms").search(name), name


@pytest.mark.parametrize("leg, fwd_a_layer, bwd_a_layer", [("lm_ssm", 2, 1), ("lm_kda", 3, 3)])
def test_a_step_under_remat_holds_the_conv_kernels_its_plan_says(
        topo, as_on_a_tpu, leg, fwd_a_layer, bwd_a_layer):
    """The small presets of chip_smoke.py's `lm_ssm` and `lm_kda` legs as
    `cli.train_lm` builds their steps, `remat` on. A state-space layer runs
    its conv forward twice (the forward, and `remat`'s) and backward once. A
    delta-rule layer's three branches run it forward once each and backward
    once each: the backward kernel makes all it needs from x, the L2 norm's
    gradient included, so what the branch's own `jax.checkpoint` runs again
    is the product alone (and the blocks' policy keeps q, k, v, so `remat`
    runs no branch again). Nothing of the plain conv, and the plan's
    `conv_path` says so."""
    import chip_smoke
    from ps_pytorch_tpu.models.lm import lm_family, load_lm_config
    from ps_pytorch_tpu.ops.pallas_mode import kernel_census

    published = {"lm_ssm": chip_smoke.LM_SSM_CONFIG, "lm_kda": chip_smoke.LM_KDA_CONFIG}[leg]
    cfg = load_lm_config(dict(published), attention_impl="flash", remat=True,
                         compute_dtype=jnp.bfloat16)
    (name, _, plan), = [p for p in lm_family(cfg).plans(cfg, 128, 1) if p[0] in ("ssd_plan", "kda_plan")]
    layers = plan["mamba_layers"] if name == "ssd_plan" else plan["kda_layers"]
    assert plan["conv_path"] == "pallas" and layers >= 3
    census = kernel_census(_lm_step_compiled(topo, cfg, 2, 128).as_text())
    assert "ps_causal_conv" not in census["jnp"]
    assert {k: v for k, v in census["mosaic"].items() if k.startswith("ps_causal_conv")} == {
        "ps_causal_conv_fwd": fwd_a_layer * layers,
        "ps_causal_conv_bwd": bwd_a_layer * layers}


# ------------------------------------------------ the rotation's kernel


# the metrics that go by an op's name or by the shape at its end, and must
# not take `ps_rope` in
OTHER_KERNEL_METRICS = ("flash_ms", "swa_flash_ms", "moe_buffer_ms", "moe_routed_ms", "kda_ms",
                        "ssd_ms", "short_conv_ms")


@pytest.mark.parametrize("dims, rope", [
    ((1, 8192, 72, 128), dict(rope_theta=10000.0)),                # laguna: a sliding layer's q
    ((1, 8192, 48, 128), dict(                                     # its global layers': YaRN, half a head
        rope_type="yarn", rope_theta=500000.0, factor=32.0, original_max_position_embeddings=4096,
        attention_factor=1.35, partial_rotary_factor=0.5)),
    ((1, 8192, 8, 128), dict(rope_theta=10000.0)),                 # laguna: k
    ((1, 16384, 28, 128), dict(rope_theta=1500000.0)),             # smallthinker: q
], ids=["sliding_q", "global_q_yarn_half", "k", "smallthinker_q"])
def test_the_rotation_compiles_at_the_cells_shapes_with_its_time_in_rope_ms_alone(
        shape, as_on_a_tpu, dims, rope):
    """`ps_rope` at the two cells' shapes, bfloat16, forward and backward
    each ONE Mosaic call on `[B, T, H * 128]`, tiles inside the VMEM it asks
    for. `rope_ms` reads them by name; no other kernel's metric takes them
    in, by name or by the shape at the end of an op's short name."""
    from benchmark.reducers.trace import short_name
    from ps_pytorch_tpu.models.swa_moe import Rope, _rope_leading
    from ps_pytorch_tpu.ops import rope as rp
    from ps_pytorch_tpu.ops.pallas_mode import kernel_census

    rope = Rope(**rope)
    b, t, heads, d = dims
    freqs, scale = rope.frequencies(d)
    assert rp.rope_path(d, 2 * len(freqs)) == "pallas"
    plan = rp.plan_rope(t, heads * d, jnp.bfloat16)
    assert plan.vmem_bytes(jnp.bfloat16) <= rp.VMEM_LIMIT // 2 and rp.VMEM_LIMIT <= 64 << 20

    def both(x, dy):
        pos = jnp.arange(t)
        y, vjp = jax.vjp(lambda a: rp.rotate_leading(
            (a,), pos, freqs, scale, twin=lambda a: _rope_leading(a, pos, rope))[0], x)
        return y, vjp(dy)

    text = jax.jit(both).lower(shape(dims), shape(dims)).compile().as_text()
    assert kernel_census(text) == {"jnp": {}, "mosaic": {"ps_rope": 2}}
    lines = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(lines) == 2
    for line in lines:
        assert f"bf16[{b},{t},{heads * d}]" in line
        name = short_name(line.strip().removeprefix("ROOT "))
        assert _pattern("rope_ms").search(name), name
        for other in OTHER_KERNEL_METRICS:
            assert not _pattern(other).search(name), (other, name)


# the dropless layer's way back to the tokens at the four expert cells'
# [N, k] over a pass's [M, D] (M = parallel/moe.pass_rows)
ROWS_SUM_SHAPES = {"kanana": (16384, 6, 2048, 28672), "smallthinker": (16384, 6, 2560, 53248),
                   "kimi": (16384, 8, 2304, 10240), "laguna": (8192, 10, 3072, 7168)}


def _weighted_rows_sum(ys, w, pos, held):
    from ps_pytorch_tpu.ops import moe_rows_sum as mr

    def refuse(v):
        pytest.fail("the kernel's path took the twin")
    return mr.rows_sum(ys * w[:, None], pos, held, refuse)


@pytest.mark.parametrize("cell", sorted(ROWS_SUM_SHAPES))
def test_the_rows_sum_compiles_at_the_cells_shapes_with_its_time_in_moe_rows_sum_ms_alone(
        shape, as_on_a_tpu, cell):
    """`ps_moe_rows_sum` (ops/moe_rows_sum.py) behind the product that makes
    its operand, bfloat16: ONE Mosaic call whose ys is handed over as a
    BITCAST of what that product wrote (the tile order, pairs of rows a word,
    costs no pass: a relayout there would cost more than the gather the
    kernel replaces, and so would a pack), the landing buffers inside the
    VMEM the call asks for. `moe_rows_sum_ms` reads it by name; no other
    kernel's metric takes it in, `moe_routed_ms` (`ps_moe_t?gmm`) and
    `moe_buffer_ms` (the N x k in an op's name) among them."""
    import re

    from benchmark.reducers.trace import short_name
    from ps_pytorch_tpu.ops import moe_rows_sum as mr
    from ps_pytorch_tpu.ops.pallas_mode import kernel_census

    n, k, d, m = ROWS_SUM_SHAPES[cell]
    assert mr.rows_sum_path(d, jnp.bfloat16) == "pallas"
    plan = mr.plan_rows(n, k, d, jnp.bfloat16)
    assert n % plan.tile == 0 and plan.vmem_bytes(k) <= mr.BUFFER_BYTES
    text = jax.jit(_weighted_rows_sum).lower(
        shape((m, d)), shape((m,)), shape((n, k), jnp.int32), shape((n, k), jnp.bool_)
    ).compile().as_text()
    assert kernel_census(text) == {"jnp": {}, "mosaic": {"ps_moe_rows_sum": 1}}
    (line,) = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    pairs = f"bf16[{m // 8},{d // 128},4,2,128]"
    words = n // plan.tile * plan.entries_block(k)
    assert pairs in line and f"bf16[{n},{d}]" in line and f"s32[{words}]" in line
    assert re.search(re.escape(pairs) + r"\{4,3,2,1,0:T\(2,128\)\(2,1\)(S\(\d\))?\} bitcast\(", text)
    assert not re.search(r"(u32|bf16)\[[0-9,]+\]\S* (copy|transpose|reshape)\(", text)
    assert not re.search(r"u32\[[0-9]", text)
    (limit,) = re.findall(r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"\d+","size":"(\d+)"', line)
    assert int(limit) == plan.vmem_bytes(k) + mr.VMEM_ROOM
    name = short_name(line.strip().removeprefix("ROOT "))
    assert _pattern("moe_rows_sum_ms").search(name), name
    for other in OTHER_KERNEL_METRICS + ("rope_ms",):
        assert not _pattern(other).search(name), (other, name)


@pytest.mark.parametrize("leg", ["lm_swa", "lm_pre"])
def test_a_step_under_remat_holds_the_rotations_its_plans_say(topo, as_on_a_tpu, leg):
    """The small presets of chip_smoke.py's `lm_swa` and `lm_pre` legs as
    `cli.train_lm` builds their steps, `remat` on, heads of 128: every layer
    kind whose plan says `rope_path: pallas` runs `ps_rope` on q and on k,
    forward and backward (the blocks' policy keeps the rotated q and k, so
    `remat` runs no rotation again); a layer without positions runs none;
    nothing of the plain rotation is left."""
    import chip_smoke
    from ps_pytorch_tpu.models.lm import lm_family, load_lm_config
    from ps_pytorch_tpu.ops.pallas_mode import kernel_census

    published = {"lm_swa": chip_smoke.LM_SWA_CONFIG, "lm_pre": chip_smoke.LM_PRE_CONFIG}[leg]
    cfg = load_lm_config(dict(published), attention_impl="flash", remat=True,
                         compute_dtype=jnp.bfloat16)
    plans = [f for name, _, f in lm_family(cfg).plans(cfg, 256, 1) if name == "flash_plan"]
    assert [p["rope_path"] for p in plans] == {"lm_swa": ["pallas", "pallas"],
                                               "lm_pre": ["pallas", "none"]}[leg]
    for p in plans:
        if p["rope_path"] == "pallas":
            assert (p["rope_block_t"], p["rope_block_c_kv"]) == (256, 256)
            assert p["heads"] * 128 % p["rope_block_c"] == 0
    rotary = sum(p["layers"] for p in plans if p["rope_path"] == "pallas")
    census = kernel_census(_lm_step_compiled(topo, cfg, 1, 256).as_text())
    assert "ps_rope" not in census["jnp"] and census["mosaic"]["ps_rope"] == 2 * 2 * rotary


def _eva_one_layer():
    """benchmark/configs/evabyte_6b5_4layers.json at ONE layer (the compile's
    time, not its shapes), bfloat16, `remat`."""
    import json
    import os

    from ps_pytorch_tpu.models.lm import load_lm_config

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "evabyte_6b5_4layers.json")) as f:
        published = {**json.load(f), "num_hidden_layers": 1}
    return load_lm_config(published, attention_impl="flash", remat=True,
                          compute_dtype=jnp.bfloat16)


@pytest.fixture(scope="module")
def eva_step(topo):
    """The one-layer evabyte step at the cell's row, 1 x 16,384 bytes,
    compiled once for the tests that read it."""
    with pytest.MonkeyPatch.context() as monkeypatch:
        _say_tpu(monkeypatch)
        return _lm_step_compiled(topo, _eva_one_layer(), 1, 16384)


def test_an_eva_step_at_the_cells_row_holds_no_score_array(eva_step):
    """The one-layer evabyte step at 1 x 16,384 bytes: both passes
    of the flash kernels are Mosaic calls at [256, 2048, 128] and [32, 16384
    x 1024, 128], each once forward (not again under `remat`) and once
    backward; no array of the step is shaped like a window's or the
    summaries' scores; its largest is an 11008-wide MLP tensor."""
    import re

    from ps_pytorch_tpu.ops.pallas_mode import kernel_census

    text = eva_step.as_text()
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


# ------------------------------------- where the update stands (PR 42)


def _folded_updates(compiled):
    """The leaves whose Adam rides their gradient's product: the shape of
    every fused computation's result that holds both a `convolution` and
    the update's `sqrt` (jax names it `.../update/sqrt`)."""
    import re

    folded, head, product, update = [], None, False, False
    for line in compiled.as_text().splitlines():
        if line.startswith("%") and line.rstrip().endswith("{"):
            head, product, update = line, False, False
        elif head is not None and line.startswith("}"):
            if product and update:
                dims = re.search(r"-> \(?f32\[([0-9,]+)\]", head).group(1)
                folded.append(tuple(int(d) for d in dims.split(",")))
            head = None
        elif head is not None:
            product |= " convolution(" in line
            update |= " sqrt(" in line and "/update/sqrt" in line
    return sorted(folded)


def test_the_update_stands_apart_of_the_wide_products_and_rides_the_narrow_ones(
        topo, as_on_a_tpu, eva_step, monkeypatch):
    """parallel/dp_sp.plan_update against the compiler that would fold
    through it, and the memory bound against a barrier over the tree. The
    one-layer evabyte step at the cell's row (16,384): no fusion holds a
    product and the update's sqrt for a leaf the plan names; with the plan
    forced to none the same step folds all seven matrices of the layer
    (what the chip ran before PR 42), and the step that keeps them apart
    needs under two of the largest leaf's float32 bytes more in
    temporaries (a barrier a leaf lets the scheduler run each update right
    after its product; all gradients live at once would be seven leaves).
    A layer at the GPT-2 cell's widths and rows (8 x 1,024) keeps its
    folded fusions: there Adam hides under the MXUs as it should."""
    from ps_pytorch_tpu.models.transformer import TransformerConfig
    from ps_pytorch_tpu.parallel import dp_sp

    apart = eva_step
    layer = [(4096, 4096)] * 4 + [(4096, 11008)] * 2 + [(11008, 4096)]
    assert all(dp_sp.plan_update(shape, 16384) for shape in layer)
    assert not [shape for shape in _folded_updates(apart) if dp_sp.plan_update(shape, 16384)]
    with monkeypatch.context() as forced:
        forced.setattr(dp_sp, "plan_update", lambda shape, rows: False)
        folded = _lm_step_compiled(topo, _eva_one_layer(), 1, 16384)
    assert [s for s in _folded_updates(folded) if s in layer] == sorted(layer)
    grown = (apart.memory_analysis().temp_size_in_bytes
             - folded.memory_analysis().temp_size_in_bytes)
    assert grown < 2 * 4 * 4096 * 11008, grown

    gpt2 = TransformerConfig(vocab_size=50257, dim=1024, depth=1, heads=16, mlp_ratio=4,
                             max_seq_len=1024, attention_impl="flash",
                             compute_dtype=jnp.bfloat16)
    block = [(1024, 1024), (1024, 3072), (1024, 4096), (4096, 1024)]
    assert not any(dp_sp.plan_update(shape, 8 * 1024) for shape in block)
    assert [s for s in _folded_updates(_lm_step_compiled(topo, gpt2, 8, 1024))
            if s in block] == block


# ------------------------------------------------ the dropless layer's passes


def _moe_calls(text):
    """{kernel: [(the call's line, the computation that holds it)]} for the
    grouped products' Mosaic calls and the way back's (`ps_moe_rows_sum`) in
    a compiled program's text, and the name of every `while`'s body."""
    import io
    import re

    from ps_pytorch_tpu.obs import hlo

    comps, _ = hlo._computations(io.StringIO(text))
    line_of = {m.group(1): line for line in text.splitlines()
               if hlo.MOSAIC_TARGET in line and (m := hlo._INSTR.match(line))}
    calls = {"ps_moe_gmm": [], "ps_moe_tgmm": [], "ps_moe_rows_sum": []}
    for comp, body in comps.items():
        for ins in body:
            kernel = (hlo._KERNEL.findall(ins.op_name) or [""])[-1]
            if ins.mosaic and kernel in calls:
                calls[kernel].append((line_of[ins.name], comp))
    return calls, set(re.findall(r"\bbody=%?([\w.\-]+)", text))


def _assignment_gathers(text, n, k, d):
    """The arrays of a compiled program shaped like the plain way back's
    gather, a row of d for each of the N x k assignments ([k, N, D])."""
    import re

    return re.findall(rf"(?:bf16|f32)\[{k},{n},{d}\]", text)


def test_an_expert_step_under_remat_holds_the_layer_once_inside_its_loops(topo, as_on_a_tpu):
    """The small preset of chip_smoke.py's `lm_config` leg (one dense and one
    expert layer) as `cli.train_lm` builds its step, `remat` on. The step of
    the tree before the passes held `ps_moe_gmm` 9 times and `ps_moe_tgmm` 3
    (an expert layer's three products forward, again under `remat`, their
    three transposes and three weight gradients; counted at that tree with
    this function). The layer in passes holds no more: ONE body a loop, the
    forward's three products in the forward's `while`, the re-run, the
    transposes and the weight gradients in the backward's, and the
    recomputed half-block runs no loop of its own. A second size of the
    layer behind a `cond` would hold every one of them twice. Beside them
    the way back to the tokens, `ps_moe_rows_sum`: the forward's combine in
    the forward's loop, the tokens' gradient in the backward's (the step's
    jaxpr holds a third site, the re-run's combine, whose result nothing
    reads: XLA drops it; tests/test_moe_rows_sum.py counts the jaxpr's), and
    no gather over the N x k assignments is left in the step."""
    import chip_smoke
    from ps_pytorch_tpu.models.lm import load_lm_config

    cfg = load_lm_config(dict(chip_smoke.LM_CONFIG), attention_impl="flash", remat=True,
                         compute_dtype=jnp.bfloat16)
    text = _lm_step_compiled(topo, cfg, 2, 256).as_text()
    calls, bodies = _moe_calls(text)
    assert {kernel: len(found) for kernel, found in calls.items()} == {
        "ps_moe_gmm": 9, "ps_moe_tgmm": 3, "ps_moe_rows_sum": 2}
    held_in = {comp for found in calls.values() for _, comp in found}
    assert held_in <= bodies and len(held_in) == 2, held_in      # forward's loop, backward's
    per_loop = sorted(sum(comp == b for found in calls.values() for _, comp in found)
                      for b in held_in)
    assert per_loop == [3 + 1, 9 + 1]
    assert sorted(comp for _, comp in calls["ps_moe_rows_sum"]) == sorted(held_in)
    assert not _assignment_gathers(text, 2 * 256, cfg.routing.top_k, cfg.hidden_size)


def test_the_layer_in_passes_compiles_at_the_laguna_cells_shapes_with_its_vmem_statement(
        shape, as_on_a_tpu):
    """parallel/moe.moe_dropless_local alone, forward and backward under
    `jax.checkpoint`, at 8,192 tokens, top 10 of 256, 8 held, 3072 x 1024
    experts: a pass of 7,168 rows where the worst case is 83,968. The
    gradient's program holds what the layer before the passes held (6 and 3
    calls: the forward's own loop is dead once only gradients are asked
    for), all inside ONE `while` body with the tokens' gradient, one
    `ps_moe_rows_sum`, and every `ps_moe_gmm` still states its own VMEM limit
    (ops/grouped_matmul.GMM_VMEM_DEFAULT)."""
    from ps_pytorch_tpu.parallel import moe

    spec = moe.DroplessSpec(num_experts=256, top_k=10, experts_held=8, routed_scale=2.5)
    n, d, f = 8192, 3072, 1024
    assert moe.pass_rows(n, spec) == 7168 and gm.buffer_rows(n * 10, 8) == 83968
    f32 = partial(shape, dtype=jnp.float32)
    blk = {"router": f32((d, 256)), "router_bias": f32((256,)),
           "experts": {"w_gate": f32((8, d, f)), "w_up": f32((8, d, f)), "w_down": f32((8, f, d))}}

    def loss(x, blk):
        layer = jax.checkpoint(lambda x, blk: moe.moe_dropless_local(x, blk, spec, jnp.bfloat16)[0])
        return jnp.sum(layer(x, blk).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1))).lower(f32((1, n, d)), blk).compile().as_text()
    calls, bodies = _moe_calls(text)
    assert {kernel: len(found) for kernel, found in calls.items()} == {
        "ps_moe_gmm": 6, "ps_moe_tgmm": 3, "ps_moe_rows_sum": 1}
    held_in = {comp for found in calls.values() for _, comp in found}
    assert len(held_in) == 1 and held_in <= bodies
    assert not _assignment_gathers(text, n, 10, d)
    for line, _ in calls["ps_moe_gmm"]:
        assert '"scoped_memory_configs":[{' in line
        assert "7168" in line and "83968" not in line


# ------------------- the family routed from the attention's input (PR 49)


def _cell_config(name, **cut):
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", name + ".json")) as f:
        return {**json.load(f), **cut}


@pytest.fixture(scope="module")
def prerouted_step(topo):
    """The smallthinker cell's own step, 1 x 16,384 tokens over all four
    layers, bfloat16, `remat`, compiled once for the tests that read it."""
    from ps_pytorch_tpu.models.lm import load_lm_config

    cfg = load_lm_config(_cell_config("smallthinker_21b_a3b_ep4"), attention_impl="flash",
                         remat=True, compute_dtype=jnp.bfloat16)
    with pytest.MonkeyPatch.context() as monkeypatch:
        _say_tpu(monkeypatch)
        return cfg, _lm_step_compiled(topo, cfg, 1, 16384)


def test_the_prerouted_cells_step_fits_the_chip_with_its_kernels_at_the_cells_shapes(
        prerouted_step):
    """The step of `smallthinker_train_b1s16384_ep4share` for a described
    v5e: state and temporaries under the 15.75 GiB a v5e gives, with
    `plan_remat_saves` keeping the kernels' operands; the flash kernels once
    a layer each way at `[28, 16384, 128]`, three walking the band's 252
    live tiles and one the causal 528; the grouped products 9 + 3 a layer at
    2560 x 768 over 16 experts and a pass of `pass_rows(16384, spec)` rows,
    never the worst case's; `ps_moe_rows_sum` twice a layer over those rows;
    no rotation pass in the global layer, and in
    the others `ps_rope` on q and k each way (the operands are kept, so
    `remat` runs no rotation again)."""
    import re

    from ps_pytorch_tpu.models.lm import lm_family
    from ps_pytorch_tpu.obs import hlo
    from ps_pytorch_tpu.ops.pallas_mode import kernel_census
    from ps_pytorch_tpu.parallel import moe

    cfg, compiled = prerouted_step
    memory = compiled.memory_analysis()
    held = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    assert 12.0 < held / 2 ** 30 < 14.0 < fa.V5E_BYTES_LIMIT / 2 ** 30, held / 2 ** 30
    text = compiled.as_text()
    assert kernel_census(text) == {"jnp": {}, "mosaic": {
        "ps_flash_fwd": 4, "ps_flash_dqkv": 4, "ps_moe_gmm": 36, "ps_moe_tgmm": 12,
        "ps_moe_rows_sum": 4 * 2,   # the forward's combine and the tokens' gradient a layer
        "ps_rope": 3 * 2 * 2}}      # q and k, forward and backward, in the three rotary layers
    spec = cfg.routing
    rows, worst = moe.pass_rows(16384, spec), gm.buffer_rows(16384 * 6, 16)
    assert (rows, worst) == (53248, 102400)
    walks = {"ps_flash_fwd": [], "ps_flash_dqkv": []}
    for line in _mosaic_lines(compiled):
        kernel = re.search(r"%(ps_[a-z_]+)", line).group(1)
        if kernel in walks:
            assert "bf16[28,16384,128]" in line
            walks[kernel].append(int(re.search(r"s32\[(\d+)\]\{0\}", line).group(1)))
        elif kernel == "ps_rope":
            assert re.search(r"bf16\[1,16384,(3584|512)\]", line), line
        elif kernel == "ps_moe_rows_sum":
            # a pass's rows in the chip's own tile order, pairs of rows a word; the words; [N, D] out
            assert f"bf16[{rows // 8},20,4,2,128]" in line and "bf16[16384,2560]" in line, line
            assert "s32[131072]" in line and str(worst) not in line      # 128 tiles' 768 words in blocks of 1,024
        else:
            assert f"[{rows}," in line and str(worst) not in line, line
            assert re.search(r"\[16,(2560,768|768,2560)\]", line), line
    sliding, glob = fa.plan_flash(16384, 16384, 128, jnp.bfloat16, fa.SlidingWindow(4096)), \
        fa.plan_flash(16384, 16384, 128, jnp.bfloat16, True)
    assert (sliding.grid_steps, glob.grid_steps, sliding.bwd) == (252, 528, "fused")
    assert sorted(walks["ps_flash_fwd"]) == sorted(walks["ps_flash_dqkv"]) == [252, 252, 252, 528]
    kinds = [fields for name, _, fields in lm_family(cfg).plans(cfg, 16384, 1)
             if name == "flash_plan"]
    assert [(k["grid_steps"], k["layers"], k["rotary"]) for k in kinds] == [
        (252, 3, "default"), (528, 1, "none")]
    places = {row["scope"] for row in hlo.census(text)["by_place"]}
    assert {"mixer/swa/rope", "ffn/moe/route"} <= places and "mixer/attention/rope" not in places


# cell's configuration -> (its depth cut to the leading dense layer and ONE
# expert layer with the per-layer lists that go with it, rows, the pass's
# rows, experts held, an expert's [D, F]): the kernels' shapes are the
# parent's, read off the tree before PR 49 with the same function
EXPERT_CELLS = {
    "kanana2_30b_a3b_ep8": ({"num_hidden_layers": 2}, 2, 28672, 16, (2048, 768)),
    "kimi_linear_48b_a3b_ep32": (
        {"num_hidden_layers": 2, "linear_attn_config": {
            "full_attn_layers": [], "head_dim": 128, "kda_layers": [1, 2], "num_heads": 32,
            "short_conv_kernel_size": 4}}, 2, 10240, 8, (2304, 1024)),
    "laguna_s_2_1_ep32": (
        {"num_hidden_layers": 2, "layer_types": ["full_attention", "sliding_attention"],
         "num_attention_heads_per_layer": [48, 72], "mlp_layer_types": ["dense", "sparse"],
         "gating_types": ["per_head"] * 2}, 1, 7168, 8, (3072, 1024)),
}


@pytest.mark.parametrize("name", list(EXPERT_CELLS))
def test_the_accepted_expert_cells_still_hold_the_layer_once_inside_its_loops(
        topo, as_on_a_tpu, name):
    """The sigmoid-routed, SiLU-gated layer that routes its own rows is the
    parent's program: at the kanana, kimi and laguna cells' rows (depth cut
    to one expert layer) the step holds `ps_moe_gmm` 9 times and
    `ps_moe_tgmm` 3, in the forward's loop and the backward's, on operands
    shaped as before this family came: a pass's rows, the held experts'
    matrices, and nothing the ReLU gate's count would add. The way back to
    the tokens is `ps_moe_rows_sum` once in either loop over the pass's rows
    in the chip's tile order, and no [k, N, D] gather is left."""
    import re

    from ps_pytorch_tpu.models.lm import load_lm_config

    cut, batch, rows, held, (d, f) = EXPERT_CELLS[name]
    cfg = load_lm_config(_cell_config(name, **cut), attention_impl="flash", remat=True,
                         compute_dtype=jnp.bfloat16)
    assert (cfg.routing.scores, cfg.routing.router_input, cfg.routing.activation) == (
        "sigmoid", "ffn_norm", "silu")
    text = _lm_step_compiled(topo, cfg, batch, 8192).as_text()
    calls, bodies = _moe_calls(text)
    assert {kernel: len(found) for kernel, found in calls.items()} == {
        "ps_moe_gmm": 9, "ps_moe_tgmm": 3, "ps_moe_rows_sum": 2}
    held_in = {comp for found in calls.values() for _, comp in found}
    assert held_in <= bodies and len(held_in) == 2, held_in
    assert sorted(comp for _, comp in calls["ps_moe_rows_sum"]) == sorted(held_in)
    n, k = batch * 8192, cfg.routing.top_k
    assert not _assignment_gathers(text, n, k, d)
    shapes = {kernel: {tuple(re.findall(r"(?:bf16|f32|s32)\[[0-9,]+\]", line))
                       for line, _ in found} for kernel, found in calls.items()}
    walk = (f"s32[{rows // gm.TILE_M}]", "s32[1]")
    wide, narrow = f"bf16[{rows},{d}]", f"bf16[{rows},{f}]"
    up, down = f"bf16[{held},{d},{f}]", f"bf16[{held},{f},{d}]"
    assert shapes["ps_moe_gmm"] == {
        (narrow, *walk, wide, up), (wide, *walk, narrow, down),      # gate / up, down
        (wide, *walk, narrow, up), (narrow, *walk, wide, down)}      # their transposes
    assert shapes["ps_moe_tgmm"] == {
        (f"f32[{held},{d},{f}]", *walk, wide, narrow), (f"f32[{held},{f},{d}]", *walk, narrow, wide)}
    # [N, D] out of the tiles' words and the pass's rows, pairs of rows a word
    from ps_pytorch_tpu.ops import moe_rows_sum as mr

    plan = mr.plan_rows(n, k, d, jnp.bfloat16)
    words = n // plan.tile * plan.entries_block(k)
    assert shapes["ps_moe_rows_sum"] == {
        (f"bf16[{n},{d}]", f"s32[{words}]", f"bf16[{rows // 8},{d // 128},4,2,128]")}
