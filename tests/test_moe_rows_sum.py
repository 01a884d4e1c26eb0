"""ops/moe_rows_sum.rows_sum, the dropless layer's way back from a pass's rows
to the tokens: the kernel `ps_moe_rows_sum` through the Pallas interpreter
against the plain form it replaces (parallel/moe._gather_assignments and a
sum), alone and inside `moe_dropless_local` over one pass, two and four:
values, both gradients, the counter that says which form ran. And what the
call costs before it runs, as counts: the body's equations at the four expert
cells' shapes, how often a step traces the body, and the kernel's sites in a
step's jaxpr. Reduced D on the CPU; a CPU run says nothing of speed
(tests/test_mosaic_compile.py compiles the kernel at the cells' shapes)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.ops import grouped_matmul as gm
from ps_pytorch_tpu.ops import moe_rows_sum as mr
from ps_pytorch_tpu.parallel import moe

M = 1024        # rows of ys: whole (8, 128) tiles


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")


def _plain(ys, pos, held):
    """The form the kernel replaces, as parallel/moe writes it."""
    return jnp.sum(moe._gather_assignments(ys, (None, None, pos, held)), axis=0)


def _route(case: str, n: int, k: int):
    """(pos, held): `all` every assignment held, `none` a pass that holds
    none of any token's rows, `part` / `ragged` a share of them, so that
    some tokens have no held assignment and some all k."""
    pos = jax.random.randint(jax.random.key(1), (n, k), 0, M, dtype=jnp.int32)
    share = {"part": 0.25, "none": 0.0, "all": 1.0, "ragged": 0.4}[case]
    held = jax.random.uniform(jax.random.key(2), (n, k)) < share
    if case in ("part", "ragged"):      # a token with none of its rows here, one with all k
        held = held.at[3].set(False).at[5].set(True)
    return jnp.where(held, pos, 0), held


def _refuse(ys):
    pytest.fail("the kernel's path took the twin")


@jax.jit
def _through_the_kernel(ys, pos, held):
    return mr.rows_sum(ys, pos, held, _refuse)


# n: no multiple of the kernel's tile, below and above it
CASES = [("part", 512), ("none", 256), ("all", 256), ("ragged", 300), ("ragged", 72)]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("k", [6, 8, 10])
@pytest.mark.parametrize("case,n", CASES, ids=[f"{c}{n}" for c, n in CASES])
def test_kernel_equals_the_plain_sum(interpreted, case, n, k, dtype):
    d = {6: 128, 8: 256, 10: 1152}[k]               # one line a row, two, nine (a stride of sixteen)
    assert mr.rows_sum_path(d, dtype) == "pallas"
    ys = jax.random.normal(jax.random.key(0), (M, d), jnp.float32).astype(dtype)
    pos, held = _route(case, n, k)
    got = _through_the_kernel(ys, pos, held)
    want = _plain(ys, pos, held)
    assert got.shape == (n, d) and got.dtype == ys.dtype
    if case == "none":
        assert not np.any(np.asarray(got, np.float32))
    else:
        assert not np.any(np.asarray(got[3], np.float32)) or case == "all"
    # float32 inside, one rounding: the plain sum's own bits at these k
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_the_sum_is_carried_in_float32_and_rounded_once(interpreted):
    """Three rows whose bfloat16 sum depends on where the rounding is: 256 +
    1 + 1 is 258 in float32 and rounds to 258; rounded after each addition it
    stays 256 (bfloat16 holds eight bits: 257 falls back to 256 twice)."""
    ys = jnp.zeros((M, 128), jnp.bfloat16).at[8].set(256.0).at[17].set(1.0).at[40].set(1.0)
    pos = jnp.zeros((16, 6), jnp.int32).at[2, :3].set(jnp.array([8, 17, 40]))
    held = jnp.zeros((16, 6), bool).at[2, :3].set(True)
    got = np.asarray(_through_the_kernel(ys, pos, held), np.float32)
    assert got[2, 0] == 258.0 and not got[:2].any() and not got[3:].any()
    stepwise = (ys[8] + ys[17] + ys[40]).astype(jnp.float32)
    assert float(stepwise[0]) == 256.0


@pytest.mark.parametrize("d,dtype,path", [
    (2048, jnp.bfloat16, "pallas"), (2560, jnp.bfloat16, "pallas"), (128, jnp.float32, "pallas"),
    (128, jnp.bfloat16, "pallas"), (64, jnp.float32, "xla"), (2048 + 64, jnp.bfloat16, "xla"),
    (2048, jnp.int32, "xla"),
    (2048, jnp.float16, "xla")])    # the kernel widens a half by its place in a word: bfloat16's alone
def test_the_shapes_decide_the_path(interpreted, d, dtype, path):
    assert mr.rows_sum_path(d, dtype) == path


def test_without_a_pallas_mode_the_twin_runs(monkeypatch):
    monkeypatch.delenv("PS_TPU_PALLAS_INTERPRET", raising=False)
    assert mr.rows_sum_path(2048, jnp.bfloat16) == "xla"
    ys, (pos, held) = jnp.ones((M, 256), jnp.bfloat16), _route("part", 64, 6)
    assert mr.rows_sum(ys, pos, held, lambda v: "twin") == "twin"


# the four expert cells' (N, k, D, a pass's rows): kanana, smallthinker, kimi, laguna
CELLS = [(16384, 6, 2048, 28672), (16384, 6, 2560, 53248), (16384, 8, 2304, 10240),
         (8192, 10, 3072, 7168)]


@pytest.mark.parametrize("n,k,d,dtype,tile", [
    (16384, 6, 2048, jnp.bfloat16, 256), (16384, 6, 2560, jnp.bfloat16, 128),
    (16384, 8, 2304, jnp.bfloat16, 128), (8192, 10, 3072, jnp.bfloat16, 128),
    (16384, 6, 1024, jnp.float32, 256),
    (600, 3, 128, jnp.float32, 256)])
def test_the_tile_is_a_function_of_the_shapes(n, k, d, dtype, tile):
    plan = mr.plan_rows(n, k, d, dtype)
    assert plan.tile == tile and plan.tile % mr.LANES == 0 and plan.tile % mr.SCAN_UNROLL == 0
    assert plan.vmem_bytes(k) <= mr.BUFFER_BYTES or plan.tile == mr.LANES
    # a landed row (a bfloat16's pair) is d words, and starts on a whole register
    assert plan.chunks * mr.LANES == d
    assert plan.stride % mr.SUBLANES == 0 and 0 <= plan.stride - plan.chunks < mr.SUBLANES


# --------------------------------------- what the call costs before it runs


def _walk(jaxpr):
    """Every equation of a jaxpr, those of its loops', branches' and jitted
    calls' bodies included (a call to a jitted function at each of its sites)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def _kernel_equations(n, k, d, rows, dtype=jnp.bfloat16) -> int:
    closed = jax.make_jaxpr(partial(mr._call, interpret=False))(
        jax.ShapeDtypeStruct((rows, d), dtype), jax.ShapeDtypeStruct((n, k), jnp.int32),
        jax.ShapeDtypeStruct((n, k), jnp.bool_))
    (call,) = [e for e in closed.jaxpr.eqns[0].params["jaxpr"].jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    return sum(1 for _ in _walk(call.params["jaxpr"]))


BODY_EQUATIONS_CEILING = 320


def test_the_bodys_jaxpr_is_the_same_size_at_the_four_cells_shapes():
    """The body is rolled: no loop of it is unrolled by the tile, by k or by
    D / 128, only by the module's constants (SCAN_UNROLL words, ROW_UNROLL
    rows, OUT_TOKENS tokens a turn), so the equations jax traces and Mosaic
    lowers are as many at each of the four expert cells' shapes (290 at PR 53;
    a form that unrolls a row's lines alone holds 16 to 24 strided loads and
    stores more a shape)."""
    counts = {cell: _kernel_equations(*cell) for cell in CELLS}
    assert len(set(counts.values())) == 1, counts
    assert next(iter(counts.values())) <= BODY_EQUATIONS_CEILING, counts


# ------------------------------------------------ inside the dropless layer

N, D, F, EXPERTS, HELD = 600, 128, 32, 16, 4


def _spec(k: int):
    return moe.DroplessSpec(num_experts=EXPERTS, top_k=k, experts_held=HELD, expert_offset=4,
                            routed_scale=2.5)


def _inputs(seed=0, d=D):
    ks = jax.random.split(jax.random.key(seed), 6)
    blk = {"router": jax.random.normal(ks[0], (d, EXPERTS)) / 8,
           "router_bias": jnp.zeros((EXPERTS,)),
           "experts": {"w_gate": jax.random.normal(ks[1], (HELD, d, F)) / 8,
                       "w_up": jax.random.normal(ks[2], (HELD, d, F)) / 8,
                       "w_down": jax.random.normal(ks[3], (HELD, F, d)) / 6}}
    return jax.random.normal(ks[4], (2, N // 2, d)), jax.random.normal(ks[5], (2, N // 2, d)), blk


@partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _layer(x, g, blk, k, rows, interpret, dtype=jnp.float32):
    """(y, the layer's counters, the gradients of sum(y * g) by x and every
    leaf); `interpret` keys the trace, which reads the mode as it is made."""
    def f(x, blk):
        y, stats = moe.moe_dropless_local(x, blk, _spec(k), dtype, rows=rows)
        return jnp.sum(y.astype(jnp.float32) * g), (y, stats)

    (_, (y, stats)), grads = jax.value_and_grad(f, (0, 1), has_aux=True)(x, blk)
    return y, stats, grads


def _rows_for(passes: int, k: int) -> int:
    """A buffer that the routing of `_inputs` (a tile of rows for each of the
    four held experts) walks in `passes` passes: the worst case, three tiles
    (the second pass is a third full, and its rows no multiple of what a
    tile of tokens reaches), one tile."""
    return {1: gm.buffer_rows(N * k, HELD), 2: 3 * gm.TILE_M, 4: gm.TILE_M}[passes]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("k", [3, 6])
@pytest.mark.parametrize("passes", [1, 2, 4])
def test_the_layer_through_the_kernel_equals_the_layer_through_the_twin(monkeypatch, k, passes, dtype):
    """Values and every gradient of `_routed`, over one pass, two and four
    (rows that are no multiple of what a tile of tokens reaches): the kernel
    is the forward's combine and the tokens' gradient, to the tolerance the
    grouped products' tests use."""
    x, g, blk = _inputs()
    rows = _rows_for(passes, k)
    want = _layer(x, g, blk, k, rows, False, dtype)
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    got = _layer(x, g, blk, k, rows, True, dtype)
    ran = int(got[1]["passes"])
    assert ran == int(want[1]["passes"]) == passes
    tol = 3e-6 if dtype == jnp.float32 else 2e-2
    for a, b in zip(jax.tree.leaves((got[0], got[2])), jax.tree.leaves((want[0], want[2]))):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=0, atol=tol * max(float(np.abs(b).max()), 1e-3))
    # the counter, by the form really taken
    held = int(jnp.sum(want[1]["counts"]))
    assert 0 < held < N * k
    assert int(moe.combine_rows_read(got[1], N * k, D, dtype)) == held
    monkeypatch.delenv("PS_TPU_PALLAS_INTERPRET")
    assert int(moe.combine_rows_read(want[1], N * k, D, dtype)) == N * k * ran


def test_the_counter_reaches_the_steps_counters(interpreted):
    x, _, blk = _inputs()
    _, stats = moe.moe_dropless_local(x, blk, _spec(3), jnp.float32)
    assert "moe_combine_rows_read" not in moe.routing_counters(moe.stack_layers([stats]))
    stats = {**stats, "combine_rows_read": moe.combine_rows_read(stats, N * 3, D, jnp.float32)}
    c = moe.routing_counters(moe.stack_layers([stats, moe.no_routing(HELD), stats]))
    assert c["moe_combine_rows_read_per_layer"].tolist() == [int(c["moe_rows_here"]) // 2, 0,
                                                             int(c["moe_rows_here"]) // 2]
    assert int(c["moe_combine_rows_read"]) == int(c["moe_rows_here"])
    from ps_pytorch_tpu.obs.schema import EVENT_KINDS
    assert "moe_combine_rows_read" in EVENT_KINDS["train_lm"].int_fields


def test_a_width_the_kernel_refuses_keeps_the_twin_and_says_so(interpreted):
    """D = 64: no whole tile of words, so the layer's combine is the plain
    form under the interpreter too, and the counter reads N x k a pass."""
    x, _, blk = _inputs(3, d=64)
    y, stats = moe.moe_dropless_local(x, blk, _spec(3), jnp.float32)
    assert int(moe.combine_rows_read(stats, N * 3, 64, jnp.float32)) == N * 3 * int(stats["passes"])
    assert "ps_moe_rows_sum" not in str(jax.make_jaxpr(
        lambda x: moe.moe_dropless_local(x, blk, _spec(3), jnp.float32)[0])(x)).replace(
            "ps_moe_rows_sum_jnp", "")


# ------------------------------------------------ a whole step's sites


def _step_of(layers: int):
    """A train step of the smallest expert family (one dense layer and
    `layers` expert layers, `remat` on) as `cli.train_lm` builds it, with its
    abstract arguments."""
    import chip_smoke
    from ps_pytorch_tpu.models.lm import lm_family, load_lm_config
    from ps_pytorch_tpu.optim import build_optimizer
    from ps_pytorch_tpu.parallel.dp_sp import make_lm_train_step, make_mesh_2d

    cfg = load_lm_config({**chip_smoke.LM_CONFIG, "num_hidden_layers": 1 + layers},
                         attention_impl="flash", remat=True, compute_dtype=jnp.bfloat16)
    tx = build_optimizer("adam", 3e-4, b1=0.9, b2=0.999, eps=1e-8)
    mesh = make_mesh_2d(1, 1, devices=jax.devices()[:1])
    state = jax.eval_shape(lambda key: (lambda p: (p, tx.init(p)))(lm_family(cfg).init(cfg, key)),
                           jax.random.key(0))
    return make_lm_train_step(cfg, tx, mesh), (*state, jax.ShapeDtypeStruct((2, 256), jnp.int32))


def _sites(jaxpr, name="ps_moe_rows_sum") -> int:
    """The Pallas calls named `name` in a jaxpr."""
    return sum(eqn.primitive.name == "pallas_call" and eqn.params["name"] == name
               for eqn in _walk(jaxpr))


def test_a_steps_expert_layers_share_the_bodys_trace_and_hold_it_at_three_sites_each(
        interpreted, monkeypatch):
    """What a Mosaic call costs in Python is its body's trace and its
    lowering, at every site unless the sites share them. The step of one
    expert layer traces the body a few times (a trace context each: the
    forward, the backward's re-run and twin); the step of two expert layers
    of the same shape traces it NO more often, and each step's jaxpr holds
    the kernel at three sites an expert layer: the forward's loop once, the
    backward's twice."""
    calls = []
    body = mr._kernel
    monkeypatch.setattr(mr, "_kernel", lambda *a, **kw: (calls.append(1), body(*a, **kw))[1])
    traced, sites = {}, {}
    for layers in (1, 2):
        mr._call.clear_cache()
        calls.clear()
        step, args = _step_of(layers)
        sites[layers] = _sites(step.trace(*args).jaxpr.jaxpr)
        traced[layers] = len(calls)
    assert sites == {1: 3, 2: 6}
    assert 1 <= traced[2] <= traced[1] <= 3, traced
