"""The dropless expert layer walks its rows in passes of a load-sized
buffer (parallel/moe.moe_dropless_local, `rows`): held against THE SAME
FUNCTION at `rows` = the worst case, which is the layer in one pass whatever
the routing (the layer as it stood before the passes, and the oracle here).
float32 on the CPU, the grouped products through their jnp twin and through
the Pallas interpreter; a CPU run says nothing of speed."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ps_pytorch_tpu.models.lm import lm_family, load_lm_config
from ps_pytorch_tpu.ops import grouped_matmul as gm
from ps_pytorch_tpu.parallel import moe
from ps_pytorch_tpu.parallel.dp_sp import make_lm_train_step, make_mesh_2d

from .test_mla_moe import PUBLISHED

N, D, F, EXPERTS, HELD, TOP = 600, 64, 32, 16, 4, 3
SPEC = moe.DroplessSpec(num_experts=EXPERTS, top_k=TOP, experts_held=HELD, expert_offset=4,
                        routed_scale=2.5)
WORST = gm.buffer_rows(N * TOP, HELD)           # 3,072 rows: 12 tiles
# every token chooses experts 4, 5, 6, all held here: 600, 600, 600 and 0 rows
ALL_HERE = jnp.zeros((EXPERTS,)).at[jnp.array([4, 5, 6])].set(10.0)

MODE = pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "pallas"])


@pytest.fixture
def mode(monkeypatch):
    def pick(interpret):
        if interpret:
            monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    return pick


def _inputs(bias=None, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    blk = {"router": jax.random.normal(ks[0], (D, EXPERTS)) / 8,
           "router_bias": jnp.zeros((EXPERTS,)) if bias is None else bias,
           "experts": {"w_gate": jax.random.normal(ks[1], (HELD, D, F)) / 8,
                       "w_up": jax.random.normal(ks[2], (HELD, D, F)) / 8,
                       "w_down": jax.random.normal(ks[3], (HELD, F, D)) / 6}}
    return jax.random.normal(ks[4], (2, N // 2, D)), jax.random.normal(ks[5], (2, N // 2, D)), blk


@partial(jax.jit, static_argnums=(3, 4))
def _layer(x, g, blk, rows, interpret):
    """(y, the layer's counters, the gradients of sum(y * g) by x and every
    leaf); `interpret` keys the trace, which reads the mode as it is made."""
    def f(x, blk):
        y, stats = moe.moe_dropless_local(x, blk, SPEC, jnp.float32, rows=rows)
        return jnp.sum(y * g), (y, stats)

    (_, (y, stats)), grads = jax.value_and_grad(f, (0, 1), has_aux=True)(x, blk)
    return y, stats, grads


def _implied_passes(counts, rows):
    live = sum(max(-(-c // gm.TILE_M), 1) for c in counts)
    return -(-live * gm.TILE_M // rows)


def _leaves(tree):
    return {jax.tree_util.keystr(p): np.asarray(v)
            for p, v in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.mark.parametrize("cell, n, k, held, of, rows, worst, passes", [
    ("laguna", 8192, 10, 8, 256, 7168, 83968, 12),
    ("kimi", 16384, 8, 8, 256, 10240, 133120, 13),
    ("kanana", 16384, 6, 16, 128, 28672, 102400, 4),
    ("smallthinker", 16384, 6, 16, 64, 53248, 102400, 2)])
def test_a_pass_holds_twice_the_uniform_load_at_the_cells_shapes(cell, n, k, held, of, rows, worst, passes):
    spec = moe.DroplessSpec(num_experts=of, top_k=k, experts_held=held)
    assert moe.pass_rows(n, spec) == rows and gm.buffer_rows(n * k, held) == worst
    assert -(-worst // rows) == passes and rows % gm.TILE_M == 0
    # an assignment's uniform share fits twice over, with a tile an expert to spare
    assert rows >= 2 * n * k * held / of + held * (gm.TILE_M - 1)


def test_a_layer_that_holds_every_expert_is_one_pass_of_the_worst_case():
    whole = moe.DroplessSpec(num_experts=4, top_k=2, experts_held=4)
    assert moe.pass_rows(300, whole) == gm.buffer_rows(600, 4)
    assert moe.pass_rows(N, SPEC) == 2048 < WORST


def test_layout_pass_slices_tiles_live_count_and_each_experts_rows():
    counts = jnp.array([600, 600, 600, 0], jnp.int32)
    whole = gm.group_layout(counts, WORST)
    assert whole.tile_expert.tolist() == [0, 0, 0, 1, 1, 1, 2, 2, 2, 3, 3, 3] and int(whole.n_live[0]) == 10
    want = {0: ([0, 0], 2, [0, 512, 512, 512], [512, 0, 0, 0]),
            1: ([0, 1], 2, [0, 256, 512, 512], [256, 256, 0, 0]),     # expert 0 ends, 1 begins
            4: ([2, 3], 2, [0, 0, 0, 256], [0, 0, 256, 256]),
            5: ([3, 3], 1, [0, 0, 0, 0], [0, 0, 0, 0])}               # past the live tiles: not run
    a_pass = jax.jit(partial(gm.layout_pass, rows=512))       # p traced, as in the layer's loop
    for p, (tiles, live, starts, sizes) in want.items():
        part = a_pass(whole, p)
        assert (part.tile_expert.tolist(), int(part.n_live[0]), part.starts.tolist(),
                part.sizes.tolist()) == (tiles, live, starts, sizes), p
    assert gm.experts_live(gm.layout_pass(whole, 1, 512), 4).tolist() == [True, True, False, False]
    assert gm.experts_live(gm.layout_pass(whole, 4, 512), 4).tolist() == [False, False, True, True]
    assert gm.experts_live(whole, 4).tolist() == [True] * 4


def test_the_weight_gradient_kernel_leaves_absent_experts_unwritten_and_the_product_masks_them(mode):
    """ops/grouped_matmul.py's trap, seen here: over one pass's part of the
    layout `ps_moe_tgmm` writes the blocks of the experts that own a live
    tile and no other (the interpreter fills what a kernel leaves unwritten
    with NaN; the chip leaves garbage); `grouped_matmul`'s backward gives
    zeros there."""
    mode(True)
    whole = gm.group_layout(jnp.array([600, 600, 600, 0], jnp.int32), WORST)
    part = gm.layout_pass(whole, 1, 512)                   # experts 0 and 1 only
    x = jax.random.normal(jax.random.key(0), (512, D))
    dy = jax.random.normal(jax.random.key(1), (512, F))
    raw = gm._tgmm(x, dy, part, gm.TILE_M, 4, gm.INTERPRET)
    assert np.isfinite(raw[:2]).all() and np.isnan(raw[2:]).all()
    w = jnp.ones((4, D, F))
    _, dw = jax.vjp(lambda x, w: gm.grouped_matmul(x, w, part), x, w)[1](dy)
    np.testing.assert_array_equal(dw[:2], raw[:2])
    assert not dw[2:].any()


CASES = {
    # name: (router bias, rows of a pass, passes)
    "fits_one_pass": (None, None, 1),
    "past_the_buffer": (ALL_HERE, None, 2),               # 10 live tiles in passes of 8
    "a_tile_a_pass": (ALL_HERE, 256, 10),                 # the most this routing can need
    "uniform_in_small_passes": (None, 512, 2),
}


@MODE
@pytest.mark.parametrize("case", list(CASES))
def test_values_and_every_gradient_are_the_one_pass_layers(case, interpret, mode):
    """One pass: bitwise the worst-case layer (under the jnp twin the
    experts' weight gradients to float32 rounding: XLA:CPU sums a ragged
    product's rows in blocks that depend on how many rows the buffer has).
    Several: float32 summation order, nothing else."""
    mode(interpret)
    bias, rows, passes = CASES[case]
    x, g, blk = _inputs(bias)
    y, stats, grads = _layer(x, g, blk, rows, interpret)
    want_y, want_stats, want_grads = _layer(x, g, blk, WORST, interpret)
    # (c) the counters: the passes the routing implies, the others unchanged
    counts = stats["counts"].tolist()
    assert int(stats["passes"]) == passes == _implied_passes(counts, rows or moe.pass_rows(N, SPEC))
    assert int(want_stats["passes"]) == 1 and int(want_stats["buffer_rows"]) == WORST
    assert int(stats["buffer_rows"]) == (rows or 2048)
    assert counts == want_stats["counts"].tolist()
    assert int(stats["unserved"]) == int(want_stats["unserved"])
    assert sum(counts) == int(jnp.sum(moe.dropless_route(
        x.reshape(N, D), blk["router"], blk["router_bias"], SPEC)[0] // 4 == 1))
    got, want = _leaves((y, grads)), _leaves((want_y, want_grads))
    assert got.keys() == want.keys() and len(got) == 7
    for name, w in want.items():
        if passes == 1 and (interpret or "experts" not in name):
            np.testing.assert_array_equal(got[name], w, err_msg=name)
        else:
            np.testing.assert_allclose(got[name], w, rtol=0, atol=2e-6 * np.abs(w).max(),
                                       err_msg=name)
    assert all(np.abs(w).max() > 0 for name, w in want.items() if "bias" not in name)


@MODE
def test_an_expert_astride_two_passes_and_one_absent_from_a_pass_get_the_oracles_gradient(
        interpret, mode):
    """Passes of two tiles over 600, 600, 600 and 0 rows (tiles 0-2, 3-5,
    6-8, 9): expert 4's rows end and expert 5's begin inside pass 1, each
    pass names two experts of four, expert 7 has a tile and no row."""
    mode(interpret)
    x, g, blk = _inputs(ALL_HERE, seed=1)
    _, stats, grads = _layer(x, g, blk, 512, interpret)
    _, _, want = _layer(x, g, blk, WORST, interpret)
    assert int(stats["passes"]) == 5 and stats["counts"].tolist() == [600, 600, 600, 0]
    for name in ("w_gate", "w_up", "w_down"):
        got_w, want_w = np.asarray(grads[1]["experts"][name]), np.asarray(want[1]["experts"][name])
        assert np.isfinite(got_w).all() and not got_w[3].any() and not want_w[3].any()
        for e in range(3):
            assert np.abs(want_w[e]).max() > 1.0
            np.testing.assert_allclose(got_w[e], want_w[e], rtol=0, atol=2e-6 * np.abs(want_w).max())


def test_no_assignment_is_left_out_between_passes():
    """Each held assignment lies in exactly one pass's rows, each live row
    of a pass holds one assignment, and together they are all of them."""
    x, _, blk = _inputs(jnp.zeros((EXPERTS,)).at[jnp.array([4, 6])].set(0.3), seed=2)
    idx, _ = moe.dropless_route(x.reshape(N, D), blk["router"], blk["router_bias"], SPEC)
    plan = moe._dispatch_plan(idx, SPEC, N, 512)
    passes = int(moe._passes(plan, 512))
    assert passes == _implied_passes(plan.counts.tolist(), 512) >= 2
    seen_rows, seen_here = [], np.zeros((N, TOP), int)
    for p in range(passes):
        row_assign, row_live, pos, here = map(np.asarray, moe._pass_route(plan, p, 512))
        seen_rows += row_assign[row_live].tolist()
        seen_here += here
        # the two maps are each other's inverse inside the pass
        n, j = np.nonzero(here)
        np.testing.assert_array_equal(row_assign[pos[n, j]], n * TOP + j)
        assert row_live[pos[n, j]].all() and row_live.sum() == here.sum()
    held = np.asarray(plan.held)
    np.testing.assert_array_equal(seen_here, held.astype(int))
    assert sorted(seen_rows) == np.flatnonzero(held.reshape(-1)).tolist()


# ------------------------------------------------ through a family's step


def _step_out(cfg, tokens, rows, monkeypatch):
    """(loss, counters, gradients as Adam's first moment) of one step of the
    tiny latent-attention family with the passes at `rows` rows (None: the
    layer's own choice)."""
    if rows is not None:
        monkeypatch.setattr(moe, "pass_rows", lambda n, spec: rows)
    tx = optax.adam(1e-3, b1=0.0)          # the first moment IS the gradient
    params = lm_family(cfg).init(cfg, jax.random.key(0))
    step = make_lm_train_step(cfg, tx, make_mesh_2d(1, 1), donate=False)
    _, opt, loss, counters = step(params, tx.init(params), tokens)
    return float(loss), counters, opt[0].mu


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_a_step_in_several_passes_is_the_step_in_one_with_its_counters(remat, monkeypatch):
    cfg = load_lm_config(PUBLISHED, attention_impl="naive", remat=remat)
    tokens = jax.random.randint(jax.random.key(1), (2, 48), 0, 101)
    loss1, c1, g1 = _step_out(cfg, tokens, None, monkeypatch)
    loss, c, g = _step_out(cfg, tokens, 256, monkeypatch)
    assert c1["moe_passes_per_layer"].tolist() == [1, 1] and int(c1["moe_passes"]) == 2
    assert c1["moe_buffer_rows_per_layer"].tolist() == [1280, 1280]
    # four experts a layer, each a tile of its own: a pass an expert
    assert c["moe_passes_per_layer"].tolist() == [4, 4] and int(c["moe_buffer_rows"]) == 512
    for name in ("moe_rows_here_per_layer", "moe_tokens_unserved", "moe_max_expert_rows"):
        assert np.asarray(c[name]).tolist() == np.asarray(c1[name]).tolist()
    assert loss == pytest.approx(loss1, rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=0, atol=3e-6 * max(float(np.abs(b).max()), 1e-3))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_remat_over_several_passes_is_the_step_without_it(dtype, monkeypatch):
    """float32: bitwise, as tests/test_remat_saves.py holds every family at
    one pass. bfloat16: XLA:CPU keeps excess precision in one program and
    not the other (PERF.md section 7), so to bfloat16's rounding."""
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    tokens = jax.random.randint(jax.random.key(2), (2, 48), 0, 101)
    out = {}
    for remat in (False, True):
        cfg = load_lm_config(PUBLISHED, attention_impl="flash", remat=remat, compute_dtype=dtype)
        out[remat] = _step_out(cfg, tokens, 256, monkeypatch)
    (loss0, c0, g0), (loss1, c1, g1) = out[False], out[True]
    assert c0["moe_passes_per_layer"].tolist() == c1["moe_passes_per_layer"].tolist() == [4, 4]
    for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g0)):
        if dtype == jnp.float32:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=0, atol=0.03 * max(float(np.abs(b).max()), 1e-3))
    assert loss1 == (loss0 if dtype == jnp.float32 else pytest.approx(loss0, rel=2e-3))
