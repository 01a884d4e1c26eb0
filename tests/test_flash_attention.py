"""Pallas flash attention vs. the jnp oracle (interpret mode on CPU).

full_attention (plain softmax attention) is the oracle; the blockwise
kernel must match it in value AND gradient, causal and not, including
q/k block sizes that tile the sequence unevenly (auto-shrunk blocks) and
fully-masked rows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.ops.flash_attention import flash_attention
from ps_pytorch_tpu.parallel.ring_attention import full_attention

B, T, H, D = 2, 128, 2, 32


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")


def _qkv(seed=0, t=T):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, t, H, D).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True], ids=["bidir", "causal"])
def test_flash_matches_full(causal):
    q, k, v = _qkv()
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    want = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("causal", [False, True], ids=["bidir", "causal"])
def test_flash_gradients_match_full(causal, flash_bwd):
    q, k, v = _qkv(1)

    def loss_flash(q, k, v):
        return jnp.sum(
            jnp.square(flash_attention(q, k, v, causal=causal,
                                       block_q=32, block_k=64))
        )

    def loss_full(q, k, v):
        return jnp.sum(jnp.square(full_attention(q, k, v, causal=causal)))

    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=3e-4, atol=3e-4
        )


def test_flash_uneven_seq_pads_to_full_blocks():
    from ps_pytorch_tpu.ops.flash_attention import plan_flash

    # T=192 with 128-wide blocks asked for: pad up to 256 and keep them
    # (the old behavior shrank blocks; padding keeps the MXU shape)
    plan = plan_flash(192, 192, D, jnp.float32, True, 128, 128)
    assert plan[:4] == (128, 128, 256, 256)
    q, k, v = _qkv(2, t=192)
    got = flash_attention(q, k, v, causal=True)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("causal", [False, True], ids=["bidir", "causal"])
def test_flash_odd_seq_keeps_mxu_blocks(causal, flash_bwd):
    """VERDICT r02 weak #3: T=1000 (small odd factors) must NOT degrade to
    a 1-wide grid — it pads to 1024 with MXU-shaped blocks, masks the
    tail, and still matches the oracle in value and gradient."""
    from ps_pytorch_tpu.ops.flash_attention import plan_flash

    bq, bk, tqp, tkp = plan_flash(1000, 1000, D, jnp.float32, causal)[:4]
    assert bq >= 128 and bk >= 128 and (tqp, tkp) == (1024, 1024)

    t = 250  # keep interpret-mode runtime sane; same 1000-style odd factors
    bq, bk, tqp, tkp = plan_flash(t, t, D, jnp.float32, causal)[:4]
    assert bq >= 128 and bk >= 128 and (tqp, tkp) == (256, 256)

    q, k, v = _qkv(7, t=t)
    got = flash_attention(q, k, v, causal=causal)
    want = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )

    def loss_flash(q, k, v):
        return jnp.sum(jnp.square(flash_attention(q, k, v, causal=causal)))

    def loss_full(q, k, v):
        return jnp.sum(jnp.square(full_attention(q, k, v, causal=causal)))

    got_g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    want_g = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=3e-4, atol=3e-4
        )


def test_flash_non_pow2_block_request_stays_correct():
    """A non-pow2 block size is floored to a pow2 so the padded grid
    covers the whole sequence (code-review r03 finding)."""
    from ps_pytorch_tpu.ops.flash_attention import plan_flash

    bq, bk, tqp, tkp = plan_flash(200, 200, D, jnp.float32, True, 96, 128)[:4]
    assert (bq, bk) == (64, 128) and tqp % bq == 0 and tkp % bk == 0
    q, k, v = _qkv(9, t=200)
    got = flash_attention(q, k, v, causal=True, block_q=96, block_k=128)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_flash_tiny_seq_pads_to_min_block():
    """T smaller than a block: pad to the pow2/8 minimum, still exact."""
    q, k, v = _qkv(8, t=7)
    got = flash_attention(q, k, v, causal=True)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_flash_in_jit_and_value_and_grad():
    q, k, v = _qkv(3)

    @jax.jit
    def f(q, k, v):
        return jnp.mean(flash_attention(q, k, v, causal=True,
                                        block_q=32, block_k=32))

    val, grads = jax.value_and_grad(f, argnums=(0,))(q, k, v)
    assert np.isfinite(float(val))
    assert np.all(np.isfinite(np.asarray(grads[0])))


def test_disable_falls_back_to_oracle(monkeypatch):
    monkeypatch.setenv("PS_TPU_DISABLE_PALLAS", "1")
    q, k, v = _qkv(4)
    got = flash_attention(q, k, v, causal=True)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_transformer_flash_matches_naive():
    """attention_impl='flash' end-to-end through the LM forward + grads."""
    from ps_pytorch_tpu.models.transformer import (
        TransformerConfig,
        apply_transformer,
        init_transformer,
    )
    from ps_pytorch_tpu.ops.metrics import next_token_nll

    base = dict(vocab_size=41, dim=64, depth=2, heads=2, max_seq_len=64)
    cfg_n = TransformerConfig(**base)
    cfg_f = TransformerConfig(**base, attention_impl="flash")
    params = init_transformer(cfg_n, jax.random.key(0))
    rng = np.random.RandomState(0)
    tok = jnp.asarray(rng.randint(0, 41, (2, 64)), jnp.int32)

    loss_n, g_n = jax.value_and_grad(
        lambda p: next_token_nll(apply_transformer(cfg_n, p, tok), tok)
    )(params)
    loss_f, g_f = jax.value_and_grad(
        lambda p: next_token_nll(apply_transformer(cfg_f, p, tok), tok)
    )(params)
    assert abs(float(loss_n) - float(loss_f)) < 2e-5
    for a, b in zip(jax.tree.leaves(g_n), jax.tree.leaves(g_f)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
        )


def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(1.0, np.max(np.abs(want))))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [False, True], ids=["bidir", "causal"])
@pytest.mark.parametrize("t", [96, 1000, 1024])
def test_planned_path_matches_full(t, causal, dtype, flash_bwd):
    """No block size passed: plan_flash's tiles. T=1024 causal skips the
    tile above the diagonal, T=1000 pads a tail and masks it, T=96 is one
    padded tile; bfloat16 puts the cast of p and ds on the path. Output
    and all three gradients against the float32 oracle on the same
    (rounded) inputs, from the fused backward and from the split pair."""
    rng = np.random.RandomState(t)
    q, k, v = (jnp.asarray(rng.randn(1, t, 2, 64) * 0.5, dtype)
               for _ in range(3))

    def loss(attend):
        def f(q, k, v):
            o = attend(q, k, v, causal=causal)
            return jnp.sum(o.astype(jnp.float32) ** 2), o
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    (_, o), grads = loss(flash_attention)(q, k, v)
    f32 = lambda x: x.astype(jnp.float32)
    (_, o_ref), grads_ref = loss(full_attention)(f32(q), f32(k), f32(v))
    assert o.dtype == dtype and all(g.dtype == dtype for g in grads)
    bound = 2e-5 if dtype == jnp.float32 else 2e-2
    errs = {"o": _rel_err(o, o_ref)}
    errs.update((n, _rel_err(g, r))
                for n, g, r in zip(("dq", "dk", "dv"), grads, grads_ref))
    assert all(e < bound for e in errs.values()), errs


WALK_MASKS = {"bidir": False, "causal": True, "earlier_windows": (256, 256)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("mask", sorted(WALK_MASKS))
@pytest.mark.parametrize("t", [96, 1000, 1024, 2048])
def test_the_live_walk_is_the_rectangular_walk_bitwise(t, mask, dtype, flash_bwd, monkeypatch):
    """o, lse, dq, dk, dv of the kernels as they walk the live tiles, and
    of the SAME kernels handed the whole rectangle as their table (every
    tile an entry, the dead ones skipped by their flag: the walk before
    PR 43): equal to the bit, since each accumulator sums the same tiles
    in the same order. T = 96 is one tile (wholly dead under the windows),
    1000 pads and masks a tail, 2048 has runs of dead tiles."""
    from ps_pytorch_tpu.ops import flash_attention as fa
    from ps_pytorch_tpu.ops.pallas_mode import INTERPRET

    causal = WALK_MASKS[mask]
    if isinstance(causal, tuple):
        causal = fa.EarlierWindows(*causal)
    plan = fa.plan_flash(t, t, 64, dtype, causal)
    rng = np.random.RandomState(t)
    q, k, v, do = (fa._pad_t(jnp.asarray(rng.randn(1, t, 64) * 0.5, dtype), plan.tq_pad)
                   for _ in range(4))

    def run():
        args = (0.125, causal, plan.block_q, plan.block_k, INTERPRET)
        o, lse = fa._flash_fwd(q, k, v, *args, k_len=plan.k_len)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
        return (o, lse) + tuple(fa._flash_bwd(q, k, v, lse, delta, do, *args, k_len=plan.k_len))

    live = run()
    with monkeypatch.context() as whole:
        whole.setattr(fa, "_kept", lambda live: np.ones(live.shape, bool))
        assert fa.plan_flash(t, t, 64, dtype, causal).grid_steps == plan.tiles_total
        rectangle = run()
    assert plan.grid_steps < plan.tiles_total or mask == "bidir" or t < 1000
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), live, rectangle):
        assert a.dtype == b.dtype and np.array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)), name


@pytest.mark.parametrize("k_major", [False, True], ids=["q_major", "k_major"])
def test_a_traced_walk_is_the_static_walk_and_an_idle_tail(k_major):
    """Offsets known only at run time (a ring hop): the tables are built in
    jnp at the rectangle's length; their first entries are the static
    walk's for the same offsets, and the tail repeats the last entry's
    blocks with no flag set, so no block index moves and nothing runs."""
    from ps_pytorch_tpu.ops import flash_attention as fa

    n_q, n_k, bq, bk = 3, 5, 32, 32

    @jax.jit
    def traced(q_off, k_off):
        return tuple(fa._walk(fa._live_tiles(n_q, n_k, bq, bk, True, 150, q_off, k_off), k_major))

    for q_off, k_off in [(64, 0), (0, 64), (40, 250), (0, 0), (1000, 0)]:
        static = fa._walk(fa._live_tiles(n_q, n_k, bq, bk, True, 150, q_off, k_off), k_major)
        assert isinstance(static.qi, np.ndarray) and static.steps <= n_q * n_k
        got = [np.asarray(x) for x in traced(jnp.int32(q_off), jnp.int32(k_off))]
        assert all(x.shape == (n_q * n_k,) and x.dtype == np.int32 for x in got)
        for name, g, s in zip(fa.Walk._fields, got, static):
            assert np.array_equal(g[:static.steps], s), (name, q_off, k_off)
            tail = g[static.steps:]
            assert np.all(tail == (0 if name == "flags" else s[-1])), (name, q_off, k_off)
        # every output block is written, in exactly one run of steps
        qi, ki, flags, in_block = static
        swept, summed = ((ki, qi) if k_major else (qi, ki))
        assert sorted(swept[flags & fa.LAST != 0]) == list(range(n_k if k_major else n_q))
        assert sorted(summed[flags & fa.LAST_IN != 0]) == list(range(n_q if k_major else n_k))
        runs = in_block[np.r_[True, in_block[1:] != in_block[:-1]]]
        assert len(set(runs)) == len(runs)


def test_plan_flash():
    """The tile plan is a pure function of what a call can observe."""
    from ps_pytorch_tpu.ops.flash_attention import (
        FUSED_BWD_CAP, MAX_BLOCK, VMEM_BUDGET, _vmem_bytes, plan_flash)

    # cell 3's call: 512-wide tiles, the one above the diagonal skipped
    plan = plan_flash(1024, 1024, 64, jnp.bfloat16, True)
    assert plan[:4] == (512, 512, 1024, 1024)
    # the grid walks the live tiles alone: three steps of the rectangle's four
    assert (plan.tiles_run, plan.tiles_total, plan.grid_steps) == (3, 4, 3)
    assert plan_flash(1024, 1024, 64, jnp.bfloat16, False).tiles_run == 4
    # the old 128-wide plan, as the tests can still ask for it
    old = plan_flash(1024, 1024, 64, jnp.bfloat16, True, 128, 128)
    assert (old.tiles_run, old.tiles_total) == (36, 64)
    for t_q, t_k, d, dtype in [
        (7, 7, 32, jnp.float32), (96, 96, 64, jnp.bfloat16),
        (200, 200, 16, jnp.float32), (520, 520, 64, jnp.bfloat16),
        (1000, 1000, 64, jnp.bfloat16), (1024, 1024, 128, jnp.float32),
        (10, 10, 16, jnp.float32), (512, 4096, 64, jnp.bfloat16),
        (8192, 8192, 256, jnp.float32),
    ]:
        for causal in (False, True):
            p = plan_flash(t_q, t_k, d, dtype, causal)
            for b, t, tp in ((p.block_q, t_q, p.tq_pad),
                             (p.block_k, t_k, p.tk_pad)):
                assert b & (b - 1) == 0 and 8 <= b <= MAX_BLOCK
                assert tp % b == 0 and t <= tp < t + b
                # compiled blocks: 128-multiples, or the whole padded axis
                assert b % 128 == 0 or b == tp
            # the tiles alone decide the blocks; the fused backward holds
            # the whole head's dq beside them, and says how much
            itemsize = jnp.dtype(dtype).itemsize
            assert _vmem_bytes(p.block_q, p.block_k, d, itemsize) <= VMEM_BUDGET
            assert p.bwd == "fused" and p.dq_acc_bytes == p.tq_pad * d * 4
            assert p.vmem_bytes == _vmem_bytes(
                p.block_q, p.block_k, d, itemsize, None, p.dq_acc_bytes)
            assert p.dq_acc_bytes < p.vmem_bytes <= FUSED_BWD_CAP
            assert p.tiles_total == (
                (p.tq_pad // p.block_q) * (p.tk_pad // p.block_k))
            # a square, causal or not: every q block meets its own keys and
            # every k block its own queries, so the walk holds no dead entry
            # (512 queries against 4,096 keys: one live tile, and an entry
            # for each of the seven k blocks that lie in the future)
            assert 1 <= p.tiles_run <= p.grid_steps <= p.tiles_total
            assert t_q != t_k or p.tiles_run == p.grid_steps
            assert causal or p.tiles_run == p.tiles_total
    # padding stays small: T=520 takes 128-wide blocks over 640, T=1000
    # 512-wide ones over 1024
    assert plan_flash(520, 520, 64, jnp.bfloat16, True)[:4] == (
        128, 128, 640, 640)
    assert plan_flash(1000, 1000, 64, jnp.bfloat16, True)[:4] == (
        512, 512, 1024, 1024)
    # a visiting shard of another length is tiled on its own axis; its
    # second k block lies past every query and keeps one dead entry, in
    # which dk and dv are written as zeros
    shard = plan_flash(96, 1024, 64, jnp.bfloat16, True)
    assert shard[:4] == (128, 512, 128, 1024)
    assert (shard.tiles_run, shard.grid_steps, shard.tiles_total) == (1, 2, 2)
    for cell, (_, t, d, d_v) in CELL_SHAPES.items():
        p = plan_flash(t, t, d, jnp.bfloat16, True, d_v=d_v)
        assert (p.grid_steps, p.tiles_run, p.tiles_total) == (
            (136, 136, 256) if t == 8192 else (3, 3, 4)), cell


def test_plan_eva_remote_keeps_an_entry_for_each_block_no_live_tile_touches():
    """Under EarlierWindows the queries of window 0 see no summary and (at
    narrow blocks) the last window's summaries are seen by no query: each
    such q block and k block keeps ONE dead entry of the walk, which writes
    the parent's outputs there (m = NEG_INF, l = 0, pv = 0; zero
    gradients), and nothing else above the live tiles is walked."""
    from ps_pytorch_tpu.ops import eva
    from ps_pytorch_tpu.ops import flash_attention as fa

    remote = eva.plan_eva(16384, 128, jnp.bfloat16, 2048, 16).remote
    # 4 q blocks of window 0; both k blocks hold a window some query sees
    assert (remote.tiles_run, remote.grid_steps, remote.tiles_total) == (40, 44, 64)
    kind = fa.EarlierWindows(64, 8)
    plan = fa.plan_flash(256, 32, 16, jnp.float32, kind, 32, 8)
    live = fa._live_tiles(8, 4, 32, 8, kind, None)
    no_key = int((~live.any(axis=1)).sum())    # q blocks of window 0
    no_query = int((~live.any(axis=0)).sum())  # k blocks of the last window
    assert (no_key, no_query) == (2, 1)
    assert plan.tiles_run == int(live.sum()) == 12
    assert plan.tiles_run < plan.grid_steps == 12 + no_key + no_query < plan.tiles_total == 32
    for k_major in (False, True):
        walk = fa._walk(live, k_major)
        dead = walk.flags & fa.LIVE == 0
        # the dead entries: q blocks 0 and 1 at k block 0, k block 3 at q block 0
        assert sorted(zip(walk.qi[dead], walk.ki[dead])) == [(0, 0), (0, 3), (1, 0)]
        assert walk.steps == plan.grid_steps


# [B * H, T, D_qk, D_v] of an attention layer in the benchmark's four LM
# cells (benchmark/workloads/: kanana and kimi share the first)
CELL_SHAPES = {
    "kanana_kimi": (64, 8192, 192, 128),
    "granite": (32, 8192, 64, 64),
    "gpt2m": (128, 1024, 64, 64),
}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_plan_fuses_the_backward_at_the_cells_shapes(cell):
    from ps_pytorch_tpu.ops.flash_attention import plan_flash

    _, t, d, d_v = CELL_SHAPES[cell]
    plan = plan_flash(t, t, d, jnp.bfloat16, True, d_v=d_v)
    assert (plan.block_q, plan.block_k, plan.bwd) == (512, 512, "fused")
    assert plan.dq_acc_bytes == t * d * 4  # 6 MiB at [8192, 192]


@pytest.mark.parametrize("t, d, d_v, bwd", [
    (32768, 192, 128, "fused"), (65536, 192, 128, "fused"),
    (131072, 192, 128, "split"), (131072, 64, 64, "fused"),
    (262144, 64, 64, "split")])
def test_plan_splits_the_backward_past_its_cap(t, d, d_v, bwd):
    """From shapes alone: the whole head's float32 dq and the tiles come
    under FUSED_BWD_CAP, or the pair runs with no accumulator, on the
    same tiles."""
    from ps_pytorch_tpu.ops.flash_attention import (
        FUSED_BWD_CAP, VMEM_BYTES, _vmem_bytes, plan_flash, vmem_limit)

    plan = plan_flash(t, t, d, jnp.bfloat16, True, d_v=d_v)
    assert plan.bwd == bwd and (plan.block_q, plan.block_k) == (512, 512)
    tiles = _vmem_bytes(512, 512, d, 2, d_v)
    if bwd == "fused":
        assert plan.dq_acc_bytes == t * d * 4
        assert tiles + plan.dq_acc_bytes < plan.vmem_bytes <= FUSED_BWD_CAP
        assert vmem_limit(plan.vmem_bytes) < VMEM_BYTES
    else:
        assert (plan.dq_acc_bytes, plan.vmem_bytes) == (0, tiles)
        assert tiles + t * d * 4 > FUSED_BWD_CAP


def test_every_kernel_name_is_read_by_flash_ms(flash_bwd):
    """`flash_ms` and `flash_roofline` find the kernels by a pattern on
    the event's name (benchmark/layer_metrics/flash_ms.json): every name
    the forward and either backward can launch must match it, so a rename
    cannot silence the two metrics."""
    import json
    import os
    import re

    from .test_remat_saves import _count_kernels

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    patterns = set()
    for metric in ("flash_ms", "flash_roofline"):
        with open(os.path.join(root, "benchmark", "layer_metrics",
                               f"{metric}.json")) as f:
            patterns.add(json.load(f)["args"]["pattern"])
    (pattern,) = patterns
    q, k, v = _qkv(5)
    loss = lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True))
    names = _count_kernels(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr, {})
    want = {"fused": ["ps_flash_dqkv"],
            "split": ["ps_flash_dq", "ps_flash_dkv"]}[flash_bwd]
    assert names == dict.fromkeys(["ps_flash_fwd"] + want, 1)
    for name in names:
        # XLA spells the instruction after the end of its op_name
        for spelt in (name, f"{name}.3", f"transpose_jvp_{name}_.1"):
            assert re.search(pattern, spelt), (pattern, spelt)


# sha256 of the jaxpr (kernel bodies and all) of flash_attention's value and
# gradient at a cell's shapes. PR 41 held them to the commit before ops/eva.py
# and the mask kind EarlierWindows (PR 40); PR 43 meant to change the dense
# path's kernels (their grids walk the live tiles from tables in SMEM) and
# these are its digests. A PR that means to change them again prints the new
# digest with this test and says so.
DENSE_JAXPR = {
    "kanana_kimi": ((2, 8192, 32, 192, 128),
                    "f6a7cbeb3f41e9012d5052c7f5c5e844e2670bd8817adedc9582cedb4137b74c"),
    "gpt2m": ((8, 1024, 16, 64, 64),
              "f4fca00e1c578ef06ac67fac2156ef31b447eaba995bebd93c062f3b4f03be7e"),
}


@pytest.mark.parametrize("cell", sorted(DENSE_JAXPR))
def test_the_dense_path_lowers_as_it_did_before_the_second_mask_kind(cell):
    """`causal` False | True reaches _mask_scores and _tile_live through the
    branches it always took: the kanana / kimi and gpt2m attention calls
    trace to one held text (since PR 43 the live walk's: grid (bh, 136) and
    (bh, 3)), so a change to another mask kind cannot move the dense path
    unseen."""
    import hashlib

    (b, t, h, d, d_v), want = DENSE_JAXPR[cell]
    q = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, t, h, d_v), jnp.bfloat16)
    loss = lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True).astype(jnp.float32))
    text = str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, q, v))
    assert text.count("ps_flash_fwd") == text.count("ps_flash_dqkv") == 1
    steps = 136 if t == 8192 else 3
    assert text.count(f"grid=({b * h}, {steps})") == 2
    assert hashlib.sha256(text.encode()).hexdigest() == want
