"""ops/eva.eva_attention against its definition, position by position, in
float32: values and the gradients of q, k, v, phi and mu, over rows that
fill their windows, do not, and are shorter than one; the mask kind
EarlierWindows tile by tile; what `remat` changes (nothing, bitwise).

Small: window 64, chunk 8, 4 heads of 16. The kernels run interpreted
(PS_TPU_PALLAS_INTERPRET), the jnp twin without it."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.ops import eva
from ps_pytorch_tpu.ops import flash_attention as fa

B, H, D, WINDOW, CHUNK = 1, 4, 16, 64, 8
HI = jax.lax.Precision.HIGHEST
# a whole number of windows; a part-filled last window and chunk; under one window
LENGTHS = {"windows4": 256, "part_window": 203, "under_one": 40}


@pytest.fixture(params=["kernels", "jnp"])
def path(request, monkeypatch):
    if request.param == "kernels":
        monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PS_TPU_PALLAS_INTERPRET", raising=False)
    return request.param


@pytest.fixture()
def kernels(monkeypatch):
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")


def _inputs(t, seed=0, dtype=jnp.float32):
    """q, k, v, phi, mu of values that bfloat16 holds exactly, so both
    dtypes are given the same numbers."""
    rng = np.random.RandomState(seed)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape).astype(np.float32)).astype(
        jnp.bfloat16).astype(dtype)
    return mk(B, t, H, D), mk(B, t, H, D), mk(B, t, H, D), mk(H, D), mk(H, D)


def _seen(t):
    """[t, t], [t, chunks] and [chunks, t] booleans, filled position by
    position from the definition: own window up to itself; chunks of whole
    earlier windows; the positions a chunk holds."""
    n = -(-t // CHUNK)
    own, far, holds = np.zeros((t, t), bool), np.zeros((t, n), bool), np.zeros((n, t), bool)
    for i in range(t):
        w = i // WINDOW
        own[i, WINDOW * w:i + 1] = True
        far[i, :w * (WINDOW // CHUNK)] = True
        holds[i // CHUNK, i] = True
    return own, far, holds


def definition(q, k, v, phi, mu):
    """(o, mass on summaries [B, T, H]) in float32 at `highest`: every chunk
    pooled by its own softmax over its real positions, then for every
    query ONE softmax over the explicit union."""
    f32 = jnp.float32
    q, k, v, phi, mu = (x.astype(f32) for x in (q, k, v, phi, mu))
    t, s = q.shape[1], D ** -0.5
    own, far, holds = _seen(t)
    weight = s * jnp.einsum("bmhd,hd->bhm", k, phi, precision=HI)              # [B, H, T]
    a = jax.nn.softmax(jnp.where(holds, weight[:, :, None, :], -jnp.inf), axis=-1)  # [B, H, n, T]
    kp = jnp.einsum("bhjm,bmhd->bjhd", a, k, precision=HI) + mu
    vp = jnp.einsum("bhjm,bmhd->bjhd", a, v, precision=HI)
    keys, values = jnp.concatenate([k, kp], axis=1), jnp.concatenate([v, vp], axis=1)
    seen = jnp.asarray(np.concatenate([own, far], axis=1))
    scores = s * jnp.einsum("bqhd,bkhd->bhqk", q, keys, precision=HI)
    p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return (jnp.einsum("bhqk,bkhd->bqhd", p, values, precision=HI),
            jnp.sum(p[..., t:], axis=-1).transpose(0, 2, 1))


def _weighted(fn):
    """A scalar of fn's output whose gradient weighs every entry otherwise."""
    def loss(*args):
        o = fn(*args)[0].astype(jnp.float32)
        return jnp.sum(o * jnp.cos(jnp.arange(o.size, dtype=jnp.float32)).reshape(o.shape))
    return loss


def _with_grads(fn):
    """One program: (fn's outputs, the gradients of _weighted(fn))."""
    def both(*args):
        return fn(*args), jax.grad(_weighted(fn), argnums=range(5))(*args)

    return jax.jit(both)


def _attend(*args):
    return eva.eva_attention(*args, WINDOW, CHUNK)


@functools.cache
def _defined(t):
    """((o, mass), gradients) of the definition on _inputs(t, seed=t)."""
    return _with_grads(definition)(*_inputs(t, seed=t))


CASES = [(path, length, dtype) for path in ("kernels", "jnp") for length in sorted(LENGTHS)
         for dtype in ("f32", "bf16") if path == "kernels" or dtype == "f32"]


@pytest.mark.parametrize("path, length, dtype", CASES, ids=["-".join(c) for c in CASES])
def test_values_and_gradients_match_the_definition(monkeypatch, path, length, dtype):
    if path == "kernels":
        monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PS_TPU_PALLAS_INTERPRET", raising=False)
    t, dtype = LENGTHS[length], {"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype]
    args = _inputs(t, seed=t, dtype=dtype)
    tol = 3e-5 if dtype == jnp.float32 else 6e-2
    (o, counts), got = _with_grads(_attend)(*args)
    (want, mass), ref = _defined(t)
    assert o.dtype == dtype and o.shape == (B, t, H, D)
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(want), rtol=tol, atol=tol)
    # the counter is the definition's mass over the queries past window 0
    far = max(t - WINDOW, 0)
    assert float(counts["mass_queries"]) == B * H * far
    np.testing.assert_allclose(float(counts["mass_sum"]), float(jnp.sum(mass[:, WINDOW:])),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-4)
    if far:
        assert 0.0 < float(counts["mass_sum"]) / float(counts["mass_queries"]) < 1.0
    for name, g, r in zip("q k v phi mu".split(), got, ref):
        assert g.dtype == dtype and bool(jnp.all(jnp.isfinite(g))), name
        scale = float(jnp.max(jnp.abs(r))) + 1e-6
        np.testing.assert_allclose(np.asarray(g, np.float32) / scale, np.asarray(r) / scale,
                                   rtol=0, atol=10 * tol, err_msg=name)


def test_a_query_of_window_0_sees_no_summary(path):
    """Its output is plain causal attention, phi and mu do not reach it, and
    nothing of the row's later windows does."""
    from ps_pytorch_tpu.parallel.ring_attention import full_attention

    q, k, v, phi, mu = _inputs(256, seed=3)
    first = lambda *a: _attend(*a)[0][:, :WINDOW]
    np.testing.assert_allclose(
        np.asarray(jax.jit(first)(q, k, v, phi, mu)),
        np.asarray(full_attention(q[:, :WINDOW], k[:, :WINDOW], v[:, :WINDOW], causal=True)),
        rtol=3e-5, atol=3e-5)
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.square(first(*a))), argnums=range(5)))(
        q, k, v, phi, mu)
    assert float(jnp.max(jnp.abs(grads[3]))) == 0.0 and float(jnp.max(jnp.abs(grads[4]))) == 0.0
    for g in grads[:3]:
        assert float(jnp.max(jnp.abs(g[:, WINDOW:]))) == 0.0
    # ... and a later window's queries do lean on summaries: phi and mu get a gradient
    later = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.square(_attend(*a)[0][:, WINDOW:])),
                             argnums=(3, 4)))(q, k, v, phi, mu)
    assert all(float(jnp.max(jnp.abs(g))) > 1e-3 for g in later)


def test_a_summary_stands_for_its_chunk_alone(kernels):
    """Changing one byte's key and value moves later windows' outputs (through
    its chunk's summary), not its own window's earlier positions, and the
    own window's later positions see the byte itself."""
    q, k, v, phi, mu = _inputs(256, seed=5)
    at = 70                                        # window 1, chunk 8
    k2, v2 = k.at[:, at].add(1.0), v.at[:, at].add(1.0)
    moved = jnp.max(jnp.abs(_attend(q, k2, v2, phi, mu)[0] - _attend(q, k, v, phi, mu)[0]),
                    axis=(0, 2, 3))
    assert float(jnp.max(moved[:at])) == 0.0                       # window 0, and window 1 before it
    assert float(jnp.min(moved[at:2 * WINDOW])) > 0.0              # its own window sees the byte
    assert float(jnp.min(moved[2 * WINDOW:])) > 0.0                # later windows see its summary


POLICIES = {"residuals": eva.EVA_SAVED, "operands_too": eva.EVA_SAVED + eva.EVA_OPERANDS}


@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_remat_is_bitwise_the_plain_backward(kernels, policy):
    """Under a policy that keeps the op's residuals (and under one that keeps
    its operands too) the gradients are the same bits, and the forward run
    again holds no kernel pass."""
    args = _inputs(LENGTHS["part_window"], seed=7, dtype=jnp.bfloat16)
    loss = _weighted(_attend)
    plain = jax.jit(jax.grad(loss, argnums=range(5)))(*args)
    kept = jax.grad(jax.checkpoint(loss, policy=jax.checkpoint_policies.save_only_these_names(
        *POLICIES[policy])), argnums=range(5))
    grads = jax.jit(kept)(*args)
    for g, p in zip(grads, plain):
        assert np.array_equal(np.asarray(g, np.float32), np.asarray(p, np.float32))
    text = str(jax.make_jaxpr(kept)(*args))
    assert text.count("ps_flash_fwd") == text.count("ps_flash_dqkv") == 2  # local, remote


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_both_passes_walk_live_tiles_to_the_rectangles_bits(kernels, flash_bwd, dtype, monkeypatch):
    """A part-filled last window (868 of 4 x 256 positions, chunks of 4) at
    blocks capped at 128, so the local pass is 2 x 2 tiles a window (three
    live) and the remote pass 8 x 2 (eight live, and a dead entry for each
    of the two q blocks of window 0): o and the five gradients from the walk
    over live tiles equal, to the bit, those of the same kernels handed
    every tile of the rectangle (the walk before PR 43)."""
    monkeypatch.setattr(fa, "MAX_BLOCK", 128)
    t, window, chunk = 868, 256, 4
    plan = eva.plan_eva(t, D, dtype, window, chunk)
    assert (plan.local.tiles_run, plan.local.grid_steps, plan.local.tiles_total) == (3, 3, 4)
    assert (plan.remote.tiles_run, plan.remote.grid_steps, plan.remote.tiles_total) == (8, 10, 16)
    args = _inputs(t, seed=13, dtype=dtype)
    both = lambda: _with_grads(lambda *a: eva.eva_attention(*a, window, chunk))(*args)
    (o, _), grads = both()
    with monkeypatch.context() as whole:
        whole.setattr(fa, "_kept", lambda live: np.ones(live.shape, bool))
        assert eva.plan_eva(t, D, dtype, window, chunk).remote.grid_steps == 16
        (o_rect, _), grads_rect = both()
    for name, a, b in zip("o q k v phi mu".split(), (o, *grads), (o_rect, *grads_rect)):
        assert np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32)), name
    assert float(jnp.max(jnp.abs(grads[3]))) > 0   # the summaries were seen


# ------------------------------------------------------------ the mask kind


MASKS = [
    # (q_window, k_window, block_q, block_k, t_q, t_k)
    (64, 8, 32, 8, 256, 32),      # tiles inside a window
    (64, 8, 128, 16, 256, 32),    # a query tile over two windows
    (64, 8, 64, 32, 256, 27),     # a padded key tail (k_len)
    (32, 32, 32, 32, 128, 128),   # keys in the queries' units: whole earlier blocks
]


@pytest.mark.parametrize("mask", MASKS, ids=lambda m: "x".join(map(str, m)))
def test_a_tile_is_skipped_iff_every_score_in_it_is_masked(mask):
    q_window, k_window, bq, bk, t_q, t_k = mask
    kind = fa.EarlierWindows(q_window, k_window)
    plan = fa.plan_flash(t_q, t_k, D, jnp.float32, kind, bq, bk)
    assert (plan.block_q, plan.block_k) == (bq, bk)
    n_q, n_k = plan.tq_pad // bq, plan.tk_pad // bk
    live = 0
    for qi in range(n_q):
        for ki in range(n_k):
            tile = fa._mask_scores(jnp.zeros((bk, bq), jnp.float32), qi, ki, bq, bk, kind,
                                   plan.k_len)
            kept = np.asarray(tile) == 0.0
            # position by position: key j is seen by query i iff j // k_window < i // q_window
            for kk in range(0, bk, max(bk // 4, 1)):
                for qq in range(0, bq, max(bq // 4, 1)):
                    j, i = ki * bk + kk, qi * bq + qq
                    assert kept[kk, qq] == (j // k_window < i // q_window and j < t_k)
            assert bool(fa._tile_live(qi, ki, bq, bk, kind, plan.k_len)) == bool(kept.any())
            live += bool(kept.any())
    assert plan.tiles_run == live < plan.tiles_total == n_q * n_k
    assert live <= plan.grid_steps <= plan.tiles_total   # live, and a q or k block's dead entry


def test_the_partial_kernels_under_the_mask_kind_match_dense_scores(kernels):
    """flash_partial / flash_grads_partial with an EarlierWindows against the
    masked softmax written out, at blocks that make several live and several
    dead tiles; a query with no key at all comes out as (0, NEG_INF, 0)."""
    rng = np.random.RandomState(11)
    bh, t_q, t_k, d = 3, 256, 32, D
    q, do = (jnp.asarray(rng.randn(bh, t_q, d).astype(np.float32)) for _ in range(2))
    k, v = (jnp.asarray(rng.randn(bh, t_k, d).astype(np.float32)) for _ in range(2))
    kind, s = fa.EarlierWindows(WINDOW, WINDOW // CHUNK), d ** -0.5
    pv, m, l = fa.flash_partial(q, k, v, s, kind, 0, 0, block_q=32, block_k=8)
    seen = (np.arange(t_k)[None] // (WINDOW // CHUNK)) < (np.arange(t_q)[:, None] // WINDOW)
    scores = jnp.where(seen, s * jnp.einsum("bqd,bkd->bqk", q, k, precision=HI), -jnp.inf)
    assert np.all(np.asarray(l[:, :WINDOW]) == 0) and np.all(np.asarray(pv[:, :WINDOW]) == 0)
    assert np.all(np.asarray(m[:, :WINDOW]) == fa.NEG_INF)
    lse = jax.nn.logsumexp(scores[:, WINDOW:], axis=-1)
    np.testing.assert_allclose(np.asarray(m + jnp.log(l))[:, WINDOW:], np.asarray(lse), rtol=1e-5)
    p = jax.nn.softmax(scores[:, WINDOW:], axis=-1)
    o = jnp.einsum("bqk,bkd->bqd", p, v, precision=HI)
    np.testing.assert_allclose(np.asarray(pv / l[..., None])[:, WINDOW:], np.asarray(o),
                               rtol=2e-5, atol=2e-5)
    # gradients of sum(o * do) over the queries that see something
    def dense(q, k, v):
        sc = jnp.where(seen, s * jnp.einsum("bqd,bkd->bqk", q, k, precision=HI), -jnp.inf)
        out = jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(sc[:, WINDOW:], axis=-1), v, precision=HI)
        return jnp.sum(out * do[:, WINDOW:])
    want = jax.grad(dense, argnums=(0, 1, 2))(q, k, v)
    full_lse = jnp.concatenate([jnp.full((bh, WINDOW), -fa.NEG_INF), lse], axis=1)
    delta = jnp.concatenate([jnp.zeros((bh, WINDOW)), jnp.sum(o * do[:, WINDOW:], axis=-1)], axis=1)
    got = fa.flash_grads_partial(q, k, v, do.at[:, :WINDOW].set(0.0), full_lse, delta, s, kind,
                                 0, 0, block_q=32, block_k=8)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=3e-4, atol=3e-4)


def test_the_plan_counts_what_the_cell_runs():
    """At the benchmark cell's shape (T 16,384, heads of 128, bfloat16): 8
    windows of 2,048, 1,024 summaries, 512-wide tiles; ten live tiles of
    sixteen a window, forty of sixty-four over the summaries."""
    plan = eva.plan_eva(16384, 128, jnp.bfloat16, 2048, 16)
    assert (plan.windows, plan.t_pad, plan.summaries, plan.per_window) == (8, 16384, 1024, 128)
    assert (plan.local.block_q, plan.local.block_k, plan.local.tiles_run) == (512, 512, 10)
    assert (plan.remote.block_q, plan.remote.block_k) == (512, 512)
    # the four q blocks of window 0 keep a dead entry each
    assert (plan.remote.tiles_total, plan.remote.grid_steps, plan.remote.tiles_run) == (64, 44, 40)
    assert (plan.local.tiles_total, plan.local.grid_steps) == (16, 10)
    assert plan.tiles() == (80, 40) and plan.local.bwd == plan.remote.bwd == "fused"
    # before tile rounding: 8 x 2048^2 / 2 local and 2048 x 128 x 28 remote score entries
    assert 80 * 512 * 512 >= 8 * 2048 * 2048 // 2 and 40 * 512 * 512 >= 2048 * 128 * 28
    saves = eva.eva_saves(1, 16384, 32, 128, jnp.bfloat16, 2048, 16, 4)
    assert saves.count == 4 and set(saves.residuals) == set(eva.EVA_SAVED)
    assert saves.residuals["ps_eva_o"].shape == (32, 16384, 128)
    assert saves.operands["ps_eva_kp"].shape == (32, 1024, 128)


def test_refusals():
    q, k, v, phi, mu = _inputs(64)
    with pytest.raises(ValueError, match="chunk 7 does not divide window 64"):
        eva.eva_attention(q, k, v, phi, mu, 64, 7)
    with pytest.raises(ValueError, match="unknown attention_impl"):
        eva.eva_attention(q, k, v, phi, mu, 64, 8, impl="ring")
