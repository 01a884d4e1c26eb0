"""The third mask kind of ops/flash_attention.py, SlidingWindow (key j seen
iff i - window < j <= i), through the ONE _mask_scores / _tile_live / _walk
set: the kernels in interpret mode against the jnp twin
(parallel/ring_attention.full_attention under the same mask kind), forward
and backward, fused and split; the walk holds the band's tiles only and is
the rectangle's walk to the bit; the walks of `causal` and EarlierWindows are
the parent's at the five accepted cells' shapes."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.ops import flash_attention as fa
from ps_pytorch_tpu.ops.flash_attention import EarlierWindows, SlidingWindow, flash_attention
from ps_pytorch_tpu.parallel.ring_attention import full_attention

from .test_flash_attention import _rel_err


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")


# (T, window, block): a window narrower than a tile, as wide as one, wider
# than one and wider than the row; T 100 and 130 are no multiple of the tile
# (a padded, masked tail); block None is plan_flash's own choice
CASES = [(96, 8, 32), (100, 16, 32), (128, 32, 32), (130, 50, 64), (96, 200, 32),
         (192, 40, None)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("t, window, block", CASES)
def test_the_kernels_match_the_jnp_twin_forward_and_backward(t, window, block, dtype, flash_bwd):
    rng = np.random.RandomState(t + window)
    q, k, v = (jnp.asarray(rng.randn(2, t, 3, 32) * 0.5, dtype) for _ in range(3))
    mask = SlidingWindow(window)

    def both(attend, **blocks):
        def f(q, k, v):
            o = attend(q, k, v, causal=mask, **blocks)
            return jnp.sum(o.astype(jnp.float32) ** 2), o
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    (_, o), grads = both(flash_attention, block_q=block, block_k=block)(q, k, v)
    f32 = lambda x: x.astype(jnp.float32)
    (_, o_ref), grads_ref = both(full_attention)(f32(q), f32(k), f32(v))
    assert o.dtype == dtype and all(g.dtype == dtype for g in grads)
    bound = 2e-5 if dtype == jnp.float32 else 2e-2
    errs = {"o": _rel_err(o, o_ref)}
    errs.update((n, _rel_err(g, r)) for n, g, r in zip(("dq", "dk", "dv"), grads, grads_ref))
    assert all(e < bound for e in errs.values()), errs


def test_the_twin_under_a_window_is_the_written_out_softmax():
    """full_attention's own reading of the mask kind, against the definition
    spelt with a loop over queries."""
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(1, 20, 1, 8), jnp.float32) for _ in range(3))
    got = np.asarray(full_attention(q, k, v, causal=SlidingWindow(5)))[0, :, 0]
    for i in range(20):
        lo = max(i - 5 + 1, 0)
        s = np.asarray(k[0, lo:i + 1, 0] @ q[0, i, 0]) / np.sqrt(8)
        p = np.exp(s - s.max())
        np.testing.assert_allclose(got[i], (p / p.sum()) @ np.asarray(v[0, lo:i + 1, 0]),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("t, window", [(1000, 128), (1024, 300), (2048, 512)])
def test_the_band_walk_is_the_rectangular_walk_bitwise(t, window, dtype, flash_bwd, monkeypatch):
    """o, lse, dq, dk, dv from the walk over the band's tiles, and from the
    SAME kernels handed the whole rectangle (every tile an entry, the dead
    ones skipped by their flag): equal to the bit."""
    from ps_pytorch_tpu.ops.pallas_mode import INTERPRET

    mask = SlidingWindow(window)
    plan = fa.plan_flash(t, t, 64, dtype, mask)
    rng = np.random.RandomState(t)
    q, k, v, do = (fa._pad_t(jnp.asarray(rng.randn(1, t, 64) * 0.5, dtype), plan.tq_pad)
                   for _ in range(4))

    def run():
        args = (0.125, mask, plan.block_q, plan.block_k, INTERPRET)
        o, lse = fa._flash_fwd(q, k, v, *args, k_len=plan.k_len)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
        return (o, lse) + tuple(fa._flash_bwd(q, k, v, lse, delta, do, *args, k_len=plan.k_len))

    band = run()
    with monkeypatch.context() as whole:
        whole.setattr(fa, "_kept", lambda live: np.ones(live.shape, bool))
        assert fa.plan_flash(t, t, 64, dtype, mask).grid_steps == plan.tiles_total
        rectangle = run()
    assert plan.grid_steps == plan.tiles_run < plan.tiles_total
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), band, rectangle):
        assert a.dtype == b.dtype and np.array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)), name


MASKS = {"causal": True, "earlier_windows": EarlierWindows(48, 6), "window_24": SlidingWindow(24),
         "window_100": SlidingWindow(100)}


@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("t_q, t_k, bq, bk", [(96, 96, 32, 32), (200, 200, 64, 32),
                                              (160, 20, 32, 8), (130, 130, 16, 64)])
def test_a_tile_is_live_iff_the_dense_mask_keeps_one_of_its_entries(mask, t_q, t_k, bq, bk):
    """_tile_live against dense_mask, the comparison _mask_scores makes entry
    by entry: the skip and the mask cannot part, for every mask kind."""
    causal = MASKS[mask]
    n_q, n_k = -(-t_q // bq), -(-t_k // bk)
    dense = np.zeros((n_q * bq, n_k * bk), bool)
    dense[:, :t_k] = np.asarray(fa.dense_mask(causal, n_q * bq, t_k))
    want = dense.reshape(n_q, bq, n_k, bk).any(axis=(1, 3))
    k_len = t_k if n_k * bk != t_k else None
    assert np.array_equal(fa._live_tiles(n_q, n_k, bq, bk, causal, k_len), want)
    # and mask_fill counts the kept entries of the real rows over the live tiles'
    plan = fa.plan_flash(t_q, t_k, 32, jnp.float32, causal, bq, bk)
    kept = int(np.asarray(fa.dense_mask(causal, t_q, t_k)).sum())
    assert fa.mask_fill(plan, t_q, t_k, causal) == pytest.approx(
        kept / (plan.tiles_run * plan.block_q * plan.block_k))


def test_the_plan_of_a_band_holds_the_bands_tiles_only():
    """T 8,192 under a window of 512 at heads of 128: 512-wide tiles, the 16
    on the diagonal and the 15 below it of the rectangle's 256, each but the
    first half full; the causal plan at the same shape is the parent's."""
    band = fa.plan_flash(8192, 8192, 128, jnp.bfloat16, SlidingWindow(512))
    assert (band.block_q, band.block_k, band.grid_steps, band.tiles_run, band.tiles_total,
            band.bwd) == (512, 512, 31, 31, 256, "fused")
    assert fa.mask_fill(band, 8192, 8192, SlidingWindow(512)) == pytest.approx(0.5, abs=1e-3)
    full = fa.plan_flash(8192, 8192, 128, jnp.bfloat16, True)
    assert (full.block_q, full.grid_steps, full.tiles_run) == (512, 136, 136)
    assert fa.mask_fill(full, 8192, 8192, True) == pytest.approx(0.9413, abs=1e-4)
    assert fa.mask_fill(fa.plan_flash(1024, 1024, 64, jnp.bfloat16, False), 1024, 1024, False) == 1.0
    # a narrow band takes narrower tiles (fewer wasted entries outweigh more
    # steps), a wide one the square plan_flash takes anyway
    assert fa.plan_flash(8192, 8192, 128, jnp.bfloat16, SlidingWindow(128)).block_q == 256
    assert fa.plan_flash(8192, 8192, 128, jnp.bfloat16, SlidingWindow(2048)).block_q == 512
    # a requested block is obeyed, as for the other masks
    assert fa.plan_flash(8192, 8192, 128, jnp.bfloat16, SlidingWindow(512), 256, 256).tiles_run == 93
    assert [fa.mask_name(m) for m in (False, True, SlidingWindow(4), EarlierWindows(8, 2))] == [
        "none", "causal", "sliding_window", "earlier_windows"]
    walk = fa._walk(fa._live_tiles(16, 16, 512, 512, SlidingWindow(512), None), k_major=False)
    assert walk.steps == 31 and np.all(walk.qi - walk.ki <= 1) and np.all(walk.qi >= walk.ki)


# sha256 of the int32 tables of _walk (q-major then k-major: qi, ki, flags,
# in_block) at the accepted LM cells' attention calls, computed with the
# parent's ops/flash_attention.py (commit 8b57eae) before the third mask kind
PARENT_WALKS = {
    "gpt2m": ((1024, 1024, 64, 64, True),
              "c79a7e88c8f7a96566abe15e3da757cb26280e62062bb97e8c1741ae3d022f79"),
    "kanana_kimi": ((8192, 8192, 192, 128, True),
                    "760ee7faf7d6afc1a388e8d01966441cd578c9c2899c230f4387ed437e60c6ea"),
    "granite": ((8192, 8192, 64, 64, True),
                "760ee7faf7d6afc1a388e8d01966441cd578c9c2899c230f4387ed437e60c6ea"),
    "evabyte_local": ((2048, 2048, 128, 128, True),
                      "57ce108d3533c24ac93fc1f01f82f5827d43c971ce7edfde8ba72fcb9ad1e02b"),
    "evabyte_remote": ((16384, 1024, 128, 128, EarlierWindows(2048, 128)),
                       "7261b4826345c1ab117adea537eb2e8504c2c74cee1322246b6789e99c5a7b04"),
}


@pytest.mark.parametrize("cell", sorted(PARENT_WALKS))
def test_the_walks_of_causal_and_earlier_windows_are_the_parents(cell):
    (t_q, t_k, d, d_v, mask), want = PARENT_WALKS[cell]
    plan = fa.plan_flash(t_q, t_k, d, jnp.bfloat16, mask, d_v=d_v)
    live = fa._live_tiles(plan.tq_pad // plan.block_q, plan.tk_pad // plan.block_k,
                          plan.block_q, plan.block_k, mask, plan.k_len)
    digest = hashlib.sha256()
    for k_major in (False, True):
        for table in fa._walk(live, k_major):
            digest.update(np.asarray(table, np.int32).tobytes())
    assert digest.hexdigest() == want


def test_a_ring_hop_refuses_the_window_by_name():
    q = jnp.zeros((1, 64, 16))
    with pytest.raises(NotImplementedError, match="flash_partial: a SlidingWindow.*ROADMAP M5"):
        fa.flash_partial(q, q, q, 0.25, SlidingWindow(8), 0, 0)
    with pytest.raises(NotImplementedError, match="flash_grads_partial: a SlidingWindow"):
        fa.flash_grads_partial(q, q, q, q, q[..., 0], q[..., 0], 0.25, SlidingWindow(8), 0, 0)


def test_what_remat_names_differs_by_the_layers_heads():
    """plan_remat_saves over two kinds of layer that differ in head count:
    one {name: bytes} a kind, the residuals of both always."""
    kinds = [fa.flash_saves(1, 8192, 72, 128, 128, jnp.bfloat16, SlidingWindow(512), 3),
             fa.flash_saves(1, 8192, 48, 128, 128, jnp.bfloat16, True, 2)]
    plan = fa.plan_remat_saves(kinds, 811_018_240, fa.V5E_BYTES_LIMIT)
    assert not plan.operands_kept and plan.names == fa.FLASH_SAVED * 2
    o = lambda heads: heads * 8192 * 128 * 2
    assert [kind["ps_flash_o"] for kind in plan.kept] == [o(72), o(48)]
    assert plan.saved_bytes == 3 * (o(72) + 72 * 8192 * 4) + 2 * (o(48) + 48 * 8192 * 4)
    roomy = fa.plan_remat_saves(kinds, 100_000_000, fa.V5E_BYTES_LIMIT)
    assert roomy.operands_kept and set(roomy.kept[0]) == set(fa.FLASH_SAVED + fa.FLASH_OPERANDS)
