"""The flash kernels with a value width that differs from the query/key
width (latent attention: 192-wide q/k beside 128-wide v at the published
sizes), against full_attention, in the Pallas interpreter on the CPU:
forward, dq, dk, dv, the ring's partial triples, odd lengths. And the tile
plan, which must not move for the callers whose widths are equal."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ps_pytorch_tpu.ops.flash_attention import (
    _vmem_bytes,
    flash_attention,
    flash_grads_partial,
    flash_partial,
    plan_flash,
)
from ps_pytorch_tpu.parallel.ring_attention import (
    SEQ_AXIS,
    full_attention,
    make_seq_mesh,
    ring_attention,
    ring_flash_attention,
)

B, H, D, DV = 2, 2, 24, 16


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")


def _qkv(t, seed=0, dtype=np.float32, d=D, dv=DV):
    rng = np.random.RandomState(seed)
    mk = lambda w: jnp.asarray(rng.randn(B, t, H, w).astype(dtype))
    return mk(d), mk(d), mk(dv)


@pytest.mark.parametrize("t", [128, 200, 13], ids=["even", "odd", "tiny"])
@pytest.mark.parametrize("causal", [False, True], ids=["bidir", "causal"])
def test_forward_matches_full(t, causal):
    q, k, v = _qkv(t)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    want = full_attention(q, k, v, causal=causal)
    assert got.shape == (B, t, H, DV)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("t", [128, 200], ids=["even", "odd"])
@pytest.mark.parametrize("causal", [False, True], ids=["bidir", "causal"])
def test_dq_dk_dv_match_full(t, causal, flash_bwd):
    q, k, v = _qkv(t, seed=1)
    w = jnp.asarray(np.random.RandomState(2).randn(B, t, H, DV).astype(np.float32))
    loss = lambda f: (lambda q, k, v: jnp.sum(f(q, k, v) * w))
    flash = lambda q, k, v: flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    full = lambda q, k, v: full_attention(q, k, v, causal=causal)
    got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(full), (0, 1, 2))(q, k, v)
    for g, r, name, width in zip(got, want, ("dq", "dk", "dv"), (D, D, DV)):
        assert g.shape[-1] == width, name
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-5, err_msg=name)


def test_the_published_widths_in_bfloat16():
    """192-wide q/k beside 128-wide v, bfloat16, causal, planned tiles."""
    q, k, v = _qkv(256, seed=3, d=192, dv=128)
    cast = lambda x: x.astype(jnp.bfloat16)
    got = flash_attention(cast(q), cast(k), cast(v), causal=True)
    want = full_attention(q, k, v, causal=True)
    assert got.dtype == jnp.bfloat16 and got.shape == (B, 256, H, 128)
    np.testing.assert_allclose(got.astype(np.float32), want, atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("bwd", ["fused", "split"])
@pytest.mark.parametrize("d, dv", [(192, 128), (64, 64)], ids=["mla", "gpt2"])
def test_gradients_at_the_published_widths_in_bfloat16(d, dv, bwd, monkeypatch):
    """The cells' head widths, bfloat16 operands, causal, 128-wide tiles
    asked for so that T 256 has a skipped tile and two k sweeps of the dq
    accumulator: all three gradients against the float32 oracle on the
    same rounded inputs, and split against fused to the last bit (the
    same sums in the same order)."""
    from ps_pytorch_tpu.ops import flash_attention as fa

    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(256, seed=8, d=d, dv=dv))
    w = jnp.asarray(np.random.RandomState(9).randn(B, 256, H, dv), jnp.bfloat16)
    f32 = lambda x: x.astype(jnp.float32)

    def grads(attend, *xs, **kw):
        loss = lambda q, k, v: jnp.sum(f32(attend(q, k, v, causal=True, **kw) * w))
        return jax.grad(loss, (0, 1, 2))(*xs)

    got = fused = grads(flash_attention, q, k, v, block_q=128, block_k=128)
    if bwd == "split":
        monkeypatch.setattr(fa, "FUSED_BWD_CAP", 0)
        got = grads(flash_attention, q, k, v, block_q=128, block_k=128)
    want = grads(full_attention, f32(q), f32(k), f32(v))
    for g, f, r, name in zip(got, fused, want, ("dq", "dk", "dv")):
        assert g.dtype == jnp.bfloat16
        np.testing.assert_array_equal(f32(g), f32(f), err_msg=name)
        scale = max(1.0, float(jnp.max(jnp.abs(r))))
        assert float(jnp.max(jnp.abs(f32(g) - r))) / scale < 2e-2, name


@pytest.mark.parametrize("tk", [64, 50], ids=["even", "odd"])
def test_partial_triples_and_their_gradients(tk, flash_bwd):
    """One ring hop: queries against a visiting shard of another length."""
    tq, scale = 64, D ** -0.5
    rng = np.random.RandomState(4)
    mk = lambda t, w: jnp.asarray(rng.randn(B * H, t, w).astype(np.float32))
    q3, k3, v3, do3 = mk(tq, D), mk(tk, D), mk(tk, DV), mk(tq, DV)
    pv, m, l = flash_partial(q3, k3, v3, scale, False, 0, 0)
    assert pv.shape == (B * H, tq, DV) and pv.dtype == jnp.float32
    s = jnp.einsum("bqd,bkd->bqk", q3, k3) * scale
    np.testing.assert_allclose(pv / l[..., None], jax.nn.softmax(s, -1) @ v3, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(m + jnp.log(l), jax.nn.logsumexp(s, -1), atol=2e-5, rtol=2e-5)
    o = pv / l[..., None]
    lse, delta = m + jnp.log(l), jnp.sum(do3 * o, -1)
    dq, dk, dv = flash_grads_partial(q3, k3, v3, do3, lse, delta, scale, False, 0, 0)
    ref = jax.grad(lambda q, k, v: jnp.sum(
        (jax.nn.softmax(jnp.einsum("bqd,bkd->bqk", q, k) * scale, -1) @ v) * do3), (0, 1, 2))
    for g, r, shape in zip((dq, dk, dv), ref(q3, k3, v3),
                           ((tq, D), (tk, D), (tk, DV))):
        assert g.shape[1:] == shape
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("impl, bwd", [("flash", "fused"), ("flash", "split"), ("naive", None)])
def test_ring_of_four_matches_full_in_value_and_gradient(impl, bwd, monkeypatch):
    if bwd == "split":
        from ps_pytorch_tpu.ops import flash_attention as fa
        monkeypatch.setattr(fa, "FUSED_BWD_CAP", 0)
    mesh = make_seq_mesh(4)
    q, k, v = _qkv(64, seed=5)
    w = jnp.asarray(np.random.RandomState(6).randn(B, 64, H, DV).astype(np.float32))
    ring = ring_flash_attention if impl == "flash" else ring_attention
    mapped = jax.shard_map(
        lambda q, k, v: ring(q, k, v, axis_name=SEQ_AXIS, causal=True), mesh=mesh,
        in_specs=(P(None, SEQ_AXIS),) * 3, out_specs=P(None, SEQ_AXIS), check_vma=False)
    got, grads = jax.value_and_grad(
        lambda q, k, v: jnp.sum(mapped(q, k, v) * w), (0, 1, 2))(q, k, v)
    want, ref = jax.value_and_grad(
        lambda q, k, v: jnp.sum(full_attention(q, k, v, causal=True) * w), (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, r in zip(grads, ref):
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("t, d, dtype, causal", [
    (1024, 64, jnp.bfloat16, True), (1024, 64, jnp.float32, True), (1000, 64, jnp.bfloat16, False),
    (8192, 128, jnp.bfloat16, True), (520, 32, jnp.float32, True), (96, 64, jnp.bfloat16, True)])
def test_plan_is_unchanged_where_the_widths_are_equal(t, d, dtype, causal):
    assert plan_flash(t, t, d, dtype, causal) == plan_flash(t, t, d, dtype, causal, d_v=d)
    assert _vmem_bytes(512, 512, d, 2) == _vmem_bytes(512, 512, d, 2, d_v=d)


def test_plan_at_the_published_widths():
    """PR 25's plan for its cell stays (512 x 512 at T 1024, D 64: three of
    four tiles), and MLA at T 8192 takes 512 x 512 tiles, 136 of 256 run."""
    small = plan_flash(1024, 1024, 64, jnp.bfloat16, True)
    assert (small.block_q, small.block_k, small.tiles_run, small.tiles_total) == (512, 512, 3, 4)
    tiles = 2 * (512 + 512) * 128 * 2 + 2 * 2 * 512 * 4 \
        + 3 * 512 * 128 * 4 + 4 * 512 * 512 * 4 + 2 * 512 * 512 * 2
    assert _vmem_bytes(512, 512, 64, 2) == tiles
    # the fused backward: the head's dq and its block, twice, beside them
    assert (small.bwd, small.dq_acc_bytes) == ("fused", 1024 * 64 * 4)
    assert small.vmem_bytes == tiles + 1024 * 64 * 4 + 2 * 512 * 64 * 4
    mla = plan_flash(8192, 8192, 192, jnp.bfloat16, True, d_v=128)
    assert (mla.block_q, mla.block_k, mla.tiles_run, mla.tiles_total) == (512, 512, 136, 256)
    assert (small.grid_steps, mla.grid_steps) == (3, 136)   # the grids walk the live tiles alone
    assert mla.k_len is None and _vmem_bytes(512, 512, 192, 2, 128) < 12 * 2 ** 20
    assert (mla.bwd, mla.dq_acc_bytes) == ("fused", 6 * 2 ** 20)
    assert mla.vmem_bytes == _vmem_bytes(512, 512, 192, 2, 128) + 6 * 2 ** 20 + 2 * 512 * 192 * 4
