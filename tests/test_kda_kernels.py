"""ops/kda.py's Pallas kernels `ps_kda_*` by themselves, under the
interpreter: the body that solves a chunk's system against the XLA inverse
and the identity, chunks of 16 beside values of 256 and chunks of 128
against the recurrence, and the heads the kernels do not take. The chunked
rule against its definition, by both forms, is tests/test_kda.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.ops import kda

from .test_kda import NAMES, PATHS


def _inverse_by_the_kernels_body(a):
    """`kda._inverse_body` (what `ps_kda_inverse` solves a chunk's system
    with) on a [N, C, C] under the Pallas interpreter."""
    from jax.experimental import pallas as pl

    def kernel(a_ref, t_ref):
        _, ri, ci = kda._iotas(a_ref.shape[1])
        for i in range(a_ref.shape[0]):
            t_ref[i] = kda._inverse_body(a_ref[i], ri, ci, kda._levels(a_ref.shape[1]))

    return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
                          interpret=True)(a)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("c", [2, 8, 64])
def test_the_unit_lower_inverse_and_its_backward(c, path):
    """The kernels' inverse is the XLA one's products in the same order; its
    backward lives inside `ps_kda_within_bwd` and is held by the gradient
    tests of tests/test_kda.py."""
    a = jnp.tril(jax.random.normal(jax.random.key(c), (3, c, c)), -1)
    t = kda.unit_lower_inverse(a) if path == "xla" else _inverse_by_the_kernels_body(a)
    eye = jnp.eye(c)
    np.testing.assert_allclose(jnp.einsum("bij,bjk->bik", t, eye + a), jnp.broadcast_to(eye, a.shape),
                               atol=2e-4 * float(jnp.max(jnp.abs(t))))
    if path == "pallas":
        np.testing.assert_allclose(t, kda.unit_lower_inverse(a), rtol=1e-5,
                                   atol=1e-5 * float(jnp.max(jnp.abs(t))))
        return
    if c == 64:
        return  # a random 64 x 64 triangle is ill-conditioned: the identity above holds it
    probe = jax.random.normal(jax.random.key(c + 1), a.shape)
    plain = lambda a: jnp.sum(probe * jnp.linalg.inv(eye + jnp.tril(a, -1)))
    got = jax.grad(lambda a: jnp.sum(probe * kda.unit_lower_inverse(a)))(a)
    np.testing.assert_allclose(got, jnp.tril(jax.grad(plain)(a), -1), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("chunk, d_value", [(16, 256), (128, 128)])
def test_the_kernels_take_other_chunks_and_value_widths(chunk, d_value, monkeypatch):
    """Two chunks go through a kernel as one matrix of 2C rows, a ragged T is
    padded to pairs of chunks, and fewer pairs make a grid step where they are
    larger (`_pairs_a_step`): value and gradients hold at C = 16 beside values
    of 256 and at C = 128."""
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    assert kda.scan_path(chunk, 128, d_value) == "pallas_within+xla_scan"
    assert kda._pairs_a_step(chunk, 128, d_value) == (4 if chunk == 16 else 1)
    assert kda.padded_len(100, chunk, 128, d_value) == (128 if chunk == 16 else 256)
    ks = jax.random.split(jax.random.key(11), 5)
    q = kda.l2_normalize(jax.random.normal(ks[0], (1, 100, 1, 128)), 128 ** -0.5)
    k = kda.l2_normalize(jax.random.normal(ks[1], (1, 100, 1, 128)))
    v = jax.random.normal(ks[2], (1, 100, 1, d_value))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (1, 100, 1, 128))) * jnp.where(
        jnp.arange(128) % 4 == 0, 3.0, 0.01)
    args = (q, k, v, g, jax.nn.sigmoid(jax.random.normal(ks[4], (1, 100, 1))))
    loss = lambda fn: (lambda *a: jnp.sum(jnp.sin(fn(*a))))
    want, wants = jax.value_and_grad(loss(kda.kda_recurrence), argnums=range(5))(*args)
    got, grads = jax.value_and_grad(
        loss(lambda *a: kda.kda_chunked(*a, chunk)[0]), argnums=range(5))(*args)
    assert abs(float(got - want)) <= 2e-5 * abs(float(want))
    for name, a, r in zip(NAMES, grads, wants):
        assert float(jnp.max(jnp.abs(a - r))) <= 2e-5 * float(jnp.max(jnp.abs(r))), name


def test_a_head_the_kernels_do_not_take_goes_to_the_xla_form(monkeypatch):
    """K = 64 is no whole (8, 128) tile: under the interpreter too the
    chunk's own part is the XLA twin, and says so in the traced program."""
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    assert kda.scan_path(64, 128, 128) == "pallas_within+xla_scan"
    assert kda.scan_path(64, 64, 128) == kda.scan_path(64, 128, 64) == kda.scan_path(4, 128, 128) == "xla"
    assert kda.scan_path(256, 128, 128) == "xla"        # a pair of chunks' squares outgrow VMEM
    ks = jax.random.split(jax.random.key(9), 5)
    q, k, v, g = (jax.random.normal(ks[i], (1, 128, 2, 64)) for i in range(4))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, 128, 2)))
    args = (kda.l2_normalize(q), kda.l2_normalize(k), v, -jax.nn.softplus(g), beta)
    text = jax.jit(kda.kda_chunked, static_argnums=5).lower(*args, 64).as_text(debug_info=True)
    assert "ps_kda_within_jnp" in text and "ps_kda_within_fwd" not in text
    np.testing.assert_allclose(kda.kda_chunked(*args, 64)[0], kda.kda_recurrence(*args), atol=2e-5)
