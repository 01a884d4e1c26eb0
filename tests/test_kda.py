"""ops/kda.py: the chunked gated delta rule (one decay a key channel)
against its definition token by token, in value and in all five gradients,
at three kinds of decay: the source's (A in [1, 16], dt in [1e-3, 1e-1]: the
state crosses chunks), the benchmark's (g about -0.69 a token and channel:
the state halves every token) and a mix whose harshest channels pass -88 a
chunk, where exp(G_i) * exp(-G_j) would overflow float32. Small sizes (3
heads of 16 keys and 8 values, chunks of 16 over T 150: nine whole chunks
and a ragged tail), float32 and bfloat16, on the CPU. Every such test runs
by both forms of the chunk's own part (`path`): the XLA one at those sizes,
and the Pallas kernels `ps_kda_*` under the interpreter at sizes they take
(2 heads of 128 keys and values, chunks of 64 over T 150: two whole chunks
and a ragged tail), same tolerances. What holds the kernels by themselves
(the inverse's body, other chunks and value widths, a head they do not take)
is in tests/test_kda_kernels.py, a file of its own for `--dist loadfile`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.ops import kda

B, T, H, K, V, CHUNK = 2, 150, 3, 16, 8, 16
NAMES = ("q", "k", "v", "g", "beta")
PATHS = ("xla", "pallas")


@pytest.fixture
def sized(request, monkeypatch):
    """The sizes `path` runs at, as this module's globals; "pallas" turns the
    interpreter on, and the kernels must then take these sizes."""
    path = request.param
    monkeypatch.delenv("PS_TPU_DISABLE_PALLAS", raising=False)
    if path == "pallas":
        monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
        for name, value in dict(B=1, H=2, K=128, V=128, CHUNK=64).items():
            monkeypatch.setitem(globals(), name, value)
        assert kda.scan_path(CHUNK, K, V) == "pallas_within+xla_scan"
    else:
        monkeypatch.delenv("PS_TPU_PALLAS_INTERPRET", raising=False)
        assert kda.scan_path(CHUNK, K, V) == "xla"
    return path


both_paths = pytest.mark.parametrize("sized", PATHS, indirect=True)


def _inputs(decays, seed=1, t=None):
    t = T if t is None else t
    ks = jax.random.split(jax.random.key(seed), 8)
    q = kda.l2_normalize(jax.random.normal(ks[0], (B, t, H, K)), K ** -0.5)
    k = kda.l2_normalize(jax.random.normal(ks[1], (B, t, H, K)))
    v = jax.random.normal(ks[2], (B, t, H, V))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (B, t, H)))
    x = jax.random.normal(ks[4], (B, t, H, K))
    if decays == "source":
        a = jax.random.uniform(ks[5], (H, 1), minval=1.0, maxval=16.0)
        dt = jnp.exp(jax.random.uniform(ks[6], (H, K), minval=np.log(1e-3), maxval=np.log(1e-1)))
        g = -a * jax.nn.softplus(0.1 * x + dt + jnp.log(-jnp.expm1(-dt)))
    elif decays == "benchmark":          # A_log = 0, dt_bias = 0: minus softplus of a small number
        g = -jax.nn.softplus(0.05 * x)
    else:                                # every fourth channel loses 8 a token: 128 a chunk of 16
        #                                  (2 a token: 132 a chunk of 64)
        g = -jax.nn.softplus(0.05 * x) * jnp.where(jnp.arange(K) % 4 == 0, 12.0 * 16 / CHUNK, 0.01)
    return q, k, v, g, beta


def _chunked(dtype=jnp.float32, **kw):
    def f(q, k, v, g, beta):
        return kda.kda_chunked(q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta,
                               CHUNK, **kw)[0]
    return f


def _value_and_grads(fn, args):
    probe = jnp.cos(jnp.arange(B * args[0].shape[1] * H * V, dtype=jnp.float32)).reshape(
        B, -1, H, V)
    return jax.value_and_grad(lambda *a: jnp.sum(probe * fn(*a)), argnums=range(5))(*args)


@both_paths
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("decays", ["source", "benchmark", "past_minus_88_a_chunk"])
def test_chunked_is_the_recurrence_in_value_and_in_all_five_gradients(decays, dtype, tol, sized):
    """bfloat16 has 8 bits of mantissa and an output is behind four products
    of them: 3% of each array's range."""
    args = _inputs(decays)
    if decays == "past_minus_88_a_chunk":
        assert float(jnp.min(jnp.sum(args[3][:, :CHUNK], axis=1))) < -88.0
    want = kda.kda_recurrence(*args)
    got = _chunked(dtype)(*args)
    assert got.dtype == jnp.float32 and bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got - want))) <= tol * float(jnp.max(jnp.abs(want)))
    _, grads = _value_and_grads(_chunked(dtype), args)
    _, wants = _value_and_grads(kda.kda_recurrence, args)
    for name, g, r in zip(NAMES, grads, wants):
        assert np.any(np.asarray(r)), name
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert float(jnp.max(jnp.abs(g - r))) <= tol * float(jnp.max(jnp.abs(r))), name


def test_a_ragged_tail_is_padded_and_cut_off():
    args = _inputs("source", seed=2, t=37)
    want = kda.kda_recurrence(*args)
    got = _chunked()(*args)
    assert got.shape == want.shape == (B, 37, H, V)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.max(jnp.abs(want))))


def test_a_zeroed_carried_state_fails_at_source_decays_and_hides_at_the_benchmarks():
    """The state really crosses chunks where decays are the source's; at the
    benchmark's weights it halves every token, and a rule that forgets it is
    wrong in a chunk's first tokens only (PERF.md section 7)."""
    source, bench = _inputs("source", t=48), _inputs("benchmark", t=48)
    broken = _chunked(zero_carried=True)
    gap = lambda a: float(jnp.max(jnp.abs(broken(*a) - kda.kda_recurrence(*a)))
                          / jnp.max(jnp.abs(kda.kda_recurrence(*a))))
    assert gap(source) > 0.1
    np.testing.assert_allclose(_chunked()(*source), kda.kda_recurrence(*source), atol=1e-5)
    off = np.asarray(jnp.abs(broken(*bench) - kda.kda_recurrence(*bench)))[:, CHUNK:]
    late = off.reshape(B, -1, CHUNK, H, V)
    assert late[:, :, 12:].max() < 0.02 * late[:, :, :2].max()


def test_beta_zero_writes_nothing_and_orthogonal_keys_reduce_to_a_decayed_sum():
    q, k, v, g, beta = _inputs("source", seed=3, t=K)
    assert not np.any(np.asarray(_chunked()(q, k, v, g, jnp.zeros_like(beta))))
    # keys that never meet (one-hot, each channel once): S'^T k_t = 0, no
    # correction, so o_t = sum_(j<=t) beta_j (q_t o exp(G_t - G_j)) . k_j v_j
    k = jnp.broadcast_to(jnp.eye(K)[None, :, None, :], (B, K, H, K))
    cum = jnp.cumsum(g, axis=1)
    decay = jnp.exp(cum[:, :, None] - cum[:, None, :])                     # [B, i, j, H, K]
    scores = jnp.einsum("bihd,bijhd,bjhd->bijh", q, decay, k) * jnp.tril(jnp.ones((K, K)))[None, :, :, None]
    want = jnp.einsum("bijh,bjh,bjhv->bihv", scores, beta, v)
    np.testing.assert_allclose(_chunked()(q, k, v, g, beta), want, atol=1e-5)


def test_no_decay_and_beta_one_is_the_plain_delta_rule():
    q, k, v, _, _ = _inputs("source", seed=4, t=40)

    def turn(s, inp):                      # S_t = S_(t-1) + k_t (v_t - S_(t-1)^T k_t)^T
        q_t, k_t, v_t = inp
        s = s + k_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    _, want = jax.lax.scan(turn, jnp.zeros((B, H, K, V)),
                           tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v)))
    got = _chunked()(q, k, v, jnp.zeros((B, 40, H, K)), jnp.ones((B, 40, H)))
    np.testing.assert_allclose(got, jnp.moveaxis(want, 0, 1), atol=1e-5)


def test_the_counter_counts_chunks_whose_slowest_channel_is_under_2_to_the_minus_24():
    q, k, v, g, beta = _inputs("benchmark", t=48)
    # -2 a token: 32 a chunk of 16, under 24 ln 2 = 16.6, in every channel ...
    heavy = jnp.full_like(g, -2.0)
    _, cut = kda.kda_chunked(q, k, v, heavy, beta, CHUNK)
    assert int(cut) == B * 3 * H
    # ... but one slow channel keeps a chunk's state alive
    _, cut = kda.kda_chunked(q, k, v, heavy.at[0, 16:32, 1, 5].set(-0.01), beta, CHUNK)
    assert int(cut) == B * 3 * H - 1
    _, none = kda.kda_chunked(q, k, v, 0.1 * g, beta, CHUNK)
    assert int(none) == 0


@both_paths
def test_correlated_keys_do_not_lose_the_inverse(sized):
    """Neighbouring keys all but equal, beta near 1, hardly any decay: A is
    all but the strict triangle of ones, whose powers reach 1e17 and cancel
    (a Neumann product in float32 is lost there); the inverse by halves holds
    the recurrence."""
    ks = jax.random.split(jax.random.key(7), 4)
    base = jax.random.normal(ks[0], (B, 1, H, K))
    k = kda.l2_normalize(base + 0.05 * jax.random.normal(ks[1], (B, 64, H, K)))
    q = kda.l2_normalize(jax.random.normal(ks[2], (B, 64, H, K)), K ** -0.5)
    v = jax.random.normal(ks[3], (B, 64, H, V))
    g, beta = jnp.full((B, 64, H, K), -1e-3), jnp.full((B, 64, H), 0.98)
    want = kda.kda_recurrence(q, k, v, g, beta)
    got, _ = kda.kda_chunked(q, k, v, g, beta, 64)
    np.testing.assert_allclose(got, want, atol=1e-4 * float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("sized", ["pallas"], indirect=True)
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-6), (jnp.bfloat16, 1.5e-2)],
                         ids=["float32", "bfloat16"])
def test_the_kernels_are_their_xla_twin_closer_than_either_is_to_the_recurrence(
        dtype, tol, sized, monkeypatch):
    """One algorithm in two forms: the same levels, masks, dtypes and
    precisions. float32 differs in the order of a few sums (a tenth of the
    2e-5 either is held to the recurrence by); in bfloat16 the backward's
    operands are rounded where the TPU rounds them, the twin's on the CPU
    are not."""
    args = _inputs("source", seed=5)
    kernels = _value_and_grads(_chunked(dtype), args)
    monkeypatch.setenv("PS_TPU_DISABLE_PALLAS", "1")
    assert kda.scan_path(CHUNK, K, V) == "xla"
    twin = _value_and_grads(_chunked(dtype), args)
    assert abs(float(kernels[0] - twin[0])) <= tol * abs(float(twin[0])) + tol
    for name, g, r in zip(NAMES, kernels[1], twin[1]):
        assert float(jnp.max(jnp.abs(g - r))) <= tol * float(jnp.max(jnp.abs(r))), name


def test_a_chunk_that_is_no_power_of_two_is_refused():
    args = _inputs("source", t=48)
    with pytest.raises(ValueError, match="chunk=24 is not a power of two"):
        kda.kda_chunked(*args, 24)
