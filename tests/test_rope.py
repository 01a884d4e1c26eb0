"""ops/rope.py: the kernel `ps_rope` under the Pallas interpreter against the
plain rotation it replaces (models/swa_moe._rope_leading), value and
gradient, plain and YaRN, whole and half heads, over tiles that a T does and
does not fill; which form a head's width takes; the tiles. About a minute on
one worker."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.models.swa_moe import Rope, _rope_leading
from ps_pytorch_tpu.ops import rope as rp

f32, bf16 = jnp.float32, jnp.bfloat16
D = 128

ROPES = {
    "default": dict(rope_theta=500000.0),
    # the laguna cell's global layers: a blend of the angles, cos and sin times 1.35
    "yarn": dict(rope_type="yarn", rope_theta=500000.0, factor=32.0,
                 original_max_position_embeddings=4096, attention_factor=1.35),
}


def _rotate(x, pos, rope):
    """The entry as models/swa_moe.gqa_attention calls it, for one x."""
    return rp.rotate_leading((x,), pos, *rope.frequencies(x.shape[-1]),
                             twin=lambda a: _rope_leading(a, pos, rope))[0]


@pytest.fixture()
def kernels(monkeypatch):
    """The interpreter, and time tiles of 64 rows so that a T of a hundred
    odd crosses several of them."""
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("PS_TPU_DISABLE_PALLAS", raising=False)
    monkeypatch.setattr(rp, "BLOCK_T", 64)


def _close(got, want, dtype):
    """float32 to 1e-6 of the largest value; a bfloat16 result to one unit
    in its last place (both forms round float32 arithmetic once)."""
    assert got.dtype == want.dtype == dtype and got.shape == want.shape
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == bf16:
        np.testing.assert_allclose(got, want, rtol=2.0 ** -7, atol=1e-6)
    else:
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6 * max(1.0, float(np.max(np.abs(want)))))


@pytest.mark.parametrize("dtype", [f32, bf16], ids=["f32", "bf16"])
@pytest.mark.parametrize("b", [1, 2])
@pytest.mark.parametrize("t", [128, 136], ids=["whole_tiles", "ragged"])
@pytest.mark.parametrize("heads", [4, 9])
@pytest.mark.parametrize("part", [1.0, 0.5], ids=["whole_head", "half_head"])
@pytest.mark.parametrize("kind", sorted(ROPES))
def test_the_kernel_holds_the_plain_rotation_and_its_gradient(kernels, kind, part, heads, t, b, dtype):
    """T 128 is two tiles of 64, T 136 a third that is ragged; nine heads
    are one block of channels, four another; the positions start at 1,000
    (a sequence shard's). The lanes past r pass through to the bit, and the
    gradient is the plain form's `jax.grad`."""
    rope = Rope(partial_rotary_factor=part, **ROPES[kind])
    r = int(D * part)
    kx, kd = jax.random.split(jax.random.key(heads + t + b))
    x = jax.random.normal(kx, (b, t, heads, D), f32).astype(dtype)
    dy = jax.random.normal(kd, (b, t, heads, D), f32).astype(dtype)
    pos = jnp.arange(t) + 1000
    assert rp.rope_path(D, r) == "pallas" and rp.plan_rope(t, heads * D, dtype).block_t == 64
    got, vjp = jax.vjp(lambda a: _rotate(a, pos, rope), x)
    want, vjp_plain = jax.vjp(lambda a: _rope_leading(a, pos, rope), x)
    _close(got, want, dtype)
    np.testing.assert_array_equal(np.asarray(got[..., r:], np.float32), np.asarray(x[..., r:], np.float32))
    assert float(jnp.max(jnp.abs(got[..., :r].astype(f32) - x[..., :r].astype(f32)))) > 0.1
    _close(vjp(dy)[0], vjp_plain(dy)[0], dtype)


@pytest.mark.parametrize("part", [1.0, 0.5], ids=["whole_head", "half_head"])
def test_the_backward_of_the_backward_is_the_forward(kernels, part):
    """The op is linear in x and keeps nothing of it: its transpose is the
    same kernel at the sines of the opposite rotation, and the transpose of
    that is the kernel at the forward's sines again, to the bit."""
    rope = Rope(partial_rotary_factor=part, **ROPES["yarn"])
    x = jax.random.normal(jax.random.key(5), (2, 72, 4, D), f32)
    pos = jnp.arange(72)
    fwd = lambda a: _rotate(a, pos, rope)
    y, vjp = jax.vjp(fwd, x)
    back = lambda g: vjp(g)[0]
    again, vjp2 = jax.vjp(back, y)
    np.testing.assert_array_equal(vjp2(x)[0], y)
    # a rotation's transpose undoes it, up to the squared scale
    np.testing.assert_allclose(again[..., :int(D * part)], 1.35 ** 2 * x[..., :int(D * part)],
                               rtol=0, atol=2e-5)
    np.testing.assert_allclose(again[..., int(D * part):], x[..., int(D * part):], rtol=0, atol=0)
    # grad of a nonlinear loss through it, against the plain form
    loss = lambda f: (lambda a: jnp.sum(jnp.tanh(f(a)) ** 2))
    np.testing.assert_allclose(jax.grad(loss(fwd))(x),
                               jax.grad(loss(lambda a: _rope_leading(a, pos, rope)))(x),
                               rtol=0, atol=2e-6)


def test_q_and_k_share_one_pair_of_tables(kernels):
    """One call rotates q and k: two `ps_rope` calls in the traced program
    and ONE cos and ONE sin (the tables are made once)."""
    rope = Rope(**ROPES["default"])
    q, k = jnp.ones((1, 64, 6, D), bf16), jnp.ones((1, 64, 2, D), bf16)
    pos = jnp.arange(64)
    text = str(jax.make_jaxpr(lambda q, k: rp.rotate_leading(
        (q, k), pos, *rope.frequencies(D), twin=None))(q, k))
    assert text.count(" cos ") == 1 and text.count(" sin ") == 1
    assert text.count("name=ps_rope") == 2


@pytest.mark.parametrize("head_dim, r, interpret, path", [
    (128, 128, True, "pallas"), (128, 64, True, "pallas"), (128, 2, True, "pallas"),
    (64, 64, True, "xla"), (256, 128, True, "xla"),     # a head is not one 128-lane tile
    (128, 0, True, "xla"),                              # nothing turns
    (128, 128, False, "xla"),                           # the CPU without the interpreter
])
def test_rope_path_says_which_form_a_heads_width_takes(monkeypatch, head_dim, r, interpret, path):
    monkeypatch.delenv("PS_TPU_DISABLE_PALLAS", raising=False)
    if interpret:
        monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PS_TPU_PALLAS_INTERPRET", raising=False)
    assert rp.rope_path(head_dim, r) == path


def test_disabling_pallas_takes_the_plain_form(monkeypatch):
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("PS_TPU_DISABLE_PALLAS", "1")
    assert rp.rope_path(128, 128) == "xla"


@pytest.mark.parametrize("head_dim, interpret", [(64, True), (128, False)])
def test_the_plain_form_is_the_call_sites_old_expression_to_the_bit(monkeypatch, head_dim, interpret):
    """Off the chip (and at a head the kernel does not take) the entry IS
    `_rope_leading` under `ps_rope_jnp`: what every CPU test and small
    config computed before, and nothing else is traced (no table)."""
    from ps_pytorch_tpu.ops.pallas_mode import kernel_census

    if interpret:
        monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PS_TPU_PALLAS_INTERPRET", raising=False)
    rope = Rope(partial_rotary_factor=0.5, **ROPES["yarn"])
    x = jax.random.normal(jax.random.key(11), (2, 40, 3, head_dim), f32).astype(bf16)
    pos = jnp.arange(40) + 7

    @jax.jit
    def entry(a):
        return _rotate(a, pos, rope)

    @jax.jit
    def plain(a):
        return _rope_leading(a, pos, rope)

    np.testing.assert_array_equal(np.asarray(entry(x), np.float32), np.asarray(plain(x), np.float32))
    assert str(jax.make_jaxpr(entry)(x)).count(" cos ") == str(jax.make_jaxpr(plain)(x)).count(" cos ") == 1
    census = kernel_census(entry.lower(x).compile().as_text())
    assert census["mosaic"] == {} and set(census["jnp"]) == {"ps_rope"}


@pytest.mark.parametrize("t, width, dtype", [
    (8192, 9216, bf16),      # the laguna cell: a sliding layer's q, 72 heads
    (8192, 6144, bf16),      # its global layers' q, 48 heads
    (8192, 1024, bf16),      # k, 8 heads
    (16384, 3584, bf16),     # the smallthinker cell's q, 28 heads
    (40, 384, f32), (40, 128, bf16),
])
def test_the_tiles_are_whole_heads_that_divide_the_width_inside_the_vmem_limit(t, width, dtype):
    plan = rp.plan_rope(t, width, dtype)
    assert width % plan.block_c == 0 and plan.block_c % 128 == 0 and plan.block_c <= rp.BLOCK_C
    assert plan.block_t % plan.rows == 0 and plan.block_t <= max(rp.BLOCK_T, plan.rows)
    assert plan.block_t - plan.rows < t               # at most one turn of the loop past a short T
    assert plan.vmem_bytes(dtype) <= rp.VMEM_LIMIT // 2 and rp.VMEM_LIMIT <= 64 << 20


@pytest.mark.parametrize("cell", ["laguna_train_b1s8192_ep32share", "smallthinker_train_b1s16384_ep4share"])
def test_rope_ms_reads_the_kernel_by_name(cell):
    """The benchmark's `rope_ms` (benchmark/layer_metrics/rope_ms.json, data
    alone) is the device time a step of the ops named `ps_rope*`, as XLA
    spells the Mosaic call forward and (transposed) backward; in a trace
    without them the metric is left out of the line, not read as 0."""
    from benchmark import reducers, spec

    metric, = [m for m in spec.load_cell(cell).per_layer if m["name"] == "rope_ms"]
    assert (metric["kind"], metric["source"], metric["moves"], metric["layer"]) == (
        "scope_time", "device_trace", "train_tokens_per_s", "Kernels")
    fwd, bwd = "ps_rope.7_bf16_1_8192_9216", "transpose_jvp_ps_rope__.3_bf16_1_8192_9216"
    step = lambda t: [[fwd, t, 0.0005], ["fusion.12_f32_8192_9216", t + 0.0005, 0.001],
                      [fwd, t + 0.0015, 0.0005], [bwd, t + 0.002, 0.0005]]
    ev = {"trace": {"devices": {"/device:TPU:0": step(0.0) + step(0.01)}, "host": []},
          "steps_traced": 2, "cell": None, "peaks": {}}
    assert reducers.reduce(metric["kind"], metric["args"], ev) == pytest.approx(1.5)
    ev["trace"] = {"devices": {"/device:TPU:0": [["fusion.12_f32_8192_9216", 0.0, 0.001]]}, "host": []}
    assert reducers.reduce(metric["kind"], metric["args"], ev) is None
