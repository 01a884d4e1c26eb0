"""ops/causal_conv.py: the kernels `ps_causal_conv_fwd` / `_bwd` under the
Pallas interpreter against the plain conv they replace
(models/ssm_hybrid._causal_conv with its silu), value and all three
gradients, over tiles that a T does and does not fill; what stands before a
row's first token; which form a width takes. 68 s on one worker."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.models.ssm_hybrid import _causal_conv
from ps_pytorch_tpu.ops import causal_conv as cc

f32, bf16 = jnp.float32, jnp.bfloat16


def _plain(x, w, bias, out_dtype):
    """What both call sites computed before the kernels existed."""
    return jax.nn.silu(_causal_conv(x.astype(f32), w.astype(f32),
                                    0.0 if bias is None else bias.astype(f32))).astype(out_dtype)


def _operands(b, t, c, k, bias, dtype, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(keys[0], (b, t, c), f32).astype(dtype),
            0.5 * jax.random.normal(keys[1], (k, c), f32),
            jax.random.normal(keys[2], (c,), f32) if bias else None,
            jax.random.normal(keys[3], (b, t, c), f32))


@pytest.fixture()
def kernels(monkeypatch):
    """The interpreter, and time tiles of 64 rows so that a T of a few
    hundred crosses several of them."""
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(cc, "BLOCK_T", 64)


def _close(got, want, dtype):
    """float32 to 1e-6 of the largest value; a bfloat16 result to the
    rounding the plain form makes itself (one unit in its last place)."""
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(1.0, float(np.max(np.abs(want))))
    if dtype == bf16:
        np.testing.assert_allclose(got, want, rtol=2.0 ** -7, atol=2.0 ** -8 * scale * 1e-2)
    else:
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6 * scale)


@pytest.mark.parametrize("dtype", [f32, bf16], ids=["f32", "bf16"])
@pytest.mark.parametrize("b", [1, 2])
@pytest.mark.parametrize("t", [192, 200], ids=["whole_tiles", "ragged"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("k", [2, 4])
def test_the_kernels_hold_the_plain_conv_and_its_gradients(kernels, k, bias, t, b, dtype):
    """T 192 is three tiles of 64, T 200 a fourth that is ragged: the
    forward's carry and both of the backward's halos are crossed three
    times. x in `dtype`, the result too; dx comes back in x's dtype, dw and
    db in float32."""
    c = 256
    x, w, bv, dy = _operands(b, t, c, k, bias, dtype, seed=k + t + b)
    assert cc.conv_path(c, k) == "pallas" and cc.plan_conv(t, c, dtype).block_t == 64
    got, vjp = jax.vjp(lambda *a: cc.causal_conv_silu(*a, dtype, _causal_conv), x, w, bv)
    want, vjp_plain = jax.vjp(lambda *a: _plain(*a, dtype), x, w, bv)
    _close(got, want, dtype)
    (dx, dw, db), (dx_p, dw_p, db_p) = vjp(dy.astype(dtype)), vjp_plain(dy.astype(dtype))
    _close(dx, dx_p, dtype)
    _close(dw, dw_p, f32)
    assert (db is None and db_p is None) if not bias else _close(db, db_p, f32) is None


@pytest.mark.parametrize("out_dtype", [f32, bf16], ids=["f32_out", "bf16_out"])
def test_a_float32_input_is_rounded_once_where_the_caller_says(kernels, out_dtype):
    """The delta-rule branches hand the conv a float32 product and take the
    result in float32 (into the L2 norm) or in the compute dtype (v): the
    kernel reads float32 either way and dx is float32."""
    x, w, _, dy = _operands(2, 136, 128, 4, False, f32, seed=3)
    got, vjp = jax.vjp(lambda x, w: cc.causal_conv_silu(x, w, None, out_dtype, _causal_conv), x, w)
    want, vjp_plain = jax.vjp(lambda x, w: _plain(x, w, None, out_dtype), x, w)
    _close(got, want, out_dtype)
    for a, p in zip(vjp(dy.astype(out_dtype)), vjp_plain(dy.astype(out_dtype))):
        _close(a, p, f32)


def _plain_normed(x, w, heads, scale, out_dtype):
    """models/kda_hybrid._short_branch's q and k before the kernels existed."""
    from ps_pytorch_tpu.ops.kda import l2_normalize

    y = jax.nn.silu(_causal_conv(x.astype(f32), w.astype(f32), 0.0))
    return l2_normalize(y.reshape(y.shape[:2] + (heads, -1)), scale).reshape(x.shape).astype(out_dtype)


@pytest.mark.parametrize("out_dtype", [f32, bf16], ids=["f32_out", "bf16_out"])
@pytest.mark.parametrize("t, c, heads", [(200, 256, 2), (192, 512, 4), (72, 256, 4)],
                         ids=["heads_of_128_ragged", "heads_of_128", "heads_of_64"])
def test_the_l2_norm_a_head_is_ops_kda_l2_normalize_of_the_plain_conv(kernels, t, c, heads, out_dtype):
    """With `heads` the entry L2-normalises each head in float32 before its
    one rounding: inside the kernels where a head is one 128-lane tile
    (`ps_causal_conv_bwd` then takes the normalised result's gradient), after
    them where it is not. Either way it is l2_normalize of the plain conv,
    value and gradients."""
    x, w, _, dn = _operands(2, t, c, 4, False, f32, seed=t + heads)
    scale = 128 ** -0.5
    got, vjp = jax.vjp(lambda x, w: cc.causal_conv_silu(
        x, w, None, out_dtype, _causal_conv, heads=heads, head_scale=scale), x, w)
    want, vjp_plain = jax.vjp(lambda x, w: _plain_normed(x, w, heads, scale, out_dtype), x, w)
    _close(got, want, out_dtype)
    for a, p in zip(vjp(dn.astype(out_dtype)), vjp_plain(dn.astype(out_dtype))):
        np.testing.assert_allclose(a, p, rtol=0, atol=2e-6 * float(jnp.max(jnp.abs(p))))


@pytest.mark.parametrize("k", [2, 4])
def test_nothing_stands_before_a_rows_first_token_or_after_its_last(kernels, k):
    """Row 1's first K - 1 tokens see zeros, not row 0's tail (the carry is
    cleared at a row's first tile), and row 0's last tokens take no gradient
    from row 1's first: each row alone gives the same numbers, bit for bit."""
    x, w, bv, dy = _operands(2, 130, 128, k, True, f32, seed=7)
    x = x.at[0, -4:].set(1e3)
    dy = dy.at[1, :4].set(1e3)
    run = lambda x, dy: (lambda y, vjp: (y,) + vjp(dy))(
        *jax.vjp(lambda x, w, bv: cc.causal_conv_silu(x, w, bv, f32, _causal_conv), x, w, bv))
    y, dx, _, _ = run(x, dy)
    for row in (0, 1):
        y_alone, dx_alone, _, _ = run(x[row:row + 1], dy[row:row + 1])
        np.testing.assert_array_equal(y[row], y_alone[0])
        np.testing.assert_array_equal(dx[row], dx_alone[0])
    # and the plain form agrees on what the first tokens see
    np.testing.assert_allclose(y[1, :4], _plain(x, w, bv, f32)[1, :4], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("channels, taps, interpret, path", [
    (4096, 4, True, "pallas"), (4352, 4, True, "pallas"), (128, 2, True, "pallas"),
    (4000, 4, True, "xla"),       # no whole 128-lane tiles
    (4096, 10, True, "xla"),      # more taps than a register's sublanes hold behind a tile
    (4096, 4, False, "xla"),      # the CPU without the interpreter
])
def test_conv_path_says_which_form_a_width_takes(monkeypatch, channels, taps, interpret, path):
    monkeypatch.delenv("PS_TPU_DISABLE_PALLAS", raising=False)
    if interpret:
        monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PS_TPU_PALLAS_INTERPRET", raising=False)
    assert cc.conv_path(channels, taps) == path


def test_disabling_pallas_takes_the_plain_form(monkeypatch):
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("PS_TPU_DISABLE_PALLAS", "1")
    assert cc.conv_path(4096) == "xla"


@pytest.mark.parametrize("channels, bias", [(4000, True), (256, False)])
def test_the_plain_form_is_the_call_sites_old_expression_to_the_bit(monkeypatch, channels, bias):
    """Off the chip (and at a width the kernels do not take) the entry IS
    silu(_causal_conv(...)) rounded once, under `ps_causal_conv_jnp`: what
    every CPU test and small config computed before."""
    from ps_pytorch_tpu.ops.pallas_mode import kernel_census

    if channels % 128:
        monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PS_TPU_PALLAS_INTERPRET", raising=False)
    x, w, bv, _ = _operands(2, 40, channels, 4, bias, bf16, seed=11)
    assert cc.conv_path(channels) == "xla"
    @jax.jit
    def entry(x, w, bv):
        return cc.causal_conv_silu(x, w, bv, bf16, _causal_conv)

    @jax.jit
    def plain(x, w, bv):
        return _plain(x, w, bv, bf16)

    np.testing.assert_array_equal(np.asarray(entry(x, w, bv), np.float32),
                                  np.asarray(plain(x, w, bv), np.float32))
    census = kernel_census(entry.lower(x, w, bv).compile().as_text())
    assert census["mosaic"] == {} and set(census["jnp"]) == {"ps_causal_conv"}


@pytest.mark.parametrize("t, channels, dtype, want", [
    (8192, 4352, bf16, (2048, 256, 32, 16)),     # the granite cell: 34 lane tiles = 2 x 17
    (8192, 4096, f32, (2048, 512, 32, 8)),       # the kimi cell
    (40, 128, f32, (64, 128, 32, 8)),            # one ragged tile
    (40, 384, bf16, (64, 384, 32, 16)),
])
def test_the_tiles_are_a_function_of_the_shapes_alone(t, channels, dtype, want):
    plan = cc.plan_conv(t, channels, dtype)
    assert tuple(plan) == want
    assert channels % plan.block_c == 0 and plan.block_t % plan.rows == 0 and plan.rows % plan.halo == 0


@pytest.mark.parametrize("cell", ["granite4hm_train_remat_1period", "kimilinear_train_b2s8192_ep32share"])
def test_short_conv_ms_reads_the_kernels_by_name_and_nothing_in_a_trace_without_them(cell):
    """The benchmark's `short_conv_ms` (benchmark/layer_metrics/
    short_conv_ms.json, data alone) is the device time a step of the ops
    named `ps_causal_conv*`, as XLA spells the two Mosaic calls; the traces
    recorded on the chip before the kernels existed hold no such op, and
    the metric is then left out of the line, not read as 0."""
    import os

    from benchmark import reducers, spec
    from benchmark.reducers import trace as tr

    metric, = [m for m in spec.load_cell(cell).per_layer if m["name"] == "short_conv_ms"]
    assert (metric["kind"], metric["source"], metric["moves"]) == (
        "scope_time", "device_trace", "train_tokens_per_s")
    fwd, bwd = "ps_causal_conv_fwd.7_bf16_1_8192_4352", "transpose_jvp_ps_causal_conv_bwd__.3_bf16_1_8192_4352"
    step = lambda t: [[fwd, t, 0.0003], ["fusion.12_f32_8192_4352", t + 0.0003, 0.001],
                      [fwd, t + 0.0013, 0.0003], [bwd, t + 0.0016, 0.00045]]
    ev = {"trace": {"devices": {"/device:TPU:0": step(0.0) + step(0.01)}, "host": []},
          "steps_traced": 2, "cell": None, "peaks": {}}
    assert reducers.reduce(metric["kind"], metric["args"], ev) == pytest.approx(1.05)
    recorded = os.path.join(os.path.dirname(spec.__file__), "tests", "data", cell + ".trace.json.gz")
    ev = {**ev, "trace": tr.load_json(recorded)}
    assert reducers.reduce(metric["kind"], metric["args"], ev) is None
