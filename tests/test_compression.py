"""Bandwidth-honest compressed collectives + error feedback.

- quantized_allreduce_2round must approximate the exact mean within the
  per-block quantization bound, agree on every worker, and round-trip
  padding for awkward sizes.
- local_quantized_contribution must satisfy the accounting identity
  psum(contribution_w) == k * aggregate for the int8 psum path — the
  invariant that makes error-feedback residuals the TRUE on-wire error.
- The PS engine with error_feedback must train, carry worker-stacked
  residuals in PSTrainState.comm_state, checkpoint/resume them, and
  accumulate the FULL gradient as residual on mask-excluded workers.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ps_pytorch_tpu.models import build_model
from ps_pytorch_tpu.optim import sgd
from ps_pytorch_tpu.parallel import (
    DCN_AXIS,
    WORKER_AXIS,
    PSConfig,
    init_ps_state,
    make_mesh,
    make_ps_train_step,
    shard_batch,
    shard_state,
    tree_view,
)
from ps_pytorch_tpu.parallel.collectives import (
    local_quantized_contribution,
    psum_mean,
    quantized_allreduce_2round,
    quantized_psum,
)

N = 8


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(num_workers=N, axis_name=WORKER_AXIS)


def _tree(seed, shapes=((33, 7), (129,), (5, 5, 3))):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(*s).astype(np.float32)) for s in shapes]


def _run_collective(mesh, fn, tree):
    """Run `fn(worker_local_tree)` under shard_map with replicated inputs
    but per-worker scaled values (so workers genuinely differ)."""

    def body(t):
        w = jax.lax.axis_index(WORKER_AXIS).astype(jnp.float32)
        local = jax.tree.map(lambda g: g * (1.0 + 0.1 * w), t)
        return fn(local)

    return jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False
        )
    )(tree)


@pytest.mark.parametrize("block", [0, 128], ids=["per_tensor", "per_block"])
def test_2round_close_to_exact_mean(mesh, block):
    tree = _tree(0)
    got = _run_collective(
        mesh,
        lambda t: quantized_allreduce_2round(
            t, WORKER_AXIS, float(N), N, block_size=block
        ),
        tree,
    )
    want = _run_collective(
        mesh, lambda t: psum_mean(t, WORKER_AXIS, float(N)), tree
    )
    for g, w, orig in zip(got, want, tree):
        # two quantization rounds: error <= (absmax_grad + absmax_sum)/127
        # per element; bound loosely via the data's scale
        bound = 2.5 * float(jnp.max(jnp.abs(orig))) * (1.7) / 127.0
        err = float(jnp.max(jnp.abs(g - w)))
        assert err <= bound, (err, bound)


def test_2round_awkward_sizes(mesh):
    # sizes that don't divide by workers or blocks: padding must round-trip
    tree = _tree(1, shapes=((1,), (13,), (257,), (8, 9)))
    got = _run_collective(
        mesh,
        lambda t: quantized_allreduce_2round(
            t, WORKER_AXIS, float(N), N, block_size=128
        ),
        tree,
    )
    want = _run_collective(
        mesh, lambda t: psum_mean(t, WORKER_AXIS, float(N)), tree
    )
    for g, w in zip(got, want):
        assert g.shape == w.shape
        assert float(jnp.max(jnp.abs(g - w))) < 0.1 * (
            1 + float(jnp.max(jnp.abs(w)))
        )


@pytest.mark.parametrize("block", [0, 128], ids=["per_tensor", "per_block"])
@pytest.mark.parametrize("rounding", ["nearest", "stochastic"])
def test_hier_2round_close_to_exact_mean(block, rounding):
    """quantized_allreduce_2round_hier over a 2x4 hybrid mesh: single-DCN-
    crossing scheme stays within quantization error of the exact mean and
    agrees on every chip (out_specs P() would fail otherwise)."""
    from ps_pytorch_tpu.parallel import make_hybrid_mesh
    from ps_pytorch_tpu.parallel.collectives import (
        quantized_allreduce_2round_hier,
    )

    hmesh = make_hybrid_mesh(num_hosts=2, per_host=4)
    tree = _tree(4, shapes=((57, 5), (301,)))
    key = jax.random.key(0)

    def body(t):
        d = jax.lax.axis_index(DCN_AXIS).astype(jnp.float32)
        w = jax.lax.axis_index(WORKER_AXIS).astype(jnp.float32)
        local = jax.tree.map(lambda g: g * (1.0 + 0.05 * (4 * d + w)), t)
        got = quantized_allreduce_2round_hier(
            local, (DCN_AXIS, WORKER_AXIS), float(N), (2, 4),
            block_size=block, rounding=rounding,
            key=key if rounding == "stochastic" else None,
        )
        want = psum_mean(local, (DCN_AXIS, WORKER_AXIS), float(N))
        return got, want

    got, want = jax.jit(
        jax.shard_map(
            body, mesh=hmesh, in_specs=(P(),), out_specs=P(),
            check_vma=False,
        )
    )(tree)
    for g, w, orig in zip(got, want, tree):
        bound = 3.0 * float(jnp.max(jnp.abs(orig))) * 1.5 / 127.0
        err = float(jnp.max(jnp.abs(g - w)))
        assert err <= bound, (err, bound)


@pytest.mark.parametrize("block", [0, 128], ids=["per_tensor", "per_block"])
def test_contribution_accounting_identity(mesh, block):
    """psum of per-worker transmitted values == k * quantized_psum result
    (denominator k) — bit-exact, so EF residuals are the true wire error."""
    tree = _tree(2)

    def both(t):
        agg = quantized_psum(t, WORKER_AXIS, float(N), block_size=block)
        contrib = local_quantized_contribution(t, WORKER_AXIS, block_size=block)
        contrib_sum = jax.tree.map(
            lambda c: jax.lax.psum(c, WORKER_AXIS), contrib
        )
        return agg, contrib_sum

    agg, csum = _run_collective(mesh, both, tree)
    for a, c in zip(agg, csum):
        np.testing.assert_allclose(
            np.asarray(a) * N, np.asarray(c), rtol=1e-6, atol=1e-6
        )


def _tiny_setup(mesh, cfg, seed=0):
    from ps_pytorch_tpu.data import make_preprocessor

    model = build_model("LeNet")
    tx = sgd(0.05, momentum=0.9)
    state = init_ps_state(model, tx, cfg, jax.random.key(seed), (28, 28, 1))
    state = shard_state(state, mesh, cfg)
    step = make_ps_train_step(
        model, tx, cfg, mesh, preprocess=make_preprocessor("MNIST", train=False)
    )
    rng = np.random.RandomState(seed)
    batch = shard_batch(
        {
            "image": rng.randint(0, 255, (2 * N, 28, 28, 1)).astype(np.uint8),
            "label": rng.randint(0, 10, (2 * N,)).astype(np.int32),
        },
        mesh,
        cfg,
    )
    return state, step, batch


@pytest.mark.parametrize("compress", ["int8", "int8_2round"])
def test_error_feedback_trains_and_carries_residuals(mesh, compress):
    cfg = PSConfig(
        num_workers=N, compress=compress, quant_block_size=128,
        error_feedback=True,
    )
    state, step, batch = _tiny_setup(mesh, cfg)
    assert state.comm_state is not None
    losses = []
    for i in range(6):
        state, metrics = step(state, batch, jax.random.key(i))
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    # residuals exist, are worker-stacked, and are not all zero
    leaves = jax.tree_util.tree_leaves(state.comm_state)
    assert all(l.shape[0] == N for l in leaves)
    assert any(float(jnp.max(jnp.abs(l))) > 0 for l in leaves)


def test_ef_untracked_round2_noise_measured(mesh):
    """Quantify the round-2 requantization noise EF does NOT track (r04
    VERDICT item 5): on real LeNet gradients through the real aggregation
    path, measure ||2round_wire_output - mean(round1_contributions)|| —
    the gap between what the wire actually delivered and what the EF
    residual accounting assumes it delivered. Pins (a) the magnitude of
    the untracked noise relative to the aggregate and (b) that block-128
    scales shrink it vs per-tensor — the mechanism the r05 convergence
    legs lean on."""
    from ps_pytorch_tpu.models import apply_model
    from ps_pytorch_tpu.ops.metrics import cross_entropy_loss
    from ps_pytorch_tpu.parallel.collectives import aggregate_gradients

    model = build_model("LeNet")
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 28, 28, 1), jnp.float32), train=False
    )["params"]
    rng = np.random.RandomState(7)
    # per-worker disjoint real batches => genuine gradient heterogeneity
    images = jnp.asarray(rng.rand(N, 16, 28, 28, 1).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 10, (N, 16)).astype(np.int32))

    def rel_untracked(block):
        def body(x, y):
            def loss_fn(p):
                logits, _ = apply_model(model, p, {}, x[0], train=False)
                return cross_entropy_loss(logits, y[0])

            grads = jax.grad(loss_fn)(params)
            agg, contrib = aggregate_gradients(
                grads, WORKER_AXIS, N, compress="int8_2round",
                quant_block_size=block, return_contribution=True,
            )
            # the EF accounting's view of the aggregate: every worker's
            # round-1 transmitted value, exactly averaged (round 2 assumed
            # lossless)
            ef_view = jax.tree.map(
                lambda c: jax.lax.psum(c, WORKER_AXIS) / N, contrib
            )
            return agg, ef_view

        agg, ef_view = jax.jit(
            jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(WORKER_AXIS), P(WORKER_AXIS)),
                out_specs=P(), check_vma=False,
            )
        )(images, labels)
        num = sum(
            float(jnp.sum((a - e) ** 2))
            for a, e in zip(jax.tree.leaves(agg), jax.tree.leaves(ef_view))
        )
        den = sum(
            float(jnp.sum(a**2)) for a in jax.tree.leaves(agg)
        )
        return float(np.sqrt(num / den))

    per_tensor = rel_untracked(0)
    per_block = rel_untracked(128)
    # measured on this config: per-tensor 1.5e-2, block-128 8.0e-3 —
    # round-2 noise is ~1-2% of the aggregate's norm, and block scales
    # halve it. The assertions pin the measured order of magnitude with
    # headroom, not the exact draw.
    assert per_block < per_tensor, (per_block, per_tensor)
    assert per_tensor < 0.05, per_tensor
    assert per_block < 0.02, per_block


def test_error_feedback_accumulates_masked_gradients(mesh):
    """With first_k masking, excluded workers transmit nothing — their
    residual must hold their ENTIRE (feedback-corrected) gradient."""
    cfg = PSConfig(
        num_workers=N, compress="int8", num_aggregate=2,
        mask_mode="first_k", error_feedback=True,
    )
    state, step, batch = _tiny_setup(mesh, cfg, seed=3)
    state, _ = step(state, batch, jax.random.key(0))
    leaves = jax.tree_util.tree_leaves(state.comm_state)
    # masked-out workers (idx >= 2) carry much larger residuals than the
    # transmitting ones (theirs is just int8 rounding error)
    for l in leaves:
        l = np.asarray(jax.device_get(l))
        excluded = np.abs(l[2:]).max()
        included = np.abs(l[:2]).max()
        if excluded > 0:  # leaves with zero grads (e.g. last-layer bias) skip
            assert excluded >= included, (excluded, included)


def test_error_feedback_state_checkpoints(mesh, tmp_path):
    from ps_pytorch_tpu.checkpoint import load_checkpoint, save_checkpoint

    cfg = PSConfig(num_workers=N, compress="int8", error_feedback=True)
    state, step, batch = _tiny_setup(mesh, cfg, seed=4)
    state, _ = step(state, batch, jax.random.key(0))
    save_checkpoint(state, str(tmp_path), 1)

    cfg2 = PSConfig(num_workers=N, compress="int8", error_feedback=True)
    fresh = init_ps_state(
        build_model("LeNet"), sgd(0.05, momentum=0.9), cfg2,
        jax.random.key(9), (28, 28, 1),
    )
    restored = load_checkpoint(fresh, str(tmp_path), 1)
    for a, b in zip(
        jax.tree_util.tree_leaves(restored.comm_state),
        jax.tree_util.tree_leaves(jax.device_get(state.comm_state)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pre_comm_state_checkpoints_still_resume(mesh, tmp_path):
    """Checkpoints written BEFORE PSTrainState gained comm_state (their
    state dict has no such key) must restore into a comm_state=None
    target — the forward-compat shim in checkpoint.load_checkpoint."""
    from flax import serialization

    from ps_pytorch_tpu.checkpoint import load_checkpoint

    cfg = PSConfig(num_workers=N)  # no EF: comm_state is None
    state = init_ps_state(
        build_model("LeNet"), sgd(0.05), cfg, jax.random.key(0), (28, 28, 1)
    )
    old_dict = serialization.to_state_dict(jax.device_get(state))
    old_dict.pop("comm_state")  # simulate the pre-feature format
    (tmp_path / "model_step_7").write_bytes(
        serialization.msgpack_serialize(old_dict)
    )
    restored = load_checkpoint(state, str(tmp_path), 7)
    assert restored.comm_state is None
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(restored.step)),
        np.asarray(jax.device_get(state.step)),
    )


@pytest.mark.parametrize("block", [0, 128], ids=["per_tensor", "per_block"])
def test_sharded_2round_wire_matches_int8_scatter_bitwise(mesh, block):
    """In the ZeRO-1 placement, the int8 all_to_all + local int32 sum
    ("int8_2round": genuinely-int8 wire) must produce BIT-IDENTICAL
    training math to the int32 psum_scatter ("int8"): both sum the same
    int8 payloads exactly — only the bytes on the interconnect differ."""
    results = {}
    for compress in ("int8", "int8_2round"):
        cfg = PSConfig(
            num_workers=N, opt_placement="sharded", compress=compress,
            quant_block_size=block,
        )
        state, step, batch = _tiny_setup(mesh, cfg, seed=5)
        for i in range(3):
            state, m = step(state, batch, jax.random.key(i))
        results[compress] = (
            jax.device_get(state.params), float(m["loss"])
        )
    assert results["int8"][1] == results["int8_2round"][1]
    for a, b in zip(
        jax.tree_util.tree_leaves(results["int8"][0]),
        jax.tree_util.tree_leaves(results["int8_2round"][0]),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("compress", ["int8", "int8_2round"])
def test_sharded_error_feedback_trains_and_carries_residuals(mesh, compress):
    """EF in the ZeRO-1 placement: residuals live on the flat padded
    gradient vector, one [L] row per worker, and training converges."""
    cfg = PSConfig(
        num_workers=N, opt_placement="sharded", compress=compress,
        quant_block_size=128, error_feedback=True,
    )
    state, step, batch = _tiny_setup(mesh, cfg, seed=2)
    assert state.comm_state is not None and state.comm_state.ndim == 2
    assert state.comm_state.shape[0] == N
    losses = []
    for i in range(6):
        state, metrics = step(state, batch, jax.random.key(i))
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    assert float(jnp.max(jnp.abs(state.comm_state))) > 0


def test_sharded_ef_masked_workers_accumulate_full_gradient(mesh):
    """first_k masking + sharded EF: excluded workers transmit zeros, so
    their flat residual must dominate the transmitting workers'."""
    cfg = PSConfig(
        num_workers=N, opt_placement="sharded", compress="int8",
        num_aggregate=2, mask_mode="first_k", error_feedback=True,
    )
    state, step, batch = _tiny_setup(mesh, cfg, seed=7)
    state, _ = step(state, batch, jax.random.key(0))
    res = np.asarray(jax.device_get(state.comm_state))  # [N, L]
    excluded = np.abs(res[2:]).max()
    included = np.abs(res[:2]).max()
    assert excluded > included, (excluded, included)


def test_hierarchical_2round_over_dcn(mesh):
    """compress='int8_2round' with dcn_hosts=2: the hierarchical scheme
    (ICI 2-round inside each host, then DCN 2-round on host sums) stays
    within quantization error of the exact mean and trains."""
    from ps_pytorch_tpu.parallel import make_hybrid_mesh

    hmesh = make_hybrid_mesh(num_hosts=2, per_host=4)
    cfg = PSConfig(num_workers=N, dcn_hosts=2, compress="int8_2round",
                   quant_block_size=128)
    state, step, batch = _tiny_setup(hmesh, cfg, seed=3)
    losses = []
    for i in range(6):
        state, metrics = step(state, batch, jax.random.key(i))
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses

    # one-step update close to the uncompressed hybrid run
    cfg_ref = PSConfig(num_workers=N, dcn_hosts=2)
    s_ref, step_ref, batch_ref = _tiny_setup(hmesh, cfg_ref, seed=3)
    s_q, step_q, batch_q = _tiny_setup(hmesh, cfg, seed=3)
    s_ref, _ = step_ref(s_ref, batch_ref, jax.random.key(0))
    s_q, _ = step_q(s_q, batch_q, jax.random.key(0))
    for a, b in zip(
        # tree views: the quantized config pads its flat state to the
        # 128-elem block, the reference to 1 — raw vectors differ in len
        jax.tree_util.tree_leaves(jax.device_get(tree_view(s_ref.params))),
        jax.tree_util.tree_leaves(jax.device_get(tree_view(s_q.params))),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0.1, atol=5e-3
        )


def test_hierarchical_2round_ef_trains(mesh):
    """EF on top of the hierarchical DCN scheme (residual mirrors the
    inner ICI ring's round-1 transform)."""
    from ps_pytorch_tpu.parallel import make_hybrid_mesh

    hmesh = make_hybrid_mesh(num_hosts=2, per_host=4)
    cfg = PSConfig(num_workers=N, dcn_hosts=2, compress="int8_2round",
                   quant_block_size=128, error_feedback=True)
    state, step, batch = _tiny_setup(hmesh, cfg, seed=3)
    losses = []
    for i in range(6):
        state, metrics = step(state, batch, jax.random.key(i))
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


def test_ef_checkpoint_into_non_ef_target_errors(mesh, tmp_path):
    """The converse mismatch: a checkpoint CARRYING comm_state restored
    into an error_feedback=False target (comm_state None) must raise — not
    silently pass raw arrays through the None target (ADVICE r02)."""
    from ps_pytorch_tpu.checkpoint import load_checkpoint, save_checkpoint

    cfg_ef = PSConfig(num_workers=N, compress="int8", error_feedback=True)
    state_ef = init_ps_state(
        build_model("LeNet"), sgd(0.05), cfg_ef, jax.random.key(0),
        (28, 28, 1),
    )
    save_checkpoint(state_ef, str(tmp_path), 3)

    cfg_plain = PSConfig(num_workers=N)
    target = init_ps_state(
        build_model("LeNet"), sgd(0.05), cfg_plain, jax.random.key(0),
        (28, 28, 1),
    )
    with pytest.raises(ValueError, match="comm_state|error-feedback"):
        load_checkpoint(target, str(tmp_path), 3)


# ------------------------------------------- homomorphic wire (§6h)


def test_accum_dtype_pins_the_overflow_bound():
    """The no-overflow contract of the compressed-domain sum: int16
    holds exactly 258 full-scale int8 payloads (259 * 127 > 32767),
    int32 exactly 16_909_320, and past that accum_dtype refuses rather
    than wraps — so PSConfig(wire_domain='homomorphic') can never build
    a mesh whose worst-case sum overflows its wire dtype."""
    from ps_pytorch_tpu.ops.quantize import ACCUM_CAPACITY, accum_dtype

    assert accum_dtype(1) == jnp.int16
    assert accum_dtype(8) == jnp.int16
    assert accum_dtype(ACCUM_CAPACITY["int16"]) == jnp.int16
    assert accum_dtype(ACCUM_CAPACITY["int16"] + 1) == jnp.int32
    assert accum_dtype(ACCUM_CAPACITY["int32"]) == jnp.int32
    with pytest.raises(ValueError, match="overflow"):
        accum_dtype(ACCUM_CAPACITY["int32"] + 1)
    with pytest.raises(ValueError, match=">= 1"):
        accum_dtype(0)
    # the bounds really are the worst-case sums, checked in numpy's own
    # integer arithmetic
    assert ACCUM_CAPACITY["int16"] * 127 <= np.iinfo(np.int16).max
    assert (ACCUM_CAPACITY["int16"] + 1) * 127 > np.iinfo(np.int16).max
    assert ACCUM_CAPACITY["int32"] * 127 <= np.iinfo(np.int32).max
    assert (ACCUM_CAPACITY["int32"] + 1) * 127 > np.iinfo(np.int32).max
    # a concrete full-scale accumulation at the int16 capacity is exact
    worst = np.full((ACCUM_CAPACITY["int16"],), 127, np.int16)
    assert int(worst.astype(np.int64).sum()) == int(
        np.add.reduce(worst, dtype=np.int16)
    )


@pytest.mark.parametrize("block", [0, 128], ids=["per_tensor", "per_block"])
def test_homomorphic_shared_scales_identical_on_every_worker(mesh, block):
    """The shared-scale rule: ONE max-abs reduction gives every worker
    the same scale row set, so one set serves all workers and the int
    payload sum is a sum on one lattice."""
    from ps_pytorch_tpu.ops.quantize import quantize_int8

    x = jnp.asarray(np.random.RandomState(3).randn(257).astype(np.float32))

    def body(t):
        w = jax.lax.axis_index(WORKER_AXIS)
        local = jnp.roll(t, w)  # distinct payloads, same value multiset
        _, scale = quantize_int8(
            local, axis_name=WORKER_AXIS, block_size=block
        )
        return scale.reshape(1, -1)

    stacked = jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=(P(),),
            out_specs=P(WORKER_AXIS), check_vma=False,
        )
    )(x)
    stacked = np.asarray(stacked)  # [N, n_rows]
    assert stacked.shape[0] == N
    for w in range(1, N):
        np.testing.assert_array_equal(stacked[0], stacked[w])


def test_homomorphic_accum_bit_exact_vs_dequantize_then_sum(mesh):
    """THE §6h numerical pin: the homomorphic integer accumulation is
    bit-exact against summing the same dequantized payloads. The test
    data's absmax is 127 * 2^-3, so the shared scale is a power of two:
    per-worker dequantization (q * s) is then EXACT in f32, the f32 sum
    of dequantized payloads equals s * (sum of ints) exactly, and the
    deferred single multiply must match it bitwise. The integer psum is
    additionally recovered and compared as integers."""
    from ps_pytorch_tpu.ops.quantize import dequantize_int8, quantize_int8

    rng = np.random.RandomState(5)
    x = (rng.randint(-127, 128, (257,)).astype(np.float32)) * (2.0 ** -3)
    x[0] = 127.0 * 2.0 ** -3  # pin absmax -> scale is exactly 2^-3
    x = jnp.asarray(x)

    def body(t):
        w = jax.lax.axis_index(WORKER_AXIS)
        local = jnp.roll(t, w)  # same multiset -> same shared scale
        hom = quantized_psum(
            [local], WORKER_AXIS, float(N),
            wire_domain="homomorphic", num_workers=N,
        )[0]
        q, scale = quantize_int8(local, axis_name=WORKER_AXIS)
        int_sum = jax.lax.psum(q.astype(jnp.int32), WORKER_AXIS)
        deq_then_sum = jax.lax.psum(
            dequantize_int8(q.astype(jnp.int32), scale), WORKER_AXIS
        )
        return hom, int_sum, deq_then_sum, scale

    hom, int_sum, deq_then_sum, scale = jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False,
        )
    )(x)
    s = float(scale)
    assert s == 2.0 ** -3  # the power-of-two premise really holds
    # bitwise: deferred-single-multiply == dequantize-then-sum (/ N is
    # exact: N is a power of two)
    np.testing.assert_array_equal(
        np.asarray(hom), np.asarray(deq_then_sum) / N
    )
    # and the integer accumulation is exactly the sum of the payloads
    recovered = np.asarray(hom) * (N / s)
    np.testing.assert_array_equal(recovered, np.asarray(int_sum))


@pytest.mark.parametrize("block", [0, 128], ids=["per_tensor", "per_block"])
def test_homomorphic_2round_close_to_exact_mean(mesh, block):
    """The homomorphic 2-round wire stays within the quant-spec
    envelope of the exact mean: round 1's shared-scale quantization
    (error <= s/2 per worker) plus ONE lattice rescale (error <= s/2) —
    the same order as the dequant twin's round-2 requantization."""
    tree = _tree(6)
    got = _run_collective(
        mesh,
        lambda t: quantized_allreduce_2round(
            t, WORKER_AXIS, float(N), N, block_size=block,
            wire_domain="homomorphic",
        ),
        tree,
    )
    want = _run_collective(
        mesh, lambda t: psum_mean(t, WORKER_AXIS, float(N)), tree
    )
    for g, w, orig in zip(got, want, tree):
        bound = 2.5 * float(jnp.max(jnp.abs(orig))) * 1.7 / 127.0
        err = float(jnp.max(jnp.abs(g - w)))
        assert err <= bound, (err, bound)


def test_homomorphic_hier_close_to_exact_mean():
    """The hierarchical homomorphic wire (globally-shared scales, int8
    on every hop incl. the ICI reassembly) stays within the declared
    envelope of the exact mean and agrees on every chip."""
    from ps_pytorch_tpu.parallel import make_hybrid_mesh
    from ps_pytorch_tpu.parallel.collectives import (
        quantized_allreduce_2round_hier,
    )

    hmesh = make_hybrid_mesh(num_hosts=2, per_host=4)
    tree = _tree(8, shapes=((57, 5), (301,)))

    def body(t):
        d = jax.lax.axis_index(DCN_AXIS).astype(jnp.float32)
        w = jax.lax.axis_index(WORKER_AXIS).astype(jnp.float32)
        local = jax.tree.map(lambda g: g * (1.0 + 0.05 * (4 * d + w)), t)
        got = quantized_allreduce_2round_hier(
            local, (DCN_AXIS, WORKER_AXIS), float(N), (2, 4),
            wire_domain="homomorphic",
        )
        want = psum_mean(local, (DCN_AXIS, WORKER_AXIS), float(N))
        return got, want

    got, want = jax.jit(
        jax.shard_map(
            body, mesh=hmesh, in_specs=(P(),), out_specs=P(),
            check_vma=False,
        )
    )(tree)
    for g, w, orig in zip(got, want, tree):
        # round 1 (s/2) + two lattice rescales (s/2 each): <= 3 lattice
        # steps of the shared scale, loosely bounded via the data
        bound = 3.5 * float(jnp.max(jnp.abs(orig))) * 1.5 / 127.0
        err = float(jnp.max(jnp.abs(g - w)))
        assert err <= bound, (err, bound)


@pytest.mark.parametrize(
    "extra",
    [
        dict(compress="int8", quant_block_size=128, error_feedback=True),
        dict(compress="int8_2round", quant_block_size=128,
             error_feedback=True),
        dict(compress="int8", opt_placement="sharded",
             quant_block_size=128, error_feedback=True),
        dict(compress="int8", quant_block_size=128, error_feedback=True,
             bucket_bytes=64 << 10, overlap="pipelined"),
    ],
    ids=["int8_ef", "2round_ef", "zero1_int8_ef", "int8_ef_pipelined"],
)
def test_homomorphic_e2e_training_parity_vs_dequant(mesh, extra):
    """End-to-end training parity (§6h acceptance): the homomorphic
    wire trains within the declared quant-spec envelope of the dequant
    wire — same seeds, same batches, EF absorbing the (coarser)
    shared-scale error exactly as it does on the dequant wire. The
    one-STEP update is pinned to the envelope (the two wires round
    differently, so multi-step trajectories drift apart chaotically —
    the same reason the dequant wire is only envelope-close to the
    uncompressed psum); the 6-step trajectory is pinned to train and
    land near the dequant loss."""
    results = {}
    for domain in ("dequant", "homomorphic"):
        cfg = PSConfig(num_workers=N, wire_domain=domain, **extra)
        # seed 2, not the original 6: at lr 0.05 / momentum 0.9 on one
        # 16-sample batch the 6-step loss oscillates, and whether it ends
        # below its start depends on the init draw. jax 0.9.0's default
        # (partitionable) threefry stream draws a different init from the
        # same key than the jax this pin was written on; under it seed 6
        # ends ABOVE its start on every wire, the uncompressed psum
        # included (3.14 -> 8.82), while seeds 2 and 3 descend on all
        # three. The two wires under test agree to 3 decimals either way.
        state, step, batch = _tiny_setup(mesh, cfg, seed=2)
        losses = []
        p1 = None
        for i in range(6):
            state, m = step(state, batch, jax.random.key(i))
            if i == 0:
                p1 = jax.device_get(tree_view(state.params))
            losses.append(float(m["loss"]))
        results[domain] = (losses, p1)
    ld, pd = results["dequant"]
    lh, ph = results["homomorphic"]
    assert all(np.isfinite(lh)), lh
    assert lh[-1] < lh[0], lh  # the homomorphic wire really trains
    # one-step parity envelope vs the dequant wire
    for a, b in zip(jax.tree_util.tree_leaves(pd),
                    jax.tree_util.tree_leaves(ph)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0.1, atol=5e-3
        )
    assert abs(lh[-1] - ld[-1]) < 0.2 * (1.0 + abs(ld[-1])), (lh, ld)


def test_homomorphic_hier_e2e_training_parity(mesh):
    """The hierarchical DCN x ICI homomorphic wire trains in parity
    with its dequant twin (serial; the hier wire has no pipelined
    registry twin — §6g covers pipelined x homomorphic on the flat
    schemes). Same one-step-envelope / multi-step-trajectory split as
    the flat-scheme parity test."""
    from ps_pytorch_tpu.parallel import make_hybrid_mesh

    hmesh = make_hybrid_mesh(num_hosts=2, per_host=4)
    results = {}
    for domain in ("dequant", "homomorphic"):
        cfg = PSConfig(num_workers=N, dcn_hosts=2, compress="int8_2round",
                       quant_block_size=128, error_feedback=True,
                       wire_domain=domain)
        state, step, batch = _tiny_setup(hmesh, cfg, seed=3)
        losses = []
        p1 = None
        for i in range(6):
            state, m = step(state, batch, jax.random.key(i))
            if i == 0:
                p1 = jax.device_get(tree_view(state.params))
            losses.append(float(m["loss"]))
        results[domain] = (losses, p1)
    ld, pd = results["dequant"]
    lh, ph = results["homomorphic"]
    assert all(np.isfinite(lh)) and lh[-1] < lh[0], lh
    for a, b in zip(jax.tree_util.tree_leaves(pd),
                    jax.tree_util.tree_leaves(ph)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0.1, atol=5e-3
        )
    assert abs(lh[-1] - ld[-1]) < 0.2 * (1.0 + abs(ld[-1])), (lh, ld)


def test_homomorphic_sharded_2round_wire_is_unchanged(mesh):
    """In the ZeRO-1 placement the 2-round wire is ALREADY
    compressed-domain (int8 a2a + local int32 sum + shard-only
    dequant), so wire_domain='homomorphic' must be a VALUE no-op there:
    bit-identical training to the dequant spelling."""
    results = {}
    for domain in ("dequant", "homomorphic"):
        cfg = PSConfig(num_workers=N, opt_placement="sharded",
                       compress="int8_2round", quant_block_size=128,
                       wire_domain=domain)
        state, step, batch = _tiny_setup(mesh, cfg, seed=5)
        for i in range(3):
            state, m = step(state, batch, jax.random.key(i))
        results[domain] = (jax.device_get(state.params), float(m["loss"]))
    assert results["dequant"][1] == results["homomorphic"][1]
    for a, b in zip(
        jax.tree_util.tree_leaves(results["dequant"][0]),
        jax.tree_util.tree_leaves(results["homomorphic"][0]),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_homomorphic_config_validation():
    """Both parse-time rejections the §6h satellites pin, plus the
    accumulator-capacity bound and the CLI flag mapping."""
    import argparse

    from ps_pytorch_tpu.cli._flags import (
        add_ps_flags,
        add_train_flags,
        ps_config_from,
    )
    from ps_pytorch_tpu.ops.quantize import ACCUM_CAPACITY

    with pytest.raises(ValueError, match="nothing to homomorphically"):
        PSConfig(num_workers=4, wire_domain="homomorphic")
    with pytest.raises(ValueError, match="nearest"):
        PSConfig(num_workers=4, compress="int8",
                 quant_rounding="stochastic", wire_domain="homomorphic")
    with pytest.raises(ValueError, match="bad wire_domain"):
        PSConfig(num_workers=4, compress="int8", wire_domain="int8")
    with pytest.raises(ValueError, match="overflow"):
        PSConfig(num_workers=ACCUM_CAPACITY["int32"] + 1,
                 compress="int8", wire_domain="homomorphic")
    # the CLI flag maps onto the config (and defaults to dequant)
    parser = argparse.ArgumentParser()
    add_train_flags(parser)
    add_ps_flags(parser)
    args = parser.parse_args(
        ["--wire-domain", "homomorphic", "--compress-grad", "compress"]
    )
    assert ps_config_from(args, 8).wire_domain == "homomorphic"
    assert ps_config_from(parser.parse_args([]), 8).wire_domain == "dequant"
    # the two rejections surface through the CLI mapping too
    with pytest.raises(ValueError, match="nothing to homomorphically"):
        ps_config_from(
            parser.parse_args(["--wire-domain", "homomorphic"]), 8
        )
    with pytest.raises(ValueError, match="nearest"):
        ps_config_from(
            parser.parse_args(
                ["--wire-domain", "homomorphic", "--compress-grad",
                 "compress", "--quant-rounding", "stochastic"]
            ),
            8,
        )


def test_config_validation():
    with pytest.raises(ValueError, match="needs a compress"):
        PSConfig(num_workers=4, error_feedback=True)
    # r03: EF x sharded and 2round x sharded are now SUPPORTED; the one
    # remaining fence is the 3-way combo whose wire has no hierarchy to
    # exploit (see PSConfig.__post_init__'s design note)
    PSConfig(num_workers=4, compress="int8", error_feedback=True,
             opt_placement="sharded")
    PSConfig(num_workers=4, compress="int8_2round", opt_placement="sharded")
    with pytest.raises(ValueError, match="unsupported"):
        PSConfig(num_workers=8, compress="int8_2round",
                 opt_placement="sharded", dcn_hosts=2)
    # the explicit-tuple form must hit the same fence (review r03)
    with pytest.raises(ValueError, match="unsupported"):
        PSConfig(num_workers=8, compress="int8_2round",
                 opt_placement="sharded", axis_name=(DCN_AXIS, WORKER_AXIS))
