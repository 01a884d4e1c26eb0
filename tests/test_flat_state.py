"""Flat-state training engine (parallel/buckets.FlatVector) acceptance
suite. The PS state is flat; what that must not change is pinned against
per-leaf (tree) references written here from pieces that remain:

- flat-state training is BIT-EXACT vs a tree-state step at both the
  collective level (aggregate_gradients flat_output moves no values)
  and the step level (``_tree_oracle_step``: the model's per-leaf
  gradients, the per-leaf aggregate, the per-leaf optimizer), the
  int8/EF paths included;
- the fused whole-vector optimizer variants (optim.sgd_flat/adam_flat)
  produce bit-identical updates to the per-leaf tree transforms;
- checkpoints are TREE-SHAPED at the save/restore boundary (the
  pre-flat-state on-disk format), and a run resumed from one continues
  bit-identically to its donor, guard counters and the EF residual
  included;
- the non-finite guard's skip-step rollback works on flat state (the
  jnp.where select covers the flat params/moment vectors);
- ResNet18's update path (jaxpr ops downstream of the gradient reduce)
  stays the handful of fused vector ops flat state made it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from ps_pytorch_tpu.models import apply_model, build_model
from ps_pytorch_tpu.ops.metrics import cross_entropy_loss
from ps_pytorch_tpu.optim import adam, adam_flat, sgd, sgd_flat
from ps_pytorch_tpu.parallel import (
    WORKER_AXIS,
    FlatVector,
    PSConfig,
    aggregate_gradients,
    init_ps_state,
    make_ps_train_step,
    shard_batch,
    shard_state,
    state_plan,
    tree_view,
)
from ps_pytorch_tpu.parallel.buckets import (
    flat_to_tree,
    pad_flat,
    to_flat_vector,
    tree_layout,
    tree_to_flat,
)

N = 8

tree_leaves = jax.tree_util.tree_leaves


def _leaves_equal(a, b):
    la, lb = tree_leaves(a), tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


# -------------------------------------------------------- fused optimizers

def _rand_tree(seed=0):
    k = jax.random.key(seed)
    return {
        "w": jax.random.normal(jax.random.fold_in(k, 1), (13, 7)),
        "b": jax.random.normal(jax.random.fold_in(k, 2), (7,)),
        "nest": {"g": jax.random.normal(jax.random.fold_in(k, 3), (31,))},
    }


@pytest.mark.parametrize(
    "make_pair",
    [
        lambda: (sgd(0.1), sgd_flat(0.1)),
        lambda: (
            sgd(0.05, momentum=0.9, weight_decay=1e-4, nesterov=True),
            sgd_flat(0.05, momentum=0.9, weight_decay=1e-4, nesterov=True),
        ),
        lambda: (
            sgd(0.05, momentum=0.9, dampening=0.5),
            sgd_flat(0.05, momentum=0.9, dampening=0.5),
        ),
        lambda: (
            adam(1e-2, weight_decay=1e-4),
            adam_flat(1e-2, weight_decay=1e-4),
        ),
        lambda: (
            adam(1e-2, amsgrad=True),
            adam_flat(1e-2, amsgrad=True),
        ),
    ],
    ids=["sgd", "sgd_nesterov_wd", "sgd_dampening", "adam_wd", "amsgrad"],
)
def test_flat_optimizers_bit_match_tree(make_pair):
    """The whole-vector update variants are the SAME math: running the
    tree transform per leaf and the flat transform on the concatenated
    vector produces bit-identical parameters over several steps."""
    tx_tree, tx_flat = make_pair()
    params_t = _rand_tree(0)
    plan = state_plan(PSConfig(num_workers=N), tree_layout(params_t).total)
    params_f = to_flat_vector(params_t, plan)
    opt_t, opt_f = tx_tree.init(params_t), tx_flat.init(params_f)
    for step in range(4):
        g_t = _rand_tree(step + 10)
        g_f = params_f.replace(flat=pad_flat(tree_to_flat(g_t), plan))
        u_t, opt_t = tx_tree.update(g_t, opt_t, params_t)
        u_f, opt_f = tx_flat.update(g_f, opt_f, params_f)
        params_t = jax.tree_util.tree_map(jnp.add, params_t, u_t)
        params_f = jax.tree_util.tree_map(jnp.add, params_f, u_f)
        assert _leaves_equal(params_t, tree_view(params_f)), step


# ------------------------------------------------- collective-level parity

def test_aggregate_flat_output_bit_exact(mesh):
    """flat_output moves no values: concat-of-tree(agg) == flat(agg),
    for the per-leaf wire, the fused bucket wire, and int8."""
    def fn(v):
        g = {
            "a": (v[0] + 1.0) * jnp.linspace(-1.0, 1.0, 96),
            "b": jnp.full((33,), v[0] * 0.5),
        }
        out = {}
        for tag, kw in (
            ("none_leaf", dict()),
            ("none_fused", dict(bucket_bytes=0)),
            ("int8", dict(compress="int8", quant_block_size=32,
                          bucket_bytes=0)),
        ):
            t = aggregate_gradients(dict(g), WORKER_AXIS, N, **kw)
            f = aggregate_gradients(
                dict(g), WORKER_AXIS, N, flat_output=True, **kw
            )
            align = 32 if tag == "int8" else 1
            plan = state_plan(
                PSConfig(
                    num_workers=N,
                    compress=kw.get("compress"),
                    quant_block_size=kw.get("quant_block_size", 0),
                    bucket_bytes=kw.get("bucket_bytes"),
                ),
                tree_layout(g).total,
            )
            assert plan.align == align
            out[tag] = (pad_flat(tree_to_flat(t), plan), f)
        return out

    vals = jnp.arange(N, dtype=jnp.float32).reshape(N, 1)
    mapped = jax.shard_map(
        fn, mesh=mesh, in_specs=(P(WORKER_AXIS),), out_specs=P(),
        check_vma=False,
    )
    res = jax.device_get(mapped(vals))
    for tag, (t, f) in res.items():
        np.testing.assert_array_equal(t, f, err_msg=tag)


# ------------------------------------------------------- step-level parity

def _batch(n=16, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "image": rng.randint(0, 255, (n, 28, 28, 1)).astype(np.uint8),
        "label": rng.randint(0, 10, (n,)).astype(np.int32),
    }


def _train(mesh, cfg, tx=None, steps=3, faults=None):
    model = build_model("LeNet")
    tx = tx or sgd(0.05, momentum=0.9)
    state = init_ps_state(model, tx, cfg, jax.random.key(0), (28, 28, 1))
    state = shard_state(state, mesh, cfg)
    step = make_ps_train_step(model, tx, cfg, mesh, donate=False,
                              faults=faults)
    b = shard_batch(_batch(), mesh, cfg)
    m = None
    for i in range(steps):
        state, m = step(state, b, jax.random.key(i))
    return state, jax.device_get(m)


def _tree_oracle_step(model, tx, cfg, mesh):
    """The PS step over per-leaf (tree) state, written from pieces that
    remain: the model's per-leaf gradients, the per-leaf aggregate
    (``aggregate_gradients`` without flat_output) and the per-leaf
    optimizer transform. Same key folds as parallel/ps.py's worker. The
    ZeRO-1 wire transforms the padded flat gradient vector, so its
    oracle aggregates that vector as ONE leaf (same blocks, same shared
    scales; a psum where the step scatters — the integer sums are the
    same) and updates per leaf; its residual stays on the flat vector,
    and it returns the UPDATE in place of the new parameters."""
    axis, n = cfg.axis_name, cfg.num_workers
    sharded = cfg.opt_placement == "sharded"

    def worker(step_idx, params, opt_state, err, images, labels, key):
        w = lax.axis_index(axis)
        k_step = jax.random.fold_in(key, step_idx)
        k_mask = jax.random.fold_in(k_step, 0xA66)
        _, k_drop = jax.random.split(jax.random.fold_in(k_step, w + 1))
        x = images.astype(jnp.float32)

        def fwd_bwd(xi, yi, kd):
            def loss_fn(p):
                logits, _ = apply_model(
                    model, p, {}, xi, train=True, dropout_rng=kd
                )
                return cross_entropy_loss(logits, yi)

            return jax.value_and_grad(loss_fn)(params)

        a = cfg.grad_accum_steps
        if a > 1:
            xm = x.reshape(a, x.shape[0] // a, *x.shape[1:])
            ym = labels.reshape(a, -1)

            def micro(carry, inp):
                gsum, lsum = carry
                i, xi, yi = inp
                l_i, g_i = fwd_bwd(xi, yi, jax.random.fold_in(k_drop, i))
                return (jax.tree_util.tree_map(jnp.add, gsum, g_i),
                        lsum + l_i), None

            (gsum, lsum), _ = lax.scan(
                micro,
                (jax.tree_util.tree_map(jnp.zeros_like, params), 0.0),
                (jnp.arange(a), xm, ym),
            )
            grads = jax.tree_util.tree_map(lambda g: g / a, gsum)
            loss = lsum / a
        else:
            loss, grads = fwd_bwd(x, labels, k_drop)

        layout = tree_layout(grads)
        if sharded:
            plan = state_plan(cfg, layout.total)
            grads = {"flat": pad_flat(tree_to_flat(grads), plan)}
        if cfg.error_feedback:
            grads = jax.tree_util.tree_map(
                jnp.add, grads, jax.tree_util.tree_map(lambda e: e[0], err)
            )
        out = aggregate_gradients(
            grads, axis, n,
            num_aggregate=cfg.num_aggregate,
            mask_key=k_mask,
            mask_mode=cfg.mask_mode,
            compress=cfg.compress,
            quant_block_size=cfg.quant_block_size,
            quant_rounding=cfg.quant_rounding,
            quant_key=(
                jax.random.fold_in(k_step, 0x5E) if cfg.compress else None
            ),
            return_contribution=cfg.error_feedback,
            bucket_bytes=None if sharded else cfg.bucket_bytes,
        )
        if cfg.error_feedback:
            agg, contribution = out
            err = jax.tree_util.tree_map(
                lambda g, c: (g - c)[None], grads, contribution
            )
        else:
            agg = out
        if sharded:
            agg = flat_to_tree(layout, agg["flat"])
        updates, opt_state = tx.update(agg, opt_state, params)
        if not sharded:
            params = jax.tree_util.tree_map(jnp.add, params, updates)
        else:
            # the ZeRO-1 step all_gathers the update before adding it, so
            # XLA:CPU cannot contract -lr * buf + p into one fused
            # multiply-add there as it does here: hand the update out
            # and let the caller add it in a program of its own
            params = updates
        return params, opt_state, err, lax.pmean(loss, axis)

    mapped = jax.shard_map(
        worker, mesh=mesh,
        in_specs=(P(), P(), P(), P(axis), P(axis), P(axis), P()),
        out_specs=(P(), P(), P(axis), P()),
        check_vma=False,
    )
    return jax.jit(mapped)


@jax.jit
def _apply_updates(params, updates):
    return jax.tree_util.tree_map(jnp.add, params, updates)


def _tree_oracle_train(mesh, cfg, steps=3):
    """``_train``'s twin on the oracle: same init, same batch, same keys.
    Returns (params tree, EF residual in the step's own shape, loss)."""
    model = build_model("LeNet")
    tx = sgd(0.05, momentum=0.9)
    init = init_ps_state(model, tx, cfg, jax.random.key(0), (28, 28, 1))
    params = jax.device_get(tree_view(init.params))
    opt_state = tx.init(params)
    err = None
    if cfg.error_feedback:
        err = jax.tree_util.tree_map(jnp.zeros_like, init.comm_state)
        if cfg.opt_placement == "sharded":
            err = {"flat": err}
    step = _tree_oracle_step(model, tx, cfg, mesh)
    b = shard_batch(_batch(), mesh, cfg)
    loss = None
    for i in range(steps):
        out, opt_state, err, loss = step(
            jnp.int32(i), params, opt_state, err, b["image"], b["label"],
            jax.random.key(i),
        )
        params = (
            _apply_updates(params, out)
            if cfg.opt_placement == "sharded" else out
        )
    if err is not None and cfg.opt_placement == "sharded":
        err = err["flat"]
    return jax.device_get(params), jax.device_get(err), jax.device_get(loss)


@pytest.mark.parametrize(
    "extra",
    [
        dict(),
        dict(compress="int8", quant_block_size=64, error_feedback=True,
             bucket_bytes=0),
        dict(opt_placement="sharded", compress="int8", quant_block_size=64,
             error_feedback=True),
        # one config stacking the remaining flat-path variants: the
        # 2-round scheme's PER-LEAF flat rebuild, random-free first_k
        # masking, microbatch accumulation, and stochastic rounding keys
        dict(compress="int8_2round", quant_block_size=32, num_aggregate=5,
             mask_mode="first_k", grad_accum_steps=2,
             quant_rounding="stochastic"),
    ],
    ids=["none_per_leaf", "int8_ef_fused", "zero1_int8_ef",
         "2round_mask_accum_stochastic"],
)
def test_step_flat_bit_exact_vs_tree(mesh, extra):
    """The flagship acceptance pin: the PS step on flat state produces
    bit-identical parameters, loss, and (when on) EF residuals to the
    per-leaf oracle — flat state is a container change, not a math
    change. Covers the uncompressed per-leaf wire, the fused int8+EF
    wire, the ZeRO-1 placement, and a stacked 2round/mask/accum/
    stochastic config (the per-leaf flat rebuild path)."""
    cfg = PSConfig(num_workers=N, **extra)
    state, m = _train(mesh, cfg)
    params, comm, loss = _tree_oracle_train(mesh, cfg)
    assert _leaves_equal(params, jax.device_get(tree_view(state.params)))
    assert _leaves_equal(comm, jax.device_get(state.comm_state))
    assert loss == m["loss"]


def test_flat_state_structure(mesh):
    """The live params/moments really ARE flat vectors (one padded leaf
    each), and the tree view really is per-leaf."""
    cfg = PSConfig(num_workers=N)
    tx = sgd_flat(0.05, momentum=0.9)
    state, _ = _train(mesh, cfg, tx=tx, steps=1)
    assert isinstance(state.params, FlatVector)
    assert isinstance(state.opt_state.momentum_buffer, FlatVector)
    assert state.params.flat.ndim == 1
    assert (
        state.params.flat.shape[0]
        == state.params.plan.padded_total
        == state.opt_state.momentum_buffer.flat.shape[0]
    )
    n_tree_leaves = len(tree_leaves(tree_view(state.params)))
    assert n_tree_leaves > 1  # LeNet: the view fans back out
    assert len(tree_leaves(state.params)) == 1  # ...but the state doesn't


# --------------------------------------------------- checkpoint portability

def test_checkpoint_cross_layout_bit_exact(mesh, tmp_path):
    """The file a flat run writes is TREE-shaped (the pre-flat-state
    on-disk format: nested per-leaf dicts, no padded buffer), and a run
    resumed from it — params, optimizer moments, guard counters, and the
    EF residual — CONTINUES bit-identically to the donor run."""
    from flax import serialization

    import ps_pytorch_tpu.checkpoint as ckpt

    model = build_model("LeNet")
    cfg = PSConfig(
        num_workers=N, compress="int8", quant_block_size=64,
        error_feedback=True,
    )
    tx = sgd(0.05, momentum=0.9)
    donor = shard_state(
        init_ps_state(model, tx, cfg, jax.random.key(0), (28, 28, 1)),
        mesh, cfg,
    )
    step = make_ps_train_step(model, tx, cfg, mesh, donate=False)
    b = shard_batch(_batch(), mesh, cfg)
    for i in range(2):
        donor, _ = step(donor, b, jax.random.key(i))
    host = jax.device_get(donor)
    ckpt.save_checkpoint(host, str(tmp_path), 2)
    # the serialization edge: per-leaf dicts shaped like the tree view
    raw = serialization.to_state_dict(host)
    view = jax.device_get(tree_view(donor.params))
    for stored in (raw["params"], raw["opt_state"]["momentum_buffer"]):
        assert jax.tree_util.tree_structure(stored) == (
            jax.tree_util.tree_structure(serialization.to_state_dict(view))
        )
    assert _leaves_equal(raw["params"], view)

    target = jax.device_get(
        init_ps_state(model, tx, cfg, jax.random.key(7), (28, 28, 1))
    )
    restored = ckpt.load_checkpoint(target, str(tmp_path), 2)
    assert _leaves_equal(tree_view(restored.params), view)
    assert _leaves_equal(restored.opt_state, host.opt_state)
    assert _leaves_equal(restored.comm_state, host.comm_state)
    assert _leaves_equal(restored.guard_state, host.guard_state)
    assert int(restored.step) == 2
    cont = shard_state(restored, mesh, cfg)
    for i in range(2, 4):
        cont, _ = step(cont, b, jax.random.key(i))
        donor, _ = step(donor, b, jax.random.key(i))
    assert _leaves_equal(tree_view(cont.params), tree_view(donor.params))
    assert _leaves_equal(cont.comm_state, donor.comm_state)


def test_flatvector_state_dict_is_tree_shaped():
    """The serialization edge itself: a FlatVector's state dict is the
    nested per-leaf dict (NOT a raw buffer), so the on-disk format is
    layout-blind."""
    from flax import serialization

    tree = _rand_tree(3)
    plan = state_plan(PSConfig(num_workers=N), tree_layout(tree).total)
    fv = to_flat_vector(tree, plan)
    sd = serialization.to_state_dict(fv)
    assert set(sd) == {"w", "b", "nest"}
    assert _leaves_equal(sd, tree)
    back = serialization.from_state_dict(
        to_flat_vector(jax.tree_util.tree_map(jnp.zeros_like, tree), plan),
        sd,
    )
    np.testing.assert_array_equal(
        np.asarray(back.flat), np.asarray(fv.flat)
    )


# ------------------------------------------------------- guard on flat state

def test_guard_skip_rolls_back_flat_state(mesh):
    """A NaN-poisoned step on flat state is the identity update: the
    flat params/moment vectors keep their pre-step bits, the skip
    counter advances, and the run continues."""
    from ps_pytorch_tpu.resilience import FaultPlan

    cfg = PSConfig(num_workers=N)
    tx = sgd_flat(0.05, momentum=0.9)
    model = build_model("LeNet")
    state = shard_state(
        init_ps_state(model, tx, cfg, jax.random.key(0), (28, 28, 1)),
        mesh, cfg,
    )
    step = make_ps_train_step(
        model, tx, cfg, mesh, donate=False,
        faults=FaultPlan(nan_grads=(2,)),
    )
    b = shard_batch(_batch(), mesh, cfg)
    state1, _ = step(state, b, jax.random.key(0))
    before = jax.device_get(state1)
    state2, m2 = step(state1, b, jax.random.key(1))  # poisoned step
    after = jax.device_get(state2)
    assert float(m2["skipped_steps"]) == 1.0
    np.testing.assert_array_equal(
        np.asarray(before.params.flat), np.asarray(after.params.flat)
    )
    np.testing.assert_array_equal(
        np.asarray(before.opt_state.momentum_buffer.flat),
        np.asarray(after.opt_state.momentum_buffer.flat),
    )
    state3, m3 = step(state2, b, jax.random.key(2))  # healthy again
    assert float(m3["skipped_steps"]) == 1.0
    assert float(m3["skip_streak"]) == 0.0
    assert not np.array_equal(
        np.asarray(after.params.flat),
        np.asarray(jax.device_get(state3.params.flat)),
    )


# -------------------------------------------------- the update-path collapse

@pytest.mark.parametrize("config_kw", [
    dict(compress="int8", placement="replicated", network="ResNet18"),
])
def test_resnet18_update_path_collapses(config_kw):
    """Acceptance pin: ResNet18's update path — jaxpr ops downstream of
    the gradient reduce — stays the fused vector update. 120 is what the
    flat state read when a per-leaf state still existed beside it (whose
    scatter + per-leaf optimizer + per-leaf apply chain read 386), so a
    regression to per-leaf updates fails. Trace-only: nothing compiles
    or executes."""
    from ps_pytorch_tpu.check.contracts import RESNET_BUCKET_BYTES, _ps_spec
    from ps_pytorch_tpu.check.opcount import update_path_op_count

    built = _ps_spec(bucket_bytes=RESNET_BUCKET_BYTES, **config_kw).build()
    assert 0 < update_path_op_count(built.step, *built.args) <= 120
