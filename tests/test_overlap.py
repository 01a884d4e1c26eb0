"""Pipelined bucket reduction (PSConfig.overlap, ARCHITECTURE §6g).

What changing WHEN the wire moves must (and must not) change, pinned:

- the pipelined piece stream is a re-SCHEDULING, not a re-VALUING: the
  same plan, the same leaf->bucket byte assignment, bit-identical bucket
  contents, and the same start-offset PRNG ids — so training under
  overlap="pipelined" is BIT-EXACT vs "serial" for every wire scheme
  (none / int8 / int8_2round) on both placements (replicated / ZeRO-1),
  including EF residuals, stochastic-rounding keys (position-stable
  under the reordered bucket enumeration), the non-finite guard's
  rollback, and static masking. The one sanctioned exception: a TRACED
  adaptive ``agg_count`` denominator can't constant-fold, XLA spells
  the divide differently across the two fusion shapes, and the result
  sits ~1 ULP apart — pinned to a tight relative envelope instead;
- bucket assembly/rebuild really is per-bucket dataflow: segments tile
  the plan exactly, assembled buckets equal slices of the global
  concat, and the per-leaf rebuild inverts it;
- readiness order is reverse bucket enumeration, and the REAL jaxpr
  agrees: a traced gradient produces the last-constructed layer's
  leaves first (parallel/overlap.grad_leaf_readiness);
- the schedule-freedom analysis discriminates: per-bucket reduces have
  strictly more independent compute and strictly smaller launch
  prefixes than slice-of-concat reduces over the same math;
- the CLI maps --overlap on|off onto the config.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ps_pytorch_tpu.models import build_model
from ps_pytorch_tpu.optim import sgd
from ps_pytorch_tpu.parallel import (
    WORKER_AXIS,
    PSConfig,
    init_ps_state,
    make_ps_train_step,
    shard_batch,
    shard_state,
    tree_view,
)
from ps_pytorch_tpu.parallel.buckets import (
    assemble_bucket,
    bucket_leaf_segments,
    leaves_from_buckets,
    pad_flat,
    piece_stream,
    plan_buckets,
    readiness_bucket_order,
    split_buckets,
    tree_layout,
    tree_to_flat,
)
from ps_pytorch_tpu.parallel.overlap import (
    grad_leaf_readiness,
    jaxpr_overlap_headroom,
)

N = 8

tree_leaves = jax.tree_util.tree_leaves


def _leaves_equal(a, b):
    la, lb = tree_leaves(a), tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def _rand_tree(seed=0):
    k = jax.random.key(seed)
    return {
        "a": jax.random.normal(jax.random.fold_in(k, 1), (37, 5)),
        "b": jax.random.normal(jax.random.fold_in(k, 2), (3,)),
        "c": {"d": jax.random.normal(jax.random.fold_in(k, 3), (101,)),
              "e": jnp.zeros((0,), jnp.float32),
              "f": jax.random.normal(jax.random.fold_in(k, 4), (64,))},
    }


# ------------------------------------------------------ static geometry

def test_bucket_leaf_segments_tile_the_plan_exactly():
    tree = _rand_tree()
    layout = tree_layout(tree)
    plan = plan_buckets(layout.total, 256, align=16)
    segs = bucket_leaf_segments(layout, plan)
    assert len(segs) == plan.n_buckets
    covered = 0
    for frags, size in zip(segs, plan.sizes):
        assert sum(n for _, _, n in frags) == size
        covered += size
    assert covered == plan.padded_total
    # the padding tail is explicit, not silently attributed to a leaf
    tail = [f for f in segs[-1] if f[0] is None]
    assert sum(n for _, _, n in tail) == plan.padded_total - layout.total


def test_assemble_bucket_matches_slice_of_concat():
    tree = _rand_tree(1)
    layout = tree_layout(tree)
    plan = plan_buckets(layout.total, 256, align=16)
    segs = bucket_leaf_segments(layout, plan)
    serial = split_buckets(pad_flat(tree_to_flat(tree), plan), plan)
    leaves = tree_leaves(tree)
    for b in range(plan.n_buckets):
        got = assemble_bucket(leaves, segs[b])
        assert np.array_equal(np.asarray(got), np.asarray(serial[b])), b


def test_leaves_from_buckets_inverts_the_carving():
    tree = _rand_tree(2)
    layout = tree_layout(tree)
    plan = plan_buckets(layout.total, 128, align=8)
    buckets = split_buckets(pad_flat(tree_to_flat(tree), plan), plan)
    rebuilt = leaves_from_buckets(layout, plan, buckets)
    assert _leaves_equal(tree, rebuilt)


def test_readiness_order_is_reverse_enumeration():
    plan = plan_buckets(1000, 256, align=4)
    assert readiness_bucket_order(plan) == tuple(
        reversed(range(plan.n_buckets))
    )


def test_readiness_order_respects_explicit_leaf_rank():
    tree = {"a": jnp.zeros((10,)), "b": jnp.zeros((10,)),
            "c": jnp.zeros((10,))}
    layout = tree_layout(tree)
    plan = plan_buckets(layout.total, 40, align=1)  # one bucket per leaf
    # leaf 0 ready LAST, leaf 2 ready FIRST (the backprop shape)
    order = readiness_bucket_order(plan, layout, leaf_rank=(2, 1, 0))
    assert order == (2, 1, 0)
    # an inverted rank inverts the dispatch
    order = readiness_bucket_order(plan, layout, leaf_rank=(0, 1, 2))
    assert order == (0, 1, 2)


def test_piece_stream_pipelined_is_a_pure_reorder():
    tree = _rand_tree(3)
    layout = tree_layout(tree)
    plan = plan_buckets(layout.total, 256, align=16)
    s_pieces, s_ids, s_rebuild = piece_stream(tree, 256, align=16)
    p_pieces, p_ids, p_rebuild = piece_stream(tree, 256, align=16,
                                              pipelined=True)
    order = readiness_bucket_order(plan)
    assert p_ids == tuple(s_ids[b] for b in order)
    for pos, b in enumerate(order):
        assert np.array_equal(
            np.asarray(p_pieces[pos]), np.asarray(s_pieces[b])
        ), b
    # rebuild inverts the reorder: feeding the pieces straight back
    # reproduces the tree under both schedules
    assert _leaves_equal(tree, p_rebuild(p_pieces))
    assert _leaves_equal(tree, s_rebuild(s_pieces))
    # bucket_output returns the canonical-order buckets
    _, _, b_rebuild = piece_stream(tree, 256, align=16, pipelined=True,
                                   bucket_output=True)
    canon = b_rebuild(p_pieces)
    for b in range(plan.n_buckets):
        assert np.array_equal(np.asarray(canon[b]),
                              np.asarray(s_pieces[b]))


def test_bucket_output_requires_bucketed_wire():
    with pytest.raises(ValueError, match="bucket_output"):
        piece_stream(_rand_tree(), None, bucket_output=True)


# --------------------------------------------- jaxpr readiness evidence

def test_grad_readiness_is_reverse_topological():
    """The real jaxpr produces the LAST layer's gradient first — the
    justification for readiness_bucket_order's reversed enumeration."""
    k = jax.random.key(0)
    params = {
        "l1": jax.random.normal(jax.random.fold_in(k, 1), (8, 8)),
        "l2": jax.random.normal(jax.random.fold_in(k, 2), (8, 8)),
        "l3": jax.random.normal(jax.random.fold_in(k, 3), (8, 8)),
    }
    x = jax.random.normal(jax.random.fold_in(k, 4), (4, 8))

    def loss(p):
        h = jnp.tanh(x @ p["l1"])
        h = jnp.tanh(h @ p["l2"])
        return jnp.sum((h @ p["l3"]) ** 2)

    ranks = grad_leaf_readiness(jax.grad(loss), params)
    assert len(ranks) == 3
    r1, r2, r3 = ranks  # tree_leaves order: l1, l2, l3
    assert r3 < r2 < r1, ranks  # last layer's grad is produced first


def _toy_mesh():
    return Mesh(np.array(jax.devices()[:N]), (WORKER_AXIS,))


def test_overlap_headroom_discriminates_schedules():
    """Per-bucket reduces over per-bucket assembly have strictly more
    independent compute and a strictly smaller first-launch prefix than
    the same math spelled as slices of one global concat."""
    mesh = _toy_mesh()

    def serial_step(p, x):
        leaves = [jnp.sin(p[i * 8:(i + 1) * 8] * x[0, 0]) for i in range(4)]
        flat = jnp.concatenate(leaves)
        parts = [lax.psum(flat[i * 8:(i + 1) * 8], WORKER_AXIS) for i in range(4)]
        return p - 0.1 * jnp.concatenate(parts)

    def pipe_step(p, x):
        leaves = [jnp.sin(p[i * 8:(i + 1) * 8] * x[0, 0]) for i in range(4)]
        parts = [lax.psum(l, WORKER_AXIS) for l in leaves]
        return p - 0.1 * jnp.concatenate(parts)

    def headroom_of(f):
        step = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P(), P(WORKER_AXIS)), out_specs=P(),
            check_vma=False,
        ))
        return jaxpr_overlap_headroom(
            step,
            jax.ShapeDtypeStruct((32,), jnp.float32),
            jax.ShapeDtypeStruct((N, 4), jnp.float32),
        )

    reps = {"serial": headroom_of(serial_step),
            "pipe": headroom_of(pipe_step)}
    assert reps["serial"]["n_collectives"] == reps["pipe"]["n_collectives"]
    assert reps["pipe"]["overlap_headroom"] > reps["serial"]["overlap_headroom"]
    assert (reps["pipe"]["first_dispatch_prefix"]
            < reps["serial"]["first_dispatch_prefix"])
    assert reps["pipe"]["overlap_headroom"] > 0


# ----------------------------------------------- step-level bit-exactness

def _batch(n=16, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "image": rng.randint(0, 255, (n, 28, 28, 1)).astype(np.uint8),
        "label": rng.randint(0, 10, (n,)).astype(np.int32),
    }


def _train(mesh, cfg, steps=2, faults=None, agg_count=None):
    model = build_model("LeNet")
    tx = sgd(0.05, momentum=0.9)
    state = init_ps_state(model, tx, cfg, jax.random.key(0), (28, 28, 1))
    state = shard_state(state, mesh, cfg)
    step = make_ps_train_step(model, tx, cfg, mesh, donate=False,
                              faults=faults)
    b = shard_batch(_batch(), mesh, cfg)
    m = None
    for i in range(steps):
        if agg_count is not None:
            state, m = step(state, b, jax.random.key(i),
                            jnp.int32(agg_count))
        else:
            state, m = step(state, b, jax.random.key(i))
    return state, jax.device_get(m)


def _assert_schedules_bit_exact(mesh, extra, steps=2, faults=None,
                                agg_count=None):
    out = {}
    for overlap in ("serial", "pipelined"):
        cfg = PSConfig(num_workers=N, overlap=overlap, **extra)
        state, m = _train(mesh, cfg, steps=steps, faults=faults,
                          agg_count=agg_count)
        out[overlap] = (state, m)
    s, p = out["serial"], out["pipelined"]
    assert _leaves_equal(tree_view(s[0].params), tree_view(p[0].params))
    assert _leaves_equal(s[0].opt_state, p[0].opt_state)
    assert _leaves_equal(s[0].comm_state, p[0].comm_state)
    assert _leaves_equal(s[0].guard_state, p[0].guard_state)
    assert s[1]["loss"] == p[1]["loss"]
    return out


# the EF / 2-round / ZeRO-1-EF / stochastic combos compile 4 LeNet
# variants each (~75-230 s on the CI host) — slow tier; the tier-1 core
# keeps one pin per mechanism (flat per-bucket update, int8 pipelined
# wire + tree rebuild, static mask, ZeRO-1 stream, adaptive envelope)
_HEAVY = pytest.mark.slow


@pytest.mark.parametrize(
    "extra",
    [
        dict(bucket_bytes=4096),
        pytest.param(
            dict(compress="int8", quant_block_size=64, error_feedback=True,
                 bucket_bytes=4096),
            marks=_HEAVY,
        ),
        pytest.param(
            dict(compress="int8_2round", quant_block_size=32,
                 bucket_bytes=8192),
            marks=_HEAVY,
        ),
        pytest.param(
            dict(opt_placement="sharded", compress="int8",
                 quant_block_size=64, error_feedback=True,
                 bucket_bytes=4096),
            marks=_HEAVY,
        ),
        pytest.param(
            dict(compress="int8", quant_block_size=64,
                 quant_rounding="stochastic", bucket_bytes=4096),
            marks=_HEAVY,
        ),
        dict(num_aggregate=3, mask_mode="first_k", bucket_bytes=4096),
        # the homomorphic wire (§6h) under the pipelined stream: the
        # compressed-domain sum is per-bucket too (shared scales fold
        # per piece; the lattice rescale is deterministic), so the
        # schedule stays a pure reorder — bit-exact like every other
        # nearest-rounding combo
        dict(compress="int8", quant_block_size=64, error_feedback=True,
             bucket_bytes=4096, wire_domain="homomorphic"),
        pytest.param(
            dict(compress="int8_2round", quant_block_size=32,
                 bucket_bytes=8192, error_feedback=True,
                 wire_domain="homomorphic"),
            marks=_HEAVY,
        ),
    ],
    ids=["none_flat", "int8_ef", "2round", "zero1_int8_ef",
         "int8_stochastic", "static_mask", "int8_homomorphic",
         "2round_homomorphic_ef"],
)
def test_pipelined_bit_exact_vs_serial(mesh, extra):
    """The flagship pin: same config, both schedules, bit-identical
    params, optimizer moments, EF residuals, guard counters, and loss —
    across every wire scheme, both placements, and position-stable
    stochastic-rounding keys."""
    _assert_schedules_bit_exact(mesh, extra)


def test_pipelined_sharded_none_bit_exact(mesh):
    """The uncompressed ZeRO-1 scatter (no quantize chain) under the
    per-bucket stream."""
    _assert_schedules_bit_exact(
        mesh, dict(opt_placement="sharded", bucket_bytes=4096)
    )


@pytest.mark.slow
def test_pipelined_guard_rollback_bit_exact(mesh):
    """A NaN-injected step skips identically under both schedules: the
    rollback selects the pre-step state and the guard counters agree."""
    from ps_pytorch_tpu.resilience import FaultPlan

    faults = FaultPlan(nan_grads=(2,))
    out = _assert_schedules_bit_exact(
        mesh,
        dict(compress="int8", quant_block_size=64, error_feedback=True,
             bucket_bytes=4096),
        steps=3, faults=faults,
    )
    m = out["pipelined"][1]
    assert m["skipped_steps"] == 1.0  # the injected step really skipped


def test_pipelined_adaptive_agg_count_ulp_envelope(mesh):
    """The traced aggregation count rides the pipelined stream: same
    mask, same traced denominator, same selected set. Unlike every other
    combo this one is NOT bitwise: with a TRACED count the divide-by-k
    can't constant-fold, and XLA compiles it as a divide or as a
    multiply-by-reciprocal depending on the surrounding fusion shape —
    the serial (one fused psum) and pipelined (per-bucket psum) graphs
    land on different spellings, ~1 ULP apart (the same strength-
    reduction caveat §7f documents for adaptive-vs-static at partial
    counts). Pinned to a tight relative envelope instead; the STATIC
    mask case in the bitwise matrix shows masking itself is
    schedule-invariant."""
    out = {}
    for overlap in ("serial", "pipelined"):
        cfg = PSConfig(
            num_workers=N, overlap=overlap, num_aggregate_min=2,
            num_aggregate_max=N, mask_mode="first_k", bucket_bytes=4096,
        )
        state, m = _train(mesh, cfg, steps=2, agg_count=3)
        out[overlap] = (state, m)
    s, p = out["serial"], out["pipelined"]
    for a, b in zip(tree_leaves(tree_view(s[0].params)),
                    tree_leaves(tree_view(p[0].params))):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )
    np.testing.assert_allclose(s[1]["loss"], p[1]["loss"], rtol=1e-5)


# ----------------------------------------------------------- config/CLI

def test_overlap_config_validation():
    with pytest.raises(ValueError, match="overlap"):
        PSConfig(num_workers=N, overlap="sometimes")
    # the replicated per-leaf wire has no buckets to stream: pipelined
    # there would silently un-fuse the whole-tree psum (one eqn per
    # leaf), so it is rejected up front...
    with pytest.raises(ValueError, match="bucketed wire"):
        PSConfig(num_workers=N, overlap="pipelined")
    # ...while the ZeRO-1 wire is flat by construction (None == one
    # fused bucket) and pipelines without the knob
    PSConfig(num_workers=N, overlap="pipelined", opt_placement="sharded")
    PSConfig(num_workers=N, overlap="pipelined", bucket_bytes=0)


def test_overlap_cli_flag_mapping():
    import argparse

    from ps_pytorch_tpu.cli._flags import (
        add_ps_flags,
        add_train_flags,
        ps_config_from,
    )

    parser = add_ps_flags(add_train_flags(argparse.ArgumentParser()))
    args = parser.parse_args(["--overlap", "on", "--bucket-bytes", "4096"])
    cfg = ps_config_from(args, N)
    assert cfg.overlap == "pipelined"
    assert cfg.bucket_bytes == 4096
    args = parser.parse_args([])
    assert ps_config_from(args, N).overlap == "serial"  # default off


def test_overlap_report_jaxpr_mode_runs():
    """tools/trace_report.py overlap jaxpr end to end on the real LeNet
    step (trace-only): the pipelined build reports a positive overlap
    fraction and a smaller first-dispatch prefix than the serial one."""
    import importlib
    import sys

    sys.path.insert(0, "tools")
    overlap_report = importlib.import_module("overlap_report")
    reps = {}
    for ov in ("off", "on"):
        reps[ov] = overlap_report.main([
            "jaxpr", "--network", "LeNet", "--dataset", "MNIST",
            "--batch", "8", "--compress", "int8",
            "--bucket-bytes", "65536", "--overlap", ov,
        ])
    assert reps["on"]["overlap_fraction"] > 0
    assert (reps["on"]["first_dispatch_prefix"]
            < reps["off"]["first_dispatch_prefix"])
    assert reps["on"]["overlap_headroom"] > reps["off"]["overlap_headroom"]
