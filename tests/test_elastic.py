"""Elastic membership suite: resume-reshape across mesh geometries and
adaptive partial aggregation (resilience/elastic.py; ARCHITECTURE §7f).

The load-bearing guarantees, each pinned here:

- geometry reshape is a BIT-EXACT rearrangement for params and optimizer
  moments (replicated<->ZeRO-1, N->M shrink/grow, bucket/quant carving
  changes) — the canonical tree interchange never rounds;
- per-worker EF residuals are re-distributed SUM-PRESERVINGLY (exact on
  power-of-two meshes), local BN stats mean/broadcast — the documented
  non-bit-exact exceptions;
- the chaos drill: a real SIGTERM mid-run on the 8-device mesh, resume
  on a 4-worker mesh (shrink), finish + evaluate, then grow back to 8 —
  with a straggler storm on the shrunken mesh driving a mask_adapt;
- adaptive aggregation at full count is bit-exact against the static
  num_aggregate=None step, including the guard + EF + stochastic
  rounding interactions; partial counts select the same worker set as
  the static mask;
- the AdaptiveMaskController drops the count within one window of a
  straggler and recovers after the storm, deterministically;
- retry backoff jitter stays inside its declared bounds and is
  reproducible under a seeded RNG.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
from flax import serialization

from ps_pytorch_tpu import checkpoint as ckpt
from ps_pytorch_tpu.data import make_synthetic
from ps_pytorch_tpu.models import build_model
from ps_pytorch_tpu.optim import build_optimizer
from ps_pytorch_tpu.parallel import (
    PSConfig,
    init_ps_state,
    make_ps_train_step,
    shard_batch,
    shard_state,
)
from ps_pytorch_tpu.parallel.buckets import FlatVector, tree_layout
from ps_pytorch_tpu.resilience import (
    AdaptiveMaskController,
    FaultPlan,
    MeshGeometry,
    elastic,
    geometry_of,
    load_geometry,
    needs_reshape,
    reshape_raw_state,
    retry_io,
    save_geometry,
)
from ps_pytorch_tpu.resilience import retry as retry_mod
from ps_pytorch_tpu.trainer import TrainConfig, Trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 8


@pytest.fixture()
def tiny_ds():
    return make_synthetic("MNIST", train_size=128, test_size=32, seed=1)


def _leaves_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


# ------------------------------------------------------- geometry manifest

def test_geometry_manifest_roundtrip(tmp_path):
    geom = geometry_of(PSConfig(
        num_workers=8, opt_placement="sharded", compress="int8",
        quant_block_size=32, bucket_bytes=65536, error_feedback=True,
    ))
    save_geometry(str(tmp_path), geom)
    assert load_geometry(str(tmp_path)) == geom


def test_geometry_manifest_tolerates_unknown_keys(tmp_path):
    save_geometry(str(tmp_path), MeshGeometry(num_workers=4))
    path = tmp_path / elastic.GEOMETRY_FILE
    d = json.loads(path.read_text())
    d["some_future_field"] = 17
    path.write_text(json.dumps(d))
    assert load_geometry(str(tmp_path)).num_workers == 4


def test_load_geometry_none_without_manifest(tmp_path):
    assert load_geometry(str(tmp_path)) is None


def test_geometry_manifest_per_step_entries(tmp_path):
    """An elastically-resumed dir holds mixed-geometry checkpoints; the
    manifest must answer 'who wrote step N', not just 'who wrote last'."""
    g8 = MeshGeometry(num_workers=8, opt_placement="sharded")
    g4 = MeshGeometry(num_workers=4, opt_placement="sharded")
    save_geometry(str(tmp_path), g8, step=3)
    save_geometry(str(tmp_path), g4, step=6)
    assert load_geometry(str(tmp_path), step=3) == g8
    assert load_geometry(str(tmp_path), step=6) == g4
    # a step with NO record predates per-step tracking: guessing from
    # the latest-writer entry could silently mis-reshape a ZeRO-1
    # carving, so the answer is honestly "unknown" (manifest-less path)
    assert load_geometry(str(tmp_path), step=99) is None
    assert load_geometry(str(tmp_path)) == g4


def test_torn_manifest_is_treated_as_manifest_less(tmp_path):
    """A damaged elastic.json must never brick resume (resume's whole
    contract is quarantine-and-fall-back); the dir degrades to the
    manifest-less path and the checkpoint CRC still guards the state."""
    save_geometry(str(tmp_path), MeshGeometry(num_workers=8), step=2)
    path = tmp_path / elastic.GEOMETRY_FILE
    path.write_text(path.read_text()[: len(path.read_text()) // 2])
    assert load_geometry(str(tmp_path)) is None
    assert load_geometry(str(tmp_path), step=2) is None


def test_fallback_resume_uses_the_writing_steps_geometry(tmp_path, tiny_ds):
    """Corrupt the newest (4-worker) checkpoint of a resumed dir: the
    fallback restore of the older 8-worker file must reshape by the
    geometry that WROTE it — the treacherous case is ZeRO-1, where a
    wrong-geometry load can be silently scrambled rather than loud."""
    base = dict(
        network="LeNet", dataset="MNIST", batch_size=8, epochs=8,
        eval_freq=2, log_interval=0, train_dir=str(tmp_path / "m"),
    )
    p8 = PSConfig(num_workers=8, opt_placement="sharded")
    Trainer(TrainConfig(max_steps=2, **base), p8, dataset=tiny_ds).train()
    t4 = Trainer(TrainConfig(max_steps=4, resume=True, **base),
                 PSConfig(num_workers=4, opt_placement="sharded"),
                 dataset=tiny_ds)
    t4.train()
    assert ckpt.latest_valid_step(str(tmp_path / "m")) == 4
    # damage the newest (step-4, 4-worker) checkpoint on disk
    path = ckpt.checkpoint_path(str(tmp_path / "m"), 4)
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 2)
    # resume on 8 workers: quarantines step 4, falls back to step 2 —
    # written by an 8-WORKER run, so no reshape must engage
    t8 = Trainer(TrainConfig(max_steps=4, resume=True,
                             metrics_file=str(tmp_path / "fb.jsonl"),
                             **base), p8, dataset=tiny_ds)
    assert t8.try_resume() == 2
    events = [json.loads(l) for l in open(tmp_path / "fb.jsonl")]
    assert any(e["kind"] == "ckpt_quarantined" for e in events)
    assert not any(e["kind"] == "resume_reshape" for e in events)


def test_needs_reshape_matrix():
    rep8 = MeshGeometry(num_workers=8)
    rep4 = MeshGeometry(num_workers=4)
    sh8 = MeshGeometry(num_workers=8, opt_placement="sharded")
    sh4 = MeshGeometry(num_workers=4, opt_placement="sharded")
    assert not needs_reshape(rep8, rep8)
    # plain replicated state stores nothing worker-stacked: N may change
    # without touching the file's shapes
    assert not needs_reshape(rep8, rep4)
    assert needs_reshape(rep8, sh8)      # placement switch
    assert needs_reshape(sh8, sh4)       # sharded shrink
    assert needs_reshape(sh8, rep8)
    # replicated bucket_bytes change: checkpoints are tree-shaped, no
    # reshape needed (PR 5's portability)
    assert not needs_reshape(
        rep8, MeshGeometry(num_workers=8, bucket_bytes=65536)
    )
    # sharded bucket_bytes change: SAME shapes, different worker->region
    # mapping — must reshape or silently scramble the moments
    assert needs_reshape(
        sh8, MeshGeometry(num_workers=8, opt_placement="sharded",
                          bucket_bytes=65536)
    )
    # EF rows and local BN stats are worker-stacked in every placement
    assert needs_reshape(
        MeshGeometry(num_workers=8, compress="int8", error_feedback=True),
        MeshGeometry(num_workers=4, compress="int8", error_feedback=True),
    )
    assert needs_reshape(
        MeshGeometry(num_workers=8, bn_mode="local"),
        MeshGeometry(num_workers=4, bn_mode="local"),
    )
    assert not needs_reshape(
        MeshGeometry(num_workers=8, bn_mode="local"),
        MeshGeometry(num_workers=8, bn_mode="local"),
    )


# ------------------------------------------------- region layout inversion

def test_worker_region_roundtrip_multibucket():
    """_regions_to_flat must exactly invert the engine's _worker_region
    carving, including multi-bucket plans with quant-block alignment."""
    geom = MeshGeometry(num_workers=4, opt_placement="sharded",
                        compress="int8", quant_block_size=8,
                        bucket_bytes=512)
    total = 301
    plan = elastic._sharded_plan(geom, total)
    assert plan.n_buckets > 1  # the interesting case
    rng = np.random.RandomState(0)
    flat = rng.randn(plan.padded_total).astype(np.float32)
    stacked = elastic._flat_to_regions(flat, plan, 4)
    back = elastic._regions_to_flat(stacked, plan, 4)
    np.testing.assert_array_equal(back, flat)
    # and the other direction
    stacked2 = elastic._flat_to_regions(back, plan, 4)
    np.testing.assert_array_equal(stacked2, stacked)


def test_flat_to_regions_matches_engine_worker_region():
    """Host-side carving == the traced ps._worker_region slicing."""
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from ps_pytorch_tpu.parallel.mesh import WORKER_AXIS
    from ps_pytorch_tpu.parallel.ps import _worker_region

    geom = MeshGeometry(num_workers=4, opt_placement="sharded",
                        bucket_bytes=256)
    plan = elastic._sharded_plan(geom, 200)
    rng = np.random.RandomState(1)
    flat = rng.randn(plan.padded_total).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()[:4]), (WORKER_AXIS,))

    def f(x):
        w = lax.axis_index(WORKER_AXIS)
        return _worker_region(x, plan, w, 4)[None]

    got = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=P(), out_specs=P(WORKER_AXIS),
        check_vma=False,
    ))(flat)
    np.testing.assert_array_equal(
        np.asarray(got), elastic._flat_to_regions(flat, plan, 4)
    )


# --------------------------------------------- EF / BN redistribution math

def test_ef_redistribution_preserves_sum():
    src = MeshGeometry(num_workers=8, compress="int8", error_feedback=True)
    dst = MeshGeometry(num_workers=4, compress="int8", error_feedback=True)
    rng = np.random.RandomState(2)
    leaf = rng.randn(8, 5, 3).astype(np.float32)
    raw = {"w": leaf}
    layout = tree_layout({"w": np.zeros((5, 3), np.float32)})
    canon = elastic._ef_to_canonical(raw, src, layout)
    out = elastic._ef_from_canonical(canon, dst, layout)
    assert out["w"].shape == (4, 5, 3)
    # power-of-two M: the re-distribution is exactly sum-preserving
    np.testing.assert_array_equal(
        out["w"].sum(axis=0), leaf.sum(axis=0)
    )


def test_ef_sharded_to_replicated_redistribution():
    src = MeshGeometry(num_workers=4, opt_placement="sharded",
                       compress="int8", error_feedback=True)
    dst = MeshGeometry(num_workers=2, compress="int8", error_feedback=True)
    layout = tree_layout({"w": np.zeros((6,), np.float32)})
    plan = elastic._sharded_plan(src, layout.total)
    rng = np.random.RandomState(3)
    rows = rng.randn(4, plan.padded_total).astype(np.float32)
    rows[:, layout.total:] = 0.0  # the pad tail carries no residual
    canon = elastic._ef_to_canonical(rows, src, layout)
    out = elastic._ef_from_canonical(canon, dst, layout)
    assert out["w"].shape == (2, 6)
    np.testing.assert_array_equal(
        out["w"].sum(axis=0), rows.sum(axis=0)[:6]
    )


def test_bn_local_mean_and_broadcast():
    rng = np.random.RandomState(4)
    stats = {"bn": {"mean": rng.randn(8, 16).astype(np.float32)}}
    canon = elastic._bn_to_canonical(stats, local=True)
    out = elastic._bn_from_canonical(canon, local=True, m=4)
    assert out["bn"]["mean"].shape == (4, 16)
    for w in range(4):
        np.testing.assert_array_equal(
            out["bn"]["mean"][w], stats["bn"]["mean"].mean(axis=0)
        )


# ------------------------------------------- end-to-end reshape bit-exact

def _train_steps(cfg, steps=3, seed=0, faults=None):
    """A few real PS train steps on the virtual mesh; returns the host
    state (and the step fn's cfg for reuse)."""
    from ps_pytorch_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(num_workers=cfg.num_workers)
    model = build_model("LeNet", num_classes=10)
    tx = build_optimizer("sgd", 0.05, momentum=0.9, flat=True)
    state = shard_state(
        init_ps_state(model, tx, cfg, jax.random.key(seed), (1, 28, 28, 1)),
        mesh, cfg,
    )
    step = make_ps_train_step(model, tx, cfg, mesh, donate=False,
                              faults=faults)
    rng = np.random.RandomState(seed)
    batch = shard_batch({
        "image": rng.randint(0, 255, (cfg.num_workers, 28, 28, 1)).astype(np.uint8),
        "label": rng.randint(0, 10, (cfg.num_workers,)).astype(np.int32),
    }, mesh, cfg)
    key = jax.random.key(seed + 1)
    metrics = None
    for i in range(steps):
        if cfg.adaptive_aggregate:
            state, metrics = step(state, batch, key,
                                  np.int32(cfg.num_aggregate_max))
        else:
            state, metrics = step(state, batch, key)
    return jax.device_get(state), metrics


def _canonical_moments(host_state, geom):
    """Optimizer state in the canonical (replicated tree) form, whatever
    geometry produced it."""
    params = host_state.params
    layout = (params.layout if isinstance(params, FlatVector)
              else tree_layout(params))
    od = serialization.to_state_dict(host_state)["opt_state"]
    if geom.opt_placement == "sharded":
        plan = elastic._sharded_plan(geom, layout.total)
        return elastic._opt_to_canonical(od, plan, geom.num_workers, layout)
    return od


def _reshape_to(host_state, src_geom, dst_cfg, seed=99):
    """Run the real reshape+restore path: raw dict -> dst-geometry state."""
    from ps_pytorch_tpu.parallel.mesh import make_mesh

    raw = serialization.msgpack_restore(
        serialization.to_bytes(host_state)
    )
    model = build_model("LeNet", num_classes=10)
    tx = build_optimizer("sgd", 0.05, momentum=0.9,
                         flat=True)
    target = jax.device_get(init_ps_state(
        model, tx, dst_cfg, jax.random.key(seed), (1, 28, 28, 1)
    ))
    reshaped = reshape_raw_state(raw, src_geom, dst_cfg, target)
    return ckpt.restore_from_raw(target, reshaped, step=0)


def test_reshape_replicated_to_sharded_shrink_bit_exact():
    """8-worker replicated -> 4-worker ZeRO-1: params and moments are the
    same f32 bits rearranged."""
    cfg_a = PSConfig(num_workers=8)
    host_a, _ = _train_steps(cfg_a, steps=3)
    cfg_b = PSConfig(num_workers=4, opt_placement="sharded",
                     bucket_bytes=4096)
    restored = _reshape_to(host_a, geometry_of(cfg_a), cfg_b)
    pa = serialization.to_state_dict(host_a)["params"]
    pb = serialization.to_state_dict(restored)["params"]
    assert _leaves_equal(pa, pb)
    assert _leaves_equal(
        _canonical_moments(host_a, geometry_of(cfg_a)),
        _canonical_moments(restored, geometry_of(cfg_b)),
    )


def test_reshape_sharded_grow_and_recarve_bit_exact():
    """4-worker ZeRO-1 (bucketed) -> 8-worker ZeRO-1 (fused): the
    worker->region mapping changes completely; moments stay bit-exact."""
    cfg_a = PSConfig(num_workers=4, opt_placement="sharded",
                     bucket_bytes=4096)
    host_a, _ = _train_steps(cfg_a, steps=3, seed=5)
    cfg_b = PSConfig(num_workers=8, opt_placement="sharded")
    restored = _reshape_to(host_a, geometry_of(cfg_a), cfg_b)
    assert _leaves_equal(
        serialization.to_state_dict(host_a)["params"],
        serialization.to_state_dict(restored)["params"],
    )
    assert _leaves_equal(
        _canonical_moments(host_a, geometry_of(cfg_a)),
        _canonical_moments(restored, geometry_of(cfg_b)),
    )


def test_reshape_ef_residual_sum_preserved_end_to_end():
    """8 -> 4 workers with int8 + EF: the residual's total mass (the
    quantization debt EF owes the next updates) survives the reshape;
    the per-worker rows are re-distributed, not bit-preserved."""
    kw = dict(compress="int8", quant_block_size=32, error_feedback=True)
    cfg_a = PSConfig(num_workers=8, **kw)
    host_a, _ = _train_steps(cfg_a, steps=3, seed=7)
    cfg_b = PSConfig(num_workers=4, **kw)
    restored = _reshape_to(host_a, geometry_of(cfg_a), cfg_b)
    ca = serialization.to_state_dict(host_a)["comm_state"]
    cb = serialization.to_state_dict(restored)["comm_state"]
    la = jax.tree_util.tree_leaves(ca)
    lb = jax.tree_util.tree_leaves(cb)
    assert la and len(la) == len(lb)
    for a, b in zip(la, lb):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape[0] == 8 and b.shape[0] == 4
        np.testing.assert_array_equal(b.sum(axis=0), a.sum(axis=0))


def test_reshape_carving_only_passes_ef_through_bit_exact():
    """Worker identity survives a ZeRO-1 bucket-carving-only change
    (same N, same placement, same padded total): the moments re-map but
    every worker's accumulated EF residual — a full padded row, never
    region-carved — must pass through bit-exactly, not be re-averaged."""
    kw = dict(num_workers=4, opt_placement="sharded", compress="int8",
              quant_block_size=32, error_feedback=True)
    cfg_a = PSConfig(bucket_bytes=4096, **kw)
    cfg_b = PSConfig(bucket_bytes=0, **kw)
    assert needs_reshape(geometry_of(cfg_a), geometry_of(cfg_b))
    host_a, _ = _train_steps(cfg_a, steps=3, seed=11)
    restored = _reshape_to(host_a, geometry_of(cfg_a), cfg_b)
    assert _leaves_equal(
        serialization.to_state_dict(host_a)["comm_state"],
        serialization.to_state_dict(restored)["comm_state"],
    )
    # and the moments are still bit-exact through the re-carving
    assert _leaves_equal(
        _canonical_moments(host_a, geometry_of(cfg_a)),
        _canonical_moments(restored, geometry_of(cfg_b)),
    )


def test_reshape_carving_only_passes_bn_local_through():
    """Same identity rule for per-worker BN stats: a ZeRO-1 carving-only
    change keeps N and locality, so local BN stats must pass through
    bit-exact instead of being averaged away. Built on a handcrafted
    state (no small BN model exists) — reshape_raw_state only reads
    shapes and dicts."""
    from ps_pytorch_tpu.parallel.ps import PSTrainState

    kw = dict(num_workers=4, opt_placement="sharded", bn_mode="local")
    cfg_a = PSConfig(bucket_bytes=4096, **kw)
    cfg_b = PSConfig(bucket_bytes=0, **kw)
    src, dst = geometry_of(cfg_a), geometry_of(cfg_b)
    assert needs_reshape(src, dst)
    rng = np.random.RandomState(13)
    params = {"w": rng.randn(8).astype(np.float32)}
    plan = elastic._sharded_plan(src, 8)
    shard = plan.padded_total // 4

    def state(cfg, seed):
        r = np.random.RandomState(seed)
        return PSTrainState(
            step=np.int32(1),
            params=dict(params),
            opt_state={
                "count": np.zeros((4,), np.int32),
                "momentum_buffer": r.randn(4, shard).astype(np.float32),
            },
            batch_stats={"bn": {"mean": r.randn(4, 5).astype(np.float32)}},
            comm_state=None,
            guard_state=None,
        )

    src_state = state(cfg_a, 1)
    raw = serialization.msgpack_restore(serialization.to_bytes(src_state))
    out = reshape_raw_state(raw, src, cfg_b, state(cfg_b, 2))
    np.testing.assert_array_equal(
        out["batch_stats"]["bn"]["mean"],
        np.asarray(src_state.batch_stats["bn"]["mean"]),
    )
    # shrinking DOES re-distribute (mean + broadcast)
    cfg_c = PSConfig(num_workers=2, opt_placement="sharded",
                     bn_mode="local")
    plan_c = elastic._sharded_plan(geometry_of(cfg_c), 8)
    shard_c = plan_c.padded_total // 2
    tgt_c = PSTrainState(
        step=np.int32(1), params=dict(params),
        opt_state={
            "count": np.zeros((2,), np.int32),
            "momentum_buffer": np.zeros((2, shard_c), np.float32),
        },
        batch_stats={"bn": {"mean": np.zeros((2, 5), np.float32)}},
        comm_state=None, guard_state=None,
    )
    out_c = reshape_raw_state(raw, src, cfg_c, tgt_c)
    want = np.asarray(src_state.batch_stats["bn"]["mean"]).mean(axis=0)
    assert out_c["batch_stats"]["bn"]["mean"].shape == (2, 5)
    np.testing.assert_array_equal(out_c["batch_stats"]["bn"]["mean"][0], want)


def test_reshape_optimizer_mismatch_errors_actionably():
    """A sharded sgd+momentum checkpoint reshaped onto an adam target
    must raise the 'same --optimizer' config error, not an obscure flax
    structure crash from a None moment."""
    from ps_pytorch_tpu.parallel.mesh import make_mesh

    cfg_a = PSConfig(num_workers=4, opt_placement="sharded")
    host_a, _ = _train_steps(cfg_a, steps=1, seed=21)
    raw = serialization.msgpack_restore(serialization.to_bytes(host_a))
    cfg_b = PSConfig(num_workers=8, opt_placement="sharded")
    model = build_model("LeNet", num_classes=10)
    adam_target = jax.device_get(init_ps_state(
        model, build_optimizer("adam", 0.001, flat=True), cfg_b,
        jax.random.key(0), (1, 28, 28, 1),
    ))
    with pytest.raises(ValueError, match="same --optimizer"):
        reshape_raw_state(raw, geometry_of(cfg_a), cfg_b, adam_target)


# --------------------------------------------------------- the chaos drill

def test_chaos_drill_sigterm_shrink_then_grow(tmp_path, monkeypatch):
    """THE drill (ISSUE 7 acceptance): SIGTERM a ZeRO-1 run mid-step on
    the 8-device CPU mesh (FaultPlan), resume the SAME run on a 4-worker
    mesh under an injected straggler storm with the adaptive mask on —
    the resumed run reshapes, continues the step numbering, adapts the
    mask within one window, finishes, and evaluates — then grow back to
    8 workers and finish again. Bit-exactness of the reshape itself is
    pinned by the dedicated tests above; the drill pins the full
    operational loop."""
    from tpu_env import clean_cpu_env

    from ps_pytorch_tpu.cli.train import main

    d = str(tmp_path / "m")
    data_dir = str(tmp_path / "nodata")  # -> deterministic synthetic data
    env = clean_cpu_env(n_devices=8)
    env["PS_TPU_DATA_DIR"] = data_dir
    monkeypatch.setenv("PS_TPU_DATA_DIR", data_dir)
    common = [
        "--network", "LeNet", "--dataset", "MNIST",
        "--batch-size", "8", "--opt-placement", "sharded",
        "--eval-freq", "100", "--log-interval", "1",
        "--train-dir", d,
    ]
    proc = subprocess.run(
        [
            sys.executable, "-m", "ps_pytorch_tpu.cli.train",
            *common,
            "--num-workers", "8", "--max-steps", "30",
            "--fault-plan", '{"sigterm": 3}',
        ],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert ckpt.latest_valid_step(d) == 3
    assert load_geometry(d).num_workers == 8

    # shrink: resume on 4 workers with adaptive aggregation + a straggler
    # storm; the watchdog feeds the controller (--mode arms it)
    mf4 = str(tmp_path / "shrink.jsonl")
    out = main(common + [
        "--num-workers", "4", "--max-steps", "6", "--resume",
        "--metrics-file", mf4,
        "--num-aggregate-min", "2", "--num-aggregate-max", "4",
        "--adapt-window", "2",
        "--mode", "kill", "--kill-threshold", "0.75",
        "--fault-plan", '{"slow_steps": [5], "slow_s": 1.5}',
    ])
    assert np.isfinite(out["train"]["loss"])
    assert out["val"] is not None and np.isfinite(out["val"]["loss"])
    assert ckpt.latest_valid_step(d) == 6
    events = [json.loads(l) for l in open(mf4)]
    kinds = [e["kind"] for e in events]
    assert "resume_reshape" in kinds
    rr = next(e for e in events if e["kind"] == "resume_reshape")
    assert rr["from"]["num_workers"] == 8 and rr["to"]["num_workers"] == 4
    # step numbering CONTINUES (no silent restart at 1)
    first_train = next(e for e in events if e["kind"] == "train")
    assert first_train["step"] == 4
    # the injected straggler dropped the mask within one window
    adapt = next(e for e in events if e["kind"] == "mask_adapt")
    assert adapt["from"] == 4 and adapt["to"] == 3
    # the resumed run re-manifests ITS geometry for the next reshape
    assert load_geometry(d).num_workers == 4

    # grow: back to the full 8-worker mesh
    mf8 = str(tmp_path / "grow.jsonl")
    out2 = main(common + [
        "--num-workers", "8", "--max-steps", "8", "--resume",
        "--metrics-file", mf8,
    ])
    assert np.isfinite(out2["train"]["loss"])
    assert ckpt.latest_valid_step(d) == 8
    events8 = [json.loads(l) for l in open(mf8)]
    rr8 = next(e for e in events8 if e["kind"] == "resume_reshape")
    assert rr8["from"]["num_workers"] == 4 and rr8["to"]["num_workers"] == 8


def test_resume_same_geometry_does_not_reshape(tmp_path, tiny_ds):
    """The reshape path must NOT engage for an ordinary resume: the
    existing bit-exact load path is the one PR 3/5 pinned."""
    tcfg = TrainConfig(
        network="LeNet", dataset="MNIST", batch_size=16, max_steps=2,
        epochs=2, eval_freq=2, log_interval=1,
        train_dir=str(tmp_path / "m"),
        metrics_file=str(tmp_path / "m.jsonl"),
    )
    pcfg = PSConfig(num_workers=2)
    Trainer(tcfg, pcfg, dataset=tiny_ds).train()
    t2 = Trainer(tcfg, pcfg, dataset=tiny_ds)
    assert t2.try_resume() == 2
    events = [json.loads(l) for l in open(tcfg.metrics_file)]
    assert not any(e["kind"] == "resume_reshape" for e in events)


# ------------------------------------------- adaptive mask: device parity

def test_adaptive_full_mask_bit_exact_vs_static_with_guard_ef_stochastic():
    """The acceptance pin: a full-count adaptive step — stacked with the
    int8 wire, EF, stochastic rounding, AND a guard-skipped NaN step —
    produces bit-identical params and EF residuals to the static
    num_aggregate=None config."""
    kw = dict(
        num_workers=8, compress="int8", quant_block_size=32,
        error_feedback=True, quant_rounding="stochastic",
    )
    faults = FaultPlan(nan_grads=(2,))
    host_s, m_s = _train_steps(PSConfig(**kw), steps=3, faults=faults)
    host_a, m_a = _train_steps(
        PSConfig(**kw, num_aggregate_min=2, num_aggregate_max=8),
        steps=3, faults=faults,
    )
    # the guard skipped the same injected step in both runs
    assert float(m_s["skipped_steps"]) == float(m_a["skipped_steps"]) == 1.0
    sd_s = serialization.to_state_dict(host_s)
    sd_a = serialization.to_state_dict(host_a)
    assert _leaves_equal(sd_s["params"], sd_a["params"])
    assert _leaves_equal(sd_s["comm_state"], sd_a["comm_state"])
    assert _leaves_equal(sd_s["opt_state"], sd_a["opt_state"])


def test_adaptive_partial_count_selects_static_worker_set():
    """Pinned at a power-of-two partial count (4 of 8, first_k): the
    adaptive selection + traced denominator match the static mask
    bit-for-bit (power-of-two divides are exact under either compilation)."""
    host_s, _ = _train_steps(
        PSConfig(num_workers=8, num_aggregate=4, mask_mode="first_k"),
        steps=2,
    )

    cfg = PSConfig(num_workers=8, mask_mode="first_k",
                   num_aggregate_min=4, num_aggregate_max=4)
    host_a, _ = _train_steps(cfg, steps=2)
    assert _leaves_equal(
        serialization.to_state_dict(host_s)["params"],
        serialization.to_state_dict(host_a)["params"],
    )


def test_adaptive_random_k_rank_formulation_matches_static():
    """aggregation_mask with a traced k selects exactly the static
    perm[:k] set for every k."""
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from ps_pytorch_tpu.parallel.collectives import aggregation_mask
    from ps_pytorch_tpu.parallel.mesh import WORKER_AXIS

    mesh = Mesh(np.array(jax.devices()[:N]), (WORKER_AXIS,))
    key = jax.random.key(11)

    dummy = np.zeros((1,), np.int32)

    def masks(k_static, k_dyn):
        def f_s(_):
            return aggregation_mask(WORKER_AXIS, N, k_static, key)[None]

        def f_d(kd):
            return aggregation_mask(WORKER_AXIS, N, kd[0], key)[None]

        sm = jax.jit(jax.shard_map(
            f_s, mesh=mesh, in_specs=P(), out_specs=P(WORKER_AXIS),
            check_vma=False))(dummy)
        dm = jax.jit(jax.shard_map(
            f_d, mesh=mesh, in_specs=P(), out_specs=P(WORKER_AXIS),
            check_vma=False))(np.asarray([k_dyn], np.int32))
        return np.asarray(sm), np.asarray(dm)

    for k in (1, 3, 5, 8):
        sm, dm = masks(k, k)
        np.testing.assert_array_equal(sm, dm)
        assert dm.sum() == min(k, N)


# ------------------------------------------ adaptive controller (host half)

def _ctrl(lo=1, hi=8, start=None, window=4, threshold=1.0, sink=None):
    cfg = PSConfig(num_workers=8, num_aggregate=start,
                   num_aggregate_min=lo, num_aggregate_max=hi)
    return AdaptiveMaskController(cfg, threshold, window, event_sink=sink)


def test_controller_drops_within_one_window_and_recovers():
    events = []
    c = _ctrl(lo=2, hi=8, window=4, threshold=1.0, sink=events.append)
    assert c.count == 8  # starts at max
    # window 1: two slow steps -> count drops by 2 at the boundary
    for step, t in ((2, 0.1), (3, 5.0), (4, 5.0), (5, 0.1)):
        c.record(step, t)
    assert c.count == 6
    assert events and events[0]["kind"] == "mask_adapt"
    assert events[0]["from"] == 8 and events[0]["to"] == 6
    assert events[0]["slow_steps"] == 2 and events[0]["window_steps"] == 4
    # clean windows: +1 per window until the max, one event each
    for w in range(2):
        for step in range(4):
            c.record(10 + 4 * w + step, 0.1)
    assert c.count == 8
    assert [e["to"] for e in events] == [6, 7, 8]
    assert c.adaptations == 3


def test_controller_respects_floor_and_ceiling():
    c = _ctrl(lo=3, hi=5, window=2, threshold=1.0)
    assert c.count == 5
    for step in range(2, 12):
        c.record(step, 9.9)  # everything slow
    assert c.count == 3  # floored, never below min
    for step in range(20, 40):
        c.record(step, 0.0)
    assert c.count == 5  # ceilinged at max


def test_controller_initial_count_from_num_aggregate():
    c = _ctrl(lo=1, hi=8, start=5, window=4, threshold=1.0)
    assert c.count == 5


def test_controller_requires_armed_watchdog():
    cfg = PSConfig(num_workers=8, num_aggregate_min=1, num_aggregate_max=8)
    with pytest.raises(ValueError, match="watchdog"):
        AdaptiveMaskController(cfg, None, 4)


def test_psconfig_rejects_bad_adaptive_bounds():
    with pytest.raises(ValueError, match="BOTH"):
        PSConfig(num_workers=8, num_aggregate_min=2)
    with pytest.raises(ValueError, match="bounds"):
        PSConfig(num_workers=8, num_aggregate_min=2, num_aggregate_max=9)
    with pytest.raises(ValueError, match="bounds"):
        PSConfig(num_workers=8, num_aggregate_min=0, num_aggregate_max=4)
    with pytest.raises(ValueError, match="outside"):
        PSConfig(num_workers=8, num_aggregate=7,
                 num_aggregate_min=1, num_aggregate_max=4)


def test_trainer_storm_drops_mask_then_recovers(tmp_path, tiny_ds):
    """End-to-end determinism: an injected slow-step storm drops the
    count within one window; the clean windows after it recover, all
    visible as mask_adapt JSONL events and final metrics."""
    mfile = tmp_path / "m.jsonl"
    tcfg = TrainConfig(
        network="LeNet", dataset="MNIST", batch_size=16, max_steps=13,
        epochs=8, eval_freq=0, log_interval=1,
        train_dir=str(tmp_path / "models"),
        metrics_file=str(mfile),
        straggler_threshold_s=0.75,
        adapt_window=3,
        fault_plan='{"slow_steps": [3, 4], "slow_s": 1.5}',
    )
    pcfg = PSConfig(num_workers=2, num_aggregate_min=1, num_aggregate_max=2)
    out = Trainer(tcfg, pcfg, dataset=tiny_ds).train()
    events = [json.loads(l) for l in open(mfile)]
    adapts = [e for e in events if e["kind"] == "mask_adapt"]
    # steps 2-4 form window 1 (step 1 compiles, exempt): slow 3,4 ->
    # drop 2->1 AT step 4 (within one window of the storm); window
    # 5-7 clean -> recover 1->2
    assert [(e["from"], e["to"]) for e in adapts][:2] == [(2, 1), (1, 2)]
    assert adapts[0]["step"] == 4 and adapts[0]["slow_steps"] == 2
    assert out["agg_count"] == 2.0
    assert out["mask_adaptations"] >= 2.0


# ------------------------------------------------------- CLI flag surface

def test_cli_rejects_negative_num_aggregate():
    import argparse

    from ps_pytorch_tpu.cli._flags import add_ps_flags

    parser = add_ps_flags(argparse.ArgumentParser())
    with pytest.raises(SystemExit):
        parser.parse_args(["--num-aggregate", "-3"])


def test_cli_clamps_oversized_num_aggregate(caplog):
    import argparse
    import logging

    from ps_pytorch_tpu.cli._flags import add_ps_flags, ps_config_from

    parser = add_ps_flags(argparse.ArgumentParser())
    args = parser.parse_args(["--num-aggregate", "99"])
    lg = logging.getLogger("ps_pytorch_tpu")
    lg.addHandler(caplog.handler)  # the repo logger has propagate=False
    try:
        with caplog.at_level(logging.WARNING, logger="ps_pytorch_tpu"):
            pcfg = ps_config_from(args, num_workers=8)
    finally:
        lg.removeHandler(caplog.handler)
    # clamped to N == aggregate everyone (the old silent semantics, now
    # with a warning), so effective_aggregate is the full mesh
    assert pcfg.effective_aggregate == 8
    assert any("clamping" in r.message for r in caplog.records)


def test_cli_adaptive_flags_reach_psconfig():
    import argparse

    from ps_pytorch_tpu.cli._flags import add_ps_flags, ps_config_from

    parser = add_ps_flags(argparse.ArgumentParser())
    args = parser.parse_args(
        ["--num-aggregate-min", "2", "--num-aggregate-max", "6"]
    )
    pcfg = ps_config_from(args, num_workers=8)
    assert pcfg.adaptive_aggregate
    assert (pcfg.num_aggregate_min, pcfg.num_aggregate_max) == (2, 6)
    assert pcfg.initial_aggregate == 6


# ----------------------------------------------------------- retry jitter

def test_retry_jitter_bounds(monkeypatch):
    import random

    sleeps = []
    monkeypatch.setattr(retry_mod.time, "sleep", sleeps.append)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 4:
            raise OSError("transient")
        return "ok"

    rng = random.Random(42)
    assert retry_io(flaky, desc="t", attempts=4, base_delay_s=0.1,
                    jitter=0.5, rng=rng) == "ok"
    assert len(sleeps) == 3
    for k, s in enumerate(sleeps):
        base = 0.1 * (2 ** k)
        assert base <= s <= base * 1.5, (k, s)


def test_retry_jitter_deterministic_under_seeded_rng(monkeypatch):
    import random

    def schedule(seed):
        sleeps = []
        monkeypatch.setattr(retry_mod.time, "sleep", sleeps.append)
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise OSError("x")
            return 1

        retry_io(flaky, desc="t", attempts=3, base_delay_s=0.05,
                 rng=random.Random(seed))
        return sleeps

    assert schedule(7) == schedule(7)
    assert schedule(7) != schedule(8)


def test_retry_zero_jitter_is_deterministic_schedule(monkeypatch):
    sleeps = []
    monkeypatch.setattr(retry_mod.time, "sleep", sleeps.append)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("x")
        return 1

    retry_io(flaky, desc="t", attempts=3, base_delay_s=0.05, jitter=0.0)
    assert sleeps == [0.05, 0.1]
