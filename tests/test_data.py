"""Data layer tests: synthetic datasets, normalization parity, augmentation
shape/determinism and its equality with the per-image-slice oracle, loader
epoch semantics, worker sharding."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.data import (
    BatchIterator,
    Dataset,
    make_preprocessor,
    make_synthetic,
    normalize,
    prefetch_to_device,
    prepare_data,
    random_crop_flip,
    shard_for_worker,
)
from ps_pytorch_tpu.data.datasets import NORM_STATS, NUM_CLASSES, PAD_MODE


@pytest.mark.parametrize("name", ["MNIST", "Cifar10", "Cifar100", "SVHN"])
def test_synthetic_datasets(name):
    ds = make_synthetic(name, train_size=256, test_size=64)
    assert ds.synthetic
    assert ds.train_images.dtype == np.uint8
    assert ds.train_labels.dtype == np.int32
    assert ds.train_images.shape[0] == 256
    assert ds.num_classes == NUM_CLASSES[name]
    assert ds.train_labels.max() < ds.num_classes


def test_prepare_data_falls_back_to_synthetic(tmp_path):
    ds = prepare_data("Cifar10", root=str(tmp_path))
    assert ds.synthetic


def test_prepare_data_unknown_name():
    with pytest.raises(ValueError):
        prepare_data("ImageNet")


def test_prepare_data_no_synthetic_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        prepare_data("MNIST", root=str(tmp_path), allow_synthetic=False)


def test_normalize_matches_reference_constants():
    mean, std = NORM_STATS["Cifar10"]
    x = np.full((1, 2, 2, 3), 128, np.uint8)
    out = np.asarray(normalize(jnp.asarray(x), mean, std))
    expected = (128 / 255.0 - mean) / std
    np.testing.assert_allclose(out[0, 0, 0], expected, rtol=1e-5)


def test_random_crop_flip_shapes_and_determinism():
    x = jnp.asarray(np.random.RandomState(0).randint(0, 255, (8, 32, 32, 3), np.uint8))
    a = random_crop_flip(jax.random.key(7), x)
    b = random_crop_flip(jax.random.key(7), x)
    c = random_crop_flip(jax.random.key(8), x)
    assert a.shape == x.shape
    assert jnp.array_equal(a, b)
    assert not jnp.array_equal(a, c)


@partial(jax.jit, static_argnames=("pad", "pad_mode"))
def crop_flip_oracle(key, images, pad=4, pad_mode="reflect"):
    """The crop written image by image: `vmap` of `lax.dynamic_slice` over
    per-image offsets (a gather; on the TPU a loop of one turn an image).
    It says what bytes `random_crop_flip` has to return for a key, which
    `benchmark/reference/resnet18_cifar10.py` also draws this way."""
    n, h, w, c = images.shape
    kc, kf = jax.random.split(key)
    padded = jnp.pad(
        images, ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode=pad_mode
    )
    offs = jax.random.randint(kc, (n, 2), 0, 2 * pad + 1)

    def crop_one(img, off):
        return jax.lax.dynamic_slice(img, (off[0], off[1], 0), (h, w, c))

    cropped = jax.vmap(crop_one)(padded, offs)
    flip = jax.random.bernoulli(kf, 0.5, (n,))
    return jnp.where(flip[:, None, None, None], cropped[:, :, ::-1, :], cropped)


def _images(shape, dtype, seed=0):
    x = np.random.RandomState(seed).randint(0, 256, shape)
    # float images take values no uint8 holds, so a round trip through
    # another dtype inside the crop would show
    return jnp.asarray(x.astype(np.uint8) if dtype == "uint8" else x / 7.0, dtype)


@pytest.mark.parametrize("dtype", ["uint8", "float32"])
@pytest.mark.parametrize("pad", [2, 4])
@pytest.mark.parametrize(
    "shape", [(2048, 32, 32, 3), (7, 32, 32, 3), (4, 28, 28, 1), (5, 24, 40, 2)]
)
@pytest.mark.parametrize("pad_mode", ["reflect", "constant"])
@pytest.mark.parametrize("seed", [0, 1, 7, 42, 2700000061])
def test_random_crop_flip_equals_per_image_slice(seed, pad_mode, shape, pad, dtype):
    x = _images(shape, dtype)
    key = jax.random.key(seed)
    got = random_crop_flip(key, x, pad=pad, pad_mode=pad_mode)
    want = crop_flip_oracle(key, x, pad=pad, pad_mode=pad_mode)
    assert got.dtype == want.dtype == x.dtype and got.shape == x.shape
    assert jnp.array_equal(got, want)


def test_random_crop_flip_lowers_to_no_gather_or_dynamic_slice():
    """The form is the guard (the chip shows the time): a gather or a
    data-dependent slice is what the TPU compiler turns into a loop of one
    turn an image. The oracle shows the search finds one where there is."""
    x = _images((16, 32, 32, 3), "uint8")
    banned = ("stablehlo.gather", "dynamic_slice", "dynamic_update_slice")
    for pad_mode in ("reflect", "constant"):
        text = random_crop_flip.lower(
            jax.random.key(0), x, pad_mode=pad_mode).as_text()
        assert not [b for b in banned if b in text]
    old = crop_flip_oracle.lower(jax.random.key(0), x).as_text()
    assert "stablehlo.gather" in old or "dynamic_slice" in old


def test_ps_step_same_with_shipped_crop_and_oracle(mesh):
    """Three PS steps on Cifar10 with augmentation: the shipped crop and the
    per-image-slice oracle feed bit-identical images, so losses and
    parameters are equal, not close."""
    from ps_pytorch_tpu.models import build_model
    from ps_pytorch_tpu.optim import sgd
    from ps_pytorch_tpu.parallel import (
        PSConfig, init_ps_state, make_ps_train_step, shard_batch, shard_state,
    )

    mean, std = NORM_STATS["Cifar10"]

    def oracle_pre(key, images):
        return normalize(
            crop_flip_oracle(key, images, pad_mode=PAD_MODE["Cifar10"]), mean, std)

    ds = make_synthetic("Cifar10", train_size=96, test_size=16, seed=5)
    cfg = PSConfig(num_workers=8)
    model = build_model("LeNet")
    tx = sgd(0.05, momentum=0.9)
    runs = []
    for pre in (make_preprocessor("Cifar10", train=True), oracle_pre):
        state = shard_state(
            init_ps_state(model, tx, cfg, jax.random.key(0), (32, 32, 3)), mesh, cfg)
        step = make_ps_train_step(model, tx, cfg, mesh, preprocess=pre)
        it = BatchIterator(ds.train_images, ds.train_labels, batch_size=32, seed=0)
        losses = []
        for i, b in zip(range(3), it.forever()):
            state, m = step(state, shard_batch(b, mesh, cfg), jax.random.key(40 + i))
            losses.append(float(m["loss"]))
        runs.append((losses, jax.device_get(jax.tree_util.tree_leaves(state.params))))
    (l_new, p_new), (l_old, p_old) = runs
    assert l_new == l_old
    for a, b in zip(p_new, p_old):
        np.testing.assert_array_equal(a, b)


def test_preprocessor_train_vs_eval():
    ds = make_synthetic("Cifar10", train_size=64, test_size=16)
    x = jnp.asarray(ds.train_images[:4])
    train_fn = make_preprocessor("Cifar10", train=True)
    eval_fn = make_preprocessor("Cifar10", train=False)
    t1 = train_fn(jax.random.key(0), x)
    t2 = train_fn(jax.random.key(1), x)
    e1 = eval_fn(jax.random.key(0), x)
    e2 = eval_fn(jax.random.key(1), x)
    assert not jnp.array_equal(t1, t2)  # train path is stochastic
    assert jnp.array_equal(e1, e2)  # eval path ignores the key
    assert t1.dtype == jnp.float32


def test_batch_iterator_epoch():
    ds = make_synthetic("MNIST", train_size=100, test_size=10)
    it = BatchIterator(ds.train_images, ds.train_labels, batch_size=32, seed=1)
    batches = list(it.epoch())
    assert len(batches) == 3  # drop_last
    assert batches[0]["image"].shape == (32, 28, 28, 1)
    assert batches[0]["label"].shape == (32,)
    e1 = list(it.epoch())
    assert not np.array_equal(batches[0]["image"], e1[0]["image"])  # reshuffled


def test_batch_iterator_tiny_dataset_pads():
    ds = make_synthetic("MNIST", train_size=8, test_size=4)
    it = BatchIterator(ds.train_images, ds.train_labels, batch_size=32)
    batches = list(it.epoch())
    assert len(batches) == 1
    assert batches[0]["image"].shape[0] == 32


def test_shard_for_worker_modes():
    ds = make_synthetic("MNIST", train_size=128, test_size=8)
    # reshuffle: full data, distinct seeds
    x0, y0, s0 = shard_for_worker(ds.train_images, ds.train_labels, 0, 4)
    x1, y1, s1 = shard_for_worker(ds.train_images, ds.train_labels, 1, 4)
    assert len(x0) == len(x1) == 128 and s0 != s1
    # disjoint: true partition
    xs = [
        shard_for_worker(ds.train_images, ds.train_labels, w, 4, mode="disjoint")[0]
        for w in range(4)
    ]
    assert all(len(x) == 32 for x in xs)
    with pytest.raises(ValueError):
        shard_for_worker(ds.train_images, ds.train_labels, 0, 4, mode="bogus")


def test_prefetch_to_device():
    ds = make_synthetic("MNIST", train_size=64, test_size=8)
    it = BatchIterator(ds.train_images, ds.train_labels, batch_size=16)
    out = list(prefetch_to_device(it.epoch()))
    assert len(out) == 4
    assert isinstance(out[0]["image"], jax.Array)


def test_prefetch_to_device_with_sharding():
    """Passing a NamedSharding lands prefetched batches pre-split across
    the mesh (leading dim over the worker axis) — the train path's
    layout, no re-shard inside the step; values are untouched."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ps_pytorch_tpu.parallel.mesh import WORKER_AXIS, make_mesh

    ds = make_synthetic("MNIST", train_size=64, test_size=8)
    it = BatchIterator(ds.train_images, ds.train_labels, batch_size=16,
                       shuffle=False)
    mesh = make_mesh(num_workers=8)
    sharding = NamedSharding(mesh, P(WORKER_AXIS))
    out = list(prefetch_to_device(it.epoch(), device=sharding))
    assert len(out) == 4
    for b in out:
        assert b["image"].sharding.is_equivalent_to(sharding, b["image"].ndim)
        assert b["label"].sharding.is_equivalent_to(sharding, b["label"].ndim)
    np.testing.assert_array_equal(
        np.asarray(out[0]["image"]), ds.train_images[:16]
    )


def test_native_gather_matches_numpy():
    from ps_pytorch_tpu.data.loader import gather_rows

    rng = np.random.RandomState(0)
    arr = rng.randint(0, 255, (100, 7, 7, 3)).astype(np.uint8)
    idx = rng.permutation(100)[:32]
    np.testing.assert_array_equal(gather_rows(arr, idx), arr[idx])
    lbl = rng.randint(0, 10, 100).astype(np.int32)
    np.testing.assert_array_equal(gather_rows(lbl, idx), lbl[idx])


def test_native_gather_rejects_bad_index():
    # identical semantics on native and numpy paths: no wrapping, IndexError
    from ps_pytorch_tpu.data.loader import gather_rows

    arr = np.zeros((10, 4), np.float32)
    with pytest.raises(IndexError):
        gather_rows(arr, np.array([0, 10]))
    with pytest.raises(IndexError):
        gather_rows(arr, np.array([-1]))
