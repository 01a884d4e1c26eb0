"""On-device batch preprocessing: normalize + (pad, random-crop, random-flip).

Replaces the reference's host-side PIL transform pipeline
(/root/reference/src/util.py:37-47: 4px reflect pad -> RandomCrop(32) ->
RandomHorizontalFlip -> normalize) with jit-compiled batched jax ops, so
augmentation rides the accelerator instead of Python workers
(src/data_loader_ops/my_data_loader.py's multiprocessing pool).

The random crop takes no per-image slice: a `dynamic_slice` under `vmap` is a
gather, which the TPU compiler runs as a loop of one turn an image (34 ms of
a 130 ms ResNet step at batch 2048), so each axis instead selects among its
`2 * pad + 1` static slices of the whole batch.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def normalize(images: jax.Array, mean: np.ndarray, std: np.ndarray) -> jax.Array:
    """uint8 [N,H,W,C] -> normalized f32 (parity: transforms.Normalize)."""
    x = images.astype(jnp.float32) / 255.0
    return (x - jnp.asarray(mean, jnp.float32)) / jnp.asarray(std, jnp.float32)


def _shifted(x: jax.Array, offs: jax.Array, axis: int, size: int) -> jax.Array:
    """`x[i, ..., offs[i] : offs[i] + size, ...]` along `axis` for every row
    `i` of the batch: one `where` per possible offset over static slices of
    the whole batch, which XLA fuses into a single pass."""
    offs = offs.reshape((-1,) + (1,) * (x.ndim - 1))
    out = jax.lax.slice_in_dim(x, 0, size, axis=axis)
    for k in range(1, x.shape[axis] - size + 1):
        out = jnp.where(
            offs == k, jax.lax.slice_in_dim(x, k, k + size, axis=axis), out
        )
    return out


@partial(jax.jit, static_argnames=("pad", "pad_mode"))
def random_crop_flip(
    key: jax.Array, images: jax.Array, pad: int = 4, pad_mode: str = "reflect"
) -> jax.Array:
    """Batched 4px-pad + random crop back to original size + random hflip."""
    n, h, w, _ = images.shape
    kc, kf = jax.random.split(key)
    padded = jnp.pad(
        images, ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode=pad_mode
    )
    offs = jax.random.randint(kc, (n, 2), 0, 2 * pad + 1)
    cropped = _shifted(_shifted(padded, offs[:, 0], 1, h), offs[:, 1], 2, w)
    flip = jax.random.bernoulli(kf, 0.5, (n,))
    flipped = jnp.where(flip[:, None, None, None], cropped[:, :, ::-1, :], cropped)
    return flipped


def preprocess_batch(
    key: jax.Array,
    images: jax.Array,
    mean: np.ndarray,
    std: np.ndarray,
    augment: bool,
    pad_mode: str = "reflect",
) -> jax.Array:
    """Full train/eval preprocessing. `augment=False` = test-path transform."""
    if augment:
        images = random_crop_flip(key, images, pad_mode=pad_mode)
    return normalize(images, mean, std)


def make_preprocessor(dataset_name: str, train: bool):
    """Returns fn(key, uint8_images) -> f32 images for the named dataset,
    with the reference's per-dataset augmentation policy baked in."""
    from .datasets import AUGMENT, NORM_STATS, PAD_MODE

    mean, std = NORM_STATS[dataset_name]
    augment = train and AUGMENT[dataset_name]
    pad_mode = PAD_MODE.get(dataset_name, "reflect")

    def fn(key: jax.Array, images: jax.Array) -> jax.Array:
        return preprocess_batch(key, images, mean, std, augment, pad_mode)

    return fn
