"""Weights and inputs made from --seed by the benchmark, on the device, in
one jitted call, in float32 (the type both trainers hold them in). The
program is given these; the reference makes the same ones by the same call
and takes nothing from the program.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

ONES = ("scale", "ln1", "ln2", "out_norm")
EMBED = ("embed", "pos_embed")


def _leaf_name(path) -> str:
    return "/".join(
        str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
        for k in path
    )


def leaf_names(tree):
    return [_leaf_name(p) for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def same_tree(have, want) -> bool:
    """Two trees with the same structure and the same leaf shapes."""
    shape = lambda t: jax.tree_util.tree_map(lambda x: tuple(x.shape), t)
    have, want = shape(have), shape(want)
    return (jax.tree_util.tree_structure(have) == jax.tree_util.tree_structure(want)
            and jax.tree_util.tree_leaves(have) == jax.tree_util.tree_leaves(want))


def make_weights(shapes, seed: int):
    """A float32 tree shaped like `shapes` (any tree of things with .shape):
    vectors named scale/ln*/out_norm are ones, other vectors zero, embedding
    tables normal x 0.02, conv kernels He-normal over fan-in, matrices
    normal / sqrt(fan-in). Each leaf's stream is keyed by its name, so the
    tree's order does not matter."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_leaf_name(p) for p, _ in flat]
    dims = [tuple(int(d) for d in leaf.shape) for _, leaf in flat]

    def build(key):
        out = []
        for name, shape in zip(names, dims):
            last = name.rsplit("/", 1)[-1]
            if len(shape) <= 1:
                fill = 1.0 if last in ONES else 0.0
                out.append(jnp.full(shape, fill, jnp.float32))
                continue
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            if last in EMBED:
                std = 0.02
            else:
                fan_in = math.prod(shape[:-1])
                std = math.sqrt((2.0 if len(shape) == 4 else 1.0) / fan_in)
            out.append(jax.random.normal(k, shape, jnp.float32) * std)
        return out

    leaves = jax.jit(build)(jax.random.key(seed % (2 ** 31 - 1)))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def leaf_norms(tree) -> jax.Array:
    """[n_leaves] float32 L2 norms, one device call."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for x in jax.tree_util.tree_leaves(tree)
    ])


def cifar_like(seed: int, n: int, classes: int = 10):
    """uint8 [n,32,32,3] images whose rows all differ, and int32 labels."""
    rng = np.random.default_rng(seed)
    images = rng.integers(0, 256, size=(n, 32, 32, 3), dtype=np.uint8)
    labels = rng.integers(0, classes, size=n).astype(np.int32)
    return images, labels


def token_rows(seed: int, n: int, seq_len: int, vocab: int) -> np.ndarray:
    """int32 [n, seq_len] uniform tokens; rows all differ."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, size=(n, seq_len), dtype=np.int32)
