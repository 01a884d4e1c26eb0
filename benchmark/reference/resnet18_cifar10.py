"""Plain reference of `resnet18_cifar10`: the CIFAR ResNet-18 of the source
system (3x3 stem, four stages of two basic blocks at 64/128/256/512 planes,
4x4 average pool, linear head), its cross-entropy loss, gradients and the
PyTorch-form SGD with momentum, in float32 jax.numpy with every product at
precision "highest". Batch norm uses the statistics of the rows it is given
(train mode), so a worker's rows are one call; the gradient of several
workers is the mean of theirs, which is what a parameter server applies.

The feed is part of the traffic: rows are drawn from the seed by the
benchmark, and the pad-4 / random-crop / random-flip augmentation is drawn
here from the same counter-based streams the trainer's step uses (key of
seed+1, folded with the step and the worker), written out below so that the
two see the same pixels. A PR that changes those draws changes the traffic
and needs a benchmark PR.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
STAGES = ((64, 1), (128, 2), (256, 2), (512, 2))
BLOCKS_PER_STAGE = 2
BN_EPS = 1e-5


def param_shapes(cfg: dict) -> dict:
    """Names and shapes of the parameters (flax's naming of the layers)."""
    S = jax.ShapeDtypeStruct
    f32 = jnp.float32

    def bn(c):
        return {"bias": S((c,), f32), "scale": S((c,), f32)}

    tree = {"Conv_0": {"kernel": S((3, 3, 3, 64), f32)}, "BatchNorm_0": bn(64)}
    cin, idx = 64, 0
    for planes, stride in STAGES:
        for i in range(BLOCKS_PER_STAGE):
            s = stride if i == 0 else 1
            blk = {
                "Conv_0": {"kernel": S((3, 3, cin, planes), f32)},
                "BatchNorm_0": bn(planes),
                "Conv_1": {"kernel": S((3, 3, planes, planes), f32)},
                "BatchNorm_1": bn(planes),
            }
            if s != 1 or cin != planes:
                blk["Conv_2"] = {"kernel": S((1, 1, cin, planes), f32)}
                blk["BatchNorm_2"] = bn(planes)
            tree[f"BasicBlock_{idx}"] = blk
            cin, idx = planes, idx + 1
    tree["Dense_0"] = {
        "kernel": S((512, int(cfg["num_classes"])), f32),
        "bias": S((int(cfg["num_classes"]),), f32),
    }
    return tree


def _cast(a, operand):
    return a if operand is None else a.astype(operand).astype(jnp.float32)


def _conv(x, w, stride, operand=None):
    pad = (w.shape[0] - 1) // 2
    return lax.conv_general_dilated(
        _cast(x, operand), _cast(w, operand), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI,
    )


def _bn(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]


def _block(x, p, stride, operand):
    out = jax.nn.relu(_bn(_conv(x, p["Conv_0"]["kernel"], stride, operand), p["BatchNorm_0"]))
    out = _bn(_conv(out, p["Conv_1"]["kernel"], 1, operand), p["BatchNorm_1"])
    if "Conv_2" in p:
        x = _bn(_conv(x, p["Conv_2"]["kernel"], stride, operand), p["BatchNorm_2"])
    return jax.nn.relu(out + x)


def logits_fn(params, x, operand=None):
    """`operand` is the control's switch: both operands of every product
    rounded to that type first (float8_e4m3fn)."""
    x = jax.nn.relu(_bn(_conv(x, params["Conv_0"]["kernel"], 1, operand), params["BatchNorm_0"]))
    idx = 0
    for _, stride in STAGES:
        for i in range(BLOCKS_PER_STAGE):
            # remat: only block inputs are kept for the backward pass, so the
            # timed batch fits beside nothing else on one chip
            x = jax.checkpoint(_block, static_argnums=(2, 3))(
                x, params[f"BasicBlock_{idx}"], stride if i == 0 else 1, operand)
            idx += 1
    x = jnp.mean(x, axis=(1, 2))  # 4x4 average pool of a 4x4 map
    return jnp.dot(_cast(x, operand), _cast(params["Dense_0"]["kernel"], operand),
                   precision=HI) + params["Dense_0"]["bias"]


def loss_fn(params, x, labels, operand=None):
    logp = jax.nn.log_softmax(logits_fn(params, x, operand), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def augment(images_u8, key, cfg: dict):
    """Reflect-pad 4, random crop back to 32x32, random horizontal flip,
    then normalise: uint8 [n,32,32,3] -> float32."""
    n, h, w, c = images_u8.shape
    pad = 4
    kc, kf = jax.random.split(key)
    padded = jnp.pad(images_u8, ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode="reflect")
    offs = jax.random.randint(kc, (n, 2), 0, 2 * pad + 1)
    crop = jax.vmap(lambda img, o: lax.dynamic_slice(img, (o[0], o[1], 0), (h, w, c)))
    x = crop(padded, offs)
    flip = jax.random.bernoulli(kf, 0.5, (n,))
    x = jnp.where(flip[:, None, None, None], x[:, :, ::-1, :], x)
    mean = jnp.asarray(cfg["norm_mean"], jnp.float32)
    std = jnp.asarray(cfg["norm_std"], jnp.float32)
    return (x.astype(jnp.float32) / 255.0 - mean) / std


def aug_key(feed_seed: int, step_idx: int, worker: int):
    k_step = jax.random.fold_in(jax.random.key(feed_seed + 1), step_idx)
    k_aug, _ = jax.random.split(jax.random.fold_in(k_step, worker + 1))
    return k_aug


def train_steps(cfg: dict, traffic: dict, make_params, feed: dict,
                n_steps: int = 3, devices=None, operand=None):
    """Follows the first `n_steps` steps from the weights `make_params()`
    gives. feed: {"images": uint8 [N,...],
    "labels": int32 [N], "rows": int [steps, workers, batch] row numbers,
    "feed_seed": int}. Returns losses, the first gradient's norm per leaf
    and the norm of the parameters' change per leaf."""
    from benchmark.weights import leaf_names, leaf_norms

    lr, mom = float(traffic["lr"]), float(traffic["momentum"])
    devices = list(devices or jax.devices()[:1])
    operand = None if operand is None else jnp.dtype(operand)

    def worker_grad(p, images, labels, key):
        return jax.value_and_grad(loss_fn)(p, augment(images, key, cfg), labels, operand)

    workers = np.asarray(feed["rows"]).shape[1]
    if workers > 1 and len(devices) == workers:
        # each worker's rows on a chip of its own, as ONE program (one
        # compilation, one cache entry): placement only, the arithmetic of a
        # worker is the same call as on one chip
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(devices), ("w",))

        def on_my_chip(p, images, labels, key_data):
            loss, g = worker_grad(p, images[0], labels[0],
                                  jax.random.wrap_key_data(key_data[0]))
            return loss[None], jax.tree_util.tree_map(lambda t: t[None], g)

        stacked = jax.shard_map(on_my_chip, mesh=mesh, check_vma=False,
                                in_specs=(P(), P("w"), P("w"), P("w")),
                                out_specs=P("w"))

        @jax.jit
        def all_workers(p, images, labels, key_data):
            loss, g = stacked(p, images, labels, key_data)
            return jnp.mean(loss), jax.tree_util.tree_map(lambda t: jnp.mean(t, 0), g)
    else:
        one = jax.jit(worker_grad)

        def all_workers(p, images, labels, key_data):
            parts = [one(p, images[w], labels[w], jax.random.wrap_key_data(key_data[w]))
                     for w in range(workers)]
            loss = sum(l for l, _ in parts) / workers
            return loss, jax.tree_util.tree_map(lambda *t: sum(t) / workers,
                                                *[g for _, g in parts])

    @jax.jit
    def update(p, buf, g, first):
        buf = jax.tree_util.tree_map(
            lambda b, d: jnp.where(first, d, mom * b + d), buf, g)
        return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, buf), buf

    params = p0 = make_params()
    buf = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    rows = np.asarray(feed["rows"])
    for s in range(n_steps):
        idx = rows[s]
        key_data = jnp.stack([jax.random.key_data(aug_key(int(feed["feed_seed"]), s, w))
                              for w in range(workers)])
        loss, g = all_workers(params, feed["images"][idx], feed["labels"][idx], key_data)
        losses.append(float(loss))
        if s == 0:
            grad_norms = np.asarray(leaf_norms(g)).tolist()
        params, buf = update(params, buf, g, s == 0)
    dparam = np.asarray(leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, params, p0))).tolist()
    return {"loss": losses, "grad_norms": grad_norms, "dparam_norms": dparam,
            "leaf_names": leaf_names(params)}
