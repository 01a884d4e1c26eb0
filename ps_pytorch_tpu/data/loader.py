"""Host-side batch iteration with device prefetch.

Replaces the reference's vendored multiprocessing DataLoader
(/root/reference/src/data_loader_ops/my_data_loader.py:254-319 — worker pool,
index/data queues, out-of-order reordering, pin-memory thread). On TPU the
datasets fit in host RAM as numpy arrays, so "loading" is an index gather;
the heavy lifting (augment/normalize) happens on-device (augment.py) and
`prefetch_to_device` keeps one batch in flight, which is the TPU-shaped
equivalent of the reference's pin-memory + worker prefetch machinery.

The reference shards data implicitly: every worker constructs its own
independently-shuffled DataLoader over the FULL dataset (distributed_nn.py:
each rank calls prepare_data; README.md:24 "no data is shipped"). `shard`
reproduces exactly that (seeded per-worker shuffles of the full set) while
`shard="disjoint"` offers the sane improvement (true partition).
"""

from __future__ import annotations

import collections
import ctypes
from typing import Iterator

import jax
import numpy as np


def gather_rows(array: np.ndarray, indices: np.ndarray) -> np.ndarray:
    """Batch assembly: array[indices] through the native threaded gather
    core (native/loader.cc — the reference's DataLoader worker pool reduced
    to its actual job, a parallel strided copy), with a numpy fallback.

    Index semantics are identical on both paths: out-of-range (including
    negative — no numpy wrapping) raises IndexError."""
    from ..ops.codec import _load

    idx = np.ascontiguousarray(indices, np.int64)
    if idx.size and (idx.min() < 0 or idx.max() >= len(array)):
        raise IndexError("gather index out of range")
    lib = _load()
    if (
        lib is None
        or array.nbytes == 0
        or not array.flags.c_contiguous
    ):
        return array[idx]
    item_bytes = array.dtype.itemsize * int(np.prod(array.shape[1:], dtype=np.int64))
    out = np.empty((len(idx),) + array.shape[1:], array.dtype)
    ok = lib.psl_gather(
        array.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        array.shape[0],
        item_bytes,
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(idx),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        0,
    )
    if not ok:
        raise IndexError("gather index out of range")
    return out


class BatchIterator:
    """Epoch-shuffled minibatch iterator over in-memory arrays.

    Yields dicts {"image": uint8 [B,H,W,C], "label": int32 [B]} as numpy.
    Drops the last partial batch (static shapes for jit).
    """

    def __init__(
        self,
        images: np.ndarray,
        labels: np.ndarray,
        batch_size: int,
        shuffle: bool = True,
        seed: int = 0,
        drop_last: bool = True,
    ):
        if len(images) < batch_size:
            # replicate up to one batch so tiny (test) datasets still yield
            reps = -(-batch_size // len(images))
            images = np.concatenate([images] * reps)
            labels = np.concatenate([labels] * reps)
        # contiguous once up front: the native gather needs C layout, and
        # doing it per batch would copy the whole dataset every iteration
        self.images = np.ascontiguousarray(images)
        self.labels = np.ascontiguousarray(labels)
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        self._rng = np.random.RandomState(seed)
        self._epoch = 0

    def __len__(self) -> int:
        n = len(self.images) // self.batch_size
        if not self.drop_last and len(self.images) % self.batch_size:
            n += 1
        return n

    @property
    def num_samples(self) -> int:
        return len(self.images)

    def epoch(self) -> Iterator[dict]:
        idx = np.arange(len(self.images))
        if self.shuffle:
            self._rng.shuffle(idx)
        self._epoch += 1
        for start in range(0, len(idx), self.batch_size):
            batch_idx = idx[start : start + self.batch_size]
            if len(batch_idx) < self.batch_size and self.drop_last:
                return
            yield {
                "image": gather_rows(self.images, batch_idx),
                "label": gather_rows(self.labels, batch_idx),
            }

    def __iter__(self):
        return self.epoch()

    def forever(self) -> Iterator[dict]:
        while True:
            yield from self.epoch()


def shard_for_worker(
    images: np.ndarray,
    labels: np.ndarray,
    worker_index: int,
    num_workers: int,
    mode: str = "reshuffle",
    seed: int = 0,
):
    """Per-worker data assignment.

    mode="reshuffle": reference parity — every worker sees the full dataset
    under its own shuffle seed (see module docstring).
    mode="disjoint": contiguous 1/num_workers partition (improvement).
    """
    if mode == "reshuffle":
        return images, labels, seed + worker_index * 1009
    if mode == "disjoint":
        n = len(images) // num_workers
        lo = worker_index * n
        return images[lo : lo + n], labels[lo : lo + n], seed
    raise ValueError(f"unknown shard mode {mode!r}")


_first_loader = True  # no loader has started in this process yet


def prefetch_to_device(
    iterator: Iterator[dict], size: int = 2, device=None, tracer=None
) -> Iterator[dict]:
    """Keep `size` batches ahead on device (reference's pin-memory analogue).

    ``device`` is anything ``jax.device_put`` accepts: None (default
    device — the single-device evaluator path), a concrete ``Device``,
    or a ``jax.sharding.Sharding`` (e.g. ``NamedSharding(mesh,
    P(axis))``) — with a sharding, prefetched batches land on the mesh
    ALREADY split across workers, so the train step consumes them
    directly instead of re-laying-out a replicated batch inside the
    step. A PartitionSpec shorter than a leaf's rank shards the leading
    (batch) dim and replicates the rest, which fits both the [B,H,W,C]
    images and the [B] labels.

    ``tracer`` (obs/trace.py) wraps each device_put dispatch in an
    ``h2d`` span carrying the ``bytes`` uploaded — dispatch walltime,
    not transfer completion: the transfer itself overlaps compute,
    which is the point of prefetching. The process's FIRST loader is also
    one ``setup.first_batch`` span of its set-up record (obs/trace.py),
    from the loader's start to its first batches queued; no later loader
    (the next epoch's, the next ``train()`` call's) records there."""
    global _first_loader
    from ..obs import NULL_TRACER, setup_tracer

    queue = collections.deque()
    if tracer is None:
        tracer = NULL_TRACER
    setup = setup_tracer() if _first_loader else NULL_TRACER
    _first_loader = False

    def enqueue(n):
        for _ in range(n):
            batch = next(iterator, None)
            if batch is None:
                return
            attrs = {}
            if tracer.enabled:
                attrs["bytes"] = sum(
                    getattr(x, "nbytes", 0)
                    for x in jax.tree_util.tree_leaves(batch)
                )
            with tracer.span("h2d", **attrs):
                queue.append(jax.device_put(batch, device))

    with setup.span("setup.first_batch"):
        enqueue(size)
    while queue:
        yield queue.popleft()
        enqueue(1)
