"""Device-mesh construction — the TPU-native replacement for the reference's
MPI star topology (mpirun + hostfile, /root/reference/src/run_pytorch.sh:1-16,
tools/pytorch_ec2.py).

One mesh axis, `workers`, plays the role of the reference's MPI worker ranks;
the parameter server is not a separate rank but a *protocol* over the mesh
(see parallel/ps.py): params replicated (the "bcast"), gradients psum'd (the
"gather+aggregate"), optimizer state replicated or sharded (the "PS chip",
generalized). Multi-host extends the same axis over DCN via jax.distributed.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

WORKER_AXIS = "workers"


def make_mesh(
    num_workers: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    axis_name: str = WORKER_AXIS,
) -> Mesh:
    """Build a 1-D mesh of `num_workers` devices (default: all devices).

    Unlike the reference — where cluster size is fixed at mpirun time by the
    hostfile (run_pytorch.sh:1) — the same process can carve any leading
    subset of visible chips into a worker mesh.
    """
    devs = list(devices if devices is not None else jax.devices())
    n = num_workers or len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} workers but only {len(devs)} devices")
    return Mesh(np.array(devs[:n]), (axis_name,))


DCN_AXIS = "dcn"


def make_hybrid_mesh(
    num_hosts: Optional[int] = None,
    per_host: Optional[int] = None,
    axis_names: tuple = (DCN_AXIS, WORKER_AXIS),
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """2-level (hosts x chips-per-host) mesh for multi-host PS training:
    the outer axis crosses DCN, the inner axis stays on ICI.

    Use with `PSConfig(axis_name=(DCN_AXIS, WORKER_AXIS), num_workers=
    total_chips)`: every collective in the PS engine takes the axis-name
    tuple, so gradient aggregation psums hierarchically — XLA reduces
    within each host over ICI first and crosses DCN once with the partial
    sums, which is exactly the traffic layout the reference's star
    topology cannot express (every worker's full gradient crossed the
    network to the PS, SURVEY.md section 2 #2).

    On a real pod (jax.process_count() > 1) device placement comes from
    mesh_utils.create_hybrid_device_mesh; single-process (tests, one
    host) falls back to a reshape of the flat device list.
    """
    devs = list(devices if devices is not None else jax.devices())
    n_hosts = num_hosts or jax.process_count()
    per = per_host or len(devs) // n_hosts
    need = n_hosts * per
    if need > len(devs) or per < 1:
        raise ValueError(
            f"need {n_hosts} hosts x {max(per, 1)} chips, have {len(devs)} devices"
        )
    if jax.process_count() > 1 and devices is None:
        # subsets must stay balanced PER HOST: take the leading `per` chips
        # of each of the first n_hosts processes (a flat devs[:need] slice
        # would take all of host 0 first and leave later hosts empty)
        by_host: dict = {}
        for d in devs:
            by_host.setdefault(d.process_index, []).append(d)
        hosts = sorted(by_host)[:n_hosts]
        if any(len(by_host[h]) < per for h in hosts) or len(hosts) < n_hosts:
            raise ValueError(
                f"need {n_hosts} hosts x {per} chips, have "
                f"{ {h: len(v) for h, v in by_host.items()} }"
            )
        picked = [d for h in hosts for d in by_host[h][:per]]
        from jax.experimental import mesh_utils

        # granule = process (host), matching this function's contract
        grid = mesh_utils.create_hybrid_device_mesh(
            (1, per), (n_hosts, 1), devices=picked, process_is_granule=True
        )
    else:
        grid = np.array(devs[:need]).reshape(n_hosts, per)
    return Mesh(grid, axis_names)


def place_on_mesh(tree, mesh: Mesh, specs):
    """Place every leaf of `tree` on `mesh` with its PartitionSpec from
    `specs` (a matching pytree of PartitionSpecs). None leaves (e.g. a
    momentum-free optimizer's buffer slot) pass through untouched.

    The single implementation behind shard_params_{tp,pp,moe},
    init_{tp,pp,moe}_state, and checkpoint.restore_sharded.
    """
    return jax.tree_util.tree_map(
        lambda x, s: None if x is None else jax.device_put(x, NamedSharding(mesh, s)),
        tree,
        specs,
        is_leaf=lambda x: x is None,
    )


def batch_sharding(mesh: Mesh, axis_name: str = WORKER_AXIS) -> NamedSharding:
    """Sharding for a global batch: split along the leading (batch) dim."""
    return NamedSharding(mesh, P(axis_name))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def worker_stacked_sharding(mesh: Mesh, axis_name: str = WORKER_AXIS) -> NamedSharding:
    """Sharding for per-worker state stacked on a leading axis of size
    num_workers (used for `bn_mode='local'` BatchNorm stats)."""
    return NamedSharding(mesh, P(axis_name))


def pool_sharding(mesh: Mesh, dim: int = 1,
                  axis_name: str = WORKER_AXIS) -> NamedSharding:
    """Sharding that splits dimension ``dim`` of a pooled buffer over the
    worker axis. The serving engine's KV pool is [depth, slots, ...] —
    slots (dim 1) shard across the mesh while depth stays whole, so every
    worker owns a contiguous band of request slots and the decode step is
    embarrassingly slot-parallel (zero collectives, see serve/engine.py)."""
    return NamedSharding(mesh, P(*([None] * dim), axis_name))


def initialize_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Join a multi-host training job over DCN (replaces mpirun's process
    spawn + rendezvous, run_pytorch.sh:1). No-op for single-process runs.

    Pass "auto" on Cloud TPU pods: jax.distributed.initialize() with no
    arguments discovers the coordinator and process ids from the TPU
    metadata service — every host runs the identical command (tools/
    run_multihost.sh relies on this).

    CPU backend note (the 2-process localhost jobs tests/test_multihost.py
    spawns): cross-process CPU collectives ride jaxlib's gloo TCP
    transport (the default ``jax_cpu_collectives_implementation``), whose
    pairs match ops by FIFO order — a multi-process job that is
    explicitly pinned to CPU therefore turns async dispatch off before
    the backend is created (see below). Must run before anything touches
    ``jax.devices()`` (backend creation reads the flag once).

    SPMD contract: every process runs this with the same effective
    arguments, and everything downstream (mesh construction, the train
    loop's collectives) assumes bit-identical control flow across hosts.
    Host code in this module is in psdiverge's scope — guards derived
    from per-process values around collective ops are flagged as PSL006
    (ARCHITECTURE §7b); the env-var gate above stays clean because it
    guards only process-local jax.config writes, never a collective."""
    if coordinator_address is None:
        return
    import os

    plats = {
        p.strip().lower()
        for p in os.environ.get("JAX_PLATFORMS", "").split(",")
        if p.strip()
    }
    if "cpu" in plats:
        # (unset JAX_PLATFORMS is left alone: a TPU pod runs that way,
        # and perturbing its cpu client config for a backend it never
        # uses for collectives buys nothing)
        # gloo TCP pairs match ops by FIFO order, not tags: with async
        # dispatch two in-flight XLA computations (a train step and a
        # host-collective psum, or a prefetch device_put's assert_equal
        # broadcast) interleave their sends nondeterministically PER
        # PROCESS, and a cross-process order mismatch aborts with
        # gloo::EnforceNotMet ("op.preamble.length <= op.nbytes").
        # Inline dispatch serializes each process's ops into strict
        # program order — identical on every process by SPMD. CPU
        # multiprocess is a test/dev topology; the throughput cost is
        # irrelevant there.
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    if coordinator_address == "auto":
        jax.distributed.initialize()
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
