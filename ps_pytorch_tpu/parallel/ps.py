"""The parameter-server data-parallel engine — shard_map over a device mesh.

This is the TPU-native re-design of the reference's L4 scheduler layer
(SURVEY.md sections 1-3): `SyncReplicasMaster_NN.start()`'s bcast/gather/
aggregate/step loop (sync_replicas_master_nn.py:133-197) and
`DistributedWorker.train()`'s fetch/forward/backward/send loop
(distributed_worker.py:104-180) collapse into ONE jitted SPMD step:

  reference protocol                      this engine
  ------------------------------------    -----------------------------------
  master bcasts step (tag 10)             XLA synchronous dispatch (implicit)
  master bcasts weights per layer         params replicated on the mesh
  worker forward/backward                 per-shard value_and_grad
  worker per-layer Isend (tag 88+l)       lax.psum / psum_scatter over ICI
  master waitany + partial aggregate      aggregation_mask + psum (collectives)
  master in-tree SGD step / num_agg       optax update, replicated or ZeRO-1
  worker BN stats stay local              bn_mode = local | pmean | synced
  Blosc codec                             int8 quantized collective (Pallas)

Optimizer placement ("where does the PS live"):
- "replicated": every chip applies the identical update — mathematically the
  reference's PS update broadcast to everyone, with zero extra comm.
- "sharded": ZeRO-1-style — gradients reduce_scatter to 1/N shards, each chip
  updates its shard of optimizer state, params all_gather back. This IS the
  parameter server, sharded across the mesh instead of parked on rank 0
  (and it cuts optimizer memory + aggregate bandwidth vs. the star topology).

BatchNorm modes (reference keeps per-worker BN stats and never syncs them —
distributed_worker.py:239-252):
- "local":  strict parity — stats stored per worker (stacked leading axis).
- "pmean":  stats averaged across workers each step (sane default).
- "synced": cross-replica BN (build the model with bn_axis_name=axis).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple, Union

import flax.struct
import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import apply_model
from ..obs.scopes import AUGMENT, GRAD_REDUCE, MODEL, UPDATE, ScopedStep, scope, stamped
from ..obs.trace import setup_span
from ..ops.metrics import accuracy, cross_entropy_loss
from ..ops.quantize import accum_dtype, dequantize_int8, quantize_int8
from ..resilience.guard import (
    init_guard_state,
    tree_all_finite,
    update_guard_state,
)
from .buckets import (
    BucketPlan,
    FlatVector,
    assemble_bucket,
    bucket_leaf_segments,
    concat_buckets,
    pad_flat,
    plan_buckets,
    readiness_bucket_order,
    to_flat_vector,
    tree_layout,
    tree_to_flat,
    tree_view,
)
from .collectives import aggregate_gradients, aggregation_mask
from .mesh import WORKER_AXIS

tree_map = jax.tree_util.tree_map
# the name obs/scopes.last_step finds this module's step under
PS_TRAIN_STEP = "ps_train_step"


@dataclasses.dataclass(frozen=True)
class PSConfig:
    """Knobs mirroring the reference CLI (distributed_nn.py:24-68) plus the
    TPU-native extensions. `num_aggregate` <-> --num-aggregate; `compress`
    <-> --compress-grad; `mask_mode='random_k'` emulates aggregating the
    first K gradients to *arrive* (arrival order is nondeterministic)."""

    num_workers: int
    # a single mesh axis, or a TUPLE of axes for hierarchical (multi-host)
    # data parallelism — e.g. (DCN_AXIS, WORKER_AXIS) over make_hybrid_mesh,
    # where num_workers is the TOTAL chip count across hosts. Every
    # collective in the engine accepts the tuple form.
    axis_name: Union[str, Tuple[str, ...]] = WORKER_AXIS
    num_aggregate: Optional[int] = None
    mask_mode: str = "random_k"
    # adaptive partial aggregation (resilience/elastic.py): when BOTH
    # bounds are set, the train step takes a traced int32 ``agg_count``
    # argument and the host picks next window's count from observed
    # step-time statistics inside [min, max] — the reference's static
    # backup-worker knob generalized to ACE-Sync-style adaptive sync.
    # ``num_aggregate`` then only seeds the initial count (default: max).
    # The masking/denominator math is identical to the static path; a
    # full-count window multiplies by exactly 1.0 and divides by exactly
    # num_workers, so it is bit-exact against num_aggregate=None on
    # power-of-two meshes.
    num_aggregate_min: Optional[int] = None
    num_aggregate_max: Optional[int] = None
    # None | "int8" (int32-psum of int8 payloads: exact sum, compute-side
    # compression) | "int8_2round" (all_to_all + requantize + all_gather:
    # the wire itself carries int8 — a true ~4x bandwidth reduction, one
    # extra bounded quantization on the partial sums; collectives.
    # quantized_allreduce_2round)
    compress: Optional[str] = None
    quant_block_size: int = 0
    quant_rounding: str = "nearest"  # "nearest" | "stochastic" (unbiased)
    # WHAT the aggregation sums (--wire-domain): "dequant" (default) is
    # the committed-contract wire — each hop widens the quantized payload
    # to f32 to add and requantizes to ship. "homomorphic" (THC/DynamiQ,
    # PAPERS.md) sums in the COMPRESSED domain: workers agree on shared
    # per-bucket scales via the one tiny max-abs reduction the quantizer
    # already pays, payloads accumulate exactly in the minimal integer
    # dtype (ops/quantize.accum_dtype pins the no-overflow bound), every
    # wire hop carries int8/int16 — the "int8" psum halves (int16 vs
    # int32), the 2-round gather hop drops its round-2 requantization
    # and scale rows, and the hierarchical DCN x ICI path forwards
    # lattice payloads across every hop (the f32 ICI reassembly becomes
    # int8: 4x) — and dequantization defers to ONE scale-multiply per
    # bucket at the consumer (the ZeRO-1 placement dequantizes only its
    # own shard region). Needs a compress mode and nearest rounding;
    # shared scales are COARSER than per-worker scales, so parity vs the
    # dequant wire is an envelope (EF absorbs the difference), while the
    # integer accumulation itself is bit-exact.
    wire_domain: str = "dequant"
    # gradient wire granularity (parallel/buckets.py): None = legacy
    # message-per-leaf collectives (the reference's tag-88+l shape), 0 =
    # ONE fused flat f32 buffer, N = ~N-byte contiguous buckets with
    # boundaries aligned to the int8 quantization block — O(n_buckets)
    # collectives per step instead of O(n_leaves). The ZeRO-1 sharded
    # placement's wire is flat by construction; there None and 0 are the
    # same fused buffer and N>0 carves the scatter into buckets. With
    # bucketing on, the non-finite guard reduces ONE fused isfinite over
    # the flat buffer instead of one per leaf.
    bucket_bytes: Optional[int] = None
    # WHEN the wire moves (--overlap on|off): "serial" (default) reduces
    # after the whole backward — the committed-contract baseline schedule.
    # "pipelined" launches each bucket's collective as soon as its
    # leaves' gradients exist: buckets are assembled from their own leaf
    # fragments (no global-concat false dependency), streamed in
    # readiness order (reverse-topological bucket enumeration: the last
    # bucket's leaves backprop first), reduced by per-bucket collective
    # eqns, and consumed by PER-BUCKET optimizer updates (the state is
    # flat, in the wire's own geometry) as reductions land, so XLA's
    # latency-hiding scheduler can interleave the wire with the
    # remaining backward AND the update. Same buckets, same bytes,
    # bit-identical values (PRNG keys fold bucket START OFFSETS, so the
    # reordered enumeration draws identical noise; PSC109 pins byte
    # equality against the serial twin). The per-bucket update requires elementwise optimizer
    # transforms with per-parameter state (the repo's sgd/adam families;
    # a global-norm-coupled transform would need the whole vector).
    overlap: str = "serial"
    # error feedback (EF-SGD): each worker keeps the residual its
    # compression dropped and adds it back next step, so quantization
    # error accumulates into the update instead of being lost — the
    # standard convergence fix for aggressive compression. Requires a
    # compress mode. Works with both placements: replicated keeps
    # per-leaf residuals; the ZeRO-1 sharded placement keeps the residual
    # on the flat padded gradient vector (same wire transform, same
    # accounting). With quant_rounding="stochastic" + "int8_2round" the
    # residual is approximate (padding changes the noise draw); pair EF
    # with "nearest" for the exact on-wire residual.
    error_feedback: bool = False
    opt_placement: str = "replicated"  # "replicated" | "sharded"
    bn_mode: str = "pmean"  # "local" | "pmean" | "synced"
    # microbatches per step, accumulated in an in-step lax.scan: scales the
    # effective per-worker batch beyond HBM without touching the protocol
    # (the reference can only shrink the batch; SURVEY section 6 shows its
    # b=4096 runs were its scaling ceiling)
    grad_accum_steps: int = 1
    # >1 = hierarchical data parallelism over a (hosts x chips) hybrid mesh
    # (mesh.make_hybrid_mesh): axis_name is promoted to the axis tuple so
    # aggregation reduces over ICI within a host before crossing DCN once
    dcn_hosts: int = 1
    # non-finite gradient guard (resilience/guard.py): one int32 pmin
    # agrees mesh-wide that every worker's gradients are finite; a bad
    # step applies the identity update instead of the optimizer, counted
    # in GuardState (checkpointed with the state). Default ON — the int8
    # wire formats make overflow/NaN a when, not an if.
    nonfinite_guard: bool = True
    # dynamic loss scaling (grow-on-success / back-off-on-overflow) for
    # the compressed wire formats; requires the guard (the skip IS the
    # overflow handler) and a compress mode (uncompressed f32 psum has
    # f32 headroom and doesn't need it)
    dynamic_loss_scale: bool = False
    loss_scale_init: float = 2.0 ** 15
    loss_scale_growth_interval: int = 2000

    def __post_init__(self):
        if self.dcn_hosts > 1:
            if self.num_workers % self.dcn_hosts:
                raise ValueError(
                    f"num_workers {self.num_workers} not divisible by "
                    f"dcn_hosts {self.dcn_hosts}"
                )
            if isinstance(self.axis_name, str):
                from .mesh import DCN_AXIS

                # frozen dataclass: promote the axis via object.__setattr__
                object.__setattr__(
                    self, "axis_name", (DCN_AXIS, self.axis_name)
                )
        if self.grad_accum_steps < 1:
            raise ValueError(f"bad grad_accum_steps {self.grad_accum_steps}")
        if self.opt_placement not in ("replicated", "sharded"):
            raise ValueError(f"bad opt_placement {self.opt_placement!r}")
        if self.bn_mode not in ("local", "pmean", "synced"):
            raise ValueError(f"bad bn_mode {self.bn_mode!r}")
        if self.compress not in (None, "none", "int8", "int8_2round"):
            raise ValueError(f"bad compress {self.compress!r}")
        if self.quant_rounding not in ("nearest", "stochastic"):
            raise ValueError(f"bad quant_rounding {self.quant_rounding!r}")
        if self.overlap not in ("serial", "pipelined"):
            raise ValueError(
                f"bad overlap {self.overlap!r} (serial | pipelined)"
            )
        if (
            self.overlap == "pipelined"
            and self.bucket_bytes is None
            and self.opt_placement != "sharded"
        ):
            # the pipelined schedule is a property of the BUCKETED wire;
            # on the replicated per-leaf wire it would silently un-fuse
            # the whole-tree psum back into one eqn per leaf (the exact
            # shape bucketing exists to avoid). The ZeRO-1 wire is flat
            # by construction (None == one fused bucket there), so it
            # pipelines fine without the knob.
            raise ValueError(
                "overlap='pipelined' needs a bucketed wire: set "
                "bucket_bytes (0 = one fused buffer, N = ~N-byte "
                "buckets) — the replicated per-leaf wire has no buckets "
                "to stream"
            )
        if self.bucket_bytes is not None and self.bucket_bytes < 0:
            raise ValueError(
                f"bad bucket_bytes {self.bucket_bytes} (None = per-leaf, "
                f"0 = one fused buffer, N>0 = ~N-byte buckets)"
            )
        if self.wire_domain not in ("dequant", "homomorphic"):
            raise ValueError(
                f"bad wire_domain {self.wire_domain!r} "
                f"(dequant | homomorphic)"
            )
        if self.wire_domain == "homomorphic":
            if self.compress in (None, "none"):
                raise ValueError(
                    "wire_domain='homomorphic' needs a compress mode "
                    "(--compress-grad compress|2round): an uncompressed "
                    "f32 psum has nothing to homomorphically sum"
                )
            if self.quant_rounding == "stochastic":
                raise ValueError(
                    "wire_domain='homomorphic' needs "
                    "quant_rounding='nearest': shared scales put every "
                    "worker on ONE lattice, and the per-worker-seeded "
                    "stochastic draws (keys fold the worker index by "
                    "design) have no coherent meaning under the "
                    "compressed-domain rescale — there is no "
                    "identically-seeded mode to opt into"
                )
            # the exact-accumulation bound: raises past the int32
            # capacity (ops/quantize.ACCUM_CAPACITY) so overflow is a
            # config error, never a silent wrap
            accum_dtype(self.num_workers)
        if self.error_feedback and self.compress in (None, "none"):
            raise ValueError("error_feedback needs a compress mode")
        if self.dynamic_loss_scale:
            if self.compress in (None, "none"):
                raise ValueError("dynamic_loss_scale needs a compress mode")
            if not self.nonfinite_guard:
                raise ValueError(
                    "dynamic_loss_scale needs nonfinite_guard (the skip "
                    "step is the overflow back-off trigger)"
                )
        if self.loss_scale_growth_interval < 1:
            raise ValueError(
                f"bad loss_scale_growth_interval "
                f"{self.loss_scale_growth_interval}"
            )
        if (self.num_aggregate_min is None) != (self.num_aggregate_max is None):
            raise ValueError(
                "adaptive aggregation needs BOTH num_aggregate_min and "
                "num_aggregate_max (set neither for the static mask)"
            )
        if self.num_aggregate_min is not None:
            if not (1 <= self.num_aggregate_min <= self.num_aggregate_max
                    <= self.num_workers):
                raise ValueError(
                    f"bad adaptive bounds [{self.num_aggregate_min}, "
                    f"{self.num_aggregate_max}]: need 1 <= min <= max <= "
                    f"num_workers ({self.num_workers})"
                )
            if self.num_aggregate is not None and not (
                self.num_aggregate_min <= self.num_aggregate
                <= self.num_aggregate_max
            ):
                raise ValueError(
                    f"num_aggregate {self.num_aggregate} (the initial "
                    f"adaptive count) is outside the declared bounds "
                    f"[{self.num_aggregate_min}, {self.num_aggregate_max}]"
                )
        if self.loss_scale_init <= 0.0:
            # scale 0 zeroes the loss and the unscale divides by it: every
            # step overflows and the guard aborts blaming the DATA
            raise ValueError(
                f"bad loss_scale_init {self.loss_scale_init} (must be > 0)"
            )
        if (
            self.compress == "int8_2round"
            and self.opt_placement == "sharded"
            and (
                self.dcn_hosts > 1
                or isinstance(self.axis_name, (tuple, list))
            )
        ):
            # design note, not a TODO: the sharded placement's gradient
            # wire is a single reduce_scatter over the full axis tuple;
            # an int8 all_to_all over a product of DCN x ICI axes has no
            # hierarchical routing to exploit (each chip's region still
            # crosses DCN once either way). Use compress="int8" (int32
            # psum_scatter) for sharded+DCN.
            raise ValueError(
                "int8_2round x sharded x dcn_hosts>1 is unsupported: the "
                "sharded wire is one reduce_scatter over the whole mesh, "
                "so there is no hierarchical structure for the 2-round "
                "scheme to exploit — use compress='int8' there"
            )

    @property
    def effective_aggregate(self) -> int:
        if self.num_aggregate is None or self.num_aggregate >= self.num_workers:
            return self.num_workers
        return self.num_aggregate

    @property
    def adaptive_aggregate(self) -> bool:
        """True when the train step takes a traced per-window aggregation
        count (``step(state, batch, key, agg_count)``) instead of baking
        ``num_aggregate`` in statically."""
        return self.num_aggregate_min is not None

    @property
    def initial_aggregate(self) -> int:
        """The adaptive controller's starting count: ``num_aggregate``
        when given (validated inside the bounds), else the max bound —
        start optimistic, back off when stragglers appear."""
        if not self.adaptive_aggregate:
            return self.effective_aggregate
        if self.num_aggregate is not None:
            return self.num_aggregate
        return self.num_aggregate_max


@flax.struct.dataclass
class PSTrainState:
    step: jax.Array
    # the master parameters: a buckets.FlatVector — ONE padded flat f32
    # vector in the wire's BucketPlan geometry. Checkpoints store the
    # TREE shape (FlatVector converts at the serialization edge), so a
    # file is portable across bucket settings and repo versions.
    params: Any
    # optax state; under the replicated placement the moments are
    # FlatVectors too (same geometry, same tree-shaped checkpoint form)
    opt_state: Any
    batch_stats: Any
    # error-feedback residuals, worker-stacked [n, ...] per param leaf
    # (cfg.error_feedback); None otherwise — checkpointed with the state
    # so resume keeps the accumulated compression error
    comm_state: Any = None
    # non-finite guard counters + live loss scale (resilience.GuardState,
    # cfg.nonfinite_guard); None when the guard is off. Checkpointed, but
    # resettable: checkpoint.load_checkpoint re-zeros it when restoring a
    # pre-guard checkpoint (the counters are observability, not math)
    guard_state: Any = None


def _flat_padded_size(params) -> int:
    return sum(int(jnp.size(p)) for p in jax.tree_util.tree_leaves(params))


def wire_align(cfg: PSConfig) -> int:
    """Bucket-boundary alignment (f32 elements) this config's wire uses:
    the int8 quantization block for the quantized schemes (1 for
    per-tensor scales / no compression), × num_workers on the ZeRO-1
    scatter so each worker's slice of each bucket owns whole scale rows.
    The PSC106 FusionSpec derives its budget from this same function —
    keep them one expression."""
    block = (
        cfg.quant_block_size
        if cfg.compress in ("int8", "int8_2round") and cfg.quant_block_size
        else 1
    )
    return (
        cfg.num_workers * block if cfg.opt_placement == "sharded" else block
    )


def _sharded_plan(cfg: PSConfig, total: int) -> BucketPlan:
    """Bucket geometry for the ZeRO-1 flat wire (buckets.plan_buckets).

    Every bucket — and the padded total — is a multiple of
    ``num_workers * quant_block`` (wire_align), so each worker's
    scattered slice of each bucket owns whole quantization-scale rows.
    The sharded wire has always been one flat buffer, so ``bucket_bytes``
    None and 0 are the same fused plan; N>0 carves the scatter into
    ~N-byte buckets. Must be identical at init (optimizer-state buffers,
    EF residual rows) and in the update step."""
    return plan_buckets(total, cfg.bucket_bytes or 0, align=wire_align(cfg))


def _zero1_shard_size(total: int, cfg: PSConfig) -> int:
    """Per-worker flat shard length for the ZeRO-1 placement: this
    worker's 1/N of every bucket of the padded flat gradient."""
    return _sharded_plan(cfg, total).padded_total // cfg.num_workers


def state_plan(cfg: PSConfig, total: int) -> BucketPlan:
    """The flat-state geometry: the SAME BucketPlan
    the config's gradient wire uses, so the reduced flat gradient drops
    straight into the vector update with no re-layout. Replicated:
    ``bucket_bytes`` carving aligned to ``wire_align`` (None = one fused
    buffer — only the padding matters for state). Sharded: the ZeRO-1
    scatter plan (alignment × num_workers), so params already live in
    shard geometry."""
    if cfg.opt_placement == "sharded":
        return _sharded_plan(cfg, total)
    return plan_buckets(total, cfg.bucket_bytes or 0, align=wire_align(cfg))


def init_ps_state(
    model,
    tx: optax.GradientTransformation,
    cfg: PSConfig,
    rng: jax.Array,
    input_shape,
) -> PSTrainState:
    """Build the (host-side) initial state with the stacking layout the
    engine expects for the configured placement/bn modes."""
    from ..models import init_model

    params_tree, batch_stats = init_model(model, rng, input_shape)
    total = _flat_padded_size(params_tree)
    # master params become ONE padded flat f32 vector in the wire's
    # own BucketPlan geometry; the tree view is materialized per
    # step inside the jitted program (and at the checkpoint edge)
    params = to_flat_vector(params_tree, state_plan(cfg, total))
    if cfg.opt_placement == "sharded":
        shard = _zero1_shard_size(total, cfg)
        flat_zeros = jnp.zeros((shard,), jnp.float32)
        one_state = tx.init(flat_zeros)
        # identical zero-init on every worker; stacked leading axis = worker
        opt_state = tree_map(
            lambda x: jnp.broadcast_to(x, (cfg.num_workers,) + jnp.shape(x)), one_state
        )
    else:
        # params is a FlatVector: moments initialize as whole padded
        # vectors carrying the same static layout (the checkpoint edge
        # converts them tree-shaped like the params)
        opt_state = tx.init(params)
    if cfg.bn_mode == "local" and batch_stats:
        batch_stats = tree_map(
            lambda x: jnp.broadcast_to(x, (cfg.num_workers,) + x.shape), batch_stats
        )
    comm_state = None
    if cfg.error_feedback:
        if cfg.opt_placement == "sharded":
            # the sharded wire transforms the FLAT padded gradient vector,
            # so its residual lives there too: one [L] row per worker
            flat_len = _zero1_shard_size(total, cfg) * cfg.num_workers
            comm_state = jnp.zeros(
                (cfg.num_workers, flat_len), jnp.float32
            )
        else:
            # zero residual per worker per param leaf, worker-stacked —
            # per-leaf though the state is flat, so EF checkpoints stay
            # portable across bucket settings
            comm_state = tree_map(
                lambda p: jnp.zeros(
                    (cfg.num_workers,) + jnp.shape(p), jnp.float32
                ),
                params_tree,
            )
    guard_state = None
    if cfg.nonfinite_guard:
        guard_state = init_guard_state(
            cfg.loss_scale_init if cfg.dynamic_loss_scale else 1.0,
            dynamic=cfg.dynamic_loss_scale,
        )
    return PSTrainState(
        step=jnp.zeros([], jnp.int32),
        params=params,
        opt_state=opt_state,
        batch_stats=batch_stats,
        comm_state=comm_state,
        guard_state=guard_state,
    )


def state_specs(cfg: PSConfig):
    """PartitionSpecs (pytree prefixes) for PSTrainState components."""
    opt_spec = P(cfg.axis_name) if cfg.opt_placement == "sharded" else P()
    bs_spec = P(cfg.axis_name) if cfg.bn_mode == "local" else P()
    return PSTrainState(
        step=P(),
        params=P(),
        opt_state=opt_spec,
        batch_stats=bs_spec,
        comm_state=P(cfg.axis_name),  # worker-stacked residuals (if any)
        guard_state=P(),  # scalar counters, replicated
    )


@setup_span("setup.shard_state")
def shard_state(state: PSTrainState, mesh: Mesh, cfg: PSConfig) -> PSTrainState:
    """Place a host-built state onto the mesh with the right shardings."""
    specs = state_specs(cfg)

    def put(tree, spec):
        return tree_map(lambda x: jax.device_put(x, NamedSharding(mesh, spec)), tree)

    return PSTrainState(
        step=put(state.step, P()),
        params=put(state.params, specs.params),
        opt_state=put(state.opt_state, specs.opt_state),
        batch_stats=put(state.batch_stats, specs.batch_stats),
        comm_state=put(state.comm_state, specs.comm_state),
        guard_state=put(state.guard_state, specs.guard_state),
    )


def batch_sharding(mesh: Mesh, cfg: PSConfig) -> NamedSharding:
    """The per-worker batch sharding (leading dim split over the data
    axis) — pass to ``data.prefetch_to_device`` so prefetched batches
    land on the mesh already split instead of being re-laid-out inside
    the step."""
    return NamedSharding(mesh, P(cfg.axis_name))


def shard_batch(batch, mesh: Mesh, cfg: PSConfig):
    """Split the global batch across workers (leading dim)."""
    return jax.device_put(batch, batch_sharding(mesh, cfg))


def _worker_region(flat, plan: BucketPlan, w, n: int):
    """Worker ``w``'s region of a bucketed flat buffer: its 1/n slice of
    every bucket, concatenated in bucket order (one slice for the fused
    single-bucket plan)."""
    parts = []
    for start, size in zip(plan.starts, plan.sizes):
        s = size // n
        parts.append(lax.dynamic_slice(flat, (start + w * s,), (s,)))
    return concat_buckets(parts) if len(parts) > 1 else parts[0]


# ------------------------------------------------ per-bucket vector update
# (overlap="pipelined": the optimizer starts as each bucket's reduction
# lands, instead of waiting for the whole aggregate to concatenate)

def _is_flatvec(x) -> bool:
    return isinstance(x, FlatVector)


def _strip_flat(tree):
    """Replace every FlatVector node with its bare padded buffer, so the
    per-bucket slices feed tree- and flat-form optimizer transforms
    alike (a tree_map over mixed FlatVector/bare operands would reject
    the structure)."""
    return jax.tree_util.tree_map(
        lambda x: x.flat if _is_flatvec(x) else x, tree, is_leaf=_is_flatvec
    )


def _rewrap_flat(template, bare):
    """Inverse of ``_strip_flat``: restore the template's FlatVector
    wrappers (their static layout/plan metadata) around the stitched
    bare buffers, so the step's output state structure is unchanged."""
    return jax.tree_util.tree_map(
        lambda t, v: t.replace(flat=v) if _is_flatvec(t) else v,
        template, bare, is_leaf=_is_flatvec,
    )


def _bucket_opt_views(opt_bare, seg_len: int):
    """(leaves, treedef, is_seg): flatten a bare optimizer state and mark
    which leaves are per-parameter vectors of ``seg_len`` elements (the
    moment buffers — sliced per bucket) vs scalars like the step count
    (replicated into every bucket's update unchanged)."""
    leaves, treedef = jax.tree_util.tree_flatten(opt_bare)
    is_seg = [
        getattr(l, "ndim", None) == 1 and int(l.shape[0]) == seg_len
        for l in leaves
    ]
    return leaves, treedef, is_seg


def _stitch_opt(treedef, per_bucket_leaves, is_seg, first_bucket: int):
    """Reassemble the whole-vector optimizer state from per-bucket
    updates: segment leaves concatenate in CANONICAL bucket order,
    scalar leaves (every bucket computed the identical count+1) come
    from the first-dispatched bucket."""
    first = per_bucket_leaves[first_bucket]
    out = []
    for j, seg in enumerate(is_seg):
        if seg:
            out.append(jnp.concatenate(
                [pb[j] for pb in per_bucket_leaves]
            ))
        else:
            out.append(first[j])
    return jax.tree_util.tree_unflatten(treedef, out)


def _pipelined_flat_update(tx, agg_buckets, opt_state, params: FlatVector,
                           plan: BucketPlan):
    """Replicated flat-state update, one ``tx.update`` per bucket: bucket
    b's new params/moments depend only on bucket b's aggregate, so the
    update chain for an early-reduced bucket can run while later buckets
    are still on the wire. Bit-exact vs the whole-vector update for
    elementwise transforms (the repo's sgd/adam families): slicing an
    elementwise chain commutes with it, and every bucket reads the same
    input ``count``. Returns (new_params, new_opt)."""
    opt_bare = _strip_flat(opt_state)
    leaves, treedef, is_seg = _bucket_opt_views(opt_bare, plan.padded_total)
    order = readiness_bucket_order(plan)
    new_p = [None] * plan.n_buckets
    new_opt = [None] * plan.n_buckets
    for b in order:
        start, size = plan.starts[b], plan.sizes[b]
        with jax.named_scope(f"bucket_update_o{start}"):
            p_b = lax.slice(params.flat, (start,), (start + size,))
            opt_b = jax.tree_util.tree_unflatten(treedef, [
                lax.slice(l, (start,), (start + size,)) if seg else l
                for l, seg in zip(leaves, is_seg)
            ])
            u_b, opt_b_new = tx.update(agg_buckets[b], opt_b, p_b)
            new_p[b] = p_b + _strip_flat(u_b)
            new_opt[b] = jax.tree_util.tree_leaves(_strip_flat(opt_b_new))
    stitched = _stitch_opt(treedef, new_opt, is_seg, order[0])
    return (
        params.replace(flat=concat_buckets(new_p)),
        _rewrap_flat(opt_state, stitched),
    )


def _shard_reduce_bucket(bucket, size: int, axis, n: int, w, k, cfg,
                         bkey, want_contrib: bool):
    """One bucket of the ZeRO-1 wire: (quantize) -> psum_scatter / int8
    all_to_all -> THIS worker's dequantized 1/n shard divided by the
    aggregation count. Shared by the serial and pipelined schedules so
    the per-bucket transform (and therefore the bytes and the values)
    can never diverge between them. Returns ``(g_shard [size//n],
    contribution [size] or None)``."""
    s = size // n
    bsz = cfg.quant_block_size
    if cfg.compress in ("int8", "int8_2round"):
        q, scale = quantize_int8(
            bucket,
            axis_name=axis,
            block_size=bsz,
            rounding=cfg.quant_rounding,
            key=bkey,
        )
        contrib = None
        if want_contrib:
            # what the wire carries after the int8 round trip — the
            # residual is everything it dropped (incl. the whole
            # gradient on mask-excluded steps: sent==0 -> q==0 ->
            # contribution 0)
            contrib = dequantize_int8(
                q.astype(jnp.int32), scale, block_size=bsz, shape=(size,)
            )
        homomorphic = cfg.wire_domain == "homomorphic"
        if cfg.compress == "int8":
            # homomorphic: the scatter-sum rides the minimal exact
            # accumulator (int16 through 258 workers — half the dequant
            # path's int32 wire); the sums are bit-identical integers
            acc_dt = accum_dtype(n) if homomorphic else jnp.int32
            sb = lax.psum_scatter(
                q.reshape(-1).astype(acc_dt), axis, tiled=True
            )
        else:
            # the sharded 2-round wire is already compressed-domain by
            # construction (int8 a2a + LOCAL int32 sum, shard-only
            # dequant) — wire_domain changes nothing here
            q8 = q.reshape(n, s).astype(jnp.int8)
            recv = lax.all_to_all(
                q8, axis, split_axis=0, concat_axis=0, tiled=True
            )
            sb = jnp.sum(recv.astype(jnp.int32), axis=0)  # [s]
        if bsz:
            nb_loc = s // bsz
            my_scales = lax.dynamic_slice(scale, (w * nb_loc, 0), (nb_loc, 1))
            if homomorphic:
                # ONE deferred scale-multiply: the aggregation count
                # folds into the shard's own scale rows
                return (
                    sb.reshape(nb_loc, bsz).astype(jnp.float32)
                    * (my_scales / k)
                ).reshape(-1), contrib
            return (
                sb.reshape(nb_loc, bsz).astype(jnp.float32) * my_scales
            ).reshape(-1) / k, contrib
        if homomorphic:
            return dequantize_int8(sb, scale / k), contrib
        return dequantize_int8(sb, scale) / k, contrib
    return lax.psum_scatter(bucket, axis, tiled=True) / k, None


def _sharded_ps_update(params, opt_state, grads, tx, cfg, mask_key,
                       quant_key=None, err=None, agg_count=None):
    """ZeRO-1 "sharded PS": (EF add-back) -> mask -> (quantize) ->
    reduce_scatter per bucket -> per-shard optax update -> all_gather the
    parameter delta. The flat geometry comes from the buckets engine
    (buckets.tree_layout / tree_to_flat — the same concat order and
    round-trip the replicated wire uses), carved by ``_sharded_plan``:
    one fused bucket for bucket_bytes None/0, ~N-byte buckets otherwise.
    Two compressed wires:

    - "int8": quantize, int32 psum_scatter — the sum is EXACT in int32
      but the interconnect carries int32 (compute-side compression).
    - "int8_2round": quantize, int8 all_to_all, local int32 sum — the
      wire genuinely carries int8 (~4x cut). In the sharded placement the
      reduce_scatter IS round 1 of the 2-round scheme and no second round
      exists: each chip keeps only its own region, so nothing is
      re-broadcast (parameters return via the f32 all_gather of updates,
      the analogue of the reference master's weight bcast).

    Per-bucket quantization keys fold the bucket's START OFFSET in the
    flat buffer (position-stable — the same discipline as
    collectives.piece_stream), so the noise stream a byte sees depends on
    where it lives, not on how many buckets precede it.

    `params` is a FlatVector ALREADY in this wire's shard geometry, so
    the gathered update adds straight onto the flat buffer.

    `err` (error feedback) is this worker's residual on the FLAT padded
    gradient vector; returns (new_params, new_opt, new_err).

    ``agg_count`` (adaptive partial aggregation): a traced int32 count
    replacing the static ``cfg.num_aggregate`` — the mask is always
    applied (exactly 1.0 at full count) and the denominator is the
    traced count, so the same compiled program serves every count in
    the declared bounds."""
    axis, n = cfg.axis_name, cfg.num_workers
    dynamic = agg_count is not None
    if dynamic:
        k = agg_count.astype(jnp.float32)
    else:
        k = cfg.effective_aggregate
    layout = tree_layout(grads)
    total = layout.total
    plan = _sharded_plan(cfg, total)
    w = lax.axis_index(axis)
    if (
        cfg.compress in ("int8", "int8_2round")
        and cfg.quant_rounding == "stochastic"
        and quant_key is not None
    ):
        quant_key = jax.random.fold_in(quant_key, w)

    def bucket_key(start):
        return (
            jax.random.fold_in(quant_key, start)
            if quant_key is not None
            and cfg.compress in ("int8", "int8_2round")
            else None
        )

    sel = None
    if dynamic or k != n:
        sel = aggregation_mask(
            axis, n, agg_count if dynamic else cfg.num_aggregate,
            mask_key, cfg.mask_mode,
        )

    if cfg.overlap == "pipelined":
        return _sharded_ps_update_pipelined(
            params, opt_state, grads, tx, cfg, layout, plan, w, k, sel,
            bucket_key, err,
        )

    with scope(GRAD_REDUCE):
        flat_g = pad_flat(tree_to_flat(grads), plan)
        if err is not None:
            flat_g = flat_g + err
        sent = flat_g * sel if sel is not None else flat_g
        new_err = None
        g_shards, contribs = [], []
        for start, size in zip(plan.starts, plan.sizes):
            bucket = lax.slice(sent, (start,), (start + size,))
            g_b, contrib = _shard_reduce_bucket(
                bucket, size, axis, n, w, k, cfg, bucket_key(start),
                want_contrib=err is not None,
            )
            g_shards.append(g_b)
            if contrib is not None:
                contribs.append(contrib)
        g_shard = concat_buckets(g_shards)
        if err is not None:
            new_err = flat_g - concat_buckets(contribs)
    with scope(UPDATE):
        flat_p = params.flat  # already padded in this plan's geometry
        p_shard = _worker_region(flat_p, plan, w, n)
        upd_shard, new_opt = tx.update(g_shard, opt_state, p_shard)
        # reassemble: each bucket's shard segment gathers back tiled, in
        # bucket order, inverting _worker_region's layout exactly
        off, full = 0, []
        for size in plan.sizes:
            s = size // n
            full.append(lax.all_gather(
                lax.slice(upd_shard, (off,), (off + s,)), axis, tiled=True
            ))
            off += s
        # one vector add, no per-leaf scatter (the pad tail stays zero —
        # zero gradient => zero update)
        new_params = params.replace(flat=flat_p + concat_buckets(full))
    return new_params, new_opt, new_err


def _sharded_ps_update_pipelined(params, opt_state, grads, tx, cfg, layout,
                                 plan, w, k, sel, bucket_key, err):
    """The ZeRO-1 update as a per-bucket stream (overlap="pipelined"):
    every bucket is assembled from its own gradient leaves
    (``assemble_bucket`` — no global ``tree_to_flat`` concat, so bucket
    b's chain depends only on its leaves' gradients), reduced via the
    SAME ``_shard_reduce_bucket`` transform as the serial schedule,
    updated on its own shard segment, and gathered back — all in
    readiness order, so an early bucket's scatter/update/gather can
    overlap the rest of the backward. Values and bytes are identical to
    the serial schedule; only the dataflow (and therefore what a
    latency-hiding scheduler may interleave) changes."""
    axis, n = cfg.axis_name, cfg.num_workers
    segs = bucket_leaf_segments(layout, plan)
    order = readiness_bucket_order(plan)
    g_leaves = jax.tree_util.tree_leaves(grads)
    shard_len = plan.padded_total // n
    opt_bare = _strip_flat(opt_state)
    opt_leaves, opt_def, is_seg = _bucket_opt_views(opt_bare, shard_len)
    # canonical per-bucket offsets into the worker's shard
    shard_off = []
    off = 0
    for size in plan.sizes:
        shard_off.append(off)
        off += size // n
    nb = plan.n_buckets
    new_p = [None] * nb
    new_opt = [None] * nb
    err_parts = [None] * nb
    for b in order:
        start, size = plan.starts[b], plan.sizes[b]
        s = size // n
        with scope(GRAD_REDUCE), jax.named_scope(f"bucket_reduce_o{start}"):
            g_b = assemble_bucket(g_leaves, segs[b])
            if err is not None:
                g_b = g_b + lax.slice(err, (start,), (start + size,))
            sent_b = g_b * sel if sel is not None else g_b
            g_shard_b, contrib = _shard_reduce_bucket(
                sent_b, size, axis, n, w, k, cfg, bucket_key(start),
                want_contrib=err is not None,
            )
            if err is not None:
                err_parts[b] = g_b - contrib
        with scope(UPDATE), jax.named_scope(f"bucket_update_o{start}"):
            p_b = lax.dynamic_slice(params.flat, (start + w * s,), (s,))
            opt_b = jax.tree_util.tree_unflatten(opt_def, [
                lax.slice(l, (shard_off[b],), (shard_off[b] + s,))
                if seg else l
                for l, seg in zip(opt_leaves, is_seg)
            ])
            u_b, opt_b_new = tx.update(g_shard_b, opt_b, p_b)
            gathered = lax.all_gather(_strip_flat(u_b), axis, tiled=True)
            new_p[b] = (
                lax.slice(params.flat, (start,), (start + size,))
                + gathered
            )
            new_opt[b] = jax.tree_util.tree_leaves(_strip_flat(opt_b_new))
    stitched = _stitch_opt(opt_def, new_opt, is_seg, order[0])
    new_opt_state = _rewrap_flat(opt_state, stitched)
    new_params = params.replace(flat=concat_buckets(new_p))
    new_err = concat_buckets(err_parts) if err is not None else None
    return new_params, new_opt_state, new_err


@setup_span("setup.make_step")
def make_ps_train_step(
    model,
    tx: optax.GradientTransformation,
    cfg: PSConfig,
    mesh: Mesh,
    preprocess: Optional[Callable[[jax.Array, jax.Array], jax.Array]] = None,
    donate: bool = True,
    faults=None,
):
    """Build the jitted SPMD train step: (state, batch, key) -> (state, metrics).

    `batch` is {"image": uint8 [B,...], "label": int32 [B]} with B divisible by
    num_workers; `key` drives augmentation/dropout (per-worker folded) and the
    random-K aggregation mask (shared). One call = one global step of the
    reference protocol (master step N + all workers' iteration N together).

    With cfg.nonfinite_guard the step carries its own defense: a per-worker
    all-finite reduction over the gradients, one int32 pmin for mesh
    consensus (4 B on the wire, no host transfer), and a `jnp.where` select
    that turns the whole state update into the identity on a bad step —
    the guard decision never leaves the device. That rollback selects a
    handful of whole flat vectors (params + each optimizer moment), not
    every pytree leaf.

    Master params and optimizer moments are padded flat f32 vectors end
    to end: the forward pass reads a once-per-step tree view, the reduced
    flat gradient feeds one fused vector update, and the ZeRO-1 path
    needs no per-step tree_to_flat(params).

    `faults` (resilience.FaultPlan) bakes deterministic NaN/Inf gradient
    injection into the compiled step at the planned global steps — the
    chaos harness that proves the guard end-to-end.

    cfg.adaptive_aggregate (num_aggregate_min/max set) changes the step
    signature to ``(state, batch, key, agg_count) -> (state, metrics)``:
    ``agg_count`` is a traced int32 scalar the host updates per window
    (resilience/elastic.AdaptiveMaskController), clipped on device to the
    declared bounds so a host bug can never divide by zero or mask out
    everything. Same compiled program for every count — no retrace on
    adaptation.
    """
    axis, n = cfg.axis_name, cfg.num_workers
    specs = state_specs(cfg)
    # per-axis sizes for the hierarchical (DCN x ICI) 2-round scheme
    hier_sizes = (
        tuple(mesh.shape[a] for a in axis)
        if isinstance(axis, (tuple, list))
        else None
    )

    def worker_fn(step_idx, params, opt_state, batch_stats, comm_state,
                  guard_state, images, labels, key, *extras):
        # the traced per-window controller input (cfg.adaptive_aggregate)
        agg_count = extras[0] if cfg.adaptive_aggregate else None
        if agg_count is not None:
            # device-side clamp to the declared bounds: the contract the
            # PSC108 envelope relies on must hold even against a buggy
            # host-side controller
            agg_count = jnp.clip(
                agg_count, cfg.num_aggregate_min, cfg.num_aggregate_max
            ).astype(jnp.int32)
        w = lax.axis_index(axis)
        k_step = jax.random.fold_in(key, step_idx)
        k_mask = jax.random.fold_in(k_step, 0xA66)
        k_aug, k_drop = jax.random.split(jax.random.fold_in(k_step, w + 1))

        with scope(AUGMENT):
            x = preprocess(k_aug, images) if preprocess else images.astype(jnp.float32)

        params_in, opt_in, bs_in_raw, comm_in = (
            params, opt_state, batch_stats, comm_state
        )
        # tree view for the forward/backward pass: the once-per-step
        # flat_to_tree materialization (static slices/reshapes XLA fuses
        # into the consumers); the master `params` stays the padded flat
        # vector end to end
        params_t = tree_view(params)
        scale = (
            guard_state.scale
            if cfg.nonfinite_guard and cfg.dynamic_loss_scale
            else None
        )

        if cfg.opt_placement == "sharded":
            opt_state = tree_map(lambda a: a[0], opt_state)
        bs = tree_map(lambda a: a[0], batch_stats) if cfg.bn_mode == "local" else batch_stats

        def fwd_bwd(bs_in, xi, yi, kd):
            @scope(MODEL)
            def loss_fn(p):
                logits, new_bs = apply_model(
                    model, p, bs_in, xi, train=True, dropout_rng=kd
                )
                loss = cross_entropy_loss(logits, yi)
                if scale is not None:
                    loss = loss * scale
                return loss, (logits, new_bs)

            (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(params_t)
            if scale is not None:
                # unscale immediately: everything downstream (EF residual,
                # quantization, the finite check) sees true-magnitude
                # gradients; overflow shows up as inf surviving the divide
                loss = loss / scale
                g = tree_map(lambda t: t / scale, g)
            return (loss, aux), g

        if cfg.grad_accum_steps > 1:
            a = cfg.grad_accum_steps
            if x.shape[0] % a:  # static shape: raises at trace time
                raise ValueError(
                    f"per-worker batch {x.shape[0]} not divisible by "
                    f"grad_accum_steps={a}"
                )
            xm = x.reshape(a, x.shape[0] // a, *x.shape[1:])
            ym = labels.reshape(a, -1)

            def micro(carry, inp):
                bs_c, gsum, lsum, p1sum, p5sum = carry
                i, xi, yi = inp
                (loss_i, (logits_i, bs_i)), g_i = fwd_bwd(
                    bs_c, xi, yi, jax.random.fold_in(k_drop, i)
                )
                p1_i, p5_i = accuracy(logits_i, yi, (1, 5))
                carry = (
                    bs_i,
                    tree_map(jnp.add, gsum, g_i),
                    lsum + loss_i,
                    p1sum + p1_i,
                    p5sum + p5_i,
                )
                return carry, None

            zeros = tree_map(jnp.zeros_like, params_t)
            (new_bs, gsum, lsum, p1sum, p5sum), _ = lax.scan(
                micro,
                (bs, zeros, 0.0, 0.0, 0.0),
                (jnp.arange(a), xm, ym),
            )
            grads = tree_map(lambda g: g / a, gsum)
            loss, prec1, prec5 = lsum / a, p1sum / a, p5sum / a
        else:
            (loss, (logits, new_bs)), grads = fwd_bwd(bs, x, labels, k_drop)
            prec1, prec5 = accuracy(logits, labels, (1, 5))

        if faults is not None and (faults.nan_grads or faults.inf_grads):
            # deterministic chaos: poison the gradients at the planned
            # global steps (host numbering: step_idx is pre-increment)
            host_step = step_idx + 1
            for steps, val in ((faults.nan_grads, jnp.nan),
                               (faults.inf_grads, jnp.inf)):
                if steps:
                    hit = jnp.any(host_step == jnp.asarray(steps, jnp.int32))
                    grads = tree_map(
                        lambda g, h=hit, v=val: jnp.where(h, v, g), grads
                    )

        finite = None
        if cfg.nonfinite_guard:
            # mesh-wide agreement on "every worker's gradients are
            # finite": one int32 pmin — 4 bytes on the interconnect, no
            # host transfer, and every worker takes the same branch.
            # With bucketing on, the per-worker half reduces ONE fused
            # isfinite over the flat buffer (XLA CSEs the concat with
            # the wire's own flatten) instead of one reduction per leaf.
            with scope(GRAD_REDUCE):
                probe = (
                    tree_to_flat(grads)
                    if cfg.bucket_bytes is not None
                    else grads
                )
                finite = lax.pmin(
                    tree_all_finite(probe).astype(jnp.int32), axis
                ) > 0

        new_comm = comm_state
        quant_key = (
            jax.random.fold_in(k_step, 0x5E) if cfg.compress else None
        )
        if cfg.opt_placement == "sharded":
            # _sharded_ps_update names its own two halves
            err = comm_state[0] if cfg.error_feedback else None
            params, new_opt, new_err = _sharded_ps_update(
                params, opt_state, grads, tx, cfg, k_mask,
                quant_key=quant_key, err=err, agg_count=agg_count,
            )
            new_opt = tree_map(lambda a: a[None], new_opt)
            if cfg.error_feedback:
                new_comm = new_err[None]
        else:
            with scope(GRAD_REDUCE):
                if cfg.error_feedback:
                    # EF-SGD: add back last step's compression residual before
                    # transmitting; the new residual is what the wire dropped
                    # — including the ENTIRE gradient on mask-excluded steps
                    # (EF subsumes stale-gradient accumulation for the
                    # backup-worker mode)
                    err = tree_map(lambda a: a[0], comm_state)
                    grads = tree_map(jnp.add, grads, err)
                pipelined = cfg.overlap == "pipelined"
                # pipelined x bucketed: the aggregate stays a LIST of
                # per-bucket vectors so the optimizer can start per bucket —
                # the only spelling with no whole-vector barrier at all
                bucket_out = pipelined and cfg.bucket_bytes is not None
                out = aggregate_gradients(
                    grads,
                    axis,
                    n,
                    num_aggregate=(
                        agg_count if agg_count is not None else cfg.num_aggregate
                    ),
                    mask_key=k_mask,
                    mask_mode=cfg.mask_mode,
                    compress=cfg.compress,
                    quant_block_size=cfg.quant_block_size,
                    quant_rounding=cfg.quant_rounding,
                    quant_key=quant_key,
                    return_contribution=cfg.error_feedback,
                    axis_sizes=hier_sizes,
                    bucket_bytes=cfg.bucket_bytes,
                    flat_output=not bucket_out,
                    pipelined=pipelined,
                    bucket_output=bucket_out,
                    wire_domain=cfg.wire_domain,
                )
                if cfg.error_feedback:
                    # the contribution (and the residual it defines) stays
                    # per-leaf — checkpoint portability
                    agg, contribution = out
                    new_err = tree_map(lambda a, b: a - b, grads, contribution)
                    new_comm = tree_map(lambda a: a[None], new_err)
                else:
                    agg = out
            with scope(UPDATE):
                if bucket_out:
                    # per-bucket fused vector updates, dispatched as each
                    # bucket's reduction lands (state_plan and the wire share
                    # one BucketPlan, so the per-bucket aggregates drop
                    # straight onto the state's own carving)
                    params, new_opt = _pipelined_flat_update(
                        tx, agg, opt_state, params, params.plan
                    )
                else:
                    # the reduced flat gradient, already in the state's
                    # BucketPlan geometry (piece_stream and state_plan share
                    # wire_align) — wrap it and run ONE fused vector update
                    agg = params.replace(flat=agg)
                    updates, new_opt = tx.update(agg, opt_state, params)
                    params = optax.apply_updates(params, updates)

        with scope(UPDATE):
            if cfg.bn_mode == "local":
                out_bs = tree_map(lambda a: a[None], new_bs)
            else:
                out_bs = lax.pmean(new_bs, axis) if new_bs else new_bs

            metrics = lax.pmean(
                {"loss": loss, "prec1": prec1, "prec5": prec5}, axis
            )
            new_guard = guard_state
            if cfg.nonfinite_guard:
                # skip-step: a non-finite step becomes the identity update —
                # params, optimizer state, BN stats, and EF residuals all keep
                # their pre-step values bit-identically; only the guard
                # counters (and the loss scale) advance. The aggregation
                # collectives still ran (NaNs flow through them harmlessly),
                # so the per-step wire accounting is step-invariant.
                def sel(new, old):
                    return tree_map(
                        lambda a, b: jnp.where(finite, a, b), new, old
                    )

                params = sel(params, params_in)
                new_opt = sel(new_opt, opt_in)
                out_bs = sel(out_bs, bs_in_raw)
                new_comm = sel(new_comm, comm_in)
                new_guard = update_guard_state(
                    guard_state, finite, cfg.dynamic_loss_scale,
                    cfg.loss_scale_growth_interval,
                )
                # ride the metrics dict the host already fetches once per log
                # window — the guard adds no per-step host transfer
                metrics["skipped_steps"] = new_guard.skipped.astype(jnp.float32)
                metrics["skip_streak"] = new_guard.consec.astype(jnp.float32)
                if cfg.dynamic_loss_scale:
                    metrics["loss_scale"] = new_guard.scale
        return params, new_opt, out_bs, new_comm, new_guard, metrics

    base_in_specs = (
        P(),
        specs.params,
        specs.opt_state,
        specs.batch_stats,
        specs.comm_state,
        specs.guard_state,
        P(axis),
        P(axis),
        P(),
    )
    out_specs = (
        specs.params,
        specs.opt_state,
        specs.batch_stats,
        specs.comm_state,
        specs.guard_state,
        P(),
    )
    # the adaptive signature threads the traced count through shard_map
    # (a replicated scalar); the static path keeps the 9-arg shape so
    # its jaxpr — and the committed comm contract — is untouched
    extra_specs = (P(),) if cfg.adaptive_aggregate else ()
    mapped = jax.shard_map(
        worker_fn,
        mesh=mesh,
        in_specs=base_in_specs + extra_specs,
        out_specs=out_specs,
        check_vma=False,
    )

    def step(state: PSTrainState, batch, key, *agg):
        params, opt_state, batch_stats, comm_state, guard_state, metrics = (
            mapped(
                state.step,
                state.params,
                state.opt_state,
                state.batch_stats,
                state.comm_state,
                state.guard_state,
                batch["image"],
                batch["label"],
                key,
                *agg,
            )
        )
        new_state = PSTrainState(
            step=state.step + 1,
            params=params,
            opt_state=opt_state,
            batch_stats=batch_stats,
            comm_state=comm_state,
            guard_state=guard_state,
        )
        return new_state, metrics

    # a fixed-arity wrapper so the jitted signature names its extra arg.
    # The `donate_argnums=... if donate else ()` conditional stays
    # inline in each return: pslint's PSL005 donor discovery reads
    # exactly this idiom to learn the factory's donated positions and
    # honor callers' donate=False opt-outs.
    if cfg.adaptive_aggregate:
        def step_adaptive(state: PSTrainState, batch, key, agg_count):
            return step(state, batch, key, agg_count)

        return ScopedStep(PS_TRAIN_STEP, jax.jit(
            stamped(step_adaptive), donate_argnums=(0,) if donate else ()))
    return ScopedStep(PS_TRAIN_STEP, jax.jit(stamped(step), donate_argnums=(0,) if donate else ()))


def make_ps_eval_step(model, cfg: PSConfig, mesh: Mesh, preprocess=None):
    """Sharded evaluation step: (state, batch) -> metrics (pmean'd)."""
    axis = cfg.axis_name

    def worker_fn(params, batch_stats, images, labels):
        bs = tree_map(lambda a: a[0], batch_stats) if cfg.bn_mode == "local" else batch_stats
        x = preprocess(None, images) if preprocess else images.astype(jnp.float32)
        logits, _ = apply_model(model, tree_view(params), bs, x, train=False)
        loss = cross_entropy_loss(logits, labels)
        prec1, prec5 = accuracy(logits, labels, (1, 5))
        return lax.pmean({"loss": loss, "prec1": prec1, "prec5": prec5}, axis)

    specs = state_specs(cfg)
    mapped = jax.shard_map(
        worker_fn,
        mesh=mesh,
        in_specs=(specs.params, specs.batch_stats, P(axis), P(axis)),
        out_specs=P(),
        check_vma=False,
    )

    def step(state: PSTrainState, batch):
        return mapped(state.params, state.batch_stats, batch["image"], batch["label"])

    return jax.jit(step)
