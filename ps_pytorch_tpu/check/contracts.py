"""pscheck contract registry: each scheme's step function + its declared
communication invariants.

A ContractSpec bundles a builder that constructs the REAL production step
(the same factory the trainer calls — nothing re-implemented here) with
the invariants ARCHITECTURE.md claims for it, as data the rules
(rules.py) can verify against the traced jaxpr:

- ``axes``: every declared mesh axis must be consumed by a collective,
  and no collective may ride any other axis (PSC101);
- ``grad_reduce``: for each axis across which gradient leaves are
  replicated, the reducing collective kinds that must feed the updated
  params (PSC102) — ``psum`` for the plain/int8 paths, ``psum_scatter``
  for the ZeRO-1 wire, ``all_to_all`` for the bandwidth-honest 2-round
  schemes (where the all_to_all + local sum IS the reduction);
- ``wire``: for configs that claim an int8 wire (§6b ladder rung 3), the
  payload dtype every collective on those axes must carry, plus the
  explicitly-allowed exceptions — scale rows, the f32 metrics pmean, the
  ZeRO-1 update all_gather (the weight bcast analogue) (PSC103);
- ``donation``: which args the compiled step donates and which outputs
  they must alias (PSC105).

Builders run CPU-only and deterministic: states are jax.eval_shape
abstractions, inputs are ShapeDtypeStructs — tracing never allocates or
executes a step.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

MESH_DEVICES = 8  # the virtual CPU mesh every contract traces on


@dataclasses.dataclass(frozen=True)
class GradReduce:
    """PSC102: a reduce over `axis` with one of `kinds` must feed params."""

    axis: str
    kinds: Tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class WireAllowance:
    """A declared non-payload-dtype collective on a compressed wire."""

    kind: str
    dtype: str
    reason: str
    max_bytes: Optional[int] = None   # None = unlimited (document why!)
    axes: Optional[Tuple[str, ...]] = None  # None = any axes


@dataclasses.dataclass(frozen=True)
class WirePolicy:
    """PSC103: collectives riding `axes` must carry `payload_dtype`
    unless a WireAllowance explicitly covers them."""

    axes: Tuple[str, ...]
    payload_dtype: str = "int8"
    allow: Tuple[WireAllowance, ...] = ()


@dataclasses.dataclass(frozen=True)
class DonationSpec:
    """PSC105: arg `argnums[i]` is donated and must alias output
    position `out_positions[i]` of the step's output tuple."""

    argnums: Tuple[int, ...]
    out_positions: Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class FusionSpec:
    """PSC106: gradient-path collective budget for a fused/bucketed wire.

    A scheme whose jaxpr emits more than
    ``per_bucket * n_buckets + slack`` (n_buckets from the engine's own
    ``plan_buckets``; ≈ ``ceil(payload_bytes / bucket_bytes)``)
    reduce-kind collectives feeding the updated params fails the gate —
    the canary for silent de-fusion (a refactor quietly going back to
    one collective per pytree leaf).

    ``payload_bytes``: f32 bytes of the gradient pytree;
    ``bucket_bytes``: PSConfig.bucket_bytes (0/None = one fused bucket);
    ``align``: the engine's bucket-boundary alignment in f32 elements
    (quant block size; × num_workers for the ZeRO-1 scatter) — the
    budget is computed by the SAME plan_buckets the wire uses, so the
    checker can never desync from the engine's round-down carving;
    ``per_bucket``: reduce collectives a healthy bucket legitimately
    costs (1 for psum/psum_scatter/all_to_all schemes, 2 for the
    hierarchical scheme's ICI + DCN all_to_all pair);
    ``slack``: extra allowed beyond the formula (document why)."""

    payload_bytes: int
    bucket_bytes: Optional[int] = 0
    align: int = 1
    per_bucket: int = 1
    slack: int = 0

    @property
    def n_buckets(self) -> int:
        from ..parallel.buckets import plan_buckets

        return plan_buckets(
            self.payload_bytes // 4, self.bucket_bytes or 0,
            align=self.align,
        ).n_buckets

    @property
    def max_collectives(self) -> int:
        return self.per_bucket * self.n_buckets + self.slack


@dataclasses.dataclass(frozen=True)
class AdaptivePolicy:
    """PSC108: the adaptive-partial-aggregation contract.

    A config taking a traced aggregation count (PSConfig.
    num_aggregate_min/max) must still declare a ``grad_reduce``
    requirement — the mask is a pre-reduce multiply, so PSC102's
    dataflow rule (the masked reduce feeds the updated params) applies
    unchanged, and PSC108 fails a spec that opted out of declaring it.
    It must also keep its gradient-path reduce collectives inside
    ``envelope_bytes``: adaptation reshapes VALUES (which workers'
    gradients are non-zero, what the denominator is), never bytes — a
    traced count that started moving per-count payloads (e.g. a gather
    of the mask, or a resize of the wire) is a regression this pin
    catches.

    PSC110: ``consensus`` names the host-consensus point that agrees the
    traced count across processes before it is fed to the step — a
    package-relative dotted path (``trainer.Trainer._count_consensus``)
    that must exist in pslint's consensus inventory (a function whose
    returned value passes through broadcast_one_to_all/process_allgather,
    see lint/diverge.py). An adaptive config with no declared consensus
    point is PR 7's per-host agg_count bug waiting to recur: each host
    adapts on its own timing and the traced counts tear."""

    min_aggregate: int
    max_aggregate: int
    envelope_bytes: int
    consensus: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class OverlapPolicy:
    """PSC109: schedule invariance for the pipelined bucket wire.

    A config running ``PSConfig.overlap="pipelined"`` declares (a) its
    mode and (b) the NAME of its serial twin — the identical config with
    ``overlap="serial"``. The rule then pins "same bytes, different
    schedule": the pipelined trace's gradient-path reduce bytes must
    equal the twin's exactly (pipelining reorders and splits the
    schedule, it never moves different bytes), the per-bucket dispatch
    must be real — at least ``n_buckets`` (× the scheme's per-bucket
    collective cost) reduce-kind collectives each feeding the updated
    params, so PSC102's dataflow guarantee holds PER BUCKET rather than
    only in aggregate — and a config claiming ``pipelined`` whose wire
    de-pipelined back to one fused eqn fails loudly."""

    mode: str = "pipelined"
    serial_twin: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class ServePolicy:
    """PSC107: the serving hot path's contract (serve/engine.py).

    A serving decode step moves NO training bytes: any collective in its
    jaxpr is a regression (the step is slot-parallel by construction —
    weights replicated, pool sharded over slots). The KV pool arg at
    ``kv_argnum`` must also honor the declared storage dtype policy:
    ``quantized`` pools carry int8 payload leaves (``*_q``) with f32
    block-scale rows (``*_s``); unquantized pools carry ``kv_dtype``
    K/V — an f32 leaf sneaking into a declared-int8 pool is the serving
    analogue of PSC103's wire-dtype regression."""

    kv_argnum: int = 1
    quantized: bool = False
    kv_dtype: str = "float32"


@dataclasses.dataclass(frozen=True)
class NarrowingAllowance:
    """One tolerated precision-narrowing convert on the update path
    (PSC114): src/dst dtype names plus the reason it is sound."""

    src: str
    dst: str
    reason: str = ""


@dataclasses.dataclass(frozen=True)
class NumericsPolicy:
    """PSC111-114: the precision-flow contract (check/numerics.py).

    Declaring a policy turns the numerics rules on for the config:
    every dequantize's scale must share a max-abs-reduction root with
    its quantize's (PSC111), the error-feedback residual must close —
    computed, fed to the carry, never double-counted (PSC112, only when
    ``error_feedback`` is declared), every integer accumulation on the
    quantized lattice must provably fit its traced dtype — worst-case
    |sum| from the TRACED axis sizes, not the config-time
    ACCUM_CAPACITY table (PSC113), and every precision-narrowing
    convert downstream of the gradient reduce on the update path must
    be a detected quantization site or a declared allowance (PSC114).

    ``quantized``: the gradient wire carries a quantized lattice — the
    trace must contain at least one rooted quantization site on the
    gradient path, and every integer reduce-kind collective feeding the
    params needs a PROVEN peak (an unbounded int wire sum on a declared
    quantized wire is a finding, not a pass).
    ``accum_dtype``: the declared integer accumulator for the lattice
    sums ("int16"/"int32"); a traced lattice reduction in any OTHER
    dtype is a finding — the static half of PR 12's widened-payload
    regression, caught from dataflow instead of wire bytes.
    """

    quantized: bool = False
    error_feedback: bool = False
    accum_dtype: Optional[str] = None
    allow_narrowing: Tuple[NarrowingAllowance, ...] = ()


@dataclasses.dataclass
class Built:
    """What a spec's builder returns: the real jitted step plus abstract
    example args and a selector for the updated-params subtree."""

    step: Callable
    args: Tuple[Any, ...]
    select_params: Callable[[Any], Any]


@dataclasses.dataclass
class ContractSpec:
    name: str
    build: Callable[[], Built]
    axes: Tuple[str, ...]
    grad_reduce: Tuple[GradReduce, ...] = ()
    wire: Optional[WirePolicy] = None
    donation: Optional[DonationSpec] = None
    fusion: Optional[FusionSpec] = None
    serve: Optional[ServePolicy] = None
    adaptive: Optional[AdaptivePolicy] = None
    overlap: Optional[OverlapPolicy] = None
    numerics: Optional[NumericsPolicy] = None


# metrics / loss pmean: a handful of f32 scalars, every scheme emits it
_METRICS_PSUM = WireAllowance(
    kind="psum", dtype="float32", max_bytes=64,
    reason="metrics/loss pmean (scalars)",
)
# shared-scale agreement for round-1 quantization (ops/quantize pmax)
_SCALE_PMAX = WireAllowance(
    kind="pmax", dtype="float32", max_bytes=4096,
    reason="per-tensor/per-block scale agreement (pmax)",
)
# round-2 scale rows ride an f32 all_gather next to the int8 payload
_SCALE_GATHER = WireAllowance(
    kind="all_gather", dtype="float32", max_bytes=4096,
    reason="round-2 quantization scale rows",
)
# the non-finite gradient guard's mesh-consensus flag: one int32 pmin,
# 4 bytes per step (resilience/guard.py; PSConfig.nonfinite_guard)
_FINITE_PMIN = WireAllowance(
    kind="pmin", dtype="int32", max_bytes=8,
    reason="non-finite gradient guard flag (skip-step consensus)",
)


# input HW shape per contract network (CIFAR-10 shapes for ResNet)
_NETWORK_HW = {"LeNet": (28, 28, 1), "ResNet18": (32, 32, 3)}

# f32 gradient payload bytes per contract network, memoized by a cheap
# eval_shape of the real init (nothing allocates) — the PSC106 budget's
# numerator, derived instead of hard-coded so a model edit cannot
# silently desync the fusion contract
_PAYLOAD_CACHE: dict = {}


def payload_bytes(network: str) -> int:
    if network not in _PAYLOAD_CACHE:
        _PAYLOAD_CACHE[network] = _model_bytes(network)
    return _PAYLOAD_CACHE[network]


def bn_state_bytes(network: str) -> int:
    """f32 bytes of the model's non-parameter state (BatchNorm running
    stats) — the payload the default ``bn_mode="pmean"`` averages across
    workers each step. Derived from the real init's eval_shape, like
    ``payload_bytes``, so the PSC103 allowance below can never desync
    from the model. 0 for BN-free networks (LeNet)."""
    key = (network, "bn")
    if key not in _PAYLOAD_CACHE:
        _PAYLOAD_CACHE[key] = _model_bytes(network, state=True)
    return _PAYLOAD_CACHE[key]


def _model_bytes(network: str, state: bool = False) -> int:
    import jax

    from ..models import build_model, init_model

    model = build_model(network, num_classes=10)
    out = jax.eval_shape(
        lambda: init_model(
            model, jax.random.key(0), (1,) + _NETWORK_HW[network]
        )
    )
    tree = out[1] if state else out[0]
    return 4 * sum(
        int(_prod(l.shape)) for l in jax.tree_util.tree_leaves(tree)
    )


def _prod(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _cnn_ps_built(cfg, network: str) -> Built:
    import jax
    import jax.numpy as jnp
    import optax

    from ..models import build_model
    from ..parallel.mesh import make_hybrid_mesh, make_mesh
    from ..parallel.ps import init_ps_state, make_ps_train_step

    hw = _NETWORK_HW[network]
    model = build_model(network, num_classes=10)
    tx = optax.sgd(0.1)
    if cfg.dcn_hosts > 1:
        mesh = make_hybrid_mesh(cfg.dcn_hosts, cfg.num_workers // cfg.dcn_hosts)
    else:
        mesh = make_mesh(num_workers=cfg.num_workers)
    step = make_ps_train_step(model, tx, cfg, mesh, donate=True)
    state = jax.eval_shape(
        lambda: init_ps_state(model, tx, cfg, jax.random.key(0), (1,) + hw)
    )
    batch = {
        "image": jax.ShapeDtypeStruct(
            (cfg.num_workers,) + hw, jnp.uint8
        ),
        "label": jax.ShapeDtypeStruct((cfg.num_workers,), jnp.int32),
    }
    key = jax.eval_shape(lambda: jax.random.key(0))
    args = (state, batch, key)
    if cfg.adaptive_aggregate:
        # the traced per-window aggregation count (same compiled step
        # for every value — the whole point of the adaptive signature)
        args += (jax.ShapeDtypeStruct((), jnp.int32),)
    return Built(
        step=step,
        args=args,
        select_params=lambda out: out[0].params,
    )


def _ps_spec(
    compress,
    placement,
    dcn_hosts: int = 1,
    bucket_bytes: Optional[int] = None,
    network: str = "LeNet",
    adaptive: bool = False,
    overlap: str = "serial",
    bucket_tag: str = "",
    quant_block_size: int = 0,
    wire_domain: str = "dequant",
    error_feedback: bool = False,
) -> ContractSpec:
    from ..parallel.mesh import DCN_AXIS, WORKER_AXIS

    name = "ps_{}_{}".format(compress or "none", placement)
    if dcn_hosts > 1:
        name = "ps_hier_{}_{}".format(compress, placement)
    if network != "LeNet":
        name = name.replace("ps_", f"ps_{network.lower()}_", 1)
    if bucket_bytes is not None:
        # bucket_tag distinguishes registry entries traced with a
        # different carving of the same scheme (e.g. the 64 KiB
        # multi-bucket PSC109 twins vs the fused "_bucketed" entries)
        name += "_bucketed" + bucket_tag
    if quant_block_size:
        # block-scale granularity changes the scale-row accounting (and
        # can overflow PSC103's scale allowances — the tune/ search uses
        # exactly that as a pruning constraint), so it must be visible
        # in the config name
        name += f"_qb{quant_block_size}"
    homomorphic = wire_domain == "homomorphic"
    if homomorphic:
        name += "_homomorphic"
    if error_feedback:
        name += "_ef"
    if adaptive:
        name += "_adaptive"
    if overlap == "pipelined":
        serial_twin = name
        name += "_pipelined"
    axes: Tuple[str, ...] = (
        (DCN_AXIS, WORKER_AXIS) if dcn_hosts > 1 else (WORKER_AXIS,)
    )

    def make_cfg():
        from ..parallel.ps import PSConfig

        return PSConfig(
            num_workers=MESH_DEVICES,
            compress=compress,
            opt_placement=placement,
            dcn_hosts=dcn_hosts,
            bucket_bytes=bucket_bytes,
            overlap=overlap,
            quant_block_size=quant_block_size,
            wire_domain=wire_domain,
            error_feedback=error_feedback,
            num_aggregate_min=2 if adaptive else None,
            num_aggregate_max=MESH_DEVICES if adaptive else None,
        )

    def build() -> Built:
        return _cnn_ps_built(make_cfg(), network)

    # the reduce that must feed the optimizer, per §6b ladder rung:
    # lossless/int8 reduce with a psum (psum_scatter when ZeRO-1 sharded);
    # the 2-round schemes reduce via all_to_all + local sum
    if compress == "int8_2round":
        reduce_kinds: Tuple[str, ...] = ("all_to_all",)
    elif placement == "sharded":
        reduce_kinds = ("psum_scatter",)
    else:
        reduce_kinds = ("psum",)
    grad_reduce = tuple(GradReduce(a, reduce_kinds) for a in axes)

    wire = None
    if compress == "int8_2round":
        if homomorphic:
            # compressed-domain wire (§6h): round 2's requantization is
            # a lattice rescale with the round-1 scales everyone already
            # holds — the f32 scale-row gather allowance disappears, and
            # the hierarchical reassembly gathers int8 payload so its
            # f32 allowance disappears too. The allowance list is
            # STRICTLY SMALLER than the dequant twin's; that shrink is
            # the proof mechanism the homomorphic mode banks on.
            allow = [_METRICS_PSUM, _SCALE_PMAX, _FINITE_PMIN]
        else:
            allow = [_METRICS_PSUM, _SCALE_PMAX, _SCALE_GATHER,
                     _FINITE_PMIN]
        if bn_state_bytes(network):
            # BatchNorm running stats (bn_mode="pmean", the default)
            # ride an f32 psum sized by the model's own state tree —
            # statistics, not gradient payload, so they are allowed on
            # a compressed wire. BN-free registry networks (LeNet)
            # never declare this, so committed entries are unchanged.
            allow.append(WireAllowance(
                kind="psum", dtype="float32",
                max_bytes=bn_state_bytes(network),
                reason="BatchNorm cross-replica stats pmean "
                       "(bn_mode=pmean; model state, not gradients)",
            ))
        if placement == "sharded":
            allow.append(
                WireAllowance(
                    kind="all_gather", dtype="float32", max_bytes=None,
                    reason="ZeRO-1 f32 update all_gather (the weight "
                           "bcast analogue; §6b sharded placement)",
                )
            )
        if dcn_hosts > 1 and not homomorphic:
            allow.append(
                WireAllowance(
                    kind="all_gather", dtype="float32", max_bytes=None,
                    axes=(WORKER_AXIS,),
                    reason="hierarchical reassembly all_gather rides ICI "
                           "only (§6b: spend bytes on the link that has "
                           "them)",
                )
            )
        wire = WirePolicy(axes=axes, payload_dtype="int8",
                          allow=tuple(allow))
    elif compress == "int8" and homomorphic:
        # the dequant "int8" scheme cannot declare a wire policy at all
        # (its psum payload is int32 by design); the homomorphic twin
        # CAN — the payload IS the minimal exact accumulator
        # (ops/quantize.accum_dtype: int16 on the 8-device registry
        # mesh), and any f32/int32 widening back onto the wire trips
        # PSC103. New policing the dequant twin never had.
        from ..ops.quantize import accum_dtype

        import jax.numpy as jnp

        allow = [_METRICS_PSUM, _SCALE_PMAX, _FINITE_PMIN]
        if bn_state_bytes(network):
            allow.append(WireAllowance(
                kind="psum", dtype="float32",
                max_bytes=bn_state_bytes(network),
                reason="BatchNorm cross-replica stats pmean "
                       "(bn_mode=pmean; model state, not gradients)",
            ))
        if placement == "sharded":
            allow.append(
                WireAllowance(
                    kind="all_gather", dtype="float32", max_bytes=None,
                    reason="ZeRO-1 f32 update all_gather (the weight "
                           "bcast analogue; §6b sharded placement)",
                )
            )
        wire = WirePolicy(
            axes=axes,
            payload_dtype=jnp.dtype(accum_dtype(MESH_DEVICES)).name,
            allow=tuple(allow),
        )

    fusion = None
    if bucket_bytes is not None or placement == "sharded":
        # bucketed configs declare their O(n_buckets) budget; the ZeRO-1
        # sharded wire is flat by construction, so its fusion contract
        # (ONE reduce per step) holds even in the legacy spelling. The
        # hierarchical scheme legitimately pays two all_to_alls per
        # bucket (ICI scatter + DCN scatter).
        from ..parallel.ps import wire_align

        fusion = FusionSpec(
            payload_bytes=payload_bytes(network),
            bucket_bytes=bucket_bytes or 0,
            align=wire_align(make_cfg()),
            per_bucket=2 if dcn_hosts > 1 else 1,
        )

    adaptive_policy = None
    if adaptive:
        # the envelope: exactly the bytes the equivalent STATIC config's
        # gradient reduce moves — adaptation must not add any. Both
        # registered adaptive wires carry 4 B/element on the reduce path
        # (f32 psum; int32 psum_scatter for the ZeRO-1 int8 wire), over
        # the engine's own padded bucket plan, so the bound is derived
        # from the same plan_buckets the step uses.
        from ..parallel.buckets import plan_buckets
        from ..parallel.ps import wire_align

        cfg = make_cfg()
        plan = plan_buckets(
            payload_bytes(network) // 4, cfg.bucket_bytes or 0,
            align=wire_align(cfg),
        )
        adaptive_policy = AdaptivePolicy(
            min_aggregate=cfg.num_aggregate_min,
            max_aggregate=cfg.num_aggregate_max,
            envelope_bytes=plan.padded_total * 4,
            # the host controller's proposal is min-reduced across
            # processes before the traced count changes (PSC110)
            consensus="trainer.Trainer._count_consensus",
        )

    overlap_policy = None
    if overlap == "pipelined":
        overlap_policy = OverlapPolicy(mode="pipelined",
                                       serial_twin=serial_twin)

    # the precision-flow contract (PSC111-114): which integer
    # accumulator the quantized lattice sums into, per wire scheme —
    # quantized_psum widens int8 -> int32 (homomorphic: the minimal
    # exact accumulator, int16 on the registry mesh); both 2round
    # schemes sum their all_to_all'd slices in local int32
    if compress == "int8" and homomorphic:
        import jax.numpy as jnp

        from ..ops.quantize import accum_dtype

        num = NumericsPolicy(
            quantized=True,
            accum_dtype=jnp.dtype(accum_dtype(MESH_DEVICES)).name,
            error_feedback=error_feedback,
        )
    elif compress in ("int8", "int8_2round"):
        num = NumericsPolicy(quantized=True, accum_dtype="int32",
                             error_feedback=error_feedback)
    else:
        num = NumericsPolicy(quantized=False)

    return ContractSpec(
        name=name,
        build=build,
        axes=axes,
        grad_reduce=grad_reduce,
        wire=wire,
        donation=DonationSpec(argnums=(0,), out_positions=(0,)),
        fusion=fusion,
        adaptive=adaptive_policy,
        overlap=overlap_policy,
        numerics=num,
    )


def _lm_cfg():
    from ..models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=32, dim=16, depth=2, heads=4, max_seq_len=16
    )


def _dp_tp_spec() -> ContractSpec:
    from ..parallel.mesh import WORKER_AXIS
    from ..parallel.tp import TP_AXIS

    def build() -> Built:
        import jax
        import jax.numpy as jnp
        import optax

        from ..parallel.dp_tp import make_dp_tp_train_step, make_mesh_dp_tp
        from ..parallel.tp import _tp_param_shapes

        cfg = _lm_cfg()
        tx = optax.sgd(0.1)
        mesh = make_mesh_dp_tp(4, 2)
        step = make_dp_tp_train_step(cfg, tx, mesh, donate=True)
        params = _tp_param_shapes(cfg)
        opt = jax.eval_shape(tx.init, params)
        toks = jax.ShapeDtypeStruct((8, 16), jnp.int32)
        return Built(
            step=step,
            args=(params, opt, toks),
            select_params=lambda out: out[0],
        )

    return ContractSpec(
        name="dp_tp",
        build=build,
        axes=(WORKER_AXIS, TP_AXIS),
        grad_reduce=(
            GradReduce(WORKER_AXIS, ("psum",)),
            GradReduce(TP_AXIS, ("psum",)),
        ),
        donation=DonationSpec(argnums=(0, 1), out_positions=(0, 1)),
        numerics=NumericsPolicy(),
    )


def _pp_spec() -> ContractSpec:
    from ..parallel.pp import PP_AXIS

    def build() -> Built:
        import jax
        import jax.numpy as jnp
        import optax

        from ..parallel.pp import (
            _pp_param_shapes,
            make_pp_mesh,
            make_pp_train_step,
        )

        cfg = _lm_cfg()
        tx = optax.sgd(0.1)
        mesh = make_pp_mesh(2)
        step = make_pp_train_step(cfg, tx, mesh, num_microbatches=2,
                                  donate=True)
        params = _pp_param_shapes(cfg)
        opt = jax.eval_shape(tx.init, params)
        toks = jax.ShapeDtypeStruct((4, 16), jnp.int32)
        return Built(
            step=step,
            args=(params, opt, toks),
            select_params=lambda out: out[0],
        )

    return ContractSpec(
        name="pp",
        build=build,
        axes=(PP_AXIS,),
        grad_reduce=(GradReduce(PP_AXIS, ("psum",)),),
        donation=DonationSpec(argnums=(0, 1), out_positions=(0, 1)),
        numerics=NumericsPolicy(),
    )


def _moe_spec() -> ContractSpec:
    from ..parallel.moe import EP_AXIS

    def build() -> Built:
        import jax
        import jax.numpy as jnp
        import optax

        from ..parallel.moe import (
            MoEConfig,
            _moe_param_shapes,
            make_ep_mesh,
            make_moe_train_step,
        )

        cfg = _lm_cfg()
        moe = MoEConfig(num_experts=MESH_DEVICES)
        tx = optax.sgd(0.1)
        mesh = make_ep_mesh(MESH_DEVICES)
        step = make_moe_train_step(cfg, moe, tx, mesh, donate=True)
        params = _moe_param_shapes(cfg, moe)
        opt = jax.eval_shape(tx.init, params)
        toks = jax.ShapeDtypeStruct((8, 16), jnp.int32)
        return Built(
            step=step,
            args=(params, opt, toks),
            select_params=lambda out: out[0],
        )

    return ContractSpec(
        name="moe",
        build=build,
        axes=(EP_AXIS,),
        grad_reduce=(GradReduce(EP_AXIS, ("psum",)),),
        donation=DonationSpec(argnums=(0, 1), out_positions=(0, 1)),
        numerics=NumericsPolicy(),
    )


def _dp_tp_pp_spec() -> ContractSpec:
    from ..parallel.dp_tp_pp import DP_AXIS
    from ..parallel.pp import PP_AXIS
    from ..parallel.tp import TP_AXIS

    def build() -> Built:
        import jax
        import jax.numpy as jnp
        import optax

        from ..models.transformer import init_transformer
        from ..parallel.dp_tp_pp import (
            make_3d_train_step,
            make_mesh_3d,
            to_3d_layout,
        )

        cfg = _lm_cfg()
        tx = optax.sgd(0.1)
        mesh = make_mesh_3d(2, 2, 2)
        step = make_3d_train_step(cfg, tx, mesh, num_microbatches=2,
                                  donate=True)
        params = jax.eval_shape(
            lambda: to_3d_layout(cfg, init_transformer(cfg, jax.random.key(0)))
        )
        opt = jax.eval_shape(tx.init, params)
        toks = jax.ShapeDtypeStruct((4, 16), jnp.int32)
        return Built(
            step=step,
            args=(params, opt, toks),
            select_params=lambda out: out[0],
        )

    return ContractSpec(
        name="dp_tp_pp",
        build=build,
        axes=(DP_AXIS, PP_AXIS, TP_AXIS),
        grad_reduce=(
            GradReduce(DP_AXIS, ("psum",)),
            GradReduce(PP_AXIS, ("psum",)),
            GradReduce(TP_AXIS, ("psum",)),
        ),
        donation=DonationSpec(argnums=(0, 1), out_positions=(0, 1)),
        numerics=NumericsPolicy(),
    )


def _serve_spec(int8_kv: bool) -> ContractSpec:
    """The serving hot path's contract: the REAL compiled decode step
    (serve/engine.make_decode_step — the same factory the engine jits),
    traced over abstract pool/weights. Zero collectives, donated KV
    pool, declared storage dtype (PSC105 + PSC107)."""

    def build() -> Built:
        import jax
        import jax.numpy as jnp

        from ..models.transformer import init_transformer
        from ..parallel.buckets import FlatVector, plan_buckets, tree_layout
        from ..serve.engine import ServeConfig, make_decode_step
        from ..serve.kv import init_kv_pool

        cfg = _lm_cfg()
        serve = ServeConfig(
            slots=MESH_DEVICES, max_len=16, max_prompt_len=8,
            kv_int8=int8_kv,
        )
        params_tree = jax.eval_shape(
            lambda: init_transformer(cfg, jax.random.key(0))
        )
        layout = tree_layout(params_tree)
        plan = plan_buckets(layout.total, 0, align=1)
        params = FlatVector(
            flat=jax.ShapeDtypeStruct((plan.padded_total,), jnp.float32),
            layout=layout, plan=plan,
        )
        pool = jax.eval_shape(
            lambda: init_kv_pool(cfg, serve.slots, serve.max_len,
                                 int8=serve.kv_int8)
        )
        step = jax.jit(make_decode_step(cfg, serve), donate_argnums=(1,))
        s = serve.slots
        return Built(
            step=step,
            args=(
                params,
                pool,
                jax.ShapeDtypeStruct((s,), jnp.int32),
                jax.ShapeDtypeStruct((s,), jnp.int32),
                jax.ShapeDtypeStruct((s,), jnp.bool_),
            ),
            # the pool is the state that persists across ticks
            select_params=lambda out: out[0],
        )

    return ContractSpec(
        name="serve_decode" + ("_int8kv" if int8_kv else ""),
        build=build,
        axes=(),  # slot-parallel: NO mesh axis may be consumed
        donation=DonationSpec(argnums=(1,), out_positions=(0,)),
        serve=ServePolicy(kv_argnum=1, quantized=int8_kv),
        numerics=NumericsPolicy(quantized=int8_kv),
    )


# the flagship bucketed config's bucket size (4 MiB): ResNet18's
# ~44.7 MB f32 gradient payload -> 11 buckets instead of 62 per-leaf
# collectives. MiB-scale buckets amortize collective latency without
# blowing up program size; tiny buckets on big models de-fuse again.
RESNET_BUCKET_BYTES = 4 << 20


def get_contracts() -> Tuple[ContractSpec, ...]:
    """The committed registry: the PS matrix (compress x placement, plus
    the hierarchical DCN x ICI composition), the bucketed-wire variants
    (PSC106), the ResNet per-leaf/bucketed pair whose artifact rows
    document the collective-count collapse, and the LM schemes."""
    specs = [
        _ps_spec(c, p)
        for c in (None, "int8", "int8_2round")
        for p in ("replicated", "sharded")
    ]
    specs.append(_ps_spec("int8_2round", "replicated", dcn_hosts=2))
    # fused-wire variants of every replicated scheme (bucket_bytes=0: ONE
    # flat buffer; the sharded placement is already flat, its legacy
    # specs above carry the fusion contract directly)
    specs.extend(
        _ps_spec(c, "replicated", bucket_bytes=0)
        for c in (None, "int8", "int8_2round")
    )
    specs.append(
        _ps_spec("int8_2round", "replicated", dcn_hosts=2, bucket_bytes=0)
    )
    # the headline A/B pair: the reference-shaped per-leaf wire vs the
    # 4 MiB bucketed wire on the real ResNet18 gradient pytree — the
    # committed artifact pins one-psum-per-leaf collapsing to
    # ceil(payload / bucket_bytes)
    specs.append(_ps_spec("int8", "replicated", network="ResNet18"))
    specs.append(
        _ps_spec(
            "int8", "replicated", network="ResNet18",
            bucket_bytes=RESNET_BUCKET_BYTES,
        )
    )
    # adaptive partial aggregation (PSC108): the traced-count mask on the
    # fused replicated wire and on the ZeRO-1 int8 scatter — the two
    # paths whose masking/denominator code diverges in ps.py
    specs.append(
        _ps_spec(None, "replicated", bucket_bytes=0, adaptive=True)
    )
    specs.append(_ps_spec("int8", "sharded", adaptive=True))
    # PSC109 serial/pipelined twins (overlap="pipelined", §6g): a
    # genuinely multi-bucket LeNet pair per wire family at 64 KiB
    # buckets (LeNet's ~1.7 MB payload -> ~27 buckets), the flagship
    # ResNet18 int8 4 MiB config's pipelined twin, and the ZeRO-1
    # scatter's — each pipelined entry pins "same bytes, different
    # schedule" against the serial entry traced beside it
    for ov in ("serial", "pipelined"):
        specs.append(_ps_spec(None, "replicated", bucket_bytes=64 << 10,
                              bucket_tag="64k", overlap=ov))
        specs.append(_ps_spec("int8", "replicated", bucket_bytes=64 << 10,
                              bucket_tag="64k", overlap=ov))
    specs.append(
        _ps_spec(
            "int8", "replicated", network="ResNet18",
            bucket_bytes=RESNET_BUCKET_BYTES, overlap="pipelined",
        )
    )
    specs.append(_ps_spec("int8", "sharded", overlap="pipelined"))
    # homomorphic (compressed-domain) twins of the committed int8 wires
    # (§6h, wire_domain="homomorphic"): the artifact rows document the
    # f32 widening leaving the wire — the "int8" psum narrows int32 ->
    # int16, the 2round gather hop drops its f32 scale rows, and the
    # hierarchical twin's ICI reassembly shrinks f32 -> int8 (4x). Each
    # twin's PSC103 allowance list is strictly smaller than (or, for
    # "int8", newly existent vs) its dequant twin's.
    specs.append(_ps_spec("int8", "replicated", wire_domain="homomorphic"))
    specs.append(_ps_spec("int8", "sharded", wire_domain="homomorphic"))
    specs.append(_ps_spec("int8_2round", "replicated", bucket_bytes=0,
                          wire_domain="homomorphic"))
    specs.append(_ps_spec("int8_2round", "sharded",
                          wire_domain="homomorphic"))
    specs.append(_ps_spec("int8_2round", "replicated", dcn_hosts=2,
                          bucket_bytes=0, wire_domain="homomorphic"))
    # the cost-model leg: the flagship ResNet18 bucketed int8 wire in
    # the compressed domain (tests/test_tune.py pins that the model
    # ranks it <= the dequant twin), plus a pipelined 64 KiB pair so
    # PSC109's same-bytes/per-bucket-dispatch pins hold on the
    # homomorphic wire too
    specs.append(
        _ps_spec(
            "int8", "replicated", network="ResNet18",
            bucket_bytes=RESNET_BUCKET_BYTES, wire_domain="homomorphic",
        )
    )
    for ov in ("serial", "pipelined"):
        specs.append(_ps_spec("int8", "replicated", bucket_bytes=64 << 10,
                              bucket_tag="64k", overlap=ov,
                              wire_domain="homomorphic"))
    # error feedback: the registry's one EF entry, homomorphic 2round at
    # 64 KiB buckets — PSC112 proves the residual closes over the
    # wire's own round-1 quantization site
    specs.append(_ps_spec("int8_2round", "replicated",
                          bucket_bytes=64 << 10, bucket_tag="64k",
                          wire_domain="homomorphic", error_feedback=True))
    specs.extend(
        [_dp_tp_spec(), _pp_spec(), _moe_spec(), _dp_tp_pp_spec()]
    )
    # the serving hot path (ARCHITECTURE §7e): the compiled decode step
    # must stay collective-free with a donated, dtype-honest KV pool
    specs.extend([_serve_spec(False), _serve_spec(True)])
    return tuple(specs)
