"""Headline benchmark — the reference's own single-node workload on one chip.

The reference's published scaling curves are normalized to a single-node time
of 526.16 s for 100 steps of LeNet/MNIST at global batch 8192 on an EC2
m4.2xlarge (analysis/Speedup_Comparisons_LeNet.ipynb cells 1+5: per-step
"Time Cost" log lines summed over steps <= 100), i.e. ~1557 images/sec.

This benchmark runs the identical workload — LeNet, MNIST-shaped data,
batch 8192, 100 optimizer steps, same SGD hyperparameters as the reference's
canonical config (src/run_pytorch.sh) — through this framework's PS train
step on the available accelerator, and reports throughput.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
(unit is images/sec for the lenet/resnet18 workloads, tokens/sec for the
opt-in BENCH_WORKLOAD=lm transformer workload; the lm metric name encodes
the measured config).
"""

import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

REF_STEPS = 100
REF_BATCH = 8192
REF_SINGLE_NODE_SECONDS = 526.16  # Speedup_Comparisons_LeNet.ipynb cell 1
REF_IMAGES_PER_SEC = REF_STEPS * REF_BATCH / REF_SINGLE_NODE_SECONDS

# BENCH_WORKLOAD selects the measured config; the default is the workload
# behind the reference's published normalization constant (see module
# docstring). "resnet18" is the reference's canonical training config
# (run_pytorch.sh: ResNet18/CIFAR-10 b=1024, compression on) — reported
# against the same per-image baseline since the reference publishes no
# absolute ResNet throughput.
WORKLOADS = {
    "lenet": dict(network="LeNet", dataset="MNIST", batch=REF_BATCH,
                  compress=None, metric="lenet_mnist_b8192_train_throughput"),
    "resnet18": dict(network="ResNet18", dataset="Cifar10", batch=1024,
                     compress="int8",
                     metric="resnet18_cifar10_b1024_train_throughput"),
    # beyond the reference (it has no LM workloads): one-chip transformer
    # training throughput in tokens/sec; vs_baseline is per-sample against
    # the same reference normalization (apples-to-oranges, labeled as such).
    # The metric name is built from the actual (env-overridable) config.
    "lm": dict(metric=None),
    # serving side of the same transformer: KV-cache autoregressive
    # generation (models/decode.py), tokens/sec of NEW tokens
    "decode": dict(metric=None),
    # the serving ENGINE under open-loop traffic: continuous-batching
    # slot pool + scheduler (ps_pytorch_tpu/serve), tokens/sec of
    # completed tokens plus p50/p99 per-token latency
    "serve": dict(metric=None),
}


# one source for the lm workload's env-overridable defaults, consumed by
# BOTH _bench_lm and _lm_tag so success and error records share a metric key
_LM_DEFAULTS = {"BATCH": 8, "SEQ": 1024, "DIM": 512, "DEPTH": 6, "SP": 1}


def _chain() -> int:
    """BENCH_CHAIN=K runs K train steps inside ONE jitted lax.fori_loop
    per dispatch. Every host dispatch has a fixed launch cost; when the
    step is short (LeNet) per-call dispatch makes the benchmark partly a
    dispatch-rate measurement, and chaining amortizes it so the record
    reflects the device. Identical math (same step, same data flow);
    default 1 is the per-call behavior the trainer itself has."""
    return max(1, int(os.environ.get("BENCH_CHAIN", 1)))


def _chain_steps(step_fn, n_iter):
    """Wrap a (carry -> carry) step in a jitted n_iter-deep fori_loop."""
    import jax
    from jax import lax

    @jax.jit
    def run(carry):
        return lax.fori_loop(0, n_iter, lambda i, c: step_fn(c), carry)

    return run


def _timed_chain(step_fn, carry, sync, steps, k):
    """Shared chained-measurement protocol for every workload: compile+warm
    the K-deep loop, then time ceil-free outer iterations. `sync` is the
    workload's host-read barrier over a carry. Returns
    (final_carry, elapsed_seconds, actual_steps)."""
    run = _chain_steps(step_fn, k)
    carry = run(carry)  # compile + warm the chained program
    sync(carry)
    outer = max(1, steps // k)
    t0 = time.perf_counter()
    for _ in range(outer):
        carry = run(carry)
    sync(carry)
    return carry, time.perf_counter() - t0, outer * k


def _lm_env(name: str) -> int:
    return int(os.environ.get(f"BENCH_LM_{name}", _LM_DEFAULTS[name]))


# single source for the BENCH_DTYPE contract, shared by _validate_env,
# _bench_dtype, _lm_tag, and the error-record tagging — these must agree
# or a failed run's metric key diverges from its success key
_BENCH_DTYPES = ("float32", "bfloat16")
_LM_DTYPE_DEFAULT = "bfloat16"  # MXU-native; CNNs default float32 (parity)
_CNN_DTYPE_DEFAULT = "float32"


_DEC_DEFAULTS = {"BATCH": 8, "PROMPT": 128, "NEW": 128, "DIM": 512,
                 "DEPTH": 6}

# the serve leg's own knobs; model shape comes from the SAME BENCH_DEC_*
# envs as the decode leg (serving measures the same model, open-loop)
_SRV_DEFAULTS = {"SLOTS": 8, "REQS": 32}
_SRV_RATE_DEFAULT = 100.0


def _dec_env(name: str) -> int:
    return int(os.environ.get(f"BENCH_DEC_{name}", _DEC_DEFAULTS[name]))


def _srv_env(name: str) -> int:
    return int(os.environ.get(f"BENCH_SRV_{name}", _SRV_DEFAULTS[name]))


def _srv_rate() -> float:
    """Arrival rate is a FLOAT everywhere traffic is modeled (TrafficConfig
    .rate_rps, cli/serve --rate) — sub-1 rps open-loop regimes are real."""
    return float(os.environ.get("BENCH_SRV_RATE", _SRV_RATE_DEFAULT))


def _dec_shape_tag(extra: str) -> str:
    """THE decode-family metric-shape helper: model shape from the SAME
    BENCH_DEC_* envs both the decode and serve workloads read, plus the
    leg's own ``extra`` knob segment; error records share the key (same
    contract as _lm_tag). One parser, two legs — the tags cannot drift."""
    tag = (
        f"d{_dec_env('DIM')}x{_dec_env('DEPTH')}"
        f"_p{_dec_env('PROMPT')}_n{_dec_env('NEW')}{extra}"
    )
    if os.environ.get("BENCH_DTYPE", _LM_DTYPE_DEFAULT) == "float32":
        tag += "_f32"
    return tag


def _dec_tag() -> str:
    return _dec_shape_tag(f"_b{_dec_env('BATCH')}")


def _srv_tag() -> str:
    # %g renders integral rates without a trailing .0 ("r100", "r0.5")
    extra = f"_s{_srv_env('SLOTS')}_r{_srv_rate():g}"
    if os.environ.get("BENCH_SRV_INT8KV") == "1":
        extra += "_q8kv"
    if os.environ.get("BENCH_SRV_OVERLOAD") == "1":
        # the overload drill (10x spike + deadlines + admission control)
        # measures goodput under shedding — a different regime, its own
        # metric key
        extra += "_ovl"
    return _dec_shape_tag(extra)


def _bench_decode(steps: int) -> tuple:
    """KV-cache autoregressive generation throughput: NEW tokens/sec across
    the batch (prefill included in the measured loop — it is part of
    serving a request)."""
    import jax
    import jax.numpy as jnp

    from ps_pytorch_tpu.models.decode import make_generate
    from ps_pytorch_tpu.models.transformer import (
        TransformerConfig,
        init_transformer,
    )
    from ps_pytorch_tpu.utils import host_sync

    batch, t_prompt = _dec_env("BATCH"), _dec_env("PROMPT")
    n_new = _dec_env("NEW")
    _, dt = _bench_dtype(jnp, _LM_DTYPE_DEFAULT)
    cfg = TransformerConfig(
        vocab_size=2048,
        dim=_dec_env("DIM"),
        depth=_dec_env("DEPTH"),
        heads=8,
        max_seq_len=t_prompt + n_new,
        compute_dtype=dt,
    )
    params = init_transformer(cfg, jax.random.key(0))
    gen = make_generate(cfg, max_new_tokens=n_new)
    prompt = jax.random.randint(
        jax.random.key(1), (batch, t_prompt), 0, cfg.vocab_size, jnp.int32
    )
    # greedy decode (temperature=0): the key argument is unconsumed — what
    # we're timing is the KV-cache scan, not sampling. Each iteration's
    # prompt takes a token from the previous output so the calls form a
    # data-dependence chain: call N cannot retire before call N-1 however
    # the backend streams its dispatches, so the final host_sync bounds
    # ALL steps.
    key = jax.random.key(2)
    # ONE compile via the AOT path: warmup, the timed loop, and the
    # op-count probe all share it (a second jit-cache compile of the
    # KV-cache scan would dominate smoke-window startup)
    compiled = gen.lower(params, prompt, key).compile()
    try:
        from ps_pytorch_tpu.check.opcount import hlo_op_count

        hlo_ops = hlo_op_count(compiled.as_text())
    except Exception:
        hlo_ops = None
    out = compiled(params, prompt, key)
    host_sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = compiled(params, prompt, key)
        prompt = prompt.at[:, 0].set(out[:, -1] % cfg.vocab_size)
    host_sync(out, prompt)
    elapsed = time.perf_counter() - t0
    return batch * n_new * steps / elapsed, elapsed, hlo_ops


def _bench_serve() -> tuple:
    """Open-loop serving throughput/latency: the continuous-batching
    engine (ps_pytorch_tpu/serve) under the seeded Poisson traffic
    generator — tokens/sec of completed new tokens plus p50/p99
    per-token latency. Mixed request shapes (prompt lengths in
    [PROMPT/2, PROMPT], budgets in [NEW/2, NEW]) exercise admission,
    eviction, and slot reuse; the compile warmup runs outside the
    measured window (the decode bench excludes compile the same way)."""
    import jax
    import jax.numpy as jnp

    from ps_pytorch_tpu.models.transformer import (
        TransformerConfig,
        init_transformer,
    )
    from ps_pytorch_tpu.serve import (
        AdmissionController,
        ServeConfig,
        ServingEngine,
        TrafficConfig,
        make_requests,
        run_open_loop,
    )

    _, dt = _bench_dtype(jnp, _LM_DTYPE_DEFAULT)
    t_prompt, n_new = _dec_env("PROMPT"), _dec_env("NEW")
    cfg = TransformerConfig(
        vocab_size=2048,
        dim=_dec_env("DIM"),
        depth=_dec_env("DEPTH"),
        heads=8,
        max_seq_len=t_prompt + n_new,
        compute_dtype=dt,
    )
    from ps_pytorch_tpu.obs import Tracer, summarize_spans

    params = init_transformer(cfg, jax.random.key(0))
    serve = ServeConfig(
        slots=_srv_env("SLOTS"),
        max_len=t_prompt + n_new,
        max_prompt_len=t_prompt,
        kv_int8=os.environ.get("BENCH_SRV_INT8KV") == "1",
    )
    # the overload drill (BENCH_SRV_OVERLOAD=1): a 10x seeded traffic
    # spike over the whole nominal schedule, per-request deadlines, and
    # SLO-aware admission — measures GOODPUT under shedding, where the
    # plain leg measures throughput under headroom
    overload = os.environ.get("BENCH_SRV_OVERLOAD") == "1"
    reqs, rate = _srv_env("REQS"), _srv_rate()
    admission = None
    if overload:
        admission = AdmissionController(
            slo_budget_s=float(os.environ.get("BENCH_SRV_SLO", "1.0")),
            window_s=0.1,
        )
    # in-memory tracer (no file): the drained spans become the record's
    # per-phase breakdown
    tracer = Tracer("bench_serve")
    engine = ServingEngine(cfg, params, serve, tracer=tracer,
                           admission=admission)
    engine.warmup()
    tracer.drain()  # compile-warmup spans are not the measurement
    try:
        from ps_pytorch_tpu.check.opcount import hlo_op_count

        hlo_ops = hlo_op_count(engine.compiled_decode_text())
    except Exception:
        hlo_ops = None
    tc = TrafficConfig(
        n_requests=reqs,
        rate_rps=rate,
        prompt_len_min=max(1, t_prompt // 2),
        prompt_len_max=t_prompt,
        new_tokens_min=max(1, n_new // 2),
        new_tokens_max=n_new,
        vocab_size=cfg.vocab_size,
        seed=0,
        spike=(10.0, 0.0, reqs / rate) if overload else None,
        deadline_s=(
            float(os.environ.get("BENCH_SRV_DEADLINE", "2.0"))
            if overload else None
        ),
    )
    summary = run_open_loop(engine, make_requests(tc))
    return summary, hlo_ops, summarize_spans(tracer.drain())


def _serve_contract_entry():
    """The committed serve accounting row for the MEASURED KV config
    (serve_decode / serve_decode_int8kv) — pinned ZERO collectives/bytes
    (PSC107); attached to the record so the serving wire's silence is
    evidence, not assumption."""
    name = (
        "serve_decode_int8kv"
        if os.environ.get("BENCH_SRV_INT8KV") == "1"
        else "serve_decode"
    )
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        with open(os.path.join(here, "runs", "comm_contract.json")) as f:
            data = json.load(f)
        entry = data["configs"][name]
    except (OSError, ValueError, KeyError):
        return None
    return {
        "config": name,
        "n_collectives": entry["n_collectives"],
        "wire_bytes": entry["total_bytes"],
        "mesh_devices": data.get("mesh_devices"),
    }


def _bench_dtype(jnp, default: str):
    """(name, jnp dtype) from BENCH_DTYPE (validated by _validate_env
    before backend init; re-checked here for library callers)."""
    name = os.environ.get("BENCH_DTYPE", default)
    table = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
    if name not in table:
        raise SystemExit(
            f"BENCH_DTYPE must be one of {sorted(table)}, got {name!r}"
        )
    return name, table[name]


def _lm_tag() -> str:
    """The lm metric's shape tag, derived from the SAME BENCH_LM_* envs
    (and defaults) the workload reads."""
    tag = (
        f"d{_lm_env('DIM')}x{_lm_env('DEPTH')}"
        f"_s{_lm_env('SEQ')}_b{_lm_env('BATCH')}"
    )
    if os.environ.get("BENCH_LM_FLASH") == "1":
        tag += "_flash"
    if _lm_env("SP") > 1:
        tag += f"_sp{_lm_env('SP')}"
    if os.environ.get("BENCH_DTYPE", _LM_DTYPE_DEFAULT) == "float32":
        tag += "_f32"
    return tag


def _cnn_dtype_suffix() -> str:
    """Metric-key dtype tag for the CNN workloads (success AND error
    records must share it)."""
    if os.environ.get("BENCH_DTYPE", _CNN_DTYPE_DEFAULT) == "bfloat16":
        return "_bf16"
    return ""


# BENCH_COMPRESS overrides a CNN workload's gradient-compression mode
# (default: the workload's canonical mode — int8 for resnet18, none for
# lenet). Overridden records get a distinct metric key so they can never
# shadow the canonical record.
_COMPRESS_VALUES = ("none", "int8", "int8_2round")


def _cnn_compress(default):
    val = os.environ.get("BENCH_COMPRESS")
    if val is None:
        return default, ""
    mode = None if val == "none" else val
    if mode == default:
        return default, ""  # explicit request for the canonical mode
    tag = {"none": "_nocomp", "int8": "_int8w",
           "int8_2round": "_2round"}[val]
    return mode, tag


# BENCH_BUCKET_BYTES selects the CNN workloads' gradient wire granularity
# (PSConfig.bucket_bytes): unset = legacy per-leaf collectives, 0 = one
# fused flat buffer, N = ~N-byte buckets. BENCH_AB_BUCKETING=1 instead
# runs BOTH variants (per-leaf, then bucketed at BENCH_BUCKET_BYTES or 0)
# and emits them in ONE record, so the fusion win is measured in the same
# process on the same data. Either mode tags the metric key so these
# records never shadow the canonical record.
def _bench_bucket_bytes():
    val = os.environ.get("BENCH_BUCKET_BYTES")
    return None if val is None else int(val)


def _bucket_tag() -> str:
    if os.environ.get("BENCH_AB_BUCKETING") == "1":
        return "_ab_bucketing"
    bb = _bench_bucket_bytes()
    return "" if bb is None else f"_bkt{bb}"


# BENCH_AB_STATE_LAYOUT=1 runs the CNN workload TWICE in one process —
# PSConfig.state_layout="tree" then "flat" — and emits both in ONE record
# (same shape as the bucketing A/B), each variant carrying its compiled
# hlo_op_count and jaxpr update-path op count so the trajectory JSONs
# capture the update-path collapse, not just walltime. Mutually exclusive
# with BENCH_AB_BUCKETING (one A/B dimension per record).
def _layout_tag() -> str:
    if os.environ.get("BENCH_AB_STATE_LAYOUT") == "1":
        return "_ab_state_layout"
    return ""


# BENCH_AB_OVERLAP=1 runs the CNN workload TWICE in one process —
# PSConfig.overlap="serial" then "pipelined" on the same wire
# (BENCH_BUCKET_BYTES or the fused plan) — and emits both in ONE record:
# per-variant step walltime, dispatch/sync span breakdown (an in-memory
# obs tracer around the measured window), compiled hlo_op_count, and the
# jaxpr schedule-freedom numbers (parallel/overlap.py), so the record
# carries both what the host measured and what the program's dataflow
# permits. Mutually exclusive with the other A/B dimensions.
def _overlap_tag() -> str:
    if os.environ.get("BENCH_AB_OVERLAP") == "1":
        return "_ab_overlap"
    return ""


# BENCH_AB_WIRE=1 runs the CNN workload TWICE in one process —
# PSConfig.wire_domain="dequant" then "homomorphic" on the same
# compressed wire (§6h) — and emits both in ONE record: per-variant step
# walltime, compiled hlo_op_count, backend stamp, and the committed
# contract's comm shape incl. the gradient-path wire bytes, so the
# record shows the compressed-domain byte shrink next to the measured
# walltime. Needs a compressed BENCH_COMPRESS (the homomorphic domain
# has nothing to sum on an f32 wire); mutually exclusive with the other
# A/B dimensions.
def _wire_tag() -> str:
    if os.environ.get("BENCH_AB_WIRE") == "1":
        return "_ab_wire"
    return ""


# BENCH_AB_PRECISION=1 runs the CNN workload TWICE in one process —
# static int8 (PSConfig.precision_adapt off) then the telemetry-adaptive
# per-bucket wire (§6i: a PrecisionController retags buckets skip/4-bit/
# int8/hi from the step's bucket_sqnorm telemetry, values-not-bytes, no
# retrace) on the SAME 64 KiB bucketed wire — and emits both in ONE
# record: per-variant walltime, backend stamp, the committed contract's
# comm shape, and the adaptive variant's tag histogram + effective wire
# bytes next to its static-int8 baseline, so the record shows what a
# byte-honest transport would ship. BENCH_WIRE_BUDGET_BYTES (optional)
# caps the adaptive variant's effective bytes (--wire-budget-bytes): on
# smoke-sized windows the density ladder's debounce may adopt nothing,
# and a budget just above the all-4-bit floor makes the retag
# deterministic. Needs an int8-family wire; mutually exclusive with the
# other A/B dimensions.
def _precision_tag() -> str:
    if os.environ.get("BENCH_AB_PRECISION") == "1":
        return "_ab_precision"
    return ""


def _grad_wire_bytes(entry) -> int:
    """Gradient-path payload bytes from a contract entry's rows: drop
    the declared overheads — scale pmax rows, the guard pmin, the
    <= 64 B metrics psum scalars, and (on a compressed wire) every f32
    psum: in a compressed config the gradient reduce is integer by
    construction, so a fat f32 psum is statistics (ResNet's BatchNorm
    pmean — the contract's own allowance calls it "model state, not
    gradients"), never payload. f32 GATHER rows stay counted: the
    dequant hier wire's f32 reassembly all_gather is exactly the
    gradient-path widening the homomorphic A/B exists to show."""
    rows = entry["collectives"]
    # integer PAYLOAD rows mark a compressed wire — the guard's int32
    # pmin is overhead, not evidence of one
    compressed = any(
        r["dtype"].startswith("int") for r in rows
        if r["kind"] not in ("pmax", "pmin")
    )
    total = 0
    for r in rows:
        if r["kind"] in ("pmax", "pmin"):
            continue
        if r["dtype"] == "float32" and (
            r["bytes"] <= 64 or (compressed and r["kind"] == "psum")
        ):
            continue
        total += r["bytes"]
    return total


def _comm_contract_entry(workload: str, compress, bucket_bytes,
                         wire_domain: str = "dequant",
                         precision_adapt: bool = False):
    """The committed pscheck accounting row for the PS config this CNN
    workload trains: {config, n_collectives, wire_bytes,
    grad_wire_bytes, mesh_devices} from runs/comm_contract.json, or
    None when the registry has no matching traced entry. Contract
    entries are keyed by config name and traced with FIXED bucket plans
    (LeNet variants pin the fused plan plus a 64 KiB carving, ResNet
    the 4 MiB plan), so only exact bucket matches attach — mislabeling
    a different carving would be worse than omitting."""
    name = "ps_"
    if workload == "resnet18":
        name += "resnet18_"
    name += (compress or "none") + "_replicated"
    if bucket_bytes is not None:
        name += "_bucketed"
        if workload == "resnet18":
            from ps_pytorch_tpu.check.contracts import RESNET_BUCKET_BYTES

            traced = {RESNET_BUCKET_BYTES: ""}
        else:
            # fused plan (the legacy LeNet trace) or the 64 KiB carving
            # the precision-adapt registry pair rides
            traced = {0: "", 64 << 10: "64k"}
        if bucket_bytes not in traced:
            return None
        name += traced[bucket_bytes]
    if wire_domain == "homomorphic":
        name += "_homomorphic"
    if precision_adapt:
        name += "_precadapt"
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        with open(os.path.join(here, "runs", "comm_contract.json")) as f:
            data = json.load(f)
        entry = data["configs"][name]
    except (OSError, ValueError, KeyError):
        return None
    return {
        "config": name,
        "n_collectives": entry["n_collectives"],
        "wire_bytes": entry["total_bytes"],
        "grad_wire_bytes": _grad_wire_bytes(entry),
        "mesh_devices": data.get("mesh_devices"),
    }


def _bench_lm(steps: int) -> tuple:
    import jax
    import jax.numpy as jnp

    from ps_pytorch_tpu.cli.train_lm import make_synthetic_tokens
    from ps_pytorch_tpu.models.transformer import (
        TransformerConfig,
        init_transformer,
    )
    from ps_pytorch_tpu.optim import sgd
    from ps_pytorch_tpu.parallel.dp_sp import (
        make_lm_train_step,
        make_mesh_2d,
        shard_tokens_2d,
    )
    from ps_pytorch_tpu.utils import host_sync

    # TPU-sized defaults; BENCH_LM_* env overrides shrink for CPU smoke.
    # BENCH_LM_FLASH=1 runs the Pallas flash kernel (inside the ring when
    # BENCH_LM_SP > 1) — the long-context configuration to report on
    # hardware: e.g. BENCH_LM_SEQ=8192 BENCH_LM_FLASH=1.
    batch = _lm_env("BATCH")
    seq = _lm_env("SEQ")
    n_sp = _lm_env("SP")
    _, lm_dtype = _bench_dtype(jnp, _LM_DTYPE_DEFAULT)
    cfg = TransformerConfig(
        vocab_size=2048,
        dim=_lm_env("DIM"),
        depth=_lm_env("DEPTH"),
        heads=8,
        max_seq_len=seq,
        remat=True,
        compute_dtype=lm_dtype,
        attention_impl=(
            "flash" if os.environ.get("BENCH_LM_FLASH") == "1" else "naive"
        ),
    )
    mesh = make_mesh_2d(1, n_sp)  # single chip default; sp for long context
    tx = sgd(0.01, momentum=0.9)
    params = init_transformer(cfg, jax.random.key(0))
    opt = tx.init(params)
    step = make_lm_train_step(cfg, tx, mesh)
    corpus = make_synthetic_tokens(cfg.vocab_size, max(64, batch), seq, seed=0)
    tok = shard_tokens_2d(jnp.asarray(corpus[:batch]), mesh)

    for _ in range(2):
        params, opt, loss = step(params, opt, tok)
    host_sync(params, loss)
    flops, hlo_ops = _step_cost(step, params, opt, tok)
    # never exceed the requested budget: BENCH_STEPS trims smoke runs on
    # timeout-bounded windows, so a 10-deep default chain must shrink to
    # the request rather than 4x it (non-multiples floor to outer*k)
    k = min(_chain(), steps)
    if k > 1:
        carry, elapsed, steps = _timed_chain(
            lambda c: step(c[0], c[1], tok), (params, opt, loss),
            lambda c: host_sync(c[0], c[2]), steps, k,
        )
        loss = carry[2]
    else:
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt, loss = step(params, opt, tok)
        host_sync(params, loss)
        elapsed = time.perf_counter() - t0
    return (batch * seq * steps / elapsed, float(loss), elapsed, flops,
            n_sp, steps, k, hlo_ops)


# Peak dense matmul FLOP/s per chip keyed by exact (generation, variant)
# parsed out of the PJRT device_kind. bf16 peaks (the compute dtype of every
# workload here); from public TPU spec sheets. Unlisted kinds (e.g. a future
# "v6p") return None — MFU is omitted rather than misattributed to another
# generation's peak.
_PEAK_BY_GEN = {
    ("6", "e"): 918e12,    # Trillium; device_kind "TPU v6e"/"TPU v6 lite"
    ("5", "p"): 459e12,
    ("5", "e"): 197e12,    # v5e; device_kind "TPU v5 lite"
    ("4", ""): 275e12,
    ("3", ""): 123e12,
    ("2", ""): 45e12,
}


def _peak_flops_per_sec(device) -> float | None:
    kind = getattr(device, "device_kind", "").lower()
    if "tpu" not in kind:
        return None  # not a TPU: no peak to divide by, omit
    m = re.search(r"v(\d+) ?(p\b|e\b|lite\b)?", kind)
    if not m:
        return None
    variant = m.group(2) or ""
    if variant == "lite":
        variant = "e"
    return _PEAK_BY_GEN.get((m.group(1), variant))


def _step_cost(step, *args) -> tuple:
    """(flops, hlo_op_count) of one compiled step — XLA cost analysis for
    the FLOPs, an instruction count of the optimized HLO for the size
    (ps_pytorch_tpu.check.opcount). One .lower().compile() serves both.

    The FLOP count includes rematerialized recompute, so the derived MFU
    is hardware-FLOPs utilization, a slight overcount of model-FLOPs MFU
    when remat is on. hlo_op_count rides every bench record so the
    trajectory JSONs capture program-size changes (e.g. the
    state_layout=flat update-path collapse), not just walltime.
    """
    try:
        compiled = step.lower(*args).compile()
        flops = float(compiled.cost_analysis()["flops"])
    except Exception:
        return None, None
    # separate guard: an opcount failure must not take the long-standing
    # flops/mfu fields down with it
    try:
        from ps_pytorch_tpu.check.opcount import hlo_op_count

        return flops, hlo_op_count(compiled.as_text())
    except Exception:
        return flops, None


def _mfu(flops_per_step, steps, elapsed, jax, n_devices) -> float | None:
    """n_devices = devices the measured mesh actually spans (the lm
    workload runs a 1x1 mesh regardless of host size)."""
    peak = _peak_flops_per_sec(jax.devices()[0])
    if flops_per_step is None or peak is None:
        return None
    return round(flops_per_step * steps / elapsed / (peak * n_devices), 4)




def _utc_now() -> str:
    """Measurement timestamp embedded in every record so a saved record
    stays correctly dated across clones (mtime does not survive checkout)."""
    import datetime

    return datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y-%m-%dT%H:%M:%SZ"
    )


def _validate_env() -> None:
    """Fail bad knobs BEFORE backend init and the first compile — those
    are the slow part, and a typo must not cost minutes of chip time."""
    if os.environ.get("BENCH_DTYPE") not in (None, *_BENCH_DTYPES):
        raise SystemExit(
            f"BENCH_DTYPE must be one of {list(_BENCH_DTYPES)}, "
            f"got {os.environ['BENCH_DTYPE']!r}"
        )
    if os.environ.get("BENCH_COMPRESS") is not None:
        if os.environ["BENCH_COMPRESS"] not in _COMPRESS_VALUES:
            raise SystemExit(
                f"BENCH_COMPRESS must be one of {list(_COMPRESS_VALUES)}, "
                f"got {os.environ['BENCH_COMPRESS']!r}"
            )
        if os.environ.get("BENCH_WORKLOAD", "lenet") in ("lm", "decode",
                                                         "serve"):
            raise SystemExit(
                "BENCH_COMPRESS only applies to the CNN (PS) workloads; "
                "it would be silently ignored for lm/decode/serve"
            )
    # AB=0 is the documented "off" value — as inert as unset, so a CI
    # wrapper exporting it globally must not abort the lm/decode legs
    for knob in ("BENCH_BUCKET_BYTES", "BENCH_AB_BUCKETING",
                 "BENCH_AB_STATE_LAYOUT", "BENCH_AB_OVERLAP",
                 "BENCH_AB_WIRE", "BENCH_AB_PRECISION"):
        val = os.environ.get(knob)
        if knob in ("BENCH_AB_BUCKETING", "BENCH_AB_STATE_LAYOUT",
                    "BENCH_AB_OVERLAP", "BENCH_AB_WIRE",
                    "BENCH_AB_PRECISION") and val == "0":
            val = None
        if val is not None and os.environ.get(
            "BENCH_WORKLOAD", "lenet"
        ) in ("lm", "decode", "serve"):
            raise SystemExit(
                f"{knob} only applies to the CNN (PS) workloads; "
                "it would be silently ignored for lm/decode/serve"
            )
    ab_on = [
        k for k in ("BENCH_AB_BUCKETING", "BENCH_AB_STATE_LAYOUT",
                    "BENCH_AB_OVERLAP", "BENCH_AB_WIRE",
                    "BENCH_AB_PRECISION")
        if os.environ.get(k) == "1"
    ]
    if len(ab_on) > 1:
        raise SystemExit(
            f"{' and '.join(ab_on)} are mutually exclusive — one A/B "
            "dimension per record"
        )
    if os.environ.get("BENCH_AB_WIRE") == "1":
        name = os.environ.get("BENCH_WORKLOAD", "lenet")
        mode, _ = _cnn_compress(WORKLOADS.get(name, {}).get("compress"))
        if mode in (None, "none"):
            raise SystemExit(
                "BENCH_AB_WIRE needs a compressed wire (the homomorphic "
                "domain has nothing to sum on an f32 psum) — set "
                "BENCH_COMPRESS=int8 or int8_2round, or pick a workload "
                "whose canonical mode is compressed (resnet18)"
            )
    if os.environ.get("BENCH_AB_PRECISION") == "1":
        name = os.environ.get("BENCH_WORKLOAD", "lenet")
        mode, _ = _cnn_compress(WORKLOADS.get(name, {}).get("compress"))
        if mode not in ("int8", "int8_2round"):
            raise SystemExit(
                "BENCH_AB_PRECISION needs an int8-family wire (the "
                "adaptive lattice retags quantized buckets) — set "
                "BENCH_COMPRESS=int8 or int8_2round"
            )
    if os.environ.get("BENCH_WIRE_BUDGET_BYTES") is not None:
        try:
            if int(os.environ["BENCH_WIRE_BUDGET_BYTES"]) < 1:
                raise ValueError
        except ValueError:
            raise SystemExit(
                f"BENCH_WIRE_BUDGET_BYTES must be an integer >= 1, "
                f"got {os.environ['BENCH_WIRE_BUDGET_BYTES']!r}"
            )
    if os.environ.get("BENCH_BUCKET_BYTES") is not None:
        try:
            bb = int(os.environ["BENCH_BUCKET_BYTES"])
        except ValueError:
            raise SystemExit(
                f"BENCH_BUCKET_BYTES must be an integer >= 0, "
                f"got {os.environ['BENCH_BUCKET_BYTES']!r}"
            )
        if bb < 0:
            raise SystemExit(
                "BENCH_BUCKET_BYTES must be >= 0 (unset it for the "
                "legacy per-leaf wire)"
            )
        if bb == 0 and os.environ.get("BENCH_AB_OVERLAP") == "1":
            raise SystemExit(
                "BENCH_AB_OVERLAP with BENCH_BUCKET_BYTES=0 is a "
                "degenerate A/B: one fused bucket still depends on every "
                "gradient leaf, so the pipelined variant traces the "
                "serial schedule — pick a multi-bucket size (e.g. 65536) "
                "or unset it for the 64 KiB default"
            )
    for knob in ("BENCH_AB_BUCKETING", "BENCH_AB_STATE_LAYOUT",
                 "BENCH_AB_OVERLAP", "BENCH_AB_WIRE",
                 "BENCH_AB_PRECISION"):
        if os.environ.get(knob) not in (None, "0", "1"):
            raise SystemExit(
                f"{knob} must be 0 or 1, got {os.environ[knob]!r}"
            )
    if os.environ.get("BENCH_WORKLOAD", "lenet") not in WORKLOADS:
        raise SystemExit(
            f"BENCH_WORKLOAD must be one of {sorted(WORKLOADS)}, "
            f"got {os.environ['BENCH_WORKLOAD']!r}"
        )
    int_knobs = (
        ["BENCH_STEPS", "BENCH_CHAIN"]
        + [f"BENCH_LM_{k}" for k in _LM_DEFAULTS]
        + [f"BENCH_DEC_{k}" for k in _DEC_DEFAULTS]
        + [f"BENCH_SRV_{k}" for k in _SRV_DEFAULTS]
    )
    for knob in int_knobs:
        val = os.environ.get(knob)
        if val is not None:
            try:
                int(val)
            except ValueError:
                raise SystemExit(f"{knob} must be an integer, got {val!r}")
    for knob in ("BENCH_SRV_SLOTS", "BENCH_SRV_REQS"):
        if os.environ.get(knob) is not None and int(os.environ[knob]) < 1:
            raise SystemExit(f"{knob} must be >= 1")
    if os.environ.get("BENCH_SRV_RATE") is not None:
        try:
            rate = float(os.environ["BENCH_SRV_RATE"])
        except ValueError:
            raise SystemExit(
                f"BENCH_SRV_RATE must be a number > 0, "
                f"got {os.environ['BENCH_SRV_RATE']!r}"
            )
        if not (rate > 0 and np.isfinite(rate)):
            raise SystemExit("BENCH_SRV_RATE must be a finite number > 0")
    if os.environ.get("BENCH_SRV_INT8KV") not in (None, "0", "1"):
        raise SystemExit(
            f"BENCH_SRV_INT8KV must be 0 or 1, "
            f"got {os.environ['BENCH_SRV_INT8KV']!r}"
        )


def _backend_info(device_kind) -> dict:
    """The measuring backend's identity, stamped on every record (and on
    every A/B variant sub-record): an explicit JAX_PLATFORMS=cpu run is
    told from a chip run by this block and nothing else — platform +
    device kind make the provenance part of the artifact, and
    ``_require_same_backend`` refuses to compute a speedup across
    mismatched ones."""
    import jax

    return {
        "platform": jax.default_backend(),
        "device_kind": str(device_kind) if device_kind else None,
    }


def _require_same_backend(*variants: dict) -> None:
    """Refuse a mixed-backend A/B: a speedup of a TPU leg over a CPU
    leg is not a measurement of anything. ONE policy —
    the tune subsystem's (autotune probes enforce the same refusal) —
    so the two checks can never drift; a variant missing its stamp
    counts as a distinct (unknown) backend."""
    from ps_pytorch_tpu.tune.search import require_same_backend

    require_same_backend([v.get("backend") or {} for v in variants])


def _run_info(n_devices, device_kind) -> dict:
    """The self-describing run block every bench record carries (obs/
    schema.py): run id + schema version + the measured geometry, so a
    bench record is interpretable without the env that produced it."""
    from ps_pytorch_tpu.obs import SCHEMA_VERSION, new_run_id

    return {
        "run_id": new_run_id(),
        "schema_version": SCHEMA_VERSION,
        "geometry": {
            "workload": os.environ.get("BENCH_WORKLOAD", "lenet"),
            "devices": n_devices,
            "device_kind": str(device_kind) if device_kind else None,
        },
    }


def _success_metric() -> str:
    """The metric key the CURRENT env's record carries, built from the
    same BENCH_* knobs the workload itself reads."""
    name = os.environ.get("BENCH_WORKLOAD", "lenet")
    if name == "lm":
        return f"lm_{_lm_tag()}_train_tokens_per_sec"
    if name == "decode":
        return f"decode_{_dec_tag()}_new_tokens_per_sec"
    if name == "serve":
        return f"serve_{_srv_tag()}_tokens_per_sec"
    metric = WORKLOADS.get(name, {}).get("metric") or f"{name}_train_throughput"
    _, ctag = _cnn_compress(WORKLOADS.get(name, {}).get("compress"))
    return (metric + ctag + _bucket_tag() + _layout_tag()
            + _overlap_tag() + _wire_tag() + _precision_tag()
            + _cnn_dtype_suffix())


def main() -> None:
    _validate_env()
    import jax

    from ps_pytorch_tpu.utils import enable_persistent_compile_cache

    enable_persistent_compile_cache()

    from ps_pytorch_tpu.data import IMAGE_SHAPES, make_preprocessor, make_synthetic
    from ps_pytorch_tpu.models import build_model
    from ps_pytorch_tpu.parallel import (
        PSConfig,
        init_ps_state,
        make_mesh,
        make_ps_train_step,
        shard_batch,
        shard_state,
    )

    name = os.environ.get("BENCH_WORKLOAD", "lenet")
    w = WORKLOADS[name]
    n_dev = len(jax.devices())
    device_kind = getattr(jax.devices()[0], "device_kind", "unknown")
    if name == "lm":
        steps = int(os.environ.get("BENCH_STEPS", 20))
        leg_t0 = time.perf_counter()
        (tokens_per_sec, loss, elapsed, flops, lm_dev, steps,
         chain_used, hlo_ops) = _bench_lm(steps)
        leg_wall = time.perf_counter() - leg_t0
        assert np.isfinite(loss), f"non-finite loss {loss}"
        rec = {
            "run": _run_info(lm_dev, device_kind),
            # where the leg's walltime went: everything outside the
            # measured window is setup + compile
            "phases": {
                "setup_compile_s": round(max(leg_wall - elapsed, 0.0), 3),
                "measure_s": round(elapsed, 3),
            },
            "metric": _success_metric(),
            "value": round(tokens_per_sec, 1),
            "unit": "tokens/sec",
            "vs_baseline": round(tokens_per_sec / REF_IMAGES_PER_SEC, 2),
            "mfu": _mfu(flops, steps, elapsed, jax, n_devices=lm_dev),
            "device": device_kind,
            "backend": _backend_info(device_kind),
            "timestamp": _utc_now(),
            "hlo_op_count": hlo_ops,
            # comm shape rides only the PS (CNN) records — the lm
            # workload's dp_sp scheme has no entry in the PS contract
            "comm": None,
        }
        if chain_used > 1:  # the EFFECTIVE depth (clamped to BENCH_STEPS)
            rec["chain"] = chain_used
        print(json.dumps(rec))
        print(
            f"# 1 device (1x1 mesh), {elapsed:.2f}s for {steps} LM steps, "
            f"final loss {loss:.4f}",
            file=sys.stderr,
        )
        return
    if name == "decode":
        steps = int(os.environ.get("BENCH_STEPS", 10))
        leg_t0 = time.perf_counter()
        tokens_per_sec, elapsed, dec_hlo_ops = _bench_decode(steps)
        leg_wall = time.perf_counter() - leg_t0
        rec = {
            "run": _run_info(1, device_kind),
            "phases": {
                "setup_compile_s": round(max(leg_wall - elapsed, 0.0), 3),
                "measure_s": round(elapsed, 3),
            },
            "metric": _success_metric(),
            "value": round(tokens_per_sec, 1),
            "unit": "tokens/sec",
            # generation has no reference counterpart at all; keep the
            # field for schema stability, explicitly null
            "vs_baseline": None,
            "mfu": None,  # decode is KV-cache-bandwidth-bound by design
            "device": device_kind,
            "backend": _backend_info(device_kind),
            "timestamp": _utc_now(),
            "hlo_op_count": dec_hlo_ops,
            "comm": None,  # serving path: no gradient wire at all
        }
        print(json.dumps(rec))
        print(
            f"# 1 device, {elapsed:.2f}s for {steps} generate calls",
            file=sys.stderr,
        )
        return
    if name == "serve":
        summary, srv_hlo_ops, srv_phases = _bench_serve()
        rec = {
            "run": _run_info(1, device_kind),
            # per-phase p50/p99 from the engine's own span tracer: where
            # a serve tick's walltime goes (dispatch vs token fetch vs
            # admission prefill)
            "phases": srv_phases,
            "metric": _success_metric(),
            "value": summary["tokens_per_sec"],
            "unit": "tokens/sec",
            "vs_baseline": None,  # no serving counterpart in the reference
            "mfu": None,  # open-loop serving is latency-bound by design
            "device": device_kind,
            "backend": _backend_info(device_kind),
            "timestamp": _utc_now(),
            "hlo_op_count": srv_hlo_ops,
            # the serving wire is PINNED silent (PSC107) — attach the
            # committed zero-collective row as evidence
            "comm": _serve_contract_entry(),
            "serving": {
                k: summary[k]
                for k in (
                    "requests_completed", "new_tokens", "elapsed_s",
                    # lifecycle accounting + goodput (§7i): under the
                    # BENCH_SRV_OVERLOAD drill shed/expired are the
                    # story; in the plain leg they pin zero
                    "requests_submitted", "requests_shed",
                    "requests_expired",
                    "goodput_tokens", "goodput_tokens_per_sec",
                    # p50/p99 TTFT are over admitted requests that got
                    # a first token: completions + mid-decode expiries
                    # (shed and pre-admission expiries never emit one)
                    "p50_token_latency_s", "p99_token_latency_s",
                    "p50_ttft_s", "p99_ttft_s",
                    # TTFT decomposition: queue + prefill == TTFT per
                    # request (serve/scheduler.Completion)
                    "p50_queue_s", "p99_queue_s",
                    "p50_prefill_s", "p99_prefill_s",
                    "p50_decode_s", "p99_decode_s",
                )
            },
        }
        print(json.dumps(rec))
        print(
            f"# 1 device, {summary['elapsed_s']:.2f}s for "
            f"{summary['requests_completed']} open-loop requests",
            file=sys.stderr,
        )
        return
    mesh = make_mesh(num_workers=n_dev)
    compress, _ = _cnn_compress(w["compress"])
    # BENCH_DTYPE=bfloat16 reports the MXU-native mixed-precision config
    # (params stay f32, same as the trainer's --dtype flag); the default
    # stays f32 for like-for-like comparison with the reference's math
    import jax.numpy as jnp

    from ps_pytorch_tpu.utils import host_sync

    _, cnn_dtype = _bench_dtype(jnp, _CNN_DTYPE_DEFAULT)
    shape = IMAGE_SHAPES[w["dataset"]]
    pre = make_preprocessor(w["dataset"], train=True)
    ds = make_synthetic(w["dataset"], train_size=w["batch"], test_size=8, seed=0)
    batch = {"image": ds.train_images, "label": ds.train_labels}
    key = jax.random.key(1)
    # BENCH_STEPS trims the measured window for smoke runs on slow hosts;
    # throughput extrapolates, the baseline comparison stays per-image.
    req_steps = int(os.environ.get("BENCH_STEPS", REF_STEPS))

    def run_variant(bucket_bytes, state_layout="flat",
                    probe_update_path=False, overlap="serial",
                    probe_overlap=False, spans=False,
                    wire_domain="dequant", precision_adapt=False):
        """Measure one (wire granularity, state layout, schedule) end to
        end; returns the variant's sub-record plus (loss, elapsed,
        steps, flops, chain). ``spans`` wraps the measured window in an
        in-memory obs tracer (per-step dispatch + sync spans) and
        ``probe_overlap`` adds the jaxpr schedule-freedom numbers —
        both used by the BENCH_AB_OVERLAP leg. ``precision_adapt``
        arms the adaptive per-bucket wire (§6i): a host
        PrecisionController retags buckets from per-step telemetry, so
        this variant measures with a PER-STEP host fetch (the adaptive
        wire's real cadence — chaining would hide the controller cost
        the A/B exists to price)."""
        from ps_pytorch_tpu.optim import build_optimizer

        cfg = PSConfig(
            num_workers=n_dev, compress=compress,
            bucket_bytes=bucket_bytes, state_layout=state_layout,
            overlap=overlap, wire_domain=wire_domain,
            precision_adapt=precision_adapt,
        )
        # the flat layout takes the whole-vector optimizer variant (the
        # trainer's own pairing); the math is bit-identical either way
        tx = build_optimizer(
            "sgd", 0.01, momentum=0.9, flat=(state_layout == "flat")
        )
        model = build_model(w["network"], dtype=cnn_dtype)
        state = init_ps_state(model, tx, cfg, jax.random.key(0), shape)
        state = shard_state(state, mesh, cfg)
        step = make_ps_train_step(model, tx, cfg, mesh, preprocess=pre)
        sharded = shard_batch(batch, mesh, cfg)
        # warmup: compile + one steady-state step. Sync via HOST reads of
        # params AND metrics (utils/sync.py): the loss alone does not
        # serialize the optimizer update, which feeds only the params, so
        # waiting on it would time a step that has not finished.
        controller = None
        if precision_adapt:
            from ps_pytorch_tpu.parallel.ps import state_plan
            from ps_pytorch_tpu.resilience.precision import (
                PrecisionController,
            )

            n_params = (
                state.params.layout.total
                if hasattr(state.params, "layout")
                else sum(
                    x.size for x in jax.tree_util.tree_leaves(state.params)
                )
            )
            # a short window so the retag lands inside even a smoke-sized
            # measured run — the A/B's evidence is the effective-bytes
            # shrink, not a long-horizon policy trace
            budget = os.environ.get("BENCH_WIRE_BUDGET_BYTES")
            controller = PrecisionController(
                cfg, state_plan(cfg, n_params).sizes, window=2,
                budget_bytes=int(budget) if budget is not None else None,
            )

        def _extras():
            if controller is None:
                return ()
            return (np.asarray(controller.tags, np.int32),)

        warm_t0 = time.perf_counter()
        for _ in range(2):
            state, metrics = step(state, sharded, key, *_extras())
        host_sync(state.params, metrics)
        warmup_s = time.perf_counter() - warm_t0
        flops, hlo_ops = _step_cost(step, state, sharded, key, *_extras())
        update_ops = None
        if probe_update_path:
            from ps_pytorch_tpu.check.opcount import update_path_op_count

            # jaxpr ops downstream of the gradient reduce — the count
            # the flat state layout collapses (trace-only, no compile)
            update_ops = update_path_op_count(step, state, sharded, key)
        overlap_probe = None
        if probe_overlap:
            from ps_pytorch_tpu.parallel.overlap import (
                jaxpr_overlap_headroom,
            )

            rep = jaxpr_overlap_headroom(step, state, sharded, key)
            rep.pop("per_collective", None)
            overlap_probe = rep
        steps = req_steps
        k = min(_chain(), steps)  # same budget clamp as the lm path
        span_summary = None
        if controller is not None:
            # per-step loop: each step ships under the CURRENT tag vector
            # and feeds the controller its bucket_sqnorm telemetry (one
            # host fetch per step — the adaptive wire's documented cost)
            t0 = time.perf_counter()
            for i in range(steps):
                state, metrics = step(state, sharded, key, *_extras())
                controller.record(
                    i, np.asarray(jax.device_get(metrics["bucket_sqnorm"]))
                )
            host_sync(state.params, metrics)
            elapsed = time.perf_counter() - t0
            k = 1
        elif spans:
            # per-step dispatch/sync spans via the in-memory tracer: the
            # dispatch span is the (async) enqueue, the sync span the
            # host's wait for the step to retire — per-step host_sync so
            # every step contributes one pair (the chained fast path
            # would hide the split)
            from ps_pytorch_tpu.obs import Tracer, summarize_spans

            tr = Tracer("bench", path=None)
            t0 = time.perf_counter()
            for _ in range(steps):
                with tr.span("dispatch"):
                    state, metrics = step(state, sharded, key)
                with tr.span("sync"):
                    host_sync(state.params, metrics)
            elapsed = time.perf_counter() - t0
            span_summary = summarize_spans(tr.drain())
            k = 1
        elif k > 1:
            carry, elapsed, steps = _timed_chain(
                lambda c: step(c[0], sharded, key), (state, metrics),
                lambda c: host_sync(c[0].params, c[1]), steps, k,
            )
            state, metrics = carry
        else:
            t0 = time.perf_counter()
            for _ in range(steps):
                state, metrics = step(state, sharded, key)
            # params chain step-to-step, so this host read serializes the
            # whole window (forward, backward, collectives, AND update)
            host_sync(state.params, metrics)
            elapsed = time.perf_counter() - t0
        loss = float(metrics["loss"])
        assert np.isfinite(loss), f"non-finite loss {loss}"
        images_per_sec = steps * w["batch"] / elapsed
        sub = {
            "images_per_sec": round(images_per_sec, 1),
            "step_time_s": round(elapsed / steps, 6),
            "bucket_bytes": bucket_bytes,
            "state_layout": state_layout,
            "backend": _backend_info(device_kind),
            "hlo_op_count": hlo_ops,
            # leg walltime breakdown: compile+settle vs measured window
            "phases": {
                "warmup_s": round(warmup_s, 3),
                "measure_s": round(elapsed, 3),
            },
            # comm shape from the committed pscheck artifact, so the
            # perf trajectory records the wire, not just walltime
            "comm": _comm_contract_entry(
                name, compress, bucket_bytes, wire_domain, precision_adapt
            ),
        }
        sub["overlap"] = overlap
        sub["wire_domain"] = wire_domain
        if controller is not None:
            from ps_pytorch_tpu.ops.quantize import PRECISION_TAG_NAMES

            # what a byte-honest transport ships under the final tags vs
            # the static int8 baseline — the A/B's evidence metric
            # (resilience/precision.py effective_wire_bytes)
            sub["precision"] = {
                "adaptations": int(controller.adaptations),
                "effective_wire_bytes": int(controller.effective_bytes()),
                "static_int8_bytes": int(controller.static_int8_bytes),
                "tags": {
                    nm: int((controller.tags == t).sum())
                    for t, nm in enumerate(PRECISION_TAG_NAMES)
                },
            }
        if update_ops is not None:
            sub["update_path_ops"] = update_ops
        if overlap_probe is not None:
            sub["overlap_jaxpr"] = overlap_probe
        if span_summary is not None:
            d = span_summary.get("dispatch", {})
            y = span_summary.get("sync", {})
            sub["spans"] = span_summary
            tot = d.get("total_s", 0.0) + y.get("total_s", 0.0)
            # fraction of the host's step wall spent with the work
            # already dispatched (the async window a latency-hiding
            # schedule can fill) vs blocked in the sync — the
            # span-derived overlap fraction the A/B record banks
            sub["overlap_fraction_spans"] = (
                round(d.get("total_s", 0.0) / tot, 4) if tot else None
            )
        return sub, loss, elapsed, steps, flops, k

    if os.environ.get("BENCH_AB_BUCKETING") == "1":
        # A/B leg: per-leaf vs bucketed in ONE process on the same data —
        # the fusion win is measured, not asserted. The headline value is
        # the bucketed variant's throughput.
        ab_bb = _bench_bucket_bytes()
        ab_bb = 0 if ab_bb is None else ab_bb
        sub_leaf, *_ = run_variant(None)
        sub_bkt, loss, elapsed, steps, flops, k = run_variant(ab_bb)
        _require_same_backend(sub_leaf, sub_bkt)
        images_per_sec = sub_bkt["images_per_sec"]
        rec = {
            "run": _run_info(n_dev, device_kind),
            "phases": sub_bkt["phases"],
            "metric": _success_metric(),
            "value": images_per_sec,
            "unit": "images/sec",
            "vs_baseline": round(images_per_sec / REF_IMAGES_PER_SEC, 2),
            "mfu": _mfu(flops, steps, elapsed, jax, n_devices=n_dev),
            "device": device_kind,
            "backend": _backend_info(device_kind),
            "timestamp": _utc_now(),
            "hlo_op_count": sub_bkt["hlo_op_count"],
            # schema stability: every record carries "comm"; the A/B
            # comm shapes live per-variant under ab_bucketing
            "comm": sub_bkt["comm"],
            "ab_bucketing": {
                "per_leaf": sub_leaf,
                "bucketed": sub_bkt,
                "speedup": round(
                    sub_bkt["images_per_sec"]
                    / max(sub_leaf["images_per_sec"], 1e-9),
                    3,
                ),
            },
        }
    elif os.environ.get("BENCH_AB_STATE_LAYOUT") == "1":
        # A/B leg: tree vs flat STATE in one process on the same data and
        # the same wire (bucket_bytes is whatever the env selected for
        # both variants) — walltime, compiled program size, and the
        # update-path op count all land in one record. Headline = flat.
        bb = _bench_bucket_bytes()
        sub_tree, *_ = run_variant(
            bb, state_layout="tree", probe_update_path=True
        )
        sub_flat, loss, elapsed, steps, flops, k = run_variant(
            bb, state_layout="flat", probe_update_path=True
        )
        _require_same_backend(sub_tree, sub_flat)
        images_per_sec = sub_flat["images_per_sec"]
        rec = {
            "run": _run_info(n_dev, device_kind),
            "phases": sub_flat["phases"],
            "metric": _success_metric(),
            "value": images_per_sec,
            "unit": "images/sec",
            "vs_baseline": round(images_per_sec / REF_IMAGES_PER_SEC, 2),
            "mfu": _mfu(flops, steps, elapsed, jax, n_devices=n_dev),
            "device": device_kind,
            "backend": _backend_info(device_kind),
            "timestamp": _utc_now(),
            "hlo_op_count": sub_flat["hlo_op_count"],
            "comm": sub_flat["comm"],
            "ab_state_layout": {
                "tree": sub_tree,
                "flat": sub_flat,
                "speedup": round(
                    sub_flat["images_per_sec"]
                    / max(sub_tree["images_per_sec"], 1e-9),
                    3,
                ),
                "update_path_ops_ratio": (
                    round(
                        sub_tree["update_path_ops"]
                        / max(sub_flat["update_path_ops"], 1), 2,
                    )
                    if sub_tree.get("update_path_ops")
                    and sub_flat.get("update_path_ops")
                    else None
                ),
            },
        }
    elif os.environ.get("BENCH_AB_OVERLAP") == "1":
        # A/B leg: serial vs pipelined SCHEDULE in one process on the
        # same wire — per-variant step walltime, per-step dispatch/sync
        # span breakdown, hlo_op_count, and the jaxpr schedule-freedom
        # probe all land in one record. Headline = pipelined.
        bb = _bench_bucket_bytes()
        if bb is None:
            # a MULTI-bucket default: bb=0 (one fused bucket) would make
            # the A/B degenerate — a single bucket still depends on every
            # leaf, so "pipelined" would trace the serial schedule and
            # the record would read "pipelining gains nothing" about an
            # experiment that never pipelined
            bb = 64 << 10
        sub_ser, *_ = run_variant(
            bb, overlap="serial", probe_overlap=True, spans=True
        )
        sub_pip, loss, elapsed, steps, flops, k = run_variant(
            bb, overlap="pipelined", probe_overlap=True, spans=True
        )
        _require_same_backend(sub_ser, sub_pip)
        images_per_sec = sub_pip["images_per_sec"]
        rec = {
            "run": _run_info(n_dev, device_kind),
            "phases": sub_pip["phases"],
            "metric": _success_metric(),
            "value": images_per_sec,
            "unit": "images/sec",
            "vs_baseline": round(images_per_sec / REF_IMAGES_PER_SEC, 2),
            "mfu": _mfu(flops, steps, elapsed, jax, n_devices=n_dev),
            "device": device_kind,
            "backend": _backend_info(device_kind),
            "timestamp": _utc_now(),
            "hlo_op_count": sub_pip["hlo_op_count"],
            "comm": sub_pip["comm"],
            "ab_overlap": {
                "serial": sub_ser,
                "pipelined": sub_pip,
                "speedup": round(
                    sub_pip["images_per_sec"]
                    / max(sub_ser["images_per_sec"], 1e-9),
                    3,
                ),
            },
        }
    elif os.environ.get("BENCH_AB_WIRE") == "1":
        # A/B leg: dequant vs homomorphic WIRE DOMAIN in one process on
        # the same compressed wire (§6h) — per-variant walltime,
        # hlo_op_count, backend stamp, and the committed contract's
        # gradient-path wire bytes land in one record, so the
        # compressed-domain byte shrink and the measured walltime ride
        # together. Headline = homomorphic.
        bb = _bench_bucket_bytes()
        sub_deq, *_ = run_variant(bb, wire_domain="dequant")
        sub_hom, loss, elapsed, steps, flops, k = run_variant(
            bb, wire_domain="homomorphic"
        )
        _require_same_backend(sub_deq, sub_hom)
        images_per_sec = sub_hom["images_per_sec"]
        wire_ratio = None
        if (sub_deq.get("comm") and sub_hom.get("comm")
                and sub_hom["comm"]["grad_wire_bytes"]):
            wire_ratio = round(
                sub_deq["comm"]["grad_wire_bytes"]
                / sub_hom["comm"]["grad_wire_bytes"], 3,
            )
        rec = {
            "run": _run_info(n_dev, device_kind),
            "phases": sub_hom["phases"],
            "metric": _success_metric(),
            "value": images_per_sec,
            "unit": "images/sec",
            "vs_baseline": round(images_per_sec / REF_IMAGES_PER_SEC, 2),
            "mfu": _mfu(flops, steps, elapsed, jax, n_devices=n_dev),
            "device": device_kind,
            "backend": _backend_info(device_kind),
            "timestamp": _utc_now(),
            "hlo_op_count": sub_hom["hlo_op_count"],
            "comm": sub_hom["comm"],
            "ab_wire": {
                "dequant": sub_deq,
                "homomorphic": sub_hom,
                "speedup": round(
                    sub_hom["images_per_sec"]
                    / max(sub_deq["images_per_sec"], 1e-9),
                    3,
                ),
                # the committed-contract byte shrink (dequant /
                # homomorphic gradient-path wire bytes), when both
                # carvings have traced entries
                "grad_wire_bytes_ratio": wire_ratio,
            },
        }
    elif os.environ.get("BENCH_AB_PRECISION") == "1":
        # A/B leg: static int8 vs telemetry-adaptive per-bucket precision
        # (§6i) on the SAME 64 KiB bucketed wire in one process — the
        # adaptive variant carries its tag histogram, effective wire
        # bytes, and static-int8 baseline, so the record shows the
        # byte-honest shrink next to the measured walltime (which PAYS
        # the per-step telemetry fetch — values-not-bytes means the
        # traced wire itself never shrinks, PSC108). Headline = adaptive.
        bb = _bench_bucket_bytes()
        if bb is None or bb == 0:
            # the precadapt contract pair is traced at the 64 KiB
            # carving; a fused single bucket would also make the A/B
            # degenerate (one tag re-prices the whole gradient)
            bb = 64 << 10
        sub_static, *_ = run_variant(bb)
        sub_adapt, loss, elapsed, steps, flops, k = run_variant(
            bb, precision_adapt=True
        )
        _require_same_backend(sub_static, sub_adapt)
        images_per_sec = sub_adapt["images_per_sec"]
        prec = sub_adapt.get("precision") or {}
        eff = prec.get("effective_wire_bytes")
        static_b = prec.get("static_int8_bytes")
        rec = {
            "run": _run_info(n_dev, device_kind),
            "phases": sub_adapt["phases"],
            "metric": _success_metric(),
            "value": images_per_sec,
            "unit": "images/sec",
            "vs_baseline": round(images_per_sec / REF_IMAGES_PER_SEC, 2),
            "mfu": _mfu(flops, steps, elapsed, jax, n_devices=n_dev),
            "device": device_kind,
            "backend": _backend_info(device_kind),
            "timestamp": _utc_now(),
            "hlo_op_count": sub_adapt["hlo_op_count"],
            "comm": sub_adapt["comm"],
            "ab_precision": {
                "static_int8": sub_static,
                "adaptive": sub_adapt,
                "speedup": round(
                    sub_adapt["images_per_sec"]
                    / max(sub_static["images_per_sec"], 1e-9),
                    3,
                ),
                # effective / static bytes under the final tag vector —
                # < 1.0 is the adaptive wire earning its keep
                "effective_wire_fraction": (
                    round(eff / static_b, 3)
                    if eff is not None and static_b else None
                ),
            },
        }
    else:
        sub, loss, elapsed, steps, flops, k = run_variant(
            _bench_bucket_bytes()
        )
        images_per_sec = sub["images_per_sec"]
        rec = {
            "run": _run_info(n_dev, device_kind),
            "phases": sub["phases"],
            "metric": _success_metric(),
            "value": images_per_sec,
            "unit": "images/sec",
            "vs_baseline": round(images_per_sec / REF_IMAGES_PER_SEC, 2),
            "mfu": _mfu(flops, steps, elapsed, jax, n_devices=n_dev),
            "device": device_kind,
            "backend": _backend_info(device_kind),
            "timestamp": _utc_now(),
            "step_time_s": sub["step_time_s"],
            "hlo_op_count": sub["hlo_op_count"],
            "comm": sub["comm"],
        }
    if k > 1:
        rec["chain"] = k
    print(json.dumps(rec))
    print(
        f"# {n_dev} device(s), {elapsed:.2f}s for {steps} steps "
        f"(reference single node: {REF_SINGLE_NODE_SECONDS}s), final loss {loss:.4f}",
        file=sys.stderr,
    )


if __name__ == "__main__":
    # no probe, no fallback, no catch-all: a backend that cannot start or
    # a workload that raises is a traceback and a non-zero exit, and no
    # record is printed. JAX_PLATFORMS=cpu is an explicit CPU run, labelled
    # by the record's backend.platform field.
    main()
