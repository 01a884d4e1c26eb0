"""Contract-guarded knob search: enumerate candidate configs, prune the
broken ones with the PSC101-109 rules, rank the survivors by modeled
cost, optionally calibrate the top-K with short measured probes.

The pipeline per candidate:

1. build a ``ContractSpec`` for the knob point through the SAME spec
   constructor the committed registry uses (check/contracts._ps_spec) —
   the candidate's declared invariants (grad-reduce kinds, wire dtype
   policy, fusion budget, overlap twin) are derived from its knobs
   exactly like a registry entry's would be;
2. trace the REAL train step (check/core.trace_spec, CPU-only, nothing
   executes) and run the contract rules on it. A config the engine
   refuses to construct (e.g. a pipelined per-leaf wire) or whose trace
   violates a rule (e.g. block-scale rows overflowing the declared
   PSC103 scale allowance on a fused 2-round wire) is PRUNED with the
   reason attached — contracts are search constraints, not crashes;
3. cost the survivors with the trace-only model (tune/costmodel.py) and
   rank ascending by modeled step time;
4. optionally run short measured probes on the top-K (real steps on the
   live backend, warm-up first and host reads as the sync, an in-memory
   obs tracer splitting dispatch vs sync) — the span-derived overlap
   fraction feeds back into the SAME step-time formula as a calibrated
   estimate, and every probe stamps its backend so mixed-backend
   comparisons are refused, never averaged.

The emitted record (runs/autotune_<model>.json) is schema-validated
(obs/schema.py kind "autotune", run_header included) and carries, for
the best candidate, a ready-to-paste flag line that
``cli/train --config-json`` applies directly.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .costmodel import (
    HardwareProfile,
    load_hardware_profile,
    model_cost,
    modeled_step_seconds,
)

# knob-space presets per tuned model. ``buckets`` carries the model's
# bucket-granularity ladder (None = legacy per-leaf, 0 = one fused
# buffer, N = ~N-byte buckets — 64 KiB suits LeNet's ~1.7 MB payload,
# 4 MiB the ResNet18 ~44.7 MB one, mirroring the registry's entries).
MODELS: Dict[str, Dict[str, Any]] = {
    "lenet": {
        "network": "LeNet",
        "dataset": "MNIST",
        "buckets": (None, 0, 64 << 10),
        "probe_batch": 64,
    },
    "resnet18": {
        "network": "ResNet18",
        "dataset": "Cifar10",
        "buckets": (None, 0, 4 << 20),
        "probe_batch": 64,
    },
}

# the banked regression-gate margin: the tuned config's MODELED step
# time must beat the CLI-default config's by at least this factor
# (tests/test_tune.py pins the committed runs/autotune_resnet18.json
# against it). A conservative floor well under the observed margin
# (1.077x at the committed profile), so legitimate model refinements
# don't trip the gate while a regression that ranks the default near
# the top does. LeNet has no gate: at a ~1.7 MB payload the model
# honestly ranks the default per-leaf f32 wire near-optimal (collective
# launch cost dominates, quantization overhead doesn't pay).
GATE_MIN_SPEEDUP = {"resnet18": 1.03}


@dataclasses.dataclass(frozen=True)
class Knobs:
    """One point of the declared knob space (the searchable subset of
    PSConfig — mesh-geometry and serving knobs are future axes)."""

    compress: Optional[str] = None      # None | "int8" | "int8_2round"
    bucket_bytes: Optional[int] = None  # None = per-leaf, 0 = fused, N
    overlap: str = "serial"             # "serial" | "pipelined"
    opt_placement: str = "replicated"   # "replicated" | "sharded"
    quant_block_size: int = 0
    wire_domain: str = "dequant"        # "dequant" | "homomorphic"

    def bucket_tag(self) -> str:
        bb = self.bucket_bytes
        if not bb:
            return ""  # per-leaf has no _bucketed suffix; fused no tag
        return f"{bb >> 10}k" if bb % 1024 == 0 else str(bb)

    def flags(self, network: str, dataset: str) -> Dict[str, Any]:
        """The exact cli/train flag assignment reproducing this point
        (the --config-json round-trip surface)."""
        return {
            "--network": network,
            "--dataset": dataset,
            "--compress-grad": {
                None: "none", "int8": "compress", "int8_2round": "2round",
            }[self.compress],
            "--bucket-bytes": (
                -1 if self.bucket_bytes is None else self.bucket_bytes
            ),
            "--overlap": "on" if self.overlap == "pipelined" else "off",
            "--opt-placement": self.opt_placement,
            "--quant-block-size": self.quant_block_size,
            "--wire-domain": self.wire_domain,
        }

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def flag_line(flags: Dict[str, Any]) -> str:
    return " ".join(f"{k} {v}" for k, v in flags.items())


DEFAULT_KNOBS = Knobs()  # == cli/train defaults: per-leaf f32 serial


def build_grid(model: str, grid: str = "default") -> List[Knobs]:
    """The declared knob grid for one model.

    - ``default``: the full compress x bucket x overlap x placement
      product (sharded skips the per-leaf rung — its wire is flat by
      construction, so None would duplicate the fused point), plus two
      the showcase point: the fused 2-round wire with block-32 scales
      (PSC103 prunes it — scale rows overflow the declared allowance).
    - ``smoke``: a trimmed replicated-only LeNet-scale grid for
      tools/smoke.sh — still contains config-invalid AND
      contract-pruned points.
    - ``tiny``: the test grid (tests/test_tune.py) — one of everything.
    """
    preset = MODELS[model]
    per_leaf, fused, bucketed = preset["buckets"]
    out: List[Knobs] = []
    if grid == "default":
        for compress in (None, "int8", "int8_2round"):
            for bb in preset["buckets"]:
                for overlap in ("serial", "pipelined"):
                    for placement in ("replicated", "sharded"):
                        if placement == "sharded" and bb is None:
                            continue
                        out.append(Knobs(
                            compress=compress, bucket_bytes=bb,
                            overlap=overlap, opt_placement=placement,
                        ))
        out.append(Knobs(compress="int8_2round", bucket_bytes=fused,
                         quant_block_size=32))
        # the wire_domain axis (§6h): the compressed-domain twins of the
        # quantized points — the model prices the narrowed psum / the
        # dropped f32 rows straight from the candidates' own traced
        # accounting
        out.append(Knobs(compress="int8", bucket_bytes=bucketed,
                         wire_domain="homomorphic"))
        out.append(Knobs(compress="int8", bucket_bytes=bucketed,
                         overlap="pipelined", wire_domain="homomorphic"))
        out.append(Knobs(compress="int8_2round", bucket_bytes=fused,
                         wire_domain="homomorphic"))
        return out
    if grid == "smoke":
        for compress in (None, "int8"):
            for bb in preset["buckets"]:
                for overlap in ("serial", "pipelined"):
                    out.append(Knobs(compress=compress, bucket_bytes=bb,
                                     overlap=overlap))
        out.append(Knobs(compress="int8_2round", bucket_bytes=fused,
                         quant_block_size=32))
        out.append(Knobs(compress="int8_2round", bucket_bytes=bucketed))
        out.append(Knobs(compress="int8", bucket_bytes=fused,
                         wire_domain="homomorphic"))
        return out
    if grid == "tiny":
        return [
            Knobs(),                                        # the default
            Knobs(compress=None, bucket_bytes=fused),
            Knobs(compress="int8", bucket_bytes=fused),
            Knobs(compress="int8", bucket_bytes=bucketed),
            Knobs(compress="int8", bucket_bytes=bucketed,
                  overlap="pipelined"),
            Knobs(compress="int8", bucket_bytes=bucketed,
                  wire_domain="homomorphic"),
            Knobs(compress="int8", overlap="pipelined"),    # config-invalid
            Knobs(compress=None,
                  wire_domain="homomorphic"),               # config-invalid
            Knobs(compress="int8_2round", bucket_bytes=fused,
                  quant_block_size=32),                     # PSC103-pruned
        ]
    raise ValueError(f"unknown grid {grid!r} (default, smoke, tiny)")


def spec_for(knobs: Knobs, network: str):
    """The candidate's ContractSpec, built by the registry's own spec
    constructor so declared invariants can't drift from the committed
    entries' derivation."""
    from ..check.contracts import _ps_spec

    return _ps_spec(
        knobs.compress,
        knobs.opt_placement,
        bucket_bytes=knobs.bucket_bytes,
        network=network,
        overlap=knobs.overlap,
        bucket_tag=knobs.bucket_tag(),
        quant_block_size=knobs.quant_block_size,
        wire_domain=knobs.wire_domain,
    )


def backend_info() -> Dict[str, Optional[str]]:
    """The live jax backend identity every probe (and bench record)
    stamps: platform + device kind — the one thing that tells a CPU run
    from a chip run in a saved record."""
    import jax

    devs = jax.devices()
    return {
        "platform": jax.default_backend(),
        "device_kind": (
            str(getattr(devs[0], "device_kind", "unknown")) if devs else None
        ),
    }


def require_same_backend(records: Sequence[Dict[str, Any]]) -> None:
    """Refuse to compare measurements taken on different backends."""
    seen = {
        (r.get("platform"), r.get("device_kind"))
        for r in records if r is not None
    }
    if len(seen) > 1:
        # str-keyed sort: a missing stamp is (None, None) and None does
        # not order against str
        raise SystemExit(
            f"refusing to compare measurements across backends: "
            f"{sorted(seen, key=str)} — re-run the probes on one backend"
        )


def measure_probe(
    knobs: Knobs,
    network: str,
    dataset: str,
    steps: int = 4,
    batch: int = 64,
) -> Dict[str, Any]:
    """One short measured probe: real steps on the live backend, synced
    by host reads (not block_until_ready), with an in-memory span tracer
    splitting dispatch from sync. Returns the measured step time, the
    span-derived overlap fraction, and the backend stamp."""
    import jax

    from ..data import IMAGE_SHAPES, make_preprocessor, make_synthetic
    from ..models import build_model
    from ..obs import Tracer, summarize_spans
    from ..optim import build_optimizer
    from ..parallel import (
        init_ps_state,
        make_mesh,
        make_ps_train_step,
        shard_batch,
        shard_state,
    )
    from ..parallel.ps import PSConfig
    from ..utils import host_sync

    n_dev = len(jax.devices())
    mesh = make_mesh(num_workers=n_dev)
    cfg = PSConfig(
        num_workers=n_dev,
        compress=knobs.compress,
        bucket_bytes=knobs.bucket_bytes,
        overlap=knobs.overlap,
        opt_placement=knobs.opt_placement,
        quant_block_size=knobs.quant_block_size,
        wire_domain=knobs.wire_domain,
    )
    tx = build_optimizer("sgd", 0.01, momentum=0.9, flat=True)
    model = build_model(network)
    ds = make_synthetic(dataset, train_size=batch, test_size=8, seed=0)
    data = {"image": ds.train_images, "label": ds.train_labels}
    pre = make_preprocessor(dataset, train=True)
    state = init_ps_state(
        model, tx, cfg, jax.random.key(0), IMAGE_SHAPES[dataset]
    )
    state = shard_state(state, mesh, cfg)
    step = make_ps_train_step(model, tx, cfg, mesh, preprocess=pre)
    sharded = shard_batch(data, mesh, cfg)
    key = jax.random.key(1)
    # warmup: compile + one steady-state step, then a full host sync so
    # the timed window starts with an idle device
    for _ in range(2):
        state, metrics = step(state, sharded, key)
    host_sync(state.params, metrics)
    tracer = Tracer("autotune_probe", path=None)
    t0 = time.perf_counter()
    for _ in range(steps):
        with tracer.span("dispatch"):
            state, metrics = step(state, sharded, key)
        with tracer.span("sync"):
            host_sync(state.params, metrics)
    elapsed = time.perf_counter() - t0
    spans = summarize_spans(tracer.drain())
    d = spans.get("dispatch", {}).get("total_s", 0.0)
    y = spans.get("sync", {}).get("total_s", 0.0)
    return {
        "measured_step_s": round(elapsed / steps, 6),
        "overlap_fraction_spans": (
            round(d / (d + y), 4) if (d + y) > 0 else None
        ),
        "steps": steps,
        "batch": batch,
        **backend_info(),
    }


def _prune_entry(knobs: Knobs, name: Optional[str], stage: str,
                 reason: str, rules: Sequence[str] = ()) -> dict:
    return {
        "name": name,
        "knobs": knobs.to_json(),
        "stage": stage,          # "config" | "contract" | "trace"
        "rules": sorted(set(rules)),
        "reason": reason,
    }


def run_search(
    model: str,
    grid: str = "default",
    profile: Optional[HardwareProfile] = None,
    probe_top: int = 0,
    probe_steps: int = 4,
    progress=None,
) -> dict:
    """The full search: enumerate -> prune-by-contract -> cost -> rank
    [-> probe top-K]. Returns the evidence record (schema-validated,
    run_header included); the caller owns writing it to disk."""
    from ..check.contracts import MESH_DEVICES
    from ..check.core import trace_spec
    from ..check.rules import check_result, psc109_schedule
    from ..obs.schema import run_header, validate_event

    say = progress or (lambda *_: None)
    preset = MODELS[model]
    network, dataset = preset["network"], preset["dataset"]
    # candidates trace on the contract registry's virtual mesh, so the
    # model prices THAT geometry (probes run on the live devices and
    # stamp their backend separately)
    n_dev = MESH_DEVICES
    axis_sizes = {"workers": n_dev}
    if profile is None:
        profile = load_hardware_profile(network, n_dev)

    t_start = time.perf_counter()
    points = build_grid(model, grid)
    pruned: List[dict] = []
    traced: List[Tuple[Knobs, Any]] = []  # (knobs, TraceResult)
    for kn in points:
        try:
            spec = spec_for(kn, network)
            result = trace_spec(spec, keep_jaxpr=True)
        except ValueError as e:
            # the engine itself refuses the combination (e.g. a
            # pipelined per-leaf wire) — pruned at construction
            pruned.append(_prune_entry(kn, None, "config", str(e)))
            say(f"prune [config] {kn.to_json()}: {e}")
            continue
        except Exception as e:  # noqa: BLE001 - a candidate must never
            # crash the search; an unbuildable point is a pruned point
            pruned.append(_prune_entry(kn, None, "trace",
                                       f"{type(e).__name__}: {e}"))
            say(f"prune [trace] {kn.to_json()}: {e}")
            continue
        traced.append((kn, result))

    # contract rules as search constraints: per-result rules plus the
    # cross-result PSC109 schedule pins (serial twins are in the grid).
    # PSC104 is out of scope — candidates are not pinned in the
    # committed artifact; the registry gate owns that.
    findings_by_name: Dict[str, List] = {}
    for kn, r in traced:
        for f in check_result(r):
            findings_by_name.setdefault(f.config, []).append(f)
    for f in psc109_schedule([r for _, r in traced]):
        findings_by_name.setdefault(f.config, []).append(f)

    survivors: List[Tuple[Knobs, Any]] = []
    for kn, r in traced:
        hits = findings_by_name.get(r.spec.name, [])
        if hits:
            pruned.append(_prune_entry(
                kn, r.spec.name, "contract",
                "; ".join(f.message for f in hits),
                rules=[f.rule for f in hits],
            ))
            say(f"prune [contract] {r.spec.name}: "
                f"{sorted({f.rule for f in hits})}")
        else:
            survivors.append((kn, r))

    candidates: List[dict] = []
    for kn, r in survivors:
        cost = model_cost(r, profile, axis_sizes)
        candidates.append({
            "name": r.spec.name,
            "knobs": kn.to_json(),
            "flags": kn.flags(network, dataset),
            "cost": cost.to_json(),
        })
    candidates.sort(key=lambda c: c["cost"]["modeled_step_s"])
    for rank, c in enumerate(candidates):
        c["rank"] = rank
    say(f"{len(candidates)} candidate(s) ranked, {len(pruned)} pruned")

    if probe_top > 0 and candidates:
        probes = []
        for c in candidates[:probe_top]:
            kn = Knobs(**c["knobs"])
            say(f"probe {c['name']} ({probe_steps} steps)")
            probe = measure_probe(
                kn, network, dataset,
                steps=probe_steps, batch=preset["probe_batch"],
            )
            c["probe"] = probe
            # feed the MEASURED dispatch fraction back through the same
            # step-time formula the trace-only estimate used
            c["cost"]["modeled_step_probe_s"] = round(modeled_step_seconds(
                c["cost"]["comm_s"],
                probe["overlap_fraction_spans"],
                c["cost"]["update_path_ops"],
                profile,
            ), 9)
            probes.append(probe)
        require_same_backend(probes)

    default_name = spec_for(DEFAULT_KNOBS, network).name
    default = next(
        (c for c in candidates if c["name"] == default_name), None
    )
    best = candidates[0] if candidates else None
    gate: Dict[str, Any] = {
        "min_modeled_speedup": GATE_MIN_SPEEDUP.get(model),
        "modeled_speedup": None,
    }
    if best and default:
        gate["modeled_speedup"] = round(
            default["cost"]["modeled_step_s"]
            / max(best["cost"]["modeled_step_s"], 1e-12), 4,
        )

    header = validate_event(run_header(
        "autotune",
        geometry={
            "workload": "autotune", "model": model, "devices": n_dev,
            "device_kind": backend_info()["device_kind"],
        },
    ))
    rec = {
        "kind": "autotune",
        "run": header,
        "model": model,
        "network": network,
        "grid": grid,
        "backend": backend_info(),
        "trace_only": probe_top == 0,
        "hardware_profile": profile.to_json(),
        "n_points": len(points),
        "n_candidates": len(candidates),
        "n_pruned": len(pruned),
        "elapsed_s": round(time.perf_counter() - t_start, 3),
        "gate": gate,
        "default": default,
        "best": (
            dict(best, flag_line=flag_line(best["flags"])) if best else None
        ),
        "candidates": candidates,
        "pruned": pruned,
    }
    return validate_event(rec)
