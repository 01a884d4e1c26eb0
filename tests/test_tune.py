"""tune/ (ARCHITECTURE §7h): the trace-only cost model, the
contract-guarded search, and the regression gate pinning the model
against evidence the repo has already banked.

Three layers of pins:

- unit: the cost formula's monotonicities, the hardware-profile loader,
  the mixed-backend refusal;
- banked-evidence consistency: the model must RANK the way committed
  artifacts measured — per-leaf vs 4 MiB-bucketed collective counts
  from runs/comm_contract.json, serial vs pipelined schedule freedom
  from runs/overlap_ab.json;
- the committed runs/autotune_resnet18.json: schema-valid, ranked,
  contains config-invalid AND PSC-rule-pruned points, the tuned
  config's modeled cost beats the CLI default's by the banked margin,
  and re-deriving the costs from the record's stored inputs through the
  LIVE formula reproduces the recorded numbers (the model and the
  artifact cannot drift apart silently).

The end-to-end search runs the tiny LeNet grid (traces only — nothing
executes) plus one 2-step measured probe.
"""

import argparse
import json
from pathlib import Path

import pytest

import ps_pytorch_tpu  # noqa: F401  (installs the jax.shard_map alias)

from ps_pytorch_tpu.obs.schema import validate_event
from ps_pytorch_tpu.tune import (
    HardwareProfile,
    Knobs,
    build_grid,
    comm_seconds_from_rows,
    load_hardware_profile,
    modeled_step_seconds,
    run_search,
)
from ps_pytorch_tpu.tune.search import (
    DEFAULT_KNOBS,
    MODELS,
    backend_info,
    require_same_backend,
)

REPO = Path(__file__).resolve().parent.parent
CONTRACT = REPO / "runs" / "comm_contract.json"
OVERLAP_AB = REPO / "runs" / "overlap_ab.json"
AUTOTUNE_RESNET = REPO / "runs" / "autotune_resnet18.json"

AXIS8 = {"workers": 8}
PROFILE = HardwareProfile(compute_s=1e-3)


# ------------------------------------------------------------ cost model

def test_comm_seconds_monotone_in_bytes_and_count():
    row = dict(kind="psum", axes=["workers"], dtype="float32",
               count=1, bytes=1 << 20)
    base = comm_seconds_from_rows([row], AXIS8, PROFILE)
    bigger = comm_seconds_from_rows(
        [dict(row, bytes=2 << 20)], AXIS8, PROFILE
    )
    chattier = comm_seconds_from_rows(
        [dict(row, count=10)], AXIS8, PROFILE
    )
    assert bigger > base
    # same bytes split across 10 collectives costs 9 extra launches
    assert chattier == pytest.approx(
        base + 9 * PROFILE.collective_launch_s
    )


def test_comm_seconds_prices_dcn_rows_on_the_nic():
    ici_row = dict(kind="psum", axes=["workers"], dtype="float32",
                   count=1, bytes=8 << 20)
    dcn_row = dict(ici_row, axes=["dcn"])
    assert (
        comm_seconds_from_rows([dcn_row], {"dcn": 8}, PROFILE)
        > comm_seconds_from_rows([ici_row], AXIS8, PROFILE)
    )


def test_modeled_step_formula():
    # full headroom hides all comm; zero headroom exposes all of it
    hidden = modeled_step_seconds(5e-3, 1.0, 100, PROFILE)
    exposed = modeled_step_seconds(5e-3, 0.0, 100, PROFILE)
    assert hidden == pytest.approx(
        PROFILE.compute_s + 100 * PROFILE.op_cost_s
    )
    assert exposed == pytest.approx(hidden + 5e-3)
    # None headroom is the conservative zero
    assert modeled_step_seconds(5e-3, None, 100, PROFILE) == exposed


def test_load_hardware_profile_reads_committed_scaling_model():
    prof = load_hardware_profile("ResNet18", 8, path=str(
        REPO / "runs" / "predicted_scaling.json"
    ))
    model = json.loads(
        (REPO / "runs" / "predicted_scaling.json").read_text()
    )["model"]
    assert prof.ici_gbs == model["ici_gbs_one_way"]
    assert prof.dcn_gbs == model["dcn_gbs_per_host"]
    # compute floor = t1_seconds / workers from the committed model
    assert prof.compute_s == pytest.approx(model["t1_seconds"] / 8)
    assert prof.source.endswith("predicted_scaling.json")
    # explicit link overrides win over the file
    prof2 = load_hardware_profile(
        "ResNet18", 8, path=str(REPO / "runs" / "predicted_scaling.json"),
        ici_gbs=10.0,
    )
    assert prof2.ici_gbs == 10.0 and prof2.dcn_gbs == 12.5
    # a missing file degrades to the documented builtin fallbacks
    prof3 = load_hardware_profile("LeNet", 8, path="/nonexistent.json")
    assert prof3.ici_gbs == 45.0 and "builtin defaults" in prof3.source
    assert prof3.compute_s == pytest.approx(7.083e-3 / 8)


def test_require_same_backend_refuses_mixed():
    cpu = {"platform": "cpu", "device_kind": "cpu"}
    require_same_backend([cpu, dict(cpu)])  # same backend: fine
    with pytest.raises(SystemExit, match="across backends"):
        require_same_backend(
            [cpu, {"platform": "tpu", "device_kind": "TPU v5 lite"}]
        )
    assert backend_info()["platform"] == "cpu"


# ---------------------------------------- banked-evidence consistency

def test_model_ranks_bucketed_wire_under_per_leaf():
    """The committed contract pins ResNet18 int8 per-leaf at 168
    collective equations vs 66 bucketed (PR 4's collapse of the gradient
    wire, 125 -> 23); the cost model must price the same rows the same
    way around."""
    cfgs = json.loads(CONTRACT.read_text())["configs"]
    leaf = cfgs["ps_resnet18_int8_replicated"]
    bkt = cfgs["ps_resnet18_int8_replicated_bucketed"]
    # re-pinned for jax 0.9.0, which binds one psum per pytree LEAF: the 3
    # metric scalars and 40 BN-stat leaves that rode 2 variadic psums when
    # the artifact was first written are 43 equations now (+41 on both
    # sides, bytes unchanged) — the gradient wire itself is still 125
    # per-leaf vs 23 bucketed
    assert leaf["n_collectives"] == 168 and bkt["n_collectives"] == 66
    t_leaf = comm_seconds_from_rows(leaf["collectives"], AXIS8, PROFILE)
    t_bkt = comm_seconds_from_rows(bkt["collectives"], AXIS8, PROFILE)
    assert t_bkt < t_leaf


def test_model_agrees_with_banked_overlap_ab():
    """runs/overlap_ab.json banked the schedule-freedom A/B (LeNet int8
    64 KiB): pipelining moves identical bytes at higher headroom.
    Through the model's step formula that must come out cheaper."""
    ab = json.loads(OVERLAP_AB.read_text())["bench_ab_overlap"]["ab_overlap"]
    ser, pip = ab["serial"]["overlap_jaxpr"], ab["pipelined"]["overlap_jaxpr"]
    assert pip["overlap_headroom"] > ser["overlap_headroom"]
    comm = 1e-3  # same wire bytes by PSC109 — any common comm time
    assert (
        modeled_step_seconds(comm, pip["overlap_headroom"], 0, PROFILE)
        < modeled_step_seconds(comm, ser["overlap_headroom"], 0, PROFILE)
    )
    assert pip["mean_dispatch_prefix"] < ser["mean_dispatch_prefix"]


def test_model_ranks_homomorphic_wire_at_or_under_dequant():
    """The §6h satellite pin: on the ResNet18 int8 leg the model must
    rank the homomorphic wire <= its dequant twin. The committed
    contract pins the mechanism — the gradient psum narrows int32 ->
    int16 (half the bytes, same rows otherwise) — so the comm term is
    strictly cheaper through the same pricing the PSC104 artifact rows
    get."""
    cfgs = json.loads(CONTRACT.read_text())["configs"]
    pairs = (
        ("ps_resnet18_int8_replicated_bucketed",
         "ps_resnet18_int8_replicated_bucketed_homomorphic"),
        ("ps_int8_replicated", "ps_int8_replicated_homomorphic"),
    )
    for deq_name, hom_name in pairs:
        deq, hom = cfgs[deq_name], cfgs[hom_name]
        t_deq = comm_seconds_from_rows(deq["collectives"], AXIS8, PROFILE)
        t_hom = comm_seconds_from_rows(hom["collectives"], AXIS8, PROFILE)
        assert t_hom < t_deq, (deq_name, t_hom, t_deq)


# -------------------------------------- committed record: the gate

@pytest.fixture(scope="module")
def resnet_record():
    return json.loads(AUTOTUNE_RESNET.read_text())


def test_autotune_record_is_schema_valid_and_ranked(resnet_record):
    rec = dict(resnet_record)
    validate_event(rec)                    # kind "autotune"
    validate_event(dict(rec["run"]))       # nested run_header
    assert rec["run"]["component"] == "autotune"
    assert rec["n_candidates"] >= 24
    costs = [c["cost"]["modeled_step_s"] for c in rec["candidates"]]
    assert costs == sorted(costs) and all(c > 0 for c in costs)
    assert [c["rank"] for c in rec["candidates"]] == list(range(len(costs)))


def test_autotune_record_pruned_points(resnet_record):
    stages = {p["stage"] for p in resnet_record["pruned"]}
    assert "config" in stages  # engine-refused (pipelined per-leaf wire)
    contract = [
        p for p in resnet_record["pruned"] if p["stage"] == "contract"
    ]
    assert contract, "no PSC-rule-pruned point in the committed record"
    assert any("PSC103" in p["rules"] for p in contract)
    # pruned points are really absent from the ranking
    names = {c["name"] for c in resnet_record["candidates"]}
    assert not names & {p["name"] for p in contract}


def test_autotune_gate_tuned_beats_default_by_banked_margin(resnet_record):
    gate = resnet_record["gate"]
    assert gate["min_modeled_speedup"] >= 1.03
    assert gate["modeled_speedup"] >= gate["min_modeled_speedup"]
    best = resnet_record["best"]
    default = resnet_record["default"]
    # the default entry is really the CLI default config
    assert default["knobs"] == DEFAULT_KNOBS.to_json()
    assert (
        default["cost"]["modeled_step_s"]
        >= gate["min_modeled_speedup"] * best["cost"]["modeled_step_s"]
    )


def test_autotune_record_costs_rederive_through_live_formula(resnet_record):
    """Every candidate's stored inputs (comm rows, headroom, update ops)
    must reproduce its stored modeled_step_s through the LIVE formula
    with the recorded profile — the banked artifact and the model
    cannot drift apart without this failing."""
    prof = HardwareProfile(**resnet_record["hardware_profile"])
    devices = resnet_record["run"]["geometry"]["devices"]
    axis_sizes = {"workers": devices}
    for c in resnet_record["candidates"]:
        cost = c["cost"]
        comm = comm_seconds_from_rows(cost["comm_rows"], axis_sizes, prof)
        assert comm == pytest.approx(
            cost["comm_s"], rel=1e-6, abs=2e-9
        ), c["name"]
        step = modeled_step_seconds(
            comm, cost["overlap_headroom"], cost["update_path_ops"], prof
        )
        assert step == pytest.approx(
            cost["modeled_step_s"], rel=1e-6, abs=2e-9
        ), c["name"]


def test_autotune_record_consistent_with_comm_contract(resnet_record):
    """The record must agree with the banked A/B evidence: the 4 MiB
    bucketed wire collapses the per-leaf collective count (comm cost
    strictly cheaper — runs/comm_contract.json pins 127 -> 25) and the
    pipelined schedule frees headroom over its serial twin
    (runs/overlap_ab.json direction), so bucketed+pipelined must model
    strictly under the per-leaf wire end to end."""
    by_name = {c["name"]: c for c in resnet_record["candidates"]}
    leaf = by_name["ps_resnet18_int8_replicated"]
    bkt = by_name["ps_resnet18_int8_replicated_bucketed4096k"]
    pip = by_name["ps_resnet18_int8_replicated_bucketed4096k_pipelined"]
    assert bkt["cost"]["n_grad_reduces"] < leaf["cost"]["n_grad_reduces"]
    assert bkt["cost"]["comm_s"] < leaf["cost"]["comm_s"]
    # pipelined vs serial twin: same wire, more schedule freedom,
    # cheaper modeled step (the banked headroom direction)
    assert (
        pip["cost"]["overlap_headroom"] > bkt["cost"]["overlap_headroom"]
    )
    assert pip["cost"]["modeled_step_s"] < bkt["cost"]["modeled_step_s"]
    assert pip["cost"]["modeled_step_s"] < leaf["cost"]["modeled_step_s"]


# ------------------------------------------------ end-to-end search

@pytest.fixture(scope="module")
def tiny_search():
    return run_search("lenet", grid="tiny", probe_top=1, probe_steps=2)


def test_search_tiny_grid_prunes_and_ranks(tiny_search):
    rec = tiny_search
    validate_event(dict(rec))
    validate_event(dict(rec["run"]))
    assert rec["n_candidates"] == 6
    stages = {p["stage"] for p in rec["pruned"]}
    assert stages == {"config", "contract"}
    # both engine-refused points (pipelined per-leaf wire, homomorphic
    # uncompressed wire) prune at the config stage
    assert len([p for p in rec["pruned"] if p["stage"] == "config"]) == 2
    (contract,) = [p for p in rec["pruned"] if p["stage"] == "contract"]
    assert contract["rules"] == ["PSC103"]
    assert contract["reason"]  # the finding text rides along as evidence
    costs = [c["cost"]["modeled_step_s"] for c in rec["candidates"]]
    assert costs == sorted(costs)
    assert rec["default"] is not None and rec["best"] is not None


def test_search_probe_feeds_back_into_the_formula(tiny_search):
    top = tiny_search["candidates"][0]
    probe = top["probe"]
    assert probe["platform"] == "cpu" and probe["steps"] == 2
    assert probe["measured_step_s"] > 0
    prof = HardwareProfile(**tiny_search["hardware_profile"])
    want = modeled_step_seconds(
        top["cost"]["comm_s"], probe["overlap_fraction_spans"],
        top["cost"]["update_path_ops"], prof,
    )
    assert top["cost"]["modeled_step_probe_s"] == pytest.approx(
        want, rel=1e-6
    )


def test_search_flags_round_trip_through_the_real_cli_parser(
    tiny_search, tmp_path
):
    """Every surviving candidate's flag dict must parse through the real
    cli/train surface (types, choices) — the --config-json round trip
    can never emit a flag the trainer rejects."""
    from ps_pytorch_tpu.cli._flags import (
        add_ps_flags,
        add_train_flags,
        expand_config_json,
    )

    parser = argparse.ArgumentParser()
    add_train_flags(parser)
    add_ps_flags(parser)
    for c in tiny_search["candidates"]:
        argv = []
        for k, v in c["flags"].items():
            argv.extend([k, str(v)])
        args = parser.parse_args(argv)
        assert args.network == "LeNet"
    # and the record itself applies through expand_config_json
    rec_path = tmp_path / "tune_roundtrip.json"
    rec_path.write_text(json.dumps(tiny_search))
    argv = expand_config_json(
        parser, ["--config-json", str(rec_path), "--max-steps", "2"]
    )
    args = parser.parse_args(argv)
    assert args.max_steps == 2
    assert args.network == "LeNet"


def test_grid_presets_shape():
    # the default grids carry the showcase point: a quant-block PSC103
    # prune candidate
    for model in MODELS:
        grid = build_grid(model, "default")
        assert len(grid) >= 30
        assert any(k.quant_block_size for k in grid)
        assert DEFAULT_KNOBS in grid
    smoke = build_grid("lenet", "smoke")
    assert all(k.opt_placement == "replicated" for k in smoke)
    with pytest.raises(ValueError, match="unknown grid"):
        build_grid("lenet", "nope")


def test_knobs_flag_mapping():
    kn = Knobs(compress="int8_2round", bucket_bytes=None,
               overlap="pipelined", quant_block_size=32)
    flags = kn.flags("LeNet", "MNIST")
    assert flags["--compress-grad"] == "2round"
    assert flags["--bucket-bytes"] == -1
    assert flags["--overlap"] == "on"
    assert flags["--quant-block-size"] == 32
    assert Knobs(bucket_bytes=64 << 10).bucket_tag() == "64k"
    assert Knobs(bucket_bytes=1000).bucket_tag() == "1000"
    assert Knobs(bucket_bytes=0).bucket_tag() == ""
