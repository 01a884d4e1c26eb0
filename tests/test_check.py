"""pscheck (ps_pytorch_tpu/check): walker dataflow units, one broken-step
fixture per rule (tests/check_fixtures.py), CLI exit codes, and the
tier-1 repo gate: every registry contract must hold and the wire-byte
accounting must round-trip against the committed runs/comm_contract.json
— so a collective/dtype/byte regression in any scheme fails CI here.

Tracing is CPU-only and executes nothing; the whole file stays well
under the 60s gate budget (registry traced once, session-scoped).
"""

import contextlib
import io
import json
import subprocess
import sys
from pathlib import Path

import pytest

import ps_pytorch_tpu  # noqa: F401  (installs the jax.shard_map alias)
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ps_pytorch_tpu.check import (
    collect_collectives,
    get_contracts,
    load_contract,
    run_checks,
    to_contract_json,
    trace_registry,
)
from ps_pytorch_tpu.check.__main__ import main as check_main
from ps_pytorch_tpu.parallel.mesh import WORKER_AXIS

REPO = Path(__file__).resolve().parent.parent
CONTRACT = REPO / "runs" / "comm_contract.json"
FIXTURES = "tests.check_fixtures"


def _run_main(args):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = check_main(args)
    return rc, buf.getvalue()


# ------------------------------------------------------------------- walker

def test_walker_finds_collectives_with_axes_dtype_bytes():
    mesh = Mesh(np.array(jax.devices()[:8]), (WORKER_AXIS,))

    def f(x):
        s = lax.psum(x, WORKER_AXIS)
        g = lax.all_gather(x.astype(jnp.int8), WORKER_AXIS, tiled=True)
        return s, g

    mapped = jax.shard_map(
        f, mesh=mesh, in_specs=P(WORKER_AXIS), out_specs=(P(), P()),
        check_vma=False,
    )
    closed = jax.make_jaxpr(jax.jit(mapped))(
        jax.ShapeDtypeStruct((8, 4), jnp.float32)
    )
    colls = collect_collectives(closed)
    kinds = {(c.kind, c.dtype): c for c in colls}
    assert ("psum", "float32") in kinds
    assert ("all_gather", "int8") in kinds
    psum = kinds[("psum", "float32")]
    assert psum.axes == (WORKER_AXIS,)
    assert psum.bytes == 4 * 4  # per-device [1, 4] f32 shard
    assert kinds[("all_gather", "int8")].bytes == 4


def test_walker_splits_mixed_dtype_collectives():
    """jax batches a whole-tree psum into ONE eqn with every leaf as an
    operand; the walker must split it per dtype so a single f32 leaf on
    an otherwise-int8 wire still surfaces for PSC103."""
    mesh = Mesh(np.array(jax.devices()[:8]), (WORKER_AXIS,))

    def f(x):
        tree = {"a": x.astype(jnp.int8).astype(jnp.int32), "b": x * 2.0}
        return lax.psum(tree, WORKER_AXIS)

    mapped = jax.shard_map(
        f, mesh=mesh, in_specs=P(WORKER_AXIS), out_specs=P(),
        check_vma=False,
    )
    closed = jax.make_jaxpr(jax.jit(mapped))(
        jax.ShapeDtypeStruct((8, 4), jnp.float32)
    )
    psums = [c for c in collect_collectives(closed) if c.kind == "psum"]
    dtypes = sorted(c.dtype for c in psums)
    assert dtypes == ["float32", "int32"], psums
    assert all(c.bytes == 16 for c in psums)


def test_walker_dataflow_distinguishes_param_and_metric_psums():
    """The PSC102 discriminator: a psum feeding only the metrics output
    must not be marked feeds_params, even through pjit nesting."""
    mesh = Mesh(np.array(jax.devices()[:8]), (WORKER_AXIS,))

    def f(p, x):
        g = lax.psum(x.sum() * jnp.ones_like(p), WORKER_AXIS)
        metric = lax.pmean(x.sum(), WORKER_AXIS)
        return p - g, metric

    mapped = jax.shard_map(
        f, mesh=mesh, in_specs=(P(), P(WORKER_AXIS)),
        out_specs=(P(), P()), check_vma=False,
    )
    closed = jax.make_jaxpr(jax.jit(mapped))(
        jax.ShapeDtypeStruct((4,), jnp.float32),
        jax.ShapeDtypeStruct((8, 4), jnp.float32),
    )
    colls = collect_collectives(closed, param_out_indices=[0])
    grad = [c for c in colls if c.bytes == 16]
    metric = [c for c in colls if c.bytes == 4]
    assert grad and metric
    assert all(c.feeds_params for c in grad)
    assert not any(c.feeds_params for c in metric)


def test_walker_is_conservative_inside_scan():
    """A collective inside a scan body keeps feeds_params when the scan's
    carry reaches the params (conservative loop treatment)."""
    mesh = Mesh(np.array(jax.devices()[:8]), (WORKER_AXIS,))

    def f(p, x):
        def body(carry, xi):
            return carry + lax.psum(xi, WORKER_AXIS), None

        total, _ = lax.scan(body, jnp.zeros_like(p), x)
        return p - total

    mapped = jax.shard_map(
        f, mesh=mesh, in_specs=(P(), P(None, WORKER_AXIS)),
        out_specs=P(), check_vma=False,
    )
    closed = jax.make_jaxpr(jax.jit(mapped))(
        jax.ShapeDtypeStruct((1, 4), jnp.float32),
        jax.ShapeDtypeStruct((2, 8, 4), jnp.float32),
    )
    colls = collect_collectives(closed, param_out_indices=[0])
    assert any(c.kind == "psum" and c.feeds_params for c in colls)


# ------------------------------------------------- fixtures: one per rule

@pytest.fixture(scope="module")
def fixture_contract(tmp_path_factory):
    """Accounting artifact for the fixture registry, with the `drift`
    config's pinned bytes tampered so PSC104 has something to catch."""
    path = tmp_path_factory.mktemp("check") / "contract.json"
    rc, _ = _run_main(
        ["--registry", FIXTURES, "--write-contract", "--contract",
         str(path)]
    )
    # the write succeeds even though the broken fixtures trip their rules
    assert rc == 1
    data = json.loads(path.read_text())
    assert set(data["configs"]) == {
        "dead_axis", "metrics_only", "fat_f32_wire", "drift",
        "undonated", "donate_mismatch", "defused", "serve_chatty",
        "serve_f32_kv", "adaptive_fat_wire", "adaptive_no_consensus",
        "homomorphic_widened", "depipelined", "numerics_fresh_scale",
        "numerics_dropped_residual", "numerics_widened_accum",
        "numerics_scan_opaque", "numerics_silent_downcast",
        "numerics_ef_closed", "ok_psum",
    }
    data["configs"]["drift"]["collectives"][0]["bytes"] += 1
    path.write_text(json.dumps(data))
    return path


@pytest.mark.parametrize(
    "name,rule",
    [
        ("dead_axis", "PSC101"),
        ("metrics_only", "PSC102"),
        ("fat_f32_wire", "PSC103"),
        ("drift", "PSC104"),
        ("undonated", "PSC105"),
        ("donate_mismatch", "PSC105"),
        ("defused", "PSC106"),
        ("serve_chatty", "PSC107"),
        ("serve_f32_kv", "PSC107"),
        ("adaptive_fat_wire", "PSC108"),
        ("adaptive_no_consensus", "PSC110"),
        ("homomorphic_widened", "PSC103"),
        ("depipelined", "PSC109"),
        ("numerics_fresh_scale", "PSC111"),
        ("numerics_dropped_residual", "PSC112"),
        ("numerics_widened_accum", "PSC113"),
        ("numerics_scan_opaque", "PSC113"),
        ("numerics_silent_downcast", "PSC114"),
    ],
)
def test_fixture_trips_exactly_one_rule(fixture_contract, name, rule):
    rc, out = _run_main(
        ["--registry", FIXTURES, "--only", name, "--contract",
         str(fixture_contract), "--format", "json"]
    )
    assert rc == 1
    rules = sorted({f["rule"] for f in json.loads(out)["findings"]})
    assert rules == [rule], out


@pytest.mark.parametrize("name", ["ok_psum", "numerics_ef_closed"])
def test_clean_fixture_passes(fixture_contract, name):
    rc, out = _run_main(
        ["--registry", FIXTURES, "--only", name, "--contract",
         str(fixture_contract), "--format", "json"]
    )
    assert rc == 0, out
    assert json.loads(out)["findings"] == []


def test_psc102_message_names_the_metrics_near_miss(fixture_contract):
    rc, out = _run_main(
        ["--registry", FIXTURES, "--only", "metrics_only", "--contract",
         str(fixture_contract), "--format", "json"]
    )
    (finding,) = json.loads(out)["findings"]
    assert "feeds only non-param outputs" in finding["message"]


# --------------------------------------------------------------- CLI usage

def test_cli_usage_errors(tmp_path):
    rc, _ = _run_main(["--registry", FIXTURES, "--only", "no_such_config"])
    assert rc == 2
    rc, _ = _run_main(
        ["--registry", FIXTURES, "--write-contract", "--only", "ok_psum",
         "--contract", str(tmp_path / "c.json")]
    )
    assert rc == 2
    assert not (tmp_path / "c.json").exists()
    rc, _ = _run_main(["--registry", "tests.no_such_registry_xyz"])
    assert rc == 2


def test_cli_select_filters_findings(fixture_contract):
    """`--select` mirrors pslint's semantics: filter to the named
    rules, exit 0 when none of them fire."""
    base = ["--registry", FIXTURES, "--only", "numerics_fresh_scale",
            "--contract", str(fixture_contract), "--format", "json"]
    rc, out = _run_main(base + ["--select", "PSC111"])
    assert rc == 1
    assert {f["rule"] for f in json.loads(out)["findings"]} == {"PSC111"}
    # the PSC111 violation is invisible through a PSC112-only lens
    rc, out = _run_main(base + ["--select", "psc112"])  # case-folded
    assert rc == 0
    assert json.loads(out)["findings"] == []


def test_cli_select_usage_errors(tmp_path):
    rc, _ = _run_main(["--registry", FIXTURES, "--select", "PSC999"])
    assert rc == 2
    rc, _ = _run_main(
        ["--registry", FIXTURES, "--write-contract",
         "--contract", str(tmp_path / "c.json"), "--select", "PSC111"]
    )
    assert rc == 2
    assert not (tmp_path / "c.json").exists()


def test_cli_list_names_registry_configs():
    rc, out = _run_main(["--list"])
    assert rc == 0
    names = out.split()
    assert "ps_none_replicated" in names
    assert "ps_int8_2round_sharded" in names
    assert "ps_int8_replicated_bucketed" in names
    assert "ps_resnet18_int8_replicated_bucketed" in names
    assert "dp_tp_pp" in names
    assert "serve_decode" in names
    assert "serve_decode_int8kv" in names


def test_check_sh_exits_nonzero_on_fixture_violation(fixture_contract):
    """The acceptance path: tools/check.sh itself (not just the python
    entry point) fails loudly on a contract violation."""
    proc = subprocess.run(
        ["bash", "tools/check.sh", "--registry", FIXTURES,
         "--only", "dead_axis", "--contract", str(fixture_contract)],
        capture_output=True, text=True, cwd=str(REPO),
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "PSC101" in proc.stdout


def test_check_sh_refuses_write_with_positional_args():
    proc = subprocess.run(
        ["bash", "tools/check.sh", "--write-contract", "somepath"],
        capture_output=True, text=True, cwd=str(REPO),
    )
    assert proc.returncode == 2
    assert "full registry" in proc.stderr


def test_check_sh_write_with_contract_value_is_not_refused(tmp_path):
    """`--contract <path>` takes a value: the value must not be mistaken
    for a positional path and trip the write-refusal — the combination
    reaches the python CLI and the artifact is written."""
    out = tmp_path / "cc.json"
    proc = subprocess.run(
        ["bash", "tools/check.sh", "--registry", FIXTURES,
         "--write-contract", "--contract", str(out)],
        capture_output=True, text=True, cwd=str(REPO),
    )
    # rc 1: the broken fixtures trip their rules, but the write happened
    # (no exit-2 refusal from the shell gate)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "wrote 20 config(s)" in proc.stdout
    assert out.exists()


def test_lint_sh_refuses_write_with_explicit_paths():
    proc = subprocess.run(
        ["bash", "tools/lint.sh", "ps_pytorch_tpu", "--write-baseline"],
        capture_output=True, text=True, cwd=str(REPO),
    )
    assert proc.returncode == 2
    assert "gate's" in proc.stderr


# ------------------------------------------------------------ tier-1 gate

# one case per configuration, named from the committed artifact (reading
# a JSON file is all collection costs); the whole-file case below fails
# if the registry and the artifact disagree about which names exist
CONFIG_NAMES = sorted(json.loads(CONTRACT.read_text())["configs"])


@pytest.fixture(scope="module")
def registry_results():
    return trace_registry(get_contracts())


@pytest.fixture(scope="module")
def registry_checked(registry_results):
    """The registry checked once without and once against the committed
    artifact, beside both JSON forms; each per-configuration case reads
    its own findings and its own entry."""
    committed = load_contract(str(CONTRACT))
    return {
        "live_findings": run_checks(registry_results, contract=None),
        "committed_findings": run_checks(registry_results, committed),
        "live_json": to_contract_json(registry_results),
        "committed_json": committed,
    }


def _report(findings):
    return "\n".join(f"{f.config}: {f.rule} {f.message}" for f in findings)


@pytest.mark.parametrize("name", CONFIG_NAMES)
def test_registry_contracts_hold(registry_checked, name):
    """THE gate (rules PSC101/102/103/105): this scheme's traced step
    satisfies its declared communication contract."""
    assert name in registry_checked["live_json"]["configs"]
    mine = [f for f in registry_checked["live_findings"] if f.config == name]
    assert mine == [], _report(mine)


@pytest.mark.parametrize("name", CONFIG_NAMES)
def test_committed_contract_roundtrips(registry_checked, name):
    """PSC104: this configuration's committed entry matches its live
    trace bit-for-bit (both through run_checks and as raw JSON) — a
    failure names the configuration whose wire moved."""
    mine = [
        f for f in registry_checked["committed_findings"] if f.config == name
    ]
    assert mine == [], _report(mine)
    assert (
        registry_checked["live_json"]["configs"][name]
        == registry_checked["committed_json"]["configs"][name]
    )


def test_committed_contract_file_roundtrips(registry_checked):
    """The artifact as a whole: no finding of either run (one charged to
    a name outside the artifact would escape the per-configuration
    cases), and the file equals the live trace's JSON — same names, same
    header."""
    for key in ("live_findings", "committed_findings"):
        assert registry_checked[key] == [], _report(registry_checked[key])
    assert registry_checked["live_json"] == registry_checked["committed_json"]


def test_committed_contract_pins_an_int8_wire():
    """The §6b headline in artifact form: the 2-round schemes' on-wire
    payloads are int8 — both the all_to_all scatter round and the
    all_gather return round."""
    committed = load_contract(str(CONTRACT))
    for name in ("ps_int8_2round_replicated", "ps_int8_2round_sharded",
                 "ps_hier_int8_2round_replicated"):
        rows = committed["configs"][name]["collectives"]
        int8_rows = [r for r in rows if r["dtype"] == "int8"]
        assert int8_rows, f"{name} pins no int8 wire entry"
        assert any(r["kind"] == "all_to_all" for r in int8_rows), name
    repl = committed["configs"]["ps_int8_2round_replicated"]["collectives"]
    assert any(
        r["kind"] == "all_gather" and r["dtype"] == "int8" for r in repl
    )


def test_committed_contract_pins_bucketing_collapse():
    """The fused-wire headline in artifact form: the replicated int8
    ResNet config drops from one gradient psum per pytree leaf to
    <= ceil(payload / bucket_bytes) bucketed psums."""
    from ps_pytorch_tpu.check.contracts import (
        RESNET_BUCKET_BYTES, payload_bytes,
    )

    committed = load_contract(str(CONTRACT))

    def grad_psums(name):
        rows = committed["configs"][name]["collectives"]
        return sum(
            r["count"] for r in rows
            if r["kind"] == "psum" and r["dtype"] == "int32"
        )

    n_leaf = grad_psums("ps_resnet18_int8_replicated")
    n_bucketed = grad_psums("ps_resnet18_int8_replicated_bucketed")
    n_buckets = -(-payload_bytes("ResNet18") // RESNET_BUCKET_BYTES)
    assert n_leaf > 50, n_leaf       # one per leaf (62 for ResNet18)
    assert n_bucketed <= n_buckets, (n_bucketed, n_buckets)
    # and the fused LeNet variants collapse to exactly one reduce
    for name in ("ps_int8_replicated_bucketed",):
        assert grad_psums(name) == 1, committed["configs"][name]


def test_committed_contract_pins_homomorphic_wire_shrink():
    """The §6h headline in artifact form: the homomorphic twins
    eliminate the gradient-path f32 widening — the hierarchical ICI
    reassembly all_gather shrinks f32 -> int8 (~4x), the "int8" psum
    narrows int32 -> int16 (2x), and the homomorphic 2round gather hop
    carries NO f32 scale rows at all."""
    committed = load_contract(str(CONTRACT))

    def rows(name):
        return committed["configs"][name]["collectives"]

    def one(name, kind, axes, dtype):
        hits = [
            r for r in rows(name)
            if r["kind"] == kind and r["axes"] == axes
            and r["dtype"] == dtype
        ]
        assert len(hits) == 1, (name, kind, axes, dtype, hits)
        return hits[0]

    # hier reassembly: f32 431080 B -> int8 107770 B, exactly 4x
    deq = one("ps_hier_int8_2round_replicated_bucketed",
              "all_gather", ["workers"], "float32")
    hom = one("ps_hier_int8_2round_replicated_bucketed_homomorphic",
              "all_gather", ["workers"], "int8")
    assert deq["bytes"] == 4 * hom["bytes"], (deq, hom)
    # and the homomorphic hier wire carries ZERO f32 payload rows
    # (metrics/scale scalars only: every f32 row is tiny)
    for r in rows("ps_hier_int8_2round_replicated_bucketed_homomorphic"):
        if r["dtype"] == "float32":
            assert r["bytes"] <= 64, r
    # the "int8" psum narrows to the minimal exact accumulator
    deq = one("ps_int8_replicated", "psum", ["workers"], "int32")
    hom = one("ps_int8_replicated_homomorphic", "psum", ["workers"],
              "int16")
    assert deq["bytes"] == 2 * hom["bytes"], (deq, hom)
    # the flat 2round gather hop loses its f32 scale-row gather
    assert any(
        r["kind"] == "all_gather" and r["dtype"] == "float32"
        for r in rows("ps_int8_2round_replicated_bucketed")
    )
    assert not any(
        r["kind"] == "all_gather" and r["dtype"] == "float32"
        for r in rows("ps_int8_2round_replicated_bucketed_homomorphic")
    )


def test_homomorphic_allowance_list_strictly_shrinks():
    """PSC103's declared allowance list must be STRICTLY SMALLER for
    homomorphic configs than for their dequant twins — the widening
    permissions (round-2 scale gather, hier f32 reassembly) stop
    existing rather than merely going unused — and the homomorphic
    "int8" scheme gains a wire policy its dequant twin cannot have."""
    from ps_pytorch_tpu.check.contracts import _ps_spec

    pairs = [
        dict(compress="int8_2round", placement="replicated",
             bucket_bytes=0),
        dict(compress="int8_2round", placement="replicated", dcn_hosts=2,
             bucket_bytes=0),
        dict(compress="int8_2round", placement="sharded"),
    ]
    for kw in pairs:
        kw = dict(kw)
        placement = kw.pop("placement")
        compress = kw.pop("compress")
        deq = _ps_spec(compress, placement, **kw)
        hom = _ps_spec(compress, placement, wire_domain="homomorphic",
                       **kw)
        assert deq.wire is not None and hom.wire is not None
        assert set(hom.wire.allow) < set(deq.wire.allow), (
            deq.name, hom.name,
        )
    # new coverage: the dequant int8 scheme declares NO wire policy
    # (int32 psum by design); the homomorphic twin declares one with the
    # narrow accumulator as payload
    assert _ps_spec("int8", "replicated").wire is None
    hom = _ps_spec("int8", "replicated", wire_domain="homomorphic")
    assert hom.wire is not None and hom.wire.payload_dtype == "int16"


def test_committed_contract_pins_a_silent_serving_wire():
    """The serving hot path in artifact form: both serve_decode configs
    are pinned with ZERO collectives and zero wire bytes — any
    communication creeping into the request loop diffs loudly (PSC104)
    on top of failing PSC107."""
    committed = load_contract(str(CONTRACT))
    for name in ("serve_decode", "serve_decode_int8kv"):
        entry = committed["configs"][name]
        assert entry["collectives"] == [], entry
        assert entry["n_collectives"] == 0
        assert entry["total_bytes"] == 0
        assert entry["axes"] == []


def test_check_sh_gate_passes():
    """End-to-end: the exact command CI documentation points at."""
    proc = subprocess.run(
        ["bash", "tools/check.sh"],
        capture_output=True, text=True, cwd=str(REPO),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout


def test_predicted_scaling_contract_cross_check():
    """tools/predicted_scaling.py's kind-level cross-check against the
    pscheck artifact: the committed scaling rows must agree, and a
    fabricated extra HLO kind must be caught."""
    sys.path.insert(0, str(REPO / "tools"))
    try:
        from predicted_scaling import contract_cross_check
    finally:
        sys.path.pop(0)
    contract = load_contract(str(CONTRACT))
    scaling = json.loads((REPO / "runs" / "predicted_scaling.json").read_text())
    report = contract_cross_check(scaling["rows"], contract)
    assert report["ok"], report
    assert all(r["ok"] for r in report["results"])
    # a wire regression shows up as a kind mismatch
    bad = json.loads(json.dumps(scaling["rows"][:1]))
    bad[0]["by_kind"]["all-to-all"] = {"count": 1, "bytes": 1}
    report = contract_cross_check(bad, contract)
    assert report["ok"] is False


# --------------------------------------------------- opcount coverage

def test_update_path_opcount_serve_decode_is_zero():
    """The serving decode step has NO gradient reduce (PSC107 pins zero
    collectives), so its update-path op count — equations downstream of
    a reduce-kind collective — must be exactly 0. Guards the opcount
    walker against counting serving compute as update path."""
    from ps_pytorch_tpu.check.contracts import _serve_spec
    from ps_pytorch_tpu.check.opcount import update_path_op_count

    built = _serve_spec(False).build()
    assert update_path_op_count(built.step, *built.args) == 0


def test_update_path_opcount_pipelined_zero1():
    """The pipelined ZeRO-1 wire streams per-bucket scatter -> shard
    update -> gather chains: every chain must land in the update-path
    count (the satellite closing the 'only pinned on the ResNet18
    replicated path' gap), and the from-closed helper must agree with
    the tracing entry point on the same step."""
    import jax

    from ps_pytorch_tpu.check.contracts import _ps_spec
    from ps_pytorch_tpu.check.opcount import (
        update_path_op_count,
        update_path_ops_from,
    )

    pip = _ps_spec("int8", "sharded", overlap="pipelined").build()
    ser = _ps_spec("int8", "sharded").build()
    n_pip = update_path_op_count(pip.step, *pip.args)
    n_ser = update_path_op_count(ser.step, *ser.args)
    assert n_pip > 0 and n_ser > 0
    # the two entry points are one walker: tracing fn+args must equal
    # walking an already-made jaxpr
    closed = jax.make_jaxpr(pip.step)(*pip.args)
    assert update_path_ops_from(closed) == n_pip
