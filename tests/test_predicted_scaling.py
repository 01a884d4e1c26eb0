"""Unit tests for the predicted-scaling model math (no compiles — the
compile-level paths are smoked by the tools themselves)."""

import importlib.util
import os

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def ps_mod():
    spec = importlib.util.spec_from_file_location(
        "predicted_scaling_under_test",
        os.path.join(REPO, "tools", "predicted_scaling.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_ring_factors(ps_mod):
    f = ps_mod._RING_FACTOR
    # ring all-reduce moves every byte twice minus the kept 1/n share
    assert f["all-reduce"](2) == pytest.approx(1.0)
    assert f["all-reduce"](8) == pytest.approx(2 * 7 / 8)
    assert f["all-gather"](8) == pytest.approx(7 / 8)
    assert f["collective-permute"](8) == 1.0


def test_predict_efficiency_bounds(ps_mod):
    row = {
        "workers": 8,
        "by_kind": {"all-reduce": {"count": 1, "bytes": 44_700_000}},
        "total_collective_bytes": 44_700_000,
        "n_collectives": 1,
        "mode": "none",
        "hosts": 1,
    }
    t1, bw = 0.067, 45e9
    out = ps_mod.predict(row, t1, bw)
    comm = 44_700_000 * (2 * 7 / 8) / bw
    assert out["modeled_comm_s"] == pytest.approx(comm, abs=1e-6)
    assert out["modeled_compute_s"] == pytest.approx(t1 / 8, abs=1e-6)
    # no-overlap is always the weaker bound
    assert out["efficiency_no_overlap"] <= out["efficiency_full_overlap"]
    assert out["speedup_no_overlap"] == pytest.approx(
        t1 / (t1 / 8 + comm), rel=1e-2
    )
    # full-overlap cannot exceed linear
    assert out["speedup_full_overlap"] <= 8.0 + 1e-6


def test_predict_per_axis_flat_crosshost(ps_mod):
    """A FLAT 16-chip all-reduce (one group g=16 spanning h=2 hosts of 8)
    must be priced at the DCN NIC, not ICI: per-link bytes S*2(g-1)/g, one
    outgoing cut edge per host (per_host/c = 8/8 = 1 group on the NIC),
    pipelined-ring bottleneck = the slower DCN link."""
    S = 44_700_000
    row = {
        "workers": 16, "mode": "none", "hosts": 1, "per_host_model": 8,
        "by_kind": {"all-reduce": {"count": 1, "bytes": S}},
        "by_class": {"all-reduce|g16|h2": {
            "kind": "all-reduce", "g": 16, "h": 2, "count": 1, "bytes": S,
        }},
        "total_collective_bytes": S, "n_collectives": 1,
    }
    ici, dcn = 45e9, 12.5e9
    out = ps_mod.predict(row, 0.067, ici, dcn_bw=dcn)
    want = S * (2 * 15 / 16) / dcn  # max(link/ici, link/dcn) = link/dcn
    assert out["modeled_comm_s"] == pytest.approx(want, abs=1e-6)
    assert out["modeled_comm_dcn_s"] == pytest.approx(want, abs=1e-6)
    assert out["modeled_comm_ici_s"] == 0.0


def test_predict_per_axis_hier_dcn_stage(ps_mod):
    """The hier scheme's DCN stage: per_host=8 groups of g=h hosts (c=1,
    one chip per host per group) all share each host's NIC — t_dcn =
    8 * S*factor(g) / dcn, with NO ICI segment (every ring edge crosses
    hosts). An intra-host class in the same row prices at ICI."""
    S_dcn, S_ici = 1_000_000, 8_000_000
    row = {
        "workers": 32, "mode": "hier_2round", "hosts": 4,
        "per_host_model": 8,
        "by_kind": {"all-to-all": {"count": 1, "bytes": S_dcn},
                    "reduce-scatter": {"count": 1, "bytes": S_ici}},
        "by_class": {
            "all-to-all|g4|h4": {
                "kind": "all-to-all", "g": 4, "h": 4, "count": 1,
                "bytes": S_dcn,
            },
            "reduce-scatter|g8|h1": {
                "kind": "reduce-scatter", "g": 8, "h": 1, "count": 1,
                "bytes": S_ici,
            },
        },
        "total_collective_bytes": S_dcn + S_ici, "n_collectives": 2,
    }
    ici, dcn = 45e9, 12.5e9
    out = ps_mod.predict(row, 0.067, ici, dcn_bw=dcn)
    want_dcn = 8 * S_dcn * (3 / 4) / dcn
    want_ici = S_ici * (7 / 8) / ici
    assert out["modeled_comm_dcn_s"] == pytest.approx(want_dcn, abs=1e-6)
    assert out["modeled_comm_ici_s"] == pytest.approx(want_ici, abs=1e-6)
    assert out["modeled_comm_s"] == pytest.approx(
        want_dcn + want_ici, abs=2e-6
    )


def test_predict_crosshost_ici_bound_attribution(ps_mod):
    """On a fast fabric the cross-host ring can be ICI-bound: time goes to
    the ICI column so the per-axis split names the real bottleneck."""
    S = 44_700_000
    row = {
        "workers": 16, "mode": "none", "hosts": 1, "per_host_model": 8,
        "by_kind": {"all-reduce": {"count": 1, "bytes": S}},
        "by_class": {"all-reduce|g16|h2": {
            "kind": "all-reduce", "g": 16, "h": 2, "count": 1, "bytes": S,
        }},
        "total_collective_bytes": S, "n_collectives": 1,
    }
    out = ps_mod.predict(row, 0.067, 45e9, dcn_bw=50e9)  # 400 Gbps NIC
    want = S * (2 * 15 / 16) / 45e9  # ICI leg is now the slower one
    assert out["modeled_comm_ici_s"] == pytest.approx(want, abs=1e-6)
    assert out["modeled_comm_dcn_s"] == 0.0


def test_predict_legacy_rows_unchanged(ps_mod):
    """Rows without by_class (r04-era artifacts) fall back to the flat
    single-bandwidth model at total chip count — re-reading old reports
    through the new model must not silently change their numbers."""
    S = 10_000_000
    row = {
        "workers": 8, "mode": "none", "hosts": 1,
        "by_kind": {"all-reduce": {"count": 1, "bytes": S}},
        "total_collective_bytes": S, "n_collectives": 1,
    }
    out = ps_mod.predict(row, 0.067, 45e9, dcn_bw=12.5e9)
    assert out["modeled_comm_s"] == pytest.approx(
        S * (2 * 7 / 8) / 45e9, abs=1e-6
    )
    assert out["modeled_comm_dcn_s"] == 0.0


def test_unknown_collective_kind_uses_conservative_factor(ps_mod):
    row = {
        "workers": 4,
        "by_kind": {"mystery-op": {"count": 1, "bytes": 1_000_000}},
        "total_collective_bytes": 1_000_000,
        "n_collectives": 1,
        "mode": "none",
        "hosts": 1,
    }
    out = ps_mod.predict(row, 0.1, 1e9)
    # falls back to the all-reduce factor (the most expensive ring cost)
    assert out["modeled_comm_s"] == pytest.approx(
        1_000_000 * (2 * 3 / 4) / 1e9, abs=1e-9
    )
