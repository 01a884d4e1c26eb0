"""Pin bench.py's record-key helpers and its failure behaviour.

The driver parses bench's ONE JSON line per run; metric keys must stay
aligned between every BENCH_* knob combination (and between f32 and bf16
configs), every record names the backend it ran on, and a run that cannot
start is a traceback and a non-zero exit — never a record."""

import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture()
def bench(monkeypatch):
    """Fresh bench module per test (its helpers read env at call time, but
    a clean import keeps sys.modules uncluttered)."""
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_lm_tag_encodes_overrides(bench, monkeypatch):
    monkeypatch.delenv("BENCH_DTYPE", raising=False)
    for var in ("BATCH", "SEQ", "DIM", "DEPTH", "SP"):
        monkeypatch.delenv(f"BENCH_LM_{var}", raising=False)
    monkeypatch.delenv("BENCH_LM_FLASH", raising=False)
    assert bench._lm_tag() == "d512x6_s1024_b8"
    monkeypatch.setenv("BENCH_LM_SEQ", "8192")
    monkeypatch.setenv("BENCH_LM_FLASH", "1")
    monkeypatch.setenv("BENCH_LM_BATCH", "2")
    assert bench._lm_tag() == "d512x6_s8192_b2_flash"
    monkeypatch.setenv("BENCH_DTYPE", "float32")
    assert bench._lm_tag().endswith("_f32")


def test_dec_tag_encodes_overrides(bench, monkeypatch):
    monkeypatch.delenv("BENCH_DTYPE", raising=False)
    for var in ("BATCH", "PROMPT", "NEW", "DIM", "DEPTH"):
        monkeypatch.delenv(f"BENCH_DEC_{var}", raising=False)
    assert bench._dec_tag() == "d512x6_p128_n128_b8"
    monkeypatch.setenv("BENCH_DEC_NEW", "256")
    monkeypatch.setenv("BENCH_DTYPE", "float32")
    assert bench._dec_tag() == "d512x6_p128_n256_b8_f32"


def test_srv_tag_shares_the_decode_shape_parser(bench, monkeypatch):
    """The serve leg's tag reads the SAME BENCH_DEC_* model-shape envs as
    the decode leg (one metric-shape helper, _dec_shape_tag) plus its own
    slots/rate knobs — an override moves BOTH tags, so the two legs'
    records can never describe different models under the same shape."""
    monkeypatch.delenv("BENCH_DTYPE", raising=False)
    for var in ("BATCH", "PROMPT", "NEW", "DIM", "DEPTH"):
        monkeypatch.delenv(f"BENCH_DEC_{var}", raising=False)
    for var in ("SLOTS", "REQS", "RATE"):
        monkeypatch.delenv(f"BENCH_SRV_{var}", raising=False)
    monkeypatch.delenv("BENCH_SRV_INT8KV", raising=False)
    assert bench._srv_tag() == "d512x6_p128_n128_s8_r100"
    monkeypatch.setenv("BENCH_DEC_DIM", "256")
    assert bench._dec_tag().startswith("d256x6_")
    assert bench._srv_tag().startswith("d256x6_")
    monkeypatch.setenv("BENCH_SRV_SLOTS", "16")
    monkeypatch.setenv("BENCH_SRV_INT8KV", "1")
    monkeypatch.setenv("BENCH_DTYPE", "float32")
    assert bench._srv_tag() == "d256x6_p128_n128_s16_r100_q8kv_f32"
    monkeypatch.setenv("BENCH_SRV_RATE", "0.5")
    assert "_r0.5_" in bench._srv_tag()


def test_srv_knob_validation(bench, monkeypatch):
    monkeypatch.setenv("BENCH_WORKLOAD", "serve")
    bench._validate_env()  # defaults pass
    monkeypatch.setenv("BENCH_SRV_SLOTS", "0")
    with pytest.raises(SystemExit):
        bench._validate_env()
    monkeypatch.setenv("BENCH_SRV_SLOTS", "8")
    monkeypatch.setenv("BENCH_SRV_INT8KV", "yes")
    with pytest.raises(SystemExit):
        bench._validate_env()
    monkeypatch.setenv("BENCH_SRV_INT8KV", "1")
    bench._validate_env()
    # rate is a FLOAT (sub-1 rps open-loop regimes are benchable) but
    # must be a finite positive number
    monkeypatch.setenv("BENCH_SRV_RATE", "0.5")
    bench._validate_env()
    assert bench._srv_rate() == 0.5
    for bad in ("0", "-1", "nan", "lots"):
        monkeypatch.setenv("BENCH_SRV_RATE", bad)
        with pytest.raises(SystemExit):
            bench._validate_env()
    monkeypatch.delenv("BENCH_SRV_RATE")
    # CNN-only knobs refuse the serve workload too
    monkeypatch.setenv("BENCH_COMPRESS", "int8")
    with pytest.raises(SystemExit):
        bench._validate_env()


def test_cnn_compress_override_tags_metric(bench, monkeypatch):
    monkeypatch.delenv("BENCH_COMPRESS", raising=False)
    monkeypatch.delenv("BENCH_DTYPE", raising=False)
    monkeypatch.setenv("BENCH_WORKLOAD", "resnet18")
    base = bench._success_metric()
    assert base == "resnet18_cifar10_b1024_train_throughput"
    # canonical mode requested explicitly -> canonical key (never forks
    # the canonical record)
    monkeypatch.setenv("BENCH_COMPRESS", "int8")
    assert bench._success_metric() == base
    monkeypatch.setenv("BENCH_COMPRESS", "int8_2round")
    assert bench._success_metric() == base + "_2round"
    monkeypatch.setenv("BENCH_COMPRESS", "none")
    assert bench._success_metric() == base + "_nocomp"
    # compress tag composes with the dtype tag
    monkeypatch.setenv("BENCH_DTYPE", "bfloat16")
    assert bench._success_metric() == base + "_nocomp_bf16"
    monkeypatch.setenv("BENCH_COMPRESS", "blosc")
    with pytest.raises(SystemExit):
        bench._validate_env()


def test_cnn_dtype_suffix_matches_contract(bench, monkeypatch):
    monkeypatch.delenv("BENCH_DTYPE", raising=False)
    assert bench._cnn_dtype_suffix() == ""
    monkeypatch.setenv("BENCH_DTYPE", "bfloat16")
    assert bench._cnn_dtype_suffix() == "_bf16"
    monkeypatch.setenv("BENCH_DTYPE", "float32")
    assert bench._cnn_dtype_suffix() == ""


def test_validate_env_rejects_bad_knobs(bench, monkeypatch):
    monkeypatch.setenv("BENCH_DTYPE", "bf16")
    with pytest.raises(SystemExit):
        bench._validate_env()
    monkeypatch.setenv("BENCH_DTYPE", "bfloat16")
    monkeypatch.setenv("BENCH_WORKLOAD", "nope")
    with pytest.raises(SystemExit):
        bench._validate_env()
    monkeypatch.setenv("BENCH_WORKLOAD", "lm")
    bench._validate_env()  # no raise


def test_bucket_knobs_tag_metric_and_validate(bench, monkeypatch):
    """BENCH_BUCKET_BYTES / BENCH_AB_BUCKETING: tagged metric keys (never
    shadow canonical records), CNN-only, value-validated."""
    monkeypatch.setenv("BENCH_WORKLOAD", "lenet")
    base = bench._success_metric()
    monkeypatch.setenv("BENCH_BUCKET_BYTES", "0")
    bench._validate_env()
    assert bench._success_metric() == base + "_bkt0"
    monkeypatch.setenv("BENCH_AB_BUCKETING", "1")
    bench._validate_env()
    assert bench._success_metric() == base + "_ab_bucketing"
    monkeypatch.setenv("BENCH_BUCKET_BYTES", "-4")
    with pytest.raises(SystemExit):
        bench._validate_env()
    monkeypatch.setenv("BENCH_BUCKET_BYTES", "0")
    monkeypatch.setenv("BENCH_WORKLOAD", "lm")
    with pytest.raises(SystemExit):
        bench._validate_env()


def test_wire_ab_knob_tags_metric_and_validates(bench, monkeypatch):
    """BENCH_AB_WIRE (§6h): tagged metric key, needs a compressed wire,
    mutually exclusive with the other A/B dimensions, CNN-only."""
    monkeypatch.setenv("BENCH_WORKLOAD", "lenet")
    monkeypatch.delenv("BENCH_COMPRESS", raising=False)
    base = bench._success_metric()
    monkeypatch.setenv("BENCH_AB_WIRE", "1")
    # lenet's canonical wire is uncompressed: nothing to homomorphically
    # sum, refused with the remedy named
    with pytest.raises(SystemExit, match="BENCH_COMPRESS"):
        bench._validate_env()
    monkeypatch.setenv("BENCH_COMPRESS", "int8")
    bench._validate_env()
    assert bench._success_metric() == base + "_int8w_ab_wire"
    # resnet18's canonical mode is already compressed — no override needed
    monkeypatch.setenv("BENCH_WORKLOAD", "resnet18")
    monkeypatch.delenv("BENCH_COMPRESS", raising=False)
    bench._validate_env()
    assert bench._success_metric().endswith("_ab_wire")
    # one A/B dimension per record
    monkeypatch.setenv("BENCH_AB_OVERLAP", "1")
    with pytest.raises(SystemExit, match="mutually exclusive"):
        bench._validate_env()
    monkeypatch.delenv("BENCH_AB_OVERLAP")
    # CNN-only, like every other wire knob
    monkeypatch.setenv("BENCH_WORKLOAD", "lm")
    with pytest.raises(SystemExit):
        bench._validate_env()
    monkeypatch.setenv("BENCH_WORKLOAD", "lenet")
    monkeypatch.setenv("BENCH_AB_WIRE", "2")
    with pytest.raises(SystemExit, match="0 or 1"):
        bench._validate_env()
    # AB_WIRE=0 is inert (a CI wrapper exporting it globally must not
    # abort the lm leg)
    monkeypatch.setenv("BENCH_AB_WIRE", "0")
    monkeypatch.setenv("BENCH_WORKLOAD", "lm")
    bench._validate_env()


def test_comm_contract_entry_homomorphic_twins(bench):
    """wire_domain routes the contract lookup to the homomorphic twin
    entries, and the derived gradient-path bytes show the §6h shrink
    (int16 psum = half the dequant twin's int32)."""
    deq = bench._comm_contract_entry("lenet", "int8", None)
    hom = bench._comm_contract_entry("lenet", "int8", None, "homomorphic")
    assert hom and hom["config"] == "ps_int8_replicated_homomorphic"
    assert deq["grad_wire_bytes"] == 2 * hom["grad_wire_bytes"]
    res = bench._comm_contract_entry(
        "resnet18", "int8", 4 << 20, "homomorphic"
    )
    assert res and res["config"] == (
        "ps_resnet18_int8_replicated_bucketed_homomorphic"
    )
    # the ResNet pair's gradient-path ratio is EXACTLY the int32->int16
    # payload shrink: the BatchNorm f32 stats psum (model state, not
    # gradients) must not dilute it
    res_deq = bench._comm_contract_entry("resnet18", "int8", 4 << 20)
    assert res_deq["grad_wire_bytes"] == 2 * res["grad_wire_bytes"]
    # the uncompressed wire's f32 gradient psum still counts as payload
    none_row = bench._comm_contract_entry("lenet", None, None)
    assert none_row["grad_wire_bytes"] > 1 << 20
    # untraced homomorphic combos still yield None, never a mislabel
    assert bench._comm_contract_entry(
        "lenet", None, None, "homomorphic"
    ) is None


def test_comm_contract_entry_exact_match_only(bench):
    """The committed pscheck rows attach only when the bench config maps
    onto a traced registry entry — a different bucket carving must yield
    None rather than mislabeled wire numbers."""
    row = bench._comm_contract_entry("lenet", None, None)
    assert row and row["config"] == "ps_none_replicated"
    assert row["n_collectives"] > 0 and row["wire_bytes"] > 0
    fused = bench._comm_contract_entry("lenet", "int8", 0)
    assert fused and fused["config"] == "ps_int8_replicated_bucketed"
    # the registry traces the LeNet bucketed variants at bucket_bytes=0
    # and ResNet18 at 4 MiB — anything else must not attach
    assert bench._comm_contract_entry("lenet", "int8", 4096) is None
    res = bench._comm_contract_entry("resnet18", "int8", 4 << 20)
    assert res and res["config"] == "ps_resnet18_int8_replicated_bucketed"
    assert bench._comm_contract_entry("resnet18", "int8", 0) is None
    # untraced combination: resnet has no compress=None registry entry
    assert bench._comm_contract_entry("resnet18", None, None) is None


def test_success_metric_covers_all_workloads(bench, monkeypatch):
    monkeypatch.delenv("BENCH_DTYPE", raising=False)
    for var in list(bench._LM_DEFAULTS) + list(bench._DEC_DEFAULTS):
        monkeypatch.delenv(f"BENCH_LM_{var}", raising=False)
        monkeypatch.delenv(f"BENCH_DEC_{var}", raising=False)
    for var in list(bench._SRV_DEFAULTS) + ["RATE"]:
        monkeypatch.delenv(f"BENCH_SRV_{var}", raising=False)
    monkeypatch.delenv("BENCH_SRV_INT8KV", raising=False)
    cases = {
        "lenet": "lenet_mnist_b8192_train_throughput",
        "resnet18": "resnet18_cifar10_b1024_train_throughput",
        "lm": "lm_d512x6_s1024_b8_train_tokens_per_sec",
        "decode": "decode_d512x6_p128_n128_b8_new_tokens_per_sec",
        "serve": "serve_d512x6_p128_n128_s8_r100_tokens_per_sec",
    }
    for wl, want in cases.items():
        monkeypatch.setenv("BENCH_WORKLOAD", wl)
        assert bench._success_metric() == want


def test_validate_env_rejects_non_integer_knobs(bench, monkeypatch):
    monkeypatch.delenv("BENCH_DTYPE", raising=False)
    monkeypatch.setenv("BENCH_WORKLOAD", "decode")
    monkeypatch.setenv("BENCH_DEC_NEW", "12b8")
    with pytest.raises(SystemExit):
        bench._validate_env()
    monkeypatch.setenv("BENCH_DEC_NEW", "128")
    bench._validate_env()


def test_peak_flops_unknown_kind_returns_none(bench):
    class Dev:
        device_kind = "TPU v9 hyper"

    assert bench._peak_flops_per_sec(Dev()) is None

    class V5e:
        device_kind = "TPU v5 lite"

    assert bench._peak_flops_per_sec(V5e()) == 197e12

    class Cpu:
        device_kind = "cpu"

    assert bench._peak_flops_per_sec(Cpu()) is None


def test_backend_info_stamps_platform_and_device_kind(bench):
    info = bench._backend_info("TPU v5 lite")
    assert info["device_kind"] == "TPU v5 lite"
    assert info["platform"] == "cpu"  # the test env's live backend
    assert bench._backend_info(None)["device_kind"] is None


def test_require_same_backend_refuses_mixed_ab_variants(bench):
    """An A/B speedup across backends must refuse, not report."""
    cpu = {"backend": {"platform": "cpu", "device_kind": "cpu"}}
    tpu = {"backend": {"platform": "tpu", "device_kind": "TPU v5 lite"}}
    bench._require_same_backend(cpu, dict(cpu))  # like-for-like: fine
    with pytest.raises(SystemExit, match="across backends"):
        bench._require_same_backend(cpu, tpu)
    # a variant missing the stamp counts as a distinct (unknown) backend
    with pytest.raises(SystemExit, match="across backends"):
        bench._require_same_backend(cpu, {})


def test_broken_backend_is_a_traceback_not_a_record():
    """No probe, no CPU re-run, no catch-all: when the backend cannot
    start, bench.py exits non-zero with the traceback on stderr and prints
    nothing a driver could parse as a record."""
    env = dict(os.environ, JAX_PLATFORMS="no_such_backend", BENCH_STEPS="1")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "Traceback" in proc.stderr
    assert "no_such_backend" in proc.stderr
    assert proc.stdout.strip() == ""
