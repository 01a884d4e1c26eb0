"""flops/granite_hybrid.py against counts made by hand at the published
sizes, the parameter count of configs/granite4_h_micro_1period.json against
the reference's shapes, and the cell as BENCHMARK.json declares it."""

import json
import math
import os

import pytest

from benchmark import flops, reference, spec

CELL = "granite4hm_train_remat_1period"
TRAFFIC = {"batch_rows": 1, "seq_len": 8192}


def _cfg():
    with open(os.path.join(spec.BENCH_DIR, "configs", "granite4_h_micro_1period.json")) as f:
        return json.load(f)


def test_parameters_by_hand():
    cfg = _cfg()
    k = flops.load(cfg["flops"])
    assert k.mamba_matmul_params(cfg) == 2048 * (4096 + 4352 + 64) + 4096 * 2048 == 25_821_184
    assert k.attention_matmul_params(cfg) == 2 * 2048 * 2048 + 2 * 2048 * 512 == 10_485_760
    mlp = 2048 * 16384 + 8192 * 2048
    assert k.matmul_params(cfg) == 9 * (25_821_184 + mlp) + (10_485_760 + mlp) + 12544 * 2048
    # what the chip holds, every leaf: the file's `parameters` (ISSUE 31's arithmetic)
    mamba = 25_821_184 + 4352 * 4 + 4352 + 3 * 64 + 4096
    held = 9 * (mamba + mlp + 2 * 2048) + (10_485_760 + mlp + 2 * 2048) + 12544 * 2048 + 2048
    assert cfg["parameters"] == held == 772_160_448
    shapes = reference.load(cfg["reference"]).param_shapes(cfg)
    import jax

    assert sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes)) == held


def test_train_flops_per_token_by_hand():
    cfg = _cfg()
    k = flops.load(cfg["flops"])
    scan = 2 * 256 * 128 + 64 * 2 * 256 * 64 + 64 * 2 * 2 * 64 * 128
    assert k.scan_flops_per_token(cfg) == scan == 4_259_840
    forward = 2 * k.matmul_params(cfg) + 9 * scan + 2 * 4096 * 32 * 128
    assert k.train_flops_per_item(cfg, TRAFFIC) == 3 * forward
    # ISSUE 31: about 40 TFLOP of model work a step of 8,192 tokens
    assert 8192 * 3 * forward == pytest.approx(39.7e12, rel=5e-3)
    # the 8192-wide MLPs carry about two thirds of the products
    assert 10 * 2 * 3 * 2048 * 8192 / forward == pytest.approx(0.62, abs=0.01)


def test_flash_step_by_hand():
    w = flops.load("granite_hybrid").flash_train_step(_cfg(), TRAFFIC)
    assert w["flops"] == 9 * (2 * 8192 * 8192 * 2048 / 2)        # one attention layer, 32 x 64 wide
    assert w["bytes"] == 12 * 8192 * 2048 * 2
    assert w["flops"] / 197e12 == pytest.approx(6.28e-3, rel=1e-2)


def test_ssd_step_by_hand():
    w = flops.load("granite_hybrid").ssd_train_step(_cfg(), TRAFFIC)
    assert w["flops"] == 9 * 8192 * 3 * 4_259_840
    per_token = 4096 * 2 + 2 * 128 * 2 + 64 * 4 + 4096 * 4       # x, B and C, dt, y
    assert w["bytes"] == 9 * 8192 * 2 * per_token
    # 0.94 TFLOP: 4.8 ms at the peak; the 3.7 GB need 4.6 ms
    assert w["flops"] / 197e12 == pytest.approx(4.78e-3, rel=1e-2)
    assert w["bytes"] / 819e9 == pytest.approx(4.56e-3, rel=1e-2)
    assert w["peak"] == "bf16_flops_per_s"


def test_the_cell_is_as_declared():
    cell = spec.load_cell(CELL)
    t, c = cell.traffic, cell.config
    assert cell.kind == "lm_config_train" and cell.chips == 1
    assert (t["batch_rows"], t["seq_len"], t["block_steps"], t["check_steps"]) == (1, 8192, 2, 3)
    assert (t["attention_impl"], t["dtype"], t["remat"]) == ("flash", "bfloat16", True)
    assert (t["optimizer"], t["lr"], t["b1"], t["b2"], t["eps"]) == ("adam", 3e-4, 0.9, 0.999, 1e-8)
    assert (t["num_dp"], t["num_sp"], t["control_operand"]) == (1, 1, "float8_e4m3fn")
    assert (c["hidden_size"], c["shared_intermediate_size"]) == (2048, 8192)
    assert (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"], c["mamba_d_conv"],
            c["mamba_chunk_size"], c["mamba_n_groups"]) == (64, 64, 128, 4, 256, 1)
    assert (c["num_attention_heads"], c["num_key_value_heads"]) == (32, 8)
    assert (c["embedding_multiplier"], c["residual_multiplier"], c["attention_multiplier"],
            c["logits_scaling"]) == (12, 0.22, 1 / 64, 8)
    assert (c["num_hidden_layers"], c["vocab_size"]) == (10, 12544)
    assert c["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert c["reduced"] == ["num_hidden_layers", "layer_types", "vocab_size"]
    names = {m["name"] for m in cell.per_layer}
    assert {"lm_step_device_ms", "flash_ms", "flash_roofline", "ssd_ms", "ssd_roofline",
            "lm_device_idle_pct", "lm_peak_hbm_gib", "compile_s"} <= names
    assert not {n for n in names if n.startswith(("moe_", "ps_"))}
    assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s", "setup_s"]
    assert set(cell.limits) == {"loss_step1_rel", "loss_step2_rel", "loss_step3_rel",
                                "grad_norm_worst_leaf", "dparam_norm_worst_leaf"}


def test_the_configuration_holds_the_catalogs_numbers():
    """Every number of the source's config.json under its own key; only the
    keys in `reduced` differ (the catalog row, where the guide is here)."""
    cat = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(cat):
        pytest.skip("the catalog is not on this machine")
    with open(cat) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-micro")
    cfg = _cfg()
    assert cfg["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if cfg.get(k, "absent") != v}
    assert differ == {"num_hidden_layers", "layer_types", "vocab_size"} == set(cfg["reduced"])
    assert cfg["layer_types"] == row["config"]["layer_types"][:10]
