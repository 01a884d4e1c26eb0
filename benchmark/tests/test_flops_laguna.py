"""flops/laguna.py against counts made by hand at the published sizes, the
parameter count of configs/laguna_s_2_1_ep32.json against the reference's
shapes, and the cell as BENCHMARK.json declares it."""

import json
import math
import os

import pytest

from benchmark import flops, reference, spec

CELL = "laguna_train_b1s8192_ep32share"
TRAFFIC = {"batch_rows": 1, "seq_len": 8192}


def _cfg():
    with open(os.path.join(spec.BENCH_DIR, "configs", "laguna_s_2_1_ep32.json")) as f:
        return json.load(f)


def test_parameters_by_hand():
    cfg = _cfg()
    k = flops.load(cfg["flops"])
    # q and o 3072 x H x 128, k and v 3072 x 8 x 128, the gate 3072 x H
    attn = lambda h: 2 * 3072 * h * 128 + 2 * 3072 * 1024 + 3072 * h
    assert (k.attention_matmul_params(cfg, 72), k.attention_matmul_params(cfg, 48)) == (
        attn(72), attn(48)) == (63_135_744, 44_187_648)
    assert k.routed_rows_share(cfg) == 10 * 8 / 256 == 0.3125
    expert, dense = 3 * 3072 * 1024, 3 * 3072 * 12288
    sparse = 3072 * 256 + expert + 0.3125 * expert
    assert k.active_matmul_params(cfg) == (2 * attn(48) + 3 * attn(72) + dense + 4 * sparse
                                           + 3072 * 12544)
    # what the chip holds, every leaf: the file's `parameters` (ISSUE 45's arithmetic)
    experts = 3072 * 256 + 256 + expert + 8 * expert       # router, its bias, shared, eight held
    held = (attn(48) + dense + 3 * (attn(72) + experts) + attn(48) + experts + 5 * 2 * 3072
            + 2 * 12544 * 3072 + 3072)
    assert cfg["parameters"] == held == 811_018_240
    shapes = reference.load(cfg["reference"]).param_shapes(cfg)
    import jax

    assert sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes)) == held


def test_train_flops_per_token_by_hand():
    cfg = _cfg()
    k = flops.load(cfg["flops"])
    band, half = 512 * 513 // 2 + 7680 * 512, 8192 * 8193 // 2
    assert (k.score_entries(cfg, "sliding_attention", 8192), k.score_entries(cfg, "full_attention", 8192)) == (band, half)
    attention = (3 * 72 * band + 2 * 48 * half) / 8192 * 4 * 128
    forward = 2 * k.active_matmul_params(cfg) + attention
    assert k.train_flops_per_item(cfg, TRAFFIC) == pytest.approx(3 * forward)
    # 1.22 GFLOP a token forward, the scores a fifth of it; 30 TFLOP of model work a step
    assert forward == pytest.approx(1.22e9, rel=5e-3) and attention / forward == pytest.approx(0.21, abs=0.01)
    assert 8192 * 3 * forward == pytest.approx(30.0e12, rel=1e-2)


def test_flash_steps_by_hand():
    k = flops.load("laguna")
    both, swa = k.flash_train_step(_cfg(), TRAFFIC), k.swa_flash_train_step(_cfg(), TRAFFIC)
    band, half = 512 * 513 // 2 + 7680 * 512, 8192 * 8193 // 2
    assert swa["flops"] == 3 * 72 * band * 7 * 2 * 128
    assert both["flops"] - swa["flops"] == 2 * 48 * half * 7 * 2 * 128
    assert swa["bytes"] == 3 * 12 * 8192 * 72 * 128 * 2 and both["bytes"] - swa["bytes"] == 2 * 12 * 8192 * 48 * 128 * 2
    # the sliding layers: 1.57 TFLOP, 8.0 ms at the peak, their 5.4 GB 6.6 ms: the products bound it
    assert swa["flops"] / 197e12 == pytest.approx(7.98e-3, rel=1e-2)
    assert swa["bytes"] / 819e9 == pytest.approx(6.64e-3, rel=1e-2)
    assert (both["flops"] - swa["flops"]) / 197e12 == pytest.approx(29.3e-3, rel=1e-2)


def test_routed_step_by_hand_and_by_the_runs_counter():
    k = flops.load("laguna")
    cfg = _cfg()
    uniform = k.moe_routed_train_step(cfg, TRAFFIC)
    rows = 4 * 8192 * 0.3125                                    # 2,560 a layer: 320 an expert
    assert uniform["flops"] == 3 * 2 * rows * 3 * 3072 * 1024
    assert uniform["bytes"] == 9 * rows * (3072 + 1024) * 2 + 4 * 8 * 3 * 3072 * 1024 * 4
    counted = k.moe_routed_train_step(cfg, TRAFFIC, {"moe_rows_here_traced": 1000.0})
    assert counted["flops"] == 3 * 2 * 1000 * 3 * 3072 * 1024


def test_the_cell_is_as_declared():
    cell = spec.load_cell(CELL)
    t, c = cell.traffic, cell.config
    assert cell.kind == "lm_config_train" and cell.chips == 1
    assert cell.traffic_name == "lm_b1s8192_flash_adam_lr3e-6_remat"
    # the granite cell's file with another rate, and nothing else
    with open(os.path.join(spec.BENCH_DIR, "traffic", "lm_b1s8192_flash_adam_remat.json")) as f:
        assert {**json.load(f), "lr": 3e-6} == t
    assert (c["hidden_size"], c["intermediate_size"], c["moe_intermediate_size"],
            c["shared_expert_intermediate_size"], c["head_dim"], c["num_key_value_heads"]) == (
        3072, 12288, 1024, 1024, 128, 8)
    assert (c["num_experts"], c["num_experts_per_tok"], c["moe_routed_scaling_factor"],
            c["experts_held"], c["expert_offset"], c["sliding_window"]) == (256, 10, 2.5, 8, 0, 512)
    assert c["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert c["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert c["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert c["rope_parameters"]["full_attention"] == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1, "beta_fast": 32,
        "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5}
    assert (c["num_hidden_layers"], c["vocab_size"]) == (5, 12544)
    assert c["reduced"] == ["num_hidden_layers", "layer_types", "experts_held", "vocab_size"]
    names = {m["name"] for m in cell.per_layer}
    assert {"lm_step_device_ms", "flash_ms", "flash_roofline", "swa_flash_ms", "swa_flash_roofline",
            "swa_mixer_ms", "attn_gate_rope_ms", "moe_routed_ms", "moe_routed_roofline",
            "moe_rows_max_over_mean", "moe_rows_here_traced", "moe_buffer_scope_ms",
            "lm_device_idle_pct", "lm_peak_hbm_gib", "lm_mixer_ms", "lm_ffn_ms", "compile_s"} <= names
    assert not {n for n in names if n.startswith(("ssd_", "ps_", "kda_", "eva_"))}
    assert "moe_buffer_ms" not in names
    assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s", "setup_s"]
    assert set(cell.limits) == {"loss_step1_rel", "loss_step2_rel", "loss_step3_rel",
                                "grad_norm_worst_leaf", "dparam_norm_worst_leaf"}
    # no other cell gained one of the four new metrics
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"] in ("swa_flash_ms", "swa_flash_roofline", "swa_mixer_ms", "attn_gate_rope_ms"):
            assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
