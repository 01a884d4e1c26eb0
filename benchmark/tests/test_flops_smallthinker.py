"""flops/smallthinker.py against counts made by hand at the published sizes,
the parameter count of configs/smallthinker_21b_a3b_ep4.json against the
reference's shapes, and the cell as BENCHMARK.json declares it."""

import json
import math
import os

import pytest

from benchmark import flops, reference, spec

CELL = "smallthinker_train_b1s16384_ep4share"
TRAFFIC = {"batch_rows": 1, "seq_len": 16384}
BAND, HALF = 4096 * 4097 // 2 + 12288 * 4096, 16384 * 16385 // 2


def _cfg():
    with open(os.path.join(spec.BENCH_DIR, "configs", "smallthinker_21b_a3b_ep4.json")) as f:
        return json.load(f)


def test_parameters_by_hand():
    cfg = _cfg()
    k = flops.load(cfg["flops"])
    # q and o 2560 x 28 x 128, k and v 2560 x 4 x 128; no gate, no bias
    attn = 2 * 2560 * 3584 + 2 * 2560 * 512
    assert k.attention_matmul_params(cfg) == attn == 20_971_520
    assert k.routed_rows_share(cfg) == 6 * 16 / 64 == 1.5
    expert = 3 * 2560 * 768
    assert k.active_matmul_params(cfg) == 4 * (attn + 2560 * 64 + 1.5 * expert) + 2560 * 18992
    # what the chip holds, every leaf: the file's `parameters` (ISSUE 49's arithmetic)
    layer = attn + 2560 * 64 + 2 * 2560 + 16 * expert
    held = 4 * layer + 2 * 18992 * 2560 + 2560
    assert (expert, layer) == (5_898_240, 115_512_320)
    assert cfg["parameters"] == held == 559_290_880
    assert round(16 * held / 1e9, 2) == 8.95 and round(16 * held / 2 ** 30, 2) == 8.33
    shapes = reference.load(cfg["reference"]).param_shapes(cfg)
    import jax

    assert sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes)) == held
    # the same count over 52 layers, 64 experts and 151,936 ids: the row's 21B
    whole = 52 * (attn + 2560 * 64 + 2 * 2560 + 64 * expert) + 2 * 151936 * 2560 + 2560
    assert round(whole / 1e9, 2) == 21.51


def test_train_flops_per_token_by_hand():
    cfg = _cfg()
    k = flops.load(cfg["flops"])
    assert (k.score_entries(cfg, 1, 16384), k.score_entries(cfg, 0, 16384)) == (BAND, HALF)
    # the window keeps 43.7% of the causal entries at the model's whole context
    assert (BAND, HALF) == (58_722_304, 134_225_920) and round(BAND / HALF, 3) == 0.437
    attention = 28 * (3 * BAND + HALF) / 16384 * 4 * 128
    forward = 2 * k.active_matmul_params(cfg) + attention
    assert k.train_flops_per_item(cfg, TRAFFIC) == pytest.approx(3 * forward)
    # 0.61 GFLOP a token forward, the scores 45% of it; 29.9 TFLOP of model work a step
    assert forward == pytest.approx(0.609e9, rel=5e-3)
    assert attention / forward == pytest.approx(0.446, abs=0.005)
    assert 16384 * 3 * forward == pytest.approx(29.9e12, rel=1e-2)


def test_flash_step_by_hand():
    k = flops.load("smallthinker")
    work = k.flash_train_step(_cfg(), TRAFFIC)
    per = lambda entries: 28 * entries * 7 * 2 * 128
    assert work["flops"] == 3 * per(BAND) + per(HALF)
    assert work["bytes"] == 4 * 12 * 16384 * 28 * 128 * 2 and work["peak"] == "bf16_flops_per_s"
    # 6.73 TFLOP the global layer, 2.95 each sliding one: 15.6 TFLOP, 79 ms at the peak;
    # the 5.6 GB take 6.9 ms: the products bound it
    assert per(HALF) == pytest.approx(6.73e12, rel=2e-3) and per(BAND) == pytest.approx(2.95e12, rel=2e-3)
    assert work["flops"] / 197e12 == pytest.approx(79.0e-3, rel=1e-2)
    assert work["bytes"] / 819e9 == pytest.approx(6.88e-3, rel=1e-2)


def test_routed_step_by_hand_and_by_the_runs_counter():
    k = flops.load("smallthinker")
    cfg = _cfg()
    uniform = k.moe_routed_train_step(cfg, TRAFFIC)
    rows = 4 * 16384 * 1.5                                     # 24,576 a layer: 1,536 an expert
    assert uniform["flops"] == 3 * 2 * rows * 3 * 2560 * 768 == pytest.approx(3.48e12, rel=2e-3)
    assert uniform["bytes"] == 9 * rows * (2560 + 768) * 2 + 4 * 16 * 3 * 2560 * 768 * 4
    counted = k.moe_routed_train_step(cfg, TRAFFIC, {"moe_rows_here_traced": 1000.0})
    assert counted["flops"] == 3 * 2 * 1000 * 3 * 2560 * 768


def test_the_cell_is_as_declared():
    cell = spec.load_cell(CELL)
    t, c = cell.traffic, cell.config
    assert cell.kind == "lm_config_train" and cell.chips == 1
    assert cell.traffic_name == "lm_b1s16384_flash_adam_lr3e-6_remat"
    # the evabyte cell's file with another rate, and nothing else
    with open(os.path.join(spec.BENCH_DIR, "traffic", "lm_b1s16384_flash_adam_remat.json")) as f:
        assert {**json.load(f), "lr": 3e-6} == t
    # every published width unchanged
    assert (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
            c["moe_ffn_hidden_size"], c["moe_num_primary_experts"],
            c["moe_num_active_primary_experts"], c["sliding_window_size"], c["rope_theta"],
            c["rms_norm_eps"], c["max_position_embeddings"]) == (
        2560, 28, 4, 128, 768, 64, 6, 4096, 1500000, 1e-6, 16384)
    assert (c["moe_primary_router_apply_softmax"], c["norm_topk_prob"], c["rope_scaling"],
            c["tie_word_embeddings"], c["model_name"], c["model_type"]) == (
        True, True, None, False, "smallthinker_21b_instruct", "smallthinker")
    assert (c["num_hidden_layers"], c["vocab_size"], c["experts_held"], c["expert_offset"]) == (
        4, 18992, 16, 0)
    assert c["sliding_window_layout"] == c["rope_layout"] == [0, 1, 1, 1]
    assert c["reduced"] == ["num_hidden_layers", "sliding_window_layout", "rope_layout",
                            "experts_held", "vocab_size"]
    assert c["published"]["num_hidden_layers"] == 52 and c["published"]["vocab_size"] == 151936
    assert 151936 / 8 == 18992 and {"router_input", "router_scores", "expert",
                                    "secondary_experts"} <= set(c["assumed"])
    names = {m["name"] for m in cell.per_layer}
    assert {"lm_step_device_ms", "lm_forward_ms", "lm_backward_ms", "lm_remat_ms", "lm_update_ms",
            "lm_mixer_ms", "lm_ffn_ms", "lm_head_loss_ms", "lm_scope_unplaced_ms",
            "lm_device_idle_pct", "lm_peak_hbm_gib", "flash_ms", "flash_roofline",
            "moe_routed_ms", "moe_routed_roofline", "moe_rows_max_over_mean",
            "moe_rows_here_traced", "moe_passes_traced", "moe_buffer_scope_ms", "swa_mixer_ms",
            "attn_gate_rope_ms", "moe_route_ms", "swa_flash_scope_ms", "moe_gate_active",
            "compile_s"} == names
    assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s", "setup_s"]
    assert set(cell.limits) == {"loss_step1_rel", "loss_step2_rel", "loss_step3_rel",
                                "grad_norm_worst_leaf", "dparam_norm_worst_leaf"}
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # no other cell gained one of the three new metrics; still one four-chip cell of nine
    for m in bench["per_layer"]:
        if m["name"] in ("moe_route_ms", "swa_flash_scope_ms", "moe_gate_active"):
            assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
    assert len(bench["workloads"]) == 9 and len(bench["configs"]) == 8
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == ["resnet18_b2048x4_int8"]
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert all(word in entry["why"] for word in ("quarter", "four layers of 52", "43.7%"))
