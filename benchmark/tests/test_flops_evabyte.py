"""flops/evabyte.py against counts made by hand at the published sizes, the
parameter count of configs/evabyte_6b5_4layers.json against the reference's
shapes, and the cell as BENCHMARK.json declares it."""

import json
import math
import os

import pytest

from benchmark import flops, reference, spec

CELL = "evabyte_train_b1s16384_4layers"
TRAFFIC = {"batch_rows": 1, "seq_len": 16384}


def _cfg():
    with open(os.path.join(spec.BENCH_DIR, "configs", "evabyte_6b5_4layers.json")) as f:
        return json.load(f)


def test_parameters_by_hand():
    cfg = _cfg()
    k = flops.load(cfg["flops"])
    assert k.layer_matmul_params(cfg) == 4 * 4096 ** 2 + 3 * 4096 * 11008 == 202_375_168
    assert k.matmul_params(cfg) == 4 * 202_375_168 + 4096 * 8 * 320 == 819_986_432
    # what the chip holds, every leaf: the file's `parameters` (ISSUE 41's arithmetic)
    layer = 202_375_168 + 2 * 4096 + 2 * 32 * 128
    held = 4 * layer + 320 * 4096 + 4096 * 8 * 320 + 4096
    assert cfg["parameters"] == held == 821_366_784
    import jax

    shapes = reference.load(cfg["reference"]).param_shapes(cfg)
    assert sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes)) == held


def test_score_entries_and_live_tiles_by_hand():
    cfg = _cfg()
    k = flops.load(cfg["flops"])
    local, remote = k.score_entries_per_head(cfg, 16384)
    # eight windows' causal halves (with the diagonal); windows 1..7 see 128, 256, ... 896 summaries
    assert local == 8 * (2048 * 2049 // 2) and remote == 2048 * 128 * 28 == 7_340_032
    assert local == pytest.approx(8 * 2048 ** 2 / 2, rel=1e-3)
    # a query sees on average 1,024 bytes of its window and 448 summaries
    assert local / 16384 == pytest.approx(1024, rel=1e-3) and remote / 16384 == 448
    # tiles of 512: ten of a window's sixteen; over the two tiles of 512 summaries, the 28
    # query tiles of windows 1-7 and the 12 of windows 5-7
    assert k.live_tiles_per_head(cfg, 16384) == (80, 28 + 12)
    assert 80 * 512 * 512 / local == pytest.approx(1.25, rel=1e-3)
    assert 40 * 512 * 512 / remote == pytest.approx(10 / 7)
    # a part-filled last window
    assert k.score_entries_per_head(cfg, 2048 + 100) == (2048 * 2049 // 2 + 100 * 101 // 2, 100 * 128)


def test_train_flops_per_byte_by_hand():
    cfg = _cfg()
    k = flops.load(cfg["flops"])
    local, remote = k.score_entries_per_head(cfg, 16384)
    attention = 32 * ((local + remote) / 16384 * 2 * 2 * 128 + 6 * 128)
    forward = 2 * 819_986_432 + 4 * attention
    assert k.train_flops_per_item(cfg, TRAFFIC) == 3 * forward
    # about 85 TFLOP of model work a step of 16,384 bytes (a second forward under
    # `remat` is not counted); the attention is about 6% of it
    assert 16384 * 3 * forward == pytest.approx(85.4e12, rel=5e-3)
    assert 4 * attention / forward == pytest.approx(0.056, abs=0.003)
    # the 11008-wide MLPs carry about two thirds of the products
    assert 4 * 2 * 3 * 4096 * 11008 / forward == pytest.approx(0.62, abs=0.01)


def test_flash_step_by_hand():
    w = flops.load("evabyte").flash_train_step(_cfg(), TRAFFIC)
    assert w["flops"] == 4 * 32 * 120 * 7 * (2 * 512 * 512 * 128)     # seven products a live tile
    assert w["bytes"] == 4 * (12 + 5 + 6 / 16) * (16384 * 4096 * 2)
    assert w["flops"] / 197e12 == pytest.approx(36.6e-3, rel=1e-2)      # 7.2 TFLOP: compute-bound
    assert w["bytes"] / 819e9 == pytest.approx(11.4e-3, rel=1e-2)
    assert w["peak"] == "bf16_flops_per_s"


def test_the_cell_is_as_declared():
    cell = spec.load_cell(CELL)
    t, c = cell.traffic, cell.config
    assert cell.kind == "lm_config_train" and cell.chips == 1
    assert cell.traffic_name == "lm_b1s16384_flash_adam_remat"
    assert (t["batch_rows"], t["seq_len"], t["block_steps"], t["check_steps"]) == (1, 16384, 2, 3)
    assert (t["attention_impl"], t["dtype"], t["remat"]) == ("flash", "bfloat16", True)
    assert (t["optimizer"], t["lr"], t["b1"], t["b2"], t["eps"]) == ("adam", 3e-4, 0.9, 0.999, 1e-8)
    assert (t["num_dp"], t["num_sp"], t["control_operand"]) == (1, 1, "float8_e4m3fn")
    assert (c["hidden_size"], c["intermediate_size"], c["num_attention_heads"]) == (4096, 11008, 32)
    assert (c["window_size"], c["chunk_size"], c["vocab_size"], c["num_pred_heads"]) == (2048, 16, 320, 8)
    assert (c["num_hidden_layers"], c["published"]["num_hidden_layers"]) == (4, 32)
    names = {m["name"] for m in cell.per_layer}
    assert {"flash_ms", "flash_roofline", "eva_remote_ms", "eva_pool_ms", "eva_merge_ms",
            "eva_remote_mass", "lm_mixer_ms", "lm_scope_unplaced_ms"} <= names
    assert not {"ssd_ms", "kda_ms", "moe_routed_ms"} & names
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s", "setup_s"}
    assert set(cell.limits) == {"loss_step1_rel", "loss_step2_rel", "loss_step3_rel",
                                "grad_norm_worst_leaf", "dparam_norm_worst_leaf"}
    # every published key of the catalog's row is in the file under its own name
    for key, value in {"attention_class": "eva", "rope_theta": 100000, "rms_norm_eps": 1e-5,
                       "max_position_embeddings": 32768, "num_key_value_heads": 32,
                       "model_type": "evabyte", "fp32_logits": True, "init_std": 0.01275}.items():
        assert c[key] == value
