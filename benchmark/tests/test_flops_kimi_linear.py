"""flops/kimi_linear.py against counts made by hand at the published sizes,
the parameter count of configs/kimi_linear_48b_a3b_ep32.json against the
reference's shapes, and the cell as BENCHMARK.json declares it."""

import json
import math
import os
import re

import pytest

from benchmark import flops, reference, spec

CELL = "kimilinear_train_b2s8192_ep32share"
TRAFFIC = {"batch_rows": 2, "seq_len": 8192}


def _cfg():
    with open(os.path.join(spec.BENCH_DIR, "configs", "kimi_linear_48b_a3b_ep32.json")) as f:
        return json.load(f)


def test_parameters_by_hand():
    cfg = _cfg()
    k = flops.load(cfg["flops"])
    # q, k, v, o 2304 x 4096 each; two low-rank pairs through 128; beta a head
    kda = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    assert k.kda_matmul_params(cfg) == kda == 39_460_864
    mla = 2304 * 32 * 192 + 2304 * (512 + 64) + 512 * 32 * 256 + 32 * 128 * 2304
    assert k.mla_matmul_params(cfg) == mla == 29_114_368
    assert k.routed_rows_share(cfg) == 8 * 8 / 256 == 0.25
    expert, dense = 3 * 2304 * 1024, 3 * 2304 * 9216
    sparse = 2304 * 256 + expert + 0.25 * expert
    assert k.active_matmul_params(cfg) == 4 * kda + mla + dense + 4 * sparse + 2304 * 20480
    # what the chip holds, every leaf: the file's `parameters` (ISSUE 33's arithmetic)
    kda_mixer = kda + 3 * 4 * 4096 + 32 + 4096 + 128         # taps, A_log, dt_bias, the norm's gain
    experts = 2304 * 256 + 256 + expert + 8 * expert            # router, its bias, shared, eight held
    held = (4 * kda_mixer + (mla + 512) + 5 * 2 * 2304 + dense + 4 * experts
            + 2 * 20480 * 2304 + 2304)
    assert cfg["parameters"] == held == 602_434_432
    assert kda_mixer + 2 * 2304 + dense == 103_219_872         # layer 1
    assert kda_mixer + 2 * 2304 + experts == 103_809_952       # a KDA expert layer
    assert mla + 512 + 2 * 2304 + experts == 93_410_560        # the MLA expert layer
    shapes = reference.load(cfg["reference"]).param_shapes(cfg)
    import jax

    assert sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes)) == held


def test_train_flops_per_token_by_hand():
    cfg = _cfg()
    k = flops.load(cfg["flops"])
    # a head, chunks of 64, 128 wide: scores 32,768; inverse 2,730.7; W and
    # U_0 16,384 each; three state products 98,304; outputs 16,384
    head = 4 * 64 * 128 + 2 * 64 * 64 / 3 + 2 * 64 * 128 + 2 * 64 * 128 + 6 * 128 * 128 + 2 * 64 * 128
    assert k.kda_flops_per_token(cfg) == pytest.approx(32 * head) == pytest.approx(5_854_549.3)
    forward = 2 * k.active_matmul_params(cfg) + 4 * 32 * head + 2 * 4096 * 32 * (192 + 128)
    assert k.train_flops_per_item(cfg, TRAFFIC) == pytest.approx(3 * forward)
    # about 38 TFLOP of model work a step of 16,384 tokens
    assert 16384 * 3 * forward == pytest.approx(38.3e12, rel=5e-3)


def test_kda_step_by_hand():
    w = flops.load("kimi_linear").kda_train_step(_cfg(), TRAFFIC)
    assert w["flops"] == pytest.approx(4 * 16384 * 3 * 5_854_549.3, rel=1e-6)
    per_token = 32 * (3 * 128 * 2 + 128 * 4 + 4 + 128 * 4)     # q, k, v; g; beta; o
    assert per_token == 57_472 and w["bytes"] == 4 * 16384 * 2 * per_token
    # 1.15 TFLOP: 5.8 ms at the peak; the 7.5 GB need 9.2 ms: the memory bounds it
    assert w["flops"] / 197e12 == pytest.approx(5.84e-3, rel=1e-2)
    assert w["bytes"] / 819e9 == pytest.approx(9.20e-3, rel=1e-2)
    assert w["peak"] == "bf16_flops_per_s"


def test_flash_step_by_hand():
    w = flops.load("kimi_linear").flash_train_step(_cfg(), TRAFFIC)
    half = 2 * 2 * 32 * 8192 * 8192 / 2
    assert w["flops"] == half * (320 + 512 + 640)               # ONE latent attention layer
    qk, vo = 2 * 8192 * 32 * 192 * 2, 2 * 8192 * 32 * 128 * 2
    assert w["bytes"] == 6 * qk + 6 * vo
    # a fifth of the kanana cell's five layers at the same widths and tokens
    kanana = flops.load("kanana2").flash_train_step(
        json.load(open(os.path.join(spec.BENCH_DIR, "configs", "kanana2_30b_a3b_ep8.json"))), TRAFFIC)
    assert w["flops"] == kanana["flops"] / 5 and w["bytes"] == kanana["bytes"] / 5


def test_routed_step_by_hand_and_by_the_runs_counter():
    k = flops.load("kimi_linear")
    cfg = _cfg()
    uniform = k.moe_routed_train_step(cfg, TRAFFIC)
    rows = 4 * 16384 * 0.25                                     # 4,096 a layer: 512 an expert
    assert uniform["flops"] == 3 * 2 * rows * 3 * 2304 * 1024
    assert uniform["bytes"] == 9 * rows * (2304 + 1024) * 2 + 4 * 8 * 3 * 2304 * 1024 * 4
    counted = k.moe_routed_train_step(cfg, TRAFFIC, {"moe_rows_here_traced": 1000.0})
    assert counted["flops"] == 3 * 2 * 1000 * 3 * 2304 * 1024
    assert counted["bytes"] == 9 * 1000 * (2304 + 1024) * 2 + 4 * 8 * 3 * 2304 * 1024 * 4


def test_the_cell_is_as_declared():
    cell = spec.load_cell(CELL)
    t, c = cell.traffic, cell.config
    assert cell.kind == "lm_config_train" and cell.chips == 1
    assert cell.traffic_name == "lm_b2s8192_flash_adam_remat"    # the kanana cell's file, unedited
    assert (t["batch_rows"], t["seq_len"], t["block_steps"], t["check_steps"]) == (2, 8192, 2, 3)
    assert (t["attention_impl"], t["dtype"], t["remat"]) == ("flash", "bfloat16", True)
    assert (t["optimizer"], t["lr"], t["b1"], t["b2"], t["eps"]) == ("adam", 3e-4, 0.9, 0.999, 1e-8)
    assert (t["num_dp"], t["num_sp"], t["control_operand"]) == (1, 1, "float8_e4m3fn")
    assert (c["hidden_size"], c["intermediate_size"], c["moe_intermediate_size"]) == (2304, 9216, 1024)
    assert (c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
            c["num_attention_heads"], c["mla_use_nope"]) == (512, 128, 64, 128, 32, True)
    lin = c["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert (lin["kda_layers"], lin["full_attn_layers"]) == ([1, 2, 3, 5], [4])
    assert (c["num_experts"], c["num_experts_per_token"], c["num_shared_experts"],
            c["routed_scaling_factor"], c["experts_held"], c["expert_offset"]) == (256, 8, 1, 2.446, 8, 0)
    assert (c["num_hidden_layers"], c["vocab_size"], c["first_k_dense_replace"]) == (5, 20480, 1)
    assert c["reduced"] == ["num_hidden_layers", "linear_attn_config", "experts_held", "vocab_size"]
    names = {m["name"] for m in cell.per_layer}
    assert {"lm_step_device_ms", "flash_ms", "flash_roofline", "kda_ms", "kda_roofline",
            "moe_routed_ms", "moe_routed_roofline", "moe_rows_max_over_mean",
            "moe_rows_here_traced", "lm_device_idle_pct", "lm_peak_hbm_gib", "compile_s"} <= names
    # `moe_buffer_ms` reads the kanana buffers' row counts; this cell's has 131,072
    assert not {n for n in names if n.startswith(("ssd_", "ps_"))} and "moe_buffer_ms" not in names
    assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s", "setup_s"]
    assert set(cell.limits) == {"loss_step1_rel", "loss_step2_rel", "loss_step3_rel",
                                "grad_norm_worst_leaf", "dparam_norm_worst_leaf"}


def test_every_declaration_keeps_the_files_form():
    """What the driver refuses before any run (it refused this cell's configuration
    once for a `why` of 202 characters): every line at most 200 printable
    characters, every name at most 64 of the allowed ones, no key but the known."""
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
    line = lambda s: 1 <= len(s) <= 200 and s.isprintable()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}, c["name"]
        assert name.fullmatch(c["name"]) and line(c["source"]) and line(c["why"]), c["name"]
        assert len(c["reduced"]) <= 16 and all(name.fullmatch(k) for k in c["reduced"]), c["name"]
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}, w["name"]
        assert name.fullmatch(w["name"]) and name.fullmatch(w["traffic"]) and line(w["why"]), w["name"]
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}, m["name"]
        assert name.fullmatch(m["name"]) and line(m["layer"]), m["name"]
        assert re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", m["unit"]), m["name"]
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_the_configuration_holds_the_catalogs_numbers():
    """Every number of the source's config.json under its own key; only the
    keys in `reduced` differ (the catalog row, where the guide is here), and
    inside the reduced group only its two lists."""
    cat = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(cat):
        pytest.skip("the catalog is not on this machine")
    with open(cat) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    cfg = _cfg()
    assert cfg["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if cfg.get(k, "absent") != v}
    assert differ | {"experts_held"} == set(cfg["reduced"])
    want, have = row["config"]["linear_attn_config"], cfg["linear_attn_config"]
    assert {k for k in want if want[k] != have[k]} == {"kda_layers", "full_attn_layers"}
    keep = lambda layers: [i for i in layers if i <= 5]
    assert have["kda_layers"] == keep(want["kda_layers"])
    assert have["full_attn_layers"] == keep(want["full_attn_layers"])
