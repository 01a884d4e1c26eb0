"""flops/kanana2.py against counts made by hand at the published sizes."""

import json
import os

import pytest

from benchmark import flops, spec

TRAFFIC = {"batch_rows": 2, "seq_len": 8192}


def _cfg():
    with open(os.path.join(spec.BENCH_DIR, "configs", "kanana2_30b_a3b_ep8.json")) as f:
        return json.load(f)


def test_parameters_by_hand():
    cfg = _cfg()
    k = flops.load(cfg["flops"])
    att = 2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 32 * 128 * 2048
    assert k.attention_params(cfg) == att == 26_345_472
    assert k.routed_rows_share(cfg) == 0.75
    dense = att + 3 * 2048 * 6144
    sparse = att + 2048 * 128 + 3 * 2048 * 1536 + 0.75 * 3 * 2048 * 768
    assert k.active_matmul_params(cfg) == dense + 4 * sparse + 2048 * 16032
    # what the chip holds, every leaf: the file's `parameters`
    norms = 2 * 2048 + 512
    held = (att + norms + 3 * 2048 * 6144) + 4 * (
        att + norms + 2048 * 128 + 128 + 3 * 2048 * 1536 + 16 * 3 * 2048 * 768
    ) + 2 * 16032 * 2048 + 2048
    assert cfg["parameters"] == held == 575_955_968


def test_train_flops_per_token_by_hand():
    cfg = _cfg()
    k = flops.load(cfg["flops"])
    forward = 2 * k.active_matmul_params(cfg) + 5 * 2 * 4096 * 32 * (192 + 128)
    assert k.train_flops_per_item(cfg, TRAFFIC) == 3 * forward
    assert forward == pytest.approx(0.93e9, rel=5e-3)      # ISSUE 27's 0.93 GFLOP a token


def test_flash_step_by_hand():
    w = flops.load("kanana2").flash_train_step(_cfg(), TRAFFIC)
    unit = 2 * 32 * 8192 * 8192               # B * H * T * T: 2 ops over half the square
    assert w["flops"] == 5 * unit * (320 + 512 + 640)
    qk, vo = 2 * 8192 * 32 * 192 * 2, 2 * 8192 * 32 * 128 * 2
    assert w["bytes"] == 5 * ((2 * qk + 2 * vo) + (2 * qk + 3 * vo) + (2 * qk + vo))
    # at the v5e's peak the kernels of one step need at least 160 ms
    assert w["flops"] / 197e12 == pytest.approx(0.1605, rel=1e-2)


def test_moe_routed_step_by_hand():
    k = flops.load("kanana2")
    w = k.moe_routed_train_step(_cfg(), TRAFFIC)
    rows = 16384 * 6 * 16 / 128
    assert rows == 12288
    assert w["flops"] == 4 * 3 * 2 * rows * 3 * 2048 * 768
    assert w["bytes"] == 4 * (9 * rows * (2048 + 768) * 2 + 16 * 3 * 2048 * 768 * 4)
    # 1.39 TFLOP a step: 7.1 ms at the peak; the 3.7 GB need 4.5 ms
    assert w["flops"] / 197e12 == pytest.approx(7.06e-3, rel=1e-2)
    assert w["bytes"] / 819e9 == pytest.approx(4.52e-3, rel=1e-2)
    # the rows the step counted, summed over the layers, in uniform routing's place
    assert k.moe_routed_train_step(_cfg(), TRAFFIC, {"moe_rows_here_traced": 4 * rows}) == w
    none = k.moe_routed_train_step(_cfg(), TRAFFIC, {"moe_rows_here_traced": 0.0})
    assert none["flops"] == 0 and none["bytes"] == 4 * 16 * 3 * 2048 * 768 * 4
