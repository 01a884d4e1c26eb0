"""Each ops/bytes function against a count made by hand."""

import json
import os

import pytest

from benchmark import flops, spec

CFG = os.path.join(spec.BENCH_DIR, "configs")


def _cfg(name):
    with open(os.path.join(CFG, name + ".json")) as f:
        return json.load(f)


def test_resnet18_forward_macs_by_hand():
    # stem 32*32*27*64; stage 1: four 3x3 convs 64->64 at 32x32; stage 2:
    # 64->128 at 16x16 (+1x1 shortcut), then three 128->128; stages 3, 4 alike
    stem = 32 * 32 * 27 * 64
    s1 = 4 * 32 * 32 * 9 * 64 * 64
    s2 = 16 * 16 * (9 * 64 * 128 + 64 * 128) + 3 * 16 * 16 * 9 * 128 * 128
    s3 = 8 * 8 * (9 * 128 * 256 + 128 * 256) + 3 * 8 * 8 * 9 * 256 * 256
    s4 = 4 * 4 * (9 * 256 * 512 + 256 * 512) + 3 * 4 * 4 * 9 * 512 * 512
    want = stem + s1 + s2 + s3 + s4 + 512 * 10
    cfg = _cfg("resnet18_cifar10")
    resnet = flops.load(cfg["flops"])
    assert resnet.forward_macs_per_image(cfg) == want == 555_422_720
    assert resnet.train_flops_per_item(cfg) == 6 * want


def test_gpt2_medium_flops_per_token_by_hand():
    cfg = _cfg("gpt2_medium")
    gpt2 = flops.load(cfg["flops"])
    d = 1024
    matmul = 24 * 12 * d * d + 50257 * d
    assert gpt2.matmul_params(cfg) == matmul == 353_453_056
    want = 3 * (2 * matmul + 24 * 2 * 1024 * d)
    assert gpt2.train_flops_per_item(cfg, {"seq_len": 1024}) == want
    assert want == pytest.approx(2.2717e9, rel=1e-4)


def test_flash_step_by_hand():
    w = flops.load("gpt2").flash_train_step(
        _cfg("gpt2_medium"), {"batch_rows": 8, "seq_len": 1024})
    one_product_half = 8 * 16 * 1024 * 1024 * 64     # B*H*T*T*hd multiply-adds / 2 * 2
    assert w["flops"] == 24 * 9 * one_product_half
    assert w["bytes"] == 24 * 12 * 8 * 1024 * 1024 * 2
    # at the v5e's peak the kernels of one step need at least 9.4 ms
    assert w["flops"] / 197e12 == pytest.approx(9.42e-3, rel=1e-2)


def test_quantize_bytes_by_hand():
    w = flops.load("wire").ps_quantize_step(_cfg("resnet18_cifar10"), {})
    assert w["bytes"] == 11_173_962 * 4 + 11_173_962
    assert w["bytes"] / 819e9 == pytest.approx(6.8e-5, rel=1e-2)


def test_unknown_device_is_an_error():
    assert spec.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        spec.load_peaks("TPU v9000")
