"""A cell, a traffic mix, a configuration and a per-layer metric are added
as new files plus one entry each; nothing that exists is edited."""

import glob
import hashlib
import json
import os
import sys
import types

import pytest

from benchmark import flops, run, spec
from benchmark.tests.conftest import edit_json


def _digest(root):
    out = {}
    for d, _, files in os.walk(root / "benchmark"):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[p] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_add_cell_mix_config_and_metric_as_files(tiny_root):
    before = _digest(tiny_root)
    b = tiny_root / "benchmark"
    (b / "configs" / "gpt2_small.json").write_text(json.dumps(
        {"name": "gpt2_small", "reference": "gpt2_medium", "flops": "gpt2", "n_layer": 12,
         "n_embd": 768, "n_head": 12, "n_positions": 1024, "vocab_size": 50257,
         "mlp_ratio": 4, "reduced": []}))
    mix = json.loads((b / "traffic" / "lm_b8s1024_flash_adam.json").read_text())
    mix["batch_rows"] = 16
    (b / "traffic" / "lm_b16s1024.json").write_text(json.dumps(mix))
    (b / "workloads" / "gpt2s_train_b16.json").write_text(json.dumps(
        {"limits": {"loss_step1_rel": 1e-3}}))
    (b / "layer_metrics" / "mlp_ms.json").write_text(json.dumps(
        {"kind": "scope_time", "args": {"pattern": "w_up|w_down"}}))

    def add(bench):
        bench["configs"].append({"name": "gpt2_small", "source": "x", "reduced": [],
                                 "file": "benchmark/configs/gpt2_small.json", "why": "y"})
        bench["workloads"].append({"name": "gpt2s_train_b16", "config": "gpt2_small",
                                   "traffic": "lm_b16s1024", "chips": 1, "why": "z"})
        bench["per_layer"].append({"name": "mlp_ms", "unit": "ms", "better": "lower",
                                   "source": "device_trace", "layer": "Kernels",
                                   "moves": "train_tokens_per_s",
                                   "workloads": ["gpt2s_train_b16"]})
        for m in bench["end_to_end"]:
            if m["name"] == "train_tokens_per_s":
                m["workloads"].append("gpt2s_train_b16")

    edit_json(tiny_root / "BENCHMARK.json", add)
    cell = spec.load_cell("gpt2s_train_b16", root=str(tiny_root))
    assert cell.kind == "lm_train" and cell.config["n_embd"] == 768
    assert cell.traffic["batch_rows"] == 16
    names = [m["name"] for m in cell.per_layer]
    assert "mlp_ms" in names and "compile_s" in names and "flash_ms" not in names
    assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s", "setup_s"]
    after = _digest(tiny_root)
    assert all(after[p] == h for p, h in before.items())   # nothing edited
    # the cells that were there read as before
    old = spec.load_cell("gpt2m_train_b8s1024", root=str(tiny_root))
    assert "mlp_ms" not in [m["name"] for m in old.per_layer]
    # the added cell's utilization line is worked out from its own sizes
    peaks = {"bf16_flops_per_s": 197e12}
    per_token = 3 * (2 * (12 * 12 * 768 * 768 + 50257 * 768) + 12 * 2 * 1024 * 768)
    assert run.model_flop_utilization_pct(cell, 1000.0, 1, peaks) == pytest.approx(
        100 * 1000.0 * per_token / 197e12)


def test_an_added_architecture_brings_its_ops_module_and_no_driver_names_a_model(
        tiny_root, monkeypatch):
    """VGG-16 on the `ps_train` kind: configs/vgg16.json names flops/vgg16.py
    (a new file; stood in for here by a module put under that name), and the
    utilization line of an untraced run follows without an edit to a driver,
    to run.py or to another architecture's module."""
    vgg = types.ModuleType("benchmark.flops.vgg16")
    vgg.train_flops_per_item = lambda config, traffic: 3 * 2 * config["forward_macs"]
    monkeypatch.setitem(sys.modules, "benchmark.flops.vgg16", vgg)
    b = tiny_root / "benchmark"
    (b / "configs" / "vgg16_cifar10.json").write_text(json.dumps(
        {"name": "vgg16_cifar10", "reference": "vgg16_cifar10", "flops": "vgg16",
         "forward_macs": 313_000_000, "parameters": 14_700_000, "reduced": []}))
    (b / "workloads" / "vgg16_b2048_1chip.json").write_text(json.dumps({"limits": {}}))

    def add(bench):
        bench["configs"].append({"name": "vgg16_cifar10", "source": "x", "reduced": [],
                                 "file": "benchmark/configs/vgg16_cifar10.json", "why": "y"})
        bench["workloads"].append({"name": "vgg16_b2048_1chip", "config": "vgg16_cifar10",
                                   "traffic": "ps_b2048", "chips": 1, "why": "z"})

    edit_json(tiny_root / "BENCHMARK.json", add)
    cell = spec.load_cell("vgg16_b2048_1chip", root=str(tiny_root))
    assert cell.kind == "ps_train"
    got = run.model_flop_utilization_pct(cell, 20000.0, 1, {"bf16_flops_per_s": 197e12})
    assert got == pytest.approx(100 * 20000.0 * 6 * 313e6 / 197e12)
    # the wire's kernel work needs only the configuration's `parameters`
    assert flops.load("wire").ps_quantize_step(cell.config, cell.traffic)["bytes"] == 5 * 14_700_000
    for path in glob.glob(os.path.join(spec.BENCH_DIR, "drivers", "*.py")) + [
            os.path.join(spec.BENCH_DIR, "run.py"), os.path.join(spec.BENCH_DIR, "spec.py")]:
        with open(path) as f:
            text = f.read().lower()
        assert not [m for m in ("resnet", "gpt2", "vgg") if m in text], path


def test_every_declared_metric_has_its_reader_and_cells_exist():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    readers = {os.path.basename(p)[:-5] for p in spec.all_layer_metric_files()}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["name"] in readers and m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.per_layer and len(cell.end_to_end) >= 2
        assert sum(c["chips"] == 4 for c in bench["workloads"]) == 1
