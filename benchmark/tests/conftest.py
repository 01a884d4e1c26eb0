"""Tests of the benchmark's own code. They run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

The repo's tier-1 command collects tests/ only; this directory is the
benchmark's and is run by hand (PERF.md says so).
"""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_LM_CONFIG = {"n_layer": 2, "n_embd": 64, "n_head": 4, "n_positions": 32,
                  "vocab_size": 101}
TINY_LM_TRAFFIC = {"batch_rows": 4, "seq_len": 32, "attention_impl": "naive",
                   "corpus_rows": 16}


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout-shaped directory whose data files are copies of the real
    ones, so a test can add to them without touching the repo's."""
    bench = tmp_path / "benchmark"
    for sub in ("configs", "traffic", "workloads", "layer_metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmark", sub), bench / sub)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    return tmp_path


def edit_json(path, fn):
    with open(path) as f:
        data = json.load(f)
    fn(data)
    with open(path, "w") as f:
        json.dump(data, f)


@pytest.fixture
def tiny_lm_cell():
    from benchmark import spec

    cell = spec.load_cell("gpt2m_train_b8s1024")
    cell.config.update(TINY_LM_CONFIG)
    cell.traffic.update(TINY_LM_TRAFFIC)
    # limits for this size, set as the cell's own were: float32 on the CPU
    # against the reference reads at most loss 9e-5, grad 2.3e-3, dparam
    # 1.8e-3 over seeds 5, 6, 2**31+11; the float8 control at least grad
    # 1.8e-2, dparam 5.2e-3
    cell.limits = {"loss_step1_rel": 3e-4, "loss_step2_rel": 3e-4, "loss_step3_rel": 3e-4,
                   "grad_norm_worst_leaf": 8e-3, "dparam_norm_worst_leaf": 4e-3}
    return cell
