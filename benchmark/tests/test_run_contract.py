"""The run's contract, driven at tiny sizes on the CPU with the harness's
look for a chip skipped: the last stdout line, `correct` going false when
the timed path is broken underneath, and the control failing its limit."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import compare, drivers, run, spec
from benchmark.tests.conftest import ROOT

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(cell, capsys, trace=0, seconds=1.0, seed=2 ** 31 + 11):
    import jax

    rc = run.run_cell(cell, seed, seconds, trace, jax.devices()[: cell.chips], PEAKS)
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, lines


def test_no_chip_is_an_error_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "gpt2m_train_b8s1024",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "no accelerator" in p.stderr


def test_last_line_holds_the_contract_keys(tiny_lm_cell, capsys):
    rc, lines = _run(tiny_lm_cell, capsys)
    assert rc == 0
    last = json.loads(lines[-1])
    assert set(last) == RESULT_KEYS
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert set(last["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(set(v) == {"value", "unit"} for v in last["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(last["device"])
    tags = [ln.split()[1] for ln in lines[:-1] if ln.startswith("[bench]")]
    assert {"blocks", "compared", "warmup_block_s", "model_flop_utilization_pct"} <= set(tags)
    blocks = json.loads(next(ln for ln in lines if ln.startswith("[bench] blocks")).split(" ", 2)[2])
    assert blocks["blocks"] == len(blocks["block_rates"]) >= 1
    # the metric is all the window's work over all of its time
    assert last["metrics"]["train_tokens_per_s"]["value"] == blocks["window_rate"]
    assert blocks["window_rate"] == pytest.approx(
        blocks["blocks"] * 4 * 32 * 3 / blocks["window_s"])


def test_traced_line_holds_per_layer_metrics(tiny_lm_cell, capsys):
    rc, lines = _run(tiny_lm_cell, capsys, trace=1)
    assert rc == 0
    last = json.loads(lines[-1])
    assert set(last) == RESULT_KEYS | {"breakdown"}
    assert {"busy_s", "window_s"} <= set(last["device"])
    # on the CPU there is no device plane: the trace readers find nothing
    # and leave their metrics out; the counters are there
    assert "compile_s" in last["metrics"] and "train_tokens_per_s" not in last["metrics"]


def test_step_that_returns_its_state_unchanged_is_not_correct(tiny_lm_cell, capsys, monkeypatch):
    from benchmark.drivers import lm_train

    real = lm_train._Session.__init__

    def broken(self, *a, **kw):
        real(self, *a, **kw)
        step = self._step
        self._step = lambda p, o, tok: (p, o, step(
            *__import__("jax").tree_util.tree_map(lambda x: x + 0, (p, o)), tok)[2])

    monkeypatch.setattr(lm_train._Session, "__init__", broken)
    rc, lines = _run(tiny_lm_cell, capsys)
    last = json.loads(lines[-1])
    assert rc == 0 and last["correct"] is False
    rows = [json.loads(ln.split(" ", 2)[2]) for ln in lines if ln.startswith("[bench] compared")]
    bad = {r["number"] for r in rows if not r["ok"]}
    assert "dparam_norm_worst_leaf" in bad


def test_part_of_the_batch_left_out_is_not_correct(tiny_lm_cell, capsys, monkeypatch):
    from benchmark.drivers import lm_train

    real = lm_train._Session.__init__

    def broken(self, *a, **kw):
        real(self, *a, **kw)
        put = self._put
        self._put = lambda tok: put(__import__("numpy").concatenate([tok[:2], tok[:2]]))

    monkeypatch.setattr(lm_train._Session, "__init__", broken)
    rc, lines = _run(tiny_lm_cell, capsys)
    assert json.loads(lines[-1])["correct"] is False


@pytest.mark.parametrize("seed", [5, 6, 2 ** 31 + 11])
def test_the_lower_precision_control_fails_a_limit(tiny_lm_cell, seed):
    """The control (the reference with float8 operands in the program's
    place) on three seeds at test size; on the chip at the cell's own size
    the readings are in workloads/<cell>.json."""
    ctx = {"out_dir": None, "compiles": None}
    check = drivers.load("lm_train").check
    sound = compare.training_numbers(*check(tiny_lm_cell, seed, False, ctx))
    control = compare.training_numbers(*check(tiny_lm_cell, seed, True, ctx))
    assert compare.decide(sound, tiny_lm_cell.limits)[0] is True
    ok, rows = compare.decide(control, tiny_lm_cell.limits)
    assert ok is False
    assert not next(r for r in rows if r["number"] == "grad_norm_worst_leaf")["ok"]
    assert control["grad_norm_worst_leaf"] > 3 * sound["grad_norm_worst_leaf"]


def test_ps_trainer_with_its_step_broken_is_not_correct(capsys, monkeypatch):
    from benchmark.drivers import ps_train

    cell = spec.load_cell("resnet18_b2048_1chip")
    argv = cell.traffic["argv"]
    argv[argv.index("--batch-size") + 1] = "4"
    cell.traffic.update(train_rows=32, block_steps=2)
    real = ps_train._Session.__init__

    def broken(self, *a, **kw):
        real(self, *a, **kw)
        step = self.trainer._train_step

        def unchanged(state, batch, key, *extra):
            import jax

            new, metrics = step(jax.tree_util.tree_map(lambda x: x + 0, state),
                                batch, key, *extra)
            return state.replace(step=new.step), metrics

        self.trainer._train_step = unchanged

    monkeypatch.setattr(ps_train._Session, "__init__", broken)
    rc, lines = _run(cell, capsys)
    last = json.loads(lines[-1])
    assert rc == 0 and set(last) == RESULT_KEYS and last["correct"] is False
    assert set(last["metrics"]) == {"train_images_per_s", "setup_s"}


def test_a_directory_without_the_program_is_an_error(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "resnet18_b2048_1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "ps_pytorch_tpu" in p.stderr
