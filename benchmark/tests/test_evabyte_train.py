"""The EvaByte cell through the `lm_config_train` kind at a tiny size on the
CPU: the driver names no model, so the family, its fourth value (the
attention's counters) and the comparison ride the kind as the other
published families' do. Three Adam steps of the program against
reference/evabyte_eva.py; the float8 control fails; so do a program whose
remote pass is left out and one whose pooling is flat (phi zeroed): the two
blind spots the cell's file records at the published widths."""

import json

import pytest

from benchmark import compare, drivers, run, spec

CELL = "evabyte_train_b1s16384_4layers"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TINY_CONFIG = dict(vocab_size=67, hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                   num_key_value_heads=4, intermediate_size=96, window_size=64, chunk_size=8,
                   num_pred_heads=3)
TINY_TRAFFIC = dict(batch_rows=2, seq_len=200, attention_impl="naive", corpus_rows=16,
                    dtype="float32")
# float32 on the CPU against the reference reads at most 3e-6 in every number
# over seeds 5 and 2**31+11; the control and the two broken programs read far above
LIMITS = {"loss_step1_rel": 3e-5, "loss_step2_rel": 3e-5, "loss_step3_rel": 3e-5,
          "grad_norm_worst_leaf": 1e-3, "dparam_norm_worst_leaf": 1e-3}


@pytest.fixture
def tiny_cell():
    cell = spec.load_cell(CELL)
    cell.config.update(TINY_CONFIG)
    cell.traffic.update(TINY_TRAFFIC)
    cell.limits = dict(LIMITS)
    return cell


def _run(cell, capsys, trace=0, seconds=0.3, seed=2 ** 31 + 11):
    import jax

    rc = run.run_cell(cell, seed, seconds, trace, jax.devices()[: cell.chips], PEAKS)
    return rc, capsys.readouterr().out.strip().splitlines()


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 11])
def test_last_line_is_correct_and_the_counters_ride_the_step(tiny_cell, capsys, seed):
    rc, lines = _run(tiny_cell, capsys, trace=1, seed=seed)
    assert rc == 0
    last = json.loads(lines[-1])
    assert set(last) == RESULT_KEYS | {"breakdown"}
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    # no device plane on the CPU: the trace readers leave their metrics out;
    # the counter's metric is read from the step
    assert "compile_s" in last["metrics"]
    assert not {"flash_ms", "flash_roofline", "eva_remote_ms"} & set(last["metrics"])
    assert 0.0 < last["metrics"]["eva_remote_mass"]["value"] < 1.0


def test_the_kernels_run_the_same_cell(tiny_cell, capsys, monkeypatch):
    """The cell's own options (flash kernels, interpreted here; remat) at
    float32: still `correct`, and the tiles counted are the plan's."""
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    tiny_cell.traffic.update(attention_impl="flash", remat=True, batch_rows=1)
    rc, lines = _run(tiny_cell, capsys)
    assert rc == 0 and json.loads(lines[-1])["correct"] is True


def test_the_float8_control_fails_a_limit(tiny_cell):
    import jax

    ctx = {"out_dir": None, "compiles": None, "devices": jax.devices()[:1]}
    prog, ref = drivers.load(tiny_cell.kind).check(tiny_cell, 5, True, ctx)
    ok, rows = compare.decide(compare.training_numbers(prog, ref), tiny_cell.limits)
    assert not ok and [r["number"] for r in rows if not r["ok"]]


def test_summaries_left_out_is_not_correct(tiny_cell, capsys, monkeypatch):
    """The program with no remote pass (the kernels' path, interpreted):
    every query sees its own window alone."""
    from ps_pytorch_tpu.ops import eva

    sound = eva.plan_eva
    monkeypatch.setattr(eva, "plan_eva",
                        lambda *a: sound(*a)._replace(remote=None, mask=None))
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    tiny_cell.traffic.update(attention_impl="flash", batch_rows=1)
    rc, lines = _run(tiny_cell, capsys)
    assert rc == 0 and json.loads(lines[-1])["correct"] is False


def test_pooling_flat_is_not_correct(tiny_cell, capsys, monkeypatch):
    """phi zeroed in the program: a chunk's summary is its plain mean."""
    from ps_pytorch_tpu.ops import eva

    sound = eva.eva_pool
    monkeypatch.setattr(eva, "eva_pool", lambda k, v, phi, *a: sound(k, v, 0.0 * phi, *a))
    rc, lines = _run(tiny_cell, capsys)
    assert rc == 0 and json.loads(lines[-1])["correct"] is False
