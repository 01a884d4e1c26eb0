"""The new cell through the `lm_config_train` kind at a tiny size on the
CPU: the driver names no model, so the family, its fourth value (the scan's
counter) and the comparison ride the kind as the kanana cell's do. `correct`
goes false when the scan forgets its carried state only where the decay
parameters let the state live (benchmark/weights.py makes them zero: PERF.md
section 7), so the broken step here is one whose conv taps are reversed."""

import json

import pytest

from benchmark import run, spec

CELL = "granite4hm_train_remat_1period"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TINY_CONFIG = dict(
    vocab_size=101, hidden_size=64, num_hidden_layers=4,
    layer_types=["mamba", "mamba", "attention", "mamba"], num_attention_heads=4,
    num_key_value_heads=2, shared_intermediate_size=128, mamba_n_heads=4, mamba_d_head=16,
    mamba_d_state=16, mamba_chunk_size=16, mamba_expand=1, attention_multiplier=0.0625)
TINY_TRAFFIC = dict(batch_rows=2, seq_len=48, attention_impl="naive", corpus_rows=16,
                    dtype="float32")


@pytest.fixture
def tiny_cell():
    cell = spec.load_cell(CELL)
    cell.config.update(TINY_CONFIG)
    cell.traffic.update(TINY_TRAFFIC)
    # float32 on the CPU against the reference reads at most 2e-6 in every
    # number over seeds 5, 6, 2**31+11; the reversed taps read far above them
    cell.limits = {"loss_step1_rel": 3e-5, "loss_step2_rel": 3e-5, "loss_step3_rel": 3e-5,
                   "grad_norm_worst_leaf": 1e-3, "dparam_norm_worst_leaf": 1e-3}
    return cell


def _run(cell, capsys, trace=0, seconds=0.3, seed=2 ** 31 + 11):
    import jax

    rc = run.run_cell(cell, seed, seconds, trace, jax.devices()[: cell.chips], PEAKS)
    return rc, capsys.readouterr().out.strip().splitlines()


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 11])
def test_last_line_is_correct_and_the_counter_rides_the_step(tiny_cell, capsys, seed):
    rc, lines = _run(tiny_cell, capsys, trace=1, seed=seed)
    assert rc == 0
    last = json.loads(lines[-1])
    assert set(last) == RESULT_KEYS | {"breakdown"}
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    # no device plane on the CPU: the trace readers leave their metrics out
    assert "compile_s" in last["metrics"]
    assert not {"ssd_ms", "ssd_roofline", "flash_ms"} & set(last["metrics"])


def test_a_conv_with_its_taps_reversed_is_not_correct(tiny_cell, capsys, monkeypatch):
    from ps_pytorch_tpu.models import ssm_hybrid

    sound = ssm_hybrid._causal_conv
    monkeypatch.setattr(ssm_hybrid, "_causal_conv",
                        lambda x, w, bias: sound(x, w[::-1], bias))
    rc, lines = _run(tiny_cell, capsys)
    assert rc == 0 and json.loads(lines[-1])["correct"] is False
