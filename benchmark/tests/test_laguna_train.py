"""The new cell through the `lm_config_train` kind at a tiny size on the
CPU: the driver names no model, so the family, its fourth value (the routing
counters and the gate's in one dict) and the comparison ride the kind as the
other cells' do. `correct` goes false for each of the cell's two blind-spot
controls: the sliding layers run plain causal (`window_ignored`), the gate on
the attention output left out (`gate_left_out`)."""

import json

import pytest

from benchmark import run, spec

CELL = "laguna_train_b1s8192_ep32share"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TINY_CONFIG = dict(
    vocab_size=97, hidden_size=64, intermediate_size=128, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, num_experts=32, num_experts_per_tok=10,
    moe_intermediate_size=32, shared_expert_intermediate_size=32, sliding_window=24,
    num_attention_heads_per_layer=[4, 6, 6, 6, 4], experts_held=8,
    rope_parameters={
        "full_attention": {"rope_theta": 100, "rope_type": "yarn", "factor": 8,
                           "original_max_position_embeddings": 32, "beta_slow": 1,
                           "beta_fast": 4, "attention_factor": 1.2,
                           "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}})
# the cell's own rate would move nothing a float32 run on the CPU can read
# against its rounding: the tiny cell trains at 1e-3
TINY_TRAFFIC = dict(batch_rows=2, seq_len=80, attention_impl="naive", corpus_rows=16,
                    dtype="float32", lr=1e-3)


@pytest.fixture
def tiny_cell():
    cell = spec.load_cell(CELL)
    cell.config.update(TINY_CONFIG)
    cell.traffic.update(TINY_TRAFFIC)
    # float32 on the CPU against the reference reads at most 1e-5 in every
    # number over these seeds; each fault reads far above them
    cell.limits = {"loss_step1_rel": 3e-5, "loss_step2_rel": 3e-5, "loss_step3_rel": 3e-5,
                   "grad_norm_worst_leaf": 1e-3, "dparam_norm_worst_leaf": 1e-3}
    return cell


def _run(cell, capsys, trace=0, seconds=0.3, seed=2 ** 31 + 11):
    import jax

    rc = run.run_cell(cell, seed, seconds, trace, jax.devices()[: cell.chips], PEAKS)
    return rc, capsys.readouterr().out.strip().splitlines()


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 11])
def test_last_line_is_correct_and_both_groups_of_counters_ride_the_step(tiny_cell, capsys, seed):
    rc, lines = _run(tiny_cell, capsys, trace=1, seed=seed)
    assert rc == 0
    last = json.loads(lines[-1])
    assert set(last) == RESULT_KEYS | {"breakdown"}
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    # no device plane on the CPU: the trace readers leave their metrics out,
    # the counters' readers find the step's own numbers
    assert {"compile_s", "moe_rows_max_over_mean", "moe_rows_here_traced"} <= set(last["metrics"])
    assert not {"swa_flash_ms", "swa_flash_roofline", "flash_ms", "moe_routed_ms"} & set(
        last["metrics"])


@pytest.mark.parametrize("fault", ["window_ignored", "gate_left_out"])
def test_a_blind_spot_control_is_not_correct(tiny_cell, capsys, monkeypatch, fault):
    import jax
    import jax.numpy as jnp

    from ps_pytorch_tpu.models import swa_moe

    if fault == "window_ignored":
        monkeypatch.setattr(swa_moe.SwaMoeConfig, "mask", lambda self, kind: True)
    else:
        monkeypatch.setattr(jax.nn, "sigmoid", lambda x: jnp.ones_like(x)
                            if x.ndim == 3 and x.shape[-1] in (4, 6) else jax.lax.logistic(x))
    rc, lines = _run(tiny_cell, capsys)
    assert rc == 0 and json.loads(lines[-1])["correct"] is False
