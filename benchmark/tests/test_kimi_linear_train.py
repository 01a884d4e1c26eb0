"""The new cell through the `lm_config_train` kind at a tiny size on the
CPU: the driver names no model, so the family, its fourth value (the routing
counters and the delta rule's counter in one dict) and the comparison ride
the kind as the kanana and granite cells' do. `correct` goes false when the
triangular system inside a chunk is left unsolved (the corrections taken as
beta (V - K S_0), each token blind to its chunk's earlier ones): that acts
at any decay, the carried state only where the decay parameters let it live
(benchmark/weights.py makes them zero: PERF.md section 7)."""

import json

import pytest

from benchmark import run, spec

CELL = "kimilinear_train_b2s8192_ep32share"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TINY_CONFIG = dict(
    vocab_size=101, hidden_size=64, num_hidden_layers=5, num_attention_heads=4,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=128, moe_intermediate_size=32, num_experts=16, num_experts_per_token=3,
    experts_held=8, kda_chunk_size=16,
    linear_attn_config={"kda_layers": [1, 2, 3, 5], "full_attn_layers": [4], "num_heads": 4,
                        "head_dim": 16, "short_conv_kernel_size": 4})
TINY_TRAFFIC = dict(batch_rows=2, seq_len=48, attention_impl="naive", corpus_rows=16,
                    dtype="float32")


@pytest.fixture
def tiny_cell():
    cell = spec.load_cell(CELL)
    cell.config.update(TINY_CONFIG)
    cell.traffic.update(TINY_TRAFFIC)
    # float32 on the CPU against the reference reads at most 3e-6 in every
    # number over these seeds; the unsolved system reads far above them
    cell.limits = {"loss_step1_rel": 3e-5, "loss_step2_rel": 3e-5, "loss_step3_rel": 3e-5,
                   "grad_norm_worst_leaf": 1e-3, "dparam_norm_worst_leaf": 1e-3}
    return cell


def _run(cell, capsys, trace=0, seconds=0.3, seed=2 ** 31 + 11):
    import jax

    rc = run.run_cell(cell, seed, seconds, trace, jax.devices()[: cell.chips], PEAKS)
    return rc, capsys.readouterr().out.strip().splitlines()


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 11])
def test_last_line_is_correct_and_both_counters_ride_the_step(tiny_cell, capsys, seed):
    rc, lines = _run(tiny_cell, capsys, trace=1, seed=seed)
    assert rc == 0
    last = json.loads(lines[-1])
    assert set(last) == RESULT_KEYS | {"breakdown"}
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    # no device plane on the CPU: the trace readers leave their metrics out,
    # the counters' readers find the step's own numbers
    assert {"compile_s", "moe_rows_max_over_mean", "moe_rows_here_traced"} <= set(last["metrics"])
    assert not {"kda_ms", "kda_roofline", "flash_ms", "moe_routed_ms"} & set(last["metrics"])


def test_a_chunk_whose_system_is_left_unsolved_is_not_correct(tiny_cell, capsys, monkeypatch):
    import jax.numpy as jnp

    from ps_pytorch_tpu.ops import kda

    monkeypatch.setattr(kda, "unit_lower_inverse",
                        lambda a: jnp.broadcast_to(jnp.eye(a.shape[-1], dtype=a.dtype), a.shape))
    # jax.checkpoint keeps a function's trace by its shapes: chunks of 8, which
    # no other test of this process has traced, so that the patch is read
    tiny_cell.config["kda_chunk_size"] = 8
    rc, lines = _run(tiny_cell, capsys)
    assert rc == 0 and json.loads(lines[-1])["correct"] is False
