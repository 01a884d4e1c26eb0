"""The new cell through the `lm_config_train` kind at a tiny size on the
CPU: the driver names no model, so the family, its fourth value (the routing
counters with the ReGLU gate's share) and the comparison ride the kind as the
other cells' do. `correct` goes false for each of the cell's three blind-spot
controls: the route taken from the FFN's own norm (`router_reads_ffn_norm`),
the experts' gate a SiLU (`relu_as_silu`), rotary on the global layer
(`global_layer_rotated`)."""

import json

import pytest

from benchmark import run, spec

CELL = "smallthinker_train_b1s16384_ep4share"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TINY_CONFIG = dict(
    vocab_size=97, hidden_size=64, num_attention_heads=6, num_key_value_heads=2, head_dim=16,
    moe_num_primary_experts=16, moe_num_active_primary_experts=6, moe_ffn_hidden_size=32,
    sliding_window_size=24, rope_theta=10000, experts_held=4)
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
def test_last_line_is_correct_and_the_gates_share_rides_the_step(tiny_cell, capsys, seed):
    rc, lines = _run(tiny_cell, capsys, trace=1, seed=seed)
    assert rc == 0
    last = json.loads(lines[-1])
    assert set(last) == RESULT_KEYS | {"breakdown"}
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    # no device plane on the CPU: the trace readers leave their metrics out,
    # the counters' readers find the step's own numbers
    metrics = last["metrics"]
    assert {"compile_s", "moe_rows_max_over_mean", "moe_rows_here_traced", "moe_passes_traced",
            "moe_gate_active"} <= set(metrics)
    assert 0.3 < metrics["moe_gate_active"]["value"] < 0.7
    assert metrics["moe_passes_traced"]["value"] == 4.0
    assert not {"flash_ms", "moe_routed_ms", "moe_route_ms", "swa_flash_scope_ms"} & set(metrics)


def _fault(monkeypatch, fault):
    import jax

    from ps_pytorch_tpu.models import prerouted_moe
    from ps_pytorch_tpu.parallel import moe

    if fault == "router_reads_ffn_norm":
        half = prerouted_moe.ffn_half

        def own_norm(cfg, x, blk, route=None):
            n32 = prerouted_moe._rms32(x, blk["ln2"], cfg.rms_norm_eps)
            return half(cfg, x, blk, route=moe.route_tokens(n32, blk, cfg.routing))

        monkeypatch.setattr(prerouted_moe, "ffn_half", own_norm)
    elif fault == "relu_as_silu":
        monkeypatch.setitem(moe.ACTIVATIONS, "relu", jax.nn.silu)
    else:
        block = prerouted_moe.prerouted_block
        monkeypatch.setattr(prerouted_moe, "prerouted_block",
                            lambda cfg, sliding, rotary, *rest: block(cfg, sliding, 1, *rest))


@pytest.mark.parametrize("fault", ["router_reads_ffn_norm", "relu_as_silu", "global_layer_rotated"])
def test_a_blind_spot_control_is_not_correct(tiny_cell, capsys, monkeypatch, fault):
    _fault(monkeypatch, fault)
    rc, lines = _run(tiny_cell, capsys)
    assert rc == 0 and json.loads(lines[-1])["correct"] is False
