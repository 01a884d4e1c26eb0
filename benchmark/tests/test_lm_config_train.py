"""The `lm_config_train` kind's contract, at a tiny size on the CPU: the
cell loads as declared, the result line, `correct` going false when the
timed path is broken underneath (a step that returns its state unchanged,
part of the batch left out), and the float8 control failing its limit.
The sizes are the published ones shrunk; the family, the share (4 of 16
experts, ids 0-100 of the vocabulary) and every code path are the cell's."""

import json

import pytest

from benchmark import compare, drivers, run, spec

CELL = "kanana2_train_b2s8192_ep8share"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TINY_CONFIG = dict(hidden_size=64, num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
                   qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
                   moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=3,
                   num_hidden_layers=3, vocab_size=101, experts_held=4, expert_offset=0)
TINY_TRAFFIC = dict(batch_rows=2, seq_len=64, attention_impl="naive", corpus_rows=16,
                    dtype="float32")


@pytest.fixture
def tiny_cell():
    cell = spec.load_cell(CELL)
    cell.config.update(TINY_CONFIG)
    cell.traffic.update(TINY_TRAFFIC)
    # limits for this size, set as the cell's own are: float32 on the CPU
    # against the reference reads at most loss 3.2e-7, grad 7.3e-7, dparam
    # 2.6e-6 over seeds 5, 6, 2**31+11; the float8 control at least loss
    # 6.5e-4, grad 0.127, dparam 0.028
    cell.limits = {"loss_step1_rel": 3e-5, "loss_step2_rel": 3e-5, "loss_step3_rel": 3e-5,
                   "grad_norm_worst_leaf": 1e-3, "dparam_norm_worst_leaf": 1e-3}
    return cell


def _run(cell, capsys, trace=0, seconds=0.5, seed=2 ** 31 + 11):
    import jax

    rc = run.run_cell(cell, seed, seconds, trace, jax.devices()[: cell.chips], PEAKS)
    return rc, capsys.readouterr().out.strip().splitlines()


def test_the_cell_is_as_declared():
    cell = spec.load_cell(CELL)
    t, c = cell.traffic, cell.config
    assert cell.kind == "lm_config_train" and cell.chips == 1
    assert (t["batch_rows"], t["seq_len"], t["block_steps"], t["check_steps"]) == (2, 8192, 2, 3)
    assert (t["attention_impl"], t["dtype"], t["remat"]) == ("flash", "bfloat16", True)
    assert (t["optimizer"], t["lr"], t["b1"], t["b2"], t["eps"]) == ("adam", 3e-4, 0.9, 0.999, 1e-8)
    assert (t["num_dp"], t["num_sp"], t["control_operand"]) == (1, 1, "float8_e4m3fn")
    assert "warmup_steps" not in t and "lr_schedule" not in t   # ISSUE 27: a constant rate
    # every published width unchanged; the three cuts, and no other
    assert (c["hidden_size"], c["intermediate_size"], c["moe_intermediate_size"]) == (2048, 6144, 768)
    assert (c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]) == (
        512, 128, 64, 128)
    assert (c["num_attention_heads"], c["n_routed_experts"], c["num_experts_per_tok"],
            c["n_shared_experts"]) == (32, 128, 6, 2)
    assert (c["num_hidden_layers"], c["experts_held"], c["vocab_size"]) == (5, 16, 16032)
    assert c["reduced"] == ["num_hidden_layers", "experts_held", "vocab_size"]
    names = {m["name"] for m in cell.per_layer}
    assert {"lm_step_device_ms", "flash_ms", "flash_roofline", "moe_routed_ms",
            "moe_routed_roofline", "moe_rows_max_over_mean", "lm_device_idle_pct",
            "lm_peak_hbm_gib", "compile_s"} <= names
    assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s", "setup_s"]
    assert set(cell.limits) == {"loss_step1_rel", "loss_step2_rel", "loss_step3_rel",
                                "grad_norm_worst_leaf", "dparam_norm_worst_leaf"}


def test_the_configuration_holds_the_catalogs_numbers():
    """Every number of the source's config.json under its own key; only the
    keys in `reduced` differ (the catalog row, where the guide is here)."""
    import os

    cat = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(cat):
        pytest.skip("the catalog is not on this machine")
    with open(cat) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    cfg = spec.load_cell(CELL).config
    assert cfg["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if cfg.get(k, "absent") != v}
    assert differ == {"num_hidden_layers", "vocab_size"} <= set(cfg["reduced"])


def test_last_line_and_counters(tiny_cell, capsys):
    rc, lines = _run(tiny_cell, capsys)
    assert rc == 0
    last = json.loads(lines[-1])
    assert set(last) == RESULT_KEYS
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert set(last["metrics"]) == {"train_tokens_per_s", "setup_s"}
    blocks = json.loads(next(ln for ln in lines if ln.startswith("[bench] blocks")).split(" ", 2)[2])
    assert last["metrics"]["train_tokens_per_s"]["value"] == blocks["window_rate"]
    assert blocks["window_rate"] == pytest.approx(blocks["blocks"] * 2 * 64 * 2 / blocks["window_s"])
    # the routed experts' leaves are among the leaves compared, one by one
    worst = [ln for ln in lines if ln.startswith("[bench] worst_leaves_grad_norms")]
    assert worst


def test_traced_line_reports_the_routing_counter(tiny_cell, capsys):
    rc, lines = _run(tiny_cell, capsys, trace=1)
    assert rc == 0
    last = json.loads(lines[-1])
    assert set(last) == RESULT_KEYS | {"breakdown"}
    # no device plane on the CPU: the trace readers leave their metrics out
    assert {"compile_s", "moe_rows_max_over_mean", "moe_rows_here_traced"} <= set(last["metrics"])
    # the rows the traced steps routed, layers summed: 2 layers x 128 tokens x 3 of which a quarter is held
    assert 0 <= last["metrics"]["moe_rows_here_traced"]["value"] <= 2 * 128 * 3
    assert 1.0 <= last["metrics"]["moe_rows_max_over_mean"]["value"] <= 4.0
    assert "moe_routed_ms" not in last["metrics"] and "moe_routed_roofline" not in last["metrics"]


def test_traced_steps_counters_are_means_over_the_steps_read():
    """Four traced blocks of two steps: `trim` reads runs 2..6 of the eight."""
    import numpy as np

    from benchmark.drivers.lm_config_train import _Session

    s = _Session.__new__(_Session)
    s.traced = [{"moe_rows_here": np.float32(r)} for r in (900, 800, 10, 20, 30, 40, 50, 700)]
    assert s.traced_means(4, 2) == {"moe_rows_here_traced": 30.0}


def test_routed_roofline_counts_the_rows_the_traced_steps_routed():
    """On the recorded capture: the share follows the rows counted, a run
    without the counter gives no metric, and a capture without its run reads
    at uniform routing (what benchmark/tests/data pins)."""
    import os

    from benchmark import reducers
    from benchmark.reducers import trace as tr

    data = os.path.join(os.path.dirname(__file__), "data")
    cell = spec.load_cell(CELL)
    with open(os.path.join(data, CELL + ".expected.json")) as f:
        expected = json.load(f)
    trace, steps = tr.trim(tr.load_json(os.path.join(data, CELL + ".trace.json.gz")),
                           expected["blocks"], expected["block_steps"])
    metric = next(m for m in cell.per_layer if m["name"] == "moe_routed_roofline")
    assert metric["kind"] == "roofline_counted"
    ev = {"trace": trace, "steps_traced": steps, "cell": cell,
          "peaks": spec.load_peaks("TPU v5 lite")}
    share = lambda **kw: reducers.reduce(metric["kind"], metric["args"], {**ev, **kw})
    uniform = share()
    assert uniform == pytest.approx(expected["metrics"]["moe_routed_roofline"], rel=1e-9)
    assert share(counters={"moe_rows_here_traced": 4 * 12288.0}) == pytest.approx(uniform)
    assert share(counters={"moe_rows_here_traced": 2 * 12288.0}) == pytest.approx(uniform / 2)
    # no rows: what is left is each held expert's gradient written, 1.47 ms a step
    ms = expected["metrics"]["moe_routed_ms"]
    assert share(counters={"moe_rows_here_traced": 0.0}) == pytest.approx(
        100 * 4 * 16 * 3 * 2048 * 768 * 4 / 819e9 / (ms * 1e-3))
    assert share(counters={"window_compiles": 0}) is None


def _broken(monkeypatch, patch):
    from benchmark.drivers import lm_config_train as drv

    real = drv._Session.__init__

    def init(self, *a, **kw):
        real(self, *a, **kw)
        patch(self)

    monkeypatch.setattr(drv._Session, "__init__", init)


def test_step_that_returns_its_state_unchanged_is_not_correct(tiny_cell, capsys, monkeypatch):
    import jax

    def patch(s):
        step = s._step
        s._step = lambda p, o, tok: (p, o) + tuple(step(
            *jax.tree_util.tree_map(lambda x: x + 0, (p, o)), tok)[2:])

    _broken(monkeypatch, patch)
    rc, lines = _run(tiny_cell, capsys)
    assert rc == 0 and json.loads(lines[-1])["correct"] is False
    rows = [json.loads(ln.split(" ", 2)[2]) for ln in lines if ln.startswith("[bench] compared")]
    assert "dparam_norm_worst_leaf" in {r["number"] for r in rows if not r["ok"]}


def test_part_of_the_batch_left_out_is_not_correct(tiny_cell, capsys, monkeypatch):
    import numpy as np

    def patch(s):
        put = s._put
        s._put = lambda tok: put(np.concatenate([tok[:1], tok[:1]]))

    _broken(monkeypatch, patch)
    rc, lines = _run(tiny_cell, capsys)
    assert json.loads(lines[-1])["correct"] is False


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 11])
def test_the_lower_precision_control_fails_a_limit(tiny_cell, seed):
    ctx = {"out_dir": None, "compiles": None}
    check = drivers.load("lm_config_train").check
    sound = compare.training_numbers(*check(tiny_cell, seed, False, ctx))
    control = compare.training_numbers(*check(tiny_cell, seed, True, ctx))
    assert compare.decide(sound, tiny_cell.limits)[0] is True
    ok, rows = compare.decide(control, tiny_cell.limits)
    assert ok is False
    assert not next(r for r in rows if r["number"] == "grad_norm_worst_leaf")["ok"]
    assert control["grad_norm_worst_leaf"] > 100 * sound["grad_norm_worst_leaf"]


def test_stacked_and_unstacked_are_inverse():
    import jax.numpy as jnp
    import numpy as np

    from benchmark.drivers.lm_config_train import stacked, unstacked

    tree = {"blocks": [{"w": jnp.ones((2, 3)), "experts": [
        {"a": jnp.full((2, 2), float(i)), "b": jnp.full((3,), float(i))} for i in range(4)]}]}
    s = stacked(tree, ("experts",))
    assert s["blocks"][0]["experts"]["a"].shape == (4, 2, 2)
    assert stacked(tree, ()) == tree
    back = unstacked(s, ("experts",))
    assert np.array_equal(back["blocks"][0]["experts"][3]["a"], tree["blocks"][0]["experts"][3]["a"])
    assert len(back["blocks"][0]["experts"]) == 4
