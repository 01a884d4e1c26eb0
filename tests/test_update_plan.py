"""Where the optimizer's update reads a materialised gradient.

On one chip nothing stands between the product that makes a leaf's gradient
and `tx.update` + `apply_updates`, and XLA folds the update into the
product as its epilogue. parallel/dp_sp.plan_update says, from a leaf's
shape and the rows a step contracts over, for which leaves the step puts
the stage boundary back (a `lax.optimization_barrier` on that leaf's
gradient at the end of `grad_reduce`). The arithmetic is the same either
way: forced to every leaf and to none, the step gives the same bits. The
compiled program's side (no fusion holds a product and the update's sqrt;
the temporaries grow by under two leaves) is in tests/test_mosaic_compile.py.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.models.lm import lm_family, load_lm_config
from ps_pytorch_tpu.optim import build_optimizer
from ps_pytorch_tpu.parallel import dp_sp
from ps_pytorch_tpu.parallel.dp_sp import (
    make_lm_train_step, make_mesh_2d, plan_update, update_plan)

from . import test_attention_path as paths
from .test_evabyte_family import PUBLISHED as EVA

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FORCED = {"all": lambda shape, rows: True, "none": lambda shape, rows: False}


def _cfg(family):
    run = dict(attention_impl="flash", remat=True)
    if family == "eva_dense":
        return load_lm_config({**EVA, "vocab_size": paths.V, "window_size": 16, "chunk_size": 4}, **run)
    return paths._cfg({"transformer": "dense"}.get(family, family), **run)


def _two_steps(cfg, monkeypatch, answer):
    """(parameters, Adam's state, the second loss) after two steps with
    plan_update answering `answer` for every leaf; and how many barriers
    the step's jaxpr holds."""
    monkeypatch.setattr(dp_sp, "plan_update", FORCED[answer])
    tx = build_optimizer("adam", 1e-3, b1=0.9, b2=0.999, eps=1e-8)
    params = lm_family(cfg).init(cfg, jax.random.key(0))
    opt = tx.init(params)
    step = make_lm_train_step(cfg, tx, make_mesh_2d(1, 1), donate=False)
    barriers = str(jax.make_jaxpr(step)(params, opt, paths._tokens())).count("optimization_barrier")
    for seed in (0, 1):
        params, opt, loss, *_ = step(params, opt, paths._tokens(seed))
    return (params, opt, loss), barriers


@pytest.mark.parametrize("family", ["transformer", "eva_dense", "mla_moe"])
def test_the_update_apart_and_folded_give_the_same_bits(monkeypatch, family):
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    cfg = _cfg(family)
    apart, barriers = _two_steps(cfg, monkeypatch, "all")
    folded, none = _two_steps(cfg, monkeypatch, "none")
    leaves = len(jax.tree_util.tree_leaves(apart[0]))
    # one barrier a leaf, never one over the tree; none where the plan says none
    assert (barriers, none) == (leaves, 0)
    for a, b in zip(jax.tree_util.tree_leaves(apart), jax.tree_util.tree_leaves(folded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.isfinite(float(apart[2]))


# the five LM cells of BENCHMARK.json and PERF.md's table ("where the
# update stands"): the leaves whose update stands apart, by shape with their
# count. The rows a step contracts over on one chip are the cell's traffic's
# batch_rows x seq_len: 16,384 in the first three, 8,192 in the last two.
CELLS = {
    "evabyte_train_b1s16384_4layers": (16384, {
        (4096, 11008): 8, (11008, 4096): 4, (4096, 4096): 16}),
    "kimilinear_train_b2s8192_ep32share": (16384, {}),
    "kanana2_train_b2s8192_ep8share": (16384, {}),
    "granite4hm_train_remat_1period": (8192, {}),
    "gpt2m_train_b8s1024": (8192, {}),
}


def _cell(name):
    """(the parameters' shapes, rows a step) of a cell, as its driver
    builds the model: `load_lm_config` on a file with a `model_type`,
    TransformerConfig on the GPT-2 shape."""
    bench = os.path.join(ROOT, "benchmark")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (cell,) = [w for w in json.load(f)["workloads"] if w["name"] == name]
    with open(os.path.join(bench, "configs", cell["config"] + ".json")) as f:
        c = json.load(f)
    with open(os.path.join(bench, "traffic", cell["traffic"] + ".json")) as f:
        t = json.load(f)
    if "model_type" in c:
        cfg = load_lm_config(c, attention_impl="flash", remat=True, compute_dtype=jnp.bfloat16)
    else:
        from ps_pytorch_tpu.models.transformer import TransformerConfig

        cfg = TransformerConfig(vocab_size=c["vocab_size"], dim=c["n_embd"], depth=c["n_layer"],
                                heads=c["n_head"], mlp_ratio=c["mlp_ratio"],
                                max_seq_len=int(t["seq_len"]))
    shapes = jax.eval_shape(lambda k: lm_family(cfg).init(cfg, k), jax.random.key(0))
    return shapes, int(t["batch_rows"]) * int(t["seq_len"])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_plan_update_answers_the_cells_leaves_as_the_table_says(cell):
    shapes, rows = _cell(cell)
    assert rows == CELLS[cell][0]
    table = CELLS[cell][1]
    apart = {}
    for leaf in jax.tree_util.tree_leaves(shapes):
        if plan_update(leaf.shape, rows):
            apart[leaf.shape] = apart.get(leaf.shape, 0) + 1
    assert apart == table
    # the summary the CLI logs counts what the step's own tree_map will do
    leaves = jax.tree_util.tree_leaves(shapes)
    assert update_plan(shapes, rows) == {
        "rows": rows, "leaves": len(leaves), "leaves_apart": sum(table.values()),
        "params": sum(leaf.size for leaf in leaves),
        "params_apart": sum(int(np.prod(s)) * n for s, n in table.items())}


def test_plan_update_leaves_what_no_product_makes():
    """Norm gains and biases, conv taps, the experts' stacked matrices
    (a Pallas kernel makes their gradient and their Adam already stands
    alone): never, at any number of rows."""
    for shape in [(4096,), (4, 4096), (32, 128), (16, 2048, 768), (8, 1024, 2304), ()]:
        assert not plan_update(shape, 1 << 20)


def test_train_lm_traces_the_update_plan_once(tmp_path, monkeypatch):
    """The engagement counter: one `update_plan` instant a build, from the
    function the step's own tree_map asks (here made to name the matrices,
    which at these widths the rule would not)."""
    from ps_pytorch_tpu.cli import train_lm
    from ps_pytorch_tpu.obs.schema import validate_event

    monkeypatch.setattr(dp_sp, "plan_update", lambda shape, rows: len(shape) == 2)
    train_lm.main([
        "--dim", "32", "--depth", "1", "--heads", "2", "--seq-len", "32", "--vocab-size", "64",
        "--batch-size", "2", "--max-steps", "1", "--num-dp", "1", "--num-sp", "1",
        "--attention-impl", "flash", "--trace", str(tmp_path)])
    spans = [json.loads(line) for line in open(tmp_path / "trace_train_lm_p0.jsonl")]
    (plan,) = [s for s in spans if s.get("name") == "update_plan"]
    from ps_pytorch_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(vocab_size=64, dim=32, depth=1, heads=2, max_seq_len=32)
    leaves = jax.tree_util.tree_leaves(
        jax.eval_shape(lambda k: lm_family(cfg).init(cfg, k), jax.random.key(0)))
    matrices = [leaf for leaf in leaves if leaf.ndim == 2]
    assert 0 < len(matrices) < len(leaves)
    assert {k: plan[k] for k in ("rows", "leaves", "leaves_apart")} == {
        "rows": 64, "leaves": len(leaves), "leaves_apart": len(matrices)}
    assert plan["params_apart"] < plan["params"]
    assert validate_event(dict(plan))["leaves_apart"] == len(matrices)
