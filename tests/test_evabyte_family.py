"""The dense EVA-attention LM family (models/eva_dense.py over ops/eva.py)
against its plain reference, benchmark/reference/evabyte_eva.py: the one
reference, the file the benchmark's `correct` runs at the published widths.
Small sizes (4 heads of 16, window 64, chunks of 8, T 150: two whole windows
and a part-filled one with a part-filled chunk; 3 byte heads), seeded
weights from benchmark/weights.py, float32 on the CPU."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import spec, weights
from benchmark.reference import evabyte_eva as ref
from ps_pytorch_tpu.models import eva_dense, lm
from ps_pytorch_tpu.models.lm import lm_family, load_lm_config
from ps_pytorch_tpu.parallel.dp_sp import (
    init_lm_state, lm_loss_local, make_lm_train_step, make_mesh_2d, shard_tokens_2d)

CONFIG = os.path.join(spec.BENCH_DIR, "configs", "evabyte_6b5_4layers.json")
PUBLISHED = {
    "model_type": "evabyte", "attention_class": "eva", "vocab_size": 67, "hidden_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 96, "window_size": 64, "chunk_size": 8, "num_chunks": None,
    "num_pred_heads": 3, "rope_theta": 100000, "rope_scaling": None, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "norm_add_unit_offset": True, "fp32_skip_add": True,
    "fp32_logits": True, "hidden_act": "silu", "attention_bias": False,
}
B, T = 1, 150


@pytest.fixture()
def kernels(monkeypatch):
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")


def _weights(seed=0, pub=PUBLISHED):
    plain = weights.make_weights(ref.param_shapes(pub), seed)
    # benchmark/weights.py makes every norm gain exactly one: move them, so
    # that a gain applied as w and not as 1 + w would show
    bump = lambda g, i: g + 0.1 * jnp.cos(jnp.arange(g.size, dtype=jnp.float32) + i)
    for i, blk in enumerate(plain["blocks"]):
        blk["ln1"], blk["ln2"] = bump(blk["ln1"], i), bump(blk["ln2"], i + 0.5)
    plain["out_norm"] = bump(plain["out_norm"], 9)
    return plain


def _tokens(seed=1, b=B, t=T):
    return jnp.asarray(weights.token_rows(seed, b, t, PUBLISHED["vocab_size"]))


@functools.cache
def _reference():
    """(logits, loss, gradients) of the reference on _weights() and _tokens()."""
    params, tokens = _weights(), _tokens()
    def both(p):
        loss, grads = jax.value_and_grad(lambda p: _ref_loss(p, tokens))(p)
        return jnp.stack([ref.logits_fn(PUBLISHED, p, row) for row in tokens]), loss, grads

    return jax.jit(both)(params)


def _ref_loss(params, tokens):
    heads = PUBLISHED["num_pred_heads"]
    count = tokens.shape[0] * sum(tokens.shape[1] - 1 - p for p in range(heads))
    return sum(ref.nll_sum(PUBLISHED, params, row) for row in tokens) / count


def _program_loss(cfg, params, tokens):
    mesh = make_mesh_2d(1, 1)
    fn = jax.shard_map(lambda p, tok: lm_loss_local(cfg, p, tok)[0], mesh=mesh,
                       in_specs=(jax.sharding.PartitionSpec(),) * 2,
                       out_specs=jax.sharding.PartitionSpec(), check_vma=False)
    return fn(params, tokens)


@pytest.mark.parametrize("impl", ["naive", "flash"])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(kernels, impl):
    cfg = load_lm_config(PUBLISHED, attention_impl=impl)
    params, tokens = _weights(), _tokens()
    def forward(p):
        return lm_family(cfg).apply(cfg, p, tokens)

    logits, aux = jax.jit(forward)(params)
    assert logits.shape == (B, T, 3, 67) and logits.dtype == jnp.float32
    want, ref_loss, ref_grads = _reference()
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), rtol=2e-4, atol=2e-4)
    assert set(aux) == {"eva_mass_sum", "eva_mass_queries"}
    assert all(a.shape == (2,) for a in aux.values())
    loss, grads = jax.jit(jax.value_and_grad(lambda p: _program_loss(cfg, p, tokens)))(params)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    names = weights.leaf_names(grads)
    assert any(n.endswith("phi") for n in names) and any(n.endswith("mu") for n in names)
    for name, g, r in zip(names, jax.tree_util.tree_leaves(grads),
                          jax.tree_util.tree_leaves(ref_grads)):
        scale = float(jnp.max(jnp.abs(r)))
        assert scale > 0, name          # every leaf is live: phi and mu too
        np.testing.assert_allclose(np.asarray(g) / scale, np.asarray(r) / scale,
                                   rtol=0, atol=2e-3, err_msg=name)


def test_the_loss_over_the_offsets_is_the_hand_loop():
    """Head p of position i is held to the byte at i + 1 + p; a target past
    the row's end is no term; the mean is over what is left."""
    rng = np.random.RandomState(0)
    b, t, heads, vocab = 2, 11, 4, 7
    logits = jnp.asarray(rng.randn(b, t, heads, vocab).astype(np.float32))
    tokens = jnp.asarray(rng.randint(0, vocab, (b, t)).astype(np.int32))
    from ps_pytorch_tpu.parallel.dp_sp import _offsets_loss

    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    total, count = 0.0, 0
    for r in range(b):
        for i in range(t):
            for p in range(heads):
                if i + 1 + p < t:
                    total -= logp[r, i, p, int(tokens[r, i + 1 + p])]
                    count += 1
    assert count == b * sum(t - 1 - p for p in range(heads))
    np.testing.assert_allclose(float(_offsets_loss(logits, tokens, 1)), total / count, rtol=1e-6)
    with pytest.raises(NotImplementedError, match="several prediction heads.*--num-sp 1"):
        _offsets_loss(logits, tokens, 2)


def test_flash_remat_and_bfloat16_run_the_same_model(kernels):
    """The benchmark's options (flash kernels, remat, bfloat16 blocks) against
    the reference: the loss to bfloat16's rounding; `remat` the same bits
    as without it."""
    tokens, params = _tokens(), _weights()
    run = dict(attention_impl="flash", compute_dtype=jnp.bfloat16)
    loss = lambda cfg: jax.jit(jax.value_and_grad(lambda p: _program_loss(cfg, p, tokens)))(params)
    (plain, g_plain), (kept, g_kept) = loss(load_lm_config(PUBLISHED, **run)), loss(
        load_lm_config(PUBLISHED, remat=True, **run))
    assert float(plain) == float(kept)
    for a, b in zip(jax.tree_util.tree_leaves(g_plain), jax.tree_util.tree_leaves(g_kept)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(float(plain), float(_reference()[1]), rtol=2e-3)


def test_the_step_returns_the_mass_counter_and_holds_the_four_scopes(kernels):
    cfg = load_lm_config(PUBLISHED, attention_impl="flash", remat=True)
    mesh = make_mesh_2d(2, 1)
    tx = optax.adam(1e-3)
    params, opt = init_lm_state(cfg, tx, jax.random.key(0), mesh)
    tokens = shard_tokens_2d(_tokens(5, b=4), mesh)
    step = make_lm_train_step(cfg, tx, mesh)
    out = step(params, opt, tokens)
    assert len(out) == 4 and np.isfinite(float(out[2]))
    assert set(out[3]) == {"eva_remote_mass", "eva_remote_mass_per_layer"}
    assert out[3]["eva_remote_mass_per_layer"].shape == (2,)
    assert 0.0 < float(out[3]["eva_remote_mass"]) < 1.0
    plan = eva_dense.eva_plan(cfg, T)
    assert (plan["windows"], plan["padded_len"], plan["summaries"]) == (3, 192, 24)
    assert plan["tiles_local"] > 0 and plan["tiles_remote"] > 0 and plan["eva_layers"] == 2
    # the scopes of the attention's four parts are in the step that ran
    places = step.scopes()["by_place"]
    assert {"mixer/eva", "mixer/eva/pool", "mixer/eva/local", "mixer/eva/remote",
            "mixer/eva/merge"} <= {row["scope"] for row in places}
    for part in ("local", "remote", "merge"):       # each in the forward and in the backward
        assert {"forward", "backward"} <= {
            row["phase"] for row in places if row["scope"] == "mixer/eva/" + part}, part


REFUSALS = [
    ({"attention_class": "softmax"}, "attention_class='softmax'"),
    ({"num_chunks": 64}, "num_chunks=64"),
    ({"rope_scaling": {"type": "linear", "factor": 2}}, "rope_scaling="),
    ({"num_key_value_heads": 2}, "num_key_value_heads=2.*no grouped key/value heads"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings=True"),
    ({"chunk_size": 7}, "chunk_size=7 does not divide window_size=64"),
    ({"norm_add_unit_offset": False}, "norm_add_unit_offset=False"),
    ({"fp32_skip_add": False}, "fp32_skip_add=False"),
    ({"hidden_act": "gelu"}, "hidden_act='gelu'"),
    ({"attention_bias": True}, "attention_bias=True"),
]


@pytest.mark.parametrize("over, named", REFUSALS, ids=[next(iter(o)) for o, _ in REFUSALS])
def test_what_the_family_cannot_express_is_refused_by_name(over, named):
    with pytest.raises(ValueError, match=named):
        load_lm_config({**PUBLISHED, **over})


def test_a_missing_key_is_named():
    lacking = {k: v for k, v in PUBLISHED.items() if k != "window_size"}
    with pytest.raises(ValueError, match=r"config lacks \['window_size'\]"):
        load_lm_config(lacking)


def test_a_sequence_axis_of_two_is_refused_and_the_messages_read_one_table():
    cfg = load_lm_config(PUBLISHED)
    mesh = make_mesh_2d(1, 2)
    tx = optax.adam(1e-3)
    params, opt = init_lm_state(cfg, tx, jax.random.key(0), mesh)
    tokens = shard_tokens_2d(jnp.zeros((2, 128), jnp.int32), mesh)
    with pytest.raises(NotImplementedError, match="chunk summaries.*--num-sp 1"):
        make_lm_train_step(cfg, tx, mesh)(params, opt, tokens)
    with pytest.raises(TypeError, match="SsmHybridConfig, KdaHybridConfig, EvaByteConfig"):
        lm_family(object())
    with pytest.raises(NotImplementedError, match="evabyte: an attention_class other than eva"):
        lm.require_dense(cfg, "tensor parallelism")
    assert isinstance(cfg, eva_dense.EvaByteConfig) and lm_family(cfg).counters is not None


def test_the_program_holds_the_parameters_the_configuration_states():
    """At the published widths, from shapes alone: the catalog's config builds,
    the program's tree is the reference's, and its count is the file's
    (ISSUE 41's arithmetic)."""
    with open(CONFIG) as f:
        pub = json.load(f)
    cfg = load_lm_config(CONFIG)
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim, cfg.intermediate_size,
            cfg.window_size, cfg.chunk_size, cfg.vocab_size, cfg.num_pred_heads,
            cfg.num_hidden_layers, cfg.rope_theta) == (4096, 32, 128, 11008, 2048, 16, 320, 8, 4, 1e5)
    tree = jax.eval_shape(lambda: lm_family(cfg).init(cfg, jax.random.key(0)))
    assert weights.same_tree(tree, ref.param_shapes(pub))
    count = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 32 * 128
    assert layer == 202_391_552
    assert count == pub["parameters"] == 4 * layer + 320 * 4096 + 4096 * 8 * 320 + 4096 \
        == 821_366_784
    # the 32 layers the source publishes build too (shapes only)
    whole = load_lm_config({**pub, "num_hidden_layers": 32})
    assert len(jax.eval_shape(lambda: lm_family(whole).init(whole, jax.random.key(0)))["blocks"]) == 32
    # benchmark/weights.py draws phi and mu (matrices), and the norm gains as ones
    drawn = weights.make_weights(ref.param_shapes(PUBLISHED), 5)["blocks"][0]
    assert float(jnp.std(drawn["phi"])) > 0.1 and float(jnp.std(drawn["mu"])) > 0.1
    assert float(jnp.min(drawn["ln1"])) == float(jnp.max(drawn["ln2"])) == 1.0


def test_the_flops_modules_tile_is_the_plans():
    """benchmark/flops/evabyte.py counts the kernels' work in the tiles
    ops/flash_attention.plan_flash gives at the cell's shapes."""
    from benchmark import flops
    from ps_pytorch_tpu.ops.eva import plan_eva

    with open(CONFIG) as f:
        pub = json.load(f)
    k = flops.load(pub["flops"])
    for t in (16384, 8192, 4096):
        plan = plan_eva(t, 128, jnp.bfloat16, 2048, 16)
        assert {plan.local.block_q, plan.local.block_k, plan.remote.block_q} == {k.TILE}
        assert plan.remote.block_k == min(k.TILE, t // 16)
        assert plan.tiles() == k.live_tiles_per_head(pub, t)


def test_train_lm_traces_the_plan_once_and_the_state_at_log_steps(tmp_path, kernels):
    from ps_pytorch_tpu.cli import train_lm
    from ps_pytorch_tpu.obs import schema

    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(PUBLISHED))
    out = train_lm.main([
        "--lm-config", str(path), "--num-dp", "1", "--num-sp", "1", "--seq-len", "150",
        "--batch-size", "2", "--max-steps", "4", "--log-interval", "2", "--optimizer", "adam",
        "--lr", "1e-3", "--train-size", "8", "--attention-impl", "flash", "--remat",
        "--trace", str(tmp_path / "trace"), "--metrics-file", str(tmp_path / "metrics.jsonl")])
    assert np.isfinite(out["loss"])
    spans = [json.loads(line) for line in open(tmp_path / "trace" / "trace_train_lm_p0.jsonl")]
    plans = [s for s in spans if s.get("name") == "eva_plan"]
    assert len(plans) == 1 and not [s for s in spans if s.get("name") == "flash_plan"]
    cfg = load_lm_config(PUBLISHED, attention_impl="flash")
    assert {k: plans[0][k] for k in eva_dense.eva_plan(cfg, 150)} == eva_dense.eva_plan(cfg, 150)
    assert plans[0]["remat_saves"] == "ps_eva_o,ps_eva_lse,ps_eva_q,ps_eva_k,ps_eva_v,ps_eva_kp,ps_eva_vp"
    states = [s for s in spans if s.get("name") == "eva_state"]
    assert len(states) == 3 and all(0.0 < s["remote_mass"] < 1.0 for s in states)
    assert all(len(s["remote_mass_per_layer"]) == 2 for s in states)
    for line in open(tmp_path / "metrics.jsonl"):
        rec = json.loads(line)
        schema.validate_event(rec)
        if rec.get("kind") == "train_lm":
            assert 0.0 < rec["eva_remote_mass"] < 1.0
