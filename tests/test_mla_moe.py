"""The latent-attention, sparse-expert LM family (models/mla_moe.py, the
dropless layer of parallel/moe.py, ops/grouped_matmul.py) against its plain
reference, benchmark/reference/kanana2_moe_mla.py: the one reference, the
file the benchmark's `correct` runs at the published widths. Small sizes,
seeded weights from benchmark/weights.py, float32 on the CPU."""

import copy
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import compare, drivers, spec, weights
from benchmark.drivers import lm_config_train as drv
from benchmark.reference import kanana2_moe_mla as ref
from ps_pytorch_tpu.models.lm import lm_family, load_lm_config
from ps_pytorch_tpu.models.mla_moe import MlaMoeConfig, _rms32, apply_mla_moe
from ps_pytorch_tpu.ops import grouped_matmul as gm
from ps_pytorch_tpu.parallel import moe
from ps_pytorch_tpu.parallel.dp_sp import (
    init_lm_state, make_lm_train_step, make_mesh_2d, shard_tokens_2d)

PUBLISHED = {
    "model_type": "deepseek_v3", "vocab_size": 101, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 16, "n_shared_experts": 2,
    "num_experts_per_tok": 3, "first_k_dense_replace": 1, "routed_scaling_factor": 2.448,
    "norm_topk_prob": True, "rope_theta": 1000000, "rms_norm_eps": 1e-6, "rope_interleave": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "experts_held": 4, "expert_offset": 4,
}
B, T = 2, 48
stacked = partial(drv.stacked, groups=ref.GROUPS)
unstacked = partial(drv.unstacked, groups=ref.GROUPS)


def _setup(seed=3, **over):
    pub = {**PUBLISHED, **over}
    cfg = load_lm_config(pub, attention_impl="naive")
    plain = weights.make_weights(ref.param_shapes(pub), seed)
    tokens = jnp.asarray(weights.token_rows(seed, B, T, pub["vocab_size"]))
    return pub, cfg, plain, tokens


def _prog_loss(cfg, params, tokens):
    logits, _ = apply_mla_moe(cfg, params, tokens)
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)) / (
        tokens.shape[0] * (tokens.shape[1] - 1))


def _ref_loss(pub, plain, tokens):
    return sum(ref.nll_sum(pub, plain, row) for row in tokens) / (
        tokens.shape[0] * (tokens.shape[1] - 1))


def test_logits_and_loss_match_the_reference():
    pub, cfg, plain, tokens = _setup()
    logits, routing = jax.jit(partial(apply_mla_moe, cfg))(stacked(plain), tokens)
    want = jnp.stack([ref.logits_fn(pub, plain, row) for row in tokens])
    np.testing.assert_allclose(logits, want, atol=2e-5, rtol=2e-5)
    assert routing["counts"].shape == (2, 4) and routing["unserved"].shape == (2,)
    np.testing.assert_allclose(_prog_loss(cfg, stacked(plain), tokens),
                               _ref_loss(pub, plain, tokens), rtol=1e-6)


def test_every_gradient_leaf_matches_the_reference():
    pub, cfg, plain, tokens = _setup(seed=4)
    got = unstacked(jax.jit(jax.grad(lambda p: _prog_loss(cfg, p, tokens)))(stacked(plain)))
    want = jax.jit(jax.grad(lambda p: _ref_loss(pub, p, tokens)))(plain)
    names = weights.leaf_names(want)
    for name, g, r in zip(names, jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(r))) + 1e-12
        assert float(jnp.max(jnp.abs(g - r))) <= 2e-4 * scale + 1e-9, name
    # no gradient reaches the aux-loss-free bias; the routed experts' do
    by = dict(zip(names, jax.tree_util.tree_leaves(got)))
    assert not np.any(by["blocks/1/router_bias"])
    assert np.any(by["blocks/1/experts/0/w_down"]) and np.any(by["blocks/2/router"])


def _tiny_cell(dtype="float32"):
    cell = spec.load_cell("kanana2_train_b2s8192_ep8share")
    cell.config.update({k: v for k, v in PUBLISHED.items() if k != "model_type"})
    cell.traffic.update(batch_rows=2, seq_len=64, attention_impl="naive", corpus_rows=16,
                        dtype=dtype)
    return cell


def test_three_adam_steps_match_the_reference_and_the_control_does_not():
    """Through the path the benchmark's cell runs (dp_sp.make_lm_train_step,
    the program's Adam), by the comparison that decides `correct`."""
    cell = _tiny_cell()
    check = drivers.load("lm_config_train").check
    ctx = {"out_dir": None, "compiles": None}
    sound = compare.training_numbers(*check(cell, 7, False, ctx))
    # the cell warms its rate up from 0: steps of 1.5e-7 and 3e-7 are a few
    # float32 ulps of a norm gain of 1.0, so the change is the coarser number
    change = sound.pop("dparam_norm_worst_leaf")
    assert max(sound.values()) < 2e-5 and change < 5e-4, (sound, change)
    control = compare.training_numbers(*check(cell, 7, True, ctx))
    assert control["grad_norm_worst_leaf"] > 0.02, control


def _layer(pub, n, blk_plain):
    """The reference's whole expert layer on one row n [T, D]."""
    return ref._expert_ffn(pub, n, blk_plain, ref._mm(None))


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight shares of two experts each: their routed parts, plus the shared
    experts counted once, are the uncut reference layer's result."""
    uncut = {**PUBLISHED, "experts_held": 16, "expert_offset": 0}
    plain = weights.make_weights(ref.param_shapes(uncut), 11)["blocks"][1]
    n = jax.random.normal(jax.random.key(0), (1, 40, 64))
    want = _layer(uncut, n[0], plain)
    shared = ref._gated(n[0], plain["shared"], ref._mm(None))
    total, rows = shared, 0
    for share in range(8):
        blk = {"router": plain["router"], "router_bias": plain["router_bias"],
               "experts": stacked({"experts": plain["experts"][2 * share:2 * share + 2]})["experts"]}
        sp = moe.DroplessSpec(num_experts=16, top_k=3, experts_held=2, expert_offset=2 * share,
                              routed_scale=2.448)
        y, stats = moe.moe_dropless_local(n, blk, sp, jnp.float32)
        total, rows = total + y[0], rows + int(jnp.sum(stats["counts"]))
    assert rows == 40 * 3                       # every assignment lives on exactly one share
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-5)
    # and a share left out is seen
    assert float(jnp.max(jnp.abs(total - y[0] - want))) > 1e-3


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "pallas"])
def test_no_token_is_dropped_when_all_route_to_the_same_experts(interpret, monkeypatch):
    """The worst imbalance: a bias makes every token choose experts 4, 5, 6,
    all held here; every one of the N * 3 assignments gets its row."""
    if interpret:
        monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    pub, cfg, plain, _ = _setup(seed=5)
    blk_plain = plain["blocks"][1]
    blk_plain["router_bias"] = jnp.zeros((16,)).at[jnp.array([4, 5, 6])].set(10.0)
    n = jax.random.normal(jax.random.key(1), (2, 300, 64))
    blk = stacked({"b": [blk_plain]})["b"][0]
    y, stats = jax.jit(partial(
        moe.moe_dropless_local, spec=cfg.routing, compute_dtype=jnp.float32))(n, blk)
    assert stats["counts"].tolist() == [600, 600, 600, 0] and int(stats["unserved"]) == 0
    mm = ref._mm(None)
    for row in range(2):
        want = _layer(pub, n[row], blk_plain) - ref._gated(n[row], blk_plain["shared"], mm)
        np.testing.assert_allclose(y[row], want, atol=3e-5, rtol=3e-5)


def test_pallas_grouped_products_match_the_jnp_twin_in_value_and_gradient(monkeypatch):
    _, cfg, plain, _ = _setup(seed=6)
    blk = stacked({"b": [plain["blocks"][2]]})["b"][0]
    n = jax.random.normal(jax.random.key(2), (2, 150, 64))
    w = jax.random.normal(jax.random.key(3), (2, 150, 64))
    f = lambda n, blk: jnp.sum(moe.moe_dropless_local(n, blk, cfg.routing, jnp.float32)[0] * w)
    want, gwant = jax.value_and_grad(f, (0, 1))(n, blk)
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    got, ggot = jax.value_and_grad(f, (0, 1))(n, blk)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, r in zip(jax.tree_util.tree_leaves(ggot), jax.tree_util.tree_leaves(gwant)):
        np.testing.assert_allclose(g, r, atol=2e-5, rtol=2e-4)


def test_group_layout_gives_every_expert_a_tile_and_the_buffer_always_fits():
    counts = jnp.array([5, 0, 17, 8], jnp.int32)
    rows = gm.buffer_rows(30, 4, tile_m=8)
    lay = gm.group_layout(counts, rows, tile_m=8)
    assert rows == (4 + 4) * 8
    assert lay.sizes.tolist() == [8, 8, 24, 8] and lay.starts.tolist() == [0, 8, 16, 40]
    assert int(lay.n_live[0]) == 6 and lay.tile_expert.tolist() == [0, 1, 2, 2, 2, 3, 3, 3]
    worst = gm.group_layout(jnp.array([30, 0, 0, 0], jnp.int32), rows, tile_m=8)
    assert int(worst.n_live[0]) * 8 <= rows


def test_a_bfloat16_router_fails_the_comparison(monkeypatch):
    """The router's product is float32 as published: in bfloat16 other
    experts are chosen and the logits leave the reference."""
    pub, cfg, plain, tokens = _setup(seed=8)
    want = jnp.stack([ref.logits_fn(pub, plain, row) for row in tokens])
    real = moe.dropless_route
    monkeypatch.setattr(moe, "dropless_route", lambda n, r, b, s: real(
        n.astype(jnp.bfloat16).astype(jnp.float32), r.astype(jnp.bfloat16).astype(jnp.float32), b, s))
    got, _ = apply_mla_moe(cfg, stacked(plain), tokens)
    assert float(jnp.max(jnp.abs(got - want))) > 1e-3


def test_sequence_parallel_step_matches_one_device():
    """The new family through make_lm_train_step at dp 2 x sp 2 (ring
    attention over a 192/128-shaped head, global rotary positions, the
    boundary target) against dp 1 x sp 1."""
    _, cfg, _, _ = _setup()
    tokens = weights.token_rows(9, 4, 32, cfg.vocab_size)
    tx = optax.adam(1e-3)
    out = {}
    for dp, sp in ((1, 1), (2, 2)):
        mesh = make_mesh_2d(dp, sp)
        params, opt = init_lm_state(cfg, tx, jax.random.key(0), mesh)
        step = make_lm_train_step(cfg, tx, mesh, donate=False)
        out[dp] = step(params, opt, shard_tokens_2d(jnp.asarray(tokens), mesh))
    (p1, _, l1, c1), (p2, _, l2, c2) = out[1], out[2]
    np.testing.assert_allclose(l1, l2, rtol=1e-5)
    # what a shard counts of itself (its passes, its buffer) adds up over the mesh
    own = lambda k: "mean" in k or "passes" in k or "buffer_rows" in k
    assert {k: np.asarray(v).tolist() for k, v in c1.items() if not own(k)} == {
        k: np.asarray(v).tolist() for k, v in c2.items() if not own(k)}
    assert c1["moe_passes_per_layer"].tolist() == [1, 1] and c2["moe_passes_per_layer"].tolist() == [4, 4]
    assert int(c1["moe_rows_here"]) + 0 == int(np.sum(c1["moe_rows_here_per_layer"]))
    for a, b in zip(jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-4)


def test_flash_and_remat_and_bfloat16_run_the_same_model(monkeypatch):
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    pub, cfg, plain, tokens = _setup(seed=10)
    want, _ = apply_mla_moe(cfg, stacked(plain), tokens)
    fast = load_lm_config(pub, attention_impl="flash", remat=True)
    got, _ = jax.jit(partial(apply_mla_moe, fast))(stacked(plain), tokens)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)
    half = load_lm_config(pub, attention_impl="flash", compute_dtype=jnp.bfloat16)
    low, _ = apply_mla_moe(half, stacked(plain), tokens)
    assert low.dtype == jnp.bfloat16
    # a near-tie in the routing may choose another expert for a few tokens
    assert float(jnp.mean(jnp.abs(low.astype(jnp.float32) - want))) < 0.05


def test_dense_family_keeps_its_three_values_and_the_new_one_adds_counters():
    from ps_pytorch_tpu.models.transformer import TransformerConfig

    assert lm_family(TransformerConfig()).counters is None
    assert lm_family(MlaMoeConfig()).counters is not None
    assert lm_family(MlaMoeConfig(num_hidden_layers=1)).counters is None  # dense layers only
    mesh = make_mesh_2d(1, 1)
    dense = TransformerConfig(vocab_size=64, dim=32, depth=1, heads=2, max_seq_len=16)
    tx = optax.sgd(0.1)
    params, opt = init_lm_state(dense, tx, jax.random.key(0), mesh)
    tok = shard_tokens_2d(jnp.zeros((2, 16), jnp.int32), mesh)
    assert len(make_lm_train_step(dense, tx, mesh, donate=False)(params, opt, tok)) == 3


def test_what_the_family_cannot_express_is_refused():
    with pytest.raises(ValueError, match="q_lora_rank"):
        load_lm_config({**PUBLISHED, "q_lora_rank": 1536})
    with pytest.raises(ValueError, match="model_type"):
        load_lm_config({**PUBLISHED, "model_type": "mystery"})
    with pytest.raises(ValueError, match="n_group"):
        load_lm_config({**PUBLISHED, "n_group": 8})
    with pytest.raises(ValueError, match="not a share"):
        load_lm_config({**PUBLISHED, "experts_held": 8, "expert_offset": 12})
    with pytest.raises(ValueError, match="top_k"):
        moe.DroplessSpec(num_experts=4, top_k=6, experts_held=4)
    cfg = load_lm_config(copy.deepcopy(PUBLISHED))
    with pytest.raises(NotImplementedError, match="exchange"):
        moe.moe_dropless_local(jnp.zeros((1, 4, 64)), {}, cfg.routing, jnp.float32, axis_name="expert")
    from ps_pytorch_tpu.parallel.pp import make_pp_train_step
    from ps_pytorch_tpu.parallel.tp import make_tp_train_step

    for build, args in ((make_tp_train_step, ()), (make_pp_train_step, (2,))):
        with pytest.raises(NotImplementedError, match="dp_sp"):
            build(cfg, optax.sgd(0.1), None, *args)
    from ps_pytorch_tpu.serve.engine import ServeConfig, ServingEngine

    with pytest.raises(NotImplementedError, match="serving engine"):
        ServingEngine(cfg, {}, ServeConfig())


def test_rms_statistics_are_float32():
    x = (jnp.arange(64, dtype=jnp.float32) * 100).astype(jnp.bfloat16)[None]
    assert _rms32(x, jnp.ones((64,)), 1e-6).dtype == jnp.float32
