"""The sliding-window / global grouped-query attention LM family whose
experts are routed from the attention's input (models/prerouted_moe.py over
models/swa_moe.gqa_attention, models/mla_moe.ffn_half and the dropless layer
of parallel/moe.py under its second router kind, a router input of its own
and a ReLU gate) against its plain reference, benchmark/reference/
smallthinker_swa_moe.py: the one reference, the file the benchmark's
`correct` runs at the published widths. Small sizes at the published ratios:
6 query heads over 2 key/value heads of 16, a window of 24 under T 80, 8
experts, 3 a token, 2 held; a global layer without positions and three
sliding ones with rotary. Seeded weights from benchmark/weights.py, float32
on the CPU. The comparisons of the whole model with the reference (logits,
loss, every gradient leaf, the faults that must fail) are in
tests/test_prerouted_moe_reference.py, so that `--dist loadfile` can give
the two files to two workers; it imports the configuration and the helpers
from here."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import spec, weights
from benchmark.drivers.lm_config_train import stacked, unstacked
from benchmark.reference import smallthinker_swa_moe as ref
from ps_pytorch_tpu.models import lm, prerouted_moe
from ps_pytorch_tpu.models.lm import lm_family, load_lm_config
from ps_pytorch_tpu.models.prerouted_moe import apply_prerouted_moe
from ps_pytorch_tpu.parallel import moe
from ps_pytorch_tpu.parallel.dp_sp import (
    init_lm_state, make_lm_train_step, make_mesh_2d, shard_tokens_2d)

CONFIG = os.path.join(spec.BENCH_DIR, "configs", "smallthinker_21b_a3b_ep4.json")
PUBLISHED = {
    "model_type": "smallthinker", "model_name": "tiny", "vocab_size": 97, "hidden_size": 64,
    "num_hidden_layers": 4, "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-6, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 3, "moe_ffn_hidden_size": 32,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "sliding_window_size": 24, "sliding_window_layout": [0, 1, 1, 1],
    "rope_layout": [0, 1, 1, 1], "rope_theta": 10000, "rope_scaling": None,
    "tie_word_embeddings": False, "experts_held": 2, "expert_offset": 0,
}
B, T = 2, 80
GROUPS = ("experts",)


@pytest.fixture()
def kernels(monkeypatch):
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")


def _weights(seed=3, pub=PUBLISHED):
    """benchmark/weights.py's, with the norm gains moved off one, so that a
    gain left out, or the router on the wrong norm, shows."""
    plain = weights.make_weights(ref.param_shapes(pub), seed)
    bump = lambda g, i: g + 0.1 * jnp.cos(jnp.arange(g.size, dtype=jnp.float32) + i)
    for i, blk in enumerate(plain["blocks"]):
        blk["ln1"], blk["ln2"] = bump(blk["ln1"], i), bump(blk["ln2"], i + 0.5)
    plain["out_norm"] = bump(plain["out_norm"], 9)
    return plain


def _tokens(seed=1, b=B, t=T):
    return jnp.asarray(weights.token_rows(seed, b, t, PUBLISHED["vocab_size"]))


def _loss_and_logits(cfg, params, tokens):
    logits, _ = apply_prerouted_moe(cfg, params, tokens)
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)) / (
        tokens.shape[0] * (tokens.shape[1] - 1)), logits


def _ref_loss(plain, tokens, pub=PUBLISHED):
    return sum(ref.nll_sum(pub, plain, row) for row in tokens) / (
        tokens.shape[0] * (tokens.shape[1] - 1))


@functools.cache
def _reference():
    """(logits, loss, gradients in the program's stacked form) of the
    reference on _weights() and _tokens()."""
    plain, tokens = _weights(), _tokens()

    def both(p):
        loss, grads = jax.value_and_grad(lambda p: _ref_loss(p, tokens))(p)
        return jnp.stack([ref.logits_fn(PUBLISHED, p, row) for row in tokens]), loss, grads

    logits, loss, grads = jax.jit(both)(plain)
    return logits, loss, stacked(grads, GROUPS)


def test_flash_remat_and_bfloat16_run_the_same_model(kernels):
    """The benchmark's options against the reference. `remat` under the flash
    kernels gives the same bits as without it in float32 (the route is
    re-made with the block); in bfloat16 the loss is the same and to
    bfloat16's rounding the reference's."""
    tokens, params = _tokens(), stacked(_weights(), GROUPS)
    loss = lambda **run: jax.jit(jax.value_and_grad(lambda p: _loss_and_logits(
        load_lm_config(PUBLISHED, attention_impl="flash", **run), p, tokens)[0]))(params)
    (plain, g_plain), (kept, g_kept) = loss(), loss(remat=True)
    assert float(plain) == float(kept)
    for a, b in zip(jax.tree_util.tree_leaves(g_plain), jax.tree_util.tree_leaves(g_kept)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    half, _ = loss(compute_dtype=jnp.bfloat16)
    half_kept, _ = loss(compute_dtype=jnp.bfloat16, remat=True)
    assert float(half) == float(half_kept)
    np.testing.assert_allclose(float(half), float(_reference()[1]), rtol=2e-3)


def _by_hand(logits, k, kind, bias=None, scale=1.0):
    """(chosen ids, weights) a row, in Python: sort, pick, weigh."""
    idx, w = [], []
    for row in np.asarray(logits, np.float64):
        s = row if kind == "softmax_topk" else 1 / (1 + np.exp(-row))
        chosen = sorted(range(len(row)), key=lambda e: -(s[e] + (0 if bias is None else bias[e])))[:k]
        if kind == "softmax_topk":
            e = np.exp(row[chosen] - row[chosen].max())
            idx.append(chosen), w.append(e / e.sum())
        else:
            idx.append(chosen), w.append(s[chosen] / s[chosen].sum() * scale)
    return np.asarray(idx), np.asarray(w)


@pytest.mark.parametrize("kind", ["sigmoid", "softmax_topk"])
def test_dropless_route_is_the_hand_written_top_k_under_both_score_kinds(kind):
    """parallel/moe.dropless_route against a top-k written out in Python
    (float64): `sigmoid` picks by score plus bias and renormalises the
    scores themselves, scaled; `softmax_topk` picks the largest logits and
    takes a softmax over those alone, which is a softmax over all of them
    renormalised over the chosen, and reads no bias."""
    n, d, e, k = 40, 16, 12, 4
    x = jax.random.normal(jax.random.key(0), (n, d))
    router = jax.random.normal(jax.random.key(1), (d, e))
    bias = 0.3 * np.asarray(jax.random.normal(jax.random.key(2), (e,)))
    spec = moe.DroplessSpec(num_experts=e, top_k=k, experts_held=e, routed_scale=2.5, scores=kind)
    idx, w = moe.dropless_route(x, router, None if kind == "softmax_topk" else jnp.asarray(bias),
                                spec)
    logits = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    want_idx, want_w = _by_hand(logits, k, kind, bias if kind == "sigmoid" else None, 2.5)
    assert np.array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(w, want_w, rtol=2e-6)
    if kind == "softmax_topk":
        np.testing.assert_allclose(np.sum(w, axis=-1), 1.0, rtol=1e-6)
        full = np.exp(logits - logits.max(-1, keepdims=True))
        full = np.take_along_axis(full / full.sum(-1, keepdims=True), want_idx, axis=-1)
        np.testing.assert_allclose(w, full / full.sum(-1, keepdims=True), rtol=2e-6)


def test_the_spec_refuses_a_choice_it_does_not_know_and_a_route_it_was_not_promised():
    for over, named in (({"scores": "softmax"}, "scores='softmax'"),
                        ({"activation": "gelu"}, "activation='gelu'"),
                        ({"router_input": "embedding"}, "router_input='embedding'")):
        with pytest.raises(ValueError, match=named):
            moe.DroplessSpec(num_experts=8, top_k=2, experts_held=8, **over)
    cfg = load_lm_config(PUBLISHED)
    blk = stacked(_weights(), GROUPS)["blocks"][0]
    n = jnp.ones((1, 8, 64))
    with pytest.raises(ValueError, match="router_input='attention_norm'"):
        moe.moe_dropless_local(n, blk, cfg.routing, jnp.float32)


@functools.partial(jax.jit, static_argnums=(3,))
def _program_share(n_route, m, share, routing):
    route = moe.route_tokens(n_route[None], share, routing)
    return moe.moe_dropless_local(m[None], share, routing, jnp.float32, route=route)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _reference_share(n_route, m, blk, held, off):
    """The reference's routed sum over experts off .. off + held - 1 of the
    8 (`blk` holds those alone)."""
    cut, mm = {**PUBLISHED, "experts_held": held, "expert_offset": off}, ref._mm(None)
    return ref.routed_experts(m, ref.route_weights(cut, n_route, blk, mm), blk, mm)


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The routed parts of all four shares of one expert layer (2 of 8
    experts each, top 3, softmax over the chosen logits, the router on other
    rows than the experts, a ReLU gate) are the reference's layer with all 8
    held; nothing is computed by every chip alike (no shared expert)."""
    pub = {**PUBLISHED, "experts_held": 8}
    blk = weights.make_weights(ref.param_shapes(pub)["blocks"][1], 5)
    whole = stacked(blk, GROUPS)
    n_route = jax.random.normal(jax.random.key(1), (T, 64))
    m = jax.random.normal(jax.random.key(2), (T, 64))
    want = _reference_share(n_route, m, blk, 8, 0)
    routed, rows, active, entries = 0.0, 0, 0, 0
    for off in (0, 2, 4, 6):
        cfg = load_lm_config({**PUBLISHED, "experts_held": 2, "expert_offset": off})
        share = {**whole, "experts": jax.tree_util.tree_map(lambda a: a[off:off + 2], whole["experts"])}
        y, stats = _program_share(n_route, m, share, cfg.routing)
        routed, rows = routed + y[0], rows + int(jnp.sum(stats["counts"]))
        active, entries = active + int(stats["gate_active"]), entries + int(stats["gate_entries"])
        # and each share is the reference's share
        part = {**blk, "experts": blk["experts"][off:off + 2]}
        np.testing.assert_allclose(y[0], _reference_share(n_route, m, part, 2, off), atol=2e-5)
    assert rows == T * 3 and entries == T * 3 * 32   # every assignment lands on one share
    assert 0.4 < active / entries < 0.6
    np.testing.assert_allclose(routed, want, atol=5e-5)
    # the router read n_route: the experts' own rows in its place give another layer
    other = _reference_share(m, m, blk, 8, 0)
    assert float(jnp.max(jnp.abs(other - want))) > 0.1


def _gate_share(blk, x, spec):
    route = moe.route_tokens(x, blk, spec)
    _, stats = moe.moe_dropless_local(x, blk, spec, jnp.float32, route=route)
    return moe.routing_counters(moe.stack_layers([stats]))


def test_the_gates_share_reads_a_half_at_symmetric_weights_and_the_forced_signs():
    """`moe_gate_active` over the rows routed here: a half where the gate's
    pre-activations are symmetric about zero, 1.0 and 0.0 where every one is
    forced positive or negative; a SiLU layer counts none."""
    spec = moe.DroplessSpec(num_experts=8, top_k=3, experts_held=4, scores="softmax_topk",
                            router_input="attention_norm", activation="relu")
    d, f = 32, 64
    key = jax.random.split(jax.random.key(4), 4)
    x = jnp.abs(jax.random.normal(key[0], (2, 96, d)))           # every row positive
    blk = {"router": jax.random.normal(key[1], (d, 8)),
           "experts": {"w_gate": jax.random.normal(key[2], (4, d, f)),
                       "w_up": jax.random.normal(key[3], (4, d, f)),
                       "w_down": jax.random.normal(key[3], (4, f, d))}}
    fresh = _gate_share(blk, x - 0.8, spec)                      # rows of both signs
    assert abs(float(fresh["moe_gate_active"]) - 0.5) < 0.03
    assert fresh["moe_gate_active_per_layer"].shape == (1,)
    for sign, share in ((1.0, 1.0), (-1.0, 0.0)):
        forced = {**blk, "experts": {**blk["experts"],
                                     "w_gate": sign * jnp.abs(blk["experts"]["w_gate"])}}
        got = _gate_share(forced, x, spec)
        assert float(got["moe_gate_active"]) == share and int(got["moe_rows_here"]) > 0
    silu = moe.DroplessSpec(num_experts=8, top_k=3, experts_held=4)
    _, stats = moe.moe_dropless_local(x, {**blk, "router_bias": jnp.zeros((8,))}, silu, jnp.float32)
    assert set(stats) == {"counts", "unserved", "passes", "buffer_rows"}
    assert "moe_gate_active" not in moe.routing_counters(moe.stack_layers([stats]))


def test_the_routes_gradient_reaches_the_first_norms_gain_and_not_the_seconds():
    """With the attention and the experts' own rows held still, the only way
    the loss reaches a norm's gain is through the router's weights: it does
    through `ln1`, and `ln2` feeds the experts alone."""
    cfg = load_lm_config(PUBLISHED)
    blk = stacked(_weights(), GROUPS)["blocks"][1]
    x = jax.random.normal(jax.random.key(3), (1, T, 64))
    pos = jnp.arange(T)
    frozen = jax.lax.stop_gradient

    def through_route(gains):
        part = {**blk, "ln1": gains["ln1"], "ln2": gains["ln2"]}
        route = moe.route_tokens(prerouted_moe._rms32(x, part["ln1"], 1e-6), part, cfg.routing)
        # the experts read rows no gain reaches
        y, _ = moe.moe_dropless_local(frozen(prerouted_moe._rms32(x, part["ln2"], 1e-6)), part,
                                      cfg.routing, jnp.float32, route=route)
        return jnp.sum(jnp.square(y))

    g = jax.grad(through_route)({"ln1": blk["ln1"], "ln2": blk["ln2"]})
    assert float(jnp.max(jnp.abs(g["ln1"]))) > 1e-3 and not np.any(g["ln2"])
    # and in the block itself both gains get a gradient, the router's only from the first
    attend = lambda q, k, v: q
    block = lambda b: jnp.sum(jnp.square(
        prerouted_moe.prerouted_block(cfg, 1, 1, x, b, attend, pos)[0]))
    whole = jax.grad(block)(blk)
    assert np.any(whole["ln1"]) and np.any(whole["ln2"]) and np.any(whole["router"])
    routed_only = jax.grad(lambda r: block({**blk, "router": r}))(blk["router"])
    np.testing.assert_allclose(routed_only, whole["router"], rtol=1e-6)


def test_a_layer_without_rotary_runs_no_rotation_pass():
    """The global layer's jaxpr holds no cos or sin; a sliding layer's does."""
    cfg = load_lm_config(PUBLISHED)
    blk = stacked(_weights(), GROUPS)["blocks"][0]
    x = jnp.ones((1, T, 64))
    attend = lambda q, k, v: q
    text = lambda sliding, rotary: str(jax.make_jaxpr(
        lambda x: prerouted_moe.prerouted_block(cfg, sliding, rotary, x, blk, attend,
                                                jnp.arange(T))[0])(x))
    assert " cos " not in text(0, 0) and " sin " not in text(0, 0)
    assert " cos " in text(1, 1) and " sin " in text(1, 1)


def test_the_step_returns_the_routing_counters_with_the_gates_share_and_holds_its_scopes(kernels):
    cfg = load_lm_config(PUBLISHED, attention_impl="flash", remat=True)
    mesh = make_mesh_2d(2, 1)
    tx = optax.adam(1e-3)
    params, opt = init_lm_state(cfg, tx, jax.random.key(0), mesh)
    step = make_lm_train_step(cfg, tx, mesh)
    out = step(params, opt, shard_tokens_2d(_tokens(5, b=4), mesh))
    assert len(out) == 4 and np.isfinite(float(out[2]))
    counters = out[3]
    assert {"moe_rows_here", "moe_rows_max_over_mean", "moe_tokens_unserved", "moe_passes",
            "moe_gate_active", "moe_gate_active_per_layer"} <= set(counters)
    assert counters["moe_rows_here_per_layer"].shape == (4,)
    assert counters["moe_gate_active_per_layer"].shape == (4,)
    assert np.all(np.abs(np.asarray(counters["moe_gate_active_per_layer"]) - 0.5) < 0.1)
    assert 0 < int(counters["moe_rows_here"]) <= 4 * 4 * T * 3
    scopes = {row["scope"] for row in step.scopes()["by_place"]}
    assert {"mixer/swa", "mixer/swa/rope", "mixer/swa/kv_repeat", "mixer/swa/flash",
            "mixer/attention", "mixer/attention/kv_repeat", "mixer/attention/flash",
            "ffn/moe/route", "ffn/moe/dispatch", "ffn/moe/experts", "ffn/moe/combine",
            "head_loss"} <= scopes, sorted(scopes)
    # nothing rotates in the global layer, nothing gates either kind, no dense MLP
    assert not {"mixer/attention/rope", "mixer/swa/gate", "mixer/attention/gate",
                "ffn/mlp"} & scopes


REFUSALS = [
    ({"moe_primary_router_apply_softmax": False}, "moe_primary_router_apply_softmax=False"),
    ({"norm_topk_prob": False}, "norm_topk_prob=False"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling=.*yarn"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings=True"),
    ({"sliding_window_layout": [0, 1, 1]}, "sliding_window_layout names 3 layers"),
    ({"rope_layout": [0, 1, 2, 1]}, r"rope_layout names 4 layers of kinds \[0, 1, 2\]"),
    ({"num_key_value_heads": 4}, "num_key_value_heads=4 has to divide"),
    ({"experts_held": 9}, "are not a share of 8"),
]


@pytest.mark.parametrize("over, named", REFUSALS, ids=[next(iter(o)) + f"_{i}"
                                                        for i, (o, _) in enumerate(REFUSALS)])
def test_what_the_family_cannot_express_is_refused_by_name(over, named):
    with pytest.raises(ValueError, match=named):
        load_lm_config({**PUBLISHED, **over})


def test_a_missing_key_is_named():
    lacking = {k: v for k, v in PUBLISHED.items() if k != "sliding_window_size"}
    with pytest.raises(ValueError, match=r"config lacks \['sliding_window_size'\]"):
        load_lm_config(lacking)


def test_a_sequence_axis_of_two_is_refused_and_the_messages_read_one_table():
    cfg = load_lm_config(PUBLISHED)
    mesh = make_mesh_2d(1, 2)
    tx = optax.adam(1e-3)
    params, opt = init_lm_state(cfg, tx, jax.random.key(0), mesh)
    tokens = shard_tokens_2d(jnp.zeros((2, 128), jnp.int32), mesh)
    with pytest.raises(NotImplementedError, match="sliding window.*ROADMAP M5.*--num-sp 1"):
        make_lm_train_step(cfg, tx, mesh)(params, opt, tokens)
    with pytest.raises(TypeError, match="SwaMoeConfig, PreroutedMoeConfig"):
        lm_family(object())
    with pytest.raises(NotImplementedError,
                       match="smallthinker: moe_primary_router_apply_softmax false"):
        lm.require_dense(cfg, "tensor parallelism")
    assert isinstance(cfg, prerouted_moe.PreroutedMoeConfig)
    assert lm_family(cfg).counters is not None


def test_the_program_holds_the_parameters_the_configuration_states():
    """At the published widths, from shapes alone: the benchmark's file
    builds, the program's tree is the reference's, its count is ISSUE 49's
    arithmetic; and the catalog's 52 layers build too."""
    with open(CONFIG) as f:
        pub = json.load(f)
    cfg = load_lm_config(CONFIG)
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            cfg.moe_ffn_hidden_size, cfg.moe_num_primary_experts,
            cfg.moe_num_active_primary_experts, cfg.experts_held, cfg.sliding_window_size,
            cfg.rope_theta, cfg.rms_norm_eps, cfg.vocab_size) == (
        2560, 28, 4, 128, 768, 64, 6, 16, 4096, 1500000, 1e-6, 18992)
    assert cfg.layer_kinds() == ((1, 1, 3), (0, 0, 1))
    spec_ = cfg.routing
    assert (spec_.scores, spec_.router_input, spec_.activation, spec_.routed_scale) == (
        "softmax_topk", "attention_norm", "relu", 1.0)
    tree = jax.eval_shape(lambda: lm_family(cfg).init(cfg, jax.random.key(0)))
    assert weights.same_tree(jax.eval_shape(lambda t: unstacked(t, GROUPS), tree),
                             ref.param_shapes(pub))
    assert "router_bias" not in tree["blocks"][0] and "shared" not in tree["blocks"][0]
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(t))
    attention = 2 * 2560 * 28 * 128 + 2 * 2560 * 4 * 128
    expert = 3 * 2560 * 768
    layer = attention + 2560 * 64 + 2 * 2560 + 16 * expert
    assert (attention, expert, layer) == (20_971_520, 5_898_240, 115_512_320)
    assert count(tree) == pub["parameters"] == 4 * layer + 2 * 18992 * 2560 + 2560 == 559_290_880
    # the catalog's 52 layers at all 64 experts and the whole vocabulary
    whole = load_lm_config({**pub, "num_hidden_layers": 52, "vocab_size": 151936,
                            "experts_held": 64, "sliding_window_layout": [0, 1, 1, 1] * 13,
                            "rope_layout": [0, 1, 1, 1] * 13})
    full = jax.eval_shape(lambda: lm_family(whole).init(whole, jax.random.key(0)))
    assert len(full["blocks"]) == 52 and round(count(full) / 1e9, 2) == 21.51
    assert whole.layer_kinds() == ((1, 1, 39), (0, 0, 13))


def test_the_flops_module_counts_the_entries_each_mask_keeps():
    from benchmark import flops
    from ps_pytorch_tpu.ops.flash_attention import SlidingWindow, dense_mask

    with open(CONFIG) as f:
        pub = json.load(f)
    k = flops.load(pub["flops"])
    for t in (300, 5000):
        assert k.score_entries(pub, 1, t) == int(dense_mask(SlidingWindow(4096), t, t).sum())
        assert k.score_entries(pub, 0, t) == int(dense_mask(True, t, t).sum())
    traffic = {"batch_rows": 1, "seq_len": 16384}
    band, half = 4096 * 4097 // 2 + (16384 - 4096) * 4096, 16384 * 16385 // 2
    assert round(band / half, 3) == 0.437
    assert k.flash_train_step(pub, traffic)["flops"] == 28 * (3 * band + half) * 7 * 2 * 128


def test_train_lm_traces_a_plan_a_layer_kind_the_moe_plan_and_the_gate_at_log_steps(
        tmp_path, kernels):
    from ps_pytorch_tpu.cli import train_lm
    from ps_pytorch_tpu.obs import schema

    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(PUBLISHED))
    out = train_lm.main([
        "--lm-config", str(path), "--num-dp", "1", "--num-sp", "1", "--seq-len", "80",
        "--batch-size", "2", "--max-steps", "4", "--log-interval", "2", "--optimizer", "adam",
        "--lr", "1e-3", "--train-size", "8", "--attention-impl", "flash", "--remat",
        "--trace", str(tmp_path / "trace"), "--metrics-file", str(tmp_path / "metrics.jsonl")])
    assert np.isfinite(out["loss"])
    spans = [json.loads(line) for line in open(tmp_path / "trace" / "trace_train_lm_p0.jsonl")]
    for span in spans:
        schema.validate_event(span)
    sliding, glob = [s for s in spans if s.get("name") == "flash_plan"]
    assert (sliding["mask"], sliding["window"], sliding["heads"], sliding["kv_heads"],
            sliding["layers"], sliding["rotary"]) == ("sliding_window", 24, 6, 2, 3, "default")
    assert (glob["mask"], glob["window"], glob["heads"], glob["layers"], glob["rotary"]) == (
        "causal", 0, 6, 1, "none")
    # heads of 16 here: the plain rotation; the layer without positions runs none
    assert (sliding["rope_path"], sliding["rope_dims"], glob["rope_path"]) == ("xla", 16, "none")
    for plan in (sliding, glob):
        assert plan["tiles_run"] <= plan["tiles_total"] and 0 < plan["tile_fill"] <= 1
        assert plan["remat_saves"].startswith("ps_flash_o,ps_flash_lse")
    assert sliding["saved_bytes_per_layer"] == glob["saved_bytes_per_layer"]    # one head count
    (plan,) = [s for s in spans if s.get("name") == "moe_plan"]
    assert {k: plan[k] for k in ("scores", "router_input", "activation", "experts", "top_k",
                                 "experts_held", "pass_rows", "shared_expert")} == {
        "scores": "softmax_topk", "router_input": "attention_norm", "activation": "relu",
        "experts": 8, "top_k": 3, "experts_held": 2, "shared_expert": False,
        "pass_rows": moe.pass_rows(80, load_lm_config(PUBLISHED).routing)}
    states = [s for s in spans if s.get("name") == "moe_route"]
    assert len(states) == 3 and all(0.35 < s["gate_active"] < 0.65 for s in states)
    assert all(len(s["gate_active_per_layer"]) == 4 for s in states)
    for line in open(tmp_path / "metrics.jsonl"):
        rec = json.loads(line)
        schema.validate_event(rec)
        if rec.get("kind") == "train_lm":
            assert 0.35 < rec["moe_gate_active"] < 0.65 and rec["moe_rows_here"] > 0
