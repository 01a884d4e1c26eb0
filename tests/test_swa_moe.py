"""The sliding-window / global grouped-query attention expert LM family
(models/swa_moe.py over ops/flash_attention.SlidingWindow and the FFN half
of models/mla_moe.py) against its plain reference, benchmark/reference/
laguna_swa_gqa_moe.py: the one reference, the file the benchmark's `correct`
runs at the published widths. Small sizes at the published ratios: 6 and 4
query heads over 2 key/value heads of 16, a window of 24 under T 80, YaRN on
the first half of a global head, 32 experts, 10 a token, 8 held; layers
global + dense, three sliding and a global one over experts. Seeded weights
from benchmark/weights.py, float32 on the CPU. The comparisons of the whole
model with the reference (logits, loss, every gradient leaf, the faults that
must fail) are in tests/test_swa_moe_reference.py, so that `--dist loadfile`
can give the two files to two workers; it imports the configuration and the
helpers from here."""

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
from benchmark.reference import laguna_swa_gqa_moe as ref
from ps_pytorch_tpu.models import lm, swa_moe
from ps_pytorch_tpu.models.lm import lm_family, load_lm_config
from ps_pytorch_tpu.models.swa_moe import FULL, SLIDING, apply_swa_moe
from ps_pytorch_tpu.parallel.dp_sp import (
    init_lm_state, make_lm_train_step, make_mesh_2d, shard_tokens_2d)
from ps_pytorch_tpu.parallel.moe import moe_dropless_local

CONFIG = os.path.join(spec.BENCH_DIR, "configs", "laguna_s_2_1_ep32.json")
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 97, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 5, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 4096, "attention_bias": False, "rms_norm_eps": 1e-6,
    "num_experts": 32, "num_experts_per_tok": 10, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "norm_topk_prob": True, "decoder_sparse_step": 1,
    "mlp_only_layers": [0], "tie_word_embeddings": False, "gating": "per-head",
    "sliding_window": 24,
    "rope_parameters": {
        FULL: {"rope_theta": 100, "rope_type": "yarn", "factor": 8,
               "original_max_position_embeddings": 32, "beta_slow": 1, "beta_fast": 4,
               "attention_factor": 1.2, "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}},
    "layer_types": [FULL, SLIDING, SLIDING, SLIDING, FULL],
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "gating_types": ["per_head"] * 5, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4], "moe_router_logit_softcapping": 0,
    "experts_held": 8, "expert_offset": 0,
}
B, T = 2, 80
GROUPS = ("experts",)


@pytest.fixture()
def kernels(monkeypatch):
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")


def _weights(seed=3, pub=PUBLISHED):
    """benchmark/weights.py's, with the norm gains moved off one and a live
    correction bias, so that a gain left out or a bias in the weights shows."""
    plain = weights.make_weights(ref.param_shapes(pub), seed)
    bump = lambda g, i: g + 0.1 * jnp.cos(jnp.arange(g.size, dtype=jnp.float32) + i)
    for i, blk in enumerate(plain["blocks"]):
        blk["ln1"], blk["ln2"] = bump(blk["ln1"], i), bump(blk["ln2"], i + 0.5)
        if "router_bias" in blk:
            blk["router_bias"] = 0.05 * jax.random.normal(
                jax.random.key(seed + i), blk["router_bias"].shape)
    plain["out_norm"] = bump(plain["out_norm"], 9)
    return plain


def _tokens(seed=1, b=B, t=T):
    return jnp.asarray(weights.token_rows(seed, b, t, PUBLISHED["vocab_size"]))


def _loss_and_logits(cfg, params, tokens):
    logits, _ = apply_swa_moe(cfg, params, tokens)
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
    kernels gives the same bits as without it in float32; in bfloat16 the
    loss is the same and to bfloat16's rounding the reference's (the
    gradients there differ by roundings XLA:CPU's excess precision skips in
    one program and not in the other: with --xla_allow_excess_precision=false
    they too are equal to the bit, my CPU run, PR 45)."""
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


def test_the_rotary_is_the_references_and_the_yarn_blend_is_the_written_out_one():
    """The program's frequencies against the reference's table, and the YaRN
    blend against numbers worked by hand at the tiny sizes: r = 8 rotated
    dims, theta 100, 32 positions: the correction dims are floor(0.21) = 0
    and ceil(1.41) = 2, so the ramp over the four pairs is 0, 1/2, 1, 1."""
    cfg = load_lm_config(PUBLISHED)
    for kind in (FULL, SLIDING):
        freqs, scale = cfg.rope(kind).frequencies(16)
        cos, sin = ref.rotary_table(PUBLISHED["rope_parameters"][kind], 16, T)
        ang = np.arange(T, dtype=np.float32)[:, None] * freqs[None]
        np.testing.assert_allclose(np.cos(ang) * scale, cos, atol=2e-6)
        np.testing.assert_allclose(np.sin(ang) * scale, sin, atol=2e-6)
    freqs, scale = cfg.rope(FULL).frequencies(16)
    base = 100.0 ** (-np.arange(4) / 4)
    np.testing.assert_allclose(freqs, base * [1, (1 + 1 / 8) / 2, 1 / 8, 1 / 8], rtol=1e-6)
    assert scale == 1.2 and cfg.rope(SLIDING).frequencies(16)[0].shape == (8,)
    # the second half of a global head passes through; a sliding head turns whole
    x = jnp.ones((1, 3, 1, 16))
    turned = swa_moe._rope_leading(x, jnp.arange(3) + 5, cfg.rope(FULL))
    assert np.array_equal(turned[..., 8:], x[..., 8:]) and not np.any(turned[..., :8] == 1.0)
    # at the published sizes: 64 rotated dims, those that turn more than 32
    # times in 8,192 positions keep f_j, those that turn less than once f_j / 128
    with open(CONFIG) as f:
        big = load_lm_config(json.load(f))
    f, scale = big.rope(FULL).frequencies(128)
    base = 500000.0 ** (-np.arange(32) / 32)
    turns = base * 8192 / (2 * np.pi)
    assert f.shape == (32,) and scale == 1.4852030263919618
    np.testing.assert_allclose(f[turns > 40], base[turns > 40], rtol=1e-6)
    np.testing.assert_allclose(f[turns < 0.8], base[turns < 0.8] / 128, rtol=1e-6)
    assert np.all(np.diff(f) < 0) and np.all((f <= base * (1 + 1e-6)) & (f >= base / 128 * (1 - 1e-6)))


@functools.partial(jax.jit, static_argnums=(2,))
def _program_share(n, share, routing):
    return moe_dropless_local(n[None], share, routing, jnp.float32)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _reference_share(n, blk, held, off, shared):
    """The reference's routed sum over experts off .. off + held - 1 of the
    32 (`blk` holds those alone), with the shared expert where asked."""
    cut, mm = {**PUBLISHED, "experts_held": held, "expert_offset": off}, ref._mm(None)
    return ref._expert_ffn(cut, n, blk, mm) if shared else ref.routed_experts(cut, n, blk, mm)


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The routed parts of all four shares of one expert layer (8 of 32
    experts each, top 10), with the shared expert counted once, are the
    reference's layer with all 32 held."""
    pub = {**PUBLISHED, "experts_held": 32}
    blk = weights.make_weights(ref.param_shapes(pub)["blocks"][2], 5)   # one expert layer
    blk["router_bias"] = 0.05 * jax.random.normal(jax.random.key(7), blk["router_bias"].shape)
    whole = stacked(blk, GROUPS)
    n = jax.random.normal(jax.random.key(1), (T, 64))
    want = _reference_share(n, blk, 32, 0, True)
    routed, rows, unserved = 0.0, 0, []
    for off in (0, 8, 16, 24):
        cfg = load_lm_config({**PUBLISHED, "experts_held": 8, "expert_offset": off})
        share = {**whole, "experts": jax.tree_util.tree_map(lambda a: a[off:off + 8], whole["experts"])}
        y, stats = _program_share(n, share, cfg.routing)
        routed, rows = routed + y[0], rows + int(jnp.sum(stats["counts"]))
        unserved.append(int(stats["unserved"]))
        # and each share is the reference's share
        part = {**blk, "experts": blk["experts"][off:off + 8]}
        np.testing.assert_allclose(y[0], _reference_share(n, part, 8, off, False), atol=2e-5)
    assert rows == T * 10 and max(unserved) < T        # every assignment lands on one share
    np.testing.assert_allclose(routed + ref._gated(n, blk["shared"], ref._mm(None)), want,
                               atol=5e-5)


def test_the_step_returns_both_groups_of_counters_and_holds_the_new_scopes(kernels):
    cfg = load_lm_config(PUBLISHED, attention_impl="flash", remat=True)
    mesh = make_mesh_2d(2, 1)
    tx = optax.adam(1e-3)
    params, opt = init_lm_state(cfg, tx, jax.random.key(0), mesh)
    step = make_lm_train_step(cfg, tx, mesh)
    out = step(params, opt, shard_tokens_2d(_tokens(5, b=4), mesh))
    assert len(out) == 4 and np.isfinite(float(out[2]))
    counters = out[3]
    assert {"attn_gate_open", "attn_gate_open_per_layer", "moe_rows_here",
            "moe_rows_max_over_mean", "moe_tokens_unserved"} <= set(counters)
    assert counters["attn_gate_open_per_layer"].shape == (5,)
    assert counters["moe_rows_here_per_layer"].shape == (4,)
    # a fresh gate is open by about a half, in every layer
    assert np.all(np.abs(np.asarray(counters["attn_gate_open_per_layer"]) - 0.5) < 0.1)
    assert abs(float(counters["attn_gate_open"]) - 0.5) < 0.05
    assert 0 < int(counters["moe_rows_here"]) <= 4 * 4 * T * 10
    scopes = {row["scope"] for row in step.scopes()["by_place"]}
    for mixer in ("mixer/swa", "mixer/attention"):
        assert {mixer, mixer + "/rope", mixer + "/gate", mixer + "/kv_repeat",
                mixer + "/flash"} <= scopes, (mixer, sorted(scopes))
    assert {"ffn/moe/route", "ffn/moe/experts", "ffn/mlp", "head_loss"} <= scopes


REFUSALS = [
    ({"gating": True}, "gating=True"),
    ({"gating": "per-channel"}, "gating='per-channel'"),
    ({"gating_types": ["per_head"] * 4 + ["per_channel"]}, "gating_types=.*per_channel"),
    ({"moe_router_logit_softcapping": 30.0}, "moe_router_logit_softcapping=30.0"),
    ({"moe_apply_router_weight_on_input": True}, "moe_apply_router_weight_on_input=True"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings=True"),
    ({"attention_bias": True}, "attention_bias=True"),
    ({"rope_parameters": {**PUBLISHED["rope_parameters"],
                          SLIDING: {"rope_type": "llama3", "rope_theta": 1e4}}},
     "rope_type='llama3' in a rope_parameters group"),
    ({"layer_types": [FULL, "chunked_attention", SLIDING, SLIDING, FULL]}, "chunked_attention"),
    ({"num_attention_heads_per_layer": [4, 6, 6, 6]}, "name 5, 4 and 5 layers"),
    ({"num_attention_heads_per_layer": [4, 5, 6, 6, 4]}, "num_key_value_heads=2 has to divide"),
    ({"experts_held": 40}, "are not a share of 32"),
]


@pytest.mark.parametrize("over, named", REFUSALS, ids=[f"{next(iter(o))}_{i}"
                                                        for i, (o, _) in enumerate(REFUSALS)])
def test_what_the_family_cannot_express_is_refused_by_name(over, named):
    with pytest.raises(ValueError, match=named):
        load_lm_config({**PUBLISHED, **over})


def test_a_missing_key_is_named():
    lacking = {k: v for k, v in PUBLISHED.items() if k != "sliding_window"}
    with pytest.raises(ValueError, match=r"config lacks \['sliding_window'\]"):
        load_lm_config(lacking)


def test_a_sequence_axis_of_two_is_refused_and_the_messages_read_one_table():
    cfg = load_lm_config(PUBLISHED)
    mesh = make_mesh_2d(1, 2)
    tx = optax.adam(1e-3)
    params, opt = init_lm_state(cfg, tx, jax.random.key(0), mesh)
    tokens = shard_tokens_2d(jnp.zeros((2, 128), jnp.int32), mesh)
    with pytest.raises(NotImplementedError, match="sliding window.*ROADMAP M5.*--num-sp 1"):
        make_lm_train_step(cfg, tx, mesh)(params, opt, tokens)
    with pytest.raises(TypeError, match="EvaByteConfig, SwaMoeConfig"):
        lm_family(object())
    with pytest.raises(NotImplementedError, match="laguna: a rope_type other than default and yarn"):
        lm.require_dense(cfg, "tensor parallelism")
    assert isinstance(cfg, swa_moe.SwaMoeConfig) and lm_family(cfg).counters is not None


def test_the_program_holds_the_parameters_the_configuration_states():
    """At the published widths, from shapes alone: the benchmark's file
    builds, the program's tree is the reference's, its count is ISSUE 45's
    arithmetic; and the catalog's 48 layers build too."""
    with open(CONFIG) as f:
        pub = json.load(f)
    cfg = load_lm_config(CONFIG)
    assert (cfg.hidden_size, cfg.num_key_value_heads, cfg.head_dim, cfg.intermediate_size,
            cfg.num_experts, cfg.num_experts_per_tok, cfg.experts_held, cfg.sliding_window,
            cfg.vocab_size, cfg.num_attention_heads_per_layer) == (
        3072, 8, 128, 12288, 256, 10, 8, 512, 12544, (48, 72, 72, 72, 48))
    assert cfg.layer_kinds() == ((SLIDING, 72, 3), (FULL, 48, 2))
    tree = jax.eval_shape(lambda: lm_family(cfg).init(cfg, jax.random.key(0)))
    assert weights.same_tree(jax.eval_shape(lambda t: unstacked(t, GROUPS), tree),
                             ref.param_shapes(pub))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(t))
    attention = lambda h: 2 * 3072 * h * 128 + 2 * 3072 * 8 * 128 + 3072 * h
    assert attention(72) == 63_135_744
    experts = 8 * 3 * 3072 * 1024 + 3 * 3072 * 1024 + 3072 * 256 + 256
    layer0 = attention(48) + 3 * 3072 * 12288 + 2 * 3072
    sliding, glob = attention(72) + experts + 2 * 3072, attention(48) + experts + 2 * 3072
    assert (layer0, sliding, glob) == (157_440_000, 148_863_232, 129_915_136)
    assert count(tree) == pub["parameters"] == layer0 + 3 * sliding + glob + (
        2 * 12544 * 3072 + 3072) == 811_018_240
    # the catalog's 48 layers at all 256 experts and the whole vocabulary
    whole = load_lm_config({
        **pub, "num_hidden_layers": 48, "vocab_size": 100352, "experts_held": 256,
        "layer_types": [FULL, SLIDING, SLIDING, SLIDING] * 12,
        "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
        "mlp_layer_types": ["dense"] + ["sparse"] * 47, "gating_types": ["per_head"] * 48})
    full = jax.eval_shape(lambda: lm_family(whole).init(whole, jax.random.key(0)))
    assert len(full["blocks"]) == 48 and round(count(full) / 1e9, 1) == 117.6
    # benchmark/weights.py draws the gate (a matrix) and leaves the bias zero
    drawn = weights.make_weights(ref.param_shapes(PUBLISHED), 5)["blocks"][1]
    assert float(jnp.std(drawn["wg"])) > 0.05 and not np.any(drawn["router_bias"])


def test_the_flops_module_counts_the_entries_each_mask_keeps():
    from benchmark import flops
    from ps_pytorch_tpu.ops.flash_attention import SlidingWindow, dense_mask

    with open(CONFIG) as f:
        pub = json.load(f)
    k = flops.load(pub["flops"])
    for t in (300, 1024):
        assert k.score_entries(pub, SLIDING, t) == int(dense_mask(SlidingWindow(512), t, t).sum())
        assert k.score_entries(pub, FULL, t) == int(dense_mask(True, t, t).sum())
    traffic = {"batch_rows": 1, "seq_len": 8192}
    both, swa = k.flash_train_step(pub, traffic), k.swa_flash_train_step(pub, traffic)
    per = lambda heads, entries: heads * entries * 7 * 2 * 128
    band, half = 512 * 513 // 2 + (8192 - 512) * 512, 8192 * 8193 // 2
    assert swa["flops"] == 3 * per(72, band) and both["flops"] == swa["flops"] + 2 * per(48, half)
    assert swa["bytes"] == 3 * 12 * 8192 * 72 * 128 * 2
    # the global layers' kernels carry about five times a sliding layer's work
    assert 4.5 < per(48, half) / per(72, band) < 6.0
    assert round(k.train_flops_per_item(pub, traffic) / 3 / 1e9, 2) == 1.22


def test_train_lm_traces_a_plan_a_layer_kind_and_the_gate_at_log_steps(tmp_path, kernels):
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
    assert (sliding["mask"], sliding["window"], sliding["heads"], sliding["layers"],
            sliding["layer_type"]) == ("sliding_window", 24, 6, 3, SLIDING)
    assert (glob["mask"], glob["window"], glob["heads"], glob["layers"]) == ("causal", 0, 4, 2)
    # heads of 16 here: the plain rotation, and no tile of `ps_rope` to name
    assert all(plan["rope_path"] == "xla" and "rope_block_t" not in plan for plan in (sliding, glob))
    assert (sliding["rope_dims"], glob["rope_dims"]) == (16, 8)      # a whole head, and YaRN on half
    for plan in (sliding, glob):
        assert plan["tiles_run"] <= plan["tiles_total"] and 0 < plan["tile_fill"] <= 1
        assert plan["remat_saves"].startswith("ps_flash_o,ps_flash_lse")
    # each plan carries its own kind's bytes: a layer's o and lse at its heads
    assert sliding["saved_bytes_per_layer"] * 4 == glob["saved_bytes_per_layer"] * 6
    states = [s for s in spans if s.get("name") == "attn_state"]
    assert len(states) == 3 and all(0.4 < s["gate_open"] < 0.6 for s in states)
    assert all(len(s["gate_open_per_layer"]) == 5 for s in states)
    assert len([s for s in spans if s.get("name") == "moe_route"]) == 3
    for line in open(tmp_path / "metrics.jsonl"):
        rec = json.loads(line)
        schema.validate_event(rec)
        if rec.get("kind") == "train_lm":
            assert 0.4 < rec["attn_gate_open"] < 0.6 and rec["moe_rows_here"] > 0
