"""The hybrid delta-rule / latent-attention expert LM family
(models/kda_hybrid.py, the chunked rule of ops/kda.py, the FFN half and the
latent attention of models/mla_moe.py) against its plain reference,
benchmark/reference/kimi_linear_kda_mla_moe.py: the one reference, the file
the benchmark's `correct` runs at the published widths. Small sizes (4 KDA
heads of 16, chunks of 16 over T 40: two whole chunks and a ragged tail; 4
latent-attention heads of 16 + 8 and 16; 16 experts, 3 a token, 8 held;
layers KDA KDA KDA MLA KDA, the first one dense), seeded weights from
benchmark/weights.py, float32 on the CPU. The comparisons of the whole model
with the reference (logits, loss, every gradient leaf) are in
tests/test_kda_hybrid_reference.py, so that `--dist loadfile` can give the
two files to two workers; it imports the configuration and `_setup` from
here."""

import json
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import spec, weights
from benchmark.drivers.lm_config_train import stacked
from benchmark.reference import kimi_linear_kda_mla_moe as ref
from ps_pytorch_tpu.models import kda_hybrid, lm, mla_moe
from ps_pytorch_tpu.models.kda_hybrid import apply_kda_hybrid
from ps_pytorch_tpu.models.lm import lm_family, load_lm_config
from ps_pytorch_tpu.parallel.dp_sp import (
    init_lm_state, make_lm_train_step, make_mesh_2d, shard_tokens_2d)
from ps_pytorch_tpu.parallel.moe import combine_rows_read, moe_dropless_local, no_routing

PUBLISHED = {
    "model_type": "kimi_linear", "vocab_size": 101, "hidden_size": 64, "num_hidden_layers": 5,
    "num_attention_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32, "num_experts": 16,
    "num_experts_per_token": 3, "num_shared_experts": 1, "first_k_dense_replace": 1,
    "routed_scaling_factor": 2.446, "moe_renormalize": True, "mla_use_nope": True,
    "rope_theta": 10000, "rms_norm_eps": 1e-5, "moe_router_activation_func": "sigmoid",
    "num_expert_group": 1, "topk_group": 1, "q_lora_rank": None, "rope_scaling": None,
    "tie_word_embeddings": False, "num_nextn_predict_layers": 0, "hidden_act": "silu",
    "linear_attn_config": {"kda_layers": [1, 2, 3, 5], "full_attn_layers": [4], "num_heads": 4,
                           "head_dim": 16, "short_conv_kernel_size": 4},
    "kda_chunk_size": 16, "experts_held": 8, "expert_offset": 0,
}
B, T = 2, 40
GROUPS = ("experts",)


def _source_decays(plain, seed):
    """The decay parameters as the source initialises them (A in [1, 16], dt
    in [1e-3, 1e-1]) and a live correction bias: benchmark/weights.py makes
    every such vector zero."""
    key = jax.random.key(seed)
    for i, blk in enumerate(plain["blocks"]):
        k = jax.random.split(jax.random.fold_in(key, i), 3)
        if "router_bias" in blk:
            blk["router_bias"] = 0.05 * jax.random.normal(k[2], blk["router_bias"].shape)
        if "a_log" not in blk:
            continue
        step = jnp.exp(jax.random.uniform(k[0], blk["dt_bias"].shape,
                                          minval=np.log(1e-3), maxval=np.log(1e-1)))
        blk.update(a_log=jnp.log(jax.random.uniform(k[1], blk["a_log"].shape, minval=1.0, maxval=16.0)),
                   dt_bias=step + jnp.log(-jnp.expm1(-step)))
    return plain


def _setup(seed=3, decays="source", **over):
    pub = {**PUBLISHED, **over}
    cfg = load_lm_config(pub, attention_impl="naive")
    plain = weights.make_weights(ref.param_shapes(pub), seed)
    if decays == "source":
        plain = _source_decays(plain, seed)
    tokens = jnp.asarray(weights.token_rows(seed, B, T, pub["vocab_size"]))
    return pub, cfg, plain, stacked(plain, GROUPS), tokens


def _prog_loss(cfg, params, tokens):
    return _loss_and_logits(cfg, params, tokens)[0]


def _loss_and_logits(cfg, params, tokens):
    """The next-token loss and the logits it reads, from one forward."""
    logits, _ = apply_kda_hybrid(cfg, params, tokens)
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)) / (
        tokens.shape[0] * (tokens.shape[1] - 1)), logits


def _loss_logits_and_grads(cfg, params, tokens):
    """((loss, logits), gradients) of one compiled program."""
    return jax.jit(jax.value_and_grad(partial(_loss_and_logits, cfg), has_aux=True))(
        params, tokens)


def _ref_loss(pub, plain, tokens):
    return sum(ref.nll_sum(pub, plain, row) for row in tokens) / (
        tokens.shape[0] * (tokens.shape[1] - 1))


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The routed parts of all four shares of one expert layer (4 of 16
    experts each), with the shared expert counted once, are the reference's
    layer with all 16 held."""
    pub = {**PUBLISHED, "experts_held": 16}
    plain = _source_decays(weights.make_weights(ref.param_shapes(pub), 5), 5)
    blk, whole = plain["blocks"][2], stacked(plain, GROUPS)["blocks"][2]
    n = jax.random.normal(jax.random.key(1), (T, 64))
    want = ref._expert_ffn(pub, n, blk, ref._mm(None))
    routed, rows, unserved = 0.0, 0, []
    for off in (0, 4, 8, 12):
        cfg = load_lm_config({**PUBLISHED, "experts_held": 4, "expert_offset": off})
        share = {**whole, "experts": jax.tree_util.tree_map(lambda a: a[off:off + 4], whole["experts"])}
        y, stats = moe_dropless_local(n[None], share, cfg.routing, jnp.float32)
        routed, rows = routed + y[0], rows + int(jnp.sum(stats["counts"]))
        unserved.append(int(stats["unserved"]))
    assert rows == T * 3 and max(unserved) < T         # every assignment lands on one share
    got = routed + mla_moe._gated_mlp(n, whole["shared"], jnp.float32)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.max(jnp.abs(want))))
    # and one share alone is not the layer
    assert float(jnp.max(jnp.abs(y[0] + mla_moe._gated_mlp(n, whole["shared"], jnp.float32) - want))) \
        > 1e-2 * float(jnp.max(jnp.abs(want)))


def test_flash_and_remat_and_bfloat16_run_the_same_model(monkeypatch):
    """Flash (interpreted) and remat (the two halves of a block apart, the
    mixer's elementwise stretches inside them) in float32 are the naive
    program, in value and in gradient; bfloat16 blocks stay near it: a
    near-tie in the routing may choose another expert for a few tokens, so
    the mean is held, as for models/mla_moe.py."""
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    pub, cfg, _, params, tokens = _setup(seed=9)
    fast = load_lm_config(pub, attention_impl="flash", remat=True)
    (l32, base), g32 = _loss_logits_and_grads(cfg, params, tokens)
    (l_r, got), g_r = _loss_logits_and_grads(fast, params, tokens)
    np.testing.assert_allclose(got, base, atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(l_r, l32, rtol=1e-6)
    for a, r in zip(jax.tree_util.tree_leaves(g_r), jax.tree_util.tree_leaves(g32)):
        assert float(jnp.max(jnp.abs(a - r))) <= 1e-4 * float(jnp.max(jnp.abs(r))) + 1e-9
    half = load_lm_config(pub, attention_impl="flash", remat=True, compute_dtype=jnp.bfloat16)
    l16, low = jax.jit(partial(_loss_and_logits, half))(params, tokens)
    assert low.dtype == jnp.bfloat16
    assert float(jnp.mean(jnp.abs(low.astype(jnp.float32) - base))) < 0.03 * float(
        jnp.max(jnp.abs(base)))
    assert abs(float(l16 - l32)) < 1e-2 * float(l32)


def test_mla_use_nope_is_the_rotation_left_out():
    """The latent attention of this family is mla_attention without both
    rotations: with `mla_use_nope` false the same weights give other logits,
    and shuffling the tokens before t moves nothing at t or later."""
    pub, cfg, _, params, tokens = _setup(seed=6)
    rotated = load_lm_config({**pub, "mla_use_nope": False}, attention_impl="naive")
    base = apply_kda_hybrid(cfg, params, tokens)[0]
    assert float(jnp.max(jnp.abs(apply_kda_hybrid(rotated, params, tokens)[0] - base))) > 1e-4
    from ps_pytorch_tpu.parallel.ring_attention import full_attention

    blk = params["blocks"][3]
    n = jax.random.normal(jax.random.key(1), (1, T, 64))
    perm = jnp.concatenate([jax.random.permutation(jax.random.key(2), 24), jnp.arange(24, T)])
    attend, pos = partial(full_attention, causal=True), jnp.arange(T)
    out = mla_moe.mla_attention(cfg, n, blk, attend, pos)
    moved = mla_moe.mla_attention(cfg, n[:, perm], blk, attend, pos)
    np.testing.assert_allclose(moved[:, 24:], out[:, 24:], atol=1e-5)
    assert float(jnp.max(jnp.abs(moved[:, :24] - out[:, :24]))) > 1e-3


@pytest.mark.parametrize("over, named", [
    ({"moe_router_activation_func": "softmax"}, "moe_router_activation_func"),
    ({"num_expert_group": 8}, "num_expert_group"),
    ({"topk_group": 4}, "topk_group"),
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
    ({"linear_attn_config": {**PUBLISHED["linear_attn_config"], "kda_layers": [1, 2, 3]}},
     "linear_attn_config"),
    ({"experts_held": 17}, "not a share of 16"),
])
def test_what_the_family_cannot_express_is_refused_by_name(over, named):
    with pytest.raises(ValueError, match=named):
        load_lm_config({**PUBLISHED, **over})


def test_a_sequence_axis_of_two_is_refused_and_the_messages_read_one_table():
    cfg = load_lm_config(PUBLISHED)
    mesh = make_mesh_2d(1, 2)
    tx = optax.adam(1e-3)
    params, opt = init_lm_state(cfg, tx, jax.random.key(0), mesh)
    tokens = shard_tokens_2d(jnp.zeros((2, 32), jnp.int32), mesh)
    with pytest.raises(NotImplementedError, match="carried state.*sequence shard"):
        make_lm_train_step(cfg, tx, mesh)(params, opt, tokens)
    with pytest.raises(ValueError, match=r"\(has: " + ", ".join(lm._PUBLISHED_FAMILIES) + r"\)"):
        load_lm_config({"model_type": "llama"})
    with pytest.raises(TypeError, match="MlaMoeConfig, SsmHybridConfig, KdaHybridConfig"):
        lm_family(object())
    with pytest.raises(NotImplementedError, match="kimi_linear: a router activation other than"):
        lm.require_dense(cfg, "tensor parallelism")


def test_the_step_returns_both_kinds_of_counter_as_its_fourth_value():
    cfg = load_lm_config(PUBLISHED)
    mesh = make_mesh_2d(2, 1)
    tx = optax.adam(1e-3)
    params, opt = init_lm_state(cfg, tx, jax.random.key(0), mesh)
    tokens = shard_tokens_2d(jnp.asarray(weights.token_rows(1, 4, 32, 101)), mesh)
    out = make_lm_train_step(cfg, tx, mesh)(params, opt, tokens)
    assert len(out) == 4 and np.isfinite(float(out[2]))
    assert {"kda_chunks_cut_off", "kda_chunks_cut_off_per_layer", "moe_rows_here",
            "moe_rows_max_over_mean", "moe_tokens_unserved"} <= set(out[3])
    assert out[3]["kda_chunks_cut_off_per_layer"].shape == (4,)
    assert out[3]["moe_rows_here_per_layer"].shape == (4,)
    # the init draws decays as the source does: no chunk of 16 tokens is cut off
    assert int(out[3]["kda_chunks_cut_off"]) == 0
    # every assignment of the global batch is counted once: 4 x 32 tokens, 3 each, half held
    assert 0 < int(out[3]["moe_rows_here"]) < 4 * 4 * 32 * 3


def test_the_program_holds_the_parameters_the_configuration_states():
    """At the published widths, from shapes alone: the program's tree is
    the reference's, and its count is the file's (ISSUE 33's arithmetic)."""
    from benchmark.drivers.lm_config_train import unstacked

    path = os.path.join(spec.BENCH_DIR, "configs", "kimi_linear_48b_a3b_ep32.json")
    with open(path) as f:
        pub = json.load(f)
    cfg = load_lm_config(path)
    tree = jax.eval_shape(lambda: lm_family(cfg).init(cfg, jax.random.key(0)))
    assert weights.same_tree(jax.eval_shape(partial(unstacked, groups=GROUPS), tree),
                             ref.param_shapes(pub))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(t))
    assert count(tree) == pub["parameters"] == 602_434_432
    kda = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32 + 3 * 4 * 4096 + 32 + 4096 + 128
    assert kda == 39_514_272                                   # the KDA mixer
    mla = 2304 * 32 * 192 + 2304 * 576 + 512 + 512 * 32 * 256 + 4096 * 2304
    assert mla == 29_114_880                                   # the latent attention
    expert = 3 * 2304 * 1024
    ffn = 2304 * 256 + 256 + 9 * expert                        # router, bias, shared, eight held
    assert count(tree["blocks"][0]) == kda + 2 * 2304 + 3 * 2304 * 9216 == 103_219_872
    assert count(tree["blocks"][1]) == kda + 2 * 2304 + ffn == 103_809_952
    assert count(tree["blocks"][3]) == mla + 2 * 2304 + ffn == 93_410_560
    assert count(tree) == 103_219_872 + 3 * 103_809_952 + 93_410_560 + 2 * 20480 * 2304 + 2304
    assert (cfg.kda_layers, cfg.full_attn_layers) == ((1, 2, 3, 5), (4,))
    assert (cfg.kda_inner, cfg.qk_head_dim, cfg.kda_chunk_size, cfg.moe_layers) == (4096, 192, 64, 4)
    spec_ = cfg.routing
    assert (spec_.num_experts, spec_.top_k, spec_.experts_held, spec_.routed_scale) == (256, 8, 8, 2.446)


# ------------------------------------------------ the block that was split

def _welded_block(cfg, x, blk, attend, pos):
    """models/mla_moe.mla_moe_block as it stood before its two halves were
    named (PR 32's text), for the comparison below."""
    cd = cfg.effective_compute_dtype
    x = x.astype(cd)
    x = x + mla_moe.mla_attention(cfg, mla_moe._rms32(x, blk["ln1"], cfg.rms_norm_eps).astype(cd),
                                  blk, attend, pos)
    n32 = mla_moe._rms32(x, blk["ln2"], cfg.rms_norm_eps)
    if "mlp" in blk:
        return x + mla_moe._gated_mlp(n32.astype(cd), blk["mlp"], cd), no_routing(cfg.experts_held)
    routed, stats = moe_dropless_local(n32, blk, cfg.routing, cd)
    # the one line younger than PR 32's text: the half counts what its combine read (PR 53)
    stats["combine_rows_read"] = combine_rows_read(
        stats, n32.shape[0] * n32.shape[1] * cfg.routing.top_k, n32.shape[2], cd)
    return x + routed.astype(cd) + mla_moe._gated_mlp(n32.astype(cd), blk["shared"], cd), stats


def _renumbered(text):
    order = {}
    return re.sub(r"@[\w.]+", lambda m: order.setdefault(m.group(), f"@f{len(order)}"), text)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_a_kanana_shaped_step_lowers_to_the_text_of_the_welded_block(monkeypatch, remat):
    """The split of mla_moe_block into its mixer half and its FFN half, and
    the rotation taken from the config, change nothing a deepseek_v3 config
    compiles (the same comparison against PR 32's tree, by hand: CHANGES.md)."""
    kanana = {"model_type": "deepseek_v3", "vocab_size": 101, "hidden_size": 64,
              "num_hidden_layers": 3, "num_attention_heads": 4, "kv_lora_rank": 32,
              "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
              "intermediate_size": 128, "moe_intermediate_size": 32, "n_routed_experts": 16,
              "n_shared_experts": 2, "num_experts_per_tok": 3, "first_k_dense_replace": 1,
              "routed_scaling_factor": 2.448, "norm_topk_prob": True, "rope_theta": 1e6,
              "rms_norm_eps": 1e-6, "q_lora_rank": None, "rope_scaling": None,
              "scoring_func": "sigmoid", "rope_interleave": True, "experts_held": 8}
    cfg = load_lm_config(kanana, remat=remat, compute_dtype=jnp.bfloat16)
    tx = optax.adam(1e-3)
    params = lm_family(cfg).init(cfg, jax.random.key(0))
    tokens = jnp.zeros((2, 32), jnp.int32)

    def lowered():
        step = make_lm_train_step(cfg, tx, make_mesh_2d(1, 1), donate=False)
        return _renumbered(step.lower(params, tx.init(params), tokens).as_text())

    split = lowered()
    monkeypatch.setattr(mla_moe, "mla_moe_block", _welded_block)
    assert split == lowered()


def test_train_lm_traces_the_plan_once_and_the_state_at_log_steps(tmp_path):
    from ps_pytorch_tpu.cli import train_lm
    from ps_pytorch_tpu.obs import schema

    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(PUBLISHED))
    out = train_lm.main([
        "--lm-config", str(path), "--num-dp", "1", "--num-sp", "1", "--seq-len", "40",
        "--batch-size", "2", "--max-steps", "4", "--log-interval", "2", "--optimizer", "adam",
        "--lr", "1e-3", "--train-size", "8", "--trace", str(tmp_path / "trace"),
        "--metrics-file", str(tmp_path / "metrics.jsonl")])
    assert np.isfinite(out["loss"])
    spans = [json.loads(line) for line in open(tmp_path / "trace" / "trace_train_lm_p0.jsonl")]
    plans = [s for s in spans if s.get("name") == "kda_plan"]
    assert len(plans) == 1
    assert {k: plans[0][k] for k in kda_hybrid.kda_plan(load_lm_config(PUBLISHED), 40)} == {
        "chunk": 16, "sub_block": 1, "n_chunks": 3, "padded_len": 48, "heads": 4, "d_head": 16,
        "kda_layers": 4, "attention_layers": 1, "scan_path": "xla", "conv_path": "xla"}
    states = [s for s in spans if s.get("name") == "kda_state"]
    assert all(set(s) >= {"chunks_cut_off", "chunks_cut_off_per_layer"} for s in states)
    assert all(len(s["chunks_cut_off_per_layer"]) == 4 for s in states)
    assert not any(k.startswith(("rows_", "tokens_")) for s in states for k in s)
    routes = [s for s in spans if s.get("name") == "moe_route"]
    assert routes and not any("chunks_cut_off" in s for s in routes)
    # the dropless layer's passes ride the same instant: one a layer here
    assert all(s["passes_per_layer"] == [1] * 4 and s["passes"] == 4 for s in routes)
    assert all(s["buffer_rows_per_layer"] == [s["buffer_rows"] // 4] * 4 for s in routes)
    logged = 0
    for rec in map(json.loads, open(tmp_path / "metrics.jsonl")):
        schema.validate_event(rec)
        if rec.get("kind") == "train_lm":
            logged += 1
            assert isinstance(rec["kda_chunks_cut_off"], int)
            assert isinstance(rec["moe_rows_here"], int)
            assert rec["moe_passes"] == 4 and isinstance(rec["moe_buffer_rows"], int)
    assert len(states) == len(routes) == logged == 3   # steps 1, 2 and 4: one instant a log step
