"""The hybrid state-space / attention LM family (models/ssm_hybrid.py, the
chunked scan of ops/ssd.py) against its plain reference,
benchmark/reference/granite_hybrid_ssm.py: the one reference, the file the
benchmark's `correct` runs at the published widths. Small sizes (4 mamba
heads of 16, state 16, chunks of 16 over T 40: two whole chunks and a ragged
tail; 4 query over 2 key/value heads; pattern m m a m), seeded weights from
benchmark/weights.py, float32 on the CPU. The comparisons of the whole model
with the reference (logits, loss, every gradient leaf, three Adam steps through the
benchmark's own check) are in tests/test_ssm_hybrid_reference.py, a file of
its own so that `--dist loadfile` can give the two to two workers; it imports
the configuration and `_setup` from here."""

import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import spec, weights
from benchmark.reference import granite_hybrid_ssm as ref
from ps_pytorch_tpu.models import lm, ssm_hybrid
from ps_pytorch_tpu.models.lm import lm_family, load_lm_config
from ps_pytorch_tpu.models.ssm_hybrid import apply_ssm_hybrid, gqa_attention
from ps_pytorch_tpu.ops import ssd
from ps_pytorch_tpu.parallel.dp_sp import (
    init_lm_state, make_lm_train_step, make_mesh_2d, shard_tokens_2d)
from ps_pytorch_tpu.parallel.ring_attention import full_attention

CELL = "granite4hm_train_remat_1period"
PUBLISHED = {
    "model_type": "granitemoehybrid", "vocab_size": 101, "hidden_size": 64,
    "num_hidden_layers": 4, "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "shared_intermediate_size": 128,
    "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 16, "mamba_n_groups": 1,
    "mamba_d_conv": 4, "mamba_chunk_size": 16, "mamba_expand": 1, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "attention_multiplier": 0.0625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 8, "rms_norm_eps": 1e-5,
    "position_embedding_type": "nope", "num_local_experts": 0, "num_experts_per_tok": 0,
    "tie_word_embeddings": True, "hidden_act": "silu", "attention_bias": False,
}
B, T = 2, 40


def _source_decays(plain, seed):
    """The decay parameters as the source initialises them (A in [1, 16], dt
    in [1e-3, 1e-1]), a random D and a live conv bias: benchmark/weights.py
    makes D (`skip/scale`) one and the other vectors zero."""
    key = jax.random.key(seed)
    for i, blk in enumerate(plain["blocks"]):
        if "in_proj" not in blk:
            continue
        k = jax.random.split(jax.random.fold_in(key, i), 4)
        n = blk["a_log"].shape[0]
        step = jnp.exp(jax.random.uniform(k[0], (n,), minval=np.log(1e-3), maxval=np.log(1e-1)))
        blk.update(a_log=jnp.log(jax.random.uniform(k[1], (n,), minval=1.0, maxval=16.0)),
                   dt_bias=step + jnp.log(-jnp.expm1(-step)),
                   skip={"scale": jax.random.normal(k[2], (n,))},
                   conv_b=0.1 * jax.random.normal(k[3], blk["conv_b"].shape))
    return plain


def _setup(seed=3, decays="source"):
    pub = dict(PUBLISHED)
    cfg = load_lm_config(pub, attention_impl="naive")
    plain = weights.make_weights(ref.param_shapes(pub), seed)
    if decays == "source":
        plain = _source_decays(plain, seed)
    tokens = jnp.asarray(weights.token_rows(seed, B, T, pub["vocab_size"]))
    return pub, cfg, plain, tokens


def _prog_loss(cfg, params, tokens):
    return _loss_and_logits(cfg, params, tokens)[0]


def _loss_and_logits(cfg, params, tokens):
    """The next-token loss and the logits it reads, from one forward."""
    logits, _ = apply_ssm_hybrid(cfg, params, tokens)
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)) / (
        tokens.shape[0] * (tokens.shape[1] - 1)), logits


def _ref_loss(pub, plain, tokens):
    return sum(ref.nll_sum(pub, plain, row) for row in tokens) / (
        tokens.shape[0] * (tokens.shape[1] - 1))


def _ref_logits(pub, plain, tokens):
    return jnp.stack([ref.logits_fn(pub, plain, row) for row in tokens])


def _scan_inputs(decays, seed=0, t=T, g=2):
    """x, dt, a_log, B, C, D for the scan alone: 4 heads of 16 in `g` groups."""
    k = jax.random.split(jax.random.key(seed), 8)
    h, p, n = 4, 16, 16
    x = jax.random.normal(k[0], (B, t, h, p))
    bm, cm = jax.random.normal(k[1], (B, t, g, n)), jax.random.normal(k[2], (B, t, g, n))
    if decays == "source":
        dt = jnp.exp(jax.random.uniform(k[3], (B, t, h), minval=np.log(1e-3), maxval=np.log(1e-1)))
        a_log = jnp.log(jax.random.uniform(k[4], (h,), minval=1.0, maxval=16.0))
    else:                       # A_log = 0, dt_bias = 0: dt = softplus of a projection
        dt = jax.nn.softplus(jax.random.normal(k[3], (B, t, h)))
        a_log = jnp.zeros((h,))
    return x, dt, a_log, bm, cm, jax.random.normal(k[5], (h,))


def _both_scans():
    chunked = lambda x, dt, a_log, bm, cm, d: ssd.ssd_chunked(
        x, dt, -jnp.exp(a_log), bm, cm, d, 16)[0]
    plain = lambda x, dt, a_log, bm, cm, d: ssd.ssd_recurrence(x, dt, -jnp.exp(a_log), bm, cm, d)
    return chunked, plain


@pytest.mark.parametrize("decays", ["source", "benchmark"])
def test_chunked_scan_is_the_recurrence_in_value_and_gradient(decays):
    args = _scan_inputs(decays)
    chunked, plain = _both_scans()
    want = plain(*args)
    np.testing.assert_allclose(chunked(*args), want, atol=2e-5 * float(jnp.max(jnp.abs(want))))
    probe = jax.random.normal(jax.random.key(9), want.shape)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(probe * f(*a)), argnums=range(6))(*args)
    for name, g, r in zip(("x", "dt", "a_log", "B", "C", "D"), grads(chunked), grads(plain)):
        assert np.any(r), name
        assert float(jnp.max(jnp.abs(g - r))) <= 5e-5 * float(jnp.max(jnp.abs(r))), name


def test_a_zeroed_carried_state_fails_at_source_decays_and_hides_at_the_benchmarks(monkeypatch):
    """The state really crosses chunks where decays are the source's; at the
    benchmark's weights (A = -1, dt about 0.8) it halves every token, and a
    scan that forgets it is wrong in a chunk's first tokens only (PERF.md
    section 7)."""
    chunked, plain = _both_scans()
    source, bench = _scan_inputs("source", t=48), _scan_inputs("benchmark", t=48)
    monkeypatch.setattr(ssd, "_carry", lambda states, total: jnp.zeros_like(states))
    gap = lambda a: float(jnp.max(jnp.abs(chunked(*a) - plain(*a))) / jnp.max(jnp.abs(plain(*a))))
    assert gap(source) > 0.1
    late = lambda a: np.asarray(jnp.abs(chunked(*a) - plain(*a)))[:, 16:].reshape(B, -1, 16, 4, 16)
    assert late(bench)[:, :, 12:].max() < 0.02 * late(bench)[:, :, :2].max()


def test_a_ragged_tail_is_padded_and_masked():
    args = _scan_inputs("source", seed=2, t=37, g=1)
    chunked, plain = _both_scans()
    np.testing.assert_allclose(chunked(*args), plain(*args), atol=2e-5)


def test_the_counter_counts_chunks_whose_whole_decay_is_zero_in_float32():
    x, dt, a_log, bm, cm, d = _scan_inputs("benchmark", t=48)
    heavy = jnp.full_like(dt, 6.0).at[0, 16:32, 1].set(0.01)     # 16 x 6 > 87; one chunk is not
    _, cut = ssd.ssd_chunked(x, heavy, -jnp.exp(a_log), bm, cm, d, 16)
    assert int(cut) == B * 3 * 4 - 1
    _, none = ssd.ssd_chunked(x, 0.1 * dt, -jnp.exp(a_log), bm, cm, d, 16)
    assert int(none) == 0


def test_the_references_two_forms_of_the_state_space_layer_agree(monkeypatch):
    """The recurrence token by token and the full-sequence dual in blocks
    of query rows: the definition twice, in value and in every gradient."""
    pub, _, plain, tokens = _setup(seed=5)
    loss = jax.jit(jax.value_and_grad(lambda p: ref.nll_sum(pub, p, tokens[0])))
    l_rec, g_rec = loss(plain)
    monkeypatch.setattr(ref, "RECURRENCE_UP_TO", 0)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    l_dual, g_dual = jax.jit(jax.value_and_grad(lambda p: ref.nll_sum(pub, p, tokens[0])))(plain)
    np.testing.assert_allclose(l_dual, l_rec, rtol=1e-6)
    for name, a, b in zip(weights.leaf_names(g_rec), jax.tree_util.tree_leaves(g_dual),
                          jax.tree_util.tree_leaves(g_rec)):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * float(jnp.max(jnp.abs(b))) + 1e-9, name


def test_attention_multiplier_is_the_scale(monkeypatch):
    pub, cfg, plain, tokens = _setup(seed=6)
    want = _ref_logits(pub, plain, tokens)
    # 1/sqrt(head_dim) = 0.25 in the published 0.0625's place fails
    other = load_lm_config({**pub, "attention_multiplier": 0.25}, attention_impl="naive")
    wrong = apply_ssm_hybrid(other, plain, tokens)[0]
    assert float(jnp.max(jnp.abs(wrong - want))) > 1e-4
    np.testing.assert_allclose(apply_ssm_hybrid(cfg, plain, tokens)[0], want, atol=2e-6)


def test_attention_has_no_positional_term():
    """Keys carry no position: shuffling the tokens before t changes the
    attention output at t or later by nothing (the causal sum is over the
    same set), and the output at a shuffled position moves with its token."""
    _, cfg, plain, _ = _setup(seed=7)
    blk = plain["blocks"][2]
    n = jax.random.normal(jax.random.key(1), (1, T, 64))
    perm = jnp.concatenate([jax.random.permutation(jax.random.key(2), 24), jnp.arange(24, T)])
    attend = partial(full_attention, causal=True)
    out, moved = gqa_attention(cfg, n, blk, attend), gqa_attention(cfg, n[:, perm], blk, attend)
    np.testing.assert_allclose(moved[:, 24:], out[:, 24:], atol=1e-5)
    assert float(jnp.max(jnp.abs(moved[:, :24] - out[:, :24]))) > 1e-3


@pytest.mark.parametrize("key, value", [("embedding_multiplier", 1.0),
                                        ("residual_multiplier", 1.0), ("logits_scaling", 1.0)])
def test_each_multiplier_changes_the_logits(key, value):
    pub, cfg, plain, tokens = _setup(seed=8)
    base = apply_ssm_hybrid(cfg, plain, tokens)[0]
    other = apply_ssm_hybrid(load_lm_config({**pub, key: value}, attention_impl="naive"),
                             plain, tokens)[0]
    assert float(jnp.max(jnp.abs(other - base))) > 1e-2 * float(jnp.max(jnp.abs(base)))
    np.testing.assert_allclose(other, _ref_logits({**pub, key: value}, plain, tokens),
                               atol=2e-5, rtol=2e-5)


def test_flash_and_remat_and_bfloat16_run_the_same_model(monkeypatch):
    """Flash (interpreted), remat and bfloat16 blocks against the float32
    naive program: bfloat16 has 8 bits of mantissa (0.4% a rounding) and a
    logit is behind four blocks of them, so the logits (at most 0.13 here)
    agree to 3% of their range, the loss to 1e-3 and the gradient to 5%."""
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    pub, cfg, plain, tokens = _setup(seed=9)
    fast = load_lm_config(pub, attention_impl="flash", remat=True, compute_dtype=jnp.bfloat16)
    # one compiled program a config: the loss, the logits it reads, the gradients
    both = lambda c: jax.jit(jax.value_and_grad(partial(_loss_and_logits, c), has_aux=True))(
        plain, tokens)
    (l32, base), g32 = both(cfg)
    (l16, got), g16 = both(fast)
    assert got.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - base))) < 3e-2 * float(
        jnp.max(jnp.abs(base)))
    assert abs(float(l16 - l32)) < 1e-3 * float(l32)
    norm = lambda t: float(jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(t))))
    assert norm(jax.tree_util.tree_map(jnp.subtract, g16, g32)) < 0.05 * norm(g32)


@pytest.mark.parametrize("over, named", [
    ({"num_local_experts": 64}, "num_local_experts"),
    ({"position_embedding_type": "rope"}, "position_embedding_type"),
    ({"mamba_n_groups": 3}, "mamba_n_groups"),
    ({"layer_types": ["mamba", "window", "attention", "mamba"]}, "layer_types"),
    ({"num_key_value_heads": 3}, "num_key_value_heads"),
    ({"mamba_expand": 2}, "mamba_expand"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings"),
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
    with pytest.raises(ValueError, match=r"has no family here \(has: " + ", ".join(lm._PUBLISHED_FAMILIES) + r"\)"):
        load_lm_config({"model_type": "llama"})
    with pytest.raises(TypeError, match="TransformerConfig, MlaMoeConfig, SsmHybridConfig"):
        lm_family(object())
    with pytest.raises(NotImplementedError, match="granitemoehybrid: routed experts"):
        lm.require_dense(cfg, "tensor parallelism")


def test_the_step_returns_the_counter_as_its_fourth_value():
    cfg = load_lm_config(PUBLISHED)
    mesh = make_mesh_2d(2, 1)
    tx = optax.adam(1e-3)
    params, opt = init_lm_state(cfg, tx, jax.random.key(0), mesh)
    tokens = shard_tokens_2d(jnp.asarray(weights.token_rows(1, 4, 32, 101)), mesh)
    out = make_lm_train_step(cfg, tx, mesh)(params, opt, tokens)
    assert len(out) == 4 and np.isfinite(float(out[2]))
    assert set(out[3]) == {"ssd_chunks_cut_off", "ssd_chunks_cut_off_per_layer"}
    assert out[3]["ssd_chunks_cut_off_per_layer"].shape == (3,)
    # the init draws decays as the source does: no chunk of 16 tokens is cut off
    assert int(out[3]["ssd_chunks_cut_off"]) == 0


def test_the_program_holds_the_parameters_the_configuration_states():
    """At the published widths, from shapes alone: the program's tree is
    the reference's, and its count is the file's (ISSUE 31's arithmetic)."""
    path = os.path.join(spec.BENCH_DIR, "configs", "granite4_h_micro_1period.json")
    with open(path) as f:
        pub = json.load(f)
    cfg = load_lm_config(path)
    tree = jax.eval_shape(lambda: lm_family(cfg).init(cfg, jax.random.key(0)))
    assert weights.same_tree(tree, ref.param_shapes(pub))
    count = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    assert count == pub["parameters"] == 772_160_448
    mamba = 2048 * (4096 + 4352 + 64) + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * 2048
    assert mamba == 25_847_232
    assert count == 9 * (mamba + 50_331_648 + 4096) + (10_485_760 + 50_331_648 + 4096) \
        + 12_544 * 2048 + 2048
    assert (cfg.d_inner, cfg.conv_dim, cfg.mamba_layers, cfg.head_dim) == (4096, 4352, 9, 64)
    assert cfg.layer_types == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


def test_train_lm_traces_the_plan_once_and_the_state_at_log_steps(tmp_path):
    from ps_pytorch_tpu.cli import train_lm
    from ps_pytorch_tpu.obs import schema

    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(PUBLISHED))
    out = train_lm.main([
        "--lm-config", str(path), "--num-dp", "1", "--num-sp", "1", "--seq-len", "32",
        "--batch-size", "2", "--max-steps", "4", "--log-interval", "2", "--optimizer", "adam",
        "--lr", "1e-3", "--train-size", "8", "--trace", str(tmp_path / "trace"),
        "--metrics-file", str(tmp_path / "metrics.jsonl")])
    assert np.isfinite(out["loss"])
    spans = [json.loads(line) for line in open(tmp_path / "trace" / "trace_train_lm_p0.jsonl")]
    plans = [s for s in spans if s.get("name") == "ssd_plan"]
    assert len(plans) == 1
    assert {k: plans[0][k] for k in ssm_hybrid.ssd_plan(load_lm_config(PUBLISHED), 32)} == {
        "chunk": 16, "n_chunks": 2, "heads": 4, "d_head": 16, "d_state": 16, "groups": 1,
        "mamba_layers": 3, "attention_layers": 1, "scan_path": "xla", "conv_path": "xla"}
    states = [s for s in spans if s.get("name") == "ssd_state"]
    assert all(len(s["chunks_cut_off_per_layer"]) == 3 for s in states)
    logged = 0
    for rec in map(json.loads, open(tmp_path / "metrics.jsonl")):
        schema.validate_event(rec)
        if rec.get("kind") == "train_lm":
            logged += 1
            assert isinstance(rec["ssd_chunks_cut_off"], int)
    assert len(states) == logged == 3          # steps 1, 2 and 4: one instant a log step
