"""A sequence axis with one member has no ring.

models/transformer.attention_path is the ONE decision: over an axis of one
member every family takes the within-chip attention (flash_attention or
the naive one), whatever cfg.sp_attention names, and parallel/dp_sp.
lm_loss_local reads the last token's target from its own shard. With two
or more members nothing changes: the ring's permutes, Ulysses' all_to_all
and the boundary target's ppermute are all still in the lowered step.

Both LM families (the dense one and the latent-attention / sparse-expert
one with its 24-wide q/k beside a 16-wide v, the 192 / 128 shape), remat on
and off, the flash kernels under the Pallas interpreter.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from ps_pytorch_tpu.models import transformer
from ps_pytorch_tpu.models.lm import lm_family, load_lm_config
from ps_pytorch_tpu.models.transformer import (
    TransformerConfig,
    attention_path,
    select_attention,
)
from ps_pytorch_tpu.parallel.dp_sp import (
    lm_loss_local,
    make_lm_train_step,
    make_mesh_2d,
)
from ps_pytorch_tpu.parallel.mesh import WORKER_AXIS
from ps_pytorch_tpu.parallel.ring_attention import (
    SEQ_AXIS,
    ring_attention,
    ring_flash_attention,
)
from ps_pytorch_tpu.parallel.ulysses import ulysses_attention

B, T, V = 2, 32, 61
MLA = {
    "model_type": "deepseek_v3", "vocab_size": V, "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 8, "n_shared_experts": 2,
    "num_experts_per_tok": 2, "first_k_dense_replace": 1, "routed_scaling_factor": 2.448,
    "norm_topk_prob": True, "rope_theta": 1000000, "rms_norm_eps": 1e-6, "rope_interleave": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "experts_held": 4, "expert_offset": 0,
}
REMAT = pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
FAMILY = pytest.mark.parametrize("family", ["dense", "mla_moe"])


def _cfg(family, **run):
    if family == "dense":
        return TransformerConfig(vocab_size=V, dim=32, depth=2, heads=2, max_seq_len=T, **run)
    return load_lm_config(MLA, **run)


def _tokens(seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(0, V, (B, T)), jnp.int32)


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")


# ------------------------------------------------ (a) what the step lowers to


def _lowered_step(cfg, sp):
    mesh = make_mesh_2d(1, sp)
    tx = optax.adam(1e-3)
    params = lm_family(cfg).init(cfg, jax.random.key(0))
    step = make_lm_train_step(cfg, tx, mesh, donate=False)
    return step.lower(params, tx.init(params), _tokens()).as_text()


@FAMILY
@REMAT
def test_one_member_step_has_no_collective_over_the_sequence(interpret, family, remat):
    text = _lowered_step(_cfg(family, attention_impl="flash", remat=remat), sp=1)
    assert text.count("collective_permute") == 0
    assert text.count("all_to_all") == 0


# collective_permute ops in the text of the (1, 2) step AT THE PARENT (the
# ring's scan bodies hold K and V forward, K, V, dK and dV backward; one
# more fetches the boundary target): what a change to the selection must
# leave as it was
PARENT_PERMUTES = {("dense", False): 13, ("dense", True): 9,
                   ("mla_moe", False): 13, ("mla_moe", True): 17}


@FAMILY
@REMAT
def test_two_member_step_keeps_the_parents_ring(interpret, family, remat):
    text = _lowered_step(_cfg(family, attention_impl="flash", remat=remat), sp=2)
    assert text.count("collective_permute") == PARENT_PERMUTES[family, remat]
    assert text.count("all_to_all") == 0


# -------------------------------- (b) the (1, 1) step against one device


def _sharded_loss_and_grads(cfg, params, tokens):
    """What dp_sp's worker_fn differentiates, on a (1, 1) mesh."""
    mesh = make_mesh_2d(1, 1)

    def worker(p, tok):
        (loss, _), grads = jax.value_and_grad(
            lambda q: lm_loss_local(cfg, q, tok, SEQ_AXIS), has_aux=True)(p)
        return lax.psum(loss, SEQ_AXIS), lax.psum(grads, SEQ_AXIS)

    return jax.jit(jax.shard_map(
        worker, mesh=mesh, in_specs=(P(), P(WORKER_AXIS, SEQ_AXIS)),
        out_specs=(P(), P()), check_vma=False))(params, tokens)


def _one_device_loss_and_grads(cfg, params, tokens):
    def loss_fn(p):
        logits, _ = lm_family(cfg).apply(cfg, p, tokens, seq_axis_name=None)
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))

    return jax.jit(jax.value_and_grad(loss_fn))(params)


def _assert_trees_close(got, want, rtol=5e-4, atol=5e-5):
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=rtol, atol=atol)


@FAMILY
@REMAT
def test_one_member_step_is_the_single_device_model_and_the_ring_of_one(
        interpret, monkeypatch, family, remat):
    cfg = _cfg(family, attention_impl="flash", remat=remat)
    params = lm_family(cfg).init(cfg, jax.random.key(1))
    tokens = _tokens(1)
    loss, grads = _sharded_loss_and_grads(cfg, params, tokens)
    want_loss, want_grads = _one_device_loss_and_grads(cfg, params, tokens)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    _assert_trees_close(grads, want_grads)
    # the parent's selection: ring_flash_attention itself at n == 1
    monkeypatch.setattr(transformer, "attention_path", lambda c, n: c.sp_attention)
    ring_loss, ring_grads = _sharded_loss_and_grads(cfg, params, tokens)
    np.testing.assert_allclose(float(loss), float(ring_loss), rtol=1e-5)
    _assert_trees_close(grads, ring_grads)


# ------------------------------------------------------- (c) the selection


def _jaxpr(attend, n, d_v=16):
    """The jaxpr text of attend(q, k, v) on [1, 8, 4, 24 | d_v] shards,
    traced where SEQ_AXIS is bound with n members."""
    q = jnp.zeros((1, 8, 4, 24), jnp.float32)
    v = jnp.zeros((1, 8, 4, d_v), jnp.float32)
    return str(jax.make_jaxpr(lambda a, b, c: attend()(a, b, c),
                              axis_env=[(SEQ_AXIS, n)])(q, q, v))


SCHEMES = {
    "ring": dict(sp_attention="ring"),
    "bidirectional_ring": dict(sp_attention="ring", bidirectional_ring=True),
    "ulysses": dict(sp_attention="ulysses"),
}


@pytest.mark.parametrize("impl", ["flash", "naive"])
@pytest.mark.parametrize("scheme", list(SCHEMES))
@FAMILY
def test_one_member_is_local_and_more_keep_their_scheme(interpret, family, scheme, impl):
    cfg = _cfg(family, attention_impl=impl, **SCHEMES[scheme])
    selected = lambda: select_attention(cfg, SEQ_AXIS)
    # one member: the program of the within-chip attention, op for op
    assert attention_path(cfg, 1) == "local"
    one = _jaxpr(selected, 1)
    assert one == _jaxpr(lambda: transformer.local_attention(cfg), 1)
    assert "ppermute" not in one and "all_to_all" not in one
    assert ("pallas_call" in one) == (impl == "flash")
    # two and four: the scheme's own function, called as the parent called it
    own = {
        ("ulysses", impl): partial(ulysses_attention, axis_name=SEQ_AXIS, causal=True, impl=impl),
        ("ring", "flash"): partial(ring_flash_attention, axis_name=SEQ_AXIS, causal=True,
                                   bidirectional=cfg.bidirectional_ring),
        ("ring", "naive"): partial(ring_attention, axis_name=SEQ_AXIS, causal=True,
                                   bidirectional=cfg.bidirectional_ring),
    }[cfg.sp_attention, impl]
    for n in (2, 4):
        assert attention_path(cfg, n) == cfg.sp_attention
        many = _jaxpr(selected, n)
        assert many == _jaxpr(lambda: own, n)
        assert ("all_to_all" in many) == (scheme == "ulysses")
        assert ("ppermute" in many) == (scheme != "ulysses")
    if scheme == "bidirectional_ring":  # both directions from three members on
        one_way = _cfg(family, attention_impl=impl, sp_attention="ring")
        assert many != _jaxpr(lambda: select_attention(one_way, SEQ_AXIS), 4)


def test_an_unknown_scheme_is_refused_at_any_size():
    cfg = TransformerConfig(sp_attention="spiral")
    for n in (1, 2):
        with pytest.raises(ValueError, match="unknown sp_attention"):
            attention_path(cfg, n)


# ------------------------------------------- (d) the boundary target


def _loss_with_fetched_target(cfg, params, tokens, sp_axis):
    """lm_loss_local as the parent had it: the next shard's first token
    always comes through ppermute, also from oneself."""
    n_sp = lax.axis_size(sp_axis)
    logits, _ = lm_family(cfg).apply(cfg, params, tokens, seq_axis_name=sp_axis)
    nxt_first = lax.ppermute(
        tokens[:, :1], sp_axis, [(j, (j - 1) % n_sp) for j in range(n_sp)])
    tgt = jnp.concatenate([tokens[:, 1:], nxt_first], axis=1)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    t_loc = tokens.shape[1]
    pos = lax.axis_index(sp_axis) * t_loc + jnp.arange(t_loc)
    valid = (pos < n_sp * t_loc - 1).astype(jnp.float32)
    count = jnp.float32(tokens.shape[0]) * jnp.sum(valid)
    return jnp.sum(nll * valid[None, :]) / lax.psum(count, sp_axis)


@pytest.mark.parametrize("sp", [1, 2])
@FAMILY
def test_the_boundary_target_is_the_ppermute_constructions(family, sp):
    cfg = _cfg(family, attention_impl="naive")
    params = lm_family(cfg).init(cfg, jax.random.key(2))
    mesh = make_mesh_2d(1, sp)

    def both(p, tok):
        ours, _ = lm_loss_local(cfg, p, tok, SEQ_AXIS)
        theirs = _loss_with_fetched_target(cfg, p, tok, SEQ_AXIS)
        return lax.psum(ours, SEQ_AXIS), lax.psum(theirs, SEQ_AXIS)

    ours, theirs = jax.jit(jax.shard_map(
        both, mesh=mesh, in_specs=(P(), P(WORKER_AXIS, SEQ_AXIS)),
        out_specs=(P(), P()), check_vma=False))(params, _tokens(2))
    assert float(ours) == float(theirs)
