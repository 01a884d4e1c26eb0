"""models/ssm_hybrid.py against its plain reference, benchmark/reference/
granite_hybrid_ssm.py, over the whole model: logits, loss and every gradient
leaf at the source's decays and at the benchmark's, and three Adam steps by
the comparison that decides the benchmark's `correct`. The configuration,
the weights and the two losses are tests/test_ssm_hybrid.py's (a file of
their own so that `--dist loadfile` can spread the family's seconds over two
workers)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, drivers, spec, weights
from ps_pytorch_tpu.models.ssm_hybrid import apply_ssm_hybrid

from .test_ssm_hybrid import CELL, PUBLISHED, _prog_loss, _ref_logits, _ref_loss, _setup


@pytest.mark.parametrize("decays", ["source", "benchmark"])
def test_logits_and_loss_match_the_reference(decays):
    pub, cfg, plain, tokens = _setup(decays=decays)
    logits, aux = jax.jit(partial(apply_ssm_hybrid, cfg))(plain, tokens)
    np.testing.assert_allclose(logits, _ref_logits(pub, plain, tokens), atol=2e-6, rtol=2e-5)
    assert aux["ssd_cut_off"].shape == (3,)            # one count a state-space layer
    np.testing.assert_allclose(_prog_loss(cfg, plain, tokens),
                               _ref_loss(pub, plain, tokens), rtol=1e-6)


@pytest.mark.parametrize("decays", ["source", "benchmark"])
def test_every_gradient_leaf_matches_the_reference(decays):
    pub, cfg, plain, tokens = _setup(seed=4, decays=decays)
    got = jax.jit(jax.grad(lambda p: _prog_loss(cfg, p, tokens)))(plain)
    want = jax.jit(jax.grad(lambda p: _ref_loss(pub, p, tokens)))(plain)
    names = weights.leaf_names(want)
    for name, g, r in zip(names, jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(r))) + 1e-12
        assert float(jnp.max(jnp.abs(g - r))) <= 2e-4 * scale + 1e-9, name
    by = dict(zip(names, jax.tree_util.tree_leaves(got)))
    for leaf in ("blocks/0/a_log", "blocks/0/dt_bias", "blocks/0/skip/scale", "blocks/1/conv_w",
                 "blocks/2/wk", "blocks/3/norm/scale", "embed"):
        assert np.any(by[leaf]), leaf


def _tiny_cell(dtype="float32"):
    cell = spec.load_cell(CELL)
    cell.config.update({k: v for k, v in PUBLISHED.items() if k != "model_type"})
    cell.traffic.update(batch_rows=2, seq_len=48, attention_impl="naive", corpus_rows=16,
                        dtype=dtype)
    return cell


def test_three_adam_steps_match_the_reference_and_the_control_does_not():
    """Through the path the benchmark's cell runs (dp_sp.make_lm_train_step,
    the program's Adam), by the comparison that decides `correct`."""
    cell = _tiny_cell()
    check = drivers.load("lm_config_train").check
    ctx = {"out_dir": None, "compiles": None}
    sound = compare.training_numbers(*check(cell, 7, False, ctx))
    assert max(sound.values()) < 2e-4, sound
    control = compare.training_numbers(*check(cell, 7, True, ctx))
    assert control["grad_norm_worst_leaf"] > 0.02, control
