"""models/swa_moe.py against its plain reference, benchmark/reference/
laguna_swa_gqa_moe.py, over the whole model: logits, loss and every gradient
leaf, through the jnp attention and through the flash kernels in interpret
mode (both masks); and the mechanisms that must not be left out. The
configuration, the weights and the losses are tests/test_swa_moe.py's (a file
of their own so that `--dist loadfile` can spread the family's seconds over
two workers)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.drivers.lm_config_train import stacked
from ps_pytorch_tpu.models import swa_moe
from ps_pytorch_tpu.models.lm import load_lm_config
from ps_pytorch_tpu.models.swa_moe import FULL

from .test_swa_moe import (  # noqa: F401  (`kernels` is a fixture)
    GROUPS, PUBLISHED, _loss_and_logits, _reference, _tokens, _weights, kernels)


@pytest.mark.parametrize("impl", ["naive", "flash"])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(kernels, impl):
    """A leaf's gradient to 2e-4 of its largest entry: float32 sums in
    another order (tiles and the expert buffer against dense rows), nothing
    more; under `flash` both masks go through the kernels (interpret mode)."""
    cfg = load_lm_config(PUBLISHED, attention_impl=impl)
    params, tokens = stacked(_weights(), GROUPS), _tokens()
    (loss, logits), grads = jax.jit(jax.value_and_grad(
        functools.partial(_loss_and_logits, cfg), has_aux=True))(params, tokens)
    want_logits, want_loss, want = _reference()
    np.testing.assert_allclose(
        logits, want_logits, atol=1e-5 * float(jnp.max(jnp.abs(want_logits))), rtol=2e-5)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
    names = weights.leaf_names(want)
    for name, g, r in zip(names, jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(r))) + 1e-12
        assert float(jnp.max(jnp.abs(g - r))) <= 2e-4 * scale + 1e-9, name
    by = dict(zip(names, jax.tree_util.tree_leaves(grads)))
    for leaf in ("blocks/0/wq", "blocks/0/wk", "blocks/0/wg", "blocks/1/wg", "blocks/2/wv",
                 "blocks/4/wo", "blocks/0/mlp/w_up", "blocks/1/router", "blocks/3/shared/w_gate",
                 "blocks/4/experts/w_down", "blocks/2/ln1", "head", "embed"):
        assert np.any(by[leaf]), leaf
    assert not np.any(by["blocks/1/router_bias"])      # outside the gradient


@pytest.mark.parametrize("fault", ["window_ignored", "gate_left_out", "yarn_left_out"])
def test_a_mechanism_left_out_fails_the_comparison(fault, monkeypatch):
    """The blind-spot controls of the cell, at the small size: each fault
    moves the loss by far more than the comparison's 1e-6."""
    cfg = load_lm_config(PUBLISHED)
    if fault == "window_ignored":
        monkeypatch.setattr(swa_moe.SwaMoeConfig, "mask", lambda self, kind: True)
    elif fault == "gate_left_out":
        monkeypatch.setattr(jax.nn, "sigmoid", lambda x: jnp.ones_like(x)
                            if x.ndim == 3 and x.shape[-1] in (4, 6) else jax.lax.logistic(x))
    else:
        plain_rope = swa_moe.Rope(rope_theta=100.0, partial_rotary_factor=0.5)
        monkeypatch.setattr(swa_moe.SwaMoeConfig, "rope", lambda self, kind: plain_rope
                            if kind == FULL else dict(self.rope_parameters)[kind])
    loss, _ = _loss_and_logits(cfg, stacked(_weights(), GROUPS), _tokens())
    assert abs(float(loss) / float(_reference()[1]) - 1.0) > 1e-4
