"""models/prerouted_moe.py against its plain reference, benchmark/reference/
smallthinker_swa_moe.py, over the whole model: logits, loss and every
gradient leaf, through the jnp attention and through the flash kernels in
interpret mode (both masks); and the mechanisms that must not be left out.
The configuration, the weights and the losses are tests/
test_prerouted_moe.py's (a file of their own so that `--dist loadfile` can
spread the family's seconds over two workers)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.drivers.lm_config_train import stacked
from ps_pytorch_tpu.models import prerouted_moe
from ps_pytorch_tpu.models.lm import load_lm_config
from ps_pytorch_tpu.parallel import moe

from .test_prerouted_moe import (  # noqa: F401  (`kernels` is a fixture)
    GROUPS, PUBLISHED, _loss_and_logits, _reference, _tokens, _weights, kernels)


@pytest.mark.parametrize("impl", ["naive", "flash"])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(kernels, impl):
    """A leaf's gradient to 2e-4 of its largest entry: float32 sums in
    another order (tiles and the expert buffer against dense rows), nothing
    more; under `flash` both masks go through the kernels (interpret mode).
    The logits to 1e-5 of their largest and the loss to 1e-6, the same
    reason."""
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
    for leaf in ("blocks/0/wq", "blocks/0/wk", "blocks/1/wq", "blocks/2/wv", "blocks/3/wo",
                 "blocks/0/router", "blocks/3/router", "blocks/1/experts/w_gate",
                 "blocks/2/experts/w_up", "blocks/3/experts/w_down", "blocks/0/ln1",
                 "blocks/2/ln2", "head", "embed"):
        assert np.any(by[leaf]), leaf


@pytest.mark.parametrize("fault", ["router_reads_ffn_norm", "relu_as_silu",
                                   "global_layer_rotated", "window_ignored"])
def test_a_mechanism_left_out_fails_the_comparison(fault, monkeypatch):
    """The blind-spot controls of the cell, at the small size: each fault
    moves the loss by far more than the comparison's 1e-6."""
    cfg = load_lm_config(PUBLISHED)
    if fault == "router_reads_ffn_norm":
        half = prerouted_moe.ffn_half

        def own_norm(cfg, x, blk, route=None):
            n32 = prerouted_moe._rms32(x, blk["ln2"], cfg.rms_norm_eps)
            return half(cfg, x, blk, route=moe.route_tokens(n32, blk, cfg.routing))

        monkeypatch.setattr(prerouted_moe, "ffn_half", own_norm)
    elif fault == "relu_as_silu":
        monkeypatch.setitem(moe.ACTIVATIONS, "relu", jax.nn.silu)
    elif fault == "global_layer_rotated":
        block = prerouted_moe.prerouted_block
        monkeypatch.setattr(prerouted_moe, "prerouted_block",
                            lambda cfg, sliding, rotary, *rest: block(cfg, sliding, 1, *rest))
    else:
        monkeypatch.setattr(prerouted_moe.PreroutedMoeConfig, "mask", lambda self, sliding: True)
    loss, _ = _loss_and_logits(cfg, stacked(_weights(), GROUPS), _tokens())
    assert abs(float(loss) / float(_reference()[1]) - 1.0) > 1e-4
