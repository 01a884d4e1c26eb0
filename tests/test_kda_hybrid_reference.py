"""models/kda_hybrid.py against its plain reference, benchmark/reference/
kimi_linear_kda_mla_moe.py, over the whole model: logits, loss and every
gradient leaf, at the source's decays and at the benchmark's. The
configuration, the weights and the two losses are tests/test_kda_hybrid.py's
(a file of their own so that `--dist loadfile` can spread the family's
seconds over two workers)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.drivers.lm_config_train import stacked
from benchmark.reference import kimi_linear_kda_mla_moe as ref
from ps_pytorch_tpu.models.kda_hybrid import apply_kda_hybrid

from .test_kda_hybrid import GROUPS, _prog_loss, _ref_loss, _setup


@pytest.mark.parametrize("decays", ["source", "benchmark"])
def test_logits_and_loss_match_the_reference(decays):
    pub, cfg, plain, params, tokens = _setup(decays=decays)
    logits, aux = jax.jit(partial(apply_kda_hybrid, cfg))(params, tokens)
    want = jnp.stack([ref.logits_fn(pub, plain, row) for row in tokens])
    # logits reach 4 here (an untied head at 1/sqrt(64)): to 1e-5 of their range
    np.testing.assert_allclose(logits, want, atol=1e-5 * float(jnp.max(jnp.abs(want))), rtol=2e-5)
    assert aux["kda_cut_off"].shape == (4,)            # one count a KDA layer
    assert aux["counts"].shape == (4, 8) and aux["unserved"].shape == (4,)
    np.testing.assert_allclose(_prog_loss(cfg, params, tokens),
                               _ref_loss(pub, plain, tokens), rtol=1e-6)


@pytest.mark.parametrize("decays", ["source", "benchmark"])
def test_every_gradient_leaf_matches_the_reference(decays):
    """A leaf's gradient to 2e-4 of its largest entry: float32 sums in
    another order (chunks against token by token), nothing more."""
    pub, cfg, plain, params, tokens = _setup(seed=4, decays=decays)
    got = jax.jit(jax.grad(lambda p: _prog_loss(cfg, p, tokens)))(params)
    want = stacked(jax.jit(jax.grad(lambda p: _ref_loss(pub, p, tokens)))(plain), GROUPS)
    names = weights.leaf_names(want)
    for name, g, r in zip(names, jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(r))) + 1e-12
        assert float(jnp.max(jnp.abs(g - r))) <= 2e-4 * scale + 1e-9, name
    by = dict(zip(names, jax.tree_util.tree_leaves(got)))
    for leaf in ("blocks/0/a_log", "blocks/0/dt_bias", "blocks/0/conv_k", "blocks/1/f_a",
                 "blocks/1/g_b", "blocks/2/w_beta", "blocks/2/o_norm/scale", "blocks/3/wkv_a",
                 "blocks/4/experts/w_down", "blocks/0/mlp/w_up", "blocks/1/router", "head"):
        assert np.any(by[leaf]), leaf
    assert not np.any(by["blocks/1/router_bias"])      # outside the gradient
