"""`remat` keeps what only the flash forward kernel can make.

ops/flash_attention._flash_vjp_fwd names its o and lse (FLASH_SAVED) and
models/transformer.remat_block, the one `remat` of the three LM families,
saves exactly those names: the backward runs each block again and
ps_flash_fwd once a layer, where a jax.checkpoint without a policy (the
parent) ran it twice. Same kernels on the same operands, so every gradient
is bitwise the one without `remat`; without a policy the names lower to
nothing, so a step without `remat` is the parent's text.

The three families at small widths (the dense one, the latent-attention one
with its 24-wide q/k beside a 16-wide v, the hybrid one with both block
kinds), the flash kernels under the Pallas interpreter.
"""

import json
import re

import jax
import numpy as np
import optax
import pytest

from ps_pytorch_tpu.models.lm import lm_family, load_lm_config
from ps_pytorch_tpu.ops import flash_attention
from ps_pytorch_tpu.parallel.dp_sp import make_lm_train_step, make_mesh_2d

from . import test_attention_path as paths
from .test_attention_path import MLA
from .test_ssm_hybrid import PUBLISHED as HYBRID

# family -> flash layers in its small config (the hybrid one: m m a m)
LAYERS = {"dense": 2, "mla_moe": 2, "ssm_hybrid": 1}
FAMILY = pytest.mark.parametrize("family", list(LAYERS))
KERNELS = ("ps_flash_fwd", "ps_flash_dqkv", "ps_flash_dq", "ps_flash_dkv")


@pytest.fixture(autouse=True)
def interpret(monkeypatch):
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")


@pytest.fixture
def unnamed(monkeypatch):
    """The two checkpoint_name calls patched out: the parent's program."""
    return lambda: monkeypatch.setattr(flash_attention, "checkpoint_name", lambda x, name: x)


def _cfg(family, remat):
    run = dict(attention_impl="flash", remat=remat)
    return load_lm_config(HYBRID, **run) if family == "ssm_hybrid" else paths._cfg(family, **run)


def _setup(family):
    """(the config with `remat`, the one without, parameters, tokens)."""
    cfg = _cfg(family, True)
    return cfg, _cfg(family, False), lm_family(cfg).init(cfg, jax.random.key(0)), paths._tokens()


def _grads(cfg, params, tokens):
    return paths._one_device_loss_and_grads(cfg, params, tokens)[1]


def _count_kernels(jaxpr, counts):
    """Every pallas_call equation under `jaxpr` by its kernel's name, each
    use of a shared sub-jaxpr counted (the printed text shows it once)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            counts[name] = counts.get(name, 0) + 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _count_kernels(sub, counts)
    return counts


def _kernel_calls(cfg, params, tokens):
    counts = _count_kernels(jax.make_jaxpr(lambda p: _grads(cfg, p, tokens))(params).jaxpr, {})
    return tuple(counts.get(k, 0) for k in KERNELS)


@FAMILY
def test_remat_runs_the_forward_kernel_once_a_layer(family, unnamed):
    cfg, plain, params, tokens = _setup(family)
    layers = LAYERS[family]
    # one fused backward a layer; the split pair is for heads past the plan's cap
    assert _kernel_calls(cfg, params, tokens) == (layers, layers, 0, 0)
    assert _kernel_calls(plain, params, tokens) == (layers, layers, 0, 0)
    unnamed()  # nothing to save by: the forward kernel runs again, as at the parent
    assert _kernel_calls(cfg, params, tokens) == (2 * layers, layers, 0, 0)


@FAMILY
def test_remat_gradients_are_bitwise_the_parents_and_those_without_it(family, unnamed):
    """Against the parent's `remat` (no names, so no policy to save by) every
    leaf is bitwise equal in every family. Against `remat` off too, but for
    the hybrid family: there XLA's CPU backend fuses the scan's float32 sums
    otherwise once a block is recomputed, and the parent's `remat` is already
    2e-6 of a leaf's largest entry off (the same bits as ours, by the line
    above)."""
    cfg, plain, params, tokens = _setup(family)
    got, want = _grads(cfg, params, tokens), _grads(plain, params, tokens)
    unnamed()
    parent = _grads(cfg, params, tokens)
    leaves = lambda tree: [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]
    assert len(leaves(got)) == len(leaves(params))
    assert sum(bool(g.any()) for g in leaves(got)) > len(leaves(got)) // 2
    for g, w, p in zip(leaves(got), leaves(want), leaves(parent)):
        np.testing.assert_array_equal(g, p)
        if family == "ssm_hybrid":
            np.testing.assert_allclose(g, w, rtol=0, atol=1e-5 * np.abs(w).max())
        else:
            np.testing.assert_array_equal(g, w)


def _renumbered(text):
    """`text` with every function symbol renamed to its order of first
    appearance: jax numbers private functions (`@_where_256`) from a counter
    that tracing a name primitive advances, so two lowerings of one program
    differ in those suffixes and in nothing else."""
    order = {}
    return re.sub(r"@[\w.]+", lambda m: order.setdefault(m.group(), f"@f{len(order)}"), text)


@FAMILY
def test_without_remat_the_names_lower_to_nothing(family, unnamed):
    _, cfg, params, tokens = _setup(family)
    tx = optax.adam(1e-3)

    def lowered():
        step = make_lm_train_step(cfg, tx, make_mesh_2d(1, 1), donate=False)
        return _renumbered(step.lower(params, tx.init(params), tokens).as_text())

    named = lowered()
    unnamed()
    assert named == lowered()


# ------------------------------------------------ the engagement counter

DENSE = ["--dim", "32", "--depth", "1", "--heads", "2", "--vocab-size", "64"]
# case -> (an --lm-config or the dense flags, further flags, sequence shards,
# B * H * T * (d_v * itemsize + 4): o in the blocks' dtype and the float32 lse)
PLANS = {
    "on": (DENSE, ["--remat"], 1, 2 * 2 * 32 * (16 * 4 + 4)),
    "on_bfloat16": (DENSE, ["--remat", "--dtype", "bfloat16"], 1, 2 * 2 * 32 * (16 * 2 + 4)),
    "on_v_narrower_than_qk": (MLA, ["--remat"], 1, 2 * 4 * 32 * (16 * 4 + 4)),
    "off": (DENSE, [], 1, 0),
    "ring": (DENSE, ["--remat"], 2, 0),
}


@pytest.mark.parametrize("case", list(PLANS))
def test_flash_plan_says_what_remat_saves(tmp_path, case):
    from ps_pytorch_tpu.cli import train_lm
    from ps_pytorch_tpu.obs.schema import validate_event

    model, flags, num_sp, saved = PLANS[case]
    if isinstance(model, dict):
        (tmp_path / "lm.json").write_text(json.dumps(model))
        model = ["--lm-config", str(tmp_path / "lm.json")]
    train_lm.main(model + flags + [
        "--seq-len", "32", "--batch-size", "2", "--max-steps", "1", "--num-dp", "1",
        "--num-sp", str(num_sp), "--attention-impl", "flash", "--train-size", "8",
        "--trace", str(tmp_path)])
    spans = [json.loads(line) for line in open(tmp_path / "trace_train_lm_p0.jsonl")]
    (plan,) = [s for s in spans if s.get("name") == "flash_plan"]
    assert plan["remat_saves"] == ("ps_flash_o,ps_flash_lse" if saved else "")
    assert plan["saved_bytes_per_layer"] == saved
    assert plan["attention_path"] == ("ring" if num_sp > 1 else "local")
    assert validate_event(dict(plan))["saved_bytes_per_layer"] == saved
