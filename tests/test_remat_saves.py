"""`remat` keeps what only the flash forward kernel can make, and the
kernels' operands where the device has the room.

ops/flash_attention._flash_vjp_fwd names its o and lse (FLASH_SAVED) and
its folded q, k, v (FLASH_OPERANDS), models/kda_hybrid.kda_mixer the delta
rule's q, k, v (KDA_OPERANDS), and models/transformer.remat_block, the one
`remat` of the LM families, saves the names plan_remat_saves gives it: the
backward runs each block again, ps_flash_fwd once a layer, where a
jax.checkpoint without a policy ran it twice, and of the second run what
makes the operands (an attention layer's projection products, a delta-rule
layer's three short branches) is dead. Same kernels on the same operands,
so every gradient is bitwise the one without `remat`; without a policy the
names lower to nothing, so a step without `remat` is the parent's text.

The four families at small widths (the dense one, the latent-attention one
with its 24-wide q/k beside a 16-wide v, the two hybrid ones with both
their block kinds), the flash kernels under the Pallas interpreter.
"""

import json
import os
import re

import jax
import numpy as np
import optax
import pytest

from ps_pytorch_tpu.models import kda_hybrid
from ps_pytorch_tpu.models.lm import lm_family, load_lm_config
from ps_pytorch_tpu.ops import flash_attention, kda
from ps_pytorch_tpu.parallel.dp_sp import make_lm_train_step, make_mesh_2d

from . import test_attention_path as paths
from .test_attention_path import MLA
from .test_kda_hybrid import PUBLISHED as KDA
from .test_ssm_hybrid import PUBLISHED as HYBRID

# family -> flash layers in its small config (the hybrid ones: m m a m, and
# k k k a k)
LAYERS = {"dense": 2, "mla_moe": 2, "ssm_hybrid": 1, "kda_hybrid": 1}
FAMILY = pytest.mark.parametrize("family", list(LAYERS))
KERNELS = ("ps_flash_fwd", "ps_flash_dqkv", "ps_flash_dq", "ps_flash_dkv")
PUBLISHED = {"ssm_hybrid": HYBRID, "kda_hybrid": KDA}
# family -> what makes the kernels' operands, {what _count_eqns calls it:
# how many a step}: n @ w by w's shape (the dense wqkv; the latent
# attention's wq and wkv_b; the grouped-query wq and wk, wv; the latent
# layer's two and the four delta-rule layers' wq, wk, wv) and the
# delta-rule layers' short convs by their one pad each
OPERAND_MAKERS = {
    "dense": {("product", (32, 96)): 2},
    "mla_moe": {("product", (64, 96)): 2, ("product", (32, 128)): 2},
    "ssm_hybrid": {("product", (64, 64)): 1, ("product", (64, 32)): 2},
    "kda_hybrid": {("product", (64, 96)): 1, ("product", (32, 128)): 1,
                   ("product", (64, 64)): 12, ("pad", (2, 32, 64)): 12},
}


@pytest.fixture(autouse=True)
def interpret(monkeypatch):
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")


@pytest.fixture
def unnamed(monkeypatch):
    """Every checkpoint_name call patched out: `remat` has the block's
    input to save and nothing else, as before the first name."""
    def patch():
        for module in (flash_attention, kda, kda_hybrid):
            monkeypatch.setattr(module, "checkpoint_name", lambda x, name: x)
    return patch


@pytest.fixture
def no_room(monkeypatch):
    """A device that states a limit with no room for the operands: the
    policy is the residuals alone, the program of before the operands had
    names."""
    return lambda: monkeypatch.setattr(flash_attention, "device_bytes_limit", lambda: 1)


def _cfg(family, remat):
    run = dict(attention_impl="flash", remat=remat)
    if family in PUBLISHED:
        return load_lm_config(PUBLISHED[family], **run)
    return paths._cfg(family, **run)


def _setup(family):
    """(the config with `remat`, the one without, parameters, tokens)."""
    cfg = _cfg(family, True)
    return cfg, _cfg(family, False), lm_family(cfg).init(cfg, jax.random.key(0)), paths._tokens()


def _grads(cfg, params, tokens):
    return paths._one_device_loss_and_grads(cfg, params, tokens)[1]


def _called(eqn):
    """What the counts below know an equation by: a pallas_call by its
    kernel's name, a product x @ w of an activation [B, T, D] and a matrix
    by w's shape, a pad by its operand's shape."""
    kind = eqn.primitive.name
    if kind == "pallas_call":
        return eqn.params["name"]
    if kind == "dot_general":
        x, w = (v.aval.shape for v in eqn.invars)
        if eqn.params["dimension_numbers"] == (((2,), (0,)), ((), ())) and len(w) == 2:
            return "product", w
    if kind == "pad":
        return "pad", eqn.invars[0].aval.shape
    return None


def _count_eqns(jaxpr, counts):
    """Every equation under `jaxpr` that _called knows, each use of a
    shared sub-jaxpr counted (the printed text shows it once)."""
    for eqn in jaxpr.eqns:
        name = _called(eqn)
        if name is not None:
            counts[name] = counts.get(name, 0) + 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _count_eqns(sub, counts)
    return counts


def _count_kernels(jaxpr, counts):
    """_count_eqns' kernels alone, by name (tests/test_flash_attention.py
    reads a gradient's with it too)."""
    counts.update({k: n for k, n in _count_eqns(jaxpr, {}).items() if isinstance(k, str)})
    return counts


def _gradient_counts(cfg, params, tokens):
    return _count_eqns(jax.make_jaxpr(lambda p: _grads(cfg, p, tokens))(params).jaxpr, {})


def _kernel_calls(cfg, params, tokens):
    counts = _gradient_counts(cfg, params, tokens)
    return tuple(counts.get(k, 0) for k in KERNELS)


@FAMILY
def test_remat_runs_the_forward_kernel_once_a_layer(family, unnamed):
    cfg, plain, params, tokens = _setup(family)
    layers = LAYERS[family]
    # one fused backward a layer; the split pair is for heads past the plan's cap
    assert _kernel_calls(cfg, params, tokens) == (layers, layers, 0, 0)
    assert _kernel_calls(plain, params, tokens) == (layers, layers, 0, 0)
    unnamed()  # nothing to save by: the forward kernel runs again, as before any name
    assert _kernel_calls(cfg, params, tokens) == (2 * layers, layers, 0, 0)


@FAMILY
def test_remat_makes_the_kernels_operands_once(family, no_room):
    """Each product and conv that makes an operand of a kernel is in the
    gradient's jaxpr one time fewer than where the policy is the residuals
    alone (a short branch is still there twice: its own checkpoint runs it
    again for the conv's and the norm's gradients); the kernels' counts do
    not move."""
    cfg, _, params, tokens = _setup(family)
    got = _gradient_counts(cfg, params, tokens)
    no_room()
    before = _gradient_counts(cfg, params, tokens)
    for maker, a_step in OPERAND_MAKERS[family].items():
        assert before[maker] - got[maker] == a_step, maker
    assert [got.get(k, 0) for k in KERNELS] == [before.get(k, 0) for k in KERNELS]


@FAMILY
def test_remat_gradients_are_bitwise_the_parents_and_those_without_it(family, unnamed, no_room):
    """Against `remat` with the residuals alone and with no names at all
    (so no policy to save by) every leaf is bitwise equal in every family.
    Against `remat` off too, but for the state-space family: there XLA's CPU
    backend fuses the scan's float32 sums otherwise once a block is
    recomputed, and `remat` without a name is already 2e-6 of a leaf's
    largest entry off (the same bits as ours, by the line above)."""
    cfg, plain, params, tokens = _setup(family)
    got, want = _grads(cfg, params, tokens), _grads(plain, params, tokens)
    no_room()
    residuals_alone = _grads(cfg, params, tokens)
    unnamed()
    parent = _grads(cfg, params, tokens)
    leaves = lambda tree: [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]
    assert len(leaves(got)) == len(leaves(params))
    assert sum(bool(g.any()) for g in leaves(got)) > len(leaves(got)) // 2
    for g, w, p, r in zip(leaves(got), leaves(want), leaves(parent), leaves(residuals_alone)):
        np.testing.assert_array_equal(g, r)
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


# ------------------------------------------------ the one decision

MIB = 2 ** 20
# cell's configuration -> (rows of its traffic file, MiB of operands and of
# everything saved as a v5e stores them: ISSUE 40's arithmetic)
CELLS = {
    # q3, k3 [64, 8192, 192] bfloat16 in 256 lanes 256 MiB each, v3 128: 640 a
    # layer; o 128 and the float32 lse 2
    "kanana2_30b_a3b_ep8": (2, 5 * 640, 5 * 770),
    # [32, 8192, 64] in 128 lanes: 64 each of q3, k3, v3, o; lse 1
    "granite4_h_micro_1period": (1, 192, 257),
    # one latent layer as kanana's; four delta-rule layers of q, k, v
    # [2, 8192, 32, 128] at 128 each and 128 of float32 inverses
    "kimi_linear_48b_a3b_ep32": (2, 640 + 4 * 384, 770 + 4 * 512),
    # one row of 16,384: q3, k3, v3, o [28, 16384, 128] bfloat16 at 112 each
    # (keys and values at the query heads' width), lse 2, in each of four layers
    "smallthinker_21b_a3b_ep4": (1, 4 * 336, 4 * 450),
}
SEQ_LEN = {"smallthinker_21b_a3b_ep4": 16_384}


def _cell(name, rows, seq_len=8192, limit=flash_attention.V5E_BYTES_LIMIT):
    """(the cell's plan on a described v5e, its configuration's dict)."""
    import jax.numpy as jnp

    from benchmark import spec

    path = os.path.join(spec.BENCH_DIR, "configs", name + ".json")
    with open(path) as f:
        pub = json.load(f)
    cfg = load_lm_config(path, attention_impl="flash", remat=True, compute_dtype=jnp.bfloat16)
    kinds = lm_family(cfg).saved_layers(cfg, rows, seq_len)
    return flash_attention.plan_remat_saves(kinds, pub["parameters"], limit), pub


@pytest.mark.parametrize("name", list(CELLS))
def test_the_cells_keep_their_operands_on_a_described_v5e(name, monkeypatch):
    monkeypatch.delenv("PS_TPU_PALLAS_INTERPRET")  # the shapes of the chip's kernels or the twin's: the same bytes
    rows, operands, saved = CELLS[name]
    seq_len = SEQ_LEN.get(name, 8192)
    plan, pub = _cell(name, rows, seq_len)
    alone, _ = _cell(name, rows, seq_len, limit=1)
    assert plan.operands_kept and not alone.operands_kept
    assert set(alone.names) < set(plan.names)
    assert (plan.saved_bytes - alone.saved_bytes, plan.saved_bytes) == (operands * MIB, saved * MIB)
    assert plan.state_bytes == 16 * pub["parameters"]
    assert plan.state_bytes + plan.saved_bytes < 13 * 2 ** 30 < 0.8 * plan.bytes_limit + 2 ** 30


def test_a_sequence_eight_times_as_long_keeps_the_residuals_alone():
    plan, _ = _cell("kanana2_30b_a3b_ep8", 2, seq_len=65_536)
    assert plan.names == flash_attention.FLASH_SAVED and not plan.operands_kept
    assert plan.saved_bytes == 8 * 5 * 130 * MIB
    # the operands would have been eight times the cell's: 25 GiB
    kept, _ = _cell("kanana2_30b_a3b_ep8", 2, seq_len=65_536, limit=2 ** 40)
    assert kept.saved_bytes - plan.saved_bytes == 8 * 5 * 640 * MIB


def test_stored_bytes_pads_lanes_and_rows_to_the_chips_tiles():
    import jax.numpy as jnp

    stored = flash_attention.stored_bytes
    assert stored((64, 8192, 192), jnp.bfloat16) == 256 * MIB
    assert stored((64, 8192, 128), jnp.bfloat16) == 128 * MIB
    assert stored((64, 8192), jnp.float32) == 2 * MIB
    assert stored((3, 5, 16), jnp.float32) == 3 * 8 * 128 * 4
    assert stored((3, 5, 16), jnp.bfloat16) == 3 * 16 * 128 * 2
    assert stored((7,), jnp.float32) == 128 * 4


def _residual_bytes(cfg, params, tokens, capsys):
    """Bytes of everything the loss's backward is handed, as
    jax.ad_checkpoint.print_saved_residuals lists it (`f32[2,32,64] ...`)."""
    import jax.numpy as jnp

    loss = lambda p: lm_family(cfg).apply(cfg, p, tokens)[0].astype(jnp.float32).sum()
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(loss, params)
    total = 0
    for line in capsys.readouterr().out.splitlines():
        if line.endswith("from a constant"):
            continue    # the flash kernels' walk (int32 tables): the program's, not a layer's
        dtype, dims = re.match(r"(\w+)\[([\d,]*)\]", line).groups()
        total += int(np.prod([int(d) for d in dims.split(",") if d])) * np.dtype(
            {"f32": "float32", "i32": "int32", "bf16": "uint16", "bool": "bool"}[dtype]).itemsize
    return total


@FAMILY
def test_the_plan_reports_the_bytes_remat_saves(family, unnamed, no_room, capsys):
    """What the named values add to the residuals of the whole model, over a
    `remat` that has the blocks' inputs alone, is what the plan says is kept
    a layer times its layers: with the operands, and with a device that has
    no room for them."""
    cfg, _, params, tokens = _setup(family)
    kinds = lm_family(cfg).saved_layers(cfg, *tokens.shape)
    reported = lambda plan: sum(k.count * sum(kept.values()) for k, kept in zip(kinds, plan.kept))
    with_operands = _residual_bytes(cfg, params, tokens, capsys)
    plan = flash_attention.plan_remat_saves(kinds, 0, flash_attention.V5E_BYTES_LIMIT)
    no_room()
    residuals_alone = _residual_bytes(cfg, params, tokens, capsys)
    alone = flash_attention.plan_remat_saves(kinds, 0, 1)
    unnamed()
    inputs_alone = _residual_bytes(cfg, params, tokens, capsys)
    # the delta rule's twin hands its inverses out of `lax.map` as its own
    # checkpoint's residuals, named or not: the listing has them all three times
    twin = sum(k.count * kept.get(kda.KDA_SAVED[0], 0) for k, kept in zip(kinds, plan.kept))
    assert with_operands - inputs_alone == reported(plan) - twin > 0
    assert residuals_alone - inputs_alone == reported(alone) - twin > 0
    assert reported(plan) > 3 * reported(alone)


# ------------------------------------------------ the engagement counter

DENSE = ["--dim", "32", "--depth", "1", "--heads", "2", "--vocab-size", "64"]
ALL = "ps_flash_o,ps_flash_lse,ps_flash_q,ps_flash_k,ps_flash_v"
# case -> (an --lm-config or the dense flags, further flags, sequence shards,
# the names kept of an attention layer, their bytes: B * H * T rows of o in
# the blocks' dtype, the float32 lse and, where they are kept, q, k and v)
PLANS = {
    "on": (DENSE, ["--remat"], 1, ALL, 2 * 2 * 32 * (16 * 4 + 4 + 3 * 16 * 4)),
    "on_bfloat16": (DENSE, ["--remat", "--dtype", "bfloat16"], 1, ALL,
                    2 * 2 * 32 * (16 * 2 + 4 + 3 * 16 * 2)),
    "on_v_narrower_than_qk": (MLA, ["--remat"], 1, ALL,
                              2 * 4 * 32 * (16 * 4 + 4 + 2 * 24 * 4 + 16 * 4)),
    "on_delta_rule": (KDA, ["--remat"], 1, ALL, 2 * 4 * 32 * (16 * 4 + 4 + 2 * 24 * 4 + 16 * 4)),
    "on_no_room": (DENSE, ["--remat"], 1, "ps_flash_o,ps_flash_lse", 2 * 2 * 32 * (16 * 4 + 4)),
    "off": (DENSE, [], 1, "", 0),
    "ring": (DENSE, ["--remat"], 2, "", 0),
}


@pytest.mark.parametrize("case", list(PLANS))
def test_flash_plan_says_what_remat_saves(tmp_path, case, no_room):
    from ps_pytorch_tpu.cli import train_lm
    from ps_pytorch_tpu.obs.schema import validate_event

    model, flags, num_sp, names, saved = PLANS[case]
    if isinstance(model, dict):
        (tmp_path / "lm.json").write_text(json.dumps(model))
        model = ["--lm-config", str(tmp_path / "lm.json")]
    if case == "on_no_room":
        no_room()
    train_lm.main(model + flags + [
        "--seq-len", "32", "--batch-size", "2", "--max-steps", "1", "--num-dp", "1",
        "--num-sp", str(num_sp), "--attention-impl", "flash", "--train-size", "8",
        "--trace", str(tmp_path)])
    spans = [json.loads(line) for line in open(tmp_path / "trace_train_lm_p0.jsonl")]
    (plan,) = [s for s in spans if s.get("name") == "flash_plan"]
    assert (plan["remat_saves"], plan["saved_bytes_per_layer"]) == (names, saved)
    assert plan["attention_path"] == ("ring" if num_sp > 1 else "local")
    assert validate_event(dict(plan))["saved_bytes_per_layer"] == saved
    if case == "on_delta_rule":
        # a delta-rule layer: two chunks of 16 x 16 float32 a row and head,
        # and q, k, v [2, 32, 4, 16] in the blocks' dtype
        (plan,) = [s for s in spans if s.get("name") == "kda_plan"]
        assert plan["remat_saves"] == "ps_kda_inverse,ps_kda_q,ps_kda_k,ps_kda_v"
        assert plan["saved_bytes_per_layer"] == 2 * 2 * 4 * 16 * 16 * 4 + 3 * 2 * 32 * 4 * 16 * 4
        assert validate_event(dict(plan))["remat_saves"] == plan["remat_saves"]
