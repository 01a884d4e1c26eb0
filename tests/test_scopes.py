"""The scopes the step programs write (obs/scopes.py) and their one reader
(obs/hlo.py): what a scope adds to a compiled program (a name, nothing the
compiler emits), where each instruction of a step is booked, the rule for a
fusion of several scopes, and the step's own census (`ScopedStep.scopes`).
CPU, tiny sizes: counts and names, never a time."""

import contextlib
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ps_pytorch_tpu.obs import hlo, scopes
from ps_pytorch_tpu.obs.scopes import (
    FFN, GRAD_REDUCE, HEAD_LOSS, MIXER_KDA, DELTA_RULE, MLP, UPDATE, ScopedStep, scope)

_META = re.compile(r',?\s*metadata=\{[^{}]*\}')
_HEADER = re.compile(r"^(ENTRY\s+)?%?[\w.\-]+ \(.*\{\s*$")
_DEFINED = re.compile(r"^\s*(?:ROOT\s+|ENTRY\s+)?(%[\w.\-]+) (?:=|\()", re.M)
_NAME = re.compile(r"%[\w.\-]+")


def stripped(text: str) -> str:
    """A compiled program's text without what a scope can touch: each
    instruction's metadata, the tables of files and stack frames, and the
    instructions' and computations' NAMES, numbered in the order they are
    defined (XLA spells some names after the `op_name`: a broadcast under
    `jvp(model)/jit(take_along_axis)` is `%jit_take_along_axis_.22`)."""
    lines = text.split("\n")
    first = next(i for i, line in enumerate(lines) if _HEADER.match(line))
    head = re.sub(r"^HloModule \S+", "HloModule m,", lines[0])      # the program's name is a label too
    body = _META.sub("", "\n".join([head] + lines[first:]))
    names = {}
    for name in _DEFINED.findall(body):
        names.setdefault(name, f"%n{len(names)}")
    return _NAME.sub(lambda m: names.get(m.group(0), m.group(0)), body)


def _sha(text: str) -> str:
    return hashlib.sha256(stripped(text).encode()).hexdigest()


# ------------------------------------------------- a small step, every phase


def _small_step():
    """value_and_grad over a scan inside a mixer scope, a block under
    jax.checkpoint with a save_only_these_names policy, a head; then the
    gradients' reduction and the update."""
    keep = jax.checkpoint_policies.save_only_these_names("kept")

    def step(p, x):
        def loss(p):
            with scope(MIXER_KDA):
                h = x @ p["a"]
                with scope(DELTA_RULE):
                    _, h = lax.scan(lambda c, xi: (c * 0.5 + xi,) * 2, jnp.zeros(h.shape[1:]), h)

            def block(h):
                with scope(FFN):
                    n = h * lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-6)
                    with scope(MLP):
                        y = checkpoint_name(jnp.tanh(n @ p["b"]), "kept")
                        return h + jnp.sin(y) @ p["c"]

            h = jax.checkpoint(block, policy=keep)(h)
            with scope(HEAD_LOSS):
                return jnp.sum(jax.nn.log_softmax(h) ** 2)

        value, grads = jax.value_and_grad(loss)(p)
        with scope(GRAD_REDUCE):
            grads = jax.tree_util.tree_map(lambda g: jnp.clip(g, -1.0, 1.0), grads)
        with scope(UPDATE):
            p = jax.tree_util.tree_map(lambda a, g: a - 0.1 * g, p, grads)
        return p, value

    p = {k: jnp.full((16, 16), 0.1) for k in "abc"}
    return ScopedStep("test_step", jax.jit(step)), p, jnp.ones((8, 16))


def test_a_small_step_yields_every_phase_and_the_right_scope_for_every_instruction():
    step, p, x = _small_step()
    step(p, x)
    census = step.scopes()
    assert census["program"] == "test_step"
    assert set(census["phases"]) >= {"forward", "backward", "remat", "update"}
    # every instruction that jax named is booked by its own name's rule,
    # and the rule gives one of the step's scopes with the phase jax wrote
    text = step.compiled_text()
    names = dict(re.findall(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"", text, re.M))
    for ins, (phase, path, work, mixed, via) in census["instructions"].items():
        if work == "container" or via:
            continue
        assert path in ("mixer/kda", "mixer/kda/delta_rule", "ffn", "ffn/mlp", "head_loss",
                        "grad_reduce", "update"), (ins, path, names.get(ins))
        if not mixed:
            op_name = names[ins]
            assert hlo.scope_path(op_name) == path, (ins, op_name)
            assert hlo.phase_of(op_name, path) == phase, (ins, op_name)
    by = {(r["phase"], r["scope"]) for r in census["by_place"]}
    assert {("forward", "mixer/kda/delta_rule"), ("backward", "mixer/kda/delta_rule"),
            ("remat", "ffn/mlp"), ("backward", "ffn/mlp"), ("forward", "head_loss"),
            ("update", "update")} <= by
    # XLA fuses the gradients' scaling into the update or the product before it
    assert ("update", "grad_reduce") in by or any(
        "update:grad_reduce" in mixed for _, _, _, mixed, _ in census["instructions"].values())
    # the scan's body is read through its `while`
    assert any(p == "mixer/kda/delta_rule" and w != "container" and "while" not in n
               for n, (_, p, w, _, _) in census["instructions"].items())
    assert census["placed_bytes_pct"] == 100.0


@pytest.mark.parametrize("op_name, path, phase", [
    ("jit(step)/jvp(mixer/kda)/delta_rule/while/body/closed_call/dot_general",
     "mixer/kda/delta_rule", "forward"),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/ffn/mlp/tanh",
     "ffn/mlp", "remat"),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/ffn/moe/dispatch/gather",
     "ffn/moe/dispatch", "backward"),
    ("jit(step)/jvp(mixer/mla)/flash/jit(_flash)/ps_flash_fwd", "mixer/mla/flash", "forward"),
    ("jit(step)/jvp(mixer/attention)/flash/custom_vjp_call/transpose", "mixer/attention/flash",
     "forward"),
    ("jit(step)/update/mul", "update", "update"),
    ("jit(step)/grad_reduce/bucket_reduce_o4096/psum", "grad_reduce/bucket_reduce_o4096", "update"),
    ("jit(step)/update/bucket_update_o0/mul", "update/bucket_update_o0", "update"),
    ("jit(step)/grad_reduce/bucket_update_o0/mul", "grad_reduce", "update"),
    ("jit(step)/augment/dynamic_slice", "augment", "input"),
    ("jit(step)/jvp(model)/conv_general_dilated", "model", "forward"),
    # not of the vocabulary: a primitive or a function named like a scope,
    # a sub-scope under the wrong parent, `flash` with no mixer over it
    ("jit(update)/mul", "", "other"),
    ("jit(step)/jvp()/scan/add", "", "forward"),
    ("jit(step)/jvp(head_loss)/scan/while/body/add", "head_loss", "forward"),
    ("jit(step)/flash/ps_flash_jnp/dot_general", "", "other"),
    ("params['blocks'][0]['wq']", "", "other"),
])
def test_the_scope_and_phase_an_op_name_holds(op_name, path, phase):
    assert hlo.scope_path(op_name) == path
    assert hlo.phase_of(op_name, path) == phase


def test_every_vocabulary_path_reads_back_as_itself():
    for path, line in scopes.SCOPES:
        assert line and "\n" not in line
        filled = path.replace("o*", "o4096").replace("*", "mla")
        assert hlo.scope_path(f"jit(step)/jvp({filled})/add") == filled
    assert len(scopes.SCOPES) == len(scopes.PATHS)


# --------------------------------------------------------- the mixed rule

_FUSED = '''HloModule jit_step, is_scheduled=true

%add_f32 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b), metadata={op_name="jit(step)/jvp(head_loss)/reduce_sum"}
}

%fused_computation.1 (param_0: bf16[64,128], param_1: bf16[64,32], param_2: f32[128,32], param_3: f32[128,32]) -> (f32[128,32], f32[128,32]) {
  %param_0 = bf16[64,128]{1,0} parameter(0)
  %param_1 = bf16[64,32]{1,0} parameter(1)
  %dot.1 = f32[128,32]{1,0} dot(%param_0, %param_1), lhs_contracting_dims={0}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/transpose(jvp(ffn/mlp))/dot_general" stack_frame_id=4}
  %param_2 = f32[128,32]{1,0} parameter(2)
  %constant.1 = f32[] constant(0.9)
  %broadcast.1 = f32[128,32]{1,0} broadcast(%constant.1), dimensions={}
  %mul.1 = f32[128,32]{1,0} multiply(%param_2, %broadcast.1), metadata={op_name="jit(step)/update/mul" stack_frame_id=9}
  %add.1 = f32[128,32]{1,0} add(%mul.1, %dot.1), metadata={op_name="jit(step)/update/add" stack_frame_id=9}
  %param_3 = f32[128,32]{1,0} parameter(3)
  %divide.1 = f32[128,32]{1,0} divide(%add.1, %param_3), metadata={op_name="jit(step)/update/div" stack_frame_id=9}
  ROOT %tuple.1 = (f32[128,32]{1,0}, f32[128,32]{1,0}) tuple(%add.1, %divide.1)
}

%fused_computation.2 (param_0.1: f32[128,32]) -> f32[] {
  %param_0.1 = f32[128,32]{1,0} parameter(0)
  %mul.2 = f32[128,32]{1,0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(step)/update/mul"}
  %zero = f32[] constant(0)
  ROOT %reduce.1 = f32[] reduce(%mul.2, %zero), dimensions={0,1}, to_apply=%add_f32, metadata={op_name="jit(step)/jvp(head_loss)/reduce_sum"}
}

%body (t: (s32[], f32[128,32])) -> (s32[], f32[128,32]) {
  %t = (s32[], f32[128,32]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %v = f32[128,32]{1,0} get-tuple-element(%t), index=1
  %tanh.1 = f32[128,32]{1,0} tanh(%v), metadata={op_name="jit(step)/jvp(mixer/ssd)/scan/while/body/tanh"}
  ROOT %out = (s32[], f32[128,32]{1,0}) tuple(%i, %tanh.1)
}

%cond (t.1: (s32[], f32[128,32])) -> pred[] {
  %t.1 = (s32[], f32[128,32]{1,0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main (x: bf16[64,128], dy: bf16[64,32], m: f32[128,32], v: f32[128,32]) -> f32[] {
  %x = bf16[64,128]{1,0} parameter(0), metadata={op_name="x"}
  %dy = bf16[64,32]{1,0} parameter(1)
  %m = f32[128,32]{1,0} parameter(2)
  %v = f32[128,32]{1,0} parameter(3)
  %divide_add_fusion = (f32[128,32]{1,0}, f32[128,32]{1,0}) fusion(%x, %dy, %m, %v), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/update/div"}
  %gte = f32[128,32]{1,0} get-tuple-element(%divide_add_fusion), index=1
  %copy.7 = f32[128,32]{1,0} copy(%gte)
  %zero.1 = s32[] constant(0)
  %init = (s32[], f32[128,32]{1,0}) tuple(%zero.1, %copy.7)
  %while.1 = (s32[], f32[128,32]{1,0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp(mixer/ssd)/scan/while"}
  %w = f32[128,32]{1,0} get-tuple-element(%while.1), index=1
  %all-reduce.1 = f32[128,32]{1,0} all-reduce(%w), replica_groups={}, to_apply=%add_f32, metadata={op_name="jit(step)/grad_reduce/psum"}
  %ps_flash_fwd.3 = f32[128,32]{1,0} custom-call(%all-reduce.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(mixer/mla)/flash/jit(_flash)/ps_flash_fwd"}
  ROOT %reduce_fusion = f32[] fusion(%ps_flash_fwd.3), kind=kInput, calls=%fused_computation.2, metadata={op_name="jit(step)/jvp(head_loss)/reduce_sum"}
}
'''


def test_a_fusion_goes_to_the_instruction_that_does_most_work_and_is_flagged_mixed():
    table = hlo.instruction_scopes(_FUSED)
    # Adam's elementwise update fused into the weight-gradient product:
    # the product's phase and scope, though two of its three named
    # instructions, most of its result bytes and its own name say `update`
    assert table["divide_add_fusion"] == hlo.Place(
        "backward", "ffn/mlp", "dot", ("update:update",))
    # a reduction before the elementwise square it reduces
    assert table["reduce_fusion"] == hlo.Place(
        "forward", "head_loss", "reduce", ("update:update",))
    # a loop's body is read through the loop; the loop itself holds no time
    assert table["tanh.1"] == hlo.Place("forward", "mixer/ssd/scan", "other", ())
    assert table["while.1"].work == "container"
    assert table["all-reduce.1"] == hlo.Place("update", "grad_reduce", "collective", ())
    assert table["ps_flash_fwd.3"] == hlo.Place("forward", "mixer/mla/flash", "kernel", ())
    # the compiler's own copy has no name: it is its reader's, found
    # through the tuple the loop is handed
    assert table["copy.7"] == hlo.Place("forward", "mixer/ssd/scan", "other", (), "user")
    assert "gte" not in table and "x" not in table and "mul.1" not in table


_ASYNC = '''HloModule jit_step, is_scheduled=true

%wrapped_slice (param_0: f32[8,8]) -> f32[4,8] {
  %param_0 = f32[8,8]{1,0} parameter(0)
  ROOT %slice.1 = f32[4,8]{1,0} slice(%param_0), slice={[0:4], [0:8]}
}

ENTRY %main (p: f32[8,8]) -> f32[4,8] {
  %p = f32[8,8]{1,0} parameter(0)
  %slice-start.1 = ((f32[8,8]{1,0}), f32[4,8]{1,0}, s32[]) async-start(%p), calls=%wrapped_slice
  %slice-done.1 = f32[4,8]{1,0} async-done(%slice-start.1), calls=%wrapped_slice
  ROOT %tanh.1 = f32[4,8]{1,0} tanh(%slice-done.1), metadata={op_name="jit(step)/jvp(model)/tanh"}
}
'''


def test_an_async_pair_in_its_generic_spelling_holds_the_op_it_wraps():
    """A program read back from the compile cache (the v5e, PR 35's chip
    run) spells `slice-start` as `async-start(...), calls=%wrapped_slice`:
    the wrapped op runs as no op of its own, and the pair takes its
    reader's place like any other instruction the compiler made."""
    table = hlo.instruction_scopes(_ASYNC)
    assert set(table) == {"slice-start.1", "slice-done.1", "tanh.1"}
    for name in ("slice-start.1", "slice-done.1"):
        assert table[name][:2] == ("forward", "model") and table[name].via == "user"
    sugar = _ASYNC.replace("async-start(%p), calls=%wrapped_slice", "slice-start(%p), slice={[0:4], [0:8]}") \
                  .replace("async-done(%slice-start.1), calls=%wrapped_slice", "slice-done(%slice-start.1)")
    assert hlo.instruction_scopes(sugar) == table
    assert hlo.census(_ASYNC)["placed_bytes_pct"] == hlo.census(sugar)["placed_bytes_pct"] == 100.0


def test_among_equals_the_place_with_most_result_bytes_wins():
    text = _FUSED.replace(
        '%dot.1 = f32[128,32]{1,0} dot(%param_0, %param_1), lhs_contracting_dims={0}, '
        'rhs_contracting_dims={0}, metadata={op_name="jit(step)/transpose(jvp(ffn/mlp))/dot_general"',
        '%dot.1 = f32[128,32]{1,0} add(%param_2, %param_2), '
        'metadata={op_name="jit(step)/transpose(jvp(ffn/mlp))/add_any"')
    assert text != _FUSED
    place = hlo.instruction_scopes(text)["divide_add_fusion"]
    assert place == hlo.Place("update", "update", "other", ("backward:ffn/mlp",))


def test_time_joins_a_capture_by_instruction_name_in_both_spellings():
    table = hlo.census(_FUSED)["instructions"]
    events = [
        ("divide_add_fusion_f32_128_32", 3.0),                               # the benchmark's traces
        ("%reduce_fusion = f32[] fusion(%ps_flash_fwd.3), kind=kInput", 1.0),  # the profiler's own
        ("ps_flash_fwd.3_f32_128_32", 2.0), ("tanh.1_f32_128_32", 0.5), ("copy.7_f32_128_32", 0.25),
        ("fusion.999_f32_8", 0.125),
    ]
    got = hlo.time_by_place(table, events)
    assert got["by_place"] == {
        ("backward", "ffn/mlp", "dot"): 3.0, ("forward", "head_loss", "reduce"): 1.0,
        ("forward", "mixer/mla/flash", "kernel"): 2.0, ("forward", "mixer/ssd/scan", "other"): 0.75}
    assert got["mixed"] == {("backward:ffn/mlp", "update:update"): 3.0,
                            ("forward:head_loss", "update:update"): 1.0}
    assert (got["unfound"], got["inherited"], got["mixed_total"], got["total"]) == (
        0.125, 0.25, 4.0, 6.875)
    assert hlo.instruction_of("divide_add_fusion.2_f32_128_32", table) is None


# ---------------------------------------- kernel_census, a view of the reader

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_KERNEL = re.compile(r"ps_[a-z0-9_]+")


def _kernel_census_as_it_was(hlo_text):
    """ops/pallas_mode.kernel_census before it became a view (PR 34)."""
    census = {"mosaic": {}, "jnp": {}}
    for line in hlo_text.splitlines():
        m = _OP_NAME.search(line)
        if m is None:
            continue
        names = _KERNEL.findall(m.group(1))
        if not names:
            continue
        name = names[-1]
        if 'custom_call_target="tpu_custom_call"' in line:
            census["mosaic"][name] = census["mosaic"].get(name, 0) + 1
        elif name.endswith("_jnp"):
            name = name[: -len("_jnp")]
            census["jnp"][name] = census["jnp"].get(name, 0) + 1
    return census


def test_kernel_census_as_a_view_returns_what_it_did(monkeypatch):
    from ps_pytorch_tpu.ops import flash_attention as fa
    from ps_pytorch_tpu.ops.pallas_mode import kernel_census
    from ps_pytorch_tpu.ops.quantize import quantize_int8

    monkeypatch.delenv("PS_TPU_PALLAS_INTERPRET", raising=False)
    q = jnp.ones((1, 64, 2, 16))

    def f(q, g):
        o = fa.flash_attention(q, q, q, causal=True)
        return jnp.sum(o) + jnp.sum(quantize_int8(g, block_size=0)[0])

    text = jax.jit(jax.grad(f)).lower(q, jnp.ones((256, 128))).compile().as_text()
    was = _kernel_census_as_it_was(text)
    assert was["jnp"].get("ps_flash", 0) > 0
    assert kernel_census(text) == was == hlo.kernel_census(text)
    assert kernel_census(_FUSED) == _kernel_census_as_it_was(_FUSED) == {
        "mosaic": {"ps_flash_fwd": 1}, "jnp": {}}


# ------------------------------- the families' steps and the PS step, tiny


def _lm_cfg(family):
    from ps_pytorch_tpu.models.kda_hybrid import KdaHybridConfig
    from ps_pytorch_tpu.models.mla_moe import MlaMoeConfig
    from ps_pytorch_tpu.models.ssm_hybrid import SsmHybridConfig
    from ps_pytorch_tpu.models.transformer import TransformerConfig

    if family == "dense":
        return TransformerConfig(vocab_size=64, dim=32, depth=2, heads=4, max_seq_len=32,
                                 attention_impl="flash", remat=True)
    if family == "eva_dense":      # windows of 16 over the 32 tokens: the summaries are seen
        from ps_pytorch_tpu.models.eva_dense import EvaByteConfig

        return EvaByteConfig(vocab_size=64, window_size=16, chunk_size=4, attention_impl="flash",
                             remat=True)
    if family == "prerouted_moe":  # a window of 8 under the 32 tokens; a global layer, three sliding
        from ps_pytorch_tpu.models.prerouted_moe import PreroutedMoeConfig

        return PreroutedMoeConfig(attention_impl="flash", remat=True)
    cls = {"mla_moe": MlaMoeConfig, "ssm_hybrid": SsmHybridConfig, "kda_hybrid": KdaHybridConfig}
    return cls[family](attention_impl="flash", remat=True)


def _lm_step(family, donate=False):
    from ps_pytorch_tpu.parallel.dp_sp import (
        init_lm_state, make_lm_train_step, make_mesh_2d, shard_tokens_2d)

    cfg = _lm_cfg(family)
    tx = optax.adam(1e-3)
    mesh = make_mesh_2d(1, 1, devices=jax.devices()[:1])
    params, opt = init_lm_state(cfg, tx, jax.random.key(0), mesh)
    tokens = shard_tokens_2d(jnp.asarray(np.arange(64).reshape(2, 32) % 64, jnp.int32), mesh)
    return make_lm_train_step(cfg, tx, mesh, donate=donate), (params, opt, tokens)


def _ps_step():
    from ps_pytorch_tpu.models import build_model
    from ps_pytorch_tpu.optim import build_optimizer
    from ps_pytorch_tpu.parallel import (
        PSConfig, init_ps_state, make_mesh, make_ps_train_step, shard_batch, shard_state)

    n = 2
    cfg = PSConfig(num_workers=n, compress="int8", bucket_bytes=1 << 16, error_feedback=True)
    mesh = make_mesh(n, devices=jax.devices()[:n])
    model = build_model("LeNet", 10)
    tx = build_optimizer("sgd", 0.1, flat=True)
    state = shard_state(init_ps_state(model, tx, cfg, jax.random.key(0), (28, 28, 1)), mesh, cfg)
    batch = shard_batch({"image": np.zeros((8 * n, 28, 28, 1), np.uint8),
                         "label": np.zeros((8 * n,), np.int32)}, mesh, cfg)
    pre = lambda key, im: im.astype(jnp.float32) / 255.0
    return make_ps_train_step(model, tx, cfg, mesh, preprocess=pre, donate=False), (
        state, batch, jax.random.key(1))


_STEPS = {"dense": lambda: _lm_step("dense"), "mla_moe": lambda: _lm_step("mla_moe"),
          "ssm_hybrid": lambda: _lm_step("ssm_hybrid"), "kda_hybrid": lambda: _lm_step("kda_hybrid"),
          "eva_dense": lambda: _lm_step("eva_dense"),
          "prerouted_moe": lambda: _lm_step("prerouted_moe"), "ps": _ps_step}
_WANTED = {
    "dense": {"embed", "mixer/attention", "mixer/attention/flash", "ffn", "ffn/mlp", "head_loss",
              "grad_reduce", "update"},
    "mla_moe": {"embed", "mixer/mla", "mixer/mla/flash", "ffn", "ffn/mlp", "ffn/moe/route",
                "ffn/moe/dispatch", "ffn/moe/experts", "ffn/moe/combine", "head_loss", "update"},
    "ssm_hybrid": {"embed", "mixer/ssd", "mixer/ssd/scan", "mixer/attention",
                   "mixer/attention/flash", "ffn", "ffn/mlp", "head_loss", "update"},
    "kda_hybrid": {"embed", "mixer/kda", "mixer/kda/delta_rule", "mixer/mla", "mixer/mla/flash",
                   "ffn/mlp", "ffn/moe/dispatch", "ffn/moe/combine", "head_loss", "update"},
    # off the chip the attention takes its jnp twin: the kernel passes' scopes
    # (local, remote, merge) are held by tests/test_evabyte_family.py, interpreted
    "eva_dense": {"embed", "mixer/eva", "mixer/eva/pool", "ffn", "ffn/mlp", "head_loss", "update"},
    # the route stands at the block's entry under the scope it has everywhere; no
    # dense MLP, no shared expert, and nothing rotates in the global layer
    "prerouted_moe": {"embed", "mixer/swa", "mixer/swa/rope", "mixer/swa/kv_repeat",
                      "mixer/swa/flash", "mixer/attention", "mixer/attention/kv_repeat",
                      "mixer/attention/flash", "ffn", "ffn/moe/route", "ffn/moe/dispatch",
                      "ffn/moe/experts", "ffn/moe/combine", "head_loss", "update"},
    "ps": {"augment", "model", "grad_reduce", "update"},
}


@pytest.mark.parametrize("which", sorted(_STEPS))
def test_a_step_compiles_with_nothing_outside_the_vocabulary_above_2_pct_of_result_bytes(which):
    step, args = _STEPS[which]()
    step(*args)
    census = step.scopes()
    assert census["placed_bytes_pct"] >= 98.0, [
        r for r in census["by_place"] if not hlo.is_placed((r["phase"], r["scope"]))]
    found = {r["scope"] for r in census["by_place"]}
    assert _WANTED[which] <= found, _WANTED[which] - found
    phases = {"forward", "backward", "update"} | ({"input"} if which == "ps" else {"remat"})
    assert phases <= set(census["phases"])
    for row in census["by_place"]:
        scope_ = row["scope"]
        assert not scope_ or hlo.in_vocabulary(scope_)
    # the registry hands the same step back to a reader that never held it
    program = "ps_train_step" if which == "ps" else "lm_train_step"
    assert scopes.last_step(program) is step


@pytest.fixture()
def no_compile_cache():
    """The persistent compile cache leaves metadata out of its key, so a
    program found there carries the scopes of whoever compiled it first; a
    CLI test that ran earlier on this worker turns the cache on for the
    process (`utils.enable_persistent_compile_cache`)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.mark.parametrize("which", sorted(_STEPS))
def test_a_scope_is_metadata_the_compiled_code_is_the_same_without_it(
        which, monkeypatch, no_compile_cache):
    def text():
        step, args = _STEPS[which]()
        return step.lower(*args).compile().as_text()

    with_scopes = text()
    with monkeypatch.context() as m:
        m.setattr(scopes, "_named_scope", lambda name: contextlib.nullcontext())
        jax.clear_caches()
        without = text()
    jax.clear_caches()
    assert _sha(with_scopes) == _sha(without)
    top = re.compile(r'op_name="[^"]*[/(](mixer|ffn|head_loss|grad_reduce|augment|model)[/)]')
    assert top.search(with_scopes) and not top.search(without)


# ------------------------------------------------------- the step's census


def test_a_steps_program_carries_the_sources_stamp_in_its_name():
    """The compile cache's key leaves metadata out: an executable found
    there has the scopes of whoever compiled it. The program's name is in
    the key, and the builders put the sources' stamp in it."""
    step, args = _lm_step("dense")
    name = scopes.stamped_name("worker_fn")
    assert re.fullmatch(r"worker_fn_src[0-9a-f]{8}", name)
    assert step.lower(*args).compile().as_text().startswith(f"HloModule jit_{name},")
    ps, ps_args = _ps_step()
    assert f"HloModule jit_{scopes.stamped_name('step')}," in ps.lower(*ps_args).compile().as_text()
    assert scopes.source_stamp() is scopes.source_stamp()     # read once a process


def test_scopes_reads_the_executable_that_ran_without_compiling_again():
    from jax import monitoring

    step, args = _lm_step("dense")
    with pytest.raises(RuntimeError, match="before the step's first call"):
        step.scopes()
    step(*args)
    compiled = []
    listen = lambda name, *a, **kw: compiled.append(name) if "backend_compile" in name else None
    monitoring.register_event_duration_secs_listener(listen)
    try:
        census = step.scopes()
    finally:
        monitoring.unregister_event_duration_listener(listen)
    assert compiled == []
    assert step.scopes() is census          # read once and kept
    # the jitted function's own surface is still there
    assert step.lower(*args).compile().as_text() == step.compiled_text()


def _add(s, k, n):
    return s + n


def _same(a):
    return a


def test_the_step_wrapper_notes_uncommitted_arguments_as_they_were():
    step = ScopedStep("test_noop", jax.jit(_add))
    out = step(jnp.zeros(3), jax.random.key(0), 2)
    a, k, n = step._avals
    assert (a.shape, a.sharding, k.sharding, n) == ((3,), None, None, 2)
    put = jax.device_put(out, jax.devices()[0])
    fresh = ScopedStep("test_noop", jax.jit(_same))
    fresh(put)
    assert fresh._avals[0].sharding == put.sharding
    assert scopes.last_step("test_noop") is fresh and scopes.last_step("no such") is None


def test_the_registry_holds_a_step_weakly_and_keeps_the_census_of_one_a_capture_saw(tmp_path):
    """A loaded executable keeps its code and scratch on the device, so the
    registry must not keep a step its loop let go of; a step that ran under
    a profiler capture reads its census as it goes."""
    import weakref

    step, p, x = _small_step()
    step(p, x)
    gone = weakref.ref(step._jitted)
    program = step.program
    del step
    assert gone() is None                                    # nothing pins the executable
    left = scopes.last_step(program)
    with pytest.raises(RuntimeError, match="no capture ran"):
        left.scopes()

    step, p, x = _small_step()
    step(p, x)
    jax.profiler.start_trace(str(tmp_path))
    try:
        step(p, x)
    finally:
        jax.profiler.stop_trace()
    step(p, x)
    assert scopes.last_step(program) is step
    want = step.compiled_text()
    gone = weakref.ref(step._jitted)
    del step
    assert gone() is None
    left = scopes.last_step(program)
    assert left.program == program
    assert left.scopes()["instructions"] == hlo.census(want)["instructions"]


def test_write_step_scopes_and_the_instant(tmp_path):
    import json

    from ps_pytorch_tpu.obs.schema import validate_event

    step, p, x = _small_step()
    step(p, x)
    path = scopes.write_step_scopes(str(tmp_path / "prof"), step)
    with open(path) as f:
        assert json.load(f)["instructions"] == step.scopes()["instructions"]
    assert scopes.write_step_scopes(str(tmp_path), jax.jit(_same)) is None
    rec = validate_event({"kind": "span", "name": "step_scopes", "t": 0.0, "dur": 0.0,
                          **scopes.step_scopes_instant(step)})
    assert rec["instructions"] > 0 and rec["mosaic_calls"] == 0
    assert "remat" in rec["phases"].split(",") and "ffn/mlp" in rec["scopes"].split(",")


# ------------------------------------------------ what an operator gets


def _no_profiler(monkeypatch):
    """The capture itself is jax's; here only what the program writes beside it."""
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: calls.append(("stop",)))
    return calls


def test_train_lm_records_the_census_once_and_writes_it_beside_the_capture(tmp_path, monkeypatch):
    import json

    from ps_pytorch_tpu.cli import train_lm
    from ps_pytorch_tpu.obs.schema import validate_event

    calls = _no_profiler(monkeypatch)
    prof = tmp_path / "prof"
    train_lm.main([
        "--dim", "32", "--depth", "1", "--heads", "2", "--seq-len", "32", "--vocab-size", "64",
        "--batch-size", "2", "--max-steps", "4", "--log-interval", "2", "--num-dp", "1",
        "--num-sp", "1", "--attention-impl", "flash", "--remat",
        "--trace", str(tmp_path / "trace"), "--profile-dir", str(prof)])
    spans = [json.loads(line) for line in open(tmp_path / "trace" / "trace_train_lm_p0.jsonl")]
    (instant,) = [s for s in spans if s.get("name") == "step_scopes"]
    validate_event(dict(instant))
    assert instant["program"] == "lm_train_step" and instant["instructions"] > 50
    assert {"forward", "backward", "remat", "update"} <= set(instant["phases"].split(","))
    assert {"mixer/attention/flash", "ffn/mlp", "head_loss", "update"} <= set(
        instant["scopes"].split(","))
    assert instant["placed_bytes_pct"] >= 98.0 and instant["census_s"] >= 0
    assert [c[0] for c in calls] == ["start", "stop"]
    with open(prof / "step_scopes.json") as f:
        census = json.load(f)
    assert census["program"] == "lm_train_step"
    assert len(census["instructions"]) >= instant["instructions"]
    # without --trace no instant is taken, and a scheme whose step keeps no
    # census writes none
    train_lm.main([
        "--dim", "32", "--depth", "1", "--heads", "2", "--seq-len", "32", "--vocab-size", "64",
        "--batch-size", "2", "--max-steps", "3", "--parallelism", "tp", "--num-shards", "1",
        "--profile-dir", str(tmp_path / "prof_tp")])
    assert not (tmp_path / "prof_tp" / "step_scopes.json").exists()


def test_the_ps_trainers_profile_window_writes_the_census_when_it_stops(tmp_path, monkeypatch):
    import json

    from ps_pytorch_tpu.data import make_synthetic
    from ps_pytorch_tpu.parallel import PSConfig
    from ps_pytorch_tpu.trainer import TrainConfig, Trainer

    _no_profiler(monkeypatch)
    prof = tmp_path / "prof"
    tcfg = TrainConfig(
        network="LeNet", dataset="MNIST", batch_size=8, max_steps=4, epochs=1, eval_freq=0,
        log_interval=2, save_checkpoints=False, train_dir=str(tmp_path / "models"),
        profile_dir=str(prof), profile_start=2, profile_steps=2)
    Trainer(tcfg, PSConfig(num_workers=2),
            dataset=make_synthetic("MNIST", train_size=64, test_size=32, seed=1)).train()
    with open(prof / "step_scopes.json") as f:
        census = json.load(f)
    assert census["program"] == "ps_train_step"
    assert {"forward", "backward", "update"} <= set(census["phases"])
    assert {"model", "grad_reduce", "update"} <= {r["scope"] for r in census["by_place"]}


class _Fake:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_trace_report_device_joins_the_census_with_the_capture(tmp_path, monkeypatch, capsys):
    import json
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    import trace_report

    prof = tmp_path / "prof"
    (prof / "plugins" / "profile" / "run").mkdir(parents=True)
    (prof / "plugins" / "profile" / "run" / "vm.xplane.pb").write_bytes(b"")
    with open(prof / "step_scopes.json", "w") as f:
        json.dump({"program": "lm_train_step", **hlo.census(_FUSED)}, f)
    ms = lambda v: int(v * 1e6)
    ops = [("%divide_add_fusion = (f32[128,32]{1,0}) fusion(%x, %dy)", 3.0),
           ("%reduce_fusion = f32[] fusion(%ps_flash_fwd.3)", 1.0),
           ("%ps_flash_fwd.3 = f32[128,32]{1,0} custom-call(%all-reduce.1)", 2.0),
           ("%tanh.1 = f32[128,32]{1,0} tanh(%v)", 0.5), ("%while.1 = (s32[]) while(%init)", 0.5),
           ("%copy.7 = f32[128,32]{1,0} copy(%gte)", 0.25), ("%fusion.999 = f32[8] fusion()", 0.25)]
    plane = _Fake(name="/device:TPU:0", lines=[
        _Fake(name="XLA Modules", events=[_Fake(name="jit_worker_fn(1)", duration_ns=ms(3.5))
                                          for _ in range(2)] + [_Fake(name="jit_small(2)",
                                                                      duration_ns=ms(0.1))]),
        _Fake(name="XLA Ops", events=[_Fake(name=n, duration_ns=ms(d)) for n, d in ops] * 2)])
    host = _Fake(name="/host:CPU", lines=[])
    from jax.profiler import ProfileData
    monkeypatch.setattr(ProfileData, "from_file",
                        staticmethod(lambda path: _Fake(planes=[host, plane])))
    report = trace_report.device_report(str(prof))
    assert (report["steps"], report["devices"], report["step_ms"]) == (2, 1, 7.0)
    assert report["ms_by_phase"] == {"forward": 3.75, "backward": 3.0}
    assert report["ms_by_scope"] == {"ffn/mlp": 3.0, "mixer/mla/flash": 2.0, "head_loss": 1.0,
                                     "mixer/ssd/scan": 0.75}
    assert report["mixed_ms"] == 4.0 and report["mixed_ms_by_pair"] == {
        "backward:ffn/mlp | update:update": 3.0, "forward:head_loss | update:update": 1.0}
    assert (report["unplaced_ms"], report["not_in_the_census_ms"],
            report["placed_by_a_neighbour_ms"]) == (0.25, 0.25, 0.25)
    assert trace_report.main(["device", str(prof)]) == 0
    out = capsys.readouterr().out
    assert "by phase" in out and "mixer/mla/flash" in out and "unplaced" in out
    # a capture of the CPU backend holds no device ops: said, not raised as a bug
    monkeypatch.setattr(ProfileData, "from_file", staticmethod(lambda path: _Fake(planes=[host])))
    with pytest.raises(SystemExit, match="no device plane"):
        trace_report.device_report(str(prof))
