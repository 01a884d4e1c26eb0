"""chip_smoke.py — does the system still start on the chip?

    python chip_smoke.py

Drives the three normal entry points once, in this one process, on every
TPU chip the process sees, through the same ``main(argv)`` functions the
CLIs expose, on synthetic data made from a seed:

  device    refuse anything but a TPU; say what was found
  kernels   every Pallas entry against its jnp reference (oracle at HIGHEST
            matmul precision), each proven Mosaic-compiled by the text of
            the program that ran
  ps        cli.train, ResNet-18 / Cifar10 at batch 128 per worker: the
            default wire, then the int8 wire (on several chips also the
            homomorphic 2-round wire), a checkpoint, cli.evaluate --once
  lm        cli.train_lm at d512 x 6, seq 1024, bf16, flash attention, with
            two checkpoints
  serve     cli.serve on the older checkpoint, a handful of requests and
            one hot rollover onto the newer
  lm_config cli.train_lm --lm-config on the small preset of the latent-
            attention / dropless-expert family (192-wide q/k, 128-wide v,
            8 of 16 routed experts held), bf16, flash, remat, Adam
  lm_ssm    cli.train_lm --lm-config on a small preset of the hybrid
            state-space / attention family (Mamba-2 heads of 64, state 128,
            chunks of 256; 4 query over 2 key/value heads; m m a m), bf16,
            flash, remat, Adam, the short conv as `ps_causal_conv_*` in
            the step and its loss beside PS_TPU_DISABLE_PALLAS's; then the
            chunked scan on the chip against the token-by-token recurrence
  lm_kda    cli.train_lm --lm-config on a small preset of the hybrid
            delta-rule / latent-attention expert family (KDA heads of 128,
            chunks of 64; k k k a k; 8 of 16 routed experts held), bf16,
            flash, remat, Adam, the short convs as `ps_causal_conv_*`;
            then the chunked delta rule on the chip against the
            token-by-token recurrence, past -88 a chunk too
  lm_eva    cli.train_lm --lm-config on benchmark/configs/
            evabyte_6b5_4layers.json itself (the dense EVA-attention
            family at its published widths, four layers) at a short row of
            4,096 bytes, two windows, so that the pass over chunk
            summaries runs; bf16, flash, remat, Adam; then ops/eva.
            eva_attention on the chip against its jnp twin
  lm_swa    cli.train_lm --lm-config on a small preset of the sliding-
            window / global grouped-query attention expert family (heads of
            128, 9 and 6 query heads over 2 key/value heads, a window of
            512 under a row of 2,048, YaRN on half a global head, a gate a
            head; g s s s g; 8 of 16 routed experts held), bf16, flash,
            remat, Adam; then flash_attention under the window on the chip
            against its jnp twin
  lm_pre    cli.train_lm --lm-config on a small preset of the family whose
            experts are routed from the attention's input (heads of 128, 14
            query heads over 2 key/value heads: the seven-fold repeat; a
            window of 512 with rotary beside a global layer without
            positions, g s s s; softmax over the 6 chosen logits of 16, 8
            held, ReLU-gated experts, no shared one), bf16, flash, remat,
            Adam: the plans it lists and `moe_gate_active` about a half

While each ``main`` runs, jax's own compile log is read: no step program
may compile twice for the same argument shapes. After each trainer leg the
same step is built once more through the library, so the script can look at
what ``main`` keeps to itself: the compiled program's text (Mosaic custom
calls by kernel name), the sharding of every state and batch leaf after a
step, and each device's memory.

A leg that fails raises: the traceback is the report, the exit code is
non-zero and no result line is printed. On success the last two lines of
stdout are JSON objects. The second to last is the summary: per-leg
ok / seconds / cache hits, compile seconds, and no speed — sizes, rates and
utilization are the benchmark's job, not this script's (``"claim": null``).
The LAST is the result, and holds exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as jax reports it; whoever runs this script parses that
line and refuses any other key in it.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.metadata
import json
import logging
import math
import os
import sys
import tempfile
import time

# the model shapes are the repo's own: ResNet-18 / Cifar10 is the
# reference's canonical job (run_pytorch.sh), d512 x 6 / seq 1024 / batch 8
# is the repo's own small LM shape. Steps are few; widths are not cut.
PS_ARGS = [
    "--network", "ResNet18", "--dataset", "Cifar10", "--batch-size", "128",
    "--lr", "0.01", "--momentum", "0.9", "--max-steps", "10",
    "--eval-freq", "0", "--log-interval", "5",
]
PS_WIRES = {
    "default": [],
    "int8": ["--compress-grad", "compress", "--bucket-bytes", "4194304",
             "--error-feedback"],
    # several chips only: accumulate_rescale_int8 and the int8
    # all_to_all / all_gather have nothing to do on one
    "2round_homomorphic": [
        "--compress-grad", "2round", "--wire-domain", "homomorphic",
        "--bucket-bytes", "4194304", "--error-feedback",
    ],
}
# Mosaic kernels each compiled PS step must contain
PS_KERNELS = {
    "default": (),
    "int8": ("ps_quantize_2d",),
    "2round_homomorphic": ("ps_quantize_2d", "ps_accum_rescale"),
}
LM_ARGS = [
    "--dim", "512", "--depth", "6", "--heads", "8", "--seq-len", "1024",
    "--batch-size", "8", "--dtype", "bfloat16", "--attention-impl", "flash",
    "--max-steps", "6", "--eval-freq", "3", "--log-interval", "1",
]
# the LM legs' shapes are far under plan_flash's cap: the backward is the
# one fused kernel, and the split pair must not show
LM_KERNELS = ("ps_flash_fwd", "ps_flash_dqkv")
LM_KERNELS_ABSENT = ("ps_flash_dq", "ps_flash_dkv")
SERVE_ARGS = [
    "--step", "3", "--slots", "8", "--requests", "16", "--rate", "20",
    "--prompt-min", "4", "--prompt-max", "16", "--new-min", "8",
    "--new-max", "32", "--poll-interval", "0.05", "--dtype", "bfloat16",
]
# the small preset of the latent-attention / dropless-expert family: the
# published head widths (192-wide q/k beside a 128-wide v), 8 of 16 routed
# experts held, one dense and one expert layer; built through --lm-config
LM_CONFIG = {
    "model_type": "deepseek_v3", "vocab_size": 1024, "hidden_size": 512,
    "num_hidden_layers": 2, "num_attention_heads": 4, "kv_lora_rank": 128,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "intermediate_size": 1024, "moe_intermediate_size": 256,
    "n_routed_experts": 16, "n_shared_experts": 2, "num_experts_per_tok": 3,
    "first_k_dense_replace": 1, "routed_scaling_factor": 2.448,
    "norm_topk_prob": True, "rope_theta": 1000000, "rms_norm_eps": 1e-6,
    "rope_interleave": True, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "experts_held": 8, "expert_offset": 0,
}
# a small preset of the hybrid state-space / attention family at the
# published head widths (mamba heads of 64 over a state of 128, chunks of
# 256, attention heads of 64, two of them sharing each key/value head)
LM_SSM_CONFIG = {
    "model_type": "granitemoehybrid", "vocab_size": 1024, "hidden_size": 256,
    "num_hidden_layers": 4, "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "shared_intermediate_size": 512, "mamba_n_heads": 8, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_n_groups": 1, "mamba_d_conv": 4,
    "mamba_chunk_size": 256, "mamba_expand": 2, "mamba_conv_bias": True,
    "attention_multiplier": 0.015625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 8, "rms_norm_eps": 1e-5,
    "position_embedding_type": "nope", "num_local_experts": 0,
    "tie_word_embeddings": True,
}
# a small preset of the hybrid delta-rule / latent-attention expert family
# at the published head widths (KDA heads of 128 for keys and values, 4
# taps, chunks of 64; latent attention 128 + 64 and 128 without rotation)
LM_KDA_CONFIG = {
    "model_type": "kimi_linear", "vocab_size": 1024, "hidden_size": 512,
    "num_hidden_layers": 5, "num_attention_heads": 4, "kv_lora_rank": 128,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "intermediate_size": 1024, "moe_intermediate_size": 256,
    "num_experts": 16, "num_experts_per_token": 3, "num_shared_experts": 1,
    "first_k_dense_replace": 1, "routed_scaling_factor": 2.446,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
    "num_expert_group": 1, "topk_group": 1, "mla_use_nope": True,
    "rope_theta": 10000, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "linear_attn_config": {"kda_layers": [1, 2, 3, 5], "full_attn_layers": [4],
                           "num_heads": 4, "head_dim": 128,
                           "short_conv_kernel_size": 4},
    "experts_held": 8, "expert_offset": 0,
}
# a small preset of the sliding-window / global grouped-query attention
# expert family at the published head width, window and head ratios (128-wide
# heads, 9 and 6 query heads a key/value head, a window of 512, YaRN on the
# first half of a global head over 512 original positions)
LM_SWA_CONFIG = {
    "model_type": "laguna", "vocab_size": 1024, "hidden_size": 512, "intermediate_size": 1024,
    "num_hidden_layers": 5, "num_attention_heads": 12, "num_key_value_heads": 2,
    "head_dim": 128, "rms_norm_eps": 1e-6, "num_experts": 16, "num_experts_per_tok": 5,
    "moe_intermediate_size": 256, "shared_expert_intermediate_size": 256,
    "norm_topk_prob": True, "moe_routed_scaling_factor": 2.5, "sliding_window": 512,
    "gating": "per-head", "tie_word_embeddings": False, "moe_router_logit_softcapping": 0,
    "rope_parameters": {
        "full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 16,
                           "original_max_position_embeddings": 512, "beta_slow": 1,
                           "beta_fast": 32, "attention_factor": 1.28,
                           "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": ["full_attention", "sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"],
    "num_attention_heads_per_layer": [12, 18, 18, 18, 12],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "experts_held": 8, "expert_offset": 0,
}
# a small preset of the family routed from the attention's input, at the
# published head width and head ratio (128-wide heads, seven query heads a
# key/value head), a window of 512 and the published 0, 1, 1, 1 layouts
LM_PRE_CONFIG = {
    "model_type": "smallthinker", "vocab_size": 1024, "hidden_size": 512,
    "num_hidden_layers": 4, "num_attention_heads": 14, "num_key_value_heads": 2,
    "head_dim": 128, "rms_norm_eps": 1e-6, "moe_num_primary_experts": 16,
    "moe_num_active_primary_experts": 6, "moe_ffn_hidden_size": 256,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "sliding_window_size": 512, "sliding_window_layout": [0, 1, 1, 1],
    "rope_layout": [0, 1, 1, 1], "rope_theta": 1500000, "rope_scaling": None,
    "tie_word_embeddings": False, "experts_held": 8, "expert_offset": 0,
}
LM_CONFIG_ARGS = [
    "--seq-len", "1024", "--batch-size", "2", "--dtype", "bfloat16",
    "--attention-impl", "flash", "--optimizer", "adam", "--lr", "0.0003",
    "--max-steps", "4", "--log-interval", "2", "--remat",
]
# the dropless layer's grouped products and its way back to the tokens
# (ops/grouped_matmul.py, ops/moe_rows_sum.py)
LM_CONFIG_KERNELS = LM_KERNELS + ("ps_moe_gmm", "ps_moe_tgmm", "ps_moe_rows_sum")
# the grouped-query families' rotation of q and k (ops/rope.py)
LM_GQA_KERNELS = LM_CONFIG_KERNELS + ("ps_rope",)
# the short conv of the state-space and the delta-rule mixers (ops/causal_conv.py)
CONV_KERNELS = ("ps_causal_conv_fwd", "ps_causal_conv_bwd")
LM_KDA_KERNELS = LM_CONFIG_KERNELS + CONV_KERNELS + (
    "ps_kda_inverse", "ps_kda_within_fwd", "ps_kda_within_bwd")
FLASH_SHAPE = (8, 1024, 8, 64)  # B, T, H, D: the LM leg's attention
BUCKET_ELEMS = (4 << 20) // 4   # one 4 MiB f32 gradient bucket


class CompileLog(logging.Handler):
    """What jax compiled and what its persistent cache did, from jax's own
    log records (they name the program; its monitoring events do not).

    Matches three message formats of the installed jax; ``check_steps``
    refuses to pass on an empty log, so a jax that words them differently
    fails the smoke instead of silently passing it."""

    LOGGERS = ("jax._src.interpreters.pxla", "jax._src.compiler")

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.compiled = []  # (program, argument shapes)
        self.hits = []      # persistent cache, by module name
        self.misses = []
        self._saved = []

    def emit(self, record):
        msg = str(record.msg)
        if msg.startswith("Compiling %s with global shapes"):
            self.compiled.append((record.args[0], str(record.args[1])))
        elif msg.startswith("Persistent compilation cache hit"):
            self.hits.append(record.args[0])
        elif msg.startswith("PERSISTENT COMPILATION CACHE MISS"):
            self.misses.append(record.args[0])
        elif record.levelno >= logging.WARNING:
            sys.stderr.write(record.getMessage() + "\n")

    def __enter__(self):
        for name in self.LOGGERS:
            lg = logging.getLogger(name)
            self._saved.append((lg, lg.level, lg.propagate))
            lg.setLevel(logging.DEBUG)
            lg.propagate = False  # DEBUG records stop here, not on stderr
            lg.addHandler(self)
        return self

    def __exit__(self, *exc):
        for lg, level, propagate in self._saved:
            lg.removeHandler(self)
            lg.setLevel(level)
            lg.propagate = propagate
        self._saved = []

    def check_steps(self, leg, programs):
        """Each named step program was compiled, and no (program, argument
        shapes) pair twice — a second compile of the same shapes is a
        sharding the first call did not have (state left off the mesh).
        Returns {program: "hit" | "miss"}: what the persistent cache did
        for it (a program that missed on any shape set is a miss)."""
        outcome = {}
        for prog in programs:
            seen = [s for p, s in self.compiled if p == prog]
            if not seen:
                raise AssertionError(
                    f"{leg}: {prog} never compiled — wrong program name, "
                    f"or jax words its compile log differently"
                )
            if len(set(seen)) != len(seen):
                raise AssertionError(
                    f"{leg}: {prog} compiled {len(seen)} times for "
                    f"{len(set(seen))} distinct argument shapes"
                )
            module = prog.replace("(", "_").replace(")", "")
            outcome[prog] = (
                "hit" if module in self.hits and module not in self.misses
                else "miss"
            )
            print(f"[{leg}] {prog}: compiled once per shape set "
                  f"({len(seen)}), persistent cache {outcome[prog]}",
                  flush=True)
        return outcome


def check_on_all_devices(leg, what, tree, devices):
    import jax

    want = set(devices)
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        if not isinstance(leaf, jax.Array):
            continue
        if set(leaf.sharding.device_set) != want:
            raise AssertionError(
                f"{leg}: {what}{jax.tree_util.keystr(path)} lives on "
                f"{len(leaf.sharding.device_set)} of {len(want)} devices "
                f"({leaf.sharding})"
            )


def check_memory_in_use(leg, devices):
    for d in devices:
        in_use = (d.memory_stats() or {}).get("bytes_in_use", 0)
        if not in_use:
            raise AssertionError(f"{leg}: {d} reports no memory in use")
    print(f"[{leg}] all {len(devices)} device(s) hold live buffers",
          flush=True)


def step_program(name):
    """A train step's program as jax's compile log names it: the builders
    name it after their function and the sources' stamp (obs/scopes.py)."""
    from ps_pytorch_tpu.obs.scopes import stamped_name

    return f"jit({stamped_name(name)})"


def check_kernels(leg, hlo_text, expect):
    """The compiled program runs each expected kernel as a Mosaic custom
    call; an entry that took its jnp twin or interpret mode is named."""
    from ps_pytorch_tpu.ops.pallas_mode import kernel_census

    census = kernel_census(hlo_text)
    missing = [k for k in expect if not census["mosaic"].get(k)]
    print(f"[{leg}] kernels: mosaic={census['mosaic']} "
          f"jnp_twin={census['jnp']}", flush=True)
    if missing:
        raise AssertionError(
            f"{leg}: no Mosaic custom call for {missing} in the compiled "
            f"program (jnp twin or interpret mode took them); census "
            f"{census}"
        )
    split = [k for k in LM_KERNELS_ABSENT if census["mosaic"].get(k)]
    if split:
        raise AssertionError(
            f"{leg}: the split flash backward {split} ran where plan_flash "
            f"fuses it (every leg's shapes are far under its cap)"
        )


def check_scopes(leg, hlo_text, remat):
    """The compiled step still carries the program's scopes (obs/scopes.py):
    its census places at least 98% of the result bytes and holds every
    phase the step has. A scope lost in a refactor fails here, on the chip
    path, before a benchmark metric falls silent."""
    from ps_pytorch_tpu.obs.hlo import census as read

    census = read(hlo_text)
    want = {"forward", "backward", "update"} | ({"remat"} if remat else set())
    print(f"[{leg}] scopes: {census['placed_bytes_pct']:.2f}% of result bytes "
          f"placed, phases {census['phases']}", flush=True)
    if census["placed_bytes_pct"] < 98.0 or not want <= set(census["phases"]):
        raise AssertionError(
            f"{leg}: the step's census places {census['placed_bytes_pct']:.2f}% of "
            f"result bytes (98 wanted) with phases {census['phases']} ({sorted(want)} "
            f"wanted); by place: {census['by_place'][:8]}")


def check_finite(leg, name, value):
    if not math.isfinite(float(value)):
        raise AssertionError(f"{leg}: {name} is {value}")


@contextlib.contextmanager
def jnp_twins():
    """Trace under PS_TPU_DISABLE_PALLAS: every ops/ entry takes its jnp
    twin — the reference side of the quantizer parities."""
    os.environ["PS_TPU_DISABLE_PALLAS"] = "1"
    try:
        yield
    finally:
        del os.environ["PS_TPU_DISABLE_PALLAS"]


def library_lm_step(config_path, num_dp, num_sp, batch, seq=None):
    """The step `cli.train_lm --lm-config <config_path>` + LM_CONFIG_ARGS
    runs (at `seq` tokens a row where given), built once more through the
    library and compiled: (cfg, the compiled step, its (params, opt_state,
    tokens))."""
    import jax
    import jax.numpy as jnp

    from ps_pytorch_tpu.cli import train_lm as train_lm_cli
    from ps_pytorch_tpu.models.lm import lm_family, load_lm_config
    from ps_pytorch_tpu.models.transformer import remat_plan
    from ps_pytorch_tpu.optim import build_optimizer
    from ps_pytorch_tpu.parallel.dp_sp import (
        init_lm_state,
        make_lm_train_step,
        make_mesh_2d,
        shard_tokens_2d,
    )

    cfg = load_lm_config(config_path, attention_impl="flash", remat=True,
                         compute_dtype=jnp.bfloat16)
    tx = build_optimizer("adam", 3e-4)
    mesh = make_mesh_2d(num_dp, num_sp)
    params, opt_state = init_lm_state(cfg, tx, jax.random.key(1), mesh)
    seq = seq or int(LM_CONFIG_ARGS[LM_CONFIG_ARGS.index("--seq-len") + 1])
    if num_sp == 1:  # the ring's hops name nothing
        saves = remat_plan(lm_family(cfg).saved_layers(cfg, batch // num_dp, seq), params)
        print(f"[{os.path.basename(config_path)}] remat keeps {','.join(saves.names)}: "
              f"{saves.saved_bytes / 2 ** 20:.1f} MiB as stored, of a limit of "
              f"{saves.bytes_limit / 2 ** 20:.0f} MiB", flush=True)
    tokens = shard_tokens_2d(
        jnp.asarray(train_lm_cli.make_synthetic_tokens(
            cfg.vocab_size, batch, seq, seed=2)), mesh)
    step = make_lm_train_step(cfg, tx, mesh).lower(
        params, opt_state, tokens
    ).compile()
    return cfg, step, (params, opt_state, tokens)


def check_passes(leg, cfg, params, tokens):
    """The dropless layer's passes on the chip (parallel/moe.py): the
    family's loss and gradient norm, bfloat16 under `remat`, with a pass a
    quarter of the worst case beside the same call at the worst-case size
    (one pass, whatever the routing), as the weights route and with every
    token sent to experts held here. The small presets hold half their
    experts, so the layer's own pass IS the worst case and the step above
    ran one pass a layer; a quarter takes several. Then the same call in
    one pass with the way back to the tokens (the forward's combine and the
    tokens' gradient) through ops/moe_rows_sum's compiled kernel beside the
    one through its jnp twin, and the counter that says which of them ran."""
    from unittest import mock

    import jax
    import numpy as np
    import optax

    from ps_pytorch_tpu.models.lm import lm_family
    from ps_pytorch_tpu.ops.grouped_matmul import TILE_M, buffer_rows
    from ps_pytorch_tpu.ops.metrics import next_token_nll
    from ps_pytorch_tpu.parallel import moe

    family, spec = lm_family(cfg), cfg.routing
    params = jax.device_get(params)
    tokens = np.asarray(jax.device_get(tokens))[:2]
    worst = buffer_rows(tokens.size * spec.top_k, spec.experts_held)
    quarter = worst // 4 // TILE_M * TILE_M
    chosen = np.arange(spec.num_experts) - spec.expert_offset
    bias = np.where((chosen >= 0) & (chosen < spec.top_k), 10.0, 0.0).astype(np.float32)
    forced = {**params, "blocks": [{**blk, "router_bias": bias} if "router_bias" in blk else blk
                                   for blk in params["blocks"]]}

    def run(p, rows):
        def loss_fn(p):
            logits, aux = family.apply(cfg, p, tokens)
            return next_token_nll(logits, tokens), family.counters(aux)

        with mock.patch.object(moe, "pass_rows", lambda n, spec: rows):
            (loss, c), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(p)
        return float(loss), float(optax.global_norm(grads)), jax.device_get(c)

    for name, p in (("as the weights route", params), ("every token to experts held here", forced)):
        loss, norm, c = run(p, quarter)
        want_loss, want_norm, want_c = run(p, worst)
        passes = c["moe_passes_per_layer"].tolist()
        print(f"[{leg}] passes of {quarter} rows of {worst}, {name}: moe_passes_per_layer "
              f"{passes}, rows here {c['moe_rows_here_per_layer'].tolist()}, loss {loss:.6f} | "
              f"{want_loss:.6f} in one pass, gradient norm {norm:.6f} | {want_norm:.6f}", flush=True)
        # the first expert layer sees the same input either way; behind it a
        # bfloat16 rounding may tip a near-tie in the routing
        same = all(c[k][0] == want_c[k][0]
                   for k in ("moe_rows_here_per_layer", "moe_tokens_unserved_per_layer"))
        if (min(passes) < 2 or set(want_c["moe_passes_per_layer"].tolist()) != {1} or not same
                or abs(loss - want_loss) > 5e-3 * abs(want_loss)
                or abs(norm - want_norm) > 5e-2 * want_norm):
            raise AssertionError(f"{leg}: the layer in passes is not the layer in one ({name})")
    path = moe.rows_sum_path(cfg.hidden_size, "bfloat16")
    loss, norm, c = run(params, worst)
    with mock.patch.object(moe, "rows_sum", lambda ys, pos, held, twin: twin(ys)), \
            mock.patch.object(moe, "rows_sum_path", lambda d, dtype: "xla"):
        twin_loss, twin_norm, twin_c = run(params, worst)
    read, here = int(c["moe_combine_rows_read"]), int(c["moe_rows_here"])
    every = tokens.size * spec.top_k * int(twin_c["moe_passes"])
    print(f"[{leg}] the way back to the tokens through {path} | its jnp twin: loss {loss:.6f} | "
          f"{twin_loss:.6f}, gradient norm {norm:.6f} | {twin_norm:.6f}, moe_combine_rows_read "
          f"{read} (rows here {here}) | {int(twin_c['moe_combine_rows_read'])}", flush=True)
    if (path != "pallas" or read != here or int(twin_c["moe_combine_rows_read"]) != every
            or abs(loss - twin_loss) > 1e-5 * abs(twin_loss) or abs(norm - twin_norm) > 1e-4 * twin_norm):
        raise AssertionError(f"{leg}: the layer through ps_moe_rows_sum is not the layer through its twin")


def check_conv(leg, cfg, params, tokens, what="conv kernels"):
    """The short conv's kernels inside the family's loss: loss and gradient
    norm of two rows, bfloat16, as this process runs them beside the same
    call under PS_TPU_DISABLE_PALLAS (every entry's jnp twin, the plain
    conv among them)."""
    from unittest import mock

    import jax
    import numpy as np
    import optax

    from ps_pytorch_tpu.models.lm import lm_family
    from ps_pytorch_tpu.ops.metrics import next_token_nll

    family = lm_family(cfg)
    tokens = np.asarray(jax.device_get(tokens))[:2]

    def run(env):
        def loss_fn(p):
            return next_token_nll(family.apply(cfg, p, tokens)[0], tokens)

        jax.clear_caches()      # a mixer's cached `jax.checkpoint` trace keeps the form it took
        with mock.patch.dict(os.environ, env):
            loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
        return float(loss), float(optax.global_norm(grads))

    loss, norm = run({})
    want_loss, want_norm = run({"PS_TPU_DISABLE_PALLAS": "1"})
    print(f"[{leg}] {what} in the loss: loss {loss:.6f} | {want_loss:.6f} by the jnp twins, "
          f"gradient norm {norm:.6f} | {want_norm:.6f}", flush=True)
    if abs(loss - want_loss) > 5e-3 * abs(want_loss) or abs(norm - want_norm) > 5e-2 * want_norm:
        raise AssertionError(f"{leg}: the step by the kernels is not the step by their jnp twins")


def check_rope(leg, cfg, params, tokens):
    """`ps_rope` inside the family's loss beside the plain rotation (and
    every other entry's jnp twin), as `check_conv` holds the conv's."""
    check_conv(leg, cfg, params, tokens, what="the rotation's kernel")


# ------------------------------------------------------------------ legs


def leg_kernels(devices):
    """Each Pallas entry, once, against its jnp reference — and the text of
    the program that produced the kernel side must hold its Mosaic call."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ps_pytorch_tpu.ops import quantize as qz
    from ps_pytorch_tpu.ops.flash_attention import flash_attention
    from ps_pytorch_tpu.parallel.ring_attention import (
        full_attention,
        make_ring_attention,
        make_seq_mesh,
    )
    from tools.tpu_validate import (
        BF16_BOUND,
        F32_DEFAULT_PRECISION_BOUND,
    )

    leg = "kernels"

    def run(fn, *args, expect):
        compiled = jax.jit(fn).lower(*args).compile()
        check_kernels(leg, compiled.as_text(), expect)
        return jax.device_get(compiled(*args))

    def rel_err(got, want):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        return float(np.max(np.abs(got - want)) / max(1.0, np.max(np.abs(want))))

    def oracle_loss(q, k, v):
        with jax.default_matmul_precision("highest"):
            o = full_attention(q, k, v, causal=True)
            return jnp.sum(o.astype(jnp.float32) ** 2), o

    def flash_loss(q, k, v):
        o = flash_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    grad = lambda f: jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    def compare(label, loss_fn, q, k, v, bound):
        """Output and all three gradients of loss_fn (Mosaic-compiled)
        against the HIGHEST-precision oracle's."""
        (_, o), g = run(grad(loss_fn), q, k, v, expect=LM_KERNELS)
        (_, o_ref), g_ref = jax.device_get(
            jax.jit(grad(oracle_loss))(q, k, v)
        )
        errs = {"o": rel_err(o, o_ref)}
        errs.update((n, rel_err(a, r))
                    for n, a, r in zip(("dq", "dk", "dv"), g, g_ref))
        print(f"[{leg}] {label}: {errs} (bound {bound})", flush=True)
        bad = {n: e for n, e in errs.items() if not e < bound}
        if bad:
            raise AssertionError(f"{leg}: {label} parity broken: {bad}")

    b, t, h, d = FLASH_SHAPE
    rng = np.random.RandomState(0)
    qkv = lambda *shape, dtype: tuple(
        jnp.asarray(rng.randn(*shape) * 0.5, dtype) for _ in range(3)
    )
    for dtype, bound in ((jnp.float32, F32_DEFAULT_PRECISION_BOUND),
                         (jnp.bfloat16, BF16_BOUND)):
        # plan_flash's tiles: T = 1024 x D = 64 is the LM cell's shape
        # (512-wide, the tile above the diagonal skipped); the second T
        # is the pad-and-mask path
        for t_len in (t, t - 24):
            compare(f"flash {jnp.dtype(dtype).name} T={t_len}", flash_loss,
                    *qkv(b, t_len, h, d, dtype=dtype), bound)

    # the ring-hop partials, over every chip (one chip: a ring of one)
    ring = make_ring_attention(
        make_seq_mesh(len(devices)), causal=True, impl="flash"
    )

    def ring_loss(q, k, v):
        o = ring(q, k, v)
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    # float32, then bfloat16: what a sequence axis of two or more chips
    # runs (the partials with float32 results and offsets read at run
    # time); over one chip dp_sp takes flash_attention proper, above
    for dtype, bound in ((jnp.float32, F32_DEFAULT_PRECISION_BOUND),
                         (jnp.bfloat16, BF16_BOUND)):
        compare(f"ring-flash {jnp.dtype(dtype).name} over {len(devices)} "
                f"device(s)", ring_loss, *qkv(2, t, 4, d, dtype=dtype), bound)

    # quantizers on one 4 MiB bucket: the kernel's integers against the
    # jnp twin's, and the round trip against the scale
    x = jnp.asarray(rng.randn(BUCKET_ELEMS).astype(np.float32))
    for name, bs, kernel in (("per_tensor", 0, "ps_quantize_2d"),
                             ("rows_128", 128, "ps_quantize_rows"),
                             ("rows_4096", 4096, "ps_quantize_rows")):
        quant = lambda a, bs=bs: qz.quantize_int8(a, block_size=bs)
        qk, sk = run(quant, x, expect=(kernel,))
        with jnp_twins():
            qr, sr = jax.device_get(jax.jit(lambda a: quant(a))(x))
        diff = np.abs(qk.astype(np.int32) - qr.astype(np.int32))
        back = np.asarray(qz.dequantize_int8(
            qk, sk, block_size=bs, shape=x.shape if bs else None))
        err = float(np.max(np.abs(back - np.asarray(x))))
        lim = float(np.max(sk)) * 1.01 + 1e-7
        print(f"[{leg}] quantize {name}: {int(np.count_nonzero(diff))} of "
              f"{diff.size} ints differ from the jnp twin (max "
              f"{int(diff.max())}), round trip {err:.3e} <= {lim:.3e}",
              flush=True)
        # a tie x.5 may round the other way under another fusion of the
        # scale multiply; anything more is a kernel bug
        if diff.max() > 1 or np.count_nonzero(diff) > diff.size * 1e-4:
            raise AssertionError(f"{leg}: quantize {name} != jnp twin")
        if not np.array_equal(sk, sr) or err > lim:
            raise AssertionError(f"{leg}: quantize {name} scale/round trip")

    n = max(len(devices), 4)
    recv = jnp.asarray(
        rng.randint(-127, 128, (n, BUCKET_ELEMS // 4)).astype(np.int8))
    accum = lambda r: qz.accumulate_rescale_int8(r, float(n))
    got = run(accum, recv, expect=("ps_accum_rescale",))
    with jnp_twins():
        want = jax.device_get(jax.jit(lambda r: accum(r))(recv))
    if not np.array_equal(got, want):
        raise AssertionError(
            f"{leg}: accumulate_rescale_int8 n={n} is not bit-identical "
            f"to its jnp twin ({int(np.count_nonzero(got != want))} differ)"
        )
    print(f"[{leg}] accum_rescale n={n}: bit-identical to the jnp twin",
          flush=True)
    return {}


def leg_ps(wire, workdir, devices, clog):
    """cli.train on one wire; the int8 wire is then read back by
    cli.evaluate --once."""
    import jax

    from ps_pytorch_tpu import checkpoint as ckpt
    from ps_pytorch_tpu.cli import _flags
    from ps_pytorch_tpu.cli import evaluate as evaluate_cli
    from ps_pytorch_tpu.cli import train as train_cli
    from ps_pytorch_tpu.parallel import batch_sharding
    from ps_pytorch_tpu.trainer import Trainer

    leg = f"ps_{wire}"
    n = len(devices)
    train_dir = os.path.join(workdir, leg)
    argv = PS_ARGS + PS_WIRES[wire] + ["--num-workers", str(n)]
    steps = int(argv[argv.index("--max-steps") + 1])

    out = train_cli.main(argv + ["--train-dir", train_dir])
    # before the library pass below compiles the same step a second time
    programs = clog.check_steps(leg, [step_program("step")])
    check_finite(leg, "train loss", out["train"]["loss"])
    check_finite(leg, "val loss", out["val"]["loss"])
    if ckpt.latest_valid_step(train_dir) != steps:
        raise AssertionError(
            f"{leg}: wanted a valid checkpoint at step {steps}, found "
            f"{ckpt.available_steps(train_dir)}"
        )
    result = {"step_programs": programs}

    if wire == "int8":
        ev = evaluate_cli.main([
            "--network", "ResNet18", "--dataset", "Cifar10",
            "--model-dir", train_dir, "--once",
        ])
        if list(ev) != [steps]:
            raise AssertionError(f"{leg}: evaluator read steps {list(ev)}")
        # the checkpoint read back on one device scores the same 1000
        # test images as the trainer's own validation on the mesh
        a, b = ev[steps]["loss"], out["val"]["loss"]
        check_finite(leg, "evaluator loss", a)
        if abs(a - b) > 1e-3 * max(1.0, abs(b)):
            raise AssertionError(
                f"{leg}: evaluator loss {a} != trainer validation {b}"
            )
        print(f"[{leg}] checkpoint read back: evaluator loss {a:.6f} == "
              f"trainer validation {b:.6f}", flush=True)

    # the same step once more through the library, to look inside it
    parser = argparse.ArgumentParser()
    _flags.add_train_flags(parser)
    _flags.add_ps_flags(parser)
    args = parser.parse_args(argv + ["--no-checkpoints"])
    pcfg = _flags.ps_config_from(args, n)
    trainer = Trainer(_flags.train_config_from(args), pcfg)
    global_batch = args.batch_size * n
    batch = jax.device_put(
        {"image": trainer.dataset.train_images[:global_batch],
         "label": trainer.dataset.train_labels[:global_batch]},
        batch_sharding(trainer.mesh, pcfg),
    )
    step = trainer._train_step.lower(
        trainer.state, batch, trainer._key
    ).compile()
    check_kernels(leg, step.as_text(), PS_KERNELS[wire])
    check_scopes(leg, step.as_text(), remat=False)
    state, metrics = step(trainer.state, batch, trainer._key)
    check_finite(leg, "library step loss", jax.device_get(metrics["loss"]))
    check_on_all_devices(leg, "params", state.params, devices)
    check_on_all_devices(leg, "opt_state", state.opt_state, devices)
    check_on_all_devices(leg, "batch", batch, devices)
    check_memory_in_use(leg, devices)
    return result


def leg_lm(train_dir, devices, clog):
    import jax
    import jax.numpy as jnp

    from ps_pytorch_tpu import checkpoint as ckpt
    from ps_pytorch_tpu.cli import train_lm as train_lm_cli
    from ps_pytorch_tpu.models.transformer import TransformerConfig
    from ps_pytorch_tpu.optim import build_optimizer
    from ps_pytorch_tpu.parallel.dp_sp import (
        init_lm_state,
        make_lm_train_step,
        make_mesh_2d,
        shard_tokens_2d,
    )

    leg = "lm"
    out = train_lm_cli.main(LM_ARGS + ["--train-dir", train_dir])
    check_finite(leg, "loss", out["loss"])
    steps = ckpt.available_steps(train_dir)
    if steps != [3, 6]:
        raise AssertionError(f"{leg}: wanted checkpoints [3, 6], got {steps}")
    programs = clog.check_steps(leg, [step_program("worker_fn")])

    # the same step once more through the library (cli.train_lm's dp_sp
    # branch, by the functions it calls), to look inside it
    opt = dict(zip(LM_ARGS[::2], LM_ARGS[1::2]))
    cfg = TransformerConfig(
        dim=int(opt["--dim"]), depth=int(opt["--depth"]),
        heads=int(opt["--heads"]), max_seq_len=int(opt["--seq-len"]),
        attention_impl=opt["--attention-impl"],
        compute_dtype=jnp.bfloat16,
    )
    tx = build_optimizer("sgd", 0.1, momentum=0.9, weight_decay=0.0)
    mesh = make_mesh_2d(1, len(devices))
    params, opt_state = init_lm_state(cfg, tx, jax.random.key(1), mesh)
    tokens = shard_tokens_2d(
        jnp.asarray(train_lm_cli.make_synthetic_tokens(
            cfg.vocab_size, int(opt["--batch-size"]), cfg.max_seq_len, seed=2
        )),
        mesh,
    )
    step = make_lm_train_step(cfg, tx, mesh).lower(
        params, opt_state, tokens
    ).compile()
    check_kernels(leg, step.as_text(), LM_KERNELS)
    check_scopes(leg, step.as_text(), remat=False)
    params, opt_state, loss = step(params, opt_state, tokens)
    check_finite(leg, "library step loss", jax.device_get(loss))
    check_on_all_devices(leg, "params", params, devices)
    check_on_all_devices(leg, "opt_state", opt_state, devices)
    check_on_all_devices(leg, "tokens", tokens, devices)
    check_memory_in_use(leg, devices)
    return {"step_programs": programs}


def family_leg(leg, config, workdir, devices, clog, kernels, over_sequence=False,
               batch=None, seq=None):
    """What every family's leg opens with. `cli.train_lm --lm-config` on
    `config` (a published dict, written under `workdir`, or a file's path)
    + LM_CONFIG_ARGS, the chips a data axis (`over_sequence`: a sequence
    axis), ends on a finite loss with one step program compiled; the same
    step through the library holds `kernels` as Mosaic calls and the
    scopes. -> (step programs, cfg, the compiled step, its arguments)."""
    from ps_pytorch_tpu.cli import train_lm as train_lm_cli

    path = config
    if isinstance(config, dict):
        path = os.path.join(workdir, f"{leg}_small.json")
        with open(path, "w") as f:
            json.dump(config, f)
    n = len(devices)
    num_dp, num_sp = (1, n) if over_sequence else (n, 1)
    argv = ["--lm-config", path, "--num-dp", str(num_dp), "--num-sp", str(num_sp)] + LM_CONFIG_ARGS
    if batch is not None:       # a flag given again overrides LM_CONFIG_ARGS'
        argv += ["--batch-size", str(batch)]
    if seq is not None:
        argv += ["--seq-len", str(seq)]
    out = train_lm_cli.main(argv)
    check_finite(leg, "loss", out["loss"])
    programs = clog.check_steps(leg, [step_program("worker_fn")])
    batch = batch or int(LM_CONFIG_ARGS[LM_CONFIG_ARGS.index("--batch-size") + 1])
    cfg, step, state = library_lm_step(path, num_dp, num_sp, batch, seq)
    text = step.as_text()
    check_kernels(leg, text, kernels)
    check_scopes(leg, text, remat="--remat" in LM_CONFIG_ARGS)
    return programs, cfg, step, state


def leg_lm_config(workdir, devices, clog):
    """The second LM family through `cli.train_lm --lm-config`: the flash
    kernels at a 192-wide query beside a 128-wide value and the dropless
    experts' grouped products must be Mosaic calls in the compiled step,
    and the routing counters must account for every token."""
    import jax

    leg = "lm_config"
    programs, cfg, step, (params, opt_state, tokens) = family_leg(
        leg, LM_CONFIG, workdir, devices, clog, LM_CONFIG_KERNELS, over_sequence=True)
    params, opt_state, loss, counters = step(params, opt_state, tokens)
    check_finite(leg, "library step loss", jax.device_get(loss))
    c = {k: v.tolist() for k, v in jax.device_get(counters).items()}
    held = cfg.experts_held / cfg.n_routed_experts * cfg.num_experts_per_tok
    n = tokens.size
    if not (0.5 * held * n < c["moe_rows_here"] < min(2.0 * held, cfg.num_experts_per_tok) * n
            and 0 <= c["moe_tokens_unserved"] < n
            and c["moe_min_expert_rows"] <= c["moe_max_expert_rows"]):
        raise AssertionError(f"{leg}: routing counters out of range: {c}")
    print(f"[{leg}] routing: {c}", flush=True)
    check_passes(leg, cfg, params, tokens)
    check_on_all_devices(leg, "params", params, devices)
    check_memory_in_use(leg, devices)
    return {"step_programs": programs}


def leg_lm_ssm(workdir, devices, clog):
    """The third LM family through `cli.train_lm --lm-config` (data parallel
    over the chips: its recurrent state crosses no sequence shard), then the
    chunked scan by itself, bfloat16 products on the chip, against the
    float32 recurrence token by token."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ps_pytorch_tpu.ops import ssd

    leg = "lm_ssm"
    programs, cfg, step, state = family_leg(
        leg, LM_SSM_CONFIG, workdir, devices, clog, LM_KERNELS + CONV_KERNELS,
        batch=2 * len(devices))  # two rows a chip
    check_conv(leg, cfg, state[0], state[2])
    del step, state

    k = jax.random.split(jax.random.key(3), 6)
    t, h, p, n = 1024, 8, 64, 128
    x = jax.random.normal(k[0], (1, t, h, p), jnp.bfloat16)
    bm, cm = (jax.random.normal(kk, (1, t, 1, n), jnp.bfloat16) for kk in k[1:3])
    dt = jnp.exp(jax.random.uniform(k[3], (1, t, h), minval=np.log(1e-3), maxval=np.log(1e-1)))
    a = -jax.random.uniform(k[4], (h,), minval=1.0, maxval=16.0)
    d = jnp.ones((h,))
    got, _ = jax.jit(ssd.ssd_chunked, static_argnums=6)(x, dt, a, bm, cm, d, 256)
    want = jax.jit(ssd.ssd_recurrence)(x, dt, a, bm, cm, d)
    gap = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    if not gap < 0.02:      # bfloat16 operands, float32 sums: under 2% of the range
        raise AssertionError(f"{leg}: chunked scan is {gap:.4f} of its range off the recurrence")
    print(f"[{leg}] chunked scan vs recurrence: {gap:.5f} of the range", flush=True)
    check_memory_in_use(leg, devices)
    return {"step_programs": programs}


def leg_lm_kda(workdir, devices, clog):
    """The fourth LM family through `cli.train_lm --lm-config` (data
    parallel over the chips: the delta rule's state crosses no sequence
    shard), then the chunked rule by itself, bfloat16 products on the chip,
    against the float32 recurrence token by token: at the source's decays
    and with every fourth channel losing 3 a token (192 a chunk of 64, where
    exp(G_i) * exp(-G_j) would overflow)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ps_pytorch_tpu.ops import kda
    from ps_pytorch_tpu.ops.pallas_mode import kernel_census

    leg = "lm_kda"
    programs, cfg, step, state = family_leg(
        leg, LM_KDA_CONFIG, workdir, devices, clog, LM_KDA_KERNELS,
        batch=2 * len(devices))  # two rows a chip
    # what the trainer's step holds: the chunk's own part as Mosaic kernels
    # (the system solved once a KDA layer, `remat` or not), no XLA twin
    census = kernel_census(step.as_text())
    check_passes(leg, cfg, state[0], state[2])
    check_conv(leg, cfg, state[0], state[2])
    del step, state
    if census["jnp"].get("ps_kda_within") or census["mosaic"]["ps_kda_inverse"] != len(cfg.kda_layers):
        raise AssertionError(
            f"{leg}: wanted ps_kda_inverse once a KDA layer ({len(cfg.kda_layers)}) and no "
            f"ps_kda_within_jnp in the compiled step; census {census}")

    k = jax.random.split(jax.random.key(4), 7)
    t, h, d = 1024, 4, 128
    q = kda.l2_normalize(jax.random.normal(k[0], (1, t, h, d)), d ** -0.5).astype(jnp.bfloat16)
    key = kda.l2_normalize(jax.random.normal(k[1], (1, t, h, d))).astype(jnp.bfloat16)
    v = jax.random.normal(k[2], (1, t, h, d), jnp.bfloat16)
    beta = jax.nn.sigmoid(jax.random.normal(k[3], (1, t, h)))
    a = jax.random.uniform(k[4], (h, 1), minval=1.0, maxval=16.0)
    dt = jnp.exp(jax.random.uniform(k[5], (1, t, h, d), minval=np.log(1e-3), maxval=np.log(1e-1)))
    harsh = jnp.where(jnp.arange(d) % 4 == 0, -3.0, -0.01) * jnp.ones((1, t, h, d))
    if kda.scan_path(64, d, d) != "pallas_within+xla_scan":
        raise AssertionError(f"{leg}: the chunked rule takes {kda.scan_path(64, d, d)} on the chip")
    for name, g in (("source decays", -a * dt), ("past -88 a chunk", harsh)):
        got, _ = jax.jit(kda.kda_chunked, static_argnums=5)(q, key, v, g, beta, 64)
        want = jax.jit(kda.kda_recurrence)(q, key, v, g, beta)
        gap = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
        if not gap < 0.03:      # bfloat16 operands, float32 sums: under 3% of the range
            raise AssertionError(
                f"{leg}: chunked delta rule ({name}) is {gap:.4f} of its range off the recurrence")
        print(f"[{leg}] chunked delta rule vs recurrence, {name}: {gap:.5f} of the range",
              flush=True)
    check_memory_in_use(leg, devices)
    return {"step_programs": programs}


def leg_lm_eva(devices, clog):
    """The fifth LM family through `cli.train_lm --lm-config`, on the
    benchmark's own file (published widths, four layers: 821M parameters,
    9.2 GiB of float32 state a chip) at a row of two windows, data parallel
    over the chips (a chunk's summary crosses no sequence shard): both
    passes of the flash kernels must be Mosaic calls in the compiled step,
    run once a layer under `remat`, and the counters must show the
    summaries live. Then the op by itself against its jnp twin."""
    import jax
    import jax.numpy as jnp

    from ps_pytorch_tpu.ops import eva
    from ps_pytorch_tpu.ops.pallas_mode import kernel_census

    leg = "lm_eva"
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmark", "configs", "evabyte_6b5_4layers.json")
    seq = 4096
    programs, cfg, step, (params, opt_state, tokens) = family_leg(
        leg, path, None, devices, clog, LM_KERNELS, batch=len(devices), seq=seq)  # one row a chip
    census = kernel_census(step.as_text())["mosaic"]
    passes = 2 * cfg.num_hidden_layers      # over the windows, over the summaries
    if census != {"ps_flash_fwd": passes, "ps_flash_dqkv": passes}:
        raise AssertionError(
            f"{leg}: wanted ps_flash_fwd and ps_flash_dqkv {passes} times each (two passes a "
            f"layer, the forward not run again under remat); census {census}")
    params, opt_state, loss, counters = step(params, opt_state, tokens)
    check_finite(leg, "library step loss", jax.device_get(loss))
    c = {k: v.tolist() for k, v in jax.device_get(counters).items()}
    if not 0.0 < c["eva_remote_mass"] < 1.0:
        raise AssertionError(f"{leg}: no softmax mass on the summaries, or all of it: {c}")
    print(f"[{leg}] counters: {c}", flush=True)
    del step, params, opt_state

    k = jax.random.split(jax.random.key(5), 5)
    q, key, v = (jax.random.normal(kk, (1, seq, 4, 128), jnp.bfloat16) for kk in k[:3])
    phi, mu = (jax.random.normal(kk, (4, 128), jnp.float32) for kk in k[3:])
    attend = lambda impl: jax.jit(lambda *a: eva.eva_attention(
        *a, cfg.window_size, cfg.chunk_size, impl=impl)[0])(q, key, v, phi, mu)
    got, want = attend("flash"), attend("naive")
    gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))
                / jnp.max(jnp.abs(want.astype(jnp.float32))))
    if not gap < 0.02:      # bfloat16 operands and output, float32 sums: under 2% of the range
        raise AssertionError(f"{leg}: the kernels are {gap:.4f} of the range off the jnp twin")
    print(f"[{leg}] eva_attention, kernels vs jnp twin: {gap:.5f} of the range", flush=True)
    check_memory_in_use(leg, devices)
    return {"step_programs": programs}


def leg_lm_swa(workdir, devices, clog):
    """The sixth LM family through `cli.train_lm --lm-config` (data parallel
    over the chips: the ring does not know the window): the flash kernels
    under both masks and the experts' grouped products must be Mosaic calls
    in the compiled step, once a layer under `remat`, with the new scopes in
    its census; `ps_rope` rotates q and k in both layer kinds; the gate reads
    about a half. Then flash_attention under the window by itself, compiled,
    against its jnp twin."""
    import jax
    import jax.numpy as jnp

    from ps_pytorch_tpu.obs.hlo import census as read
    from ps_pytorch_tpu.ops.flash_attention import SlidingWindow, flash_attention
    from ps_pytorch_tpu.ops.pallas_mode import kernel_census
    from ps_pytorch_tpu.parallel.ring_attention import full_attention

    leg = "lm_swa"
    programs, cfg, step, (params, opt_state, tokens) = family_leg(
        leg, LM_SWA_CONFIG, workdir, devices, clog, LM_GQA_KERNELS,
        batch=len(devices), seq=2048)  # one row a chip, four windows long
    text = step.as_text()
    census = kernel_census(text)["mosaic"]
    layers = cfg.num_hidden_layers
    if (census["ps_flash_fwd"], census["ps_flash_dqkv"]) != (layers, layers):
        raise AssertionError(
            f"{leg}: wanted ps_flash_fwd and ps_flash_dqkv once a layer ({layers}), the "
            f"forward not run again under remat; census {census}")
    scopes = {row["scope"] for row in read(text)["by_place"]}
    want = {f"mixer/{m}{part}" for m in ("swa", "attention")
            for part in ("", "/rope", "/gate", "/kv_repeat", "/flash")}
    if not want <= scopes:
        raise AssertionError(f"{leg}: the step's census lacks {sorted(want - scopes)}")
    params, opt_state, loss, counters = step(params, opt_state, tokens)
    check_finite(leg, "library step loss", jax.device_get(loss))
    c = {k: v.tolist() for k, v in jax.device_get(counters).items()}
    if not (0.4 < c["attn_gate_open"] < 0.6 and c["moe_rows_here"] > 0):
        raise AssertionError(f"{leg}: counters out of range: {c}")
    print(f"[{leg}] counters: {c}", flush=True)
    check_passes(leg, cfg, params, tokens)
    check_rope(leg, cfg, params, tokens)
    del step, params, opt_state

    k = jax.random.split(jax.random.key(6), 3)
    q, key, v = (jax.random.normal(kk, (1, 2048, 4, 128), jnp.bfloat16) for kk in k)
    mask = SlidingWindow(cfg.sliding_window)
    got = jax.jit(lambda *a: flash_attention(*a, causal=mask))(q, key, v)
    with jnp_twins():
        want_o = jax.jit(lambda *a: full_attention(*a, causal=mask))(
            *(x.astype(jnp.float32) for x in (q, key, v)))
    gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want_o)) / jnp.max(jnp.abs(want_o)))
    if not gap < 0.02:      # bfloat16 operands and output, float32 sums: under 2% of the range
        raise AssertionError(f"{leg}: the kernels are {gap:.4f} of the range off the jnp twin")
    print(f"[{leg}] flash_attention under a window of {cfg.sliding_window}, kernels vs jnp "
          f"twin: {gap:.5f} of the range", flush=True)
    check_memory_in_use(leg, devices)
    return {"step_programs": programs}


def leg_lm_pre(workdir, devices, clog):
    """The seventh LM family through `cli.train_lm --lm-config` (data
    parallel over the chips: the ring does not know the window): the flash
    kernels under both masks and the experts' grouped products must be Mosaic
    calls in the compiled step, once a layer under `remat`; its census holds
    the router's scope and the sliding layers' rotation, and no rotation in
    the global layer, no gate and no dense MLP anywhere; the family lists a
    `flash_plan` a layer kind (the sliding kind's says `ps_rope` rotates its
    q and k) and its `moe_plan`; the ReLU gate is open for about half its
    entries; the loss by the kernels is the loss by their jnp twins."""
    import jax

    from ps_pytorch_tpu.models.lm import lm_family
    from ps_pytorch_tpu.obs.hlo import census as read
    from ps_pytorch_tpu.ops.pallas_mode import kernel_census

    leg = "lm_pre"
    programs, cfg, step, (params, opt_state, tokens) = family_leg(
        leg, LM_PRE_CONFIG, workdir, devices, clog, LM_GQA_KERNELS,
        batch=len(devices), seq=2048)  # one row a chip, four windows long
    text = step.as_text()
    census = kernel_census(text)["mosaic"]
    layers = cfg.num_hidden_layers
    if (census["ps_flash_fwd"], census["ps_flash_dqkv"]) != (layers, layers):
        raise AssertionError(
            f"{leg}: wanted ps_flash_fwd and ps_flash_dqkv once a layer ({layers}), the "
            f"forward not run again under remat; census {census}")
    scopes = {row["scope"] for row in read(text)["by_place"]}
    want = {"mixer/swa", "mixer/swa/rope", "mixer/swa/kv_repeat", "mixer/swa/flash",
            "mixer/attention", "mixer/attention/kv_repeat", "mixer/attention/flash",
            "ffn/moe/route", "ffn/moe/dispatch", "ffn/moe/experts", "ffn/moe/combine"}
    never = {"mixer/attention/rope", "mixer/swa/gate", "mixer/attention/gate", "ffn/mlp"}
    if not want <= scopes or never & scopes:
        raise AssertionError(f"{leg}: the step's census lacks {sorted(want - scopes)} or "
                             f"holds {sorted(never & scopes)}")
    plans = lm_family(cfg).plans(cfg, 2048, 1)
    said = [(name, fields.get("rotary"), fields.get("mask"), fields.get("rope_path"))
            for name, _, fields in plans]
    moe_plan = plans[-1][2]
    if (said != [("flash_plan", "default", "sliding_window", "pallas"),
                 ("flash_plan", "none", "causal", "none"), ("moe_plan", None, None, None)]
            or (moe_plan["scores"], moe_plan["router_input"], moe_plan["activation"],
                moe_plan["shared_expert"]) != ("softmax_topk", "attention_norm", "relu", False)):
        raise AssertionError(f"{leg}: the family's plans are {plans}")
    params, opt_state, loss, counters = step(params, opt_state, tokens)
    check_finite(leg, "library step loss", jax.device_get(loss))
    c = {k: v.tolist() for k, v in jax.device_get(counters).items()}
    if not (0.4 < c["moe_gate_active"] < 0.6 and c["moe_rows_here"] > 0
            and set(c["moe_passes_per_layer"]) == {1}):
        raise AssertionError(f"{leg}: counters out of range: {c}")
    print(f"[{leg}] counters: {c}", flush=True)
    check_on_all_devices(leg, "params", params, devices)
    check_rope(leg, cfg, params, tokens)
    check_memory_in_use(leg, devices)
    return {"step_programs": programs}


def leg_serve(lm_dir, devices, clog):
    from ps_pytorch_tpu.cli import serve as serve_cli

    leg = "serve"
    n = len(devices)
    argv = SERVE_ARGS + ["--model-dir", lm_dir]
    if n > 1:
        argv += ["--num-workers", str(n)]
    summary = serve_cli.main(argv)
    want = int(SERVE_ARGS[SERVE_ARGS.index("--requests") + 1])
    if summary["requests_completed"] != want:
        raise AssertionError(
            f"{leg}: {summary['requests_completed']} of {want} requests "
            f"completed"
        )
    if summary["weights_step"] != 6 or len(summary["rollovers"]) != 1:
        raise AssertionError(
            f"{leg}: wanted one rollover 3 -> 6, ended on step "
            f"{summary['weights_step']} after {summary['rollovers']} "
            f"(aborts {summary['rollover_aborts']})"
        )
    if summary["new_tokens"] <= 0:
        raise AssertionError(f"{leg}: no tokens came out")
    return {"step_programs": clog.check_steps(
        leg, ["jit(prefill)", "jit(step)"]
    )}


# ------------------------------------------------------------------ main


def describe_devices(devices):
    """The device as jax reports it. The result line holds exactly this
    under "device": three keys, two texts and a whole number."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def result_line(device):
    """The last line of stdout: these keys and no others (the summary line
    before it is where everything else goes)."""
    return json.dumps({"ok": True, "device": device})


def _version(dist):
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return None


def main() -> int:
    # in a directory that holds this file and nothing else of the repo,
    # this import is where the script dies (non-zero, no result line)
    from ps_pytorch_tpu.utils import enable_persistent_compile_cache

    cache_dir = enable_persistent_compile_cache()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, but jax found platform "
            f"{dev.platform!r} ({getattr(dev, 'device_kind', '?')}, "
            f"{len(devices)} device(s)); JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}. A CPU run proves "
            f"nothing about the chip — not running.",
            file=sys.stderr,
        )
        return 2
    device = describe_devices(devices)
    versions = {d: _version(d) for d in ("jax", "jaxlib", "libtpu", "flax",
                                         "optax")}
    print(f"[device] {device} versions={versions} "
          f"compile_cache={cache_dir}", flush=True)

    events = {"hits": 0, "misses": 0, "backend_compile_s": 0.0}

    def on_event(name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            events["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            events["misses"] += 1

    def on_duration(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            events["backend_compile_s"] += secs

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    legs = {}

    def run(name, leg):
        t0 = time.perf_counter()
        with CompileLog() as clog:
            detail = leg(clog)
        legs[name] = {
            "ok": True,
            "seconds": round(time.perf_counter() - t0, 1),
            "cache_hits": len(clog.hits),
            "cache_misses": len(clog.misses),
            **detail,
        }
        print(f"[{name}] ok: {legs[name]}", flush=True)

    t_start = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        lm_dir = os.path.join(workdir, "lm")
        run("kernels", lambda clog: leg_kernels(devices))
        wires = ["default", "int8"]
        if len(devices) > 1:
            wires.append("2round_homomorphic")
        for wire in wires:
            run(f"ps_{wire}",
                lambda clog, w=wire: leg_ps(w, workdir, devices, clog))
        run("lm", lambda clog: leg_lm(lm_dir, devices, clog))
        run("serve", lambda clog: leg_serve(lm_dir, devices, clog))
        run("lm_config", lambda clog: leg_lm_config(workdir, devices, clog))
        run("lm_ssm", lambda clog: leg_lm_ssm(workdir, devices, clog))
        run("lm_kda", lambda clog: leg_lm_kda(workdir, devices, clog))
        run("lm_eva", lambda clog: leg_lm_eva(devices, clog))
        run("lm_swa", lambda clog: leg_lm_swa(workdir, devices, clog))
        run("lm_pre", lambda clog: leg_lm_pre(workdir, devices, clog))

    print(json.dumps({
        "versions": versions,
        "legs": legs,
        "seconds": round(time.perf_counter() - t_start, 1),
        # time inside jax's backend-compile step, cache lookups included:
        # what a cold cache costs and a warm one saves
        "compile_seconds": round(events["backend_compile_s"], 1),
        "compile_cache": {"dir": cache_dir, "hits": events["hits"],
                          "misses": events["misses"]},
        "claim": None,
    }), flush=True)
    print(result_line(device), flush=True)  # nothing after it
    return 0


if __name__ == "__main__":
    sys.exit(main())
