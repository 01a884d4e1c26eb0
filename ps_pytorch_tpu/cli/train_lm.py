"""LM training entry — every transformer parallelism axis as a product
surface, selected by --parallelism:

- dp_sp (default): 2-D (data x sequence) mesh, ring or Ulysses attention
  (--sp-attention), next-token targets fetched across shard boundaries
- tp: Megatron tensor parallelism (heads/MLP columns over a 'model' axis)
- pp: GPipe pipeline parallelism (--num-microbatches)
- moe: Switch-style mixture-of-experts over an 'expert' axis
  (--num-experts, --capacity-factor)

No reference counterpart (SURVEY.md section 5: long context and every
non-data parallelism axis are absent there).

Synthetic data is a fixed random Markov chain over the vocabulary (each
token has a handful of likely successors), so the LM has real structure to
learn and the loss has a meaningful floor — the long-context analogue of
data/datasets.make_synthetic.

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      python -m ps_pytorch_tpu.cli.train_lm --num-dp 2 --num-sp 4 \\
      --seq-len 256 --max-steps 20
  ... --parallelism tp --heads 8
  ... --parallelism pp --depth 8 --num-microbatches 4
  ... --parallelism moe --num-experts 8
  ... --parallelism ep_sp --num-shards 4 --num-sp 2 --num-experts 8
  ... --parallelism pp_moe --num-shards 4 --num-ep 2 --num-experts 8

A published architecture is named by its config.json, not by --dim/--depth/
--heads: `--lm-config <json>` (the published keys plus the chip's share,
e.g. `model_type: deepseek_v3` with `experts_held`; models/lm.load_lm_config
builds the family's config, the same call the benchmark's driver makes).
Such a model trains through --parallelism dp_sp. This file names no family:
what a run's kernels will look like is logged once and recorded as the
instants the family lists (models/lm.LMFamily.plans; `flash_plan` is the
one most share), and what the family counts is logged at log steps and
recorded as the instants it names for its groups of counters
(LMFamily.states; the expert layers' routing is `moe_route`). A new
published family costs its models/<family>.py (and an ops/ module where it
has a mechanism of its own), ONE row in models/lm.py, its rows in
obs/scopes.SCOPES, one leg in chip_smoke.py, its tests and its benchmark
data files; not this file, not obs/schema.py, not another family's tests.

  ... --lm-config benchmark/configs/kanana2_30b_a3b_ep8.json --num-dp 1 \
      --num-sp 1 --seq-len 8192 --batch-size 2 --dtype bfloat16 --remat \
      --attention-impl flash --optimizer adam --lr 3e-4
  ... --lm-config benchmark/configs/granite4_h_micro_1period.json --num-dp 1 \
      --num-sp 1 --seq-len 8192 --batch-size 1 --dtype bfloat16 --remat \
      --attention-impl flash --optimizer adam --lr 3e-4
  ... --lm-config benchmark/configs/kimi_linear_48b_a3b_ep32.json --num-dp 1 \
      --num-sp 1 --seq-len 8192 --batch-size 2 --dtype bfloat16 --remat \
      --attention-impl flash --optimizer adam --lr 3e-4
  ... --lm-config benchmark/configs/evabyte_6b5_4layers.json --num-dp 1 \
      --num-sp 1 --seq-len 16384 --batch-size 1 --dtype bfloat16 --remat \
      --attention-impl flash --optimizer adam --lr 3e-4
"""

from __future__ import annotations

import argparse
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..models.lm import lm_family, load_lm_config
from ..models.transformer import TransformerConfig, attention_path
from ..optim import build_optimizer
from ..parallel.dp_sp import (
    init_lm_state,
    make_lm_train_step,
    make_mesh_2d,
    shard_tokens_2d,
    update_plan,
)
from ..trainer import _shared_run_id, append_metrics_line
from ..utils import (
    enable_persistent_compile_cache,
    format_iter_line,
    get_logger,
    host_sync,
)

logger = get_logger()


def make_synthetic_tokens(
    vocab_size: int,
    n_sequences: int,
    seq_len: int,
    seed: int = 0,
    branching: int = 4,
    sequence_seed: Optional[int] = None,
) -> np.ndarray:
    """Sequences from a fixed sparse Markov chain: every token transitions
    uniformly to one of `branching` fixed successors -> cross-entropy floor
    of log(branching) nats that a working LM approaches.

    `seed` fixes the transition table; `sequence_seed` (default = seed)
    draws the walks — pass a different one for a held-out eval split over
    the SAME chain (what cli/evaluate_lm.py does)."""
    rng = np.random.RandomState(seed)
    successors = rng.randint(0, vocab_size, size=(vocab_size, branching))
    srng = rng if sequence_seed is None else np.random.RandomState(sequence_seed)
    toks = np.empty((n_sequences, seq_len), np.int32)
    toks[:, 0] = srng.randint(0, vocab_size, n_sequences)
    for t in range(1, seq_len):
        pick = srng.randint(0, branching, n_sequences)
        toks[:, t] = successors[toks[:, t - 1], pick]
    return toks


def main(argv=None) -> dict:
    enable_persistent_compile_cache()
    parser = argparse.ArgumentParser("ps_pytorch_tpu.cli.train_lm")
    parser.add_argument("--num-dp", type=int, default=1)
    parser.add_argument("--num-sp", type=int, default=0,
                        help="sequence shards (0 = all remaining devices)")
    parser.add_argument("--vocab-size", type=int, default=256)
    parser.add_argument("--dim", type=int, default=128)
    parser.add_argument("--depth", type=int, default=2)
    parser.add_argument("--heads", type=int, default=4)
    parser.add_argument("--lm-config", type=str, default=None, metavar="JSON",
                        help="a published config.json (model_type, widths, "
                             "and the chip's share: experts_held, the "
                             "vocabulary slice) in place of --vocab-size/"
                             "--dim/--depth/--heads; dp_sp only")
    parser.add_argument("--seq-len", type=int, default=512)
    parser.add_argument("--batch-size", type=int, default=8,
                        help="global sequences per step (divisible by num-dp)")
    parser.add_argument("--max-steps", type=int, default=100)
    parser.add_argument("--lr", type=float, default=0.1)
    parser.add_argument("--momentum", type=float, default=0.9)
    parser.add_argument("--optimizer", default="sgd",
                        choices=["sgd", "adam", "amsgrad"])
    parser.add_argument("--weight-decay", type=float, default=0.0)
    parser.add_argument("--lr-schedule", default="constant",
                        choices=["constant", "cosine"])
    parser.add_argument("--warmup-steps", type=int, default=0)
    parser.add_argument("--dtype", default="float32",
                        choices=["float32", "bfloat16"])
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--log-interval", type=int, default=10)
    parser.add_argument("--remat", action="store_true",
                        help="rematerialize each block in backward: dp_sp "
                             "keeps a block's input and the within-chip flash "
                             "kernels' o and lse (the `flash_plan` line says "
                             "how many bytes a layer) and runs the rest "
                             "again; the other schemes keep the input only")
    parser.add_argument("--bidirectional-ring", action="store_true")
    parser.add_argument("--parallelism", default="dp_sp",
                        choices=["dp_sp", "dp_tp", "tp", "pp", "moe",
                                 "ep_sp", "pp_moe"])
    parser.add_argument("--sp-attention", default="ring",
                        choices=["ring", "ulysses"])
    parser.add_argument("--attention-impl", default="naive",
                        choices=["naive", "flash"],
                        help="within-chip attention kernel (flash = Pallas)")
    parser.add_argument("--shard-vocab", action="store_true",
                        help="tp/dp_tp: vocab-parallel embedding + loss "
                             "(full logits never materialize per device)")
    parser.add_argument("--num-shards", type=int, default=0,
                        help="tp/pp/moe axis size (0 = all devices)")
    parser.add_argument("--num-microbatches", type=int, default=2,
                        help="pp only: microbatches per step")
    parser.add_argument("--num-experts", type=int, default=8,
                        help="moe only: total experts")
    parser.add_argument("--num-ep", type=int, default=0,
                        help="pp_moe: expert-axis size (0 = devices/stages)")
    parser.add_argument("--capacity-factor", type=float, default=1.25,
                        help="moe only: expert capacity factor")
    parser.add_argument("--top-k", type=int, default=1, choices=(1, 2),
                        help="moe only: 1 = Switch, 2 = GShard routing")
    parser.add_argument("--train-size", type=int, default=512,
                        help="synthetic corpus size (sequences)")
    parser.add_argument("--metrics-file", type=str, default=None)
    parser.add_argument("--profile-dir", type=str, default=None,
                        help="write a jax.profiler device trace for steps "
                             "3..12 (view with tensorboard/xprof) and, for "
                             "--parallelism dp_sp, step_scopes.json beside "
                             "it: `tools/trace_report.py device <dir>` "
                             "prints the device time by phase and scope")
    parser.add_argument("--trace", type=str, default=None, metavar="DIR",
                        help="write this process's host-phase span stream "
                             "(trace_train_lm_p<i>.jsonl) here: one `step` "
                             "span per iteration holding `fetch` (batch "
                             "gather), `dispatch` (the step call), and on "
                             "log steps `sync` (the host waiting for the "
                             "device), `log` and `metrics_write` — the "
                             "names cli.train records (obs/trace.py; "
                             "merge with tools/trace_report.py)")
    parser.add_argument("--train-dir", type=str, default=None,
                        help="checkpoint dir (scheme-agnostic plain layout; "
                             "consumed by cli.evaluate_lm)")
    parser.add_argument("--eval-freq", type=int, default=0,
                        help="checkpoint every N steps (0 = only at the end)")
    args = parser.parse_args(argv)
    from ..obs import (
        NULL_TRACER, Tracer, run_header, setup_line_once, setup_tracer)

    # the process's set-up record opens here; the first log step prints
    # where the time to the first step went. The run's own span stream
    # (--trace) comes first too, so that its first flush writes the set-up
    # of this run's life; its header's geometry is filled in once known.
    with setup_tracer().span("setup.devices"):
        n_dev = len(jax.devices())
        run_id = _shared_run_id()  # one id for the metrics and the spans, on every host
    tr = NULL_TRACER
    if args.trace:
        tr = Tracer(
            "train_lm",
            path=os.path.join(
                args.trace, f"trace_train_lm_p{jax.process_index()}.jsonl"
            ),
            run_id=run_id, pid=jax.process_index(), annotate=True,
            with_setup=True,
        )

    if args.shard_vocab and args.parallelism not in ("tp", "dp_tp"):
        raise ValueError(
            "--shard-vocab is implemented for --parallelism tp/dp_tp only "
            "(the other schemes keep the embedding replicated and would "
            "silently ignore it)"
        )
    run_opts = dict(
        remat=args.remat,
        bidirectional_ring=args.bidirectional_ring,
        sp_attention=args.sp_attention,
        attention_impl=args.attention_impl,
        # mixed precision: params/grads/moments stay f32 (bf16 Adam moments
        # are broken — bf16(0.999) == 1.0); block math runs in bf16
        compute_dtype=jnp.bfloat16 if args.dtype == "bfloat16" else None,
    )
    if args.lm_config:
        if args.parallelism != "dp_sp":
            raise ValueError(
                f"--lm-config trains through --parallelism dp_sp; "
                f"{args.parallelism} restates the dense block (ROADMAP D6)"
            )
        if args.train_dir:
            raise ValueError(
                "--train-dir: cli.evaluate_lm rebuilds the dense and the "
                "capacity-MoE families only; no checkpoint of an --lm-config model"
            )
        cfg = load_lm_config(args.lm_config, **run_opts)
        args.vocab_size = cfg.vocab_size  # the corpus draws from the slice held
        args.depth, args.dim, args.heads = (
            cfg.num_hidden_layers, cfg.hidden_size, cfg.num_attention_heads)
    else:
        cfg = TransformerConfig(
            vocab_size=args.vocab_size, dim=args.dim, depth=args.depth,
            heads=args.heads, max_seq_len=args.seq_len, **run_opts,
        )
    if args.lr_schedule == "cosine":
        lr = optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=args.lr,
            warmup_steps=args.warmup_steps,
            decay_steps=max(args.max_steps, args.warmup_steps + 1),
        )
    elif args.warmup_steps > 0:
        lr = optax.join_schedules(
            [
                optax.linear_schedule(0.0, args.lr, args.warmup_steps),
                optax.constant_schedule(args.lr),
            ],
            [args.warmup_steps],
        )
    else:
        lr = args.lr
    tx = build_optimizer(
        args.optimizer, lr, momentum=args.momentum,
        weight_decay=args.weight_decay,
    )
    n_shards = args.num_shards or n_dev
    key = jax.random.key(args.seed)

    counters_box = {}  # the newest step's counters, where the family counts
    # Each scheme yields (params, opt_state, run(params, opt, np_tokens) ->
    # (params, opt, loss)) over its own mesh; the training loop below is
    # scheme-agnostic.
    scoped_step = None  # the scheme's step where it keeps its own census
    if args.parallelism == "dp_sp":
        num_sp = args.num_sp or max(n_dev // args.num_dp, 1)
        mesh = make_mesh_2d(args.num_dp, num_sp)
        if args.seq_len % num_sp:
            raise ValueError(f"--seq-len must be divisible by num_sp={num_sp}")
        if args.batch_size % args.num_dp:
            raise ValueError(
                f"--batch-size must be divisible by num_dp={args.num_dp}"
            )
        params, opt_state = init_lm_state(cfg, tx, key, mesh)
        step = scoped_step = make_lm_train_step(cfg, tx, mesh)

        def run(p, o, tok):  # a family that counts returns a fourth value
            p, o, loss, *rest = step(p, o, shard_tokens_2d(jnp.asarray(tok), mesh))
            if rest:
                counters_box["last"] = rest[0]
            return p, o, loss

        to_plain = lambda p: p
        layout = f"dp {args.num_dp} x sp {num_sp} ({args.sp_attention})"
    elif args.parallelism == "tp":
        from ..parallel.tp import (
            from_tp_layout,
            init_tp_state,
            make_tp_mesh,
            make_tp_train_step,
        )

        mesh = make_tp_mesh(n_shards)
        params, opt_state = init_tp_state(
            cfg, tx, key, mesh, shard_vocab=args.shard_vocab
        )
        step = make_tp_train_step(cfg, tx, mesh, shard_vocab=args.shard_vocab)
        run = lambda p, o, tok: step(p, o, jnp.asarray(tok))
        to_plain = lambda p: from_tp_layout(cfg, p)
        layout = f"tp {n_shards}" + (" (vocab-parallel)" if args.shard_vocab else "")
    elif args.parallelism == "dp_tp":
        from ..parallel.dp_tp import (
            init_dp_tp_state,
            make_dp_tp_train_step,
            make_mesh_dp_tp,
            shard_tokens_dp,
        )
        from ..parallel.tp import from_tp_layout

        num_tp = args.num_shards or max(n_dev // args.num_dp, 1)
        if args.batch_size % args.num_dp:
            raise ValueError(
                f"--batch-size must be divisible by num_dp={args.num_dp}"
            )
        mesh = make_mesh_dp_tp(args.num_dp, num_tp)
        params, opt_state = init_dp_tp_state(
            cfg, tx, key, mesh, shard_vocab=args.shard_vocab
        )
        step = make_dp_tp_train_step(cfg, tx, mesh, shard_vocab=args.shard_vocab)
        run = lambda p, o, tok: step(p, o, shard_tokens_dp(jnp.asarray(tok), mesh))
        to_plain = lambda p: from_tp_layout(cfg, p)
        layout = f"dp {args.num_dp} x tp {num_tp}" + (
            " (vocab-parallel)" if args.shard_vocab else ""
        )
    elif args.parallelism == "pp":
        from ..parallel.pp import (
            from_pp_layout,
            init_pp_state,
            make_pp_mesh,
            make_pp_train_step,
        )

        if args.batch_size % args.num_microbatches:
            raise ValueError(
                f"--batch-size must be divisible by "
                f"num_microbatches={args.num_microbatches}"
            )
        mesh = make_pp_mesh(n_shards)
        params, opt_state = init_pp_state(cfg, tx, key, mesh)
        step = make_pp_train_step(
            cfg, tx, mesh, num_microbatches=args.num_microbatches
        )
        run = lambda p, o, tok: step(p, o, jnp.asarray(tok))
        to_plain = lambda p: from_pp_layout(cfg, p)
        layout = f"pp {n_shards} x {args.num_microbatches} microbatches"
    elif args.parallelism == "ep_sp":
        from ..parallel.ep_sp import (
            init_ep_sp_state,
            make_ep_sp_train_step,
            make_mesh_ep_sp,
            shard_tokens_ep_sp,
        )
        from ..parallel.moe import MoEConfig

        num_sp = args.num_sp or 2
        num_ep = args.num_shards or max(n_dev // num_sp, 1)
        if args.seq_len % num_sp:
            raise ValueError(f"--seq-len must be divisible by num_sp={num_sp}")
        if args.batch_size % num_ep:
            raise ValueError(
                f"--batch-size must be divisible by expert shards={num_ep}"
            )
        mesh = make_mesh_ep_sp(num_ep, num_sp)
        moe = MoEConfig(
            num_experts=args.num_experts,
            capacity_factor=args.capacity_factor,
            top_k=args.top_k,
        )
        params, opt_state = init_ep_sp_state(cfg, moe, tx, key, mesh)
        es_step = make_ep_sp_train_step(cfg, moe, tx, mesh)
        aux_box = {"aux": float("nan")}

        def run(p, o, tok):
            p, o, loss, aux = es_step(
                p, o, shard_tokens_ep_sp(jnp.asarray(tok), mesh)
            )
            aux_box["aux"] = aux
            return p, o, loss

        to_plain = lambda p: p
        layout = (
            f"ep {num_ep} ({args.num_experts} experts) x sp {num_sp} "
            f"({args.sp_attention})"
        )
    elif args.parallelism == "pp_moe":
        from ..parallel.moe import MoEConfig
        from ..parallel.pp_moe import (
            init_pp_moe_state,
            make_mesh_pp_moe,
            make_pp_moe_train_step,
            shard_tokens_pp_moe,
        )

        num_ep = args.num_ep or max(n_dev // n_shards, 1)
        per_col = args.batch_size // num_ep if num_ep else 0
        if args.batch_size % num_ep or per_col % args.num_microbatches:
            raise ValueError(
                f"--batch-size must split over ep={num_ep} then "
                f"num_microbatches={args.num_microbatches}"
            )
        mesh = make_mesh_pp_moe(n_shards, num_ep)
        moe = MoEConfig(
            num_experts=args.num_experts,
            capacity_factor=args.capacity_factor,
            top_k=args.top_k,
        )
        params, opt_state = init_pp_moe_state(cfg, moe, tx, key, mesh)
        pm_step = make_pp_moe_train_step(
            cfg, moe, tx, mesh, num_microbatches=args.num_microbatches
        )
        aux_box = {"aux": float("nan")}

        def run(p, o, tok):
            p, o, loss, aux = pm_step(
                p, o, shard_tokens_pp_moe(jnp.asarray(tok), mesh)
            )
            aux_box["aux"] = aux
            return p, o, loss

        from ..parallel.pp import from_pp_layout as _unstack

        to_plain = lambda p: _unstack(cfg, p)  # plain MoE layout for eval
        layout = (
            f"pp {n_shards} x ep {num_ep} ({args.num_experts} experts, "
            f"{args.num_microbatches} microbatches)"
        )
    else:  # moe
        from ..parallel.moe import (
            MoEConfig,
            init_moe_state,
            make_ep_mesh,
            make_moe_train_step,
            shard_moe_batch,
        )

        if args.batch_size % n_shards:
            raise ValueError(
                f"--batch-size must be divisible by expert shards={n_shards}"
            )
        mesh = make_ep_mesh(n_shards)
        moe = MoEConfig(
            num_experts=args.num_experts,
            capacity_factor=args.capacity_factor,
            top_k=args.top_k,
        )
        params, opt_state = init_moe_state(cfg, moe, tx, key, mesh)
        moe_step = make_moe_train_step(cfg, moe, tx, mesh)
        aux_box = {"aux": float("nan")}  # surfaced in the log/metrics below

        def run(p, o, tok):
            p, o, loss, aux = moe_step(p, o, shard_moe_batch(jnp.asarray(tok), mesh))
            aux_box["aux"] = aux
            return p, o, loss

        to_plain = lambda p: p  # MoE layout IS the model (evaluator branches)
        layout = f"moe {args.num_experts} experts over {n_shards} shards"

    corpus = make_synthetic_tokens(
        args.vocab_size, args.train_size, args.seq_len, seed=args.seed + 1
    )
    n_params = sum(int(np.prod(np.shape(x))) for x in jax.tree_util.tree_leaves(params))
    logger.info(
        "LM %dx d%d h%d (%d params), seq %d, %s",
        args.depth, args.dim, args.heads, n_params, args.seq_len, layout,
    )
    from ..obs.scopes import step_scopes_instant, write_step_scopes

    geometry = {
        "parallelism": args.parallelism,
        "dim": args.dim, "depth": args.depth,
        "heads": args.heads, "seq_len": args.seq_len,
        "params": n_params,
    }
    if args.lm_config:
        geometry["lm_config"] = os.path.basename(args.lm_config)
    append_metrics_line(
        args.metrics_file,
        run_header("train_lm", run_id=run_id, geometry=geometry),
    )
    if tr.enabled:
        tr.header["geometry"] = geometry

    # what `remat` keeps beside each block's input, by the function the
    # blocks' policy comes from (models/transformer.remat_plan), where the
    # families' own apply runs (the ring's hops name nothing)
    seq_shards = num_sp if args.parallelism in ("dp_sp", "ep_sp") else 1
    path = attention_path(cfg, seq_shards)
    kept = ()  # one {name: bytes a layer} a kind of layer
    family = lm_family(cfg)
    if cfg.remat and args.parallelism == "dp_sp" and path == "local":
        from ..models.transformer import remat_plan

        kinds = family.saved_layers(cfg, args.batch_size // args.num_dp, args.seq_len)
        saves = remat_plan(kinds, params)
        kept = saves.kept
        logger.info(
            "remat keeps %s: %.0f MiB as stored beside %.0f MiB of state, of %.0f MiB "
            "(the kernels' operands %s)", saves.names, saves.saved_bytes / 2 ** 20,
            saves.state_bytes / 2 ** 20, saves.bytes_limit / 2 ** 20,
            "kept" if saves.operands_kept else "left to recompute: no room")

    if args.parallelism == "dp_sp":
        # where the update reads a materialised gradient and where XLA may
        # fold it into the product that makes it: decided leaf by leaf as
        # the step is traced, by this function (parallel/dp_sp.plan_update)
        plan = update_plan(
            params, args.batch_size // args.num_dp * (args.seq_len // num_sp))
        logger.info(
            "update stands apart for %d of %d leaves, %.1f%% of the parameters "
            "(%d rows a step and chip)", plan["leaves_apart"], plan["leaves"],
            100.0 * plan["params_apart"] / plan["params"], plan["rows"])
        tr.instant("update_plan", **plan)

    # what every call of the family's kernels will look like, from the
    # shapes alone (models/lm.LMFamily.plans), each with `remat`'s share
    # under its kernels' names
    for name, kernels, plan in family.plans(cfg, args.seq_len, seq_shards):
        if kernels is not None:
            names = {n: b for i, kind in enumerate(kept) if plan.get("layer_kind", i) == i
                     for n, b in kind.items() if n.startswith(kernels)}
            plan = {**plan, "remat_saves": ",".join(names),
                    "saved_bytes_per_layer": sum(names.values())}
        logger.info("%s for T %d: %s", name, args.seq_len, plan)
        tr.instant(name, **plan)

    def save_lm_checkpoint(step_no):
        if args.train_dir is None:
            return
        from ..checkpoint import save_checkpoint

        # plain-layout params + enough metadata for a structure-free
        # evaluator (cli/evaluate_lm.py) to rebuild the model and the
        # held-out eval split of the same Markov chain
        save_checkpoint(
            {
                "params": jax.device_get(to_plain(params)),
                "step": step_no,
                "model": {
                    "kind": (
                        "moe"
                        if args.parallelism in ("moe", "ep_sp", "pp_moe")
                        else "dense"
                    ),
                    "vocab_size": cfg.vocab_size,
                    "dim": cfg.dim,
                    "depth": cfg.depth,
                    "heads": cfg.heads,
                    "mlp_ratio": cfg.mlp_ratio,
                    "max_seq_len": cfg.max_seq_len,
                    "num_experts": args.num_experts,
                    "capacity_factor": float(args.capacity_factor),
                    "top_k": args.top_k,
                },
                "data": {"seed": args.seed + 1, "seq_len": args.seq_len},
            },
            args.train_dir,
            step_no,
        )

    rng = np.random.RandomState(args.seed + 2)
    loss = float("nan")
    profiling = False
    profile_stop = min(12, args.max_steps)
    # steady-state window: everything after the first `warmup` steps
    # (compile + settle), bracketed by host_sync barriers so the derived
    # tokens/sec excludes JIT compile and setup (scaling_bench consumes it)
    warmup = min(2, args.max_steps - 1)
    steady_t0 = None
    steady = {}
    if args.profile_dir and args.max_steps < 3:
        logger.warning(
            "--profile-dir set but max-steps < 3: tracing starts at step 3 "
            "(after compile + settle), so no trace will be written"
        )
    flush_due = False  # a log step closed; flush after the next dispatch
    try:
        for step_no in range(1, args.max_steps + 1):
            with tr.span("step", step=step_no):
                if step_no == warmup + 1 and args.max_steps > warmup:
                    host_sync(params)
                    steady_t0 = time.perf_counter()
                if args.profile_dir and step_no == 3:  # after compile + settle
                    jax.profiler.start_trace(args.profile_dir)
                    profiling = True
                log_now = step_no % args.log_interval == 0 or step_no == 1
                if log_now:
                    # drain the async-dispatch backlog BEFORE starting the
                    # clock so dt measures ONE step, not the queue of
                    # unlogged steps (host-read barrier — block_until_ready
                    # can lie, utils/sync.py)
                    with tr.span("sync"):
                        host_sync(params)
                t0 = time.perf_counter()
                with tr.span("fetch"):
                    idx = rng.randint(0, len(corpus), args.batch_size)
                    batch = corpus[idx]
                with tr.span("dispatch"):
                    params, opt_state, loss = run(params, opt_state, batch)
                if flush_due:
                    # span I/O once the device is busy again, never
                    # between a log step's sync and the next dispatch
                    tr.flush()
                    flush_due = False
                if log_now:
                    with tr.span("sync"):
                        loss = float(loss)
                        host_sync(params)  # include the param update in dt
                    dt = time.perf_counter() - t0
                    with tr.span("log"):
                        logger.info(
                            format_iter_line(
                                rank="mesh", step=step_no, epoch=1,
                                seen=step_no * args.batch_size,
                                total=args.max_steps * args.batch_size,
                                loss=loss, time_cost=dt, forward=dt,
                            )
                        )
                    record = {"kind": "train_lm",
                              "parallelism": args.parallelism,
                              "step": step_no, "loss": loss,
                              "time_cost": round(dt, 6)}
                    if args.parallelism in ("moe", "ep_sp", "pp_moe"):
                        # router balance: aux == 1 is perfectly balanced; a
                        # climb toward num_experts signals expert collapse
                        record["aux_loss"] = round(float(aux_box["aux"]), 6)
                        logger.info(
                            "MoE load-balance aux: %.4f", record["aux_loss"]
                        )
                    if "last" in counters_box:
                        # what the family counted over this step's global
                        # batch (models/lm.LMFamily.counters)
                        c = {k: np.asarray(v).tolist() for k, v in
                             jax.device_get(counters_box["last"]).items()}
                        record.update({k: v for k, v in c.items()
                                       if not k.endswith("_per_layer")})
                        # and each group of them as the family's instant
                        for name, prefix in family.states:
                            state = {k[len(prefix):]: v for k, v in c.items()
                                     if k.startswith(prefix)}
                            if state:
                                logger.info("%s: %s", name, state)
                                tr.instant(name, **state)
                    if step_no == 1 and args.trace and scoped_step is not None:
                        # the census of the executable that just ran, once
                        # (obs/scopes.ScopedStep.scopes; the device is idle
                        # here: the log step's sync has drained it)
                        tr.instant("step_scopes", **step_scopes_instant(scoped_step))
                    with tr.span("metrics_write"):
                        append_metrics_line(args.metrics_file, record)
                    # set-up is over: the process's time to its first
                    # step, by phase, once (after --resume too)
                    line = setup_line_once()
                    if line:
                        logger.info(line)
                    flush_due = True
                if profiling and step_no >= profile_stop:
                    host_sync(params)  # trace must contain retired work
                    jax.profiler.stop_trace()
                    profiling = False
                    logger.info(
                        "profiler trace written to %s", args.profile_dir
                    )
                    # what `tools/trace_report.py device` joins it with
                    write_step_scopes(args.profile_dir, scoped_step)
                if args.eval_freq > 0 and step_no % args.eval_freq == 0:
                    save_lm_checkpoint(step_no)
    finally:
        tr.flush()  # the trailing steps' spans
    if steady_t0 is not None:
        host_sync(params)  # params chain: serializes the whole window
        steady = {
            "steady_steps": args.max_steps - warmup,
            "steady_elapsed_s": time.perf_counter() - steady_t0,
        }
    if args.train_dir is not None and (
        args.eval_freq <= 0 or args.max_steps % args.eval_freq
    ):
        save_lm_checkpoint(args.max_steps)
    return {"loss": float(loss), "params": n_params, **steady}


if __name__ == "__main__":
    main()
