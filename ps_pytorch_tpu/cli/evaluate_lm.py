"""Out-of-band LM evaluator — perplexity on a held-out split.

The LM counterpart of cli/evaluate.py (which covers the CNN families;
parity: /root/reference/src/distributed_evaluator.py polls checkpoints
every 10 s and reports metrics out-of-band). Consumes the scheme-agnostic
checkpoints train_lm writes — it never needs to know whether the producer
ran dp_sp, tp, pp, dp_tp, or moe: dense checkpoints replay through
apply_transformer, moe ones through apply_moe_transformer, single device.

The eval split regenerates the SAME Markov chain the trainer used (the
transition table is fixed by the recorded data seed) but walks fresh
sequences (sequence_seed offset), so reported perplexity is held-out.

  python -m ps_pytorch_tpu.cli.evaluate_lm --model-dir /tmp/lm --once
"""

from __future__ import annotations

import argparse
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import listify_raw, load_checkpoint_raw, poll_checkpoints
from ..ops.metrics import next_token_nll
from ..utils import enable_persistent_compile_cache, get_logger

logger = get_logger()

EVAL_SEQUENCE_SEED_OFFSET = 7919  # prime shift: held-out walks, same chain


# raw-dict list restoration lives at the checkpoint boundary now
# (checkpoint.listify_raw) — the serving engine consumes it too
_listify = listify_raw


def _fwd_dense(cfg, params, tokens):
    from ..models.transformer import apply_transformer

    return apply_transformer(cfg, params, tokens)


def _fwd_moe(cfg, moe, params, tokens):
    from ..parallel.moe import apply_moe_transformer

    return apply_moe_transformer(cfg, moe, params, tokens, None)[0]


@functools.lru_cache(maxsize=8)
def _cached_fwd(cfg, moe):
    """One compiled forward per (model config, moe config) — the polling
    loop evaluates many checkpoints of the same run and must not re-trace
    (a fresh jit per checkpoint recompiles every poll). Module-level defs
    partial-bound per config, not jit(lambda): the lru_cache already pins
    one compiled callable per config, and PSL002 can verify a named def
    where a lambda would need a baseline entry."""
    if moe is not None:
        return jax.jit(functools.partial(_fwd_moe, cfg, moe))
    return jax.jit(functools.partial(_fwd_dense, cfg))


def evaluate_checkpoint(model_dir: str, step: int, eval_size: int = 64,
                        batch_size: int = 16, generate_tokens: int = 0) -> dict:
    from ..models.transformer import TransformerConfig
    from .train_lm import make_synthetic_tokens

    raw = load_checkpoint_raw(model_dir, step)
    params = _listify(raw["params"])
    params = jax.tree.map(jnp.asarray, params)
    m = raw["model"]
    cfg = TransformerConfig(
        vocab_size=int(m["vocab_size"]),
        dim=int(m["dim"]),
        depth=int(m["depth"]),
        heads=int(m["heads"]),
        mlp_ratio=int(m["mlp_ratio"]),
        max_seq_len=int(m["max_seq_len"]),
    )
    seq_len = int(raw["data"]["seq_len"])
    toks = make_synthetic_tokens(
        cfg.vocab_size,
        eval_size,
        seq_len,
        seed=int(raw["data"]["seed"]),
        sequence_seed=int(raw["data"]["seed"]) + EVAL_SEQUENCE_SEED_OFFSET,
    )

    if m["kind"] == "moe":
        from ..parallel.moe import MoEConfig

        moe = MoEConfig(
            num_experts=int(m["num_experts"]),
            capacity_factor=float(m["capacity_factor"]),
            top_k=int(m.get("top_k", 1)),
        )
    else:
        moe = None
    fwd = _cached_fwd(cfg, moe)

    total, count = 0.0, 0
    for i in range(0, eval_size, batch_size):
        t = jnp.asarray(toks[i : i + batch_size])
        total += float(next_token_nll(fwd(params, t), t)) * t.shape[0]
        count += t.shape[0]
    nll = total / count
    out = {"step": step, "loss": nll, "perplexity": math.exp(nll)}

    if generate_tokens > 0:
        from ..models.decode import generate

        prompt = jnp.asarray(toks[:2, : min(8, seq_len // 2)])
        # clamp to the model's positional range (never crash the
        # long-running polling process over a sampling nicety)
        n_new = min(generate_tokens, cfg.max_seq_len - prompt.shape[1])
        if n_new < generate_tokens:
            logger.info(
                "generation: clamping %d -> %d tokens (max_seq_len %d)",
                generate_tokens, n_new, cfg.max_seq_len,
            )
        sample = generate(
            cfg, params, prompt, max_new_tokens=n_new,
            temperature=0.8, key=jax.random.key(step),
            max_len=prompt.shape[1] + n_new, moe=moe,
        )
        out["samples"] = np.asarray(sample).tolist()
        for row in out["samples"]:
            logger.info("sample: %s", " ".join(map(str, row)))
    return out


def main(argv=None) -> dict:
    enable_persistent_compile_cache()
    p = argparse.ArgumentParser("ps_pytorch_tpu.cli.evaluate_lm")
    p.add_argument("--model-dir", required=True)
    p.add_argument("--eval-size", type=int, default=64,
                   help="held-out sequences per evaluation")
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--once", action="store_true",
                   help="evaluate the latest checkpoint and exit")
    p.add_argument("--poll-interval", type=float, default=10.0)
    p.add_argument("--timeout", type=float, default=None,
                   help="stop after this long with no new checkpoint")
    p.add_argument("--generate", type=int, default=0,
                   help="also sample N tokens from 2 held-out prompts "
                        "(KV-cache decode; dense and MoE checkpoints)")
    args = p.parse_args(argv)

    results = {}
    if args.once:
        from ..checkpoint import latest_valid_step

        # newest VALID step: a corrupt/truncated latest file must not
        # kill the one-shot evaluation when an older good one exists
        step = latest_valid_step(args.model_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {args.model_dir}")
        steps = [step]
    else:
        steps = poll_checkpoints(
            args.model_dir, interval_s=args.poll_interval,
            timeout_s=args.timeout,
        )
    for step in steps:
        r = evaluate_checkpoint(
            args.model_dir, step, args.eval_size, args.batch_size,
            generate_tokens=args.generate,
        )
        results[step] = r
        logger.info(
            "LM Validation Step: %d, Loss: %.4f, Perplexity: %.3f",
            r["step"], r["loss"], r["perplexity"],
        )
    return results


if __name__ == "__main__":
    main()
