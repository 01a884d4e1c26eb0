"""The LM families and the one protocol the training path needs of them.

parallel/dp_sp.py (and cli/train_lm.py, the benchmark's driver) take a
family's init and apply from its CONFIG: `lm_family(cfg)`. A family is

    init(cfg, key) -> params
    apply(cfg, params, tokens, seq_axis_name=None, pos_offset=None)
        -> (logits, aux)      logits [B, T, vocab], or [B, T, heads, vocab]
                              where head p predicts the token at i + 1 + p;
                              aux: a dict of arrays (counts) the step sums
                              over the mesh ({} where nothing is counted)
    counters(aux) -> dict     None where aux is {}: what the step returns
                              beside the loss, from the summed aux
    saved_layers(cfg, batch, seq_len) -> [ops/flash_attention.SavedLayers]
                              what its blocks name for `remat`'s policy
                              (models/transformer.remat_block)

`load_lm_config` builds a config from a published config.json-shaped dict
by its `model_type` (`_PUBLISHED_FAMILIES`: deepseek_v3 -> models/mla_moe.py,
granitemoehybrid -> models/ssm_hybrid.py, kimi_linear -> models/kda_hybrid.py,
evabyte -> models/eva_dense.py);
TransformerConfig is built from sizes as before.
"""

from __future__ import annotations

import importlib
import json
from typing import Callable, Dict, NamedTuple, Optional, Union

from .transformer import TransformerConfig, apply_transformer, init_transformer, saved_layers


class LMFamily(NamedTuple):
    init: Callable
    apply: Callable
    counters: Optional[Callable]
    saved_layers: Callable


def _apply_dense(cfg, params, tokens, seq_axis_name=None, pos_offset=None):
    return apply_transformer(cfg, params, tokens, seq_axis_name, pos_offset), {}


def _mla_moe_family(cfg) -> LMFamily:
    from ..parallel.moe import routing_counters
    from .mla_moe import apply_mla_moe, init_mla_moe, saved_layers

    counters = (lambda aux: routing_counters(aux["counts"], aux["unserved"])) \
        if cfg.moe_layers else None
    return LMFamily(init_mla_moe, apply_mla_moe, counters, saved_layers)


def _ssm_hybrid_family(cfg) -> LMFamily:
    from .ssm_hybrid import apply_ssm_hybrid, init_ssm_hybrid, saved_layers, ssd_counters

    return LMFamily(init_ssm_hybrid, apply_ssm_hybrid,
                    ssd_counters if cfg.mamba_layers else None, saved_layers)


def _kda_hybrid_family(cfg) -> LMFamily:
    from .kda_hybrid import apply_kda_hybrid, init_kda_hybrid, kda_counters, saved_layers

    return LMFamily(init_kda_hybrid, apply_kda_hybrid,
                    kda_counters if cfg.moe_layers or cfg.kda_layers else None, saved_layers)


def _eva_dense_family(cfg) -> LMFamily:
    from .eva_dense import apply_eva_dense, eva_counters, init_eva_dense, saved_layers

    return LMFamily(init_eva_dense, apply_eva_dense, eva_counters, saved_layers)


class _Published(NamedTuple):
    module: str          # under models/
    config: str          # its config class
    family: Callable     # the LMFamily of such a config
    refuses: str         # what from_published turns down, for require_dense's message


# The families built from a published config.json, by its `model_type`. The
# ONE table: load_lm_config, lm_family and require_dense read it, and so do
# their messages.
_PUBLISHED_FAMILIES = {
    "deepseek_v3": _Published(
        "mla_moe", "MlaMoeConfig", _mla_moe_family,
        "query compression, rope scaling, grouped routing, a tied head"),
    "granitemoehybrid": _Published(
        "ssm_hybrid", "SsmHybridConfig", _ssm_hybrid_family,
        "routed experts, a positional term, a sequence axis of more than one member"),
    "kimi_linear": _Published(
        "kda_hybrid", "KdaHybridConfig", _kda_hybrid_family,
        "a router activation other than sigmoid, expert groups, query compression, rope "
        "scaling, a tied head, next-token-prediction layers, a sequence axis of more than "
        "one member"),
    "evabyte": _Published(
        "eva_dense", "EvaByteConfig", _eva_dense_family,
        "an attention_class other than eva, a fixed num_chunks, rope scaling, grouped "
        "key/value heads, a tied head, a chunk_size that does not divide window_size, a "
        "sequence axis of more than one member"),
}


def _config_class(model_type: str):
    entry = _PUBLISHED_FAMILIES[model_type]
    return getattr(importlib.import_module(f".{entry.module}", __package__), entry.config)


def lm_family(cfg) -> LMFamily:
    if isinstance(cfg, TransformerConfig):
        return LMFamily(init_transformer, _apply_dense, None, saved_layers)
    for model_type, entry in _PUBLISHED_FAMILIES.items():
        if isinstance(cfg, _config_class(model_type)):
            return entry.family(cfg)
    raise TypeError(
        f"no LM family for a {type(cfg).__name__} (has: TransformerConfig, "
        + ", ".join(entry.config for entry in _PUBLISHED_FAMILIES.values()) + ")")


def require_dense(cfg, where: str) -> None:
    """The schemes that restate the dense block's math (ROADMAP D6) take
    TransformerConfig only."""
    if not isinstance(cfg, TransformerConfig):
        raise NotImplementedError(
            f"{where} runs the dense TransformerConfig family only; a "
            f"{type(cfg).__name__} model trains through --parallelism dp_sp (ROADMAP D6), "
            "where each family refuses by name what it cannot express ("
            + "; ".join(f"{kind}: {entry.refuses}" for kind, entry in _PUBLISHED_FAMILIES.items())
            + ")")


def load_lm_config(published: Union[str, Dict], **run):
    """A family's config from a published config.json (a path or its
    dict) by `model_type`; `run` are the run options every family has
    (attention_impl, compute_dtype, remat, sp_attention, ...)."""
    if isinstance(published, str):
        with open(published) as f:
            published = json.load(f)
    kind = published.get("model_type")
    if kind not in _PUBLISHED_FAMILIES:
        raise ValueError(f"model_type {kind!r} has no family here "
                         f"(has: {', '.join(_PUBLISHED_FAMILIES)})")
    return _config_class(kind).from_published(published, **run)
