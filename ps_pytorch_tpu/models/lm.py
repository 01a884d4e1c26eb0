"""The LM families and the one protocol the training path needs of them.

parallel/dp_sp.py (and cli/train_lm.py, the benchmark's driver) take a
family's init and apply from its CONFIG: `lm_family(cfg)`. A family is

    init(cfg, key) -> params
    apply(cfg, params, tokens, seq_axis_name=None, pos_offset=None)
        -> (logits, aux)      aux: a dict of integer arrays the step sums
                              over the mesh ({} where nothing is counted)
    counters(aux) -> dict     None where aux is {}: what the step returns
                              beside the loss, from the summed aux

`load_lm_config` builds a config from a published config.json-shaped dict
by its `model_type`; TransformerConfig is built from sizes as before.
"""

from __future__ import annotations

import json
from typing import Callable, Dict, NamedTuple, Optional, Union

from .transformer import TransformerConfig, apply_transformer, init_transformer


class LMFamily(NamedTuple):
    init: Callable
    apply: Callable
    counters: Optional[Callable]


def _apply_dense(cfg, params, tokens, seq_axis_name=None, pos_offset=None):
    return apply_transformer(cfg, params, tokens, seq_axis_name, pos_offset), {}


def lm_family(cfg) -> LMFamily:
    if isinstance(cfg, TransformerConfig):
        return LMFamily(init_transformer, _apply_dense, None)
    from .mla_moe import MlaMoeConfig, apply_mla_moe, init_mla_moe

    if isinstance(cfg, MlaMoeConfig):
        from ..parallel.moe import routing_counters

        counters = (lambda aux: routing_counters(aux["counts"], aux["unserved"])) \
            if cfg.moe_layers else None
        return LMFamily(init_mla_moe, apply_mla_moe, counters)
    raise TypeError(f"no LM family for a {type(cfg).__name__}")


def require_dense(cfg, where: str) -> None:
    """The schemes that restate the dense block's math (ROADMAP D6) take
    TransformerConfig only."""
    if not isinstance(cfg, TransformerConfig):
        raise NotImplementedError(
            f"{where} runs the dense TransformerConfig family only; a "
            f"{type(cfg).__name__} model trains through --parallelism dp_sp (ROADMAP D6)")


def load_lm_config(published: Union[str, Dict], **run):
    """A family's config from a published config.json (a path or its
    dict) by `model_type`; `run` are the run options every family has
    (attention_impl, compute_dtype, remat, sp_attention, ...)."""
    if isinstance(published, str):
        with open(published) as f:
            published = json.load(f)
    kind = published.get("model_type")
    if kind == "deepseek_v3":
        from .mla_moe import MlaMoeConfig

        return MlaMoeConfig.from_published(published, **run)
    raise ValueError(f"model_type {kind!r} has no family here (has: deepseek_v3)")
