"""The LM families and the one protocol the training path needs of them.

parallel/dp_sp.py, cli/train_lm.py and the benchmark's driver take a family
from its CONFIG: `lm_family(cfg)`, and know nothing else of it. A family is

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
    plans(cfg, seq_len, seq_shards) -> [(instant, kernels, fields)]
                              what every call of its kernels will look like,
                              from the shapes alone: cli/train_lm.py logs
                              each once and records it as the instant of
                              that name; `kernels` is the prefix under which
                              `remat`'s kept names belong to the plan
                              ("ps_flash_"; None where it names none), whose
                              names and bytes a layer ride in the instant
                              (where the fields hold `layer_kind`, those of
                              that entry of saved_layers alone)
    states                    ((instant, prefix), ...): the groups of what
                              `counters` returns. At a log step the counters
                              whose key starts with `prefix` are one instant
                              of that name, the prefix cut off

A published family is ONE module under models/, named by its row of
`_PUBLISHED_FAMILIES`, that exports `CONFIG` (its config class, with
`from_published`), `REFUSES` (what `from_published` turns down, for
`require_dense`'s message) and `family(cfg)`. `load_lm_config` builds a
config from a published config.json-shaped dict by its `model_type`;
TransformerConfig (models/transformer.py) is built from sizes as before.
"""

from __future__ import annotations

import importlib
import json
from typing import Callable, Dict, NamedTuple, Optional, Tuple, Union

from ..obs.trace import setup_span
from . import transformer


class LMFamily(NamedTuple):
    init: Callable
    apply: Callable
    counters: Optional[Callable]
    saved_layers: Callable
    plans: Callable = lambda cfg, seq_len, seq_shards: []
    states: Tuple[Tuple[str, str], ...] = ()


# The families built from a published config.json: `model_type` -> the
# module under models/ that holds it. The ONE table: load_lm_config,
# lm_family and require_dense read it, and so do their messages.
_PUBLISHED_FAMILIES = {
    "deepseek_v3": "mla_moe",
    "granitemoehybrid": "ssm_hybrid",
    "kimi_linear": "kda_hybrid",
    "evabyte": "eva_dense",
    "laguna": "swa_moe",
    "smallthinker": "prerouted_moe",
}


def _module(model_type: str):
    return importlib.import_module(f".{_PUBLISHED_FAMILIES[model_type]}", __package__)


def _modules():
    """The dense family's module, then the published ones' in the table's
    order, each imported as it is reached."""
    yield transformer
    yield from map(_module, _PUBLISHED_FAMILIES)


def lm_family(cfg) -> LMFamily:
    for module in _modules():
        if isinstance(cfg, module.CONFIG):
            return module.family(cfg)
    raise TypeError(f"no LM family for a {type(cfg).__name__} (has: "
                    + ", ".join(module.CONFIG.__name__ for module in _modules()) + ")")


def require_dense(cfg, where: str) -> None:
    """The schemes that restate the dense block's math (ROADMAP D6) take
    TransformerConfig only."""
    if not isinstance(cfg, transformer.TransformerConfig):
        raise NotImplementedError(
            f"{where} runs the dense TransformerConfig family only; a "
            f"{type(cfg).__name__} model trains through --parallelism dp_sp (ROADMAP D6), "
            "where each family refuses by name what it cannot express ("
            + "; ".join(f"{kind}: {_module(kind).REFUSES}" for kind in _PUBLISHED_FAMILIES)
            + ")")


@setup_span("setup.lm_config")  # the family's module loads here
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
    return _module(kind).CONFIG.from_published(published, **run)
