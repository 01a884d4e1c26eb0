"""The seam between an LM family and everything around it (models/lm.py).

A family is ONE module: its row in `_PUBLISHED_FAMILIES`, its config class,
what it refuses and `family(cfg)`, whose LMFamily says its own plans and
its own groups of counters. cli/train_lm.py and obs/schema.py name no
family: a toy one defined here, registered by one row, trains through the
CLI and finds its plan instant and its state instant in the trace.
"""

import dataclasses
import json
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.models import lm
from ps_pytorch_tpu.models.lm import LMFamily, lm_family, load_lm_config
from ps_pytorch_tpu.models.transformer import TransformerConfig
from ps_pytorch_tpu.obs import schema

from .test_attention_path import MLA
from .test_evabyte_family import PUBLISHED as EVA
from .test_kda_hybrid import PUBLISHED as KDA
from .test_prerouted_moe import PUBLISHED as PREROUTED
from .test_ssm_hybrid import PUBLISHED as HYBRID
from .test_swa_moe import PUBLISHED as SWA


@dataclasses.dataclass(frozen=True)
class ToyConfig:
    vocab_size: int = 32
    hidden_size: int = 16
    num_hidden_layers: int = 1
    num_attention_heads: int = 1
    remat: bool = False
    sp_attention: str = "ring"
    attention_impl: str = "naive"

    @classmethod
    def from_published(cls, published, remat=False, sp_attention="ring",
                       attention_impl="naive", **run):
        if published.get("tied_head"):
            raise ValueError("a toy has no tied_head")
        return cls(published["vocab_size"], published["hidden_size"], remat=remat,
                   sp_attention=sp_attention, attention_impl=attention_impl)


def _toy_module():
    """A family's module as models/lm.py reads one: a bigram model that
    counts the even tokens it saw and plans one made-up kernel."""
    def init(cfg, key):
        a, b = jax.random.split(key)
        return {"embed": jax.random.normal(a, (cfg.vocab_size, cfg.hidden_size)) * 0.1,
                "head": jax.random.normal(b, (cfg.hidden_size, cfg.vocab_size)) * 0.1}

    def apply(cfg, params, tokens, seq_axis_name=None, pos_offset=None):
        logits = params["embed"][tokens] @ params["head"]
        return logits, {"even": jnp.sum(tokens % 2 == 0)[None]}

    def counters(aux):
        return {"toy_even_tokens": jnp.sum(aux["even"]), "toy_even_tokens_per_layer": aux["even"],
                "toy_even_share": jnp.sum(aux["even"]) / 64.0}

    def plans(cfg, seq_len, seq_shards):
        return [("toy_plan", "ps_toy_", {"bricks": seq_len // 4, "brick_width": cfg.hidden_size,
                                         "stacking": "plain", "seq_shards": seq_shards})]

    module = types.ModuleType("ps_pytorch_tpu.models.toy")
    module.CONFIG = ToyConfig
    module.REFUSES = "a tied_head"
    module.family = lambda cfg: LMFamily(init, apply, counters, lambda cfg, b, t: [], plans,
                                         (("toy_state", "toy_"),))
    return module


@pytest.fixture
def toy(monkeypatch):
    monkeypatch.setitem(sys.modules, "ps_pytorch_tpu.models.toy", _toy_module())
    monkeypatch.setitem(lm._PUBLISHED_FAMILIES, "toy", "toy")  # the ONE row a family costs
    return {"model_type": "toy", "vocab_size": 32, "hidden_size": 16}


def test_a_family_of_one_row_trains_through_the_cli_and_its_instants_are_in_the_trace(
        toy, tmp_path):
    from ps_pytorch_tpu.cli import train_lm

    (tmp_path / "toy.json").write_text(json.dumps(toy))
    out = train_lm.main([
        "--lm-config", str(tmp_path / "toy.json"), "--num-dp", "1", "--num-sp", "1",
        "--seq-len", "32", "--batch-size", "2", "--max-steps", "2", "--log-interval", "1",
        "--lr", "0.1", "--train-size", "8", "--trace", str(tmp_path / "trace"),
        "--metrics-file", str(tmp_path / "metrics.jsonl")])
    assert np.isfinite(out["loss"]) and out["params"] == 2 * 32 * 16
    spans = [json.loads(line) for line in open(tmp_path / "trace" / "trace_train_lm_p0.jsonl")]
    for span in spans:
        schema.validate_event(span)
    (plan,) = [s for s in spans if s.get("name") == "toy_plan"]
    assert {k: plan[k] for k in ("bricks", "brick_width", "stacking", "seq_shards")} == {
        "bricks": 8, "brick_width": 16, "stacking": "plain", "seq_shards": 1}
    # a plan that names its kernels carries `remat`'s share under them: none here
    assert (plan["remat_saves"], plan["saved_bytes_per_layer"]) == ("", 0)
    states = [s for s in spans if s.get("name") == "toy_state"]
    assert len(states) == 2                      # one a log step
    records = [r for r in map(json.loads, open(tmp_path / "metrics.jsonl"))
               if r.get("kind") == "train_lm"]
    assert len(records) == 2
    for state, record in zip(states, records):
        schema.validate_event(record)
        # the instant holds the group with its prefix cut, lists and all;
        # the metrics record keeps the counters' own keys, without the lists
        assert set(state) >= {"even_tokens", "even_tokens_per_layer", "even_share"}
        assert state["even_tokens_per_layer"] == [state["even_tokens"]]
        assert record["toy_even_tokens"] == state["even_tokens"] > 0
        assert record["toy_even_share"] == state["even_share"] == state["even_tokens"] / 64
        assert "toy_even_tokens_per_layer" not in record
    # the instants every dp_sp run has are there beside the family's
    assert {"update_plan", "step_scopes"} <= {s.get("name") for s in spans}


def test_the_messages_and_the_loaders_read_the_row(toy):
    cfg = load_lm_config(toy, remat=True, compute_dtype=jnp.bfloat16)
    assert isinstance(cfg, ToyConfig) and cfg.remat
    assert lm_family(cfg).states == (("toy_state", "toy_"),)
    with pytest.raises(ValueError, match="a toy has no tied_head"):
        load_lm_config({**toy, "tied_head": True})
    with pytest.raises(ValueError, match=r"\(has: " + ", ".join(lm._PUBLISHED_FAMILIES) + r"\)"):
        load_lm_config({"model_type": "llama"})
    with pytest.raises(TypeError, match="SwaMoeConfig, PreroutedMoeConfig, ToyConfig"):
        lm_family(object())
    with pytest.raises(NotImplementedError, match="a ToyConfig model.*toy: a tied_head"):
        lm.require_dense(cfg, "tensor parallelism")
    lm.require_dense(TransformerConfig(), "tensor parallelism")


# family -> (its config, the instants of its plans at flash and at naive
# attention, the groups of its counters): what cli/train_lm.py records of it
FAMILIES = {
    "dense": (None, ["flash_plan"], [], ()),
    "deepseek_v3": (MLA, ["flash_plan"], [], (("moe_route", "moe_"),)),
    "granitemoehybrid": (HYBRID, ["flash_plan", "ssd_plan"], ["ssd_plan"],
                         (("ssd_state", "ssd_"),)),
    "kimi_linear": (KDA, ["flash_plan", "kda_plan"], ["kda_plan"],
                    (("kda_state", "kda_"), ("moe_route", "moe_"))),
    "evabyte": (EVA, ["eva_plan"], ["eva_plan"], (("eva_state", "eva_"),)),
    # one plan a kind of attention layer: the sliding layers', the global ones'
    "laguna": (SWA, ["flash_plan", "flash_plan"], [],
               (("moe_route", "moe_"), ("attn_state", "attn_"))),
    # a plan a kind of attention layer, then which dropless layer every block runs
    "smallthinker": (PREROUTED, ["flash_plan", "flash_plan", "moe_plan"], ["moe_plan"],
                     (("moe_route", "moe_"),)),
}


def _config(kind, **run):
    published = FAMILIES[kind][0]
    if published is None:
        return TransformerConfig(vocab_size=64, dim=32, depth=2, heads=2, **run)
    return load_lm_config(published, **run)


@pytest.mark.parametrize("kind", list(FAMILIES))
def test_a_family_says_its_own_plans_and_its_own_counter_groups(kind):
    _, at_flash, at_naive, states = FAMILIES[kind]
    for impl, want in (("flash", at_flash), ("naive", at_naive)):
        cfg = _config(kind, attention_impl=impl)
        family = lm_family(cfg)
        plans = family.plans(cfg, 64, 1)
        assert [name for name, _, _ in plans] == want
        assert family.states == states
        for name, kernels, fields in plans:
            # what the trace can hold as it comes: JSON's own types
            assert json.loads(json.dumps(fields)) == fields
            assert all(type(v) in (int, float, str, bool) for v in fields.values()), fields
            assert kernels is None or kernels.startswith("ps_")
            # a kernel's prefix is that of the names its family saves for `remat`
            if kernels is not None and impl == "flash":
                saved = [n for kind_ in family.saved_layers(cfg, 2, 64)
                         for n in (*kind_.residuals, *kind_.operands)]
                assert any(n.startswith(kernels) for n in saved), (kernels, saved)
    # the counters a family returns all belong to a group it names
    family = lm_family(_config(kind))
    if family.counters is not None:
        assert family.states


def test_the_flash_plan_is_one_function_under_every_family():
    from ps_pytorch_tpu.models import transformer
    from ps_pytorch_tpu.ops.flash_attention import plan_flash

    cfg = _config("deepseek_v3", attention_impl="flash")
    ((name, kernels, fields),) = lm_family(cfg).plans(cfg, 4096, 4)
    assert (name, kernels) == ("flash_plan", "ps_flash_")
    # a ring's hops attend one shard's length at the family's own widths
    plan = plan_flash(1024, 1024, cfg.qk_head_dim, jnp.float32, True, d_v=cfg.v_head_dim)
    assert fields == {
        "block_q": plan.block_q, "block_k": plan.block_k, "grid_steps": plan.grid_steps,
        "tiles_run": plan.tiles_run, "tiles_total": plan.tiles_total, "bwd": plan.bwd,
        "dq_acc_bytes": plan.dq_acc_bytes, "d_qk": cfg.qk_head_dim, "d_v": cfg.v_head_dim,
        "attention_path": "ring", "seq_shards": 4}
    assert fields == transformer.flash_plans(cfg, 4096, 4, cfg.qk_head_dim, cfg.v_head_dim)[0][2]
    # one member has no ring, and Ulysses attends the whole row
    assert lm_family(cfg).plans(cfg, 4096, 1)[0][2]["attention_path"] == "local"
    gathered = _config("dense", attention_impl="flash", sp_attention="ulysses")
    (_, _, fields), = lm_family(gathered).plans(gathered, 4096, 4)
    assert fields["attention_path"] == "ulysses"
    assert fields["tiles_total"] == plan_flash(4096, 4096, 16, jnp.float32, True).tiles_total


@pytest.mark.parametrize("kind", list(lm._PUBLISHED_FAMILIES))
def test_a_published_family_is_its_module(kind):
    module = lm._module(kind)
    assert module.__name__ == f"ps_pytorch_tpu.models.{lm._PUBLISHED_FAMILIES[kind]}"
    assert hasattr(module.CONFIG, "from_published") and module.REFUSES
    cfg = _config(kind)
    assert isinstance(cfg, module.CONFIG)
    assert lm_family(cfg) == module.family(cfg)


def test_the_span_kind_names_what_the_obs_layer_records_and_no_plan():
    assert set(schema.EVENT_KINDS["span"].int_fields) == {
        "depth", "step", "tick", "slot", "rid", "new_tokens", "weights_step", "from_step",
        "to_step", "bytes", "block", "wall_ns", "err_ns"}
    # an attribute the schema never heard of rides along as it came
    record = {"kind": "span", "name": "toy_plan", "t": 0.0, "dur": 0.0, "depth": 1.0,
              "bricks": 8, "brick_share": 0.5, "stacking": "plain", "per_brick": [1, 2]}
    assert schema.validate_event(dict(record)) == record
    assert type(schema.validate_event(dict(record))["depth"]) is int
    assert type(schema.validate_event(dict(record))["brick_share"]) is float
