"""`reducers/hlo_scope_time.py`: device time by the scopes the program
writes inside its step. On a small census and capture written by hand, whose
answers are known exactly; through the program's own registry on a tiny
jitted step; and on one traced run of `gpt2m_train_b8s1024` recorded on the
v5e with the census of the step that ran (data/gpt2m_train_b8s1024.
scopes.json.gz: tools/record_scopes.py)."""

import gzip
import json
import os
import re

import pytest

from benchmark import reducers, spec
from benchmark.reducers import hlo_scope_time as hst

DATA = os.path.join(os.path.dirname(__file__), "data")
LM = "lm_train_step"

# instruction -> [phase, scope, work, mixed, via]
CENSUS = {
    "fusion.1": ["forward", "mixer/kda", "dot", [], ""],
    "fusion.2": ["forward", "mixer/kda", "other", [], ""],
    "ps_kda_inverse.1": ["forward", "mixer/kda/delta_rule", "kernel", [], ""],
    "fusion.3": ["remat", "mixer/kda/delta_rule", "other", ["remat:mixer/kda"], ""],
    "divide_add_fusion.4": ["backward", "ffn/mlp", "dot", ["update:update"], ""],
    "fusion.5": ["backward", "ffn/moe/dispatch", "other", [], ""],
    "fusion.6": ["forward", "ffn/moe/combine", "other", [], ""],
    "fusion.7": ["forward", "head_loss", "reduce", [], ""],
    "fusion.8": ["update", "update", "other", [], ""],
    "all-reduce.9": ["update", "grad_reduce", "collective", [], ""],
    "copy.10": ["forward", "embed", "other", [], "user"],
    "fusion.11": ["other", "", "other", [], ""],
    "fusion.12": ["forward", "", "other", [], ""],
    "while.13": ["forward", "mixer/kda/delta_rule", "container", [], ""],
}
MS = {"fusion.1": 10, "fusion.2": 20, "ps_kda_inverse.1": 30, "fusion.3": 40,
      "divide_add_fusion.4": 50, "fusion.5": 60, "fusion.6": 70, "fusion.7": 80, "fusion.8": 90,
      "all-reduce.9": 100, "copy.10": 110, "fusion.11": 1, "fusion.12": 2}


def _ev(extra=(), devices=1, census=CENSUS):
    """One step: every instruction once, back to back, as `short_name` spells
    an event; the loop's own event over its body's."""
    per_dev = {}
    for d in range(devices):
        ops, t = [], 0.0
        for name, ms in list(MS.items()) + list(extra):
            ops.append([f"{name}_f32_2_8192_{d + 1}", t, ms * 1e-3])
            t += ms * 1e-3
        ops.append(["while.13_f32_2_32", 0.0, 0.03])
        per_dev[f"/device:TPU:{d}"] = ops
    return {"trace": {"devices": per_dev}, "steps_traced": 1,
            "census": {LM: {"program": LM, "instructions": census}}}


def _metric(name):
    with open(os.path.join(spec.BENCH_DIR, "layer_metrics", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name, want", [
    ("lm_forward_ms", 10 + 20 + 30 + 70 + 80 + 110),
    ("lm_backward_ms", 50 + 60),
    ("lm_remat_ms", 40),
    ("lm_update_ms", 90 + 100),
    ("lm_scope_unplaced_ms", 1 + 2),
    ("lm_mixer_ms", 10 + 20 + 30 + 40),
    ("lm_ffn_ms", 50 + 60 + 70),
    ("lm_head_loss_ms", 80 + 110),
    ("kda_scope_ms", 30 + 40),
    ("kda_mixer_passes_ms", 20),          # not the delta rule, not the projections' products
    ("moe_buffer_scope_ms", 60 + 70),
    ("ssd_scope_ms", 0),
])
def test_each_metric_reads_its_places(name, want, capsys):
    m = _metric(name)
    assert m["kind"] == "hlo_scope_time"
    assert reducers.reduce(m["kind"], m["args"], _ev()) == pytest.approx(want)
    assert capsys.readouterr().out.startswith("[bench] scopes ")


def test_the_phases_and_the_unplaced_sum_to_the_step_and_the_line_says_what_was_joined(capsys):
    ev = _ev(devices=2)
    got = {n: reducers.reduce("hlo_scope_time", _metric(n)["args"], ev) for n in (
        "lm_forward_ms", "lm_backward_ms", "lm_remat_ms", "lm_update_ms", "lm_scope_unplaced_ms")}
    step = reducers.reduce("scope_time", {}, ev)
    assert sum(got.values()) == pytest.approx(step) == pytest.approx(sum(MS.values()))
    out = capsys.readouterr().out.strip().split("\n")
    assert len(out) == 1                                     # one line a run, not one a metric
    line = json.loads(out[0].split(" ", 2)[2])
    assert line["found_pct"] == 100.0 and line["step_ms"] == pytest.approx(step)
    assert line["ms_by_phase"] == pytest.approx(
        {"backward": 110, "forward": 320, "remat": 40, "update": 190})
    assert line["ms_by_top_scope"] == pytest.approx(
        {"embed": 110, "ffn": 180, "grad_reduce": 100, "head_loss": 80, "mixer": 100, "update": 90})
    assert line["mixed_ms"] == pytest.approx(90)
    assert line["mixed_ms_by_pair"] == pytest.approx({
        "backward:ffn/mlp | update:update": 50, "remat:mixer/kda/delta_rule | remat:mixer/kda": 40})
    assert line["unplaced_ms"] == pytest.approx(3) and line["placed_by_a_neighbour_ms"] == 110
    assert "why" not in line


def test_under_99_pct_found_every_metric_is_left_out_and_the_line_says_why(capsys):
    ev = _ev(extra=[("fusion.4711", 20)])                    # 20 of 683 ms: 97.1% found
    for name in ("lm_forward_ms", "lm_scope_unplaced_ms", "kda_scope_ms"):
        assert reducers.reduce("hlo_scope_time", _metric(name)["args"], ev) is None
    line = json.loads(capsys.readouterr().out.strip().split(" ", 2)[2])
    assert 97.0 < line["found_pct"] < 97.2 and "99%" in line["why"]
    assert line["unfound_top"] == [["fusion.4711_f32_2_8192_1", pytest.approx(20)]]
    # a little that is not in the census is carried as unplaced
    ev = _ev(extra=[("fusion.4711", 5)])
    assert reducers.reduce("hlo_scope_time", {"program": LM, "unplaced": True}, ev) == \
        pytest.approx(8)


def test_a_program_that_keeps_no_census_gives_nothing_and_says_nothing(capsys, monkeypatch):
    ev = _ev()
    del ev["census"]
    monkeypatch.setattr(hst, "program_census", lambda program: None)
    assert reducers.reduce("hlo_scope_time", {"program": LM, "phase": "^forward$"}, ev) is None
    assert reducers.reduce("hlo_scope_time", {"program": LM}, {"trace": None}) is None
    assert capsys.readouterr().out == ""
    # a program with no such module at all (the parent of the PR that
    # brought the scopes): the import fails inside, nothing is raised
    import builtins
    real = builtins.__import__

    def no_scopes(name, *a, **kw):
        if name == "ps_pytorch_tpu.obs.scopes":
            raise ImportError(name)
        return real(name, *a, **kw)

    monkeypatch.undo()
    monkeypatch.setattr(builtins, "__import__", no_scopes)
    assert hst.program_census(LM) is None


def test_the_census_comes_from_the_programs_own_registry(capsys):
    import jax
    import jax.numpy as jnp

    from ps_pytorch_tpu.obs.scopes import HEAD_LOSS, UPDATE, ScopedStep, scope

    def f(p, x):
        def loss(p):
            with scope(HEAD_LOSS):
                return jnp.sum(jnp.tanh(x @ p) ** 2)

        g = jax.grad(loss)(p)
        with scope(UPDATE):
            return p - 0.1 * g

    step = ScopedStep("bench_test_step", jax.jit(f))
    assert hst.program_census("bench_test_step") is None       # built, never called
    step(jnp.ones((8, 8)), jnp.ones((4, 8)))
    census = hst.program_census("bench_test_step")
    assert census["census_s"] >= 0 and census["program"] == "bench_test_step"
    ops, t = [], 0.0
    for name, row in census["instructions"].items():
        if row[2] != "container":
            ops.append([name + "_f32_8_8", t, 1e-3])
            t += 1e-3
    ev = {"trace": {"devices": {"/device:TPU:0": ops}}, "steps_traced": 1}
    read = lambda **args: reducers.reduce("hlo_scope_time", {"program": "bench_test_step", **args}, ev)
    assert read(scope="^head_loss") > 0 and read(phase="^update$") > 0
    assert read(phase="^forward$") + read(phase="^backward$") + read(phase="^update$") \
        + read(unplaced=True) == pytest.approx(1e3 * t)
    assert hst.program_census("no_such_program") is None


def test_every_declared_metric_of_the_kind_has_its_file_its_cells_and_sound_expressions():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    seen = 0
    for m in bench["per_layer"]:
        reader = _metric(m["name"])
        if reader["kind"] != "hlo_scope_time":
            continue
        seen += 1
        args = reader["args"]
        assert set(args) <= {"program", "phase", "scope", "less", "less_work", "unplaced"}
        for key in ("phase", "scope", "less"):
            re.compile(args.get(key, ""))
        assert (m["source"], m["layer"], m["unit"]) == ("program_span", "Step program", "ms")
        lm = args["program"] == LM
        assert m["moves"] == ("train_tokens_per_s" if lm else "train_images_per_s")
        for cell in m["workloads"]:
            kind = spec.load_cell(cell).kind
            assert (kind != "ps_train") == lm, (m["name"], cell)
            assert cells[cell]
    assert seen == 16


@pytest.mark.skipif(not os.path.exists(os.path.join(DATA, "gpt2m_train_b8s1024.scopes.json.gz")),
                    reason="the recorded census is not in this checkout")
def test_on_a_recorded_run_the_census_places_the_capture(capsys):
    from benchmark.tools import record_scopes

    with gzip.open(os.path.join(DATA, "gpt2m_train_b8s1024.scopes.json.gz"), "rt") as f:
        fixture = json.load(f)
    cell = spec.load_cell("gpt2m_train_b8s1024")
    got = record_scopes.read(fixture, cell)
    want = fixture["expected"]
    assert got["metrics"] == pytest.approx(want["metrics"])
    m, line = got["metrics"], got["line"]
    assert line["found_pct"] >= 99.0
    step = line["step_ms"]
    phases = m["lm_forward_ms"] + m["lm_backward_ms"] + m["lm_remat_ms"] + m["lm_update_ms"]
    assert phases + m["lm_scope_unplaced_ms"] == pytest.approx(step, rel=5e-3)
    assert m["lm_scope_unplaced_ms"] < 0.02 * step
    assert m["lm_remat_ms"] == 0.0                           # the cell runs without remat
    assert m["lm_mixer_ms"] + m["lm_ffn_ms"] + m["lm_head_loss_ms"] < step
