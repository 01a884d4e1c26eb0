"""The set-up record the program keeps of itself (obs/trace.setup_tracer):
one pathless Tracer a process, on whether or not --trace is given, holding
jax's own trace / lower / compile / cache-load intervals by program name
beside the spans of the program's set-up functions and each step program's
first call; `setup_summary`, the function behind the set-up log line; the
reducer and the ten layer metrics this brought (benchmark/reducers/
setup_spans.py reads the same record by the span names in each metric's own
file, and the two are held equal here; `benchmark/tests` is not collected
by tier-1, so its test stands here).

The load-bearing pin is the hot-path contract, held as a count: a
ScopedStep records its first call and ten further calls add no record.

About 20 s alone on the CPU (two LeNet trainers of a few steps).
"""

import json
import os
import sys
import time

import pytest

import jax
import jax.numpy as jnp

from ps_pytorch_tpu.data import loader, make_synthetic, prefetch_to_device
from ps_pytorch_tpu.models.lm import load_lm_config
from ps_pytorch_tpu.obs import NULL_TRACER, Tracer, trace, validate_event
from ps_pytorch_tpu.obs.scopes import ScopedStep
from ps_pytorch_tpu.obs.trace import (
    SETUP_PARTS, format_setup_summary, setup_line_once, setup_span, setup_summary,
    setup_tracer)
from ps_pytorch_tpu.optim import build_optimizer
from ps_pytorch_tpu.parallel import PSConfig
from ps_pytorch_tpu.parallel.dp_sp import init_lm_state, make_lm_train_step, make_mesh_2d
from ps_pytorch_tpu.trainer import TrainConfig, Trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import trace_report  # noqa: E402

SECONDS = ("before_program_s", "cache_load_s", "trace_lower_s", "first_call_s",
           "build_s", "warm_s", "unplaced_s")
CELLS = [w["name"] for w in json.load(open(os.path.join(REPO, "BENCHMARK.json")))["workloads"]]
SETUP_METRICS = {
    "setup_before_program_s": "before_program_s", "setup_build_s": "build_s",
    "setup_trace_lower_s": "trace_lower_s", "setup_programs": "programs",
    "setup_cache_load_s": "cache_load_s", "setup_first_call_s": "first_call_s",
    "setup_warm_s": "warm_s", "setup_unplaced_s": "unplaced_s"}
# the smallest family config the tests have: the dense byte-level decoder
PUBLISHED = {
    "model_type": "evabyte", "attention_class": "eva", "vocab_size": 67, "hidden_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 96, "window_size": 64, "chunk_size": 8, "num_chunks": None,
    "num_pred_heads": 3, "rope_theta": 100000, "rope_scaling": None, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "norm_add_unit_offset": True, "fp32_skip_add": True,
    "fp32_logits": True, "hidden_act": "silu", "attention_bias": False,
}


@pytest.fixture()
def record(monkeypatch):
    """A set-up record of this test's own: the process's is put aside and
    the next `setup_tracer()` makes a new one (the listeners write into
    whichever is current), in a process that has started no loader and
    logged no set-up line yet."""
    monkeypatch.setattr(trace, "_SETUP", None)
    monkeypatch.setattr(trace, "_line_given", False)
    monkeypatch.setattr(loader, "_first_loader", True)
    return setup_tracer()


@pytest.fixture()
def log_lines():
    """The program's log lines (its logger does not propagate)."""
    import logging

    lines = []
    handler = logging.Handler(logging.INFO)
    handler.emit = lambda rec: lines.append(rec.getMessage())
    logger = logging.getLogger("ps_pytorch_tpu")
    logger.addHandler(handler)
    yield lines
    logger.removeHandler(handler)


def _named(records, *names):
    return [r for r in records if r["name"] in names]


def _toy_step(p, x):
    return p + x.sum(), x * 2.0


def _toy_other(p, x):
    return p, x


def _hand_made():
    """A process born 3.0 s before its record opened at t = 10.0 on a clock
    whose base is 0: a build of 4 s holding one program made in it (trace
    0.5, lower 0.25, compile 1.0 of which 0.75 loading), a step's first call
    of 2 s (trace 0.5, lower 0.25, a compile of 1.0 with a load of 0.5), half a
    second under nothing between them; a program made after t = 20."""
    t = Tracer("setup", path=None)
    t._base = t.header["t_mono"] = 0.0
    rec = lambda name, t0, dur, **a: t._append(name, t0, dur, "phase", 0, a)
    rec("process_start", 10.0, 0.0, age_s=3.0)
    rec("jax.trace", 10.5, 0.5, program="init")
    rec("jax.lower", 11.0, 0.25, program="jit(init)")
    rec("jax.cache_load", 11.25, 0.75)
    rec("jax.compile", 11.25, 1.0, program="jit(init)")
    rec("build.state", 10.25, 3.0, parent="build")
    rec("build", 10.0, 4.0)
    rec("jax.trace", 14.5, 0.5, program="step")
    rec("jax.lower", 15.0, 0.25, program="jit(step)")
    rec("jax.cache_load", 15.25, 0.5)
    rec("jax.compile", 15.25, 1.0, program="jit(step)")
    rec("setup.first_call", 14.5, 2.0, program="test_step")
    rec("jax.trace", 20.5, 0.5, program="reference")
    rec("jax.compile", 21.0, 4.0, program="jit(reference)")
    return t


# ------------------------------------------------------------- the record

def test_the_record_is_one_a_process_and_reading_leaves_it_whole(record):
    assert setup_tracer() is record and setup_tracer() is setup_tracer()
    assert record.path is None and record._buf.maxlen == trace.SETUP_RING == 2048
    with record.span("setup.lm_config"):
        pass
    first, second = record.snapshot(), record.snapshot()
    assert first == second and [r["name"] for r in first][-1] == "setup.lm_config"
    assert record.flush() == 0 and record.snapshot() == first  # pathless: nothing leaves
    assert record.drain() == first and record.snapshot() == []


def test_the_record_opens_with_the_process_age(record):
    (born,) = record.snapshot()
    assert born["name"] == "process_start" and born["dur"] == 0.0
    # this interpreter has lived for a while, and not for a day
    assert 0.05 < born["age_s"] < 86400.0
    assert born["age_s"] == pytest.approx(trace.process_age_s(), abs=1.0)
    validate_event(dict(born))


def test_no_proc_no_process_start(monkeypatch):
    def no_proc(*a, **k):
        raise FileNotFoundError("/proc/self/stat")

    monkeypatch.setattr(trace, "open", no_proc, raising=False)
    assert trace.process_age_s() is None
    monkeypatch.setattr(trace, "_SETUP", None)
    assert setup_tracer().snapshot() == []
    assert setup_summary()["before_program_s"] is None


def test_jax_listener_names_the_three_intervals_of_a_toy_jit(record):
    def toy_program_of_this_test(x):
        return jnp.tanh(x) * 3.0 + jax.jit(jnp.cos)(x)  # a jit traced inside: no span

    toy = jax.jit(toy_program_of_this_test)
    x, again = jnp.arange(7.0), jnp.arange(7.0) + 1.0
    t0 = record.now()
    toy(x).block_until_ready()
    t1 = record.now()
    mine = [r for r in record.snapshot() if "toy_program_of_this_test" in r.get("program", "")]
    assert [(r["name"], r["program"]) for r in mine] == [
        ("jax.trace", "toy_program_of_this_test"),
        ("jax.lower", "jit(toy_program_of_this_test)"),
        ("jax.compile", "jit(toy_program_of_this_test)")]
    # on the record's own clock, in order, inside the call
    ends = [r["t"] + r["dur"] for r in mine]
    assert t0 <= mine[0]["t"] + 1e-3 and ends == sorted(ends) and ends[-1] <= t1 + 1e-3
    assert all(r["async"] for r in mine)
    for r in mine:
        validate_event(dict(r))
    assert not [r for r in record.snapshot() if r.get("program") in ("cos", "jit(cos)")]
    # the same program again: jit finds its executable and jax says nothing
    n = len(record.snapshot())
    toy(again).block_until_ready()
    assert len(record.snapshot()) == n


def test_a_cache_load_is_a_span_of_its_own(record):
    from jax import monitoring

    monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    monitoring.record_event_duration_secs("/jax/compilation_cache/compile_time_saved_sec", 9.0)
    (load,) = _named(record.snapshot(), "jax.cache_load")
    assert load["dur"] == 0.25 and load["t"] == pytest.approx(record.now() - 0.25, abs=0.05)
    assert setup_summary()["cache_hits"] == 1


def test_scoped_step_records_its_first_call_and_ten_more_add_nothing(record):
    """The hot-path contract, held as a count."""
    step = ScopedStep("test_setup_step", jax.jit(_toy_step))
    p, x = jnp.zeros(()), jnp.ones((4, 4))
    assert not _named(record.snapshot(), "setup.first_call")
    p, _ = step(p, x)
    (first,) = _named(record.snapshot(), "setup.first_call")
    assert first["program"] == "test_setup_step" and "async" not in first
    inside = [r for r in _named(record.snapshot(), "jax.trace", "jax.lower", "jax.compile")
              if first["t"] <= r["t"] and r["t"] + r["dur"] <= first["t"] + first["dur"] + 1e-5]
    assert {r["name"] for r in inside} == {"jax.trace", "jax.lower", "jax.compile"}
    n = len(record.snapshot())
    for _ in range(10):
        p, _ = step(p, x)
    assert float(p) == 11 * 16.0
    assert len(record.snapshot()) == n
    # another step of the same name is another program's first call
    ScopedStep("test_setup_step", jax.jit(_toy_other))(p, x)
    assert len(_named(record.snapshot(), "setup.first_call")) == 2


def test_setup_span_decorates_a_set_up_function(record):
    @setup_span("setup.make_step")
    def make(a, b=2):
        """doc"""
        return a + b

    assert make(1, b=3) == 4 and make.__name__ == "make" and make.__doc__ == "doc"
    assert [r["name"] for r in record.snapshot()][-1] == "setup.make_step"


def test_the_lm_set_up_functions_each_leave_their_span(record):
    cfg = load_lm_config(PUBLISHED)
    tx = build_optimizer("adam", 1e-3)
    mesh = make_mesh_2d(1, 1, devices=jax.devices()[:1])
    params, opt = init_lm_state(cfg, tx, jax.random.key(0), mesh)
    make_lm_train_step(cfg, tx, mesh)
    names = [r["name"] for r in record.snapshot() if r["name"].startswith("setup.")]
    assert names == ["setup.lm_config", "setup.init_state", "setup.make_step"]
    (init,) = _named(record.snapshot(), "setup.init_state")
    # the one-op programs of the initialisation lie inside it
    programs = [r for r in _named(record.snapshot(), "jax.compile")
                if init["t"] <= r["t"] <= init["t"] + init["dur"]]
    assert len(programs) >= 3
    for r in record.snapshot():
        validate_event(dict(r))


def test_the_loaders_first_batches_are_one_span_of_the_process_first_loader(record):
    batches = [{"image": jnp.zeros((2, 3)), "label": jnp.zeros((2,))} for _ in range(5)]
    assert len(list(prefetch_to_device(iter(batches), size=2))) == 5
    assert len(_named(record.snapshot(), "setup.first_batch")) == 1
    # a later epoch's loader, a later train() call's: not the process's set-up
    assert len(list(prefetch_to_device(iter(batches), size=2))) == 5
    assert len(_named(record.snapshot(), "setup.first_batch")) == 1


# ------------------------------------------------------------ the trainer

def _trainer(tmp, trace_dir=None, max_steps=5):
    ds = make_synthetic("MNIST", train_size=128, test_size=32, seed=1)
    tcfg = TrainConfig(
        network="LeNet", dataset="MNIST", batch_size=8, test_batch_size=32,
        epochs=8, max_steps=max_steps, eval_freq=0, log_interval=2,
        save_checkpoints=False, train_dir=str(tmp / "models"),
        metrics_file=str(tmp / "m.jsonl"), trace_dir=trace_dir,
    )
    return Trainer(tcfg, PSConfig(num_workers=8), dataset=ds)


def test_a_trainer_without_trace_leaves_its_build_in_the_record(record, tmp_path, log_lines):
    trainer = _trainer(tmp_path)
    assert trainer.tracer is NULL_TRACER
    spans = record.snapshot()
    (build,) = _named(spans, "build")
    parts = sorted((s for s in spans if s.get("parent") == "build"), key=lambda s: s["t"])
    assert [p["name"] for p in parts] == [
        "build.data", "build.model", "build.state", "build.step"]
    assert sum(p["dur"] for p in parts) <= build["dur"] + 5e-6
    # what the builders call lies inside their parts
    state, step = parts[2], parts[3]
    (shard,) = _named(spans, "setup.shard_state")
    (made,) = _named(spans, "setup.make_step")
    assert state["t"] <= shard["t"] and shard["t"] + shard["dur"] <= state["t"] + state["dur"] + 5e-6
    assert step["t"] <= made["t"] and made["t"] + made["dur"] <= step["t"] + step["dur"] + 5e-6
    trainer.train()
    spans = record.snapshot()
    assert trainer.tracer is NULL_TRACER
    (first,) = _named(spans, "setup.first_call")
    assert first["program"] == "ps_train_step"
    # 128 rows at 8 x 8 a step: two steps an epoch, three epochs, ONE first batch
    assert len(_named(spans, "setup.first_batch")) == 1
    # the one log line at the end of set-up, at the first log step
    lines = [line for line in log_lines if line.startswith("set-up ")]
    assert len(lines) == 1 and "before the program" in lines[0] and "programs" in lines[0]
    # a second run of the same trainer is past the process's set-up: no
    # second line, no first call, no first batch
    n_calls = len(_named(spans, "setup.first_call"))
    trainer.tcfg.max_steps = 7
    trainer.train()
    assert len([line for line in log_lines if line.startswith("set-up ")]) == 1
    assert len(_named(record.snapshot(), "setup.first_call")) == n_calls
    assert len(_named(record.snapshot(), "setup.first_batch")) == 1
    assert setup_line_once() is None


def test_a_traced_trainer_writes_the_set_up_record_into_its_stream_once(record, tmp_path):
    with record.span("setup.lm_config"):  # before this trainer's life: not its stream's
        pass
    trainer = _trainer(tmp_path, trace_dir=str(tmp_path / "trace"), max_steps=6)
    trainer.train()
    lines = [json.loads(line) for line in open(tmp_path / "trace" / "trace_train_p0.jsonl")]
    for rec in lines:
        validate_event(dict(rec))
    header, spans = lines[0], lines[1:]
    setup = [s for s in spans if s.get("cat") == "setup"]
    names = [s["name"] for s in setup]
    for once in ("process_start", "build", "build.data", "build.model", "build.state",
                 "build.step", "setup.shard_state", "setup.make_step", "setup.first_batch",
                 "setup.first_call"):
        assert names.count(once) == 1, once
    assert "setup.lm_config" not in names
    assert {"jax.trace", "jax.lower", "jax.compile"} <= set(names)
    assert all(s["async"] for s in setup)  # another stack's: the nesting check skips them
    # on the stream's own clock: the process was born before it, the build
    # opens it, the first call lies inside the first step's dispatch
    by = {s["name"]: s for s in setup}
    assert by["process_start"]["t"] < 0.0 < by["build"]["t"] < 0.05
    (dispatch,) = [s for s in spans if s["name"] == "dispatch" and s["step"] == 1]
    call = by["setup.first_call"]
    assert dispatch["t"] <= call["t"] and call["t"] + call["dur"] <= dispatch["t"] + dispatch["dur"] + 5e-6
    # ahead of the first step in the file, after the header's clock_sync
    assert spans[0]["name"] == "clock_sync" and spans[1]["cat"] == "setup"
    assert max(i for i, s in enumerate(spans) if s.get("cat") == "setup") < min(
        i for i, s in enumerate(spans) if s["name"] == "step")
    # the record itself is whole: a later reader finds what the stream took
    assert len(_named(record.snapshot(), "build")) == 1

    merged, summary = trace_report.merge([str(tmp_path / "trace" / "trace_train_p0.jsonl")], [])
    assert summary["nesting_ok"] and "build" not in summary["phases"]
    assert summary["setup"]["phases"]["build"]["count"] == 1
    (to_first,) = summary["setup"]["time_to_first_step"]
    assert to_first["component"] == "train" and to_first["first_call_s"] == 0.0
    assert sum(to_first[k] or 0.0 for k in SECONDS) == pytest.approx(to_first["stretch_s"], abs=1e-4)
    lanes = [e for e in merged["traceEvents"] if e.get("ph") == "M" and e["name"] == "thread_name"]
    assert [e["args"]["name"] for e in lanes] == ["set-up"]
    assert {e["tid"] for e in merged["traceEvents"] if e.get("cat") == "setup"} == {lanes[0]["tid"]}
    assert trace_report.main([str(tmp_path / "trace"), "--require-phases",
                              "step,build,setup.first_call"]) == 0


def test_train_lm_trace_holds_its_set_up_and_logs_the_line(record, tmp_path, log_lines):
    from ps_pytorch_tpu.cli import train_lm

    train_lm.main([
        "--dim", "32", "--depth", "1", "--heads", "2", "--seq-len", "32",
        "--vocab-size", "64", "--batch-size", "8", "--max-steps", "4",
        "--log-interval", "2", "--trace", str(tmp_path)])
    assert len([line for line in log_lines if line.startswith("set-up ")]) == 1
    recs = [json.loads(line) for line in open(tmp_path / "trace_train_lm_p0.jsonl")]
    assert recs[0]["geometry"]["seq_len"] == 32  # filled in once the parameters are counted
    names = [r["name"] for r in recs[1:] if r.get("cat") == "setup"]
    for once in ("process_start", "setup.init_state", "setup.make_step", "setup.first_call"):
        assert names.count(once) == 1, once
    # the devices came up before the stream's life began: in the record (and
    # the log line's build), not in the stream
    assert "setup.devices" not in names and _named(record.snapshot(), "setup.devices")
    (call,) = [r for r in recs[1:] if r["name"] == "setup.first_call"]
    assert call["program"] == "lm_train_step"


# ------------------------------------------------------------ the summary

def test_setup_summary_splits_a_hand_made_record_into_parts_that_sum_to_the_stretch():
    t = _hand_made()
    s = setup_summary(20.0, t.snapshot(), base=0.0)
    assert s == {
        "before_program_s": 3.0,
        "cache_load_s": 1.25,            # both loads, wherever they lie
        "trace_lower_s": 1.5,            # two traces, two lowerings
        "first_call_s": 2.0 - 0.75 - 0.5,  # less its trace, lowering and load: 0.5 of compile, 0.25 more
        "build_s": 4.0 - 0.75 - 0.75,    # less its program's trace, lowering and load
        "warm_s": 3.5,                   # the first call ends at 16.5
        "unplaced_s": 0.5,               # 14.0 to 14.5
        "stretch_s": 13.0,
        "programs": 2, "compile_s": 2.0, "cache_hits": 2,
    }
    assert sum(s[k] for k in SECONDS) == s["stretch_s"]
    assert [p for p, _ in SETUP_PARTS] == ["cache_load_s", "trace_lower_s", "first_call_s", "build_s"]
    # later on, the program made after t = 20 is inside the stretch too
    later = setup_summary(26.0, t.snapshot(), base=0.0)
    # (its trace is a trace wherever it lies; its compile stands under no
    # span of the program's, after the step's first call: warm)
    assert later["programs"] == 3 and later["trace_lower_s"] == 2.0
    assert later["warm_s"] == 26.0 - 16.5 - 0.5 and later["unplaced_s"] == 0.5
    assert sum(later[k] for k in SECONDS) == later["stretch_s"] == 19.0
    # warm-up starts at the step's first call, whatever is recorded later:
    # a later loader's first batch or a stray one-op program moves nothing
    # between `warm_s` and `unplaced_s`
    stray = t.snapshot() + [{"name": "setup.first_batch", "t": 25.0, "dur": 0.5}]
    moved = setup_summary(26.0, stray, base=0.0)
    assert moved["unplaced_s"] == 0.5 and moved["warm_s"] == later["warm_s"] - 0.5
    assert moved["build_s"] == later["build_s"] + 0.5
    # a part with no record of its kind is 0.0 (a cell's line carries every
    # metric it declares), and the rest still sum; an empty record says nothing
    bare = setup_summary(12.0, _named(t.snapshot(), "process_start", "build.state"), base=0.0)
    assert bare["build_s"] == 0.0 and bare["first_call_s"] == 0.0 and bare["programs"] == 0
    # no step was called yet: nothing is warm-up, the stretch is unplaced
    assert bare["unplaced_s"] == 2.0 and bare["warm_s"] is None and bare["stretch_s"] == 5.0
    empty = setup_summary(12.0, [], base=0.0)
    assert all(empty[k] is None for k in ("before_program_s", "build_s", "cache_load_s"))
    assert empty["stretch_s"] == 0.0 and empty["programs"] == 0
    line = format_setup_summary(s)
    assert line.startswith("set-up 13.0 s: before the program 3.0, build 2.5, trace+lower 1.5, ")
    assert "(2 programs in 2.0 s of compile or load, 2 from the cache)" in line


def test_setup_summary_reads_the_process_record_on_the_process_clock(record):
    with record.span("setup.devices"):
        time.sleep(0.02)
    until = time.perf_counter()
    with record.span("setup.init_state"):  # ends after `until`: not in it
        time.sleep(0.01)
    s = setup_summary(until)
    assert 0.02 <= s["build_s"] < 0.03 and s["programs"] == 0
    assert sum(s[k] or 0.0 for k in SECONDS) == pytest.approx(s["stretch_s"], abs=1e-5)
    assert setup_summary()["build_s"] >= 0.03


# ------------------------------------------- the benchmark's reader of it

@pytest.fixture()
def hand_made(monkeypatch):
    monkeypatch.setattr(trace, "_SETUP", _hand_made())


def _metric(name):
    from benchmark import spec

    (m,) = [m for m in spec.load_cell(CELLS[0]).per_layer if m["name"] == name]
    return m


def test_the_metrics_files_name_the_spans_and_each_form_is_used():
    """The yardstick is under benchmark/: which spans, in which order."""
    args = {name: _metric(name)["args"] for name in SETUP_METRICS}
    assert {a["form"] for a in args.values()} == {
        "instant_attr", "union", "count", "since_last", "rest"}
    order = [names for _, names in SETUP_PARTS]
    unions = [args["setup_" + part] for part, _ in SETUP_PARTS]
    for i, (a, names) in enumerate(zip(unions, order)):
        assert tuple(a["spans"]) == names
        assert a.get("without", []) == [n for earlier in order[:i] for n in earlier]
    everything = [n for names in order for n in names]
    assert args["setup_warm_s"] == {
        "form": "since_last", "spans": ["setup.first_call"], "without": everything}
    assert args["setup_unplaced_s"] == {
        "form": "rest", "until_last": ["setup.first_call"], "without": everything}


@pytest.mark.parametrize("until", [12.0, 14.25, 16.5, 20.0, 26.0])
def test_the_log_lines_parts_and_the_metrics_agree(hand_made, until):
    """Two readers of one record, one in the program (the log line) and one
    under benchmark/ (the metrics): held equal, so a span renamed or a
    rule edited on one side alone fails here."""
    from benchmark import reducers

    want = setup_summary(until, setup_tracer().snapshot(), base=0.0)
    for name, part in SETUP_METRICS.items():
        m = _metric(name)
        got = reducers.reduce(m["kind"], m["args"], {"window_t0": until})
        assert got == pytest.approx(want[part], abs=1e-9) if want[part] is not None else got is None, name


def test_the_parts_and_the_metrics_agree_on_a_real_record(record):
    from benchmark import reducers

    step = ScopedStep("test_step", jax.jit(_toy_step))
    with record.span("setup.init_state"):
        p, x = jnp.float32(0.0), jnp.arange(5.0) + 3.0
    p, x = step(p, x)
    jax.jit(_toy_other)(p, x)  # a stray program after the first call
    until = time.perf_counter()
    want = setup_summary(until)
    assert want["warm_s"] is not None and want["programs"] >= 2
    for name, part in SETUP_METRICS.items():
        m = _metric(name)
        got = reducers.reduce(m["kind"], m["args"], {"window_t0": until})
        assert got == pytest.approx(want[part], abs=1e-9), name


@pytest.mark.parametrize("metric, want", [
    ("setup_before_program_s", 3.0), ("setup_build_s", 2.5), ("setup_trace_lower_s", 1.5),
    ("setup_programs", 2), ("setup_cache_load_s", 1.25), ("setup_first_call_s", 0.75),
    ("setup_warm_s", 3.5), ("setup_unplaced_s", 0.5)])
def test_setup_spans_reads_each_part_up_to_window_t0(hand_made, metric, want):
    from benchmark import reducers, spec

    m = _metric(metric)
    assert (m["kind"], m["source"], m["layer"], m["moves"], m["better"]) == (
        "setup_spans", "program_span", "Entry points", "setup_s", "lower")
    assert "workloads" not in m
    before = setup_tracer().snapshot()
    # the program the reference compiles after window_t0 is not read
    assert reducers.reduce(m["kind"], m["args"], {"window_t0": 20.0}) == want
    assert setup_tracer().snapshot() == before


def test_setup_spans_leaves_out_what_the_record_does_not_hold(monkeypatch):
    from benchmark import reducers

    bare = Tracer("setup", path=None)
    bare._base = bare.header["t_mono"] = 0.0
    bare._append("setup.init_state", 1.0, 2.0, "phase", 0, {})
    monkeypatch.setattr(trace, "_SETUP", bare)
    read = lambda name: reducers.reduce(
        "setup_spans", _metric("setup_" + name)["args"], {"window_t0": 4.0})
    assert read("build_s") == 2.0 and read("unplaced_s") == 1.0 and read("programs") == 0
    # nothing loaded, traced or called yet: 0 s, on the line; no step called:
    # no warm-up to read; no /proc: left out
    for absent in ("first_call_s", "cache_load_s", "trace_lower_s"):
        assert read(absent) == 0.0
    assert read("warm_s") is None and read("before_program_s") is None
    with pytest.raises(ValueError, match="no form"):
        reducers.reduce("setup_spans", {"form": "sum"}, {"window_t0": 4.0})
    # a program that keeps no set-up record (this PR's parent): every one left out
    monkeypatch.delattr(trace, "setup_tracer")
    assert all(read(name[len("setup_"):]) is None for name in SETUP_METRICS)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads_the_new_layer_metrics(cell):
    from benchmark import spec

    by = {m["name"]: m for m in spec.load_cell(cell).per_layer}
    assert set(SETUP_METRICS) <= set(by)
    assert all(by[name]["reads"] for name in SETUP_METRICS)
    # the two counters that reached the driver and had no reader
    for name, only in (("ssd_chunks_cut_off", "granite4hm_train_remat_1period"),
                       ("kda_chunks_cut_off", "kimilinear_train_b2s8192_ep32share")):
        assert (name in by) == (cell == only)
        if name in by:
            m = by[name]
            assert (m["kind"], m["args"], m["source"], m["moves"]) == (
                "counter", {"counter": name + "_traced"}, "program_counter", "train_tokens_per_s")
            from benchmark import reducers

            ev = {"counters": {name + "_traced": 19769.0, name: 3.0}}
            assert reducers.reduce(m["kind"], m["args"], ev) == 19769.0
            assert reducers.reduce(m["kind"], m["args"], {"counters": {}}) is None
