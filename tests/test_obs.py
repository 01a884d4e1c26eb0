"""Observability layer (ARCHITECTURE §7g): span tracer, unified event
schema, profiler windows, trace_report merge — and the do-not-perturb
contract.

The load-bearing pins:

- tracer OFF adds zero host syncs: the instrumented hot paths
  (trainer.py, serve/engine.py) and the whole obs/ tree stay PSL004-
  clean, and obs/trace.py contains no sync primitive AT ALL (not even a
  pragma'd one);
- tracer ON reuses the drivers' existing per-window sync points — the
  tracer records time around the pre-existing `device_get`/
  `block_until_ready` call sites and never adds its own (pslint's
  strict sweep over obs/ flags any `block_until_ready` there);
- every event emitter round-trips through the kind registry: unknown
  kinds and missing required fields raise at the write choke point, and
  declared counter fields land int-typed in the JSONL.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ps_pytorch_tpu import obs
from ps_pytorch_tpu.obs import (
    EVENT_KINDS,
    NULL_TRACER,
    ProfileWindow,
    SCHEMA_VERSION,
    Tracer,
    chrome_trace_events,
    run_header,
    summarize_spans,
    validate_event,
)
from ps_pytorch_tpu.data import make_synthetic
from ps_pytorch_tpu.lint import lint_paths
from ps_pytorch_tpu.parallel import PSConfig
from ps_pytorch_tpu.serve import Request, ServeConfig, ServingEngine
from ps_pytorch_tpu.trainer import TrainConfig, Trainer, append_metrics_line

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import trace_report  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 8


# ------------------------------------------------------------------ tracer

def test_tracer_records_nested_spans_and_drains():
    t = Tracer("t")
    with t.span("outer", step=1):
        with t.span("inner"):
            time.sleep(0.001)
    spans = t.drain()
    assert [s["name"] for s in spans] == ["inner", "outer"]  # exit order
    by = {s["name"]: s for s in spans}
    assert by["outer"]["depth"] == 0 and by["inner"]["depth"] == 1
    assert by["outer"]["step"] == 1
    # containment: the child sits inside the parent
    i, o = by["inner"], by["outer"]
    assert o["t"] <= i["t"] + 1e-9
    assert i["t"] + i["dur"] <= o["t"] + o["dur"] + 1e-5
    assert i["dur"] >= 0.001
    assert t.drain() == []  # drain empties the ring


def test_tracer_ring_is_bounded():
    t = Tracer("t", ring=8)
    for i in range(20):
        with t.span("s", seq=i):
            pass
    spans = t.drain()
    assert len(spans) == 8
    assert t.dropped == 12
    assert spans[-1]["seq"] == 19  # newest kept, oldest evicted


def test_pathless_flush_keeps_spans_for_drain():
    """A memory-only tracer (tune's measured probe) must survive the serve
    engine's periodic flush: flush() without a path is a no-op, not a
    silent discard."""
    t = Tracer("bench")
    with t.span("a"):
        pass
    assert t.flush() == 0
    assert [s["name"] for s in t.drain()] == ["a"]


def test_flush_surfaces_ring_truncation(tmp_path):
    p = tmp_path / "trace_small.jsonl"
    t = Tracer("t", path=str(p), ring=2)
    for i in range(5):
        with t.span("s"):
            pass
    t.flush()
    spans = [json.loads(line) for line in open(p)][1:]  # skip run_header
    (marker,) = [s for s in spans if s["name"] == "spans_dropped"]
    assert marker["dropped_total"] == 3
    # watermark: a clean follow-up flush does not repeat the marker
    with t.span("s"):
        pass
    t.flush()
    spans = [json.loads(line) for line in open(p)][1:]
    assert sum(s["name"] == "spans_dropped" for s in spans) == 1


def test_null_tracer_is_inert():
    assert not NULL_TRACER.enabled
    with NULL_TRACER.span("x", step=1):
        pass
    NULL_TRACER.add("y", 0.0, 1.0)
    NULL_TRACER.instant("z")
    assert NULL_TRACER.drain() == []
    assert NULL_TRACER.flush() == 0


def test_tracer_flush_writes_header_then_spans(tmp_path):
    p = tmp_path / "trace_x.jsonl"
    t = Tracer("comp", path=str(p), geometry={"n": 1}, pid=3)
    with t.span("a"):
        pass
    assert t.flush() == 1
    with t.span("b"):
        pass
    t.flush()
    lines = [json.loads(line) for line in open(p)]
    assert lines[0]["kind"] == "run_header"
    assert lines[0]["schema_version"] == SCHEMA_VERSION
    assert lines[0]["pid"] == 3
    assert lines[0]["geometry"] == {"n": 1}
    # the header is written ONCE; spans append across flushes, each
    # flush closed by its clock_sync (the construction-time one first)
    assert [ln["name"] for ln in lines[1:]] == [
        "clock_sync", "a", "clock_sync", "b", "clock_sync"]
    assert all(ln["kind"] == "span" for ln in lines[1:])


def test_tracer_add_and_explicit_intervals():
    t = Tracer("t")
    t0 = t.now()
    t.add("drain", t0, 0.5, cat="serve", from_step=1, to_step=2)
    (s,) = t.drain()
    assert s["name"] == "drain" and s["dur"] == 0.5
    assert s["from_step"] == 1 and s["to_step"] == 2
    # explicit intervals are async: they overlap the span stack by
    # design, so the nesting validator and walltime fractions skip them
    assert s["async"] is True


def test_chrome_trace_events_map_to_wall_microseconds():
    t = Tracer("c", pid=2)
    with t.span("a", step=4):
        pass
    evs = chrome_trace_events(
        t.header, t.drain(), t0_wall=t.header["t_wall"] - 1.0
    )
    meta, span = evs[0], evs[1]
    assert meta["ph"] == "M" and "c p2" in meta["args"]["name"]
    assert span["ph"] == "X" and span["pid"] == 2
    assert span["ts"] >= 1e6  # the 1 s wall base offset, in µs
    assert span["args"]["step"] == 4
    json.dumps(evs)  # valid JSON payload


def test_summarize_spans_percentiles():
    spans = [
        {"kind": "span", "name": "x", "dur": d} for d in (0.1, 0.2, 0.3)
    ] + [{"kind": "span", "name": "y", "dur": 1.0}]
    s = summarize_spans(spans)
    assert s["x"]["count"] == 3 and s["x"]["p50_s"] == 0.2
    assert s["x"]["total_s"] == pytest.approx(0.6)
    assert s["y"]["p99_s"] == 1.0


# ------------------------------------------------------------------ schema

# one representative record per registered kind, shaped like its REAL
# emitter (trainer.py / elastic.py / checkpoint.py / serve spans) —
# float-typed counters on purpose where the emitter produces floats
SAMPLE_EVENTS = {
    "run_header": run_header("train", geometry={"num_workers": 8}),
    "train": {"kind": "train", "step": 3, "epoch": 1, "time_cost": 0.1,
              "loss": 0.5, "prec1": 10.0, "skipped_steps": 2.0,
              "skip_streak": 1.0},
    "eval": {"kind": "eval", "step": 3, "loss": 0.5, "prec1": 10.0,
             "prec5": 50.0},
    "train_lm": {"kind": "train_lm", "parallelism": "tp", "step": 2,
                 "loss": 1.0, "time_cost": 0.2},
    "grad_skip": {"kind": "grad_skip", "step": 4.0, "skipped_steps": 1.0,
                  "skip_streak": 1.0, "loss_scale": 1024.0},
    "straggler": {"kind": "straggler", "step": 5, "time_cost": 2.0,
                  "threshold": 0.75},
    "straggler_storm": {"kind": "straggler_storm", "step": 7,
                        "start_step": 5, "consecutive": 3,
                        "threshold": 0.75},
    "straggler_storm_end": {"kind": "straggler_storm_end", "step": 9,
                            "start_step": 5, "consecutive": 5},
    "mask_adapt": {"kind": "mask_adapt", "step": 20, "window_start": 11,
                   "from": 4, "to": 3, "slow_steps": 1,
                   "window_steps": 10},
    "resume_reshape": {"kind": "resume_reshape", "step": 6,
                       "from": {"num_workers": 8}, "to": {"num_workers": 4}},
    "ckpt_quarantined": {"kind": "ckpt_quarantined", "step": 6,
                         "path": "/tmp/x", "error": "crc"},
    "ckpt_write_failed": {"kind": "ckpt_write_failed", "step": 6,
                          "path": "/tmp/x", "error": "EIO"},
    "span": {"kind": "span", "name": "dispatch", "cat": "phase",
             "t": 1.25, "dur": 0.5, "depth": 0, "step": 3.0},
    "autotune": {"kind": "autotune", "run": run_header("autotune"),
                 "model": "lenet", "network": "LeNet", "grid": "tiny",
                 "n_points": 7.0, "n_candidates": 5.0, "n_pruned": 2.0,
                 "gate": {"min_modeled_speedup": None,
                          "modeled_speedup": 1.0}},
    # serving request lifecycle (serve/engine.py emitters, §7i) —
    # counters float-typed on purpose where JSON round-trips may float
    "request_done": {"kind": "request_done", "rid": 7, "new_tokens": 12.0,
                     "weights_step": 20.0, "met_deadline": True,
                     "ttft_s": 0.01},
    "request_shed": {"kind": "request_shed", "rid": 8,
                     "projected_wait_s": 1.25, "queue_depth": 14.0,
                     "slo_budget_s": 0.5, "at_s": 3.5},
    "deadline_expired": {"kind": "deadline_expired", "rid": 9,
                         "where": "decode", "deadline_s": 2.0,
                         "expired_s": 2.25, "tokens_done": 3.0},
    "rollover_abort": {"kind": "rollover_abort", "from_step": 10.0,
                       "staged_step": 20.0, "reason": "corrupt_staged",
                       "error": "CRC mismatch", "at_s": 4.0},
    "admission_adapt": {"kind": "admission_adapt", "state": "shedding",
                        "projected_wait_s": 1.5, "queue_depth": 14.0,
                        "window_submits": 9.0, "window_sheds": 6.0,
                        "windows": 3.0, "slo_budget_s": 0.5},
}


def test_registry_covers_every_kind_and_round_trips():
    """The audit pin: every registered kind has a sample shaped like its
    emitter, every sample validates, and declared counters come out int
    even when the emitter floats them."""
    assert set(SAMPLE_EVENTS) == set(EVENT_KINDS)
    for kind, rec in SAMPLE_EVENTS.items():
        out = validate_event(dict(rec))
        for f in EVENT_KINDS[kind].int_fields:
            if f in out and out[f] is not None:
                assert isinstance(out[f], int), (kind, f, out[f])
    # the float->int normalization is real, not vacuous
    assert validate_event(dict(SAMPLE_EVENTS["grad_skip"]))["step"] == 4
    assert isinstance(
        validate_event(dict(SAMPLE_EVENTS["train"]))["skipped_steps"], int
    )


def test_validate_rejects_unknown_and_incomplete_events():
    with pytest.raises(ValueError, match="no 'kind'"):
        validate_event({"step": 1})
    with pytest.raises(ValueError, match="unknown event kind"):
        validate_event({"kind": "made_up"})
    with pytest.raises(ValueError, match="missing required"):
        validate_event({"kind": "grad_skip", "step": 1})


def test_append_metrics_line_validates_and_stamps(tmp_path):
    p = tmp_path / "m.jsonl"
    append_metrics_line(str(p), {"kind": "eval", "step": 1.0, "loss": 2.0})
    rec = json.loads(p.read_text())
    assert rec["step"] == 1 and isinstance(rec["step"], int)
    assert "t_wall" in rec and rec["t_wall"] == pytest.approx(
        time.time(), abs=60
    )
    with pytest.raises(ValueError):
        append_metrics_line(str(p), {"kind": "bogus_kind"})
    # path=None is a no-op sink, never a validation error
    append_metrics_line(None, {"kind": "bogus_kind"})


# ----------------------------------------------------- do-not-perturb pins

def test_tracer_source_has_no_sync_primitives():
    """obs/trace.py must not contain ANY sync primitive — not even a
    pragma'd one. The tracer observes existing sync points; it never
    owns one."""
    src = open(os.path.join(os.path.dirname(obs.__file__), "trace.py")).read()
    for token in ("block_until_ready(", "device_get(", ".item(",
                  "psl: sync-ok"):
        assert token not in src, token


def test_instrumented_paths_stay_psl004_clean():
    """Tracer-off introduces no new host syncs: the instrumented trainer
    loop, serve engine, and the whole obs/ tree (strict mode, where even
    block_until_ready flags) lint clean after pragmas."""
    paths = [
        os.path.join(REPO, "ps_pytorch_tpu", "trainer.py"),
        os.path.join(REPO, "ps_pytorch_tpu", "data", "loader.py"),
        os.path.join(REPO, "ps_pytorch_tpu", "cli", "train_lm.py"),
        os.path.join(REPO, "ps_pytorch_tpu", "serve", "engine.py"),
        os.path.join(REPO, "ps_pytorch_tpu", "obs"),
    ]
    findings = [f for f in lint_paths(paths) if f.rule == "PSL004"]
    assert findings == [], [f.to_json() for f in findings]


def test_strict_psl004_flags_syncs_planted_in_obs_tree(tmp_path):
    """The lint guard is live: a host sync added anywhere under the obs/
    tree — including block_until_ready, blessed elsewhere — flags even
    outside any loop."""
    bad = tmp_path / "ps_pytorch_tpu" / "obs" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        "import jax\n"
        "def flush(x):\n"
        "    jax.block_until_ready(x)\n"
        "    return jax.device_get(x)\n"
    )
    rules = [f.rule for f in lint_paths([str(bad)])]
    assert rules.count("PSL004") == 2
    # the same file OUTSIDE the obs tree: no loop, tick-less -> clean
    ok = tmp_path / "elsewhere" / "bad.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(bad.read_text())
    assert [f.rule for f in lint_paths([str(ok)])] == []


def test_serve_tick_has_exactly_one_blessed_fetch():
    """Tracer-on adds no fetches: the engine's tick still carries exactly
    one sync-ok pragma (the fused [slots] token fetch) and no other sync
    call site."""
    src = open(
        os.path.join(REPO, "ps_pytorch_tpu", "serve", "engine.py")
    ).read()
    assert src.count("psl: sync-ok") == 1
    assert src.count("device_get") == 1
    assert "block_until_ready" not in src


# ---------------------------------------------------------- profiler window

def test_profile_window_bounds_and_idempotent_close(tmp_path):
    prof = tmp_path / "prof"
    pw = ProfileWindow(str(prof), start_step=2, num_steps=2)
    x = jnp.ones((4,))
    pw.before_step(1, x)
    assert not pw.active
    pw.before_step(2, x)
    assert pw.active
    pw.before_step(3, x)
    assert pw.active  # [2, 4): step 3 still inside
    pw.before_step(4, x)
    assert not pw.active  # stopped at the window end
    pw.close(x)  # idempotent
    assert any(prof.rglob("*")), "no profiler artifacts written"


def test_profile_window_disabled_and_validation():
    pw = ProfileWindow(None, start_step=1)
    pw.before_step(1)
    assert not pw.active
    pw.close()
    with pytest.raises(ValueError):
        ProfileWindow("/tmp/x", start_step=1, num_steps=0)
    # a no-op window must not validate: --profile-steps 0 without
    # --profile-dir cannot abort the training run it does not affect
    ProfileWindow(None, start_step=1, num_steps=0)


# -------------------------------------------------------- traced train run

def test_traced_training_run_emits_phases_and_headers(tmp_path, monkeypatch):
    ds = make_synthetic("MNIST", train_size=128, test_size=32, seed=1)
    tcfg = TrainConfig(
        network="LeNet", dataset="MNIST", batch_size=8, test_batch_size=32,
        epochs=2, max_steps=4, eval_freq=2, log_interval=2,
        train_dir=str(tmp_path / "models"),
        metrics_file=str(tmp_path / "m.jsonl"),
        trace_dir=str(tmp_path / "trace"),
    )
    trainer = Trainer(tcfg, PSConfig(num_workers=N), dataset=ds)
    assert trainer.tracer.enabled
    trainer.train()

    trace_path = tmp_path / "trace" / "trace_train_p0.jsonl"
    assert trace_path.exists()
    lines = [json.loads(line) for line in open(trace_path)]
    header, spans = lines[0], lines[1:]
    assert header["kind"] == "run_header" and header["component"] == "train"
    names = {s["name"] for s in spans}
    assert {"fetch", "h2d", "dispatch", "sync", "guard",
            "ckpt_save"} <= names
    # per-step attribution: every dispatch span carries its step int
    d_steps = [s["step"] for s in spans if s["name"] == "dispatch"]
    assert d_steps == [1, 2, 3, 4]
    assert all(isinstance(s, int) for s in d_steps)

    # metrics stream: run_header FIRST, same run_id as the trace stream,
    # and the train records' counters are ints under the schema
    events = [json.loads(line) for line in open(tcfg.metrics_file)]
    assert events[0]["kind"] == "run_header"
    assert events[0]["run_id"] == header["run_id"]
    trains = [e for e in events if e["kind"] == "train"]
    assert trains and all(isinstance(e["skipped_steps"], int) for e in trains)
    assert all("t_wall" in e for e in events)


# ------------------------------------------- the loop's spans, by structure

def _read_stream(path):
    lines = [json.loads(line) for line in open(path)]
    return lines[0], lines[1:]


def _end(s):
    return s["t"] + s["dur"]


@pytest.fixture(scope="module")
def traced_loop(tmp_path_factory):
    """A LeNet run of a dozen steps, log_interval 4, traced; every flush
    of the span file is timed on the tracer's own clock."""
    tmp = tmp_path_factory.mktemp("loop")
    ds = make_synthetic("MNIST", train_size=256, test_size=32, seed=1)
    tcfg = TrainConfig(
        network="LeNet", dataset="MNIST", batch_size=8, test_batch_size=32,
        epochs=4, max_steps=12, eval_freq=0, log_interval=4,
        save_checkpoints=False, train_dir=str(tmp / "models"),
        metrics_file=str(tmp / "m.jsonl"), trace_dir=str(tmp / "trace"),
    )
    trainer = Trainer(tcfg, PSConfig(num_workers=N), dataset=ds)
    tracer, flushes = trainer.tracer, []
    real_flush = tracer.flush

    def timed_flush():
        t0 = tracer.now()
        n = real_flush()
        flushes.append((t0, tracer.now(), n))
        return n

    tracer.flush = timed_flush
    trainer.train()
    header, spans = _read_stream(tmp / "trace" / "trace_train_p0.jsonl")
    return {"header": header, "spans": spans, "flushes": flushes}


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def test_every_span_of_an_iteration_nests_under_its_step(traced_loop):
    spans = traced_loop["spans"]
    steps = {s["step"]: s for s in _named(spans, "step")}
    assert sorted(steps) == list(range(1, 13))
    assert all(s["depth"] == 0 and "parent" not in s for s in steps.values())
    in_loop = [s for s in spans if not s.get("async")
               and not s["name"].startswith("build") and s["name"] != "step"]
    assert {s["name"] for s in in_loop} == {
        "fetch", "gather", "h2d", "dispatch", "window_close", "sync",
        "metrics_fetch", "log", "metrics_write", "guard", "stop_check",
        "trace_flush"}
    for s in in_loop:
        # it carries its iteration's number and lies inside that `step`
        parent = steps[s["step"]]
        assert parent["t"] <= s["t"] + 1e-6, s
        assert _end(s) <= _end(parent) + 5e-6, s
        assert s["depth"] >= 1 and "parent" in s
    for name in ("fetch", "dispatch"):
        assert [s["step"] for s in _named(spans, name)] == list(range(1, 13))
        assert all(s["parent"] == "step" for s in _named(spans, name))
    # the run ends at max_steps, before the last iteration's stop check
    assert [s["step"] for s in _named(spans, "stop_check")] == list(range(1, 12))


def test_each_log_step_has_one_window_close_holding_its_parts(traced_loop):
    spans = traced_loop["spans"]
    closes = _named(spans, "window_close")
    assert [(w["step"], w["block"]) for w in closes] == [(1, 1), (4, 3), (8, 4), (12, 4)]
    for w in closes:
        parts = [s for s in spans if s.get("parent") == "window_close"
                 and s["step"] == w["step"]]
        assert [p["name"] for p in sorted(parts, key=lambda p: p["t"])] == [
            "sync", "metrics_fetch", "log", "metrics_write", "guard"]
        assert all(w["t"] <= p["t"] + 1e-6 and _end(p) <= _end(w) + 5e-6 for p in parts)
        assert sum(p["dur"] for p in parts) <= w["dur"] + 5e-6
        assert all(isinstance(w[k], int) for k in ("step", "block"))


def test_gather_and_h2d_lie_inside_fetch(traced_loop):
    spans = traced_loop["spans"]
    fetches = {s["step"]: s for s in _named(spans, "fetch")}
    inner = _named(spans, "gather") + _named(spans, "h2d")
    # one gather and one upload per fetch, two in the first: the prefetch
    # queue stays one batch ahead, so twelve steps gather thirteen
    assert len(inner) == 2 * 13
    for s in inner:
        f = fetches[s["step"]]
        assert s["parent"] == "fetch" and s["depth"] == f["depth"] + 1
        assert f["t"] <= s["t"] + 1e-6 and _end(s) <= _end(f) + 5e-6
    # 8 workers x 8 rows of 28x28x1 uint8 (or float32) and their labels
    uploads = {s["bytes"] for s in _named(spans, "h2d")}
    assert len(uploads) == 1 and uploads.pop() >= 64 * 28 * 28


def test_build_and_its_four_parts_precede_step_one(traced_loop):
    spans = traced_loop["spans"]
    (build,) = _named(spans, "build")
    parts = [s for s in spans if s.get("parent") == "build"]
    assert [p["name"] for p in sorted(parts, key=lambda p: p["t"])] == [
        "build.data", "build.model", "build.state", "build.step"]
    assert all(build["t"] <= p["t"] + 1e-6 and _end(p) <= _end(build) + 5e-6
               for p in parts)
    assert sum(p["dur"] for p in parts) <= build["dur"] + 5e-6
    first = min(_named(spans, "step"), key=lambda s: s["t"])
    assert first["step"] == 1 and _end(build) <= first["t"]
    # the header was created before anything was built and still names
    # the geometry
    assert traced_loop["header"]["geometry"]["network"] == "LeNet"
    assert build["t"] < 0.05


def test_clock_sync_once_per_flush_on_the_headers_wall_clock(traced_loop):
    header, spans = traced_loop["header"], traced_loop["spans"]
    syncs = _named(spans, "clock_sync")
    wrote = [f for f in traced_loop["flushes"] if f[2] > 0]
    # one taken when the tracer was made, one closing every flush that wrote
    assert len(syncs) == len(wrote) + 1
    assert syncs[0]["t"] < 0.05 and spans[0] is syncs[0]
    for sync, (t0, t1, _) in zip(syncs[1:], wrote):
        assert t0 <= sync["t"] <= t1
    for s in syncs:
        assert isinstance(s["wall_ns"], int) and isinstance(s["err_ns"], int)
        assert 0 < s["err_ns"] < 1_000_000
        assert abs(s["wall_ns"] * 1e-9 - (header["t_wall"] + s["t"])) < 1e-3
        assert s["async"] and s["dur"] == 0


def test_no_flush_between_a_syncs_end_and_the_next_dispatchs_end(traced_loop):
    """After `sync` returns the device is idle until the next dispatch has
    been enqueued: span I/O there would lengthen the gap a traced run is
    there to measure."""
    spans, flushes = traced_loop["spans"], traced_loop["flushes"]
    dispatch_end = {s["step"]: _end(s) for s in _named(spans, "dispatch")}
    gaps = [(_end(s), dispatch_end[s["step"] + 1])
            for s in _named(spans, "sync") if s["step"] + 1 in dispatch_end]
    assert len(gaps) == 3  # after steps 1, 4 and 8
    for t0, t1, _ in flushes:
        assert not any(a - 1e-6 < t1 and t0 < b + 1e-6 for a, b in gaps), (t0, t1, gaps)
    # one flush per window, right after the dispatch that follows it, and
    # the trailing one in `finally`
    assert len([f for f in flushes if f[2] > 0]) == 4
    flushed_in = [s["step"] for s in _named(spans, "trace_flush")]
    assert flushed_in == [2, 5, 9]
    for s in _named(spans, "trace_flush"):
        assert dispatch_end[s["step"]] <= s["t"] + 1e-6


def test_trace_dir_none_records_nothing(tmp_path, monkeypatch):
    """Tracing off: the loop runs the same call sites against the shared
    no-op, no Tracer is ever made and no file appears."""
    made = []
    monkeypatch.setattr(
        "ps_pytorch_tpu.trainer.Tracer",
        lambda *a, **kw: made.append(a) or pytest.fail("a Tracer was made"),
    )
    ds = make_synthetic("MNIST", train_size=128, test_size=32, seed=1)
    tcfg = TrainConfig(
        network="LeNet", dataset="MNIST", batch_size=8, max_steps=5,
        epochs=2, eval_freq=0, log_interval=2, save_checkpoints=False,
        train_dir=str(tmp_path / "models"),
    )
    trainer = Trainer(tcfg, PSConfig(num_workers=N), dataset=ds)
    assert trainer.tracer is NULL_TRACER
    out = trainer.train()
    assert np.isfinite(out["loss"]) and not made
    assert NULL_TRACER.drain() == [] and NULL_TRACER.flush() == 0
    assert not list(tmp_path.rglob("trace_*.jsonl"))
    # (the PSL004 sweep over the loop, the loader and obs/ is
    # test_instrumented_paths_stay_psl004_clean)


def test_child_spans_inherit_step_and_name_their_parent():
    t = Tracer("t")
    with t.span("step", step=7):
        with t.span("fetch"):
            with t.span("gather"):
                pass
        with t.span("dispatch", step=8):  # an explicit step wins
            pass
    with t.span("ckpt_save"):
        pass
    by = {s["name"]: s for s in t.drain()}
    assert by["fetch"]["step"] == 7 and by["fetch"]["parent"] == "step"
    assert by["gather"]["step"] == 7 and by["gather"]["parent"] == "fetch"
    assert by["dispatch"]["step"] == 8
    assert "parent" not in by["step"] and "step" not in by["ckpt_save"]
    assert [by[n]["depth"] for n in ("step", "fetch", "gather")] == [0, 1, 2]


def test_chrome_trace_places_spans_by_the_newest_clock_sync():
    """A long run's monotonic clock drifts off the wall clock; the merged
    timeline follows the stream's clock_sync records, not the header."""
    header = run_header("train")
    spans = [
        {"kind": "span", "name": "clock_sync", "t": 0.0, "dur": 0.0,
         "async": True, "wall_ns": int(header["t_wall"] * 1e9), "err_ns": 300},
        {"kind": "span", "name": "a", "t": 1.0, "dur": 0.1},
        {"kind": "span", "name": "clock_sync", "t": 100.0, "dur": 0.0,
         "async": True, "wall_ns": int((header["t_wall"] + 100.25) * 1e9),
         "err_ns": 300},
        {"kind": "span", "name": "b", "t": 101.0, "dur": 0.1},
    ]
    evs = {e["name"]: e for e in chrome_trace_events(
        header, spans, t0_wall=header["t_wall"]) if e.get("ph") == "X"}
    assert evs["a"]["ts"] == pytest.approx(1.0e6, abs=50)
    assert evs["b"]["ts"] == pytest.approx(101.25e6, abs=50)


def test_trace_report_splits_loop_time_by_the_phases_under_step(tmp_path):
    """Sleeps of 30 ms: under six xdist workers a sleep of 1 ms took two or
    three and a span's own overhead as long, so the shares of 1 ms spans fell
    under 0.3 (it passed alone); a sleep that overruns by 3 ms moves a share
    of these by a twentieth."""
    t = Tracer("train", path=str(tmp_path / "trace_train_p0.jsonl"))
    with t.span("build"):
        time.sleep(0.005)
    for n in (1, 2):
        with t.span("step", step=n):
            with t.span("fetch"):
                with t.span("gather"):
                    time.sleep(0.03)
            with t.span("dispatch"):
                time.sleep(0.03)
    t.flush()
    _, summary = trace_report.merge([str(tmp_path / "trace_train_p0.jsonl")], [])
    frac = summary["fraction_of_loop_walltime"]["train"]
    assert set(frac) == {"fetch", "dispatch", "step.self"}  # no build, no gather
    assert sum(frac.values()) == pytest.approx(1.0, abs=1e-3)
    assert frac["fetch"] > 0.3 and frac["dispatch"] > 0.3
    assert summary["nesting_ok"] and summary["phases"]["clock_sync"]["count"] == 2


def test_profile_window_captures_with_the_benchmarks_options(tmp_path, monkeypatch):
    """An operator's --profile-dir must not distort the loop it watches:
    no Python call tracing, host tracer at level 1 (PERF.md section 3)."""
    seen = {}
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d, profiler_options=None: seen.update(dir=d, opts=profiler_options))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: seen.update(stopped=True))
    pw = ProfileWindow(str(tmp_path / "prof"), start_step=1, num_steps=1)
    pw.before_step(1)
    pw.close()
    assert seen["opts"].python_tracer_level == 0
    assert seen["opts"].host_tracer_level == 1 and seen["stopped"]


def test_train_lm_trace_records_the_trainers_names(tmp_path):
    from ps_pytorch_tpu.cli import train_lm

    train_lm.main([
        "--dim", "32", "--depth", "1", "--heads", "2", "--seq-len", "32",
        "--vocab-size", "64", "--batch-size", "8", "--max-steps", "6",
        "--log-interval", "3", "--trace", str(tmp_path),
        "--metrics-file", str(tmp_path / "m.jsonl"),
    ])
    header, spans = _read_stream(tmp_path / "trace_train_lm_p0.jsonl")
    assert header["component"] == "train_lm"
    events = [json.loads(line) for line in open(tmp_path / "m.jsonl")]
    assert events[0]["run_id"] == header["run_id"]
    steps = {s["step"]: s for s in _named(spans, "step")}
    assert sorted(steps) == [1, 2, 3, 4, 5, 6]
    for name, at in (("fetch", [1, 2, 3, 4, 5, 6]), ("dispatch", [1, 2, 3, 4, 5, 6]),
                     ("log", [1, 3, 6]), ("metrics_write", [1, 3, 6]),
                     ("sync", [1, 1, 3, 3, 6, 6])):
        got = _named(spans, name)
        assert [s["step"] for s in got] == at, name
        assert all(s["parent"] == "step" for s in got)
        assert all(steps[s["step"]]["t"] <= s["t"] + 1e-6
                   and _end(s) <= _end(steps[s["step"]]) + 5e-6 for s in got)


@pytest.mark.parametrize("num_sp, path, t_att", [(1, "local", 32), (2, "ring", 16)])
def test_train_lm_flash_plan_says_which_attention_runs(tmp_path, num_sp, path, t_att):
    """The engagement counter of the one-member selection: the `flash_plan`
    instant carries models/transformer.attention_path's answer for the mesh
    the run built, and plans the tiles for the length that path attends."""
    from ps_pytorch_tpu.cli import train_lm
    from ps_pytorch_tpu.obs.schema import validate_event

    train_lm.main([
        "--dim", "32", "--depth", "1", "--heads", "2", "--seq-len", "32",
        "--vocab-size", "64", "--batch-size", "2", "--max-steps", "1",
        "--num-dp", "1", "--num-sp", str(num_sp), "--attention-impl", "flash",
        "--trace", str(tmp_path),
    ])
    _, spans = _read_stream(tmp_path / "trace_train_lm_p0.jsonl")
    (plan,) = _named(spans, "flash_plan")
    assert plan["attention_path"] == path and plan["seq_shards"] == num_sp
    assert plan["block_q"] == plan["block_k"] == t_att
    # one backward kernel, the whole head's float32 dq in VMEM (D = 16)
    assert (plan["bwd"], plan["dq_acc_bytes"]) == ("fused", t_att * 16 * 4)
    assert validate_event(dict(plan))["seq_shards"] == num_sp


def test_tracer_off_is_null(tmp_path):
    ds = make_synthetic("MNIST", train_size=64, test_size=32, seed=1)
    tcfg = TrainConfig(
        network="LeNet", dataset="MNIST", batch_size=8, max_steps=1,
        epochs=1, eval_freq=0, log_interval=1, save_checkpoints=False,
        train_dir=str(tmp_path / "models"),
    )
    trainer = Trainer(tcfg, PSConfig(num_workers=N), dataset=ds)
    assert trainer.tracer is NULL_TRACER


# --------------------------------------------------------- traced serve run

CFG_KW = dict(vocab_size=29, dim=32, depth=2, heads=4, max_seq_len=64)


def _engine(tracer=None, **kw):
    from ps_pytorch_tpu.models.transformer import (
        TransformerConfig,
        init_transformer,
    )

    cfg = TransformerConfig(**CFG_KW)
    params = init_transformer(cfg, jax.random.key(0))
    serve = ServeConfig(slots=3, max_len=48, max_prompt_len=12)
    return ServingEngine(cfg, params, serve, tracer=tracer, **kw)


def _reqs(shapes, arrivals=None):
    rng = np.random.RandomState(0)
    out = []
    for i, (p, n) in enumerate(shapes):
        out.append(Request(
            rid=i, prompt=rng.randint(0, 29, p).astype(np.int32),
            max_new_tokens=n,
            arrival_s=None if arrivals is None else arrivals[i],
        ))
    return out


def test_traced_serve_spans_and_request_lifecycle():
    tr = Tracer("serve")
    engine = _engine(tracer=tr)
    done = engine.decode_requests(_reqs([(4, 6), (6, 4), (3, 5), (5, 3)]))
    spans = tr.drain()
    names = {s["name"] for s in spans}
    assert {"admit_prefill", "decode_dispatch", "token_fetch", "evict",
            "request"} <= names
    reqs = {s["rid"]: s for s in spans if s["name"] == "request"}
    assert set(reqs) == {0, 1, 2, 3}
    for c in done:
        r = reqs[c.rid]
        assert r["new_tokens"] == len(c.tokens)
        # lifecycle span >= the decode tail it contains
        assert r["dur"] >= c.decode_s - 1e-6
    # ticks are numbered and int-typed
    ticks = [s["tick"] for s in spans if s["name"] == "decode_dispatch"]
    assert ticks == sorted(ticks) and all(isinstance(t, int) for t in ticks)


def test_ttft_decomposition_sums_to_ttft():
    """queue + prefill == latencies_s[0] (TTFT) exactly, and decode_s is
    the inter-token tail — measured on the same scheduler clock."""
    engine = _engine()
    # virtual arrivals far in the "past" force visible queueing when all
    # slots are busy: 5 requests into 3 slots
    reqs = _reqs([(4, 6)] * 5, arrivals=[0.0] * 5)
    for r in reqs:
        engine.submit(r)
    done = engine.decode_requests([])
    assert len(done) == 5
    for c in done:
        assert c.queue_s + c.prefill_s == pytest.approx(
            c.latencies_s[0], abs=1e-9
        )
        assert c.decode_s == pytest.approx(sum(c.latencies_s[1:]), abs=1e-6)
        assert c.queue_s >= 0 and c.prefill_s >= 0
    # the 2 overflow requests queued for >= one full decode run: their
    # queue component dominates the first-token latency
    queued = sorted(done, key=lambda c: c.queue_s)[-2:]
    for c in queued:
        assert c.queue_s > 0


def test_ttft_identity_holds_when_admission_precedes_arrival():
    """The injected-clock fast-forward path can admit BEFORE the nominal
    arrival; the decomposition must still sum to the first-token
    latency (base = max(admission, arrival))."""
    from ps_pytorch_tpu.serve import SlotScheduler

    sched = SlotScheduler(1, 64, 16)
    sched.submit(Request(
        rid=0, prompt=np.zeros(4, np.int32), max_new_tokens=2,
        arrival_s=10.0,
    ))
    ((slot, _),) = sched.admit(now_s=5.0)  # admitted before arrival
    sched.record_token(slot, 1, now_s=12.0)
    assert sched.record_token(slot, 2, now_s=13.0)
    c = sched.evict(slot, now_s=13.0)
    assert c.latencies_s[0] == pytest.approx(2.0)  # from ARRIVAL
    assert c.queue_s == 0.0
    assert c.queue_s + c.prefill_s == pytest.approx(c.latencies_s[0])
    assert c.decode_s == pytest.approx(1.0)


def test_closed_loop_queue_component_is_zero():
    engine = _engine()
    (c,) = engine.decode_requests(_reqs([(4, 4)]))
    assert c.queue_s == 0.0
    assert c.prefill_s == pytest.approx(c.latencies_s[0], abs=1e-9)


def test_rollover_drain_span_recorded(tmp_path):
    """The drain interval (staged -> swapped) lands as one explicit span
    carrying the step pair — the timeline shows WHY admission paused."""
    from ps_pytorch_tpu.models.transformer import (
        TransformerConfig,
        init_transformer,
    )
    from tests.test_serve import _write_lm_ckpt

    tr = Tracer("serve")
    cfg = TransformerConfig(**CFG_KW)
    _write_lm_ckpt(tmp_path, 1, init_transformer(cfg, jax.random.key(0)))
    serve = ServeConfig(slots=3, max_len=48, max_prompt_len=12)
    engine = ServingEngine.from_checkpoint(
        str(tmp_path), serve, step=1, tracer=tr
    )
    engine.submit(_reqs([(4, 8)])[0])
    for _ in range(3):
        engine.tick()
    _write_lm_ckpt(tmp_path, 2, init_transformer(cfg, jax.random.key(1)))
    assert engine.poll_rollover() == 2
    while not engine.scheduler.idle or engine.draining:
        engine.tick()
    spans = tr.drain()
    (drain,) = [s for s in spans if s["name"] == "rollover_drain"]
    (swap,) = [s for s in spans if s["name"] == "rollover_swap"]
    assert drain["from_step"] == 1 and drain["to_step"] == 2
    assert swap["from_step"] == 1 and swap["to_step"] == 2
    # the drain began at staging and ended at the swap
    assert drain["t"] + drain["dur"] <= swap["t"] + 1e-5


# ------------------------------------------------------------- trace_report

def test_trace_report_merges_streams_and_overlays(tmp_path, capsys):
    # two "processes" with offset wall bases + one metrics overlay
    t1 = Tracer("train", path=str(tmp_path / "trace_train_p0.jsonl"), pid=0)
    with t1.span("dispatch", step=1):
        time.sleep(0.002)
    t1.flush()
    t2 = Tracer("serve", path=str(tmp_path / "trace_serve_p0.jsonl"), pid=0)
    with t2.span("decode_dispatch", tick=1):
        pass
    t2.flush()
    m = tmp_path / "m.jsonl"
    append_metrics_line(str(m), {
        "kind": "grad_skip", "step": 2, "skipped_steps": 1, "skip_streak": 1,
    })

    out = tmp_path / "merged.json"
    sout = tmp_path / "summary.json"
    rc = trace_report.main([
        str(tmp_path), "--metrics", str(m), "--out", str(out),
        "--summary-out", str(sout),
        "--require-phases", "dispatch,decode_dispatch",
    ])
    assert rc == 0
    merged = json.loads(out.read_text())
    evs = merged["traceEvents"]
    pids = {e["pid"] for e in evs if e.get("ph") == "X"}
    assert len(pids) == 2  # same-pid headers land in distinct lanes
    assert any(e.get("ph") == "i" and e["name"] == "grad_skip" for e in evs)
    assert all(e["ts"] >= 0 for e in evs if "ts" in e)
    summary = json.loads(sout.read_text())
    assert summary["nesting_ok"]
    assert {"dispatch", "decode_dispatch"} <= set(summary["phases"])
    assert summary["n_overlay_events"] == 1
    comps = {s["component"] for s in summary["streams"]}
    assert comps == {"train", "serve"}


def test_trace_report_require_phases_gate(tmp_path, capsys):
    t = Tracer("train", path=str(tmp_path / "trace_t_p0.jsonl"))
    with t.span("fetch"):
        pass
    t.flush()
    rc = trace_report.main([
        str(tmp_path), "--require-phases", "fetch,ckpt_save",
    ])
    assert rc == 1  # ckpt_save missing
    assert "ckpt_save" in capsys.readouterr().err


def test_trace_report_nesting_detects_violation():
    # overlapping-but-not-nested spans must be called out
    assert trace_report.check_nesting([
        {"t": 0.0, "dur": 1.0},
        {"t": 0.5, "dur": 1.0},
    ]) == 1
    assert trace_report.check_nesting([
        {"t": 0.0, "dur": 1.0},
        {"t": 0.1, "dur": 0.2},
        {"t": 0.4, "dur": 0.5},
        {"t": 2.0, "dur": 1.0},
    ]) == 0


def test_trace_report_rejects_headerless_stream(tmp_path):
    p = tmp_path / "trace_bad.jsonl"
    p.write_text('{"kind": "span", "name": "x", "t": 0, "dur": 1}\n')
    with pytest.raises(SystemExit, match="run_header"):
        trace_report.merge([str(p)], [])
    empty = tmp_path / "trace_empty.jsonl"
    empty.write_text("")
    with pytest.raises(SystemExit, match="no run_header"):
        trace_report.merge([str(empty)], [])


def test_trace_report_segments_appended_reruns(tmp_path):
    """A --resume rerun with the same --trace dir APPENDS a second
    run_header + spans; each segment must rebase on its OWN clock, not
    the first header's (span offsets are per-run perf_counter epochs)."""
    p = tmp_path / "trace_train_p0.jsonl"
    t1 = Tracer("train", path=str(p))
    with t1.span("dispatch", step=1):
        pass
    t1.flush()
    t2 = Tracer("train", path=str(p))  # second run, same file
    with t2.span("dispatch", step=2):
        pass
    t2.flush()
    segs = trace_report.load_stream(str(p))
    assert [h["run_id"] for h, _ in segs] == [t1.run_id, t2.run_id]
    _, summary = trace_report.merge([str(p)], [])
    assert summary["phases"]["dispatch"]["count"] == 2
    assert len(summary["streams"]) == 2
    trace, _ = trace_report.merge([str(p)], [])
    spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    # run 2 merged at its own (later) wall time, not run 1's start
    s1 = next(e for e in spans if e["args"].get("step") == 1)
    s2 = next(e for e in spans if e["args"].get("step") == 2)
    want = (t2.header["t_wall"] - t1.header["t_wall"]) * 1e6
    assert s2["ts"] - s1["ts"] == pytest.approx(want, abs=1e4)


def test_trace_report_fractions_aggregate_across_hosts(tmp_path):
    """Two processes of one component: the walltime fractions must pool
    both hosts' spans (a straggler's sync share must weigh in), not be
    overwritten by the last-listed stream."""
    def _write_stream(pid, spans):
        path = tmp_path / f"trace_train_p{pid}.jsonl"
        with open(path, "w") as f:
            f.write(json.dumps(run_header("train", pid=pid)) + "\n")
            for name, t0, dur in spans:
                f.write(json.dumps({
                    "kind": "span", "name": name, "cat": "phase",
                    "t": t0, "dur": dur, "depth": 0,
                }) + "\n")

    _write_stream(0, [("dispatch", 0.0, 0.1)])
    _write_stream(1, [("dispatch", 0.0, 0.1), ("sync", 0.2, 0.3)])
    _, summary = trace_report.merge(sorted(
        str(x) for x in tmp_path.glob("trace_*.jsonl")
    ), [])
    frac = summary["fraction_of_loop_walltime"]["train"]
    # pooled: dispatch 0.2 of 0.5 total, sync 0.3 of 0.5
    assert frac["dispatch"] == pytest.approx(0.4)
    assert frac["sync"] == pytest.approx(0.6)


def test_trace_report_require_phases_fails_on_dropped_spans(
    tmp_path, capsys
):
    """A stream whose ring overflowed carries the spans_dropped meta
    marker; the smoke gate (--require-phases) must refuse it — every
    named phase being present proves nothing about a truncated
    timeline. Without the gate flag the summary still renders."""
    t = Tracer("train", path=str(tmp_path / "trace_t_p0.jsonl"), ring=2)
    for _ in range(5):
        with t.span("dispatch"):
            pass
    t.flush()
    rc = trace_report.main([str(tmp_path), "--require-phases", "dispatch"])
    assert rc == 1
    assert "spans_dropped" in capsys.readouterr().err
    assert trace_report.main([str(tmp_path)]) == 0
