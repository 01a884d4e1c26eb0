"""The reducers that read the program's own host spans (PR 24): on a small
synthetic span stream whose answers are known exactly, and on one traced run
of cell 1 recorded on the v5e (data/resnet18_b2048_1chip.join.json.gz: the
trainer's span stream beside the capture's runs, Execute events and busy
intervals; tools/record_spans.py and tools/make_join_fixture.py)."""

import json
import os

import pytest

from benchmark import reducers, spec
from benchmark.reducers import idle_named
from benchmark.reducers import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
# a capture with device ops and no spans beside it
OPS_ONLY = {"devices": {"/device:TPU:0": [["fusion.1", 0.000, 0.006], ["fusion.1", 0.010, 0.006]]},
            "host": []}

# ------------------------------------------------- the program's host spans

K = 4           # block_steps of the synthetic stream
STEP_S = 0.010  # the device's step
T0 = 100.0      # host clock at the stream's start


class _PsCell:
    traffic = {"block_steps": K}


def _stream(blocks=5, gather=0.0030, h2d=0.0005, report=0.0012, sync_shift=0.0):
    """A trainer's span stream over `blocks` blocks of K steps as obs/trace.py
    writes it, on a host that runs ahead of a device taking STEP_S a step:
    each iteration `step` > `fetch` (`gather`, `h2d`) + `dispatch` +
    `stop_check`; the last of a block also `window_close` > `sync`, `log`,
    `metrics_write`, `guard`. Returns (spans, ends of each window_close's
    sync, device-idle boundaries): a boundary lasts `report` + gather + h2d
    + the dispatch's 0.2 ms."""
    spans, t, device_free, sync_ends = [], T0, T0, []

    def add(name, t0, dur, step, parent=None, **kw):
        spans.append({"kind": "span", "name": name, "t_abs": t0, "dur": dur, "step": step,
                      **({"parent": parent} if parent else {}), **kw})

    spans.append({"kind": "span", "name": "build", "t_abs": T0 - 7.5, "dur": 7.25})
    for n in range(1, blocks * K + 1):
        s0 = t
        add("gather", t, gather, n, "fetch")
        add("h2d", t + gather, h2d, n, "fetch", bytes=6291456)
        add("fetch", t, gather + h2d, n, "step")
        t += gather + h2d
        add("dispatch", t, 0.0002, n, "step")
        t += 0.0002
        device_free = max(device_free, t) + STEP_S
        if n % K == 0:
            w0 = t
            add("sync", t, device_free - t, n, "window_close")
            t = device_free
            sync_ends.append(t)
            add("log", t, report / 2, n, "window_close")
            add("metrics_write", t + report / 2, report / 4, n, "window_close")
            add("guard", t + 3 * report / 4, report / 8, n, "window_close")
            t += 7 * report / 8
            add("window_close", w0, t - w0, n, "step", block=K)
        add("stop_check", t, report / 8 if n % K == 0 else 0.0, n, "step")
        t += report / 8 if n % K == 0 else 0.0
        add("step", s0, t - s0, n)
    spans.append({"kind": "span", "name": "clock_sync", "t_abs": T0 + sync_shift, "dur": 0.0,
                  "async": True, "wall_ns": int((1.7e9 + T0) * 1e9), "err_ns": 400})
    return spans, sync_ends


def _host_ev(spans, window_t0, **kw):
    return {"spans": spans, "window_t0": window_t0, "cell": _PsCell, **kw}


def _seven(ev):
    out = {}
    for path in spec.all_layer_metric_files():
        name = os.path.basename(path)[:-5]
        with open(path) as f:
            m = json.load(f)
        if m["kind"] in ("boundary_time", "span_duration", "span_share", "idle_named"):
            out[name] = reducers.reduce(m["kind"], m["args"], ev)
    return out


def test_boundary_parts_sum_to_the_whole_and_read_the_settled_blocks_only():
    spans, sync_ends = _stream()
    # window_t0 is read inside the log line that closes block 3; the spans of
    # blocks 4 and 5 (the traced ones) are made ten times slower and must
    # not be read
    for s in spans:
        if s.get("step", 0) > 3 * K and s["name"] in ("fetch", "gather", "step"):
            s["dur"] *= 10
    got = _seven(_host_ev(spans, sync_ends[2] + 0.0003))
    whole, inp, rep = (got[f"ps_boundary_{p}_ms"] for p in ("host", "input", "report"))
    assert whole == pytest.approx(1.2 + 3.0 + 0.5 + 0.2, abs=1e-6)
    assert inp == pytest.approx(3.5, abs=1e-6) and rep == pytest.approx(1.4, abs=1e-6)
    assert inp + rep == pytest.approx(whole, abs=0.05)
    # an iteration that closes no window: fetch + dispatch
    assert got["ps_host_step_ms"] == pytest.approx(3.7, abs=1e-6)
    # a block: K device steps and one boundary; the host waits in sync for
    # all of it but its own K iterations
    block = K * STEP_S + 4.9e-3
    assert got["ps_host_wait_pct"] == pytest.approx(
        100 * (block - 4.9e-3 - (K - 1) * 3.7e-3) / block, abs=1e-6)
    assert got["ps_build_s"] == pytest.approx(7.25)


@pytest.mark.parametrize("drop", ["window_close", "all"])
def test_no_spans_or_no_window_close_means_no_metric(drop):
    spans, sync_ends = _stream()
    spans = [] if drop == "all" else [s for s in spans if s["name"] not in (drop, "build")]
    got = _seven(_host_ev(spans, sync_ends[2] + 0.0003, trace=OPS_ONLY))
    assert got and all(v is None for v in got.values()), got


def test_a_partial_block_is_not_read():
    spans, sync_ends = _stream()
    for s in spans:
        if s["name"] == "window_close" and s["step"] == 2 * K:
            s["block"] = K - 1
    from benchmark.reducers import host_spans as hs
    blocks = hs.settled_blocks(_host_ev(spans, sync_ends[2] + 0.0003))
    assert [(a["step"], b["step"]) for a, b in blocks] == [(2 * K, 3 * K)]


def _joined(spans, sync_ends, clock="from_capture_start", traced=(3, 5)):
    """The capture a profiler started at window_t0 (the close of block
    `traced[0]`) would hold of the stream: one Execute event inside every
    dispatch span, one run of `jit_step` per step with one op, idle from
    each block's drain to the next dispatch; trimmed as `trim` does."""
    t_cap = sync_ends[traced[0] - 1] + 0.0004       # host clock when the capture started
    zero = t_cap if clock == "from_capture_start" else -1.7e9
    host, runs, ops, free = [], [], [], 0.0
    for s in sorted((s for s in spans if s["name"] == "dispatch"), key=lambda s: s["t_abs"]):
        if not traced[0] * K < s["step"] <= traced[1] * K:
            continue
        a = s["t_abs"] - zero
        host.append([idle_named.EXECUTE, a + 2e-5, s["dur"] - 5e-5])
        host.append(["Wait for donation holds", a + 3e-5, 1e-5])
        start = max(free, a + s["dur"])
        runs.append(["jit_step", start, STEP_S])
        ops.append(["fusion.1", start, STEP_S])
        free = start + STEP_S
    capture = {"devices": {"/device:TPU:0": ops}, "modules": {"/device:TPU:0": runs},
               "async": {}, "host": host}
    return tr.trim(capture, traced[1] - traced[0], K)



@pytest.mark.parametrize("clock", ["from_capture_start", "wall"])
def test_idle_is_named_by_the_span_over_each_gaps_middle(clock, capsys):
    spans, sync_ends = _stream()
    trace, steps = _joined(spans, sync_ends, clock)
    ev = _host_ev(spans, sync_ends[2] + 0.0003, trace=trace, steps_traced=steps)
    got = reducers.reduce("idle_named", {}, ev)
    line = json.loads(capsys.readouterr().out.split(" ", 2)[2])
    assert line["join_ok"] and line["capture_clock"] == clock
    assert line["dispatch_holds_one_execute_pct"] == 100.0
    assert line["run_starts_after_its_dispatch_pct"] == 100.0
    # the stretch holds one boundary: 4.9 ms idle, its middle under `fetch`'s
    # gather; split at the spans' edges every part has its name
    assert line["idle_ms"] == pytest.approx(4.9, abs=1e-3)
    assert got == pytest.approx(100.0)
    # (to the slack of the join: an Execute event sits 20 and 30 us inside
    # its dispatch span, so the offset is known to 25 us)
    split = line["idle_ms_by_span_split_at_span_edges"]
    assert split["gather"] == pytest.approx(3.0, abs=0.03)
    assert split["log"] == pytest.approx(0.6, abs=0.03)
    assert split["dispatch"] == pytest.approx(0.2, abs=0.03)
    assert sum(split.values()) == pytest.approx(4.9, abs=1e-3)


def test_a_clock_sync_shifted_by_5_ms_fails_the_join_loudly(capsys):
    spans, sync_ends = _stream(sync_shift=0.005)
    trace, steps = _joined(spans, sync_ends, "wall")
    ev = _host_ev(spans, sync_ends[2] + 0.0003, trace=trace, steps_traced=steps)
    assert reducers.reduce("idle_named", {}, ev) is None        # left out, not wrong
    line = json.loads(capsys.readouterr().out.split(" ", 2)[2])
    assert line["join_ok"] is False and line["dispatch_holds_one_execute_pct"] < 99
    assert "idle_ms" not in line


def test_spans_that_do_not_line_up_with_the_capture_fail_the_join(capsys):
    """The capture counts from its own start: no one offset puts every
    Execute event inside the dispatch span of its ordinal once the spans of
    the second traced block are 5 ms late."""
    spans, sync_ends = _stream()
    trace, steps = _joined(spans, sync_ends)
    for s in spans:
        if s.get("step", 0) > 4 * K:
            s["t_abs"] += 0.005
    ev = _host_ev(spans, sync_ends[2] + 0.0003, trace=trace, steps_traced=steps)
    assert reducers.reduce("idle_named", {}, ev) is None
    line = json.loads(capsys.readouterr().out.split(" ", 2)[2])
    assert line["join_ok"] is False and line["offset_slack_us"] < 0


# ------------------------------------- a recorded run: spans beside a capture

def _join_fixture():
    import gzip
    import importlib.util

    path = os.path.join(os.path.dirname(DATA), "..", "tools", "make_join_fixture.py")
    tool_spec = importlib.util.spec_from_file_location("make_join_fixture", path)
    tool = importlib.util.module_from_spec(tool_spec)
    tool_spec.loader.exec_module(tool)
    with gzip.open(os.path.join(DATA, "resnet18_b2048_1chip.join.json.gz"), "rt") as f:
        return tool, json.load(f), spec.load_cell("resnet18_b2048_1chip")


def test_recorded_spans_and_capture_read_as_pinned():
    """One traced run of cell 1 on the v5e (tools/record_spans.py, cut by
    tools/make_join_fixture.py): the trainer's own span stream beside the
    capture's runs, Execute events and busy intervals."""
    tool, fixture, cell = _join_fixture()
    got = tool.read(fixture, cell)
    want = fixture["expected"]
    assert set(got["metrics"]) == set(want["metrics"]) and len(got["metrics"]) == 7
    for name, value in want["metrics"].items():
        assert got["metrics"][name] == pytest.approx(value, rel=1e-9), name
    m = got["metrics"]
    assert m["ps_boundary_input_ms"] + m["ps_boundary_report_ms"] == pytest.approx(
        m["ps_boundary_host_ms"], abs=0.05)
    assert 5 < m["ps_boundary_host_ms"] < 15 and 3 < m["ps_host_step_ms"] < 10
    assert 85 < m["ps_host_wait_pct"] < 99 and 1 < m["ps_build_s"] < 10
    line = got["line"]
    assert line["join_ok"] and line["capture_clock"] == "from_capture_start"
    assert line["dispatch_holds_one_execute_pct"] == 100.0
    assert line["run_starts_after_its_dispatch_pct"] == 100.0
    assert 0 < line["offset_slack_us"] < 500
    # the capture was started inside the log line that read window_t0
    assert 0 < line["capture_started_after_window_t0_ms"] < 5
    assert m["ps_idle_named_pct"] >= 90
    split = line["idle_ms_by_span_split_at_span_edges"]
    assert sum(split.values()) == pytest.approx(line["idle_ms"], abs=1e-3)
    assert max(split, key=split.get) == "gather"


def test_recorded_blocks_after_window_t0_are_not_read():
    """The traced blocks' host spans are twice as slow under the profiler
    (gather 11 ms against 5); only blocks ending at or before window_t0
    reach the host-clock metrics."""
    tool, fixture, cell = _join_fixture()
    before = tool.read(fixture, cell)["metrics"]
    t0 = fixture["window_t0"]
    late = [s for s in fixture["spans"] if s["t_abs"] > t0 and s["name"] == "gather"]
    early = [s for s in fixture["spans"] if s["t_abs"] < t0 and s["name"] == "gather"]
    med = lambda xs: sorted(s["dur"] for s in xs)[len(xs) // 2]
    assert med(late) > 1.5 * med(early)
    fixture["spans"] = [s for s in fixture["spans"] if s["t_abs"] <= t0
                        or s["name"] in ("dispatch", "step")]
    after = tool.read(fixture, cell)["metrics"]
    host_clock = [n for n in before if n != "ps_idle_named_pct"]
    assert {n: after[n] for n in host_clock} == {n: before[n] for n in host_clock}


def test_recorded_capture_against_a_parents_stream_leaves_the_new_metrics_out():
    """The driver lays this PR's benchmark files over the parent commit too:
    its trainer records fetch, h2d, dispatch, sync and guard only."""
    tool, fixture, cell = _join_fixture()
    old = ("fetch", "h2d", "dispatch", "sync", "guard")
    fixture["spans"] = [{k: v for k, v in s.items() if k != "parent"}
                        for s in fixture["spans"] if s["name"] in old]
    got = tool.read(fixture, cell)
    assert all(got["metrics"][n] is None for n in got["metrics"] if n != "ps_idle_named_pct")
    # the join needs dispatch spans only, so the idle time still finds its names
    assert got["line"]["join_ok"] and got["metrics"]["ps_idle_named_pct"] > 90
