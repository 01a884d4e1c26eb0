"""Each reducer kind on a small trace: one made by hand, whose answers are
known exactly, and the recorded ones in data/ (a few steps of each cell on
the v5e, normalised by reducers/trace.py), whose answers are pinned."""

import glob
import json
import os

import pytest

from benchmark import reducers, spec
from benchmark.reducers import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")

# two devices, two steps of 10 ms: compute 0-6, all-reduce 5-8 (1 ms under
# compute... on one stream: here 6-8), quantize kernel 8-9, idle 9-10
HAND = {"devices": {
    "/device:TPU:0": [["fusion.1", 0.000, 0.006], ["all-reduce.3", 0.006, 0.002],
                      ["ps_quantize_2d", 0.008, 0.001],
                      ["fusion.1", 0.010, 0.006], ["all-reduce.3", 0.016, 0.002],
                      ["ps_quantize_2d", 0.018, 0.001]],
    "/device:TPU:1": [["while.2", 0.000, 0.019],
                      ["fusion.1", 0.000, 0.005], ["all-reduce.3", 0.004, 0.004],
                      ["ps_quantize_2d", 0.008, 0.001],
                      ["fusion.1", 0.010, 0.005], ["all-reduce.3", 0.014, 0.004],
                      ["ps_quantize_2d", 0.018, 0.001]],
}, "host": []}


class _Cell:
    config = {"parameters": 1_000_000}
    traffic = {}


def _ev(trace=HAND, **kw):
    return {"trace": trace, "steps_traced": 2, "cell": _Cell,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, **kw}


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.total([(0, 2), (1, 3), (5, 6)]) == 4
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4)], [(0, 4)]) == []


def test_scope_time_is_a_union_per_device_averaged_per_step():
    # all ops (the container `while` is left out): 9 ms + 9 ms a step
    assert reducers.reduce("scope_time", {}, _ev()) == pytest.approx(9.0)
    got = reducers.reduce("scope_time", {"pattern": "all-reduce"}, _ev())
    assert got == pytest.approx((2.0 + 4.0) / 2)
    assert reducers.reduce("scope_time", {"pattern": "ps_flash"}, _ev()) is None


def test_exposed_time_leaves_out_what_compute_covers():
    # device 0: 2 ms exposed a step; device 1: all-reduce 4-8 with compute
    # until 5: 3 ms exposed
    got = reducers.reduce("exposed_time", {"pattern": "all-reduce"}, _ev())
    assert got == pytest.approx((2.0 + 3.0) / 2)


def test_idle_share_and_busy():
    busy, window = tr.busy_and_window(HAND)
    assert window == pytest.approx(0.019) and busy == pytest.approx(0.018)
    assert reducers.reduce("idle_share", {}, _ev()) == pytest.approx(100 * (1 - 18 / 19))


def _capture(blocks, k, step=0.010, stall=0.050, boundary=0.002):
    """A capture of `blocks` blocks of `k` runs of `jit_step` (one op each,
    9 ms of every 10): a gap of `boundary` between blocks and the
    profiler's stall right after each block's first run; one run of another
    program before them."""
    runs, ops, t = [["jit_init", 0.0, 0.004]], [["fusion.0", 0.0, 0.004]], 0.005
    for b in range(blocks):
        for i in range(k):
            runs.append(["jit_step", t, step])
            ops.append(["fusion.1", t, step - 0.001])
            t += step + (stall if i == 0 else 0.0)
        t += boundary
    return {"devices": {"/device:TPU:0": ops}, "modules": {"/device:TPU:0": runs},
            "async": {}, "host": []}


@pytest.mark.parametrize("blocks, k, steps", [(2, 16, 15), (3, 3, 5), (2, 4, 3)])
def test_trim_keeps_the_block_boundary_and_leaves_out_the_profilers_stall(blocks, k, steps):
    cut, got = tr.trim(_capture(blocks, k), blocks, k)
    assert got == steps == len(cut["modules"]["/device:TPU:0"])
    busy, window = tr.busy_and_window(cut)
    assert busy == pytest.approx(steps * 0.009)
    # the gaps inside: every block boundary between the first block and the
    # last, the stall of each block strictly between them, no other
    inner = blocks - 2
    assert window == pytest.approx(steps * 0.010 + (blocks - 1) * 0.002 + inner * 0.050)
    gaps = tr.run_gaps_ms(_capture(blocks, k))["/device:TPU:0"]
    assert len(gaps) == blocks * k - 1 and max(gaps) == pytest.approx(50.0)


def test_trim_without_module_events_returns_the_trace():
    assert tr.trim(HAND, 2, 16) == (HAND, 0)


def test_roofline_share_is_least_time_over_kernel_time():
    got = reducers.reduce("roofline", {"pattern": "ps_quantize_2d", "module": "wire",
                                       "work": "ps_quantize_step"}, _ev())
    assert got == pytest.approx(100 * (5e6 / 819e9) / 1e-3)


def test_span_time_counter_memory():
    spans = [{"name": "fetch", "step": s, "dur": d, "t_abs": 10.0 + s}
             for s, d in ((1, 0.5), (2, 0.001), (3, 0.003), (4, 0.002))]
    ev = _ev(spans=spans, window_t0=11.5)    # step 1 is before the window
    assert reducers.reduce("span_time", {"spans": ["fetch"]}, ev) == pytest.approx(2.0)
    assert reducers.reduce("span_time", {"spans": ["evict"]}, ev) is None
    assert reducers.reduce("counter", {"counter": "n"}, {"counters": {"n": 3}}) == 3
    assert reducers.reduce("counter", {"counter": "m"}, {"counters": {"n": 3}}) is None
    assert reducers.reduce("memory_peak", {}, {"memory_peak_bytes": 2 ** 31}) == 2.0


def test_breakdown_names_top_ops_and_gaps():
    b = tr.breakdown(HAND, [["sync", 0.0085, 0.002]])
    assert b["device_ops"][0][0] == "fusion.1"
    assert all(name != "while.2" for name, _ in b["device_ops"])
    assert b["idle_gaps"] == [["sync", pytest.approx(0.001)]]


def test_breakdown_sums_the_gaps_it_does_not_name(monkeypatch):
    monkeypatch.setattr(tr, "NAMED_GAPS", 1)
    b = tr.breakdown(HAND, [["sync", 0.0085, 0.002]])   # device 0: gaps at 9-10 ms only
    assert b["idle_gaps"] == [["sync", pytest.approx(0.001)]]
    two = {"devices": {"d": [["a", 0.0, 1.0], ["a", 1.5, 1.0], ["a", 2.6, 1.0]]}, "host": []}
    assert tr.breakdown(two)["idle_gaps"] == [["outside_any_span", pytest.approx(0.5)],
                                              ["shorter_gaps", pytest.approx(0.1)]]


def test_no_trace_means_no_metric():
    for kind in ("scope_time", "exposed_time", "idle_share"):
        assert reducers.reduce(kind, {"pattern": "x"}, _ev(trace={"devices": {}, "host": []})) is None


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(DATA, "*.trace.json.gz"))))
def test_recorded_trace_reads_as_pinned(path):
    """The end of one block and the start of the next, as recorded on the
    chip: `trim` keeps the boundary between them and leaves out the stall
    after the next block's first run, and every reducer reads as pinned."""
    cell = spec.load_cell(os.path.basename(path).split(".")[0])
    with open(path.replace(".trace.json.gz", ".expected.json")) as f:
        expected = json.load(f)
    k = expected["block_steps"]
    trace, steps = tr.trim(tr.load_json(path), expected["blocks"], k)
    assert steps == expected["steps_traced"] == k - tr.BOUNDARY_RUNS + 1
    busy, window = tr.busy_and_window(trace)
    assert busy == pytest.approx(expected["busy_s"], rel=1e-9)
    assert window == pytest.approx(expected["window_s"], rel=1e-9)
    for dev, gaps in expected["gaps_between_runs_ms"].items():
        runs = trace["modules"][dev]
        assert len(gaps) == 2 * k - 1
        inside = gaps[tr.BOUNDARY_RUNS:k]        # the gaps between the runs kept
        assert gaps[k - 1] in inside             # the block boundary is one of them
        w = trace["windows"][dev]
        assert w[1] - w[0] == pytest.approx(
            sum(r[2] for r in runs) + 1e-3 * sum(inside), abs=2e-6)
    ev = {"trace": trace, "steps_traced": steps, "cell": cell,
          "peaks": spec.load_peaks("TPU v5 lite")}
    for m in cell.per_layer:
        if m["source"] != "device_trace":
            continue
        got = reducers.reduce(m["kind"], m.get("args", {}), ev)
        assert got == pytest.approx(expected["metrics"][m["name"]], rel=1e-9), m["name"]
        if m["name"].endswith("_roofline"):
            assert 0 < got <= 100
