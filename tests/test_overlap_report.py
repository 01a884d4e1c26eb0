"""Unit coverage for the comm-overlap evidence analyzer (tools/overlap_report.py).

The analyzer's claims (async pairs overlapped by compute, payload bytes,
sync-collective positions) are exactly the artifacts quoted as component-#12
evidence, so the parsing is pinned here against synthetic scheduled-HLO text
shaped like what the TPU compiler emits (tuple types, /*index*/ comments,
long operand lists)."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import overlap_report as orp  # noqa: E402


def test_opcode_handles_tuple_types_and_comments():
    op, _ = orp._opcode(
        "  %all-reduce.1 = (f32[64]{0}, /*index=5*/f32[3,3,64,64]{3,2,1,0}) "
        "all-reduce(%fusion.9), channel_id=1, replica_groups={{0,1}}"
    )
    assert op == "all-reduce"
    op, _ = orp._opcode("  %p0 = f32[8,4]{1,0} parameter(0)")
    assert op == "parameter"
    assert orp._opcode("ENTRY %main {")[0] is None


def test_shape_bytes_sums_tuple_arrays():
    assert orp._shape_bytes("f32[3,3,64,64]{3,2,1,0}") == 3 * 3 * 64 * 64 * 4
    assert orp._shape_bytes("(bf16[128]{0}, s8[256]{0})") == 128 * 2 + 256
    assert orp._shape_bytes("pred[]") == 1  # scalar: empty dims


SYNTHETIC_HLO = """\
HloModule jit_step, is_scheduled=true

ENTRY %main (p0: f32[64,512]) -> f32[64,512] {
  %p0 = f32[64,512]{1,0} parameter(0)
  %fusion.1 = f32[64,512]{1,0} fusion(%p0), kind=kLoop
  %all-reduce-start.1 = (f32[64,512]{1,0}, f32[64,512]{1,0}) all-reduce-start(%fusion.1), channel_id=1
  %convolution.1 = f32[64,512]{1,0} convolution(%fusion.1, %p0)
  %fusion.2 = f32[64,512]{1,0} fusion(%convolution.1), kind=kLoop
  %all-reduce-done.1 = f32[64,512]{1,0} all-reduce-done(%all-reduce-start.1)
  %all-reduce.5 = f32[64,512]{1,0} all-reduce(%fusion.2), channel_id=2
  %fusion.3 = f32[64,512]{1,0} fusion(%all-reduce-done.1, %all-reduce.5)
  ROOT %copy.1 = f32[64,512]{1,0} copy(%fusion.3)
}
"""


def test_analyze_schedule_async_pair_and_sync():
    rep = orp.analyze_hlo_schedule(SYNTHETIC_HLO)
    assert rep["n_async"] == 1
    assert rep["n_sync"] == 1
    assert rep["unmatched_done"] == 0
    a = next(c for c in rep["collectives"] if c["async"])
    # two compute ops (convolution.1, fusion.2) sit between start and done
    assert a["compute_ops_between"] == 2
    assert a["overlapped"] is True
    # payload from the -done RESULT type, not the -start (input,output) tuple
    assert a["bytes"] == 64 * 512 * 4
    s = next(c for c in rep["collectives"] if not c["async"])
    assert s["kind"] == "all-reduce"
    assert s["compute_ops_after"] == 1  # fusion.3


def test_analyze_schedule_counts_unmatched_done():
    # -done whose operand regex can't resolve to a seen -start
    hlo = """\
ENTRY %main () -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %all-reduce-done.9 = f32[4]{0} all-reduce-done(%ghost.1)
  ROOT %copy.1 = f32[4]{0} copy(%x)
}
"""
    rep = orp.analyze_hlo_schedule(hlo)
    assert rep["unmatched_done"] == 1
    assert rep["collectives"] == []


def test_analyze_schedule_ignores_async_copy_pairs():
    # XLA emits copy-start/copy-done for async D2D copies; they move no
    # collective traffic and must not inflate the overlap evidence
    hlo = """\
ENTRY %main () -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %copy-start.1 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%x)
  %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop
  %copy-done.1 = f32[4]{0} copy-done(%copy-start.1)
  ROOT %copy.9 = f32[4]{0} copy(%fusion.1)
}
"""
    rep = orp.analyze_hlo_schedule(hlo)
    assert rep["n_async"] == 0
    assert rep["collectives"] == []
    assert rep["unmatched_done"] == 0


def test_analyze_schedule_generic_async_wrapper():
    # collectives without dedicated -start ops ship as generic async-start
    # wrappers naming the wrapped op; these must still count as comm, and
    # their replica_groups — printed on the WRAPPED instruction inside its
    # own computation, not the -start line — must still be resolved
    hlo = """\
%wrapped_reduce_scatter.3 (p.1: f32[8]) -> f32[4] {
  %p.1 = f32[8]{0} parameter(0)
  ROOT %reduce-scatter.9 = f32[4]{0} reduce-scatter(%p.1), replica_groups={{0,1},{2,3}}, dimensions={0}
}

ENTRY %main () -> f32[4] {
  %x = f32[8]{0} parameter(0)
  %async-start.1 = ((f32[8]{0}), f32[4]{0}, u32[]) async-start(%x), calls=%wrapped_reduce_scatter.3
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop
  %async-done.1 = f32[4]{0} async-done(%async-start.1)
  ROOT %copy.1 = f32[4]{0} copy(%async-done.1)
}
"""
    rep = orp.analyze_hlo_schedule(hlo)
    assert rep["n_async"] == 1
    a = rep["collectives"][0]
    assert a["kind"] == "reduce-scatter"
    assert a["compute_ops_between"] == 1
    assert a["bytes"] == 4 * 4  # -done result f32[4]
    assert a["groups"] == [[0, 1], [2, 3]]


def test_replica_groups_explicit_and_iota():
    assert orp._replica_groups(
        "all-reduce(%x), replica_groups={{0,1,2,3},{4,5,6,7}}, channel_id=1"
    ) == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert orp._replica_groups(
        "all-reduce(%x), replica_groups=[4,8]<=[32]"
    ) == [list(range(i * 8, (i + 1) * 8)) for i in range(4)]
    # transposed iota: reshape iota(32) to (4,8), T(1,0) -> rows stride 8
    got = orp._replica_groups(
        "all-to-all(%x), replica_groups=[8,4]<=[4,8]T(1,0)"
    )
    assert got[0] == [0, 8, 16, 24] and got[7] == [7, 15, 23, 31]
    assert orp._replica_groups("all-reduce(%x), channel_id=1") is None


def test_analyze_schedule_no_entry():
    assert "error" in orp.analyze_hlo_schedule("HloModule empty")


def _device_ops(*events):
    """Device ops as tools/overlap_report.capture_spans hands them on: an
    event is named by its instruction, times in microseconds."""
    return [{"pid": "/device:TPU:0", "name": n, "ts": ts, "dur": dur} for n, ts, dur in events]


def test_run_trace_excludes_infra_events_from_compute():
    """Only real op events count as overlapped compute (ADVICE r03): an
    infra span (barrier) fully covering the collective must not inflate
    overlap_fraction; the name breakdowns make the classification
    auditable. Which bucket an op belongs to is the census's to say (PR 35:
    a device event carries its instruction's name, never a scope)."""
    spans = _device_ops(
        ("all-reduce.1", 100, 100),
        ("fusion.42", 150, 100),        # overlaps the back half of the collective only
        # infra event spans the WHOLE collective; counting it would make
        # overlap_fraction 1.0
        ("barrier-wait", 90, 200),
        ("fusion.7", 300, 50),          # bucket 4096's own update: no overlap of its reduce
    )
    census = {
        "all-reduce.1": ["update", "grad_reduce/bucket_reduce_o4096", "collective", [], ""],
        "fusion.42": ["update", "update/bucket_update_o0", "other", [], ""],
        "fusion.7": ["update", "update/bucket_update_o4096", "other", [], ""],
    }
    rep = orp.analyze_trace(spans, census)
    assert rep["n_collective_events"] == 1
    assert rep["n_compute_events"] == 2
    assert rep["n_skipped_events"] == 1
    assert rep["overlap_fraction"] == 0.5  # fusion half, not barrier whole
    assert [e["name"] for e in rep["top_compute_events"]] == ["fusion.42", "fusion.7"]
    assert [e["name"] for e in rep["top_skipped_events"]] == ["barrier-wait"]
    # bucket 4096's reduce is overlapped by ANOTHER bucket's update
    assert rep["per_bucket"] == [{"bucket_offset": 4096, "ms": 0.1, "overlapped_ms": 0.05,
                                  "overlap_fraction": 0.5}]
    # without a census the same capture has no buckets to tell apart
    assert orp.analyze_trace(spans, {})["per_bucket"] is None


def test_run_trace_prefix_anchored_compute_classifier():
    """Op classification is anchored to the HLO op-name prefix, not free
    substring search (ADVICE r04): copy-start/copy-done DMA bookkeeping and
    address-computation thunks contain 'copy'/'dynamic' as substrings but
    must land in the skipped audit list; the exact 'copy' op and fusion
    kinds (loop_fusion) are real compute."""
    spans = _device_ops(
        ("all-reduce.1", 100, 100),
        # infra spans whose names would substring-match the old classifier;
        # each fully covers the collective, so any misclassification shows up
        # directly in overlap_fraction
        ("copy-start.2", 90, 200), ("copy-done.2", 90, 200),
        ("dynamic-address-computation.1", 90, 200),
        # real compute overlapping only the back half
        ("copy.3", 150, 25), ("loop_fusion.8", 175, 25),
    )
    rep = orp.analyze_trace(spans, {})
    assert rep["n_compute_events"] == 2
    assert rep["n_skipped_events"] == 3
    # copy.3 + loop_fusion.8 merge to [150,200] = half the collective
    assert rep["overlap_fraction"] == 0.5
    skipped = {e["name"] for e in rep["top_skipped_events"]}
    assert skipped == {"copy-start.2", "copy-done.2",
                       "dynamic-address-computation.1"}
