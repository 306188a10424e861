"""The trace reduction: busy and idle union, time per kind of operation,
exposed collective time and gap attribution."""

import json
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).parent / "data"


def small():
    devices = {"/device:TPU:0": [
        ("copy_kernel.1", 0, 10, "kernel"), ("fusion.2", 5, 15, "op"),
        ("copy_kernel.1", 30, 40, "kernel"), ("all-to-all.3", 40, 60, "collective"),
        ("fusion.4", 50, 55, "op")]}
    spans = [("call", 0, 20, "call a"), ("call", 25, 70, "call b")]
    return tr.Trace(70e-9, devices, spans)


def test_union_and_overlap():
    assert tr.union([(5, 7), (0, 3), (2, 4), (7, 9)]) == [(0, 4), (5, 9)]
    assert tr.length([(0, 4), (5, 9)]) == 8
    assert tr.overlap([(0, 4), (5, 9)], [(3, 6), (8, 20)]) == 1 + 1 + 1


def test_busy_kernel_and_collective_time():
    t = small()
    assert t.busy_s() == pytest.approx(45e-9)
    assert t.kind_s("kernel") == pytest.approx(20e-9)
    assert t.kind_s("collective") == pytest.approx(20e-9)
    assert t.exposed_collective_s() == pytest.approx(15e-9)
    span_s, busy_s = t.busy_within(("call",))
    assert span_s == pytest.approx(65e-9) and busy_s == pytest.approx(45e-9)


def test_gaps_are_labelled_by_the_host_span():
    gaps = small().gaps()
    assert gaps == [["host between spans", pytest.approx(15e-9)],
                    ["call b", pytest.approx(10e-9)]]


def test_top_ops_merge_numbered_copies():
    assert small().top_ops()[0] == ["copy_kernel [kernel]", pytest.approx(20e-9)]
    # a Pallas kernel and an XLA op of one name are kept apart
    t = tr.Trace(1.0, {"/device:TPU:0": [("copy.1", 0, 4, "kernel"), ("copy", 4, 7, "op"),
                                         ("copy.1", 9, 13, "kernel")]}, [])
    assert t.top_ops() == [["copy [kernel]", pytest.approx(8e-9)],
                           ["copy [op]", pytest.approx(3e-9)]]


def test_op_names_and_kinds_from_hlo_text():
    text = ('%copy.1 = f32[2048,2048]{1,0:T(8,128)} custom-call(f32[2048,2048]{1,0:T(8,128)} '
            '%x.1), custom_call_target="tpu_custom_call"')
    assert tr.parse_op(text) == ("copy.1", "custom-call")
    loop = ("%while.15 = (s32[]{:T(128)}, bf16[16,512]{1,0:T(8,128)(2,1)}) "
            "while((s32[]{:T(128)}, bf16[16,512]{1,0}) %tuple.67), condition=%c, body=%b")
    assert tr.parse_op(loop) == ("while.15", "while")
    assert tr.parse_op("%all-to-all.3 = bf16[4,8]{1,0} all-to-all(bf16[4,8]{1,0} %p)") == (
        "all-to-all.3", "all-to-all")
    assert tr.parse_op("plain name") == ("plain name", "")
    assert [tr.op_kind(o) for o in ("custom-call", "all-to-all", "collective-permute-start",
                                    "while", "fusion")] == [
        "kernel", "collective", "collective", "control", "op"]


def test_json_round_trip():
    t = small()
    back = tr.Trace.from_json(t.to_json())
    assert back.busy_s() == t.busy_s() and back.gaps() == t.gaps()


def test_recorded_traces():
    """Traces recorded on the chip (``record_trace.py``): kernels found,
    busy within the window, every idle gap named."""
    for path in sorted(DATA.glob("*.json")):
        rec = json.loads(path.read_text())
        t = tr.Trace.from_json(json.dumps(rec["trace"]))
        assert 0 < t.busy_s() <= t.window_s, path.name
        assert t.kind_s("kernel") == pytest.approx(rec["expect"]["kernel_s"], rel=1e-9)
        assert t.busy_s() == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
        assert all(isinstance(label, str) and s > 0 for label, s in t.gaps())

