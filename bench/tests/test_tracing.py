"""The trace reduction: busy union, idle share and named idle gaps."""
from pathlib import Path

import pytest

from benchlib import tracing

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "loop_trace.xplane.pb"


def test_union_merges_overlaps_and_nesting():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (6, 6.5), (8, 9)]) == [
        [0, 3], [5, 7], [8, 9]]


def test_gaps_are_the_complement_inside_the_window():
    busy = [[0, 3], [5, 7], [8, 12]]
    assert tracing.gaps(tracing.clip(busy, 1, 10), 1, 10) == [[3, 5], [7, 8]]
    assert tracing.gaps([], 0, 4) == [[0, 4]]


def synthetic():
    # Two requests from 0 to 100 ns and 110 to 200 ns; device ops in them,
    # one op nested in another, one op outside the window.
    ops = [("a", 10, 30), ("b", 20, 25), ("c", 50, 60), ("a", 150, 170),
           ("late", 300, 400)]
    annotations = [("request", 0, 100), ("request", 110, 200)]
    return tracing.Trace({"/device:TPU:0": ops}, annotations)


def test_summary_of_a_synthetic_trace():
    s = synthetic().summary()
    assert s["window_s"] == pytest.approx(200e-9)
    assert s["busy_s"] == pytest.approx(50e-9)          # 20 + 10 + 20
    assert s["idle_pct"] == pytest.approx(75.0)
    assert dict(s["device_ops"])["a"] == pytest.approx(40e-9)
    assert "late" not in dict(s["device_ops"])
    idle = dict(s["idle_gaps"])
    assert idle["request"] == pytest.approx(140e-9)     # 10+20+40 and 40+30
    assert idle["outside"] == pytest.approx(10e-9)      # between the requests
    assert sum(idle.values()) == pytest.approx(150e-9)


def test_gaps_are_named_by_the_innermost_program_span():
    t = synthetic()
    spans = [("prepare", 0, 50, 1), ("map", 30, 45, 2), ("measure", 160, 200, 1)]
    idle = dict(t.summary(spans)["idle_gaps"])
    assert idle["map"] == pytest.approx(15e-9)           # 30-45
    assert idle["prepare"] == pytest.approx(15e-9)       # 0-10 and 45-50
    assert idle["measure"] == pytest.approx(30e-9)       # 170-200
    assert idle["request"] == pytest.approx(80e-9)       # 60-100, 110-150


def test_clock_offset_from_the_harness_marks():
    t = synthetic()
    assert t.clock_offset([("request", -1000), ("request", -890)]) == 1000


def test_recorded_chip_trace():
    t = tracing.Trace.load(str(FIXTURE), labels=("request",))
    assert list(t.device_ops) == ["/device:TPU:0"]
    assert [a[0] for a in t.annotations] == ["request", "request"]
    s = t.summary()
    lo, hi = t.window()
    ops = t.device_ops["/device:TPU:0"]
    # Busy time by a second, plain count: sweep the sorted event edges.
    edges = sorted([(max(s0, lo), 1) for _, s0, e in ops if e > lo and s0 < hi]
                   + [(min(e, hi), -1) for _, s0, e in ops if e > lo and s0 < hi])
    depth, busy, last = 0, 0.0, None
    for x, d in edges:
        if depth > 0:
            busy += x - last
        depth += d
        last = x
    assert s["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["idle_pct"] == pytest.approx(100 * (1 - s["busy_s"] / s["window_s"]))
    assert sum(v for _, v in s["idle_gaps"]) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-6)
    assert len(s["device_ops"]) <= 10
