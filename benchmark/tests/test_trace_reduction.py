"""The trace reduction: busy/idle union, self times, top ops and gaps
named by the host span open in them — on hand-made intervals, and on a
small profile recorded on a v5e chip (tests/data/)."""

import glob
import os

import pytest

from harness import trace
from harness.runner import reduce_trace
from harness.window import Check

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_covered_and_gaps():
    ops = [(0, 10, "a"), (5, 15, "b"), (20, 30, "c"), (25, 26, "d")]
    merged = trace.union(ops)
    assert merged == [[0, 15], [20, 30]]
    assert trace.covered(merged, 0, 40) == 25
    assert trace.covered(merged, 10, 22) == 7
    assert trace.gaps(merged, 0, 40) == [(15, 20), (30, 40)]
    assert trace.gaps(merged, -5, 12) == [(-5, 0)]


def test_self_times_subtract_nested_ops():
    ops = [(0, 100, "while"), (10, 30, "fusion"), (40, 50, "sort"),
           (200, 210, "fusion")]
    st = trace.self_times(ops)
    assert st == {"while": 70, "fusion": 30, "sort": 10}
    assert trace.top(st, 2) == [["while", 70e-9], ["fusion", 30e-9]]


def test_gaps_are_named_by_the_innermost_open_span():
    spans = [(0, 100, "bench.check"), (40, 60, "bench.discoveries")]
    assert trace.innermost(spans, 50) == "bench.discoveries"
    assert trace.innermost(spans, 10) == "bench.check"
    assert trace.innermost(spans, 150) == "none"


def test_within_clips_to_windows():
    ops = [(0, 10, "a"), (15, 25, "b")]
    assert trace.within(ops, [(5, 20)]) == [(5, 10, "a"), (15, 20, "b")]


def test_recorded_v5e_profile():
    paths = glob.glob(os.path.join(DATA, "*.xplane.pb"))
    if not paths:
        pytest.skip("no recorded profile under tests/data")
    t = trace.read_xplane(paths[0], span_names=None)
    assert t.ops and t.ops[0], "the TPU plane's XLA Ops line is empty"
    records = [Check(0.0, 1.0), Check(1.0, 2.0)]
    window_s, busy, program_busy, per_check, breakdown = reduce_trace(
        paths[0], records, "jit_step")
    assert 0 < busy < window_s
    assert 0 < program_busy <= busy
    assert all(0 < b <= busy for b in per_check)
    assert breakdown["device_ops"] and len(breakdown["device_ops"]) <= 10
    names = {n for n, _ in breakdown["idle_gaps"]}
    assert names & {"bench.spawn", "bench.discoveries", "bench.check"}
