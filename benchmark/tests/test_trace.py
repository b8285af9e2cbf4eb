"""The trace reduction: interval arithmetic on hand-made events, and the
whole reduction on a small trace recorded on the chip (tests/data/
small.xplane.pb, written by tests/record_trace.py: three steps of one
matmul program and one elementwise program with 20 ms of host sleep in
each step)."""

import os

import pytest

from benchmark import trace

MS = 1_000_000


def test_union_clip_subtract():
    assert trace.union([(0, 5), (3, 8), (10, 12), (12, 13)]) == [(0, 8), (10, 13)]
    assert trace.clip([(0, 8), (10, 13)], 4, 11) == [(4, 8), (10, 11)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 11)]) == [(0, 2), (3, 5)]
    assert trace.total(trace.gaps([(2, 4), (6, 9)], 0, 10)) == 5


def test_self_time_charges_a_container_only_what_is_left():
    events = [(0, 10, "while"), (1, 3, "a"), (4, 6, "b"), (12, 13, "c")]
    assert trace.self_times(events) == [("while", 6), ("a", 2), ("b", 2), ("c", 1)]
    assert trace.time_by_pattern(events, "^(a|b)$") == (4, 2)
    assert trace.time_by_pattern(events, "while", lo=0, hi=1) == (6, 1)


def test_exposed_collective_time():
    ops = [(0, 10, "fusion.1"), (8, 14, "all-reduce.3"), (14, 20, "fusion.2"),
           (30, 34, "all-gather-start.1")]
    # 8..10 is hidden under fusion.1; 10..14 and 30..34 are exposed
    assert trace.exposed_collective_ns(ops, 0, 40) == 8


def synthetic():
    ops = {0: [(0, 4 * MS, "fusion.gram"), (6 * MS, 8 * MS, "cholesky.1")],
           1: [(0, 2 * MS, "fusion.gram")]}
    modules = {0: [(0, 8 * MS, "jit__block_step")],
               1: [(0, 2 * MS, "jit__block_step")]}
    host = [(0, 10 * MS, "bench:step"), (4 * MS, 6 * MS, "host_solve"),
            (8 * MS, 10 * MS, "device_get")]
    return trace.TraceSummary(ops, modules, host, 2)


def test_summary_on_hand_made_events():
    s = synthetic()
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx((0.006 + 0.002) / 2)
    assert s.fullest == 0
    assert s.idle_share() == pytest.approx(0.4)
    seconds, count = s.op_time("gram")
    assert seconds == pytest.approx((0.004 + 0.002) / 2) and count == 1
    b = s.breakdown()
    assert b["device_ops"][0] == ["fusion.gram", pytest.approx(0.004)]
    assert dict(map(tuple, b["idle_gaps"])) == {
        "host_solve": pytest.approx(0.002), "device_get": pytest.approx(0.002)}


RECORDED = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_recorded_chip_trace():
    s = trace.parse(RECORDED, 1)
    assert s is not None and s.chips == [0]
    # three steps with 20 ms of sleep each: mostly idle, never wholly
    assert 0.060 < s.window_s < 0.2
    assert 0.0 < s.busy_s < 0.02
    assert 0.8 < s.idle_share() < 1.0
    _, gram_runs = s.op_time("small_gram", line="modules")
    _, scale_runs = s.op_time("small_scale", line="modules")
    # in this trace the chip's clock runs about a millisecond ahead of the
    # host's, so the first step's programs fall just before its span
    assert gram_runs in (2, 3) and scale_runs in (2, 3)
    gaps = dict(map(tuple, s.breakdown()["idle_gaps"]))
    assert max(gaps, key=gaps.get) == "bench:sleep"
    assert gaps["bench:sleep"] > 0.055
