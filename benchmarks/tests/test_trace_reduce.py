"""The trace reduction: its interval arithmetic, and the recorded trace
(data/small_trace.xplane.pb, made on a TPU v5e by record_trace.py)."""
import os

import pytest

from benchmarks.harness import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small_trace.xplane.pb")


def test_union_and_uncovered_length():
    merged = tr._merge([(0, 5), (3, 8), (10, 12)])
    assert merged == [[0, 8], [10, 12]] and tr._total(merged) == 10
    assert tr._subtract([[0, 10]], [[2, 3], [5, 12]]) == 4
    assert tr._subtract([[0, 10], [20, 30]], []) == 20
    assert tr._subtract([[0, 10]], [[0, 10]]) == 0


def test_self_time_charges_a_parent_only_what_its_children_leave():
    got = tr._self_times([(0, 100, "while"), (10, 30, "a"), (40, 60, "a"),
                          (120, 130, "b")])
    assert got == pytest.approx({"while": 60e-9, "a": 40e-9, "b": 10e-9})


def test_a_loop_op_does_not_hide_what_runs_inside_it():
    evs = [(0, 100, "while"), (10, 30, "all-reduce.1"), (40, 60, "fusion"),
           (120, 130, "fusion")]
    leaves = tr._leaves(evs)
    assert sorted(e[2] for e in leaves) == ["all-reduce.1", "fusion", "fusion"]
    assert tr._total(tr._merge((s, e) for s, e, _ in leaves)) == 50


@pytest.mark.skipif(not os.path.exists(TRACE), reason="no recorded trace")
def test_the_recorded_trace_reduces_to_what_was_recorded():
    r = tr.reduce_trace(TRACE, chips=1)
    # three rounds of {matmul chain of 0.36 ms, 10 ms sleep}; the window
    # runs from the first program's start to the last one's: two whole
    # rounds, so two chains and two sleeps
    assert r["chips_traced"] == 1
    assert 0.02 < r["window_s"] < 0.03
    assert r["busy_s"] == pytest.approx(2 * 0.36e-3, rel=0.05)
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    gaps = dict(r["idle_gaps"])
    assert gaps["sleep"] >= 0.02                 # 2 x 10 ms, host asleep
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    ops = dict(r["device_ops"])
    assert sum(ops.values()) == pytest.approx(r["busy_s"], rel=1e-3)
    assert len(r["device_ops"]) <= 10 and r["exposed_collective_s"] == 0
    with pytest.raises(ValueError):
        tr.reduce_trace(TRACE, chips=0)
