"""The trace reduction on a small hand-made trace (small_trace.json): union
of intervals, busy and idle share, kernel-time sum, the breakdown lists."""

import os

import pytest

from benchmark.harness import trace as T
from benchmark.layer_metrics import device_idle_pct, glm_device_ms_per_iter
from benchmark.layer_metrics import tree_dispatches_per_tree

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def tr():
    return T.load(os.path.join(HERE, "small_trace.json"))


@pytest.mark.parametrize("intervals,want", [
    ([], 0.0),
    ([(0, 5)], 5.0),
    ([(0, 5), (5, 5)], 10.0),           # touching
    ([(0, 10), (2, 3)], 10.0),          # nested
    ([(0, 4), (2, 4), (10, 1)], 7.0),   # overlapping, then a gap
    ([(10, 1), (0, 4), (2, 4)], 7.0),   # any order
])
def test_union(intervals, want):
    assert T.union_ns(intervals) == want


def test_busy_is_the_union_not_the_sum(tr):
    # while 1..5 s covers its body; fusion.9 and copy.2 overlap: 7..8.5 s
    assert T.busy_seconds(tr) == pytest.approx(4.0 + 1.5)
    assert sum(d for _, _, d in tr["device"]["0"]) / 1e9 == pytest.approx(9.5)


def test_idle_share(tr):
    ctx = {"busy_s": T.busy_seconds(tr), "call": {"wall_s": 10.0}}
    assert device_idle_pct.read(ctx) == pytest.approx(45.0)


def test_kernel_time_is_the_sum_of_its_events(tr):
    assert T.kernel_seconds(tr, r"hist_kernel") == pytest.approx(3.0)
    assert T.kernel_seconds(tr, r"no_such_kernel") is None


def test_no_device_gives_nothing_not_zero():
    empty = {"device": {}}
    assert T.busy_seconds(empty) is None
    assert T.kernel_seconds(empty, "hist") is None
    assert T.idle_gaps(empty, 1.0, "x") == []
    assert device_idle_pct.read({"busy_s": None, "call": {"wall_s": 1.0}}) is None
    assert glm_device_ms_per_iter.read(
        {"busy_s": None, "call": {"counters": {"glm_irls_iterations_total": 5}}}) is None


def test_device_ops_leave_out_control_flow(tr):
    ops = T.device_ops(tr)
    assert ops[0] == ["%hist_kernel.3 = custom-call(...)", pytest.approx(3.0)]
    assert not any(name.startswith("%while") for name, _ in ops)


def test_idle_gaps(tr):
    gaps = T.idle_gaps(tr, 10.0, "inside train()")
    # the call's wall leaves 10 - (8.5 - 1) = 2.5 s outside the first..last op
    assert gaps[0][0].startswith("inside train(): before the first")
    assert gaps[0][1] == pytest.approx(2.5)
    assert gaps[1][0] == ("inside train(): after %while.1 = while(...) "
                          "before %fusion.9 = fusion(...)")
    assert gaps[1][1] == pytest.approx(2.0)
    assert len(gaps) == 2


def test_counter_metrics():
    call = {"counters": {"tree_dispatches_total": 2.0, "tree_trees_built_total": 10.0,
                         "glm_irls_iterations_total": 4.0}}
    assert tree_dispatches_per_tree.read({"call": call}) == pytest.approx(0.2)
    assert glm_device_ms_per_iter.read({"busy_s": 0.1, "call": call}) == pytest.approx(25.0)
    assert tree_dispatches_per_tree.read({"call": {"counters": {}}}) is None
