"""``compare.py``: the gap of norms by the worst leaf, the rule that leaves
round-off movers out of the change, and the decision."""

import math

import pytest

from benchmarks import compare


def _side(losses, grads, deltas, **more):
    return dict({"losses": losses, "grad_norms": grads,
                 "delta_norms": deltas}, **more)


REF = _side([10.0, 9.0, 8.0], {"a": 1.0, "b": 2.0, "c": 1e-6},
            {"a": 0.1, "b": 0.2, "c": 0.3})


def test_equal_sides_have_no_gap():
    found = compare.gaps(REF, REF)
    assert all(gap == 0 for gap, _ in found.values())


def test_the_gap_is_of_norms_by_the_worst_leaf_over_the_larger_of_leaf_and_median():
    prog = _side([10.1, 9.0, 8.0], {"a": 1.5, "b": 2.0, "c": 0.1},
                 {"a": 0.1, "b": 0.2, "c": 0.3})
    found = compare.gaps(prog, REF)
    assert found["loss1_gap"][0] == pytest.approx(0.01)
    # leaf a: 0.5 over max(1.0, median 1.0); leaf c: 0.1 over the median 1.0
    assert found["grad_gap"] == (pytest.approx(0.5), "a")
    assert found["grad_median_gap"] == (pytest.approx(0.1, rel=1e-4), "c")


def test_a_leaf_whose_gradient_is_nought_is_left_out_of_the_change_only():
    prog = _side(REF["losses"], REF["grad_norms"],
                 {"a": 0.1, "b": 0.2, "c": 0.9})
    assert compare.gaps(prog, REF)["delta_gap"][0] == 0      # c moves by noise
    prog = _side(REF["losses"], REF["grad_norms"],
                 {"a": 0.0, "b": 0.2, "c": 0.3})
    assert compare.gaps(prog, REF)["delta_gap"] == (pytest.approx(0.5), "a")


def test_a_nan_is_the_worst_there_is():
    prog = _side([math.nan, 9.0, 8.0], {"a": math.nan, "b": 2.0, "c": 1e-6},
                 REF["delta_norms"])
    found = compare.gaps(prog, REF)
    assert found["loss1_gap"][0] == math.inf
    assert found["grad_gap"] == (math.inf, "a")


def test_batch_statistics_are_compared_where_the_reference_gives_them():
    ref = dict(REF, stat_norms={"l/mean": 1.0, "l/var": 2.0})
    prog = dict(REF, stat_norms={"l/mean": 1.0, "l/var": 2.2})
    assert compare.gaps(prog, ref)["stat_gap"] == (pytest.approx(0.1), "l/var")
    assert "stat_gap" not in compare.gaps(REF, REF)
    ref["stat_norms"]["m/var"] = 2.0
    prog["stat_norms"]["m/var"] = 2.02
    found = compare.gaps(prog, ref)       # gaps 0, 0.01, 0.1: the middle one
    assert found["stat_median_gap"] == (pytest.approx(0.01), "m/var")


def test_decide_holds_each_number_to_its_limit_and_prints_the_rest():
    found = {"x": (0.5, "a"), "y": (0.1, "b")}
    ok, rows = compare.decide(found, {"x": 1.0})
    assert ok and rows == [("x", 0.5, 1.0, "a"), ("y", 0.1, None, "b")]
    assert not compare.decide(found, {"x": 0.4})[0]
    with pytest.raises(ValueError):
        compare.decide(found, {"z": 1.0})
