"""The comparison that decides `correct`: worst leaf against the reference's
norm or the median leaf's, dead leaves left out by rule, no number, no pass."""

import math

import pytest

from benchmark import compare


def side(loss, grad, delta):
    return {"loss": {"wm": loss}, "grad": {"wm": grad}, "delta": {"wm": delta}}


REF = side([100.0, 90.0, 80.0], {"a": 1.0, "b": 2.0, "tiny": 1e-5, "c": 4.0}, {"a": 0.1, "b": 0.2, "tiny": 0.3, "c": 0.4})


def test_a_side_compared_with_itself_reads_nought_everywhere():
    numbers, _ = compare.training_numbers(REF, REF)
    assert set(numbers) == {"loss_wm", "loss1_wm", "grad_wm", "grad_med_wm", "delta_wm", "delta_med_wm"}
    assert all(v == 0.0 for v in numbers.values())


def test_gaps_are_of_norms_against_the_larger_of_leaf_and_median_leaf():
    prog = side([100.0, 90.9, 80.0], {"a": 1.2, "b": 2.0, "tiny": 3e-5, "c": 4.0}, {"a": 0.1, "b": 0.2, "tiny": 0.9, "c": 0.4})
    numbers, where = compare.training_numbers(prog, REF)
    assert numbers["loss_wm"] == pytest.approx(0.01) and numbers["loss1_wm"] == 0.0
    # the median leaf's gradient is 1.5: leaf a's gap of 0.2 is held against that, not against 1.0
    assert numbers["grad_wm"] == pytest.approx(0.2 / 1.5) and where["grad_wm"] == "a"
    # `tiny` has a gradient under a thousandth of the median leaf's: Adam moves it by round-off alone
    assert compare.dead_leaves(REF["grad"]["wm"]) == {"tiny"}
    assert numbers["delta_wm"] == 0.0


def test_a_state_left_unchanged_reads_one_and_a_leaf_moved_double_reads_one():
    still = side(REF["loss"]["wm"], REF["grad"]["wm"], {k: 0.0 for k in REF["delta"]["wm"]})
    double = side(REF["loss"]["wm"], REF["grad"]["wm"], {k: 2 * v for k, v in REF["delta"]["wm"].items()})
    for prog in (still, double):
        assert compare.training_numbers(prog, REF)[0]["delta_wm"] == pytest.approx(1.0)


def test_what_is_not_a_number_fails_and_stays_valid_json():
    import json

    prog = side([math.nan, 90.0, 80.0], {**REF["grad"]["wm"], "b": math.inf}, REF["delta"]["wm"])
    numbers, _ = compare.training_numbers(prog, REF)
    assert numbers["loss_wm"] == numbers["grad_wm"] == compare.NOT_A_NUMBER
    ok, table = compare.judge(numbers, {"loss_wm": 1e-3, "grad_wm": None})
    assert not ok and "NaN" not in json.dumps(table) and "Infinity" not in json.dumps(table)


def test_judge_holds_only_numbers_with_a_limit_and_misses_none():
    numbers = {"x": 0.5, "y": 2.0, "exact": 0.0}
    assert compare.judge(numbers, {"x": 1.0, "y": None, "exact": 0})[0]
    assert not compare.judge(numbers, {"x": 0.4})[0]
    assert not compare.judge({"exact": 1.0}, {"exact": 0})[0]
    ok, table = compare.judge(numbers, {"gone": 1.0})
    assert not ok and table["gone"] == {"value": None, "limit": 1.0}
