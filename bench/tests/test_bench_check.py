"""The row and plan comparisons, the sampling of targets and the window
rule."""
import numpy as np
import pytest

from bench import check
from bench.run import window

LIMITS = {"row_rel_gap": 1e-9, "count_mismatches": 0, "plan_mismatches": 0,
          "over_capacity_epochs": 0}
PLANS = {"plan_mismatches": 0, "over_capacity_epochs": 0}


def _row(target=20.0, **kw):
    r = {"policy": "p", "target": target, "carbon_rate_mean": 10.0,
         "migrations_mean": 2.5, "time_on_slice": {"x1": 0.5, "x2": 0.5}}
    r.update(kw)
    return r


def test_identical_rows_are_correct():
    rows = [_row(), _row(40.0)]
    v = check.judge(rows, [rows, rows], rows, LIMITS, 0, PLANS)
    assert v["correct"] and v["failed"] == 0


@pytest.mark.parametrize("change, key", [
    ({"carbon_rate_mean": 10.0 * (1 + 1e-6)}, "row_rel_gap"),
    ({"migrations_mean": 2.5 + 1e-5}, "count_mismatches"),
    ({"time_on_slice": {"x1": 0.5}}, "row_rel_gap"),
    ({"extra_key": 1.0}, "missing_keys"),
])
def test_a_changed_row_is_not_correct(change, key):
    ref = [_row()]
    got = [_row(**change)]
    v = check.judge(got, [got], ref, LIMITS, 0, PLANS)
    assert not v["correct"]
    value, limit = v["checks"][key]
    assert value > limit


def test_broken_guarantee_or_drift_counts_as_failed_sweep():
    warm = [_row()]
    drift = [_row(carbon_rate_mean=11.0)]
    v = check.judge(warm, [warm, drift, warm], warm, LIMITS, 0, PLANS)
    assert v["failed"] == 1 and not v["correct"]
    assert not check.judge(warm, [warm], warm, LIMITS, 1, PLANS)["correct"]
    over = {**PLANS, "over_capacity_epochs": 3}
    assert not check.judge(warm, [warm], warm, LIMITS, 0, over)["correct"]


@pytest.mark.parametrize("key", ["plan_mismatches", "over_capacity_epochs"])
def test_a_plan_fault_is_not_correct(key):
    rows = [_row()]
    v = check.judge(rows, [rows], rows, LIMITS, 0, {**PLANS, key: 1})
    assert not v["correct"] and v["checks"][key] == (1, 0)


def test_over_capacity_and_plan_mismatch_counts():
    assign = np.array([[0, 1, 2, 0], [0, 0, 0, 1], [1, 1, 2, 2]])
    assert check.over_capacity_epochs(assign, 3, 2) == 1
    assert check.over_capacity_epochs(assign, 3, 3) == 0
    other = assign.copy()
    other[1, 3] = 2
    assert check.plan_mismatches(other, assign) == 1
    assert check.plan_mismatches(assign[:2], assign) == assign.size
    assert check.plan_mismatches(None, assign) == assign.size


def test_sampled_targets_follow_the_seed():
    targets = [20.0 + 5 * i for i in range(10)]
    a = check.sampled_targets(targets, 2, 2**40)
    assert a == check.sampled_targets(targets, 2, 2**40)
    assert len(a) == 2 and a == sorted(a) and set(a) <= set(targets)


def test_window_holds_whole_sweeps_and_reaches_seconds():
    now = [0.0]

    def clock():
        return now[0]

    def step():
        now[0] += 4.0
        return "rows"

    out, elapsed = window(step, 10.0, clock)
    assert out == ["rows"] * 3 and elapsed == 12.0
    out, elapsed = window(step, 0.0, clock)
    assert len(out) == 1 and elapsed == 4.0
