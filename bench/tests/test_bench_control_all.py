"""The all-layer reference's control (the reference with each region
admitting 1% more than its capacity, put in the program's place) fails the
comparison of the `r3_all` cell, at a size a test run can hold; so does
the reference on its inputs rounded to float32, by `row_rel_gap` alone.
The same readings at the cell's own size are
`python3 -m bench.control_all`."""
import pytest

from bench import cells, control_all

SIZES = {"n_traces": 1500}
SEEDS = [2**34 + 5, 3180000333]


def _reading(seed, mode):
    return control_all.readings(cells.load(), "r3_all", seed, (mode,),
                                SIZES)[mode]


@pytest.mark.parametrize("seed", SEEDS)
def test_control_is_not_correct(seed):
    r = _reading(seed, "capacity")
    assert not r["correct"]
    assert r["plan_mismatches"] > 0 and r["over_capacity_epochs"] > 0
    assert r["count_mismatches"] > 0 or r["row_rel_gap"] > 1e-7


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_on_float32_inputs_is_not_correct(seed):
    r = _reading(seed, "f32")
    limit = cells.config(cells.load(), "fleet_1m_r3_all")["limits"]
    assert not r["correct"]
    assert r["row_rel_gap"] > 10 * limit["row_rel_gap"]
