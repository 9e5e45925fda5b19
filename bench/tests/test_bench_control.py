"""The control (the reference with each region admitting 1% more than its
capacity, put in the program's place) fails the comparison of every cell,
at a size a test run can hold. The same reading at the cells' own size is
`python3 -m bench.control`."""
import pytest

from bench import cells, control


@pytest.mark.parametrize("name", [w["name"] for w in cells.load()["workloads"]])
def test_control_is_not_correct(name):
    r = control.reading(cells.load(), name, 2**34 + 5,
                        sizes={"n_traces": 1500})["control"]
    assert not r["correct"]
    assert r["plan_mismatches"] > 0 and r["over_capacity_epochs"] > 0
    assert r["count_mismatches"] > 0 or r["row_rel_gap"] > 1e-7
