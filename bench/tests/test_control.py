"""The control, the reference computed in bfloat16, fails every cell."""
import pytest

from bench.control import readings
from bench.harness.spec import Cell
from conftest import all_cells


@pytest.mark.parametrize("workload", [w["name"] for w in all_cells()])
def test_control_fails_the_cell(workload, tiny_root):
    cell = Cell(tiny_root, workload)
    got = readings(cell, seed=2 ** 33 + 7, n_requests=6)
    over = {n: v for n, v in got.items()
            if v > cell.limits[n.split(".")[0]][n.split(".")[1]]}
    assert over, f"the bf16 control passes every number: {got}"
    assert all(n.endswith(".err") for n in over)
