"""The control, at a size a test run holds: float32 in the program's place
fails the comparison of every solve cell; float64 passes it."""

import json
import os

import pytest

from perfbench import run
from perfbench.tools import control

SMALL = {"d": 40, "n_clients": 8, "n_i": 60}


def _cell(workload):
    bench = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    cell = run.resolve(bench, workload)
    cell.config = dict(cell.config, **SMALL)
    return cell


def _fails(nums, limits):
    return [n for n, lim in limits.items() if nums[n] > lim]


@pytest.mark.parametrize("workload", ["w8a-topk-solve", "w8a-toplek-solve"])
def test_float32_controls_fail(workload):
    cell = _cell(workload)
    for r in control.readings(cell, 2**31 + 5):
        assert "error" not in r, r
        assert _fails(r, cell.limits), r


def test_float64_reference_passes_itself():
    cell = _cell("w8a-toplek-solve")
    d = cell.loop
    st = d.State(cell.config, cell.traffic, 7, d.datagen.make_problem(cell.config, 7), None,
                 d.budget(cell.config))
    a, b = d.reference_solve(st, 7), d.reference_solve(st, 7)
    nums = d.compare(a, b, cell.traffic["gnorm_floor"])
    nums["tol_ratio"] = a.grad_norms[-1] / cell.traffic["tol"]
    assert not _fails(nums, cell.limits)
