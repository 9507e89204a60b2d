"""The control (the reference in bfloat16 in the program's place) fails
each cell's comparison, here at a size a test run can hold; on the chip it
runs at the cells' own sizes (``PERF.md``)."""
import os

import pytest

import control
import run

SMALL = {"gotoh.batch": ({"sizes": [250]}, 64)}


@pytest.mark.parametrize("cell", sorted(SMALL))
@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_control_is_not_correct(cell, seed):
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    _, config, traffic = run.lookup(bench, cell)
    over, k = SMALL[cell]
    out = control.control(config, dict(traffic, **over), seed, k)
    assert out["attempted"] == k
    assert not out["correct"]
    assert out["checks"]["value_gap"]["value"] > config["limits"]["value_gap"]
