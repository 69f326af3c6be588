"""The control (the reference with float32 sums) fails the comparison
that the program passes, in every cell."""

import pytest

from bench import spec

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(run_tiny, cell):
    r = run_tiny(cell, 2**32 + 3, control=True)
    assert r["correct"]
    assert r["checks"]["statements_differing"]["value"] == 0
    assert r["control"]["statements_differing"] > 0
