"""The control (the reference in bfloat16, in the program's place)
fails every cell's limit, at a width a test run holds; the float64 FIR
form of the same sum passes it by far."""

import pytest

import control
import harness
from conftest import with_held_out


@pytest.mark.parametrize("cell,files", [("batch-10k", None),
                                        ("edge-2048-60s", 6),
                                        ("edge-2048-10s", 12),
                                        ("batch-10k-mesh4", None)])
def test_control_fails_the_limit(cell, files):
    bench = with_held_out()
    limit = harness.load_cell(harness.ROOT, cell, bench)["cfg"]["limits"][
        "output_gap"]
    for seed in (1, 2, 3):
        gap, exact, n = control.readings(cell, seed, files, channels=64,
                                         bench=bench)
        assert n > 0
        assert gap > limit, (seed, gap, limit)
        assert exact < limit / 1e4, (seed, exact)
