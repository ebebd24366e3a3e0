"""The control at a size a CPU test run can hold: a whole run with the
reference in float8 in the program's place has to come out not correct
by the cell's own limits, and the program's run correct.  The same runs
at the cell's own size are `bench/calibrate.py` on the chip."""

from __future__ import annotations

import pytest

import smoke

CELLS = [w["name"] for w in smoke.spec()["workloads"]]


@pytest.mark.parametrize("seed", [2**31 + 101, 2**31 + 102, 2**31 + 103])
@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_program_passes(cell, seed):
    program = smoke.run(cell, seed, seconds=0.5)
    assert program["correct"] is True, program["checks"]
    control = smoke.run(cell, seed, seconds=0.5, control=True)
    assert control["correct"] is False, control["checks"]
