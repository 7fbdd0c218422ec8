"""The control: the program with its bfloat16 per-pair path switched on
(``dtype="mixed"``), one precision step below the float32 the
configuration states, must come out not correct; the program as configured
must come out correct.  At the small size a CPU test holds; the readings
at the cells' own sizes on the chip are in ``limits/<cell>.json``."""

import pytest

from conftest import small_run
from chipbench.calibrate import CONTROL

SEEDS = (3000000031, 3000000032, 3000000033)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", ["plummer64k_1chip.block",
                                  "plummer64k_1chip.lockstep"])
def test_control_fails_program_passes(cell, seed, tmp_path):
    assert small_run(cell, seed, tmp_path)["correct"]
    out = small_run(cell, seed, tmp_path, overrides=CONTROL)
    assert not out["correct"], out["checks"]
