"""The control at a size a test run holds: the reference computed in float8
in the program's place, and the program's own int8 path, are judged not
correct under the cell's limits, and the control reads above the sound
program on the numbers that catch it on the chip (where ``control.py`` reads
both at the cell's size)."""

from __future__ import annotations

import jax
import pytest

from tiny import tiny_cell

from benchmarks.chip import control


@pytest.mark.parametrize("name", ["lm_1b.local_sgd.c2h4"])
def test_controls_fail_the_limits(name):
    cell = tiny_cell(name)
    rows, _ = control.readings(cell, jax.devices()[:1], [2**33 + 3], 1,
                               log=lambda *_: None)
    (row,) = rows
    assert row["control"]["correct"] is False, row["control"]
    assert row["program_int8"]["correct"] is False, row["program_int8"]
    assert row["half_batch"]["correct"] is False, row["half_batch"]
    for k in ("loss_gap", "change3_gap"):
        assert row["control"][k] > row["sound"][k], (row["control"], row["sound"])
