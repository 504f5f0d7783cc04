"""A whole run of each cell on the CPU at a tiny size, past the harness's
look for a chip, with each fault the cell can have planted under the timed
round: ``correct`` comes out false. The sound run is held to the loss limit
only: the leaf-norm gaps of a model this small read a few times what the
chip's cells read (fewer elements a leaf), so the chip's limits on them do
not carry over."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from tiny import workloads

from benchmarks.chip import run

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2**33 + 77


def _cases():
    """Each cell sound, and with each fault its round kind can have."""
    out = []
    for name, traffic in workloads():
        out.append((name, "none", True))
        for fault in run.round_kind(traffic).faults:
            out.append((name, fault, False))
    return out


CASES = _cases()


@pytest.mark.parametrize("cell,fault,correct", CASES)
def test_fault_is_caught(cell, fault, correct):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "drive.py"), cell, fault,
         str(SEED)], capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    checks = result["checks"]
    if correct:
        assert checks["loss_gap"]["value"] <= checks["loss_gap"]["limit"]
    else:
        assert result["correct"] is False, (checks, proc.stderr[-2000:])
