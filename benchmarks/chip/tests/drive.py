"""Drive one tiny run of a cell on this machine's CPU devices, with a fault
of its round kind planted under the timed round, and print its result line.

    python drive.py <cell> <fault|none> <seed>
"""

from __future__ import annotations

import json
import sys

from tiny import tiny_cell

from benchmarks.chip import run


def main(cell_name: str, fault: str, seed: int) -> None:
    import jax

    cell = tiny_cell(cell_name)
    faults = run.round_kind(cell["traffic"]).faults
    result = run.run_cell(
        cell, seed, 0.5, False, jax.devices()[:cell["chips"]],
        break_step=None if fault == "none" else faults[fault])
    print(json.dumps(result))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], int(sys.argv[3]))
