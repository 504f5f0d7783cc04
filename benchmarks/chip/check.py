"""The comparison that decides ``correct`` for a training cell.

The program and the reference each run the cell's first three rounds from
the same weights on the same batches. Per round r the loss (the mean client
loss of the round) is read, and after rounds 1 and 3 the change of every
parameter leaf from the start. The numbers compared:

* ``loss_gap``: the largest |program loss - reference loss| / reference
  loss over the three rounds;
* ``grad1_gap``: after one round, the worst leaf's gap between the norms of
  the program's and the reference's change, ``| |dp| - |dr| | / max(|dr|,
  median leaf |dr|)``. With the server step of 1 this change is the first
  gradient as the server optimizer applies it;
* ``change3_gap``: the same after three rounds.

Leaves whose reference change after one round is under a thousandth of the
median leaf's (norm scales, which a bfloat16 update cannot move) are left
out of both leaf numbers.
"""

from __future__ import annotations

import math

NUMBERS = ("loss_gap", "grad1_gap", "change3_gap")
NEGLIGIBLE = 1e-3


def _median(values):
    v = sorted(values)
    n = len(v)
    return v[n // 2] if n % 2 else 0.5 * (v[n // 2 - 1] + v[n // 2])


def leaf_gap(prog: dict, ref: dict, keep) -> float:
    med = _median(ref.values())
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def kept(ref: dict) -> list:
    """The leaves the leaf numbers compare (see the module docstring)."""
    floor = NEGLIGIBLE * _median(ref["change1"].values())
    return sorted(k for k, v in ref["change1"].items() if v >= floor)


def readings(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: {"losses": [3 floats], "change1": {leaf: norm},
    "change3": {leaf: norm}}."""
    if set(prog["change1"]) != set(ref["change1"]):
        raise ValueError("program and reference leaves differ: "
                         f"{sorted(set(prog['change1']) ^ set(ref['change1']))}")
    keep = kept(ref)
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                    ref["losses"]))
    return {
        "loss_gap": loss,
        "grad1_gap": leaf_gap(prog["change1"], ref["change1"], keep),
        "change3_gap": leaf_gap(prog["change3"], ref["change3"], keep),
    }


def judge(numbers: dict, limits: dict) -> bool:
    """Every number is finite and at most its limit."""
    return all(math.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in NUMBERS)
