"""Bytes the fused reduce+compress kernel moves, computed from the
configuration's parameter layout (``weights.layout``), not from the
program: the numerator of ``reduce_compress_roundtrip_roofline``.

The nested round's intra-pod leg hands the kernel one pod partial of the
float32 deltas, every leaf packed into rows of 256 values (each leaf
zero-padded to a row boundary). Per row the kernel reads the float32
partial and writes its float32 roundtrip (``back``), its int8 values and
one float32 scale. On a ``(pod, data)`` mesh each device runs it once a
round on its pod's whole partial (the group count is 1 after the all-reduce
inside the pod).
"""

from __future__ import annotations

import math

from benchmarks.chip import weights

ROW = 256
F32, INT8 = 4, 1


def rows(c: dict) -> int:
    """Rows of 256 in the packed buffer of config ``c``'s parameters."""
    return sum(-(-math.prod(shape) // ROW)
               for shape, _ in weights.layout(c).values())


def roundtrip_bytes(c: dict, groups: int = 1) -> int:
    """HBM bytes of one ``reduce_compress_roundtrip`` call on a pod partial
    of ``groups`` float32 groups: the groups read, ``back`` (float32) and
    the int8 values written, and one float32 scale a row."""
    r = rows(c)
    return r * ROW * (groups * F32 + F32 + INT8) + r * F32
