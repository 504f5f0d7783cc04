"""One-ulp comparison for round programs whose loss is a fused reduction.

A training round's loss is a mean over clients of a mean over examples.
Executed op by op (``run_plan``, eager dispatch) each reduction runs as its
own XLA computation; compiled whole (``jax.jit``, ``plan.compile``) XLA's
CPU backend fuses the reductions and may accumulate in a different order.
The two orders can differ in the last bit of an f32 sum, and which order a
given build picks is not stable across processes. Everything else in such
a round (params, optimizer state, integer outputs) is held to the same bar.
"""

import numpy as np


def assert_within_ulp(actual, desired, maxulp=1):
    a, b = np.asarray(actual), np.asarray(desired)
    assert a.shape == b.shape and a.dtype == b.dtype, (a.shape, b.shape)
    if np.issubdtype(a.dtype, np.inexact):
        np.testing.assert_array_max_ulp(a, b, maxulp=maxulp)
    else:
        np.testing.assert_array_equal(a, b)


def assert_within_ulp_of(actual, desired, scale, maxulp=1):
    """``actual`` within ``maxulp`` units in the last place of ``scale``
    (elementwise, broadcast) of ``desired``. For a sum whose terms are
    larger than the result: an ulp of a term that cancels is many ulps of
    the result, so the ulps are counted at the terms' magnitude."""
    a, b = np.asarray(actual), np.asarray(desired)
    assert a.shape == b.shape and a.dtype == b.dtype, (a.shape, b.shape)
    unit = np.spacing(np.abs(np.asarray(scale, a.dtype)))
    gap = np.abs(a.astype(np.float64) - b.astype(np.float64))
    worst = float(np.max(gap / unit)) if a.size else 0.0
    assert worst <= maxulp, f"{worst} ulps of the scale apart (> {maxulp})"
