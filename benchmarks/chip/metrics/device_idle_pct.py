"""Share of the traced window in which no op runs on the device, for the
worst device: 100 * (1 - union of op intervals / window)."""

from benchmarks.chip import trace


def read(ctx):
    lo, hi = ctx["lo"], ctx["hi"]
    if not ctx["trace"].ops:
        return None
    idle = [1.0 - trace.total(trace.busy(ops, lo, hi)) / (hi - lo)
            for ops in ctx["trace"].ops.values()]
    return 100.0 * max(idle)
