"""Device idle ms a round inside the rounds' extents: gaps between the
round executable's own ops."""

from benchmarks.chip import legs


def read(ctx):
    return legs.readings(ctx)["step_idle_ms"]
