"""Device idle ms a round outside the rounds' extents: the device waits
while the host waits for the result, reads it back, samples and dispatches."""

from benchmarks.chip import legs


def read(ctx):
    return legs.readings(ctx)["host_idle_ms"]
