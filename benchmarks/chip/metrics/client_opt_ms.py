"""Device ms a round in the client optimizer: ops under ``clip`` and
``client_opt``."""

from benchmarks.chip import legs


def read(ctx):
    return legs.readings(ctx)["client_opt_ms"]
