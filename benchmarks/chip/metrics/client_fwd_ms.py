"""Device ms a round in the client leg's forward pass: ops under
``client_step`` outside the backward pass and the recomputation."""

from benchmarks.chip import legs


def read(ctx):
    return legs.readings(ctx)["client_fwd_ms"]
