"""Device ms a round recomputing the forward pass for the backward pass:
ops that JAX marks ``checkpoint/rematted_computation``."""

from benchmarks.chip import legs


def read(ctx):
    return legs.readings(ctx)["remat_ms"]
