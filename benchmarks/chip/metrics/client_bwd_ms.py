"""Device ms a round in the client leg's backward pass: ops under
``client_step`` that JAX marks ``transpose(``, recomputation aside."""

from benchmarks.chip import legs


def read(ctx):
    return legs.readings(ctx)["client_bwd_ms"]
