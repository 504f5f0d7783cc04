"""Device ms a round in the DrJAX primitives and the server update: ops
under ``drjax.reduce*``, ``drjax.compress``, ``drjax.broadcast``,
``client_delta`` and ``server_update``."""

from benchmarks.chip import legs


def read(ctx):
    return legs.readings(ctx)["aggregate_ms"]
