"""The one owner of the JAX surfaces that move between releases.

Mesh construction, axis types, the ambient-mesh context, shard_map,
compiled-cost analysis, sharding helpers and the jaxpr internals
(``compat.jaxpr``). The repo rule (see
ROADMAP.md, enforced by the ``compat-surface`` lint):

    No ``jax._src``, direct ``jax.sharding.AxisType`` or raw
    ``Compiled.cost_analysis()`` outside ``repro/compat``.

Written for the installed JAX release only (0.9.0); there is no branch for
another version. Each surface is called directly, so a JAX that moved one
fails ``tests/test_compat.py`` at that call.
"""

from .cost import (
    cost_analysis,
    cost_bytes_accessed,
    cost_flops,
    normalize_cost_analysis,
)
from .meshes import axis_type, make_mesh, set_mesh, shard_map
from .shardings import named_sharding, partition_spec, replicated_sharding

__all__ = [
    "axis_type",
    "cost_analysis",
    "cost_bytes_accessed",
    "cost_flops",
    "make_mesh",
    "named_sharding",
    "normalize_cost_analysis",
    "partition_spec",
    "replicated_sharding",
    "set_mesh",
    "shard_map",
]
