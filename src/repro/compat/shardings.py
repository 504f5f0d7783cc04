"""Sharding-object helpers, kept in one place so call sites name shardings
one way."""

from __future__ import annotations

from typing import Sequence, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec

SpecLike = Union[PartitionSpec, Sequence, None]


def partition_spec(*axes) -> PartitionSpec:
    return PartitionSpec(*axes)


def named_sharding(mesh: jax.sharding.Mesh, spec: SpecLike = None) -> NamedSharding:
    """NamedSharding from a PartitionSpec or a plain axis sequence."""
    if spec is None:
        spec = PartitionSpec()
    elif not isinstance(spec, PartitionSpec):
        spec = PartitionSpec(*spec)
    return NamedSharding(mesh, spec)


def replicated_sharding(mesh: jax.sharding.Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())
