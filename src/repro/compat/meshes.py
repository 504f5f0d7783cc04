"""Mesh construction, shard_map and the ambient-mesh context.

All mesh construction in this repo goes through :func:`make_mesh`; nothing
outside ``repro.compat`` may reference ``jax.sharding.AxisType`` or probe
``jax.make_mesh`` keywords.
"""

from __future__ import annotations

import contextlib
from typing import Optional, Sequence, Union

import jax

# Axis-type names accepted by make_mesh (lowercase) -> enum member name.
_AXIS_TYPE_MEMBERS = {"auto": "Auto", "explicit": "Explicit", "manual": "Manual"}

AxisTypeLike = Union[str, object, None]


def axis_type(kind: str = "auto"):
    """Resolve ``jax.sharding.AxisType.<Kind>`` from its lowercase name."""
    member = _AXIS_TYPE_MEMBERS.get(str(kind).lower())
    if member is None:
        raise ValueError(
            f"unknown axis type {kind!r}; expected one of {sorted(_AXIS_TYPE_MEMBERS)}"
        )
    return getattr(jax.sharding.AxisType, member)


def make_mesh(
    shape: Sequence[int],
    axes: Sequence[str],
    *,
    axis_types: Union[AxisTypeLike, Sequence[AxisTypeLike]] = "auto",
    devices=None,
) -> jax.sharding.Mesh:
    """Build a Mesh through ``jax.make_mesh``.

    ``axis_types`` accepts lowercase names ("auto" / "explicit" / "manual"),
    already-resolved enum members, a single value applied to every axis, or
    ``None`` (JAX's default).
    """
    axes = tuple(axes)
    kwargs = {}
    if devices is not None:
        kwargs["devices"] = devices
    if axis_types is not None:
        if isinstance(axis_types, str) or not isinstance(axis_types, (tuple, list)):
            axis_types = (axis_types,) * len(axes)
        kwargs["axis_types"] = tuple(
            axis_type(t) if isinstance(t, str) else t for t in axis_types
        )
    return jax.make_mesh(tuple(shape), axes, **kwargs)


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = True):
    """``jax.shard_map``; ``check`` is its ``check_vma`` flag (callers pass
    False when their body's collectives defeat replication checking, e.g.
    the int8 partial-sum collectives)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check
    )


@contextlib.contextmanager
def set_mesh(mesh: Optional[jax.sharding.Mesh]):
    """``jax.set_mesh`` as an ambient-mesh context. ``mesh=None`` is a no-op
    so callers can write ``with compat.set_mesh(maybe_mesh): ...``."""
    if mesh is None:
        yield None
        return
    with jax.set_mesh(mesh):
        yield mesh
