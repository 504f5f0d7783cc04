"""Hierarchical (two-stage) reductions — paper §6 future work, implemented.

At multi-pod scale the reduction crosses two very different interconnects:
ICI within a pod (~50 GB/s/link) and DCN across pods (often 10-100× slower).
A flat ``reduce_mean`` over n groups moves every group's contribution across
the slow leg. The hierarchical form:

    stage 1 (within pod):  n groups → P pod-partials        (fast ICI)
    stage 2 (cross pod):   P partials → 1, optionally compressed (slow DCN)

cuts cross-pod bytes by n/P before compression (×4 more with int8). Both
stages are REAL DrJAX reduce primitives addressed at different levels of a
placement stack — ``reduce_mean(placement="clients")`` then
``reduce_mean(placement="pods")`` — so each stage carries its own placement's
sharding annotations (pods pin the DCN axis, clients the ICI axis), MapReduce
AD applies per stage (the derivative of a hierarchical reduction is a
hierarchical broadcast, automatically), and the §5 interpreter stages the
reduction as two placement-tagged REDUCE shuffles.

Under a genuinely nested ``@drjax.program(placements={"pods": P,
"clients": m})`` the two stages bind directly. Under the flat single-
placement API, the (n, ...) value is regrouped to (P, n/P, ...) and the same
two primitives bind inside a derived two-level stack — the one remaining
reshape is pure local compute at the pod boundary.

With a recognized int8 compressor the fast path packs the tree into one
buffer and reduces it in one fused pass (:func:`_staged_reduce`). Where a
device holds several of the innermost level's groups (m groups over d
devices of a mesh, f32 leaves), it first sums its own m/d groups, each
scaled by d/m: a MapReduce combiner, local and linear, so the pack copies
one group a device instead of m/d, and the inner reduce binds at a derived
level of d groups with the same name and mesh axes. Without a mesh, with
one group a device, or with bf16 leaves, the path is the plain one.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro import compression

from . import api
from . import placement as placement_lib
from . import primitives as prims

_SUPER = "pods"


def _axes_if_divisible(axes, groups: int, mesh):
    """Keep a derived placement's mesh axes only if its group count can
    shard over them (devices | groups); otherwise leave the level logical.

    With no mesh in the context, constraints are never emitted, so the axes
    are kept as documentation. Axes missing from the mesh are also kept —
    the later sharding constraint fails loudly, which beats hiding a typo.
    """
    if axes is None or mesh is None:
        return axes
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
    if not axes_t:
        return None
    mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    devices = 1
    for a in axes_t:
        if a not in mesh_sizes:
            return axes
        devices *= mesh_sizes[a]
    return axes if groups % devices == 0 else None


def _fusable(tree, ctx, compress_fn, use_fused: Optional[bool]) -> bool:
    """Should this reduction take the fused reduce+compress fast path?

    The fast path engages when the compressor is *recognized* — it carries
    the ``drjax_fused_compress = "int8"`` tag (``compression.int8_roundtrip``
    does) — and every leaf is a floating array carrying the stack's group
    axes. ``use_fused=False`` forces the generic two-primitive composition;
    ``use_fused=True`` insists and raises if the compressor cannot be fused.
    """
    tag = getattr(compress_fn, "drjax_fused_compress", None)
    if use_fused is False:
        return False
    if tag != "int8":
        if use_fused is True:
            raise ValueError(
                "use_fused=True requires a fusable compress_fn (one tagged "
                f"drjax_fused_compress='int8'); got {compress_fn!r}"
            )
        return False
    leaves = jax.tree_util.tree_leaves(tree)
    depth = ctx.depth
    sizes = tuple(ctx.sizes)
    for leaf in leaves:
        if not jnp.issubdtype(jnp.result_type(leaf), jnp.floating):
            return False
        if jnp.shape(leaf)[:depth] != sizes:
            return False
    return bool(leaves)


def _combine(tree, ctx):
    """Sum each device's co-resident groups of the innermost level before
    the fused path packs them: the tree and a context whose innermost level
    keeps its name and axes and holds one group a device; both unchanged
    where the combine does not engage.

    It engages on a mesh with sharding annotations, where the innermost
    level names mesh axes of ``d`` devices, its ``m`` groups are a multiple
    of them with ``m/d > 1``, and every leaf is f32: a bf16 buffer summed
    in f32 would be another value, and its partial would cross the
    cross-pod wire as int8, where a bf16 partial crosses as itself
    (``primitives._wire_engages``). Shard ``j`` holds groups ``j*m/d`` to
    ``(j+1)*m/d - 1``, so the sum moves nothing between devices. Each group
    is scaled by ``d/m`` before the sum, where XLA fuses the scale into the
    sum and the groups' producer; a scale after the sum is a pass of its
    own. The mean over the ``d`` sums is the mean over the ``m`` groups."""
    inner = ctx.innermost
    axes = inner.axes_tuple()
    if ctx.mesh is None or not ctx.use_sharding_annotations or not axes:
        return tree, ctx
    d = math.prod(ctx.mesh.shape[a] for a in axes)
    per = inner.size // d
    if inner.size % d or per == 1 or any(
            jnp.result_type(leaf) != jnp.float32
            for leaf in jax.tree_util.tree_leaves(tree)):
        return tree, ctx
    i = ctx.depth - 1

    def combine(x):
        groups = x.reshape(x.shape[:i] + (d, per) + x.shape[i + 1:])
        return jnp.sum(groups * (1.0 / per), axis=i + 1)

    derived = dataclasses.replace(
        ctx, placements=ctx.placements[:-1] + (
            dataclasses.replace(inner, size=d),))
    return jax.tree_util.tree_map(combine, tree), derived


def _staged_reduce(tree, ctx, compress_fn, use_fused: Optional[bool]):
    """Bind the two-stage reduction under the ambient (nested) context.

    Fast path: where each device holds several of the innermost level's
    groups (a mesh, f32 leaves), first sum them on the device
    (:func:`_combine`, a MapReduce combiner): the pack then copies one
    group a device instead of every group, and the inner reduce binds at a
    derived innermost level of one group a device, with the same name and
    axes.
    Then flat-pack the tree (one ``(*groups, R, 256)`` buffer per
    dtype), bind ``reduce_mean@innermost`` tagged ``compress="int8"`` — a
    single eqn whose execution is the one-pass Pallas reduce+compress kernel
    on TPU (fused jnp oracle elsewhere) — then the outer reduces, and
    unpack. The first outer reduce is tagged ``wire="int8"``: where its
    level is sharded over mesh axes and the buffer is f32, the pod partials
    cross it as int8 values and scales, and ``dequant_accumulate`` takes
    their mean on receipt (``primitives._int8_wire_mean``). The program
    still stages as placement-tagged REDUCEs, so ``build_plan``/``to_beam``
    see the same communication structure as the generic composition.

    Scopes: the fused path's combine, pack, inner reduce and unpack bind
    under ``drjax.reduce_compress[<inner>]``, the generic path's
    ``compress_fn`` under ``drjax.compress[<inner>]``; the outer levels are
    ``drjax.reduce_mean[<level>]``.
    """
    inner = ctx.names[-1]
    if _fusable(tree, ctx, compress_fn, use_fused):
        with api.scope("reduce_compress", inner):
            tree, ctx = _combine(tree, ctx)
            bufs, spec = compression.flat_pack(
                tree, lead_ndim=ctx.depth, cols=compression.PACK_COLS
            )
        outs = {}
        for key, buf in bufs.items():
            with api.scope("reduce_compress", inner), \
                    placement_lib.placement_context(ctx):
                v = prims.bind_reduce_mean(buf, placement=inner,
                                           compress="int8")
            # The next level's operand is the roundtrip's output, on the
            # int8 grid, and may cross its wire as such; the levels after
            # it reduce a mean.
            bind = prims._bind_roundtrip_mean
            for name in reversed(ctx.names[:-1]):
                with api.scope("reduce_mean", name):
                    v = bind(v, placement=name)
                bind = prims.bind_reduce_mean
            outs[key] = v
        with api.scope("reduce_compress", inner):
            return compression.flat_unpack(outs, spec, lead_ndim=0)
    partials = api.reduce_mean(tree, placement=inner)
    if compress_fn is not None:
        with api.scope("compress", inner):
            partials = compress_fn(partials)
    out = partials
    for name in reversed(ctx.names[:-1]):
        out = api.reduce_mean(out, placement=name)
    return out


def hierarchical_reduce_mean(
    tree,
    num_supergroups: Optional[int] = None,
    compress_fn: Optional[Callable] = None,
    use_fused: Optional[bool] = None,
):
    """Two-stage mean over a partitioned structure.

    ``num_supergroups`` is the number of slow-link domains (pods). Under the
    flat API it is required and must divide the partition size; under a
    nested placement stack it is inferred from the stack (and validated if
    passed). ``compress_fn`` (e.g. ``repro.compression.int8_roundtrip``) is
    applied to the per-pod partial means — the value that crosses the slow
    leg.

    When ``compress_fn`` is recognized as the int8 wire format, the intra-pod
    leg runs the fused single-pass reduce+compress kernel instead of the
    reduce→quantize→dequantize chain (``use_fused``: None = auto, False =
    force the generic composition, True = insist). Derivatives are identical
    either way — the roundtrip is straight-through under MapReduce AD.
    """
    ctx = placement_lib.current_context()

    if ctx.depth >= 2:
        # Genuinely nested placements: the stack already separates the fast
        # and slow legs — bind the per-level primitives directly.
        outer_total = math.prod(ctx.sizes[:-1])
        if num_supergroups is not None and num_supergroups != outer_total:
            raise ValueError(
                f"num_supergroups={num_supergroups} contradicts the ambient "
                f"placement stack {dict(zip(ctx.names, ctx.sizes))}, which "
                f"has {outer_total} slow-link domain(s)"
            )
        return _staged_reduce(tree, ctx, compress_fn, use_fused)

    # Flat single-placement API: regroup (n, ...) -> (P, n/P, ...) and run the
    # same two primitives inside a derived {pods, <placement>} stack.
    n = ctx.partition_size
    if num_supergroups is None:
        raise ValueError(
            "num_supergroups is required under a single-placement context"
        )
    if n % num_supergroups != 0:
        raise ValueError(
            f"num_supergroups={num_supergroups} must divide partition "
            f"size {n}"
        )
    per = n // num_supergroups
    inner_name = ctx.placement
    super_name = _SUPER if inner_name != _SUPER else "superpods"
    axes = ctx.axes_tuple()
    # The outermost mesh axis carries the slow (cross-pod) leg; whatever
    # remains stays with the per-pod groups. Each derived level only pins
    # its axis when its group count is divisible by that axis's device
    # count (the paper's m | n rule) — P pod partials over an 8-way data
    # axis would otherwise fail sharding at trace time.
    super_axes = _axes_if_divisible(
        axes[0] if axes else None, num_supergroups, ctx.mesh
    )
    inner_axes = _axes_if_divisible(
        axes[1:] if len(axes) > 1 else None, per, ctx.mesh
    )
    nested = placement_lib.PlacementContext(
        placements=(
            placement_lib.Placement(super_name, num_supergroups, super_axes),
            placement_lib.Placement(inner_name, per, inner_axes),
        ),
        mesh=ctx.mesh,
        use_sharding_annotations=ctx.use_sharding_annotations,
        use_spmd_axis_name=ctx.use_spmd_axis_name,
    )

    regrouped = jax.tree_util.tree_map(
        lambda leaf: leaf.reshape(
            (num_supergroups, per) + leaf.shape[1:]
        ),
        tree,
    )
    with placement_lib.placement_context(nested):
        # stage 1: mean within each supergroup (fast leg) — a real reduce
        # primitive, so the partials carry the pod placement's sharding —
        # then stage 2: mean across supergroups (slow leg). Recognized
        # compressors take the fused reduce+compress path inside.
        return _staged_reduce(regrouped, nested, compress_fn, use_fused)


def int8_wire_ratio(block: int = 256) -> float:
    """Wire bytes of the packed int8 format as a fraction of f32 bytes.

    The packed format (``repro.compression``, PACK_COLS-block scheme; also
    ``models/tpcomm.int8_wire_bytes``) ships 1 byte per value plus one f32
    scale per ``block`` values: ``(1 + 4/block) / 4`` of the f32 payload —
    NOT the naive 0.25. For the default 256-block that is ~0.2539.
    """
    return (1.0 + 4.0 / block) / 4.0


def cross_pod_bytes(param_bytes: float, n: int, num_supergroups: int,
                    compress_ratio: float = 1.0,
                    compress: "str | None" = None) -> dict:
    """Napkin model: bytes crossing the slow (DCN) leg per round.

    ``compress="int8"`` applies the *actual* packed wire ratio
    (:func:`int8_wire_ratio`: payload + per-256-block f32 scales) instead of
    a hand-supplied ``compress_ratio`` — use it to match what the fused
    reduce+compress path really sends (the static analyzer's
    ``plan.comm_cost()`` models the same format from the IR; the two are
    pinned against each other in tests). ``compress_ratio`` remains for
    custom schemes and is ignored when ``compress`` is given.
    """
    if compress is not None:
        if compress != "int8":
            raise ValueError(f"unknown compress scheme: {compress!r}")
        compress_ratio = int8_wire_ratio()
    flat = n * param_bytes  # flat all-reduce moves every group's delta
    hier = num_supergroups * param_bytes * compress_ratio
    return {
        "flat_bytes": flat,
        "hierarchical_bytes": hier,
        "reduction_factor": flat / max(hier, 1e-9),
    }
