"""User-facing DrJAX API.

Mirrors the paper's authoring surface (Snippets 1–4):

.. code-block:: python

    from repro.core import api as drjax

    @drjax.program(partition_size=3)
    def broadcast_double_and_sum(x):
        y = drjax.broadcast(x)
        z = drjax.map_fn(lambda a: 2 * a, y)
        return drjax.reduce_sum(z)

Placements nest (hierarchical MapReduce): declare an ordered stack and
address individual levels with ``placement=``:

.. code-block:: python

    @drjax.program(placements={"pods": 2, "clients": 4})
    def hier_round(x):
        y = drjax.broadcast(x)                       # server -> (2, 4, ...)
        z = drjax.map_fn(lambda a: 2 * a, y)         # per-client compute
        partial = drjax.reduce_mean(z, placement="clients")   # (2, ...)
        return drjax.reduce_mean(partial, placement="pods")   # server

With no ``placement=``, ``broadcast``/``reduce_*`` span the whole stack (one
primitive per level), so single-placement programs are the unchanged
degenerate case.

All ops are pytree-polymorphic: partitioned *structures* are pytrees whose
every leaf carries the leading group axes (paper Fig. 2).

Every building block binds under the ``jax.named_scope``
``drjax.<op>[<placement>]`` (one per level a stack-spanning op binds; see
:func:`scope`), so the compiled program's ``op_name`` metadata, and a
profile read against it, name the primitive and level each instruction came
from.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Mapping, Optional

import jax
import jax.numpy as jnp

from . import placement as placement_lib
from . import primitives as prims
from . import sharding as sharding_lib

__all__ = [
    "program",
    "placement_context",
    "broadcast",
    "map_fn",
    "reduce_sum",
    "reduce_mean",
    "reduce_max",
    "reduce_weighted_mean",
    "masked_reduce_mean",
    "stage_transfer",
    "stage_map",
    "partition_size",
    "current_context",
]

placement_context = placement_lib.placement_context
current_context = placement_lib.current_context


def program(
    fn: Optional[Callable] = None,
    *,
    partition_size: Optional[int] = None,
    placements: Optional[Mapping[str, int]] = None,
    partition_axes=None,
    placement_kinds: Optional[Mapping[str, str]] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    use_sharding_annotations: bool = True,
    use_spmd_axis_name: bool = True,
):
    """Decorator declaring a DrJAX program.

    Either ``partition_size=n`` (paper API, one "clients" placement) or
    ``placements={"pods": P, "clients": m}`` (an ordered stack, outermost
    first — one entry is the upstream drjax API) must be given.

    ``partition_axes`` names the mesh axis/axes each placement's group axis
    shards over: a bare spec for a single placement (e.g. ``"data"`` or
    ``("pod", "data")``), or a mapping ``{placement_name: axes}`` for a
    stack (e.g. ``{"pods": "pod", "clients": "data"}`` — pods over the DCN
    axis, clients over ICI). ``None`` means purely logical partitioning with
    no sharding constraints (fine on CPU / single device).

    ``placement_kinds`` marks levels of the stack as pipeline *stages*
    rather than replicas, e.g. ``placements={"stages": 4, "clients": 8},
    placement_kinds={"stages": "stages"}``. Stage-kind levels communicate
    via :func:`stage_transfer` / :func:`stage_map` instead of
    broadcast/reduce. Unnamed levels default to ``"replicas"`` (today's
    behavior, unchanged).

    ``use_sharding_annotations=False`` reproduces the paper's DrJAX-NS
    ablation (Fig. 6).
    """
    if fn is not None:  # used as bare @program — not allowed, size required
        raise TypeError(
            "drjax.program requires a partition size: use "
            "@drjax.program(partition_size=n)."
        )
    if placements is not None and partition_size is not None:
        raise ValueError("Pass either partition_size or placements, not both.")
    if placements is None and partition_size is None:
        raise ValueError("partition_size (or placements) is required.")

    ctx = placement_lib.make_context(
        partition_size,
        placements=placements,
        partition_axes=partition_axes,
        placement_kinds=placement_kinds,
        mesh=mesh,
        use_sharding_annotations=use_sharding_annotations,
        use_spmd_axis_name=use_spmd_axis_name,
    )

    def deco(f: Callable) -> Callable:
        @functools.wraps(f)
        def wrapped(*args, **kwargs):
            with placement_lib.placement_context(ctx):
                return f(*args, **kwargs)

        wrapped.drjax_context = ctx  # introspection hook (tests, interpreter)
        return wrapped

    return deco


# ---------------------------------------------------------------------------
# building blocks (pytree-polymorphic)
# ---------------------------------------------------------------------------


def scope(op: str, levels: str):
    """The ``jax.named_scope`` a building block binds under:
    ``drjax.<op>[<levels>]``. Metadata only: it adds no equation. (XLA cuts
    an ``op_name`` at ``@``, so the placement goes in brackets.)"""
    return jax.named_scope(f"drjax.{op}[{levels}]")


def _ctx() -> placement_lib.PlacementContext:
    return placement_lib.current_context()


def _require_replica_stack(ctx: placement_lib.PlacementContext, op: str):
    """Default-span collectives only make sense on an all-replica stack."""
    stages = [n for n, k in zip(ctx.names, ctx.kinds) if k == "stages"]
    if stages:
        raise ValueError(
            f"{op} with no placement= spans the whole stack, but level(s) "
            f"{stages} are stage-kind (pipeline stages do not "
            f"broadcast/reduce — use stage_transfer/stage_map). Address a "
            f"replica-kind placement explicitly with placement=<name>."
        )


def broadcast(tree, placement: Optional[str] = None):
    """Replicate a structure to every group (paper §2, BB 1).

    With ``placement=p`` (stack index i) this is ONE broadcast primitive:
    depth-i operand → depth-(i+1) result. With no placement it spans the
    whole stack — server value → fully partitioned, one primitive per level
    (a single-placement program binds exactly one, as in the paper).
    """
    ctx = _ctx()
    if placement is None:
        _require_replica_stack(ctx, "broadcast")
        chain = ctx.names  # outermost first: server -> ... -> innermost
    else:
        chain = (placement,)

    def leaf(x):
        for name in chain:
            with scope("broadcast", name):
                x = prims.bind_broadcast(x, placement=name)
        return x

    return jax.tree_util.tree_map(leaf, tree)


def _reduce_tree(tree, op: str, binder, placement: Optional[str]):
    ctx = _ctx()
    if placement is None:
        _require_replica_stack(ctx, "reduce")
        chain = tuple(reversed(ctx.names))  # innermost first: -> server
    else:
        chain = (placement,)

    def leaf(x):
        for name in chain:
            with scope(op, name):
                x = binder(x, placement=name)
        return x

    return jax.tree_util.tree_map(leaf, tree)


def reduce_sum(tree, placement: Optional[str] = None):
    """Sum a partitioned structure over its groups (paper §2, BB 3).

    ``placement=p`` reduces that one level (depth i+1 → depth i); the
    default reduces the whole stack down to the server, innermost level
    first — on a nested stack this is automatically the hierarchical
    (two-stage) reduction."""
    return _reduce_tree(tree, "reduce_sum", prims.bind_reduce_sum, placement)


def reduce_mean(tree, placement: Optional[str] = None, *, dtype=None):
    """Average a partitioned structure over its groups (derived symbol).

    The stack-spanning default composes per-level means (equal group sizes
    make the mean-of-means the global mean).

    ``dtype`` (default: each leaf's own) is the dtype the groups are
    accumulated in and the result is given in, as for ``jnp.mean``: bf16
    parameters reduced with ``dtype=jnp.float32`` give their f32 mean, with
    no bf16 rounding of the sum. Where the placement names no mesh axis
    (all its groups on one device), the sum is formed from per-group slices
    upcast as they are read, which XLA fuses with the consumer of the mean
    into one elementwise pass (``primitives._group_sum``)."""
    binder = functools.partial(prims.bind_reduce_mean, dtype=dtype)
    return _reduce_tree(tree, "reduce_mean", binder, placement)


def reduce_max(tree, placement: Optional[str] = None):
    """Max over groups (extension primitive; sub-gradient AD)."""
    return _reduce_tree(tree, "reduce_max", prims.bind_reduce_max, placement)


def reduce_weighted_mean(tree, weights, placement: Optional[str] = None):
    """Weighted mean over groups: sum_i w_i x_i / sum_i w_i.

    ``weights`` is a partitioned array with one entry per group: shape
    ``(n,)`` for the flat API, or the stack-prefix shape (e.g. ``(P, m)``)
    when reducing a nested stack / an inner placement. Fully differentiable
    in both ``tree`` and ``weights`` — this is the reduction whose weights
    Rush et al. (2023) *learn* in tandem with training (paper §6,
    self-tuning algorithms).

    When every weight is zero (e.g. a straggler mask that dropped the whole
    cohort) the reduction returns zeros rather than 0/0 = NaN, so a fully
    dropped round leaves the server params untouched instead of poisoning
    them.
    """
    return _weighted_mean(tree, weights, placement, "reduce_weighted_mean")


def _weighted_mean(tree, weights, placement: Optional[str], op: str):
    """:func:`reduce_weighted_mean`, bound under the scope
    ``drjax.<op>[<levels>]`` (the levels it reduces, outermost first)."""
    ctx = _ctx()
    weights = jnp.asarray(weights)
    if placement is None:
        _require_replica_stack(ctx, op)
        chain = tuple(reversed(ctx.names))
        depth_in, depth_out = ctx.depth, 0
    else:
        i = ctx.index_of(placement)
        chain = (placement,)
        depth_in, depth_out = i + 1, i
    expected = ctx.sizes[:depth_in]
    if weights.shape != expected:
        raise ValueError(
            f"reduce_weighted_mean: weights have shape {weights.shape}, but "
            f"the reduction over placement(s) {list(ctx.names[:depth_in])} "
            f"needs one weight per group: expected shape {expected}."
        )

    def rsum(x):
        for name in chain:
            x = prims.bind_reduce_sum(x, placement=name)
        return x

    def leaf(x):
        if x.ndim < depth_in or x.shape[:depth_in] != expected:
            raise ValueError(
                f"reduce_weighted_mean: weights of shape {weights.shape} do "
                f"not match a leaf of shape {x.shape}: the leaf's leading "
                f"{'axis' if depth_in == 1 else f'{depth_in} axes'} must be "
                f"the group axes {expected} (one entry per group of "
                f"placement(s) {list(ctx.names[:depth_in])})."
            )
        w = weights.reshape(expected + (1,) * (x.ndim - depth_in))
        s = rsum(x * w)
        dropped = all_dropped.reshape(
            all_dropped.shape + (1,) * (s.ndim - depth_out)
        )
        denom_b = safe_denom.reshape(
            safe_denom.shape + (1,) * (s.ndim - depth_out)
        )
        return jnp.where(dropped, jnp.zeros_like(s), s / denom_b)

    with scope(op, "+".join(reversed(chain))):
        denom = rsum(weights)
        all_dropped = denom == 0
        safe_denom = jnp.where(all_dropped, jnp.ones_like(denom), denom)
        return jax.tree_util.tree_map(leaf, tree)


def masked_reduce_mean(tree, mask, placement: Optional[str] = None):
    """Mean over the groups with ``mask == 1`` (straggler-dropping reduce).

    Over-provisioning + deadline-dropping is the natural straggler mitigation
    under MapReduce semantics: sample ``n`` groups, reduce over whichever
    ``k <= n`` arrive. The mask enters as weights, so the reduction stays
    differentiable and stays within the DrJAX primitive set. An all-zero mask
    (every straggler dropped) yields zeros, not NaN.
    """
    return _weighted_mean(tree, mask, placement, "masked_reduce_mean")


def _fused_spmd_names(ctx: placement_lib.PlacementContext):
    """The combined ``spmd_axis_name`` for one vmap spanning the whole stack.

    Returns ``(ok, names)``: fusable when every level contributes mesh axes
    (the collapsed group axis shards over their concatenation, outermost
    first — the same device layout as the nested form) or when no level does
    (purely logical). A mix is not expressible as one vmap annotation, so
    the caller falls back to nested vmaps.
    """
    per_level = [ctx.spmd_axis_name_for(name) for name in ctx.names]
    if all(n is None for n in per_level):
        return True, None
    if any(n is None for n in per_level):
        return False, None
    names = []
    for n in per_level:
        names.extend(n if isinstance(n, (tuple, list)) else (n,))
    return True, tuple(names)


def map_fn(fn: Callable, tree, placement: Optional[str] = None,
           fuse: Optional[bool] = None):
    """Apply ``fn`` pointwise across the groups of a partition (paper §2, BB 2).

    ``tree`` is a partitioned structure; if it is a *tuple*, its elements are
    passed to ``fn`` as separate positional arguments (paper Snippet 4).

    Implemented as ``jax.vmap`` over the addressed placement's axis with
    that placement's ``spmd_axis_name`` — vmap's SPMD axis name is what
    installs the paper's *dynamic* sharding annotations on every intermediate
    of the mapped computation, which Fig. 6 shows to be load-bearing for weak
    scaling. With no ``placement``, the map spans every level of the stack:
    the group axes are collapsed into one and a SINGLE vmap runs over the
    collapsed axis with the levels' spmd axis names combined, so GSPMD sees
    one sharded loop nest instead of ``depth`` nested ones (``fn`` still sees
    one group's slice). ``fuse=False`` forces the nested per-level vmaps
    (bitwise-identical results); the fusion also falls back to them when the
    levels' mesh-axis annotations cannot be merged into one. The mapped
    computation itself is inlined into the jaxpr, exactly as in paper
    Snippet 5.

    The map binds under the scope ``drjax.map[<placement>]``, or
    ``drjax.map[<outermost>+...+<innermost>]`` when it spans the stack.
    """
    ctx = placement_lib.current_context()
    levels = "+".join(ctx.names) if placement is None else placement
    with scope("map", levels):
        return _map_fn(ctx, fn, tree, placement, fuse)


def _map_fn(ctx, fn: Callable, tree, placement: Optional[str],
            fuse: Optional[bool]):
    if isinstance(tree, tuple):
        f = lambda args: fn(*args)
    else:
        f = fn
    if placement is None:
        depth = ctx.depth
        fusable, fused_names = (
            _fused_spmd_names(ctx) if depth >= 2 and fuse is not False
            else (False, None)
        )
        if fusable:
            sizes = tuple(ctx.sizes)
            total = ctx.total_size()

            def collapse(x):
                if x.ndim < depth or x.shape[:depth] != sizes:
                    raise ValueError(
                        f"map_fn: a mapped leaf of shape {x.shape} does not "
                        f"carry the stack's group axes {sizes} as its "
                        "leading axes."
                    )
                return x.reshape((total,) + x.shape[depth:])

            fv = jax.vmap(f, in_axes=0, out_axes=0,
                          spmd_axis_name=fused_names)
            out = fv(jax.tree_util.tree_map(collapse, tree))
            out = jax.tree_util.tree_map(
                lambda x: x.reshape(sizes + x.shape[1:]), out
            )
            return sharding_lib.constrain_tree(
                out, ctx, partitioned=True, depth=depth
            )
        # Nested form: wrap innermost level first so the outermost
        # placement's vmap is the outermost transform; each level annotates
        # with its own mesh axes.
        for name in reversed(ctx.names):
            f = jax.vmap(
                f, in_axes=0, out_axes=0,
                spmd_axis_name=ctx.spmd_axis_name_for(name),
            )
    else:
        i = ctx.index_of(placement)
        depth = i + 1
        f = jax.vmap(
            f, in_axes=i, out_axes=i,
            spmd_axis_name=ctx.spmd_axis_name_for(placement),
        )
    out = f(tree)
    return sharding_lib.constrain_tree(out, ctx, partitioned=True, depth=depth)


# ---------------------------------------------------------------------------
# pipeline-stage building blocks (stage-kind placements)
# ---------------------------------------------------------------------------


def _stage_placement_name(
    ctx: placement_lib.PlacementContext, placement: Optional[str]
) -> str:
    """Resolve the addressed stage-kind placement (unique default)."""
    if placement is not None:
        pl = ctx.get(placement)
        if pl.kind != "stages":
            raise ValueError(
                f"placement {placement!r} is {pl.kind!r}-kind, but this op "
                "requires a stage-kind placement (declare it with "
                "placement_kinds={" + f"{placement!r}: 'stages'" + "})."
            )
        return placement
    stages = ctx.stage_names()
    if not stages:
        raise ValueError(
            "no stage-kind placement in the ambient stack: declare one with "
            "placement_kinds={<name>: 'stages'}."
        )
    if len(stages) > 1:
        raise ValueError(
            f"multiple stage-kind placements {stages}: address one "
            "explicitly with placement=<name>."
        )
    return stages[0]


def stage_transfer(tree, placement: Optional[str] = None, *,
                   shift: int = 1, wrap: bool = False):
    """Shift a stage-partitioned structure to neighboring stages.

    ``out[..., j, ...] = x[..., j - shift, ...]`` along the addressed
    stage-kind placement's group axis — stage ``j``'s activations move to
    stage ``j + shift`` (the forward pipeline hand-off for ``shift=1``).
    Vacated boundary stages receive zeros unless ``wrap=True`` (ring).
    Linear, so the transpose is the reverse transfer (``-shift``): the
    backward pipeline schedule falls out of AD. Lowers to a
    collective-permute between stage shards when the stage level pins a
    mesh axis.
    """
    ctx = _ctx()
    name = _stage_placement_name(ctx, placement)
    with scope("stage_transfer", name):
        return jax.tree_util.tree_map(
            lambda x: prims.bind_stage_transfer(
                x, placement=name, shift=shift, wrap=wrap
            ),
            tree,
        )


def stage_map(fns, tree, placement: Optional[str] = None):
    """Apply per-stage functions across a stage-partitioned structure.

    ``fns`` is either one callable (applied at every stage — this is just
    :func:`map_fn` over the stage placement) or a sequence with one callable
    per stage (heterogeneous pipeline stages: stage ``s`` runs ``fns[s]`` on
    its slice). As with :func:`map_fn`, a *tuple* ``tree`` passes its
    elements as separate positional arguments. Results are re-stacked along
    the stage axis and re-constrained to the stage level's sharding.
    """
    ctx = _ctx()
    name = _stage_placement_name(ctx, placement)
    if callable(fns):
        return map_fn(fns, tree, placement=name)
    fns = tuple(fns)
    i = ctx.index_of(name)
    size = ctx.get(name).size
    if len(fns) != size:
        raise ValueError(
            f"stage_map: got {len(fns)} stage functions for placement "
            f"{name!r} of {size} stages (pass one callable to apply it at "
            "every stage)."
        )

    def run_stage(s: int):
        fn = fns[s]
        f = (lambda args: fn(*args)) if isinstance(tree, tuple) else fn
        # Levels outside the stage axis stay mapped: wrap innermost first so
        # the outermost placement's vmap is the outermost transform.
        for lvl in range(i - 1, -1, -1):
            f = jax.vmap(
                f, in_axes=0, out_axes=0,
                spmd_axis_name=ctx.spmd_axis_name_for(ctx.names[lvl]),
            )
        sliced = jax.tree_util.tree_map(
            lambda x: x[(slice(None),) * i + (s,)], tree
        )
        return f(sliced)

    with scope("stage_map", name):
        outs = [run_stage(s) for s in range(size)]
        out = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, axis=i), *outs
        )
        return sharding_lib.constrain_tree(out, ctx, partitioned=True,
                                           depth=i + 1)


def partition_size(placement: Optional[str] = None) -> int:
    """Number of groups: one placement's size, or (default) the total number
    of innermost groups across the whole ambient stack."""
    ctx = placement_lib.current_context()
    if placement is None:
        return ctx.total_size()
    return ctx.get(placement).size
