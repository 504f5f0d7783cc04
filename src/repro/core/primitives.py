"""DrJAX MapReduce building blocks as JAX primitives.

Embeds ``broadcast``, ``reduce_sum``, ``reduce_mean`` (and a ``reduce_max``
extension) as first-class :class:`jax.extend.core.Primitive` symbols, exactly
as the paper describes (§3 "Implementation"):

* **impl / abstract-eval / MLIR lowering** — the primitives are entirely
  replaced by plain XLA ops by the time JAX dispatches to a runtime, so DrJAX
  programs are ordinary pjit-able programs.
* **JVP + transpose rules** — the derivative of a DrJAX primitive is again a
  DrJAX primitive (MapReduce AD, Rush et al. 2023): ``broadcast`` and
  ``reduce_sum`` are each other's transposes; ``reduce_mean`` transposes to a
  scaled ``broadcast``.
* **batching rules** — primitives survive ``jax.vmap``, so outer-loop
  transforms (hyperparameter sweeps, per-example clipping) compose.
* **sharding annotations** — each primitive's lowering constrains the leading
  (partition) axes onto the mesh axes of the ambient
  :class:`~repro.core.placement.PlacementContext` (static annotations). The
  context travels in the primitive *params*, so annotations survive into
  transpose rules that fire outside the user's trace (e.g. inside
  ``jax.grad``'s backward pass).

Every primitive is *placement-addressed*: it binds with a ``placement``
param naming one level of the placement stack (default: innermost). For a
placement at stack index ``i``, ``broadcast`` takes a value partitioned at
the ``i`` outer placements (depth i) and inserts that placement's group axis
at position ``i`` (depth i+1); ``reduce_*`` removes it. The bound placement
travels in the params alongside the context, so AD transposes
(broadcast-at-p ↔ reduce_sum-at-p) and batching stay placement-correct.

Partitioned values are arrays whose leading axes are the group axes of a
stack *prefix* (paper Fig. 1; depth k == k leading group axes); all
primitives here operate on single arrays and are mapped over pytrees by
:mod:`repro.core.api`.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import core
from jax.extend.core import Primitive
from jax.interpreters import ad, batching, mlir
from jax.sharding import PartitionSpec as P

from repro import compat

from . import placement as placement_lib
from . import sharding as sharding_lib

__all__ = [
    "broadcast_p",
    "reduce_sum_p",
    "reduce_mean_p",
    "reduce_max_p",
    "stage_transfer_p",
    "bind_broadcast",
    "bind_reduce_sum",
    "bind_reduce_mean",
    "bind_reduce_max",
    "bind_stage_transfer",
    "DRJAX_PRIMITIVES",
    "COMMUNICATION_PRIMITIVES",
]


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _resolve(
    pctx: placement_lib.PlacementContext, placement: Optional[str]
) -> Tuple[placement_lib.Placement, int]:
    """The addressed placement and its stack index (None = innermost)."""
    idx = pctx.index_of(placement)
    return pctx.placements[idx], idx


def _check_operand_depth(
    x_aval, pctx: placement_lib.PlacementContext, depth: int, prim: str
):
    """Operand must carry the ``depth`` outermost placements' group axes."""
    if x_aval.ndim < depth:
        raise ValueError(
            f"drjax.{prim} at placement "
            f"'{pctx.placements[depth - 1].name}' expects a value partitioned "
            f"at the {depth} outer placement(s) "
            f"{list(pctx.names[:depth])}; got a "
            f"{'scalar' if x_aval.ndim == 0 else f'rank-{x_aval.ndim} array'}."
        )
    for j in range(depth):
        pl = pctx.placements[j]
        if x_aval.shape[j] != pl.size:
            raise ValueError(
                f"drjax.{prim}: axis {j} ({x_aval.shape[j]}) does not match "
                f"the partition size ({pl.size}) of placement "
                f"'{pl.name}'. Partitioned values must carry one leading "
                f"entry per group at every placement of the stack prefix."
            )


def _check_kind(pl: placement_lib.Placement, prim: str, expect: str):
    """Replica collectives only address replica-kind placements; transfer
    only stage-kind ones (wrong-kind communication, rejected at trace time)."""
    if pl.kind != expect:
        other = ("stage_transfer/stage_map" if expect == "replicas"
                 else "broadcast/reduce")
        raise ValueError(
            f"drjax.{prim} cannot address placement '{pl.name}' of kind "
            f"'{pl.kind}' (expects a '{expect}'-kind placement; "
            f"'{pl.kind}' levels communicate via {other})."
        )


# ---------------------------------------------------------------------------
# broadcast
# ---------------------------------------------------------------------------

broadcast_p = Primitive("drjax_broadcast")


def _broadcast_impl(
    x, *, pctx: placement_lib.PlacementContext, placement: Optional[str] = None
):
    pl, i = _resolve(pctx, placement)
    _check_kind(pl, "broadcast", "replicas")  # eager binds skip abstract
    out = jnp.broadcast_to(
        jnp.expand_dims(x, i), x.shape[:i] + (pl.size,) + x.shape[i:]
    )
    return sharding_lib.constrain_partitioned(out, pctx, depth=i + 1)


def _broadcast_abstract(x, *, pctx, placement=None):
    pl, i = _resolve(pctx, placement)
    _check_kind(pl, "broadcast", "replicas")
    _check_operand_depth(x, pctx, i, "broadcast")
    return core.ShapedArray(
        x.shape[:i] + (pl.size,) + x.shape[i:], x.dtype
    )


broadcast_p.def_impl(_broadcast_impl)
broadcast_p.def_abstract_eval(_broadcast_abstract)
mlir.register_lowering(
    broadcast_p, mlir.lower_fun(_broadcast_impl, multiple_results=False)
)


def _broadcast_jvp(primals, tangents, *, pctx, placement=None):
    (x,), (t,) = primals, tangents
    out = broadcast_p.bind(x, pctx=pctx, placement=placement)
    if isinstance(t, ad.Zero):
        t_out = ad.Zero(core.get_aval(out).to_tangent_aval())
    else:
        t_out = broadcast_p.bind(t, pctx=pctx, placement=placement)
    return out, t_out


ad.primitive_jvps[broadcast_p] = _broadcast_jvp


def _broadcast_transpose(ct, x, *, pctx, placement=None):
    # d(broadcast@p)^T = reduce_sum@p  (MapReduce AD closure; Rush et al. 2023)
    if isinstance(ct, ad.Zero):
        return (ad.Zero(x.aval),)
    return (reduce_sum_p.bind(ct, pctx=pctx, placement=placement),)


ad.primitive_transposes[broadcast_p] = _broadcast_transpose


def _broadcast_batch(args, dims, *, pctx, placement=None):
    (x,), (d,) = args, dims
    if d is batching.not_mapped:
        return broadcast_p.bind(x, pctx=pctx, placement=placement), d
    # Move the batch axis to the end so the placement-prefix axes stay
    # leading (the addressed placement inserts its axis among them),
    # preserving the primitive under vmap.
    x = jnp.moveaxis(x, d, x.ndim - 1)
    out = broadcast_p.bind(x, pctx=pctx, placement=placement)
    return out, out.ndim - 1


batching.primitive_batchers[broadcast_p] = _broadcast_batch


# ---------------------------------------------------------------------------
# reductions (shared machinery)
# ---------------------------------------------------------------------------


def _spec_entry(pl: placement_lib.Placement):
    """A placement's entry in a PartitionSpec: its mesh axis, or axes."""
    axes = pl.axes_tuple()
    return (axes if len(axes) > 1 else axes[0]) if axes else None


def _fused_compress_reduce(x, i, name: str, compress: str, qaxis: int,
                           pctx: placement_lib.PlacementContext):
    """Execute a ``compress``-tagged reduction: the fused single-pass
    reduce+roundtrip (Pallas kernel on TPU, fused jnp oracle elsewhere —
    see ``repro.kernels.ops.reduce_compress_roundtrip``).

    On a mesh the kernel runs per shard, inside a shard_map: GSPMD cannot
    partition a Mosaic kernel. Where the reduced groups are themselves
    sharded, each shard sums its groups, an all-reduce completes the mean,
    and the kernel quantizes that one f32 partial (a group count of 1), as
    the one-device kernel quantizes its f32 mean. The axes after the reduced
    one must be unsharded: each shard receives them whole."""
    if name != "reduce_mean" or compress != "int8":
        raise NotImplementedError(
            f"drjax.{name}: fused compress={compress!r} is only implemented "
            "for reduce_mean with int8 (the hierarchical fast path)."
        )
    from repro.kernels import ops as kernel_ops  # lazy: keep core import-light

    if pctx.mesh is None or not pctx.use_sharding_annotations:
        return kernel_ops.reduce_compress_roundtrip(x, axis=i, qaxis=qaxis)

    lead = [_spec_entry(pl) for pl in pctx.placements[: i + 1]]
    reduced = pctx.placements[i].axes_tuple()
    groups = x.shape[i]

    def per_shard(xs):
        if not reduced:
            return kernel_ops.reduce_compress_roundtrip(xs, axis=i, qaxis=qaxis)
        part = jax.lax.psum(jnp.sum(xs.astype(jnp.float32), axis=i), reduced)
        back = kernel_ops.reduce_compress_roundtrip(
            jnp.expand_dims(part * (1.0 / groups), i), axis=i, qaxis=qaxis
        )
        return back.astype(xs.dtype)

    rest = [None] * (x.ndim - i - 1)
    return compat.shard_map(
        per_shard, mesh=pctx.mesh,
        in_specs=(P(*lead, *rest),), out_specs=P(*lead[:i], *rest),
        check=False,
    )(x)


def _wire_engages(pctx: placement_lib.PlacementContext,
                  pl: placement_lib.Placement, dtype) -> bool:
    """Does a ``wire="int8"`` tag change what a reduce_mean sends? Only on a
    mesh with sharding annotations, at a placement that names mesh axes, on
    an f32 operand: a bf16 cast of the roundtrip is off the int8 grid, and
    without sharded groups nothing crosses a wire."""
    return (pctx.mesh is not None and pctx.use_sharding_annotations
            and bool(pl.axes_tuple()) and np.dtype(dtype) == np.float32)


def _int8_wire_mean(x, i, pctx: placement_lib.PlacementContext):
    """Execute a ``wire="int8"``-tagged reduce_mean: each group's rows along
    the last axis are already ``q * s`` (the output of the int8 roundtrip),
    so the groups cross the placement's mesh axes as the int8 payload and
    one f32 scale a row, not as f32 rows.

    Per shard, inside a shard_map (GSPMD cannot partition a Mosaic kernel):
    the inner leg's quantizer (``reduce_compress`` over one group) gives the
    rows' ``q`` back exactly and their scales to an ulp or two; ``q`` and
    the scales, lane-dense (an ``(R, 1)`` f32 array takes 128 lanes a row
    in HBM), reach every device of the placement's axes; and
    ``dequant_accumulate`` dequantizes and takes the mean over the groups
    in one pass, in the groups' order on every device.

    The payload travels as an all-to-all of one copy per device, which is
    an all-gather's exchange: XLA runs an all-to-all synchronously, where
    it would schedule an all-gather as an asynchronous pair and reorder the
    rest of the program around it (on TPU that reordering packs the round's
    buffers worse, at the same peak of live bytes)."""
    from repro.kernels import ops as kernel_ops  # lazy: keep core import-light

    lead = [_spec_entry(pl) for pl in pctx.placements[: i + 1]]
    devices = math.prod(pctx.mesh.shape[a]
                        for a in pctx.placements[i].axes_tuple())
    cols = x.shape[-1]

    def gather(v):
        copies = jnp.broadcast_to(v, (devices,) + v.shape)
        return jax.lax.all_to_all(copies.reshape((-1,) + v.shape[1:]),
                                  lead[i], 0, 0, tiled=True)

    def per_shard(xs):
        groups = jnp.moveaxis(xs, i, 0)
        rows = groups.reshape(groups.shape[0], 1, -1, cols)
        q, s = jax.vmap(kernel_ops.reduce_compress)(rows)
        mean = kernel_ops.dequant_accumulate(gather(q), gather(s[:, 0]))
        return mean.reshape(groups.shape[1:])

    rest = [None] * (x.ndim - i - 1)
    return compat.shard_map(
        per_shard, mesh=pctx.mesh,
        in_specs=(P(*lead, *rest),), out_specs=P(*lead[:i], *rest),
        check=False,
    )(x)


# Up to this many groups on one device, an accumulating sum is a tree of
# per-group slices; beyond it, the reduce's one extra write is a small share
# of reading the groups, and the tree would bloat the program.
_SLICE_SUM_MAX_GROUPS = 16


def _group_sum(x, pl: placement_lib.Placement, i: int, dtype=None):
    """Sum of ``x`` over its group axis ``i``, accumulated in ``dtype``
    (default: ``x``'s own).

    With an accumulation dtype and a placement that names no mesh axis,
    every group lives on this device, and the sum is a pairwise tree of
    the groups' slices, each upcast as it is read. XLA then sees
    elementwise work that fuses with its consumers (the server step) into
    one pass over the operand; a reduce would be a fusion root that writes
    its upcast result out. A sharded placement keeps the reduce, which
    GSPMD lowers to an all-reduce, as do more than
    ``_SLICE_SUM_MAX_GROUPS`` groups."""
    if dtype is None:
        return jnp.sum(x, axis=i)
    if pl.axes_tuple() or x.shape[i] > _SLICE_SUM_MAX_GROUPS:
        return jnp.sum(x, axis=i, dtype=dtype)
    parts = [jax.lax.index_in_dim(x, k, i, keepdims=False).astype(dtype)
             for k in range(x.shape[i])]
    while len(parts) > 1:
        pairs = [a + b for a, b in zip(parts[0::2], parts[1::2])]
        parts = pairs + parts[len(pairs) * 2:]
    return parts[0]


def _make_reduction(name: str, reduce_fn):
    p = Primitive(f"drjax_{name}")

    def impl(x, *, pctx: placement_lib.PlacementContext, placement=None,
             compress=None, qaxis=-1, dtype=None, wire=None):
        pl, i = _resolve(pctx, placement)
        _check_kind(pl, name, "replicas")  # eager binds skip abstract
        if compress is not None:
            out = _fused_compress_reduce(x, i, name, compress, qaxis, pctx)
        elif wire is not None:
            out = _int8_wire_mean(x, i, pctx)
        else:
            out = reduce_fn(x, pl, i, dtype)
        if i == 0:
            return sharding_lib.constrain_replicated(out, pctx)
        return sharding_lib.constrain_partitioned(out, pctx, depth=i)

    def abstract(x, *, pctx, placement=None, compress=None, qaxis=-1,
                 dtype=None, wire=None):
        pl, i = _resolve(pctx, placement)
        _check_kind(pl, name, "replicas")
        _check_operand_depth(x, pctx, i + 1, name)
        return core.ShapedArray(x.shape[:i] + x.shape[i + 1 :],
                                x.dtype if dtype is None else dtype)

    p.def_impl(impl)
    p.def_abstract_eval(abstract)
    mlir.register_lowering(p, mlir.lower_fun(impl, multiple_results=False))

    def batch(args, dims, *, pctx, placement=None, compress=None, qaxis=-1,
              dtype=None, wire=None):
        (x,), (d,) = args, dims
        extra = {} if dtype is None else {"dtype": dtype}
        if wire is not None and d is batching.not_mapped:
            # A mapped axis lands last (below), where the int8 rows were:
            # the plain mean then runs, which has the tag's values.
            extra["wire"] = wire
        if compress is not None:
            # The batch axis lands at the end (below), so a from-the-end
            # quantization axis shifts one step deeper; a from-the-front one
            # is untouched.
            mapped = d is not batching.not_mapped and qaxis < 0
            extra.update(compress=compress,
                         qaxis=qaxis - 1 if mapped else qaxis)
        if d is batching.not_mapped:
            return p.bind(x, pctx=pctx, placement=placement, **extra), d
        # Logical operand: (sizes-prefix, *rest); physical batch dim at d.
        # Move the batch axis to the end so the partition axes stay leading,
        # preserving the primitive (and hence jaxpr interpretability) under
        # vmap.
        x = jnp.moveaxis(x, d, x.ndim - 1)
        out = p.bind(x, pctx=pctx, placement=placement, **extra)
        return out, out.ndim - 1

    batching.primitive_batchers[p] = batch
    return p


reduce_sum_p = _make_reduction("reduce_sum", _group_sum)
reduce_mean_p = _make_reduction(
    "reduce_mean",
    lambda x, pl, i, dtype: _group_sum(x, pl, i, dtype) / pl.size,
)
reduce_max_p = _make_reduction(
    "reduce_max", lambda x, pl, i, dtype: jnp.max(x, axis=i)
)


def _linear_reduction_jvp(p):
    def jvp(primals, tangents, *, pctx, placement=None, dtype=None,
            **fused):
        # ``fused`` carries compress/qaxis on the int8 fast-path eqn, or
        # the execution-only ``wire`` tag. The primal keeps them (fused
        # execution); the tangent drops them: the roundtrip is straight-
        # through under MapReduce AD, so d(fused reduce_mean@p) ==
        # d(reduce_mean@p) and grad matches the unfused composition
        # exactly. An accumulation ``dtype`` is the output's dtype, so the
        # tangent keeps it.
        (x,), (t,) = primals, tangents
        acc = {} if dtype is None else {"dtype": dtype}
        out = p.bind(x, pctx=pctx, placement=placement, **acc, **fused)
        if isinstance(t, ad.Zero):
            t_out = ad.Zero(core.get_aval(out).to_tangent_aval())
        else:
            t_out = p.bind(t, pctx=pctx, placement=placement, **acc)
        return out, t_out

    return jvp


ad.primitive_jvps[reduce_sum_p] = _linear_reduction_jvp(reduce_sum_p)
ad.primitive_jvps[reduce_mean_p] = _linear_reduction_jvp(reduce_mean_p)


def _reduce_sum_transpose(ct, x, *, pctx, placement=None, **fused):
    # d(reduce_sum@p)^T = broadcast@p
    if isinstance(ct, ad.Zero):
        return (ad.Zero(x.aval),)
    return (broadcast_p.bind(ct, pctx=pctx, placement=placement),)


def _reduce_mean_transpose(ct, x, *, pctx, placement=None, **fused):
    # d(reduce_mean@p)^T = broadcast@p / size(p). A compress- or wire-tagged
    # eqn transposes identically: the int8 roundtrip is straight-through,
    # and the wire changes only the encoding. A mean
    # that accumulated in another dtype hands back its operand's.
    if isinstance(ct, ad.Zero):
        return (ad.Zero(x.aval),)
    pl, _ = _resolve(pctx, placement)
    ct = ct / pl.size
    if ct.dtype != x.aval.dtype:
        ct = ct.astype(x.aval.dtype)
    return (broadcast_p.bind(ct, pctx=pctx, placement=placement),)


ad.primitive_transposes[reduce_sum_p] = _reduce_sum_transpose
ad.primitive_transposes[reduce_mean_p] = _reduce_mean_transpose


def _reduce_max_jvp(primals, tangents, *, pctx, placement=None):
    """Sub-gradient JVP for the (non-linear) max reduction.

    The tangent flows from the arg-max group. Expressed with reduce_sum of a
    masked tangent so that reverse-mode stays inside the DrJAX primitive set
    (the mask is constant wrt differentiation).
    """
    (x,), (t,) = primals, tangents
    _, i = _resolve(pctx, placement)
    out = reduce_max_p.bind(x, pctx=pctx, placement=placement)
    if isinstance(t, ad.Zero):
        return out, ad.Zero(core.get_aval(out).to_tangent_aval())
    hit = (x == jnp.expand_dims(out, i)).astype(x.dtype)
    hit = hit / jnp.maximum(jnp.sum(hit, axis=i, keepdims=True), 1)
    t_out = reduce_sum_p.bind(hit * t, pctx=pctx, placement=placement)
    return out, t_out


ad.primitive_jvps[reduce_max_p] = _reduce_max_jvp


# ---------------------------------------------------------------------------
# stage_transfer (stage-kind placements: pipeline neighbor exchange)
# ---------------------------------------------------------------------------

stage_transfer_p = Primitive("drjax_stage_transfer")


def _stage_transfer_impl(
    x, *, pctx: placement_lib.PlacementContext, placement=None,
    shift: int = 1, wrap: bool = False,
):
    pl, i = _resolve(pctx, placement)
    _check_kind(pl, "stage_transfer", "stages")  # eager binds skip abstract
    # out[..., s, ...] = x[..., s - shift, ...]: every stage ships its slice
    # to its (shift)-th neighbor. With wrap=False the boundary slots are
    # zero-filled — the linear map whose transpose is the reverse shift, so
    # MapReduce AD yields the backward pipeline for free. Under a mesh the
    # depth-(i+1) constraint keeps the stage axis pinned, and GSPMD lowers
    # the shift to a collective-permute (ppermute-style) neighbor exchange.
    out = jnp.roll(x, shift, axis=i)
    if not wrap:
        src = jnp.arange(pl.size) - shift
        valid = (src >= 0) & (src < pl.size)
        valid = valid.reshape(
            (1,) * i + (pl.size,) + (1,) * (x.ndim - i - 1)
        )
        out = jnp.where(valid, out, jnp.zeros_like(out))
    return sharding_lib.constrain_partitioned(out, pctx, depth=i + 1)


def _stage_transfer_abstract(x, *, pctx, placement=None, shift=1, wrap=False):
    pl, i = _resolve(pctx, placement)
    _check_kind(pl, "stage_transfer", "stages")
    _check_operand_depth(x, pctx, i + 1, "stage_transfer")
    return core.ShapedArray(x.shape, x.dtype)


stage_transfer_p.def_impl(_stage_transfer_impl)
stage_transfer_p.def_abstract_eval(_stage_transfer_abstract)
mlir.register_lowering(
    stage_transfer_p,
    mlir.lower_fun(_stage_transfer_impl, multiple_results=False),
)


def _stage_transfer_jvp(primals, tangents, *, pctx, placement=None,
                        shift=1, wrap=False):
    (x,), (t,) = primals, tangents
    out = stage_transfer_p.bind(
        x, pctx=pctx, placement=placement, shift=shift, wrap=wrap
    )
    if isinstance(t, ad.Zero):
        t_out = ad.Zero(core.get_aval(out).to_tangent_aval())
    else:
        t_out = stage_transfer_p.bind(
            t, pctx=pctx, placement=placement, shift=shift, wrap=wrap
        )
    return out, t_out


ad.primitive_jvps[stage_transfer_p] = _stage_transfer_jvp


def _stage_transfer_transpose(ct, x, *, pctx, placement=None, shift=1,
                              wrap=False):
    # d(transfer shift)^T = transfer -shift: cotangents flow stage s+shift
    # -> stage s, the backward pipeline's reverse neighbor exchange (with
    # wrap, the reverse rotation).
    if isinstance(ct, ad.Zero):
        return (ad.Zero(x.aval),)
    return (
        stage_transfer_p.bind(
            ct, pctx=pctx, placement=placement, shift=-shift, wrap=wrap
        ),
    )


ad.primitive_transposes[stage_transfer_p] = _stage_transfer_transpose


def _stage_transfer_batch(args, dims, *, pctx, placement=None, shift=1,
                          wrap=False):
    (x,), (d,) = args, dims
    if d is batching.not_mapped:
        return (
            stage_transfer_p.bind(
                x, pctx=pctx, placement=placement, shift=shift, wrap=wrap
            ),
            d,
        )
    # Batch axis to the end so the placement-prefix axes stay leading.
    x = jnp.moveaxis(x, d, x.ndim - 1)
    out = stage_transfer_p.bind(
        x, pctx=pctx, placement=placement, shift=shift, wrap=wrap
    )
    return out, out.ndim - 1


batching.primitive_batchers[stage_transfer_p] = _stage_transfer_batch


# ---------------------------------------------------------------------------
# user-facing single-leaf binders (one primitive at one placement)
# ---------------------------------------------------------------------------


def _ctx() -> placement_lib.PlacementContext:
    return placement_lib.current_context()


def _bind_params(placement: Optional[str]):
    """Resolve the addressed placement to its concrete name at bind time so
    the eqn params carry an explicit placement tag (the §5 interpreter reads
    it back without re-resolving defaults)."""
    ctx = _ctx()
    return dict(pctx=ctx, placement=ctx.get(placement).name)


def bind_broadcast(x, placement: Optional[str] = None):
    x = jnp.asarray(x)
    return broadcast_p.bind(x, **_bind_params(placement))


def bind_reduce_sum(x, placement: Optional[str] = None):
    return reduce_sum_p.bind(x, **_bind_params(placement))


def bind_reduce_mean(x, placement: Optional[str] = None, *,
                     compress: Optional[str] = None, qaxis: int = -1,
                     dtype=None):
    """``compress="int8"`` tags the eqn for the fused single-pass
    reduce+roundtrip execution (``qaxis`` = the partial's axis that carries
    the per-row-block scales). ``dtype`` is the accumulation and output
    dtype (see :func:`_group_sum`). The params are only attached when set,
    so plain reductions keep their exact eqn signature."""
    extra = {} if dtype is None else {"dtype": np.dtype(dtype)}
    if compress is not None:
        extra.update(compress=compress, qaxis=qaxis)
    return reduce_mean_p.bind(x, **extra, **_bind_params(placement))


def _bind_roundtrip_mean(x, placement: str):
    """The plain mean of ``x``, tagged ``wire="int8"`` where the tag changes
    what crosses the wire (:func:`_wire_engages`; :func:`_int8_wire_mean`).

    Only ``hierarchical._staged_reduce`` binds it: ``x`` has to be the
    output of ``bind_reduce_mean(..., compress="int8")`` at ``qaxis=-1``,
    every row along its last axis ``q * s``. Nothing here can check that,
    and any other operand would be quantized on the wire."""
    params = _bind_params(placement)
    ctx = params["pctx"]
    wired = _wire_engages(ctx, ctx.get(placement), jnp.result_type(x))
    return reduce_mean_p.bind(x, **({"wire": "int8"} if wired else {}),
                              **params)


def bind_reduce_max(x, placement: Optional[str] = None):
    return reduce_max_p.bind(x, **_bind_params(placement))


def bind_stage_transfer(x, placement: Optional[str] = None, *,
                        shift: int = 1, wrap: bool = False):
    x = jnp.asarray(x)
    return stage_transfer_p.bind(
        x, shift=int(shift), wrap=bool(wrap), **_bind_params(placement)
    )


DRJAX_PRIMITIVES: Tuple[Primitive, ...] = (
    broadcast_p,
    reduce_sum_p,
    reduce_mean_p,
    reduce_max_p,
    stage_transfer_p,
)

# Primitives that imply cross-group communication when interpreted onto a
# distributed system (used by the jaxpr interpreter, paper §5).
COMMUNICATION_PRIMITIVES = frozenset(p.name for p in DRJAX_PRIMITIVES)
