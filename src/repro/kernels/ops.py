"""Jit'd public wrappers for the Pallas kernels.

Backend dispatch: on TPU the Mosaic kernels run natively. Elsewhere the
int8 ``quantize``/``dequantize`` pair runs its kernel bodies in Python
(``interpret=True``), and the fused reduce+compress family runs its jnp
oracle (see below).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import quantize as _quant
from . import reduce_compress as _rc
from . import ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("row_block", "interpret"))
def quantize(x, *, row_block=256, interpret=None):
    interpret = (not _on_tpu()) if interpret is None else interpret
    return _quant.quantize(x, row_block=row_block, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("dtype", "row_block", "interpret")
)
def dequantize(q, scales, *, dtype=None, row_block=256, interpret=None):
    interpret = (not _on_tpu()) if interpret is None else interpret
    return _quant.dequantize(
        q, scales, dtype if dtype is not None else jnp.float32,
        row_block=row_block, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# fused intra-pod reduce + compress (hierarchical-reduction fast path)
# ---------------------------------------------------------------------------
#
# Dispatch rule (ROADMAP "Fused reduce+compress" conventions): on TPU the
# Mosaic kernels in ``reduce_compress.py`` run natively; elsewhere the fused
# jnp oracle runs (a single-pass XLA formulation, NOT the interpreted kernel,
# so the CPU fast path stays fast). ``backend="pallas"`` forces the kernel
# (pass ``interpret=True`` off-TPU), ``backend="jnp"`` forces the oracle.


def _fused_backend(backend):
    """The fused family's dispatch: ``backend`` or, by default, the Mosaic
    kernel on TPU and the jnp oracle elsewhere."""
    if backend is None:
        return "pallas" if _on_tpu() else "jnp"
    if backend not in ("pallas", "jnp"):
        raise ValueError(f"unknown backend {backend!r}")
    return backend


@functools.partial(
    jax.jit, static_argnames=("row_block", "backend", "interpret")
)
def reduce_compress(x, *, row_block=None, backend=None, interpret=False):
    """(G, R, C) -> ((R, C) int8, (1, R) f32): fused partial mean +
    quantize, the scales lane-dense."""
    if _fused_backend(backend) == "jnp":
        return _ref.reduce_compress_ref(x)
    return _rc.reduce_compress(x, row_block=row_block, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("row_block", "backend", "interpret")
)
def dequant_accumulate(q, scales, *, row_block=None, backend=None,
                       interpret=False):
    """((P, R, C) int8, (P, R) f32) -> (R, C): fused dequantize + pod
    mean."""
    if _fused_backend(backend) == "jnp":
        return _ref.dequant_accumulate_ref(q, scales)
    return _rc.dequant_accumulate(
        q, scales, row_block=row_block, interpret=interpret
    )


# Above roughly this many f32 entries the block-diagonal reduction matrix of
# the oracle's one-pass gemm stops being worth materializing.
_GEMM_WEIGHT_LIMIT = 1 << 22


def _roundtrip_rows(part, qaxis):
    """Straight-through int8 roundtrip of ``part`` with per-row scales over
    axis ``qaxis`` (the wire-format granularity)."""
    moved = part if qaxis == part.ndim - 1 else jnp.moveaxis(part, qaxis, -1)
    rows = moved.reshape(-1, moved.shape[-1])
    q, s = _ref.quantize_ref(rows)
    back = _ref.dequantize_ref(q, s, part.dtype).reshape(moved.shape)
    return back if qaxis == part.ndim - 1 else jnp.moveaxis(back, -1, qaxis)


def _reduce_compress_roundtrip_jnp(x, axis, qaxis):
    """Fused jnp oracle: one pass over ``x`` produces the roundtrip partial.

    Off TPU the partial mean is a block-diagonal matmul (one gemm reads the
    operand once and emits every pod's partial), which XLA:CPU executes far
    faster than a chain of axis reductions; the quantize/dequantize then runs
    on the small partial only. On TPU it is the plain axis mean: the gemm
    flattens each group to one vector, and a minor dimension of ~10^8
    entries sends the TPU compiler into minutes and tens of GB of memory.
    """
    lead = x.shape[:axis]
    g = x.shape[axis]
    trail = x.shape[axis + 1:]
    l = int(np.prod(lead, dtype=np.int64)) if lead else 1
    d = int(np.prod(trail, dtype=np.int64)) if trail else 1
    if (x.dtype == jnp.float32 and l * l * g <= _GEMM_WEIGHT_LIMIT
            and not _on_tpu()):
        w = jnp.repeat(jnp.eye(l, dtype=jnp.float32), g, axis=1) * (1.0 / g)
        part = (w @ x.reshape(l * g, d)).reshape(lead + trail)
    else:
        # Quantize the f32 mean, as the kernel does, and cast only the
        # roundtrip result.
        part = jnp.sum(x.astype(jnp.float32), axis=axis) * (1.0 / g)
    return _roundtrip_rows(part, qaxis).astype(x.dtype)


def _reduce_compress_roundtrip_pallas(x, axis, qaxis, row_block, interpret):
    if qaxis < axis:
        # Quant axis in the lead region: the kernel wants it trailing, but
        # moving it would reorder the pod axes too. Rare (the fast path
        # always quantizes a trailing axis) — use the jnp formulation.
        return _reduce_compress_roundtrip_jnp(x, axis, qaxis)
    lead = x.shape[:axis]
    g = x.shape[axis]
    trail = x.shape[axis + 1:]
    part_shape = lead + trail
    # Canonicalize for the kernel: (L, G, R, C) with the quant axis last.
    if qaxis != len(part_shape) - 1:
        x = jnp.moveaxis(x, qaxis + 1, -1)
        trail = x.shape[axis + 1:]
    c = trail[-1] if trail else 1
    l = int(np.prod(lead, dtype=np.int64)) if lead else 1
    r = int(np.prod(trail[:-1], dtype=np.int64)) if len(trail) > 1 else 1
    x3 = x.reshape(l, g, r, c)

    def one(pod):
        back, _, _ = _rc.reduce_compress_roundtrip(
            pod, row_block=row_block, interpret=interpret
        )
        return back

    back = jax.vmap(one)(x3).reshape(lead + trail)
    if qaxis != len(part_shape) - 1:
        back = jnp.moveaxis(back, -1, qaxis)
    return back


@functools.partial(
    jax.jit, static_argnames=("axis", "qaxis", "row_block", "backend",
                              "interpret")
)
def reduce_compress_roundtrip(x, *, axis=0, qaxis=-1, row_block=None,
                              backend=None, interpret=False):
    """Straight-through fused reduce+compress: mean over ``axis`` followed by
    an int8 roundtrip with per-row-block scales over ``qaxis`` (an axis of
    the *partial*), produced in a single pass over ``x``.

    This is the execution backend of the ``compress="int8"``-tagged DrJAX
    ``reduce_mean`` eqn (``core/hierarchical.py`` fast path).
    """
    part_ndim = x.ndim - 1
    if part_ndim < 1:
        raise ValueError("reduce_compress_roundtrip needs a non-group axis")
    qaxis = qaxis % part_ndim
    if _fused_backend(backend) == "jnp":
        return _reduce_compress_roundtrip_jnp(x, axis, qaxis)
    return _reduce_compress_roundtrip_pallas(x, axis, qaxis, row_block,
                                             interpret)
