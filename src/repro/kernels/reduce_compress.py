"""Pallas TPU kernels: fused intra-pod reduce + int8 compress.

The hierarchical reduction (``core/hierarchical.py``) splits a mean over n
clients into a fast intra-pod leg (n -> P pod partials) and a slow cross-pod
leg (P -> 1). The DCN-bound payload is the int8-quantized partial; producing
it with separate reduce / quantize / dequantize ops costs three passes over
the partials plus a full f32 materialization of the roundtrip. These kernels
produce it in a single pass over the deltas:

* :func:`reduce_compress` — partial mean over the leading group axis AND the
  int8 wire payload (values + per-row-block scales) in one kernel: each grid
  step loads one ``(G, rb, C)`` block, accumulates the mean over ``G`` in
  VMEM, and quantizes the resulting ``(rb, C)`` rows without ever writing the
  f32 partial to HBM.
* :func:`reduce_compress_roundtrip` — same pass, but emits the straight-
  through f32 roundtrip value ``dequant(quant(mean(x)))`` (what the DrJAX
  reduction semantics see) alongside the payload.
* :func:`dequant_accumulate` — the matching cross-pod leg: dequantizes the P
  per-pod payloads and accumulates their mean in one pass, so the f32
  partials are never materialized on the receiving side either.

Each grid step holds one row block of every operand in VMEM, double
buffered. :func:`_row_block` sizes the block from a VMEM budget, so the
group count G (clients per pod, or pods) does not decide whether the kernel
compiles: a fixed 256-row block of f32 deltas overruns v5e's scoped VMEM
from G=32 on. A lane-dense block holds at least 128 rows; where those
overrun the budget, :func:`_compiler_params` raises the scoped limit.

Scale granularity is per row block: rows map to the sublane dimension and a
row is one lane-contiguous block of ``C`` values (the flat-packing utility in
``repro.compression`` lays trees out as ``(..., R, 256)`` buffers, so a
"row" is a 256-wide slice of the packed delta).

Shape contract (canonical 3-D; ``repro.kernels.ops`` folds leading pod axes
in via ``jax.vmap``):

    reduce_compress:           (G, R, C) f32-like -> ((R, C) int8, (1, R) f32)
    reduce_compress_roundtrip: (G, R, C) -> ((R, C) x.dtype, (R, C) int8, (R, 1) f32)
    dequant_accumulate:        ((P, R, C) int8, (P, R) f32) -> (R, C) f32

The wire pair, ``reduce_compress`` and ``dequant_accumulate``, keeps its
scales lane-dense, one row's scale a lane: that is what the cross-pod leg
sends and receives (``core.primitives._int8_wire_mean``), and an ``(R, 1)``
f32 array is laid out in HBM with 128 lanes a row, 128 times the bytes of
its values.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _partial_mean(x_ref):
    """Mean over the group axis of one (G, rb, C) block, in f32."""
    x = x_ref[...].astype(jnp.float32)  # (G, rb, C)
    return jnp.sum(x, axis=0) * (1.0 / x.shape[0])  # (rb, C)


def _quantize_rows(part):
    """Per-row symmetric int8 quantization of a (rb, C) block."""
    absmax = jnp.max(jnp.abs(part), axis=-1, keepdims=True)  # (rb, 1)
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(part / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _reduce_compress_kernel(x_ref, q_ref, s_ref):
    q, scale = _quantize_rows(_partial_mean(x_ref))
    q_ref[...] = q
    s_ref[...] = jnp.transpose(scale)           # (1, rb): lane-dense


def _reduce_compress_roundtrip_kernel(x_ref, back_ref, q_ref, s_ref):
    q, scale = _quantize_rows(_partial_mean(x_ref))
    back_ref[...] = (q.astype(jnp.float32) * scale).astype(back_ref.dtype)
    q_ref[...] = q
    s_ref[...] = scale


def _dequant_accumulate_kernel(q_ref, s_ref, out_ref):
    """The scales come as a (P, rb) block: one transpose turns each pod's
    scales into a column, and the pods accumulate one at a time, in their
    order, so that every device that holds the same pods gets the same
    mean."""
    p = q_ref.shape[0]
    st = jnp.transpose(s_ref[...])              # (rb, P)
    acc = q_ref[0].astype(jnp.float32) * st[:, 0:1]
    for k in range(1, p):
        acc = acc + q_ref[k].astype(jnp.float32) * st[:, k:k + 1]
    out_ref[...] = acc * (1.0 / p)


# Bytes of VMEM that the double-buffered blocks of one grid step may take.
# Mosaic's default scoped VMEM limit on v5e is 16 MiB; the rest is left to
# the kernel's temporaries.
_SCOPED_VMEM = 16 * 2**20
_VMEM_BUDGET = 12 * 2**20
# A (rows, 1) f32 block is laid out in (8, 128) tiles: 128 lanes per row.
_SCALE_ROW_BYTES = 128 * 4
# int8 blocks tile rows by 32, so a partial row block is a multiple of 32.
_INT8_ROWS = 32
# Lane-dense scales put rows on lanes: a partial row block is a multiple of
# the 128 lanes of a tile.
_LANES = 128


def _lane_scale_row_bytes(groups: int) -> int:
    """VMEM a row takes in a lane-dense ``(groups, rb)`` f32 scale block,
    whose sublanes pad to 8."""
    return 4 * 8 * pl.cdiv(groups, 8)


def _row_block(rows: int, row_bytes: int, row_block: Optional[int],
               multiple: int = _INT8_ROWS) -> int:
    """Rows per grid step. ``row_bytes`` is what one row of every operand's
    block takes in VMEM. Without an explicit ``row_block``: all rows if their
    double-buffered blocks fit ``_VMEM_BUDGET``, else the largest multiple of
    ``multiple`` that fits. The grid rounds up; the last block may be partial
    (its rows past the end are never written, and every row is
    independent)."""
    if row_block is None:
        row_block = _VMEM_BUDGET // (2 * row_bytes)
        if row_block < rows:
            row_block = max(multiple, row_block - row_block % multiple)
    return min(row_block, rows)


def _compiler_params(row_bytes: int, row_block: int):
    """Mosaic's defaults, unless the double-buffered blocks of ``row_block``
    rows overrun ``_VMEM_BUDGET`` (a lane-dense block holds at least 128
    rows, however many groups a row has): then a scoped VMEM limit that
    leaves the default's room for temporaries above the blocks."""
    blocks = 2 * row_bytes * row_block
    if blocks <= _VMEM_BUDGET:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=blocks + _SCOPED_VMEM - _VMEM_BUDGET)


def reduce_compress(x, *, row_block: Optional[int] = None,
                    interpret: bool = False):
    """Fused partial mean + int8 quantize: (G, R, C) -> ((R, C) q, (1, R) s)."""
    g, r, c = x.shape
    row_bytes = g * c * x.dtype.itemsize + c + _lane_scale_row_bytes(1)
    row_block = _row_block(r, row_bytes, row_block, _LANES)
    q, s = pl.pallas_call(
        _reduce_compress_kernel,
        grid=(pl.cdiv(r, row_block),),
        in_specs=[pl.BlockSpec((g, row_block, c), lambda i: (0, i, 0))],
        out_specs=[
            pl.BlockSpec((row_block, c), lambda i: (i, 0)),
            pl.BlockSpec((1, row_block), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, c), jnp.int8),
            jax.ShapeDtypeStruct((1, r), jnp.float32),
        ],
        compiler_params=_compiler_params(row_bytes, row_block),
        interpret=interpret,
        name="reduce_compress",
    )(x)
    return q, s


def reduce_compress_roundtrip(x, *, row_block: Optional[int] = None,
                              interpret: bool = False):
    """Fused mean + quantize + dequantize: (G, R, C) -> (back, q, s).

    ``back`` is the straight-through roundtrip partial in ``x.dtype`` — the
    value the DrJAX reduction consumes; ``(q, s)`` is the wire payload.
    """
    g, r, c = x.shape
    itemsize = x.dtype.itemsize
    row_block = _row_block(
        r, (g + 1) * c * itemsize + c + _SCALE_ROW_BYTES, row_block
    )
    back, q, s = pl.pallas_call(
        _reduce_compress_roundtrip_kernel,
        grid=(pl.cdiv(r, row_block),),
        in_specs=[pl.BlockSpec((g, row_block, c), lambda i: (0, i, 0))],
        out_specs=[
            pl.BlockSpec((row_block, c), lambda i: (i, 0)),
            pl.BlockSpec((row_block, c), lambda i: (i, 0)),
            pl.BlockSpec((row_block, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, c), x.dtype),
            jax.ShapeDtypeStruct((r, c), jnp.int8),
            jax.ShapeDtypeStruct((r, 1), jnp.float32),
        ],
        interpret=interpret,
        name="reduce_compress_roundtrip",
    )(x)
    return back, q, s


def dequant_accumulate(q, scales, *, row_block: Optional[int] = None,
                       interpret: bool = False):
    """Fused dequantize + mean over pods: ((P, R, C), (P, R)) -> (R, C)."""
    p, r, c = q.shape
    # The blocks, and half of what a row's temporaries take (not double
    # buffered): the f32 sum and one pod's f32 values, and the transposed
    # scales, 128 lanes a row.
    temps = 4 * c + 4 * c + _SCALE_ROW_BYTES
    row_bytes = p * c + _lane_scale_row_bytes(p) + 4 * c + temps // 2
    row_block = _row_block(r, row_bytes, row_block, _LANES)
    out = pl.pallas_call(
        _dequant_accumulate_kernel,
        grid=(pl.cdiv(r, row_block),),
        in_specs=[
            pl.BlockSpec((p, row_block, c), lambda i: (0, i, 0)),
            pl.BlockSpec((p, row_block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((row_block, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.float32),
        compiler_params=_compiler_params(row_bytes, row_block),
        interpret=interpret,
        name="dequant_accumulate",
    )(q, scales)
    return out
