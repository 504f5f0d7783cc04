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
from G=32 on.

Scale granularity is per row block: rows map to the sublane dimension and a
row is one lane-contiguous block of ``C`` values (the flat-packing utility in
``repro.compression`` lays trees out as ``(..., R, 256)`` buffers, so a
"row" is a 256-wide slice of the packed delta).

Shape contract (canonical 3-D; ``repro.kernels.ops`` folds leading pod axes
in via ``jax.vmap``):

    reduce_compress:           (G, R, C) f32-like -> ((R, C) int8, (R, 1) f32)
    reduce_compress_roundtrip: (G, R, C) -> ((R, C) x.dtype, (R, C) int8, (R, 1) f32)
    dequant_accumulate:        ((P, R, C) int8, (P, R, 1) f32) -> (R, C) f32
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


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
    s_ref[...] = scale


def _reduce_compress_roundtrip_kernel(x_ref, back_ref, q_ref, s_ref):
    q, scale = _quantize_rows(_partial_mean(x_ref))
    back_ref[...] = (q.astype(jnp.float32) * scale).astype(back_ref.dtype)
    q_ref[...] = q
    s_ref[...] = scale


def _dequant_accumulate_kernel(q_ref, s_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)          # (P, rb, C)
    back = q * s_ref[...]                       # (P, rb, C) dequant inline
    out_ref[...] = jnp.sum(back, axis=0) * (1.0 / q.shape[0])


# Bytes of VMEM that the double-buffered blocks of one grid step may take.
# Mosaic's default scoped VMEM limit on v5e is 16 MiB; the rest is left to
# the kernel's temporaries.
_VMEM_BUDGET = 12 * 2**20
# A (rows, 1) f32 block is laid out in (8, 128) tiles: 128 lanes per row.
_SCALE_ROW_BYTES = 128 * 4
# int8 blocks tile rows by 32, so a partial row block is a multiple of 32.
_INT8_ROWS = 32


def _row_block(rows: int, row_bytes: int, row_block: Optional[int]) -> int:
    """Rows per grid step. ``row_bytes`` is what one row of every operand's
    block takes in VMEM. Without an explicit ``row_block``: all rows if their
    double-buffered blocks fit ``_VMEM_BUDGET``, else the largest multiple of
    32 that fits. The grid rounds up; the last block may be partial (its
    rows past the end are never written, and every row is independent)."""
    if row_block is None:
        row_block = _VMEM_BUDGET // (2 * row_bytes)
        if row_block < rows:
            row_block = max(_INT8_ROWS, row_block - row_block % _INT8_ROWS)
    return min(row_block, rows)


def reduce_compress(x, *, row_block: Optional[int] = None,
                    interpret: bool = False):
    """Fused partial mean + int8 quantize: (G, R, C) -> ((R, C) q, (R, 1) s)."""
    g, r, c = x.shape
    row_block = _row_block(
        r, g * c * x.dtype.itemsize + c + _SCALE_ROW_BYTES, row_block
    )
    q, s = pl.pallas_call(
        _reduce_compress_kernel,
        grid=(pl.cdiv(r, row_block),),
        in_specs=[pl.BlockSpec((g, row_block, c), lambda i: (0, i, 0))],
        out_specs=[
            pl.BlockSpec((row_block, c), lambda i: (i, 0)),
            pl.BlockSpec((row_block, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, c), jnp.int8),
            jax.ShapeDtypeStruct((r, 1), jnp.float32),
        ],
        interpret=interpret,
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
    )(x)
    return back, q, s


def dequant_accumulate(q, scales, *, row_block: Optional[int] = None,
                       interpret: bool = False):
    """Fused dequantize + mean over pods: ((P, R, C), (P, R, 1)) -> (R, C)."""
    p, r, c = q.shape
    row_block = _row_block(r, p * (c + _SCALE_ROW_BYTES) + 4 * c, row_block)
    out = pl.pallas_call(
        _dequant_accumulate_kernel,
        grid=(pl.cdiv(r, row_block),),
        in_specs=[
            pl.BlockSpec((p, row_block, c), lambda i: (0, i, 0)),
            pl.BlockSpec((p, row_block, 1), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((row_block, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.float32),
        interpret=interpret,
    )(q, scales)
    return out
