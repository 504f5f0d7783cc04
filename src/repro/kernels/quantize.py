"""Pallas TPU kernel: symmetric int8 block quantization (+ dequant).

Used by the gradient-compression path (``repro.compression``) to quantize
client→server deltas before the cross-pod reduction. Per-row-block absmax
scaling; rows map to the sublane dimension, the 128-wide lane dimension stays
contiguous.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)  # (rb, C)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)  # (rb, 1)
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale.astype(jnp.float32)


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...]).astype(x_ref.dtype)


def quantize(x, *, row_block: int = 256, interpret: bool = False):
    """x: (R, C) -> (q int8 (R, C), scales f32 (R, 1))."""
    r, c = x.shape
    row_block = min(row_block, r)
    pad = (-r) % row_block
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    rp = x.shape[0]
    nb = rp // row_block
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((row_block, c), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((row_block, c), lambda i: (i, 0)),
            pl.BlockSpec((row_block, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, c), jnp.int8),
            jax.ShapeDtypeStruct((rp, 1), jnp.float32),
        ],
        interpret=interpret,
        name="quantize",
    )(x)
    return q[:r], s[:r]


def dequantize(q, scales, dtype=jnp.float32, *, row_block: int = 256,
               interpret: bool = False):
    """Inverse of :func:`quantize`."""
    r, c = q.shape
    row_block = min(row_block, r)
    pad = (-r) % row_block
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0)))
        scales = jnp.pad(scales, ((0, pad), (0, 0)))
    rp = q.shape[0]
    nb = rp // row_block
    x = pl.pallas_call(
        _dequant_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((row_block, c), lambda i: (i, 0)),
            pl.BlockSpec((row_block, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((row_block, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, c), dtype),
        interpret=interpret,
        name="dequantize",
    )(q, scales)
    return x[:r]
