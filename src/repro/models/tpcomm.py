"""Quantized tensor-parallel collectives (beyond-paper optimization).

For large models under tensor parallelism, the per-layer tensor-parallel
activation reduction is a large cost: each TP block ends with partial sums
that GSPMD reduces in bf16 (ring all-reduce ≈ 2·(m-1)/m · bytes on the wire).

``int8_matmul_reduce`` replaces that reduction for a TP matmul's output with:

    local partial matmul (f32)
      → per-row symmetric int8 quantization (repro.kernels.quantize scheme)
      → all-gather of (int8 values + f32 row scales) over the model axis
      → local dequant-sum

Wire bytes: (m-1)/m · (1 byte + scales) vs 2·(m-1)/m · 2 bytes for bf16
all-reduce → ≈ 3.9× fewer bytes at m=16. Cost: m× dequant-add flops
(negligible vs the matmul) and bounded quantization error on *partial sums*
(error ≤ absmax/254 per row per shard; validated in tests, cosine > 0.999).

Implemented with shard_map (via ``repro.compat``, which picks the right API
across JAX versions) so the collective is explicit in the lowered HLO, as
``all-gather`` ops with ``s8`` operands.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat

from . import partitioning


def _quant_rows(x):
    """x: (..., d) f32 -> (int8, scales). Per-row symmetric quantization."""
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def int8_matmul_reduce(x, w, *, axis_name: str = "model",
                       batch_axes=("data",), out_dtype=None):
    """TP matmul with int8-quantized cross-shard reduction.

    x: (T, f) with f sharded over ``axis_name`` (and T over ``batch_axes``);
    w: (f, d) with f sharded over ``axis_name``. Returns (T, d) = x @ w with
    the partial-sum reduction carried in int8.

    Falls back to a plain matmul when no mesh is installed (CPU tests).
    """
    mesh = partitioning.current_mesh()
    out_dtype = out_dtype or x.dtype
    if mesh is None or axis_name not in mesh.axis_names:
        out = jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return out.astype(out_dtype)

    batch = tuple(a for a in batch_axes if a in mesh.axis_names)
    bspec = batch if len(batch) > 1 else (batch[0] if batch else None)

    def local(xs, ws):
        # xs: (T_loc, f_loc); ws: (f_loc, d). Partial over the f shards.
        part = jax.lax.dot_general(
            xs, ws, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        q, s = _quant_rows(part)
        qg = jax.lax.all_gather(q, axis_name)  # (m, T_loc, d) int8 on the wire
        sg = jax.lax.all_gather(s, axis_name)  # (m, T_loc, 1) f32
        out = jnp.sum(qg.astype(jnp.float32) * sg, axis=0)
        return out.astype(out_dtype)

    fn = compat.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(bspec, axis_name), P(axis_name, None)),
        out_specs=P(bspec, None),
        check=False,
    )
    return fn(x, w)


def bf16_wire_bytes(t_tokens: int, d: int, m: int) -> float:
    """Per-device wire bytes of the baseline bf16 all-reduce."""
    return 2.0 * (m - 1) / m * t_tokens * d * 2.0


def int8_wire_bytes(t_tokens: int, d: int, m: int) -> float:
    """Per-device wire bytes of the int8 all-gather reduction."""
    return (m - 1) / m * t_tokens * (d * 1.0 + 4.0)
