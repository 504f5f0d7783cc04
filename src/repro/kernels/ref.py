"""Pure-jnp oracles (allclose targets in tests): for every Pallas kernel,
and for the models' own attention, RG-LRU scan and WKV6 code."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal=True, window=0):
    """Naive full-materialization GQA attention. Same contract as
    ``models.attention.blocked_attention``."""
    b, sq, hq, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    s = s / math.sqrt(hd)
    q_pos = jnp.arange(sq)[:, None]
    k_pos = jnp.arange(skv)[None, :]
    ok = jnp.ones((sq, skv), bool)
    if causal:
        ok &= k_pos <= q_pos
    if window and window > 0:
        ok &= k_pos > q_pos - window
    s = jnp.where(ok[None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, hd).astype(q.dtype)


def lru_scan_ref(a, b):
    """Sequential h_t = a_t h_{t-1} + b_t."""
    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h

    a32 = jnp.moveaxis(a.astype(jnp.float32), 1, 0)
    b32 = jnp.moveaxis(b.astype(jnp.float32), 1, 0)
    h0 = jnp.zeros_like(a32[0])
    _, hs = jax.lax.scan(step, h0, (a32, b32))
    return jnp.moveaxis(hs, 0, 1).astype(a.dtype)


def wkv6_ref(r, k, v, logw, u):
    """Sequential WKV6 recurrence (fp32)."""
    b, s, h, n = r.shape
    S0 = jnp.zeros((b, h, n, n), jnp.float32)

    def step(S, inp):
        rt, kt, vt, lwt = inp
        wt = jnp.exp(lwt)
        kv = jnp.einsum("bhk,bhv->bhkv", kt, vt)
        out = jnp.einsum("bhk,bhkv->bhv", rt, S + u[None, :, :, None] * kv)
        return wt[..., None] * S + kv, out

    xs = tuple(
        jnp.moveaxis(t.astype(jnp.float32), 1, 0) for t in (r, k, v, logw)
    )
    _, outs = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(outs, 0, 1)


def quantize_ref(x, *, row_block=256):
    """Per-row symmetric int8 quantization (row granularity = 1 row)."""
    x32 = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_ref(q, scales, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scales).astype(dtype)


def _partial_mean_ref(x):
    """The mean over the leading group axis, accumulated in f32."""
    return jnp.sum(x.astype(jnp.float32), axis=0) * (1.0 / x.shape[0])


def reduce_compress_ref(x):
    """Fused partial mean + int8 quantize, op-for-op the kernel's math.

    (G, R, C) -> ((R, C) int8, (1, R) f32 scales). The mean accumulates in
    f32 over the leading group axis; scales are per row (one lane-contiguous
    block of C values), laid out lane-dense as the kernel writes them.
    """
    q, s = quantize_ref(_partial_mean_ref(x))
    return q, s.reshape(1, -1)


def reduce_compress_roundtrip_ref(x):
    """(G, R, C) -> (back x.dtype, q int8, (R, 1) s f32): mean + quant +
    dequant."""
    q, s = quantize_ref(_partial_mean_ref(x))
    back = dequantize_ref(q, s, x.dtype)
    return back, q, s


def dequant_accumulate_ref(q, scales):
    """Fused dequantize + mean over pods: ((P, R, C), (P, R)) -> (R, C).
    The pods accumulate one at a time, in their order, as in the kernel."""
    acc = q[0].astype(jnp.float32) * scales[0][:, None]
    for k in range(1, q.shape[0]):
        acc = acc + q[k].astype(jnp.float32) * scales[k][:, None]
    return acc * (1.0 / q.shape[0])
