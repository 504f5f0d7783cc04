"""GQA attention: blocked (flash-style, XLA), naive, decode-with-cache.

The *blocked* path is the production default: an online-softmax scan over KV
blocks that never materializes the (Sq, Skv) score matrix, expressed in XLA
so it lowers on any backend. ``flash_attention_xla`` is the same forward
with a blockwise backward of its own; ``tests/test_kernels.py`` checks both
against the naive oracle ``repro.kernels.ref.flash_attention_ref``.

Supports:
 * grouped-query attention (Hq = G * Hkv),
 * causal and local (sliding-window) masking,
 * cross-attention (no masking, separate memory length),
 * single-token decode against a fixed-size or ring-buffer KV cache.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import common
from .partitioning import with_logical_constraint

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def init_params(rng, cfg, cross: bool = False):
    """QKV/O projection params. Shapes: wq (D, Hq, hd); wk/wv (D, Hkv, hd);
    wo (Hq, hd, D)."""
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = cfg.jnp_dtype
    ks = jax.random.split(rng, 4)
    p = {
        "wq": common.normal_init(ks[0], (d, hq, hd), dt),
        "wk": common.normal_init(ks[1], (d, hkv, hd), dt),
        "wv": common.normal_init(ks[2], (d, hkv, hd), dt),
        "wo": common.normal_init(ks[3], (hq, hd, d), dt, stddev=1.0 / math.sqrt(hq * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq, hd), dt)
        p["bk"] = jnp.zeros((hkv, hd), dt)
        p["bv"] = jnp.zeros((hkv, hd), dt)
    return p


_MODEL_AXIS = 16  # production meshes always have model=16


def _shard_heads(cfg) -> bool:
    """Shard attention over heads when divisible, else over head_dim
    (56-head archs like yi-34b: 56 % 16 != 0 but head_dim 128 % 16 == 0)."""
    return cfg.num_heads % _MODEL_AXIS == 0


def head_logical_axes(cfg, kv: bool = False):
    if _shard_heads(cfg):
        if not kv:
            return ("heads", None)
        if cfg.num_kv_heads % _MODEL_AXIS == 0:
            return ("kv_heads", None)
        # GQA with few kv heads: replicate the (small) kv activations rather
        # than shard head_dim — sharding hd here conflicts with heads-sharded
        # Q in the attention contraction and forces SPMD full remat (seen in
        # compile logs). The KV *cache* still stores hd-sharded (cache_axes).
        return (None, None)
    return (None, "kv_head_dim")


def param_axes(cfg, cross: bool = False):
    if _shard_heads(cfg):
        h, hd = "p_heads", "p_head_dim"
    else:
        h, hd = None, "kv_head_dim"
    kvh = "p_kv_heads" if cfg.num_kv_heads % _MODEL_AXIS == 0 else None
    kvd = "p_head_dim" if kvh else "kv_head_dim"
    axes = {
        "wq": ("p_fsdp", h, hd),
        "wk": ("p_fsdp", kvh, kvd),
        "wv": ("p_fsdp", kvh, kvd),
        "wo": (h, hd, "p_fsdp"),
    }
    if cfg.qkv_bias:
        axes["bq"] = (h, hd)
        axes["bk"] = (kvh, kvd)
        axes["bv"] = (kvh, kvd)
    return axes


def _proj(x, w, b=None):
    out = jnp.einsum("bsd,dhk->bshk", x, w, preferred_element_type=jnp.float32)
    out = out.astype(x.dtype)
    if b is not None:
        out = out + b.astype(out.dtype)
    return out


def qkv(cfg, p, x, positions, rope: bool = True):
    q = _proj(x, p["wq"], p.get("bq"))
    k = _proj(x, p["wk"], p.get("bk"))
    v = _proj(x, p["wv"], p.get("bv"))
    if rope:
        q = common.apply_rope(q, positions, cfg.rope_theta)
        k = common.apply_rope(k, positions, cfg.rope_theta)
    qh = head_logical_axes(cfg)
    kvh = head_logical_axes(cfg, kv=True)
    q = with_logical_constraint(q, ("batch", "seq") + qh)
    k = with_logical_constraint(k, ("batch", "seq") + kvh)
    v = with_logical_constraint(v, ("batch", "seq") + kvh)
    return q, k, v


def out_proj(p, attn_out):
    out = jnp.einsum(
        "bshk,hkd->bsd", attn_out, p["wo"], preferred_element_type=jnp.float32
    )
    return out.astype(attn_out.dtype)


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------


def _mask_bias(q_pos, k_pos, causal: bool, window: int):
    """(…q, …k) additive bias from position comparisons."""
    ok = jnp.ones((q_pos.shape[-1], k_pos.shape[-1]), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window and window > 0:
        ok &= k_pos[None, :] > (q_pos[:, None] - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# naive attention (smoke tests / tiny shapes / oracle)
# ---------------------------------------------------------------------------


def naive_attention(q, k, v, *, causal=True, window=0, q_pos=None, k_pos=None):
    b, sq, hq, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, hd)
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) * scale
    if q_pos is None:
        q_pos = jnp.arange(sq)
    if k_pos is None:
        k_pos = jnp.arange(skv)
    s = s + _mask_bias(q_pos, k_pos, causal, window)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(v.dtype), v)
    return out.reshape(b, sq, hq, hd)


# ---------------------------------------------------------------------------
# blocked (online-softmax) attention
# ---------------------------------------------------------------------------


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


def blocked_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: int = 0,
    q_block: int = 512,
    kv_block: int = 1024,
    skip_masked_blocks: bool = False,
):
    """Flash-style attention via scan over KV blocks; O(Sq·block) memory.

    ``skip_masked_blocks=True`` enables the causal block-skipping schedule:
    only lower-triangular (q-block, kv-block) pairs are computed (≈2× fewer
    attention FLOPs at long sequence), at the cost of a flattened-pair scan.
    """
    b, sq_orig, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(hd)

    q, sq_orig = _pad_to(q, 1, q_block)
    k, skv_orig = _pad_to(k, 1, kv_block)
    v, _ = _pad_to(v, 1, kv_block)
    sq, skv = q.shape[1], k.shape[1]
    nq, nk = sq // q_block, skv // kv_block

    qg = q.reshape(b, nq, q_block, hkv, g, hd)
    kb = k.reshape(b, nk, kv_block, hkv, hd)
    vb = v.reshape(b, nk, kv_block, hkv, hd)

    def kv_step(carry, j, qi, i):
        acc, m, l = carry
        kj = kb[:, j]
        vj = vb[:, j]
        s = (
            jnp.einsum("bqhgd,bkhd->bqhgk", qi, kj, preferred_element_type=jnp.float32)
            * scale
        )
        q_pos = i * q_block + jnp.arange(q_block)
        k_pos = j * kv_block + jnp.arange(kv_block)
        bias = _mask_bias(q_pos, k_pos, causal, window)
        # also mask KV padding
        bias = jnp.where((k_pos < skv_orig)[None, :], bias, NEG_INF)
        s = s + bias[None, :, None, None, :]
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p.astype(vj.dtype), vj,
            preferred_element_type=jnp.float32,
        )
        return (acc_new, m_new, l_new), None

    def q_block_fn(i):
        qi = qg[:, i]
        acc0 = jnp.zeros((b, q_block, hkv, g, hd), jnp.float32)
        m0 = jnp.full((b, q_block, hkv, g), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, q_block, hkv, g), jnp.float32)

        if skip_masked_blocks and causal and not window:
            # only kv blocks whose start can be visible to this q block
            # (static bound: scan over all, but the mask-only blocks are
            # handled by the pair schedule below instead).
            pass
        step = functools.partial(kv_step, qi=qi, i=i)
        (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), jnp.arange(nk))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.astype(q.dtype)

    if skip_masked_blocks and causal and not window:
        return _blocked_attention_tri(
            qg, kb, vb, scale, b, nq, nk, q_block, kv_block, hkv, g, hd,
            sq_orig, skv_orig,
        )

    out = jax.lax.map(q_block_fn, jnp.arange(nq))  # (nq, b, qb, hkv, g, hd)
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq, hkv * g, hd)
    return out[:, :sq_orig]


def _blocked_attention_tri(
    qg, kb, vb, scale, b, nq, nk, q_block, kv_block, hkv, g, hd, sq_orig, skv_orig
):
    """Causal block-skipping schedule: scan lower-triangular (i, j) pairs only.

    Beyond-paper perf optimization: for causal
    attention with Sq == Skv this computes nq(nq+1)/2 block pairs instead of
    nq·nk, halving attention FLOPs at long sequence length.
    """
    ratio = max(kv_block // q_block, 1)
    pairs = [
        (i, j)
        for i in range(nq)
        for j in range(nk)
        if j * kv_block <= i * q_block + q_block - 1  # block intersects causal
    ]
    pair_arr = jnp.array(pairs, jnp.int32)  # (P, 2)

    acc0 = jnp.zeros((nq, b, q_block, hkv, g, hd), jnp.float32)
    m0 = jnp.full((nq, b, q_block, hkv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((nq, b, q_block, hkv, g), jnp.float32)

    def step(carry, pair):
        acc, m, l = carry
        i, j = pair[0], pair[1]
        qi = jax.lax.dynamic_index_in_dim(qg, i, axis=1, keepdims=False)
        kj = jax.lax.dynamic_index_in_dim(kb, j, axis=1, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(vb, j, axis=1, keepdims=False)
        s = (
            jnp.einsum("bqhgd,bkhd->bqhgk", qi, kj, preferred_element_type=jnp.float32)
            * scale
        )
        q_pos = i * q_block + jnp.arange(q_block)
        k_pos = j * kv_block + jnp.arange(kv_block)
        ok = (k_pos[None, :] <= q_pos[:, None]) & (k_pos < skv_orig)[None, :]
        s = s + jnp.where(ok, 0.0, NEG_INF)[None, :, None, None, :]
        mi = jax.lax.dynamic_index_in_dim(m, i, axis=0, keepdims=False)
        li = jax.lax.dynamic_index_in_dim(l, i, axis=0, keepdims=False)
        acci = jax.lax.dynamic_index_in_dim(acc, i, axis=0, keepdims=False)
        m_new = jnp.maximum(mi, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(mi - m_new)
        l_new = li * corr + p.sum(axis=-1)
        acc_new = acci * corr[..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p.astype(vj.dtype), vj,
            preferred_element_type=jnp.float32,
        )
        acc = jax.lax.dynamic_update_index_in_dim(acc, acc_new, i, axis=0)
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, i, axis=0)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, i, axis=0)
        return (acc, m, l), None

    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), pair_arr)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = jnp.moveaxis(out, 0, 1).reshape(b, nq * q_block, hkv * g, hd)
    return out[:, :sq_orig].astype(qg.dtype)


# ---------------------------------------------------------------------------
# flash attention in XLA with a custom VJP (O(S) residual memory)
#
# Plain autodiff through the blocked-attention scan stores the per-block
# softmax probabilities — O(S²) residuals that dominate training memory at
# 4k+ context. The custom VJP saves only (q, k, v, out, L=logsumexp) and
# recomputes score blocks in the backward pass (Dao et al.'s recipe, here
# expressed with a static lower-triangular block-pair schedule that also
# skips fully-masked pairs — causal FLOPs ≈ halved, fwd and bwd).
# ---------------------------------------------------------------------------


def _visible_pairs(nq, nk, q_block, kv_block, skv_orig, causal, window):
    pairs = []
    for i in range(nq):
        for j in range(nk):
            q_lo, q_hi = i * q_block, i * q_block + q_block - 1
            k_lo, k_hi = j * kv_block, j * kv_block + kv_block - 1
            if k_lo >= skv_orig:
                continue
            if causal and k_lo > q_hi:
                continue
            if window and window > 0 and k_hi <= q_lo - window:
                continue
            pairs.append((i, j))
    return pairs


def _pair_mask(i, j, q_block, kv_block, skv_orig, causal, window):
    q_pos = i * q_block + jnp.arange(q_block)
    k_pos = j * kv_block + jnp.arange(kv_block)
    ok = (k_pos < skv_orig)[None, :] & jnp.ones((q_block, 1), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window and window > 0:
        ok &= k_pos[None, :] > (q_pos[:, None] - window)
    return ok


def _flash_fwd_core(q, k, v, causal, window, q_block, kv_block):
    """Returns (out (B,Sq,Hq,hd) f32, L (B,Sq,hkv,g) f32 logsumexp)."""
    b, sq_orig, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(hd)

    qp, _ = _pad_to(q, 1, q_block)
    kp, skv_orig = _pad_to(k, 1, kv_block)
    vp, _ = _pad_to(v, 1, kv_block)
    sq, skv = qp.shape[1], kp.shape[1]
    nq, nk = sq // q_block, skv // kv_block
    qg = qp.reshape(b, nq, q_block, hkv, g, hd).astype(jnp.float32)
    kb = kp.reshape(b, nk, kv_block, hkv, hd).astype(jnp.float32)
    vb = vp.reshape(b, nk, kv_block, hkv, hd).astype(jnp.float32)

    pairs = _visible_pairs(nq, nk, q_block, kv_block, skv_orig, causal, window)
    pair_arr = jnp.array(pairs, jnp.int32)

    acc0 = jnp.zeros((nq, b, q_block, hkv, g, hd), jnp.float32)
    m0 = jnp.full((nq, b, q_block, hkv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((nq, b, q_block, hkv, g), jnp.float32)

    def step(carry, pair):
        acc, m, l = carry
        i, j = pair[0], pair[1]
        qi = jax.lax.dynamic_index_in_dim(qg, i, 1, keepdims=False)
        kj = jax.lax.dynamic_index_in_dim(kb, j, 1, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(vb, j, 1, keepdims=False)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qi, kj,
                       preferred_element_type=jnp.float32) * scale
        # dynamic mask (i, j traced)
        q_pos = i * q_block + jnp.arange(q_block)
        k_pos = j * kv_block + jnp.arange(kv_block)
        ok = (k_pos < skv_orig)[None, :] & jnp.ones((q_block, 1), bool)
        if causal:
            ok = ok & (k_pos[None, :] <= q_pos[:, None])
        if window and window > 0:
            ok = ok & (k_pos[None, :] > (q_pos[:, None] - window))
        s = jnp.where(ok[None, :, None, None, :], s, NEG_INF)
        mi = jax.lax.dynamic_index_in_dim(m, i, 0, keepdims=False)
        li = jax.lax.dynamic_index_in_dim(l, i, 0, keepdims=False)
        acci = jax.lax.dynamic_index_in_dim(acc, i, 0, keepdims=False)
        m_new = jnp.maximum(mi, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(mi - m_new)
        l_new = li * corr + p.sum(axis=-1)
        acc_new = acci * corr[..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p, vj, preferred_element_type=jnp.float32)
        acc = jax.lax.dynamic_update_index_in_dim(acc, acc_new, i, 0)
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, i, 0)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, i, 0)
        return (acc, m, l), None

    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), pair_arr)
    lsafe = jnp.maximum(l, 1e-30)
    out = acc / lsafe[..., None]
    L = m + jnp.log(lsafe)  # (nq, b, qb, hkv, g)
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq, hkv * g, hd)[:, :sq_orig]
    L = jnp.moveaxis(L, 0, 1).reshape(b, sq, hkv, g)[:, :sq_orig]
    return out, L


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_xla(q, k, v, causal, window, q_block, kv_block):
    out, _ = _flash_fwd_core(q, k, v, causal, window, q_block, kv_block)
    return out.astype(q.dtype)


def _flash_fwd_rule(q, k, v, causal, window, q_block, kv_block):
    out, L = _flash_fwd_core(q, k, v, causal, window, q_block, kv_block)
    return out.astype(q.dtype), (q, k, v, out, L)


def _flash_bwd_rule(causal, window, q_block, kv_block, res, dout):
    q, k, v, out, L = res
    b, sq_orig, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(hd)

    qp, _ = _pad_to(q, 1, q_block)
    kp, skv_orig = _pad_to(k, 1, kv_block)
    vp, _ = _pad_to(v, 1, kv_block)
    dop, _ = _pad_to(dout.astype(jnp.float32), 1, q_block)
    outp, _ = _pad_to(out, 1, q_block)
    Lp, _ = _pad_to(L, 1, q_block)
    sq, skv = qp.shape[1], kp.shape[1]
    nq, nk = sq // q_block, skv // kv_block

    qg = qp.reshape(b, nq, q_block, hkv, g, hd).astype(jnp.float32)
    kb = kp.reshape(b, nk, kv_block, hkv, hd).astype(jnp.float32)
    vb = vp.reshape(b, nk, kv_block, hkv, hd).astype(jnp.float32)
    dog = dop.reshape(b, nq, q_block, hkv, g, hd)
    og = outp.reshape(b, nq, q_block, hkv, g, hd)
    Lg = Lp.reshape(b, nq, q_block, hkv, g)
    # D_i = rowsum(dout * out)
    Dg = jnp.sum(dog * og, axis=-1)  # (b, nq, qb, hkv, g)

    pairs = _visible_pairs(nq, nk, q_block, kv_block, skv_orig, causal, window)
    pair_arr = jnp.array(pairs, jnp.int32)

    dq0 = jnp.zeros((nq, b, q_block, hkv, g, hd), jnp.float32)
    dk0 = jnp.zeros((nk, b, kv_block, hkv, hd), jnp.float32)
    dv0 = jnp.zeros((nk, b, kv_block, hkv, hd), jnp.float32)

    def step(carry, pair):
        dq, dk, dv = carry
        i, j = pair[0], pair[1]
        qi = jax.lax.dynamic_index_in_dim(qg, i, 1, keepdims=False)
        kj = jax.lax.dynamic_index_in_dim(kb, j, 1, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(vb, j, 1, keepdims=False)
        doi = jax.lax.dynamic_index_in_dim(dog, i, 1, keepdims=False)
        Li = jax.lax.dynamic_index_in_dim(Lg, i, 1, keepdims=False)
        Di = jax.lax.dynamic_index_in_dim(Dg, i, 1, keepdims=False)

        s = jnp.einsum("bqhgd,bkhd->bqhgk", qi, kj,
                       preferred_element_type=jnp.float32) * scale
        q_pos = i * q_block + jnp.arange(q_block)
        k_pos = j * kv_block + jnp.arange(kv_block)
        ok = (k_pos < skv_orig)[None, :] & jnp.ones((q_block, 1), bool)
        if causal:
            ok = ok & (k_pos[None, :] <= q_pos[:, None])
        if window and window > 0:
            ok = ok & (k_pos[None, :] > (q_pos[:, None] - window))
        s = jnp.where(ok[None, :, None, None, :], s, NEG_INF)
        p = jnp.exp(s - Li[..., None])  # exact probabilities via saved L

        dvj = jnp.einsum("bqhgk,bqhgd->bkhd", p, doi)
        dp = jnp.einsum("bqhgd,bkhd->bqhgk", doi, vj)
        ds = p * (dp - Di[..., None]) * scale
        dqi = jnp.einsum("bqhgk,bkhd->bqhgd", ds, kj)
        dkj = jnp.einsum("bqhgk,bqhgd->bkhd", ds, qi)

        dq = jax.lax.dynamic_update_index_in_dim(
            dq, jax.lax.dynamic_index_in_dim(dq, i, 0, keepdims=False) + dqi,
            i, 0)
        dk = jax.lax.dynamic_update_index_in_dim(
            dk, jax.lax.dynamic_index_in_dim(dk, j, 0, keepdims=False) + dkj,
            j, 0)
        dv = jax.lax.dynamic_update_index_in_dim(
            dv, jax.lax.dynamic_index_in_dim(dv, j, 0, keepdims=False) + dvj,
            j, 0)
        return (dq, dk, dv), None

    (dq, dk, dv), _ = jax.lax.scan(step, (dq0, dk0, dv0), pair_arr)
    dq = jnp.moveaxis(dq, 0, 1).reshape(b, sq, hq, hd)[:, :sq_orig]
    dk = jnp.moveaxis(dk, 0, 1).reshape(b, skv, hkv, hd)[:, :skv_orig]
    dv = jnp.moveaxis(dv, 0, 1).reshape(b, skv, hkv, hd)[:, :skv_orig]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention_xla.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def self_attention(cfg, q, k, v, *, causal=True, window=0):
    if cfg.attn_impl == "naive":
        return naive_attention(q, k, v, causal=causal, window=window)
    if cfg.attn_impl == "blocked_novjp":
        # plain-autodiff baseline (stores O(S²) residuals under grad;
        # kept for the §Perf before/after comparison)
        skip = getattr(cfg, "skip_masked_blocks", False)
        return blocked_attention(
            q, k, v, causal=causal, window=window,
            q_block=cfg.q_block, kv_block=cfg.kv_block,
            skip_masked_blocks=skip,
        )
    return flash_attention_xla(
        q, k, v, causal, window, min(cfg.q_block, q.shape[1]),
        min(cfg.kv_block, k.shape[1]),
    )


# ---------------------------------------------------------------------------
# KV cache + decode
# ---------------------------------------------------------------------------


def init_cache(cfg, batch: int, max_len: int, *, window: Optional[int] = None,
               ring: bool = True):
    """Fixed-size cache; for local attention pass window to get a ring buffer.

    ``ring=False`` forces the no-ring layout (size == max_len, slot index ==
    absolute position) even for windowed attention — the layout chunked
    prefill requires (``chunk_attention`` writes at absolute positions), used
    by the serve slot pool. The window is then applied as an explicit mask in
    ``decode_attention``/``chunk_attention``.
    """
    size = min(window, max_len) if (window and ring) else max_len
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    dt = cfg.jnp_dtype
    return {
        "k": jnp.zeros((batch, size, hkv, hd), dt),
        "v": jnp.zeros((batch, size, hkv, hd), dt),
        "pos": jnp.zeros((), jnp.int32),  # absolute position of next token
    }


_MODEL_AXIS_SIZE = 16  # production meshes always have model=16


def cache_logical_axes(cfg):
    """KV-cache sharding: kv_heads over the model axis when divisible, else
    head_dim (GQA archs with kv_heads < model axis; vLLM-style layout)."""
    if cfg.num_kv_heads and cfg.num_kv_heads % _MODEL_AXIS_SIZE == 0:
        return ("kv_batch", "seq", "kv_heads", None)
    return ("kv_batch", "seq", None, "kv_head_dim")


def cache_axes(cfg):
    kv = cache_logical_axes(cfg)
    return {"k": kv, "v": kv, "pos": ()}


def fill_cache(cache, k, v, *, window: int = 0):
    """Prefill: write a whole prefix into the cache (truncate to window).

    Ring-buffer invariant (window case): absolute position p lives at slot
    p % size, matching ``decode_attention``'s write slot.
    """
    size = cache["k"].shape[1]
    s = k.shape[1]
    if window and s > size:
        k = k[:, -size:]
        v = v[:, -size:]
        write = size
        start = s - size
    else:
        write = min(s, size)
        k = k[:, :write]
        v = v[:, :write]
        start = 0
    slots = (start + jnp.arange(write)) % size
    newk = cache["k"].at[:, slots].set(k.astype(cache["k"].dtype))
    newv = cache["v"].at[:, slots].set(v.astype(cache["v"].dtype))
    return {"k": newk, "v": newv, "pos": jnp.asarray(s, jnp.int32)}


def decode_attention(cfg, p, x, cache, *, window: int = 0, rope: bool = True):
    """One decode step. x: (B, 1, D). Returns (out (B,1,D), new_cache)."""
    b = x.shape[0]
    pos = cache["pos"]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q = _proj(x, p["wq"], p.get("bq"))
    k = _proj(x, p["wk"], p.get("bk"))
    v = _proj(x, p["wv"], p.get("bv"))
    if rope:
        q = common.apply_rope(q, positions, cfg.rope_theta)
        k = common.apply_rope(k, positions, cfg.rope_theta)

    size = cache["k"].shape[1]
    # Two windowed layouts: a ring buffer (size == window; recency by
    # overwrite) and the serve-pool "no-ring" layout (size > window, one slot
    # per absolute position, window applied as an explicit mask below).
    ring = bool(window) and size == window
    slot = jnp.mod(pos, size) if ring else jnp.minimum(pos, size - 1)
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
    kv_axes = cache_logical_axes(cfg)
    ck = with_logical_constraint(ck, kv_axes)
    cv = with_logical_constraint(cv, kv_axes)

    hq, hd = cfg.num_heads, cfg.head_dim
    hkv = cfg.num_kv_heads
    g = hq // hkv
    qg = q.reshape(b, 1, hkv, g, hd)
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, ck, preferred_element_type=jnp.float32
    ) * (1.0 / math.sqrt(hd))

    # valid slots: for ring buffer all slots < min(pos+1, size); absolute
    # recency is guaranteed by the ring overwrite. For global cache, slots
    # <= pos are valid. For the no-ring windowed layout slot index == absolute
    # position, so the sliding window is an explicit mask.
    idx = jnp.arange(size)
    valid = idx < jnp.minimum(pos + 1, size)
    if window and not ring:
        valid &= idx > pos - window
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(cv.dtype), cv)
    out = out.reshape(b, 1, hq, hd)
    out = out_proj(p, out)
    new_cache = {"k": ck, "v": cv, "pos": pos + 1}
    return out, new_cache


def chunk_attention(cfg, p, x, cache, positions, *, window: int = 0):
    """Chunked-prefill continuation: C prompt tokens against an existing cache.

    x: (B, C, D); ``positions`` (B, C) absolute token positions (``pos0 +
    arange(C)`` with ``pos0 == cache["pos"]``). Requires the no-ring cache
    layout (``init_cache(..., ring=False)`` — slot index == absolute
    position): writes the chunk's K/V at ``[pos0, pos0 + C)`` and attends each
    chunk query over all cached positions ``<= q_pos`` (window applied as an
    explicit mask). For a global-attention config this is bitwise-equal to
    ``naive_attention`` full prefill over the same prefix: masked slots get
    an additive ``NEG_INF`` whose ``exp`` underflows to exactly 0, so the
    softmax sums and the value contraction see exact zeros.
    """
    b, c, _ = x.shape
    pos0 = cache["pos"]
    size = cache["k"].shape[1]
    # Contract (not statically checkable): the cache must hold every absolute
    # position, i.e. size == max_len (``init_cache(..., ring=False)``). A
    # windowed *ring* cache (size == window < max_len) would wrap — its
    # writes clamp silently. A cache with size == window == max_len is fine:
    # ring and no-ring layouts coincide when no position can wrap.
    q = _proj(x, p["wq"], p.get("bq"))
    k = _proj(x, p["wk"], p.get("bk"))
    v = _proj(x, p["wv"], p.get("bv"))
    q = common.apply_rope(q, positions, cfg.rope_theta)
    k = common.apply_rope(k, positions, cfg.rope_theta)
    ck = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, pos0, 0, 0)
    )
    cv = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, pos0, 0, 0)
    )
    kv_axes = cache_logical_axes(cfg)
    ck = with_logical_constraint(ck, kv_axes)
    cv = with_logical_constraint(cv, kv_axes)

    hq, hd = cfg.num_heads, cfg.head_dim
    hkv = cfg.num_kv_heads
    g = hq // hkv
    qg = q.reshape(b, c, hkv, g, hd)
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, ck, preferred_element_type=jnp.float32
    ) * (1.0 / math.sqrt(hd))
    q_pos = pos0 + jnp.arange(c)
    idx = jnp.arange(size)
    ok = idx[None, :] <= q_pos[:, None]
    if window and window > 0:
        ok &= idx[None, :] > (q_pos[:, None] - window)
    s = jnp.where(ok[None, None, None, :, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(cv.dtype), cv)
    out = out.reshape(b, c, hq, hd)
    out = out_proj(p, out)
    return out, {"k": ck, "v": cv, "pos": pos0 + c}


# ---------------------------------------------------------------------------
# cross-attention (enc-dec)
# ---------------------------------------------------------------------------


def cross_attention(cfg, p, x, memory_k, memory_v):
    """x: (B, Sq, D) attends to precomputed encoder memory (B, Sm, Hkv, hd)."""
    q = _proj(x, p["wq"], p.get("bq"))
    if cfg.attn_impl == "naive":
        return out_proj(
            p, naive_attention(q, memory_k, memory_v, causal=False, window=0)
        )
    out = blocked_attention(
        q,
        memory_k,
        memory_v,
        causal=False,
        window=0,
        q_block=cfg.q_block,
        kv_block=cfg.kv_block,
    )
    return out_proj(p, out)
