"""RWKV-6 "Finch" (arXiv:2404.05892): attention-free time-mix with
data-dependent decay, plus squared-ReLU channel-mix.

Per head (head_dim = N) the time-mix recurrence over state S ∈ R^{N×N}:

    S_t = diag(w_t) S_{t-1} + k_t v_tᵀ
    o_t = r_tᵀ (S_{t-1} + diag(u) k_t v_tᵀ)

with data-dependent decay  w_t = exp(-exp(w0 + LoRA_w(x̃_t))) ∈ (0,1)^N.

Two execution paths, checked against ``repro.kernels.ref.wkv6_ref``:
 * ``sequential_wkv`` — plain lax.scan, the oracle (and the decode step);
 * ``chunked_wkv`` — TPU-native chunkwise-parallel form: the per-pair decay
   factorizes as exp(lcw_{i-1} - lcw_j) = (r_i e^{lcw_{i-1}})·(k_j e^{-lcw_j}),
   turning intra-chunk interaction into plain matmuls (MXU-friendly) while the
   state S carries across chunks — this is the hardware adaptation of the
   paper's CUDA kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common
from .partitioning import with_logical_constraint

_LORA = 32


def num_heads(cfg) -> int:
    return cfg.d_model // cfg.rwkv_head_dim


def init_params(rng, cfg):
    d, dt = cfg.d_model, cfg.jnp_dtype
    n = cfg.rwkv_head_dim
    h = num_heads(cfg)
    ks = jax.random.split(rng, 12)
    return {
        # time-mix projections
        "wr": common.normal_init(ks[0], (d, d), dt),
        "wk": common.normal_init(ks[1], (d, d), dt),
        "wv": common.normal_init(ks[2], (d, d), dt),
        "wg": common.normal_init(ks[3], (d, d), dt),
        "wo": common.normal_init(ks[4], (d, d), dt),
        # token-shift interpolation weights (static lerp mixes) for r,k,v,g,w
        "mix": 0.5 * jnp.ones((5, d), dt),
        # data-dependent decay: w0 + tanh(x A) B
        "w0": common.normal_init(ks[5], (d,), jnp.float32, stddev=0.5),
        "wA": common.normal_init(ks[6], (d, _LORA), jnp.float32, stddev=0.1),
        "wB": common.normal_init(ks[7], (_LORA, d), jnp.float32, stddev=0.1),
        # per-channel bonus
        "u": common.normal_init(ks[8], (d,), jnp.float32, stddev=0.5),
        # group-norm scale on heads
        "ln_scale": jnp.ones((d,), dt),
        # channel mix
        "cm_rk": 0.5 * jnp.ones((2, d), dt),
        "ck": common.normal_init(ks[9], (d, cfg.d_ff), dt),
        "cv": common.normal_init(ks[10], (cfg.d_ff, d), dt),
        "cr": common.normal_init(ks[11], (d, d), dt),
    }


def param_axes(cfg):
    return {
        "wr": ("p_fsdp", "heads"),
        "wk": ("p_fsdp", "heads"),
        "wv": ("p_fsdp", "heads"),
        "wg": ("p_fsdp", "heads"),
        "wo": ("heads", "p_fsdp"),
        "mix": (None, None),
        "w0": (None,),
        "wA": (None, None),
        "wB": (None, None),
        "u": (None,),
        "ln_scale": (None,),
        "cm_rk": (None, None),
        "ck": ("p_fsdp", "p_ff"),
        "cv": ("p_ff", "p_fsdp"),
        "cr": ("p_fsdp", None),
    }


def _shift(x, last=None):
    """Token shift: x_{t-1} (zeros or given state at t=0). x: (B,S,D)."""
    if last is None:
        last = jnp.zeros_like(x[:, :1])
    else:
        last = last[:, None].astype(x.dtype)
    return jnp.concatenate([last, x[:, :-1]], axis=1)


def _mixes(p, x, xprev):
    """Apply static lerp token-shift for (r, k, v, g, w) channels."""
    mix = p["mix"].astype(x.dtype)  # (5, D)
    outs = []
    for i in range(5):
        outs.append(x + (xprev - x) * mix[i])
    return outs  # xr, xk, xv, xg, xw


def _decay(p, xw):
    """Data-dependent per-channel decay w_t ∈ (0,1)."""
    lora = jnp.einsum(
        "bsd,dl->bsl", xw.astype(jnp.float32), p["wA"]
    )
    lora = jnp.tanh(lora)
    loga = p["w0"] + jnp.einsum("bsl,ld->bsd", lora, p["wB"])
    return -jnp.exp(loga)  # log(w_t) = -exp(...) ∈ (-inf, 0)


# ---------------------------------------------------------------------------
# WKV recurrence: sequential (oracle / decode) and chunked (TPU)
# ---------------------------------------------------------------------------


def sequential_wkv(r, k, v, logw, u, state=None):
    """r,k,v: (B, S, H, N); logw: (B, S, H, N); u: (H, N).

    Returns (out (B,S,H,N), final_state (B,H,N,N))."""
    b, s, h, n = r.shape
    if state is None:
        state = jnp.zeros((b, h, n, n), jnp.float32)

    def step(S, inp):
        rt, kt, vt, lwt = inp  # (B,H,N)
        wt = jnp.exp(lwt)
        kv = jnp.einsum("bhk,bhv->bhkv", kt, vt)
        out = jnp.einsum("bhk,bhkv->bhv", rt, S + u[None, :, :, None] * kv)
        S_new = wt[..., None] * S + kv
        return S_new, out

    xs = tuple(
        jnp.moveaxis(t.astype(jnp.float32), 1, 0) for t in (r, k, v, logw)
    )
    final, outs = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(outs, 0, 1), final


def chunked_wkv(r, k, v, logw, u, state=None, chunk: int = 64):
    """Chunkwise-parallel WKV (matmul form). Same contract as sequential."""
    b, s, h, n = r.shape
    pad = (-s) % chunk
    if pad:
        zeros = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v = zeros(r), zeros(k), zeros(v)
        logw = jnp.pad(logw, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = r.shape[1]
    nc = sp // chunk
    f32 = jnp.float32
    rc = r.astype(f32).reshape(b, nc, chunk, h, n)
    kc = k.astype(f32).reshape(b, nc, chunk, h, n)
    vc = v.astype(f32).reshape(b, nc, chunk, h, n)
    lw = logw.astype(f32).reshape(b, nc, chunk, h, n)

    if state is None:
        state = jnp.zeros((b, h, n, n), f32)

    def chunk_step(S, inp):
        rc_, kc_, vc_, lw_ = inp  # (B, C, H, N)
        # cumulative log-decay within chunk: lcw_i = sum_{t<=i} lw_t
        lcw = jnp.cumsum(lw_, axis=1)  # (B,C,H,N)
        lcw_prev = lcw - lw_  # sum_{t<i+1} = lcw_{i-1}
        # inter-chunk: o_i += (r_i ⊙ e^{lcw_{i-1}}) @ S
        r_dec = rc_ * jnp.exp(lcw_prev)
        o = jnp.einsum("bchk,bhkv->bchv", r_dec, S)
        # intra-chunk: pairwise j < i, decay e^{lcw_{i-1} - lcw_j}
        k_dec = kc_ * jnp.exp(-lcw)
        scores = jnp.einsum("bchk,bdhk->bhcd", r_dec, k_dec)  # (B,H,C,C)
        mask = jnp.tril(jnp.ones((chunk, chunk), bool), k=-1)
        scores = jnp.where(mask[None, None], scores, 0.0)
        o = o + jnp.einsum("bhcd,bdhv->bchv", scores, vc_)
        # bonus (diagonal) term: (r_i · (u ⊙ k_i)) v_i
        bonus = jnp.einsum("bchk,hk,bchk->bch", rc_, u, kc_)
        o = o + bonus[..., None] * vc_
        # state update: S' = diag(e^{lcw_C}) S + Σ_j e^{lcw_C - lcw_j} k_j v_jᵀ
        total = lcw[:, -1]  # (B,H,N)
        k_rem = kc_ * jnp.exp(total[:, None] - lcw)
        S_new = jnp.exp(total)[..., None] * S + jnp.einsum(
            "bchk,bchv->bhkv", k_rem, vc_
        )
        return S_new, o

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (rc, kc, vc, lw))
    final, outs = jax.lax.scan(chunk_step, state, xs)
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sp, h, n)
    return out[:, :s], final


def _group_norm(x, scale, n):
    """Per-head RMS-style norm. x: (B,S,H,N) f32."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * scale


# ---------------------------------------------------------------------------
# full blocks
# ---------------------------------------------------------------------------


def time_mix(cfg, p, x, *, shift_state=None, wkv_state=None, chunked=True):
    """RWKV6 attention analogue. x: (B,S,D) -> (out, (shift_state, wkv_state))."""
    b, s, d = x.shape
    h, n = num_heads(cfg), cfg.rwkv_head_dim
    xprev = _shift(x, shift_state)
    xr, xk, xv, xg, xw = _mixes(p, x, xprev)
    r = jnp.einsum("bsd,de->bse", xr, p["wr"]).reshape(b, s, h, n)
    k = jnp.einsum("bsd,de->bse", xk, p["wk"]).reshape(b, s, h, n)
    v = jnp.einsum("bsd,de->bse", xv, p["wv"]).reshape(b, s, h, n)
    g = jnp.einsum("bsd,de->bse", xg, p["wg"])
    logw = _decay(p, xw).reshape(b, s, h, n)
    u = p["u"].reshape(h, n)
    r = with_logical_constraint(r, ("batch", "seq", "heads", None))
    k = with_logical_constraint(k, ("batch", "seq", "heads", None))
    v = with_logical_constraint(v, ("batch", "seq", "heads", None))

    rf, kf, vf = (t.astype(jnp.float32) for t in (r, k, v))
    if chunked and s > 1:
        out, final = chunked_wkv(rf, kf, vf, logw, u, state=wkv_state)
    else:
        out, final = sequential_wkv(rf, kf, vf, logw, u, state=wkv_state)
    out = _group_norm(out, p["ln_scale"].astype(jnp.float32).reshape(h, n), n)
    out = out.reshape(b, s, d).astype(x.dtype) * jax.nn.silu(g)
    out = jnp.einsum("bsd,de->bse", out, p["wo"], preferred_element_type=jnp.float32)
    new_shift = x[:, -1].astype(jnp.float32)
    return out.astype(x.dtype), (new_shift, final)


def channel_mix(cfg, p, x, *, shift_state=None):
    xprev = _shift(x, shift_state)
    mix = p["cm_rk"].astype(x.dtype)
    xk = x + (xprev - x) * mix[0]
    xr = x + (xprev - x) * mix[1]
    kk = jnp.einsum("bsd,df->bsf", xk, p["ck"], preferred_element_type=jnp.float32)
    kk = jnp.square(jax.nn.relu(kk)).astype(x.dtype)
    kk = with_logical_constraint(kk, ("batch", "seq", "ff"))
    vv = jnp.einsum("bsf,fd->bsd", kk, p["cv"], preferred_element_type=jnp.float32)
    rr = jax.nn.sigmoid(
        jnp.einsum("bsd,de->bse", xr, p["cr"], preferred_element_type=jnp.float32)
    )
    out = (rr * vv).astype(x.dtype)
    return out, x[:, -1].astype(jnp.float32)


def init_state(cfg, batch: int):
    h, n = num_heads(cfg), cfg.rwkv_head_dim
    return {
        "tm_shift": jnp.zeros((batch, cfg.d_model), jnp.float32),
        "cm_shift": jnp.zeros((batch, cfg.d_model), jnp.float32),
        "wkv": jnp.zeros((batch, h, n, n), jnp.float32),
    }


def state_axes():
    return {
        "tm_shift": ("kv_batch", None),
        "cm_shift": ("kv_batch", None),
        "wkv": ("kv_batch", "heads", None, None),
    }
