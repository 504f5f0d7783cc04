"""RG-LRU recurrent block (RecurrentGemma / Griffin, arXiv:2402.19427).

Block structure (Griffin "recurrent block"):

    x ── linear_in ──┬── causal conv1d(4) ── RG-LRU ──┐
                     └── gelu gate ────────────────────⊙── linear_out

RG-LRU recurrence (diagonal, data-dependent decay):

    r_t = sigmoid(W_a x_t + b_a)           (recurrence gate)
    i_t = sigmoid(W_x x_t + b_x)           (input gate)
    a_t = exp(-c * softplus(Λ) * r_t)      (decay in (0,1), c = 8)
    h_t = a_t ⊙ h_{t-1} + sqrt(1 - a_t²) ⊙ (i_t ⊙ x_t)

Training/prefill uses ``jax.lax.associative_scan`` (log-depth, TPU-friendly);
decode is a single fused step. ``repro.kernels.ref.lru_scan_ref`` is the
sequential oracle of ``lru_scan``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common
from .partitioning import with_logical_constraint

_C = 8.0
_CONV_WIDTH = 4


def init_params(rng, cfg):
    d, w, dt = cfg.d_model, cfg.lru_width, cfg.jnp_dtype
    ks = jax.random.split(rng, 7)
    return {
        "w_in": common.normal_init(ks[0], (d, 2 * w), dt),
        "w_out": common.normal_init(ks[1], (w, d), dt),
        "conv": common.normal_init(ks[2], (_CONV_WIDTH, w), dt, stddev=0.1),
        "w_a": common.normal_init(ks[3], (w, w), dt),
        "b_a": jnp.zeros((w,), dt),
        "w_x": common.normal_init(ks[4], (w, w), dt),
        "b_x": jnp.zeros((w,), dt),
        # Λ init so that softplus(Λ) gives decays in a useful range
        "lam": common.normal_init(ks[5], (w,), jnp.float32, stddev=0.5),
    }


def param_axes(cfg):
    return {
        "w_in": ("p_fsdp", "recurrent_width"),
        "w_out": ("recurrent_width", "p_fsdp"),
        "conv": (None, "recurrent_width"),
        "w_a": ("p_fsdp", "recurrent_width"),
        "b_a": ("recurrent_width",),
        "w_x": ("p_fsdp", "recurrent_width"),
        "b_x": ("recurrent_width",),
        "lam": ("recurrent_width",),
    }


def _gates(p, u):
    """u: (..., W) post-conv input. Returns decay a and gated input."""
    r = jax.nn.sigmoid(
        jnp.einsum("...w,wv->...v", u, p["w_a"]).astype(jnp.float32)
        + p["b_a"].astype(jnp.float32)
    )
    i = jax.nn.sigmoid(
        jnp.einsum("...w,wv->...v", u, p["w_x"]).astype(jnp.float32)
        + p["b_x"].astype(jnp.float32)
    )
    log_a = -_C * jax.nn.softplus(p["lam"]) * r  # (..., W), <= 0
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * (
        i * u.astype(jnp.float32)
    )
    return a, gated


def _causal_conv(p, x, state=None):
    """Depthwise causal conv, width 4. x: (B, S, W)."""
    w = p["conv"].astype(jnp.float32)  # (4, W)
    xf = x.astype(jnp.float32)
    if state is not None:  # state (B, 3, W) holds the last 3 inputs
        if x.shape[1] == 1:  # decode
            buf = jnp.concatenate([state, xf], axis=1)  # (B, 4, W)
            out = jnp.einsum("btw,tw->bw", buf, w)[:, None]
            return out.astype(x.dtype), buf[:, 1:]
        # chunked prefill: continue the conv window across the chunk boundary
        buf = jnp.concatenate([state, xf], axis=1)  # (B, S+3, W)
        stacked = jnp.stack(
            [buf[:, i : i + x.shape[1]] for i in range(_CONV_WIDTH)], axis=-1
        )  # (B, S, W, 4)
        out = jnp.einsum("bswt,tw->bsw", stacked, w)
        return out.astype(x.dtype), buf[:, -(_CONV_WIDTH - 1):]
    pads = jnp.pad(xf, ((0, 0), (_CONV_WIDTH - 1, 0), (0, 0)))
    stacked = jnp.stack(
        [pads[:, i : i + x.shape[1]] for i in range(_CONV_WIDTH)], axis=-1
    )  # (B, S, W, 4)
    out = jnp.einsum("bswt,tw->bsw", stacked, w)
    return out.astype(x.dtype), None


def lru_scan(a, b, h0=None):
    """h_t = a_t h_{t-1} + b_t via associative scan. a,b: (B, S, W) f32."""
    if h0 is not None:
        # fold initial state into the first step
        b = b.at[:, 0].add(a[:, 0] * h0)

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h


def apply(cfg, p, x):
    """Train/prefill path. x: (B, S, D) -> (B, S, D)."""
    u = jnp.einsum("bsd,dw->bsw", x, p["w_in"], preferred_element_type=jnp.float32)
    u = u.astype(x.dtype)
    u, gate = jnp.split(u, 2, axis=-1)
    u = with_logical_constraint(u, ("batch", "seq", "recurrent_width"))
    u, _ = _causal_conv(p, u)
    a, bterm = _gates(p, u)
    h = lru_scan(a, bterm)
    h = h.astype(x.dtype) * jax.nn.gelu(gate)
    out = jnp.einsum("bsw,wd->bsd", h, p["w_out"], preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


def init_state(cfg, batch: int):
    w = cfg.lru_width
    return {
        "h": jnp.zeros((batch, w), jnp.float32),
        "conv": jnp.zeros((batch, _CONV_WIDTH - 1, w), jnp.float32),
    }


def state_axes():
    return {
        "h": ("kv_batch", "recurrent_width"),
        "conv": ("kv_batch", None, "recurrent_width"),
    }


def decode_step(cfg, p, x, state):
    """x: (B, 1, D) -> (out (B, 1, D), new_state)."""
    u = jnp.einsum("bsd,dw->bsw", x, p["w_in"], preferred_element_type=jnp.float32)
    u = u.astype(x.dtype)
    u, gate = jnp.split(u, 2, axis=-1)
    u, conv_state = _causal_conv(p, u, state["conv"])
    a, bterm = _gates(p, u[:, 0])
    h = a * state["h"] + bterm
    out = h.astype(x.dtype)[:, None] * jax.nn.gelu(gate)
    out = jnp.einsum("bsw,wd->bsd", out, p["w_out"], preferred_element_type=jnp.float32)
    return out.astype(x.dtype), {"h": h, "conv": conv_state}


def prefill(cfg, p, x, state=None):
    """Run the block over a prefix and return (out, final_state).

    With ``state`` (a previous chunk's final state) the recurrence, the conv
    window, and the LRU hidden state all continue across the chunk boundary —
    the chunked-prefill path of the serve runtime.
    """
    u = jnp.einsum("bsd,dw->bsw", x, p["w_in"], preferred_element_type=jnp.float32)
    u = u.astype(x.dtype)
    u, gate = jnp.split(u, 2, axis=-1)
    uc, _ = _causal_conv(p, u, None if state is None else state["conv"])
    a, bterm = _gates(p, uc)
    h = lru_scan(a, bterm, h0=None if state is None else state["h"])
    out = h.astype(x.dtype) * jax.nn.gelu(gate)
    out = jnp.einsum("bsw,wd->bsd", out, p["w_out"], preferred_element_type=jnp.float32)
    u32 = u.astype(jnp.float32)
    if state is not None:
        # conv inputs seen so far: previous window ++ this chunk
        u32 = jnp.concatenate([state["conv"], u32], axis=1)
    if u32.shape[1] < _CONV_WIDTH - 1:  # short prefix: left-pad with zeros
        pad = _CONV_WIDTH - 1 - u32.shape[1]
        u32 = jnp.pad(u32, ((0, 0), (pad, 0), (0, 0)))
    new_state = {
        "h": h[:, -1],
        "conv": u32[:, -(_CONV_WIDTH - 1):],
    }
    return out.astype(x.dtype), new_state
