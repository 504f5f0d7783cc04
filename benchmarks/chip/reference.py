"""The plain round: local SGD over a dense decoder in float32 jax.numpy.

Written from the paper's description of the flat round (broadcast, local
SGD on each client, mean of the client deltas, server update) and of the
decoder as the configuration file states it (pre-RMSNorm blocks, rotary
positions on the two halves of each head, causal softmax attention, SwiGLU,
untied output head, mean token cross entropy). It imports nothing of the
program under test; its weights come from ``weights.py`` and its batches
are the ones the timed rounds were fed.

Every matmul runs in float32 at ``Precision.HIGHEST`` (a TPU otherwise
multiplies float32 in bfloat16). Parameters are stored in the config's
dtype, as the configuration states, and each update is rounded to it: the
client's parameters after every local step and the server's after the round.
Clients run one after another, so the reference fits one chip beside
nothing else.

``quant="fp8"`` is the control: every matmul operand is rounded to float8
e4m3 with a per-tensor scale before the product (straight-through for the
gradient), the step below the configuration's bfloat16 that a later change
could be tempted to take.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.chip import weights

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


@jax.custom_jvp
def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)) / E4M3_MAX, 1e-30)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


@_fp8.defjvp
def _fp8_jvp(primals, tangents):
    return _fp8(primals[0]), tangents[0]


def _mm(eq, a, b, quant):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST, preferred_element_type=F32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, S, H, hd); rotates the first half of each head against the
    second by angle position / theta**(2i / hd)."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs  # (S, hd/2)
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(c, quant, x, lp):
    eps, hd = c["norm_eps"], c["head_dim"]
    h = _rms(x, lp["ln1"]["scale"], eps)
    a = lp["attn"]
    q = _rope(_mm("bsd,dhk->bshk", h, a["wq"], quant), c["rope_theta"])
    k = _rope(_mm("bsd,dhk->bshk", h, a["wk"], quant), c["rope_theta"])
    v = _mm("bsd,dhk->bshk", h, a["wv"], quant)
    rep = c["num_heads"] // c["num_kv_heads"]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = _mm("bqhk,bthk->bhqt", q, k, quant) / math.sqrt(hd)
    n = x.shape[1]
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqt,bthk->bqhk", w, v, quant)
    x = x + _mm("bqhk,hkd->bqd", o, a["wo"], quant)
    h2 = _rms(x, lp["ln2"]["scale"], eps)
    m = lp["mlp"]
    gate = jax.nn.silu(_mm("bsd,df->bsf", h2, m["wg"], quant))
    up = _mm("bsd,df->bsf", h2, m["wi"], quant)
    return x + _mm("bsf,fd->bsd", gate * up, m["wo"], quant)


def loss(c, quant, p, tokens, labels):
    """Mean next-token cross entropy of float32 parameters ``p``."""
    x = jnp.take(p["embed"]["table"], tokens, axis=0)
    body = jax.checkpoint(lambda h, lp: (_layer(c, quant, h, lp), None))
    x, _ = jax.lax.scan(body, x, p["layers"])
    x = _rms(x, p["final_ln"]["scale"], c["norm_eps"])
    head = (p["embed"]["table"].T if c.get("tie_embeddings")
            else p["lm_head"]["w"])
    logits = _mm("bsd,dv->bsv", x, head, quant)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


def _to(tree, dtype):
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)


@functools.partial(jax.jit, static_argnames=("c", "t", "quant"))
def client_update(c, t, quant, p0, tokens, labels):
    """``local_steps`` clipped SGD steps from ``p0`` on one client's
    ``(steps, batch, seq)`` tokens: (final parameters in p0's dtype, mean
    loss over the steps)."""
    c, t = dict(c), dict(t)
    grad = jax.value_and_grad(functools.partial(loss, c, quant))

    def step(p, batch):
        value, g = grad(_to(p, F32), *batch)
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
        scale = jnp.minimum(1.0, t["grad_clip"] / jnp.maximum(norm, 1e-9))
        p = jax.tree_util.tree_map(
            lambda w, gw: (w.astype(F32) - t["client_lr"] * scale * gw
                           ).astype(w.dtype), p, g)
        return p, value

    p, losses = jax.lax.scan(step, p0, (tokens, labels))
    return p, jnp.mean(losses)


@jax.jit
def mean_delta(p0, finals):
    """Mean over ``finals`` of (final - p0), in float32."""
    def mean(w0, *ws):
        return sum(w.astype(F32) - w0.astype(F32) for w in ws) / len(ws)

    return jax.tree_util.tree_map(mean, p0, *finals)


@jax.jit
def server_update(p0, server_lr, delta):
    """p0 plus server_lr times ``delta``, in p0's dtype."""
    return jax.tree_util.tree_map(
        lambda w0, d: (w0.astype(F32) + server_lr * d).astype(w0.dtype),
        p0, delta)


@jax.jit
def leaf_change_norms(p, p0):
    """Per leaf, the float32 norm of p - p0."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32) - b.astype(F32)))),
        p, p0)


@functools.partial(jax.jit, static_argnames=("c",))
def init(c, words):
    """The seed's weights (``weights.py``) for the frozen config ``c``."""
    return weights.init_params(weights.key_from(words), dict(c))


def frozen(d: dict):
    """A hashable stand-in for a dict of numbers (a jit static argument)."""
    return tuple(sorted((k, v) for k, v in d.items()
                        if isinstance(v, (int, float, str, bool))))


def run_round(c: dict, t: dict, p, batch, quant=None):
    """One plain round from parameters ``p`` on ``batch`` (tokens and
    labels of shape ``(cohort, steps, batch, seq)``): (new parameters,
    loss)."""
    cf, tf = frozen(c), frozen(t)
    finals, losses = [], []
    for i in range(batch["tokens"].shape[0]):
        final, value = client_update(cf, tf, quant, p, batch["tokens"][i],
                                     batch["labels"][i])
        finals.append(final)
        losses.append(value)
    delta = mean_delta(p, finals)
    del finals
    new = server_update(p, jnp.float32(t["server_lr"]), delta)
    return new, float(jnp.mean(jnp.stack(losses)))
