"""Random weights from the seed, made on the device in one jitted call.

The tree has the layout the program's dense decoder takes (layers stacked on
a leading axis for its scan); ``rounds/*.py`` checks it against the
program's own parameter shapes before a round is built, so a change of
layout fails loudly. The distributions are the usual fan-in scaled normals
(embedding rows of unit scale, norm scales of one). The reference in
``reference.py`` reads the same layout by name.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np



def seed_words(seed: int) -> list:
    """A seed of any size as 32-bit words, lowest first."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    words = []
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return words


def layout(c: dict) -> dict:
    """``{name: (shape, std)}`` of every leaf, for the config dict ``c``."""
    L, d, h, hd = c["num_layers"], c["d_model"], c["num_heads"], c["head_dim"]
    kv, f, v = c["num_kv_heads"], c["d_ff"], padded_vocab(c)
    out = {
        "embed/table": ((v, d), 1.0),
        "final_ln/scale": ((d,), None),
        "layers/ln1/scale": ((L, d), None),
        "layers/ln2/scale": ((L, d), None),
        "layers/attn/wq": ((L, d, h, hd), 1.0 / math.sqrt(d)),
        "layers/attn/wk": ((L, d, kv, hd), 1.0 / math.sqrt(d)),
        "layers/attn/wv": ((L, d, kv, hd), 1.0 / math.sqrt(d)),
        "layers/attn/wo": ((L, h, hd, d), 1.0 / math.sqrt(h * hd)),
        "layers/mlp/wi": ((L, d, f), 1.0 / math.sqrt(d)),
        "layers/mlp/wg": ((L, d, f), 1.0 / math.sqrt(d)),
        "layers/mlp/wo": ((L, f, d), 1.0 / math.sqrt(f)),
    }
    if not c.get("tie_embeddings", False):
        out["lm_head/w"] = ((d, v), 1.0 / math.sqrt(d))
    return out


def padded_vocab(c: dict) -> int:
    """Rows of the embedding table: the vocabulary rounded up to 512."""
    return -(-c["vocab_size"] // 512) * 512


def nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def flatten(tree, prefix: str = "") -> dict:
    """``{"a/b/c": leaf}`` of a nested dict."""
    out = {}
    for k in sorted(tree):
        v = tree[k]
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, path + "/"))
        else:
            out[path] = v
    return out


def init_params(key, c: dict):
    """The parameter tree for config ``c`` from ``key``, in the config's
    dtype. Call it under ``jax.jit``: each leaf is drawn and cast in one
    fusion on the device."""
    dtype = jnp.dtype(c["dtype"])
    spec = layout(c)
    keys = jax.random.split(key, len(spec))
    flat = {}
    for k, (path, (shape, std)) in zip(keys, sorted(spec.items())):
        if std is None:
            flat[path] = jnp.ones(shape, dtype)
        else:
            flat[path] = (std * jax.random.normal(k, shape, jnp.float32)
                          ).astype(dtype)
    return nest(flat)


def seed_array(seed: int) -> np.ndarray:
    """A seed below 2**64 as two uint32 words, the argument of
    :func:`key_from` (seeds may exceed 32 bits)."""
    words = seed_words(seed)
    if len(words) > 2:
        raise ValueError(f"--seed must be below 2**64, got {seed}")
    return np.array(words + [0] * (2 - len(words)), np.uint32)


def key_from(words):
    """A PRNG key from :func:`seed_array`'s words; traceable."""
    key = jax.random.PRNGKey(0)
    for i in range(2):
        key = jax.random.fold_in(key, words[i])
    return key
