"""Model FLOPs computed from the configuration's shapes, and the chips'
peaks.

Model FLOPs count the forward and backward matmuls a training token needs
(6 per matmul parameter) plus causal-free attention scores and values
(12 * layers * seq * heads * head_dim), and no recomputation: the numerator
of MFU.
"""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def matmul_params(c: dict) -> int:
    """Parameters that enter a matmul: attention and MLP projections of every
    layer and the output head (the embedding is a lookup)."""
    d, h, kv, hd, f = (c["d_model"], c["num_heads"], c["num_kv_heads"],
                       c["head_dim"], c["d_ff"])
    layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return c["num_layers"] * layer + d * c["vocab_size"]


def param_count(c: dict) -> int:
    """Every parameter: matmuls, embedding, norm scales."""
    d = c["d_model"]
    embed = 0 if c.get("tie_embeddings") else c["vocab_size"] * d
    return matmul_params(c) + embed + 2 * c["num_layers"] * d + d


def flops_per_token(c: dict, seq: int) -> float:
    """Training FLOPs per token, recomputation not counted."""
    attn = 12 * c["num_layers"] * seq * c["num_heads"] * c["head_dim"]
    return 6.0 * matmul_params(c) + attn


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
