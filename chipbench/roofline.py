"""Operations and bytes that a llama-architecture step needs, from shapes.

``c`` is a configuration file's dict (Hugging Face key names).  Weights
and the K/V cache are bfloat16 (2 bytes).  The bytes are what the
algorithm needs: every weight once per step (the embedding table only for
the rows looked up, unless it doubles as the output head), and the K/V of
the positions a row actually attends.  A path that reads less cannot push
a share of the roofline over 100%.
"""

from __future__ import annotations

import json
import os

BYTES = 2          # bfloat16

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """``{"flops": ..., "hbm_bytes_per_s": ...}`` of one chip of this kind.
    An unknown kind is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def layer_matmul_params(c: dict) -> int:
    d, f = c["hidden_size"], c["intermediate_size"]
    qd = c["num_attention_heads"] * c["head_dim"]
    kd = c["num_key_value_heads"] * c["head_dim"]
    return d * (qd + 2 * kd) + qd * d + 3 * d * f


def matmul_params(c: dict) -> int:
    """Parameters of every layer's projections (no embedding, no head)."""
    return c["num_hidden_layers"] * layer_matmul_params(c)


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def norm_params(c: dict) -> int:
    return (2 * c["num_hidden_layers"] + 1) * c["hidden_size"]


def param_count(c: dict) -> int:
    """Every parameter held: layers, norms, embedding, and an untied head."""
    n = matmul_params(c) + norm_params(c) + head_params(c)
    return n + (0 if c["tie_word_embeddings"] else head_params(c))


def kv_bytes_per_position(c: dict) -> int:
    return (c["num_hidden_layers"] * 2 * c["num_key_value_heads"]
            * c["head_dim"] * BYTES)


def attn_flops_per_position(c: dict) -> int:
    """Scores and weighted values of one query against one key, over every
    layer and head: two multiply-adds per head dimension."""
    return (4 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"])


def decode_step(c: dict, fills) -> tuple:
    """``(flops, bytes)`` of one decode step over rows that attend
    ``fills[i]`` positions each (the new token's included)."""
    rows = len(fills)
    flops = rows * 2 * (matmul_params(c) + head_params(c)) \
        + attn_flops_per_position(c) * sum(fills)
    weights = (matmul_params(c) + norm_params(c) + head_params(c)) * BYTES
    lookups = rows * c["hidden_size"] * BYTES
    return flops, weights + lookups + kv_bytes_per_position(c) * sum(fills)


def prefill(c: dict, n: int) -> int:
    """Operations to prefill an ``n``-token prompt: every token through
    every layer, causal attention, and the head at the last position."""
    return (2 * matmul_params(c) * n
            + attn_flops_per_position(c) * n * (n + 1) // 2
            + 2 * head_params(c))


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The shortest time the chip could take: compute- or memory-bound."""
    return max(flops / peak["flops"], nbytes / peak["hbm_bytes_per_s"])
