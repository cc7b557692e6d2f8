"""The llama block (``LlamaForCausalLM``): the program's config check, the
served weights, the reference's layer and the roofline counts.

A layer is pre-RMSNorm grouped-query attention with rotary positions
(``rotate_half`` form, base ``rope_theta``), causal softmax, output
projection and residual, then pre-RMSNorm SwiGLU MLP and residual, as the
Hugging Face description has it.  The program lays such a stack out as one
pattern position (``block_pattern ("attn",)``) with every leaf stacked
over the layers.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from chipbench.reference import _mm, _rms, static
from chipbench.roofline import BYTES
from chipbench.weights import _leaf, embed, final_norm, head, seed_words

ATTN_NORM, WQ, WK, WV, WO = 10, 11, 12, 13, 14
MLP_NORM, W_GATE, W_UP, W_DOWN = 15, 16, 17, 18

#: per-layer leaves: name -> (leaf id, is a norm gain)
LAYER_LEAVES = {
    "attn_norm": (ATTN_NORM, True), "wq": (WQ, False), "wk": (WK, False),
    "wv": (WV, False), "wo": (WO, False), "mlp_norm": (MLP_NORM, True),
    "w_gate": (W_GATE, False), "w_up": (W_UP, False),
    "w_down": (W_DOWN, False),
}


# ------------------------------------------------------------- the program
def arch_config(c: dict):
    """The program's ``ArchConfig`` for configuration file ``c``, checked
    against the file's published sizes."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(c["arch"]), **c["overrides"])
    want = {"num_layers": c["num_hidden_layers"],
            "d_model": c["hidden_size"],
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "head_dim": c["head_dim"], "d_ff": c["intermediate_size"],
            "vocab_size": c["vocab_size"],
            "tie_embeddings": c["tie_word_embeddings"],
            "rope_theta": c["rope_theta"], "norm_eps": c["rms_norm_eps"],
            "dtype": c["torch_dtype"], "block_pattern": ("attn",),
            "mlp_activation": "silu", "qk_norm": False}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"program config differs from the file: {bad}")
    return cfg


def layer_shapes(c: dict) -> dict:
    """Shape and fan-in of each per-layer leaf of configuration ``c``
    (Hugging Face key names)."""
    d, f = c["hidden_size"], c["intermediate_size"]
    qd = c["num_attention_heads"] * c["head_dim"]
    kd = c["num_key_value_heads"] * c["head_dim"]
    return {"attn_norm": ((d,), d), "wq": ((d, qd), d), "wk": ((d, kd), d),
            "wv": ((d, kd), d), "wo": ((qd, d), qd),
            "mlp_norm": ((d,), d), "w_gate": ((d, f), d),
            "w_up": ((d, f), d), "w_down": ((f, d), f)}


def layer_weights(c: dict, words, layer) -> dict:
    """One layer's leaves in bfloat16."""
    return {name: _leaf(words, lid, layer, *layer_shapes(c)[name], norm)
            for name, (lid, norm) in LAYER_LEAVES.items()}


def program_params(c: dict, words):
    """The served model's parameter tree, as ``repro.models.model.LM``
    lays it out for a pure-attention stack: one pattern position, leaves
    stacked over layers."""
    layers = jax.vmap(lambda i: layer_weights(c, words, i))(
        jnp.arange(c["num_hidden_layers"], dtype=jnp.uint32))
    params = {
        "embed": embed(c, words),
        "blocks": ({
            "attn": {"norm": layers["attn_norm"], "wq": layers["wq"],
                     "wk": layers["wk"], "wv": layers["wv"],
                     "wo": layers["wo"]},
            "mlp": {"norm": layers["mlp_norm"], "w_gate": layers["w_gate"],
                    "w_up": layers["w_up"], "w_down": layers["w_down"]},
        },),
        "final_norm": final_norm(c, words),
    }
    if not c["tie_word_embeddings"]:
        params["head"] = head(c, words)
    return params


def make_params(c: dict, seed: int):
    """The served weights, made on the device in one jitted call."""
    return jax.jit(functools.partial(program_params, c))(seed_words(seed))


# ----------------------------------------------------------- the reference
@dataclass(frozen=True)
class Dims:
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    theta: float

    @classmethod
    def of(cls, c: dict) -> "Dims":
        return cls(c["hidden_size"], c["num_attention_heads"],
                   c["num_key_value_heads"], c["head_dim"],
                   float(c["rms_norm_eps"]), float(c["rope_theta"]))


def _rope(x, theta):
    """x: (S, heads, head_dim), positions 0..S-1."""
    s, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # (S, half)
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("d", "quant"))
def _layer(x, w, d: Dims, quant: bool):
    s = x.shape[0]
    h = _rms(x, w["attn_norm"], d.eps)
    q = _mm(h, w["wq"], quant).reshape(s, d.heads, d.head_dim)
    k = _mm(h, w["wk"], quant).reshape(s, d.kv_heads, d.head_dim)
    v = _mm(h, w["wv"], quant).reshape(s, d.kv_heads, d.head_dim)
    q, k = _rope(q, d.theta), _rope(k, d.theta)
    rep = d.heads // d.kv_heads          # query head j reads kv head j//rep
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) * d.head_dim ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(s, d.heads * d.head_dim)
    x = x + _mm(o, w["wo"], quant)
    h = _rms(x, w["mlp_norm"], d.eps)
    g = jax.nn.silu(_mm(h, w["w_gate"], quant)) * _mm(h, w["w_up"], quant)
    return x + _mm(g, w["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("c_items",))
def _layer_w(words, layer, c_items):
    w = layer_weights(dict(c_items), words, layer)
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def reference_weights(c: dict, words, layer: int) -> dict:
    """Layer ``layer``'s leaves, rounded to bfloat16, in float32."""
    return _layer_w(words, layer, static(c))


def reference_layer(c: dict, layer: int, x, w, quant: bool):
    """Layer ``layer`` over one sequence ``x`` (S, hidden)."""
    return _layer(x, w, Dims.of(c), quant)


# ------------------------------------------------------------ the roofline
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
    ``fills[i]`` positions each (the new token's included): every weight
    once (the embedding table only for the rows looked up, unless it
    doubles as the head), and the K/V of the positions attended."""
    rows = len(fills)
    flops = rows * 2 * (matmul_params(c) + head_params(c)) \
        + attn_flops_per_position(c) * sum(fills)
    weights = (matmul_params(c) + norm_params(c) + head_params(c)) * BYTES
    lookups = rows * c["hidden_size"] * BYTES
    return flops, weights + lookups + kv_bytes_per_position(c) * sum(fills)


def decode_steps(c: dict, args: dict) -> list:
    """``(flops, bytes)`` of each decode step of a ``chipbench.segment``
    mark: one row, whose step ``j`` attends the prompt, the first token
    and the ``first_step + j`` tokens decoded before it, and itself."""
    base = args["plen"] + 1 + args["first_step"]
    return [decode_step(c, [base + j]) for j in range(args["steps"])]


def prefill(c: dict, args: dict) -> int:
    """Operations to prefill the ``tokens``-token prompt of a
    ``chipbench.prefill`` mark: every token through every layer, causal
    attention, and the head at the last position."""
    n = args["tokens"]
    return (2 * matmul_params(c) * n
            + attn_flops_per_position(c) * n * (n + 1) // 2
            + 2 * head_params(c))
