"""Seeded random weights of a llama-architecture configuration.

The benchmark makes the served weights itself, on the device in one jitted
call, and the reference makes the same numbers again layer by layer from
the same seed: the reference takes no array from the program.  Every leaf
of every layer has its own key, ``fold_in(fold_in(fold_in(fold_in(key(0),
seed_lo), seed_hi), leaf), layer)``, so a layer's weights do not depend on
how many layers are made at once.

Matrices are normal, scaled by ``fan_in ** -0.5`` (the embedding by
``hidden_size ** -0.5``); norm gains are ``1 + 0.1 * normal`` so that a
path that drops a gain shows.  Values are rounded to bfloat16, the served
type; the reference computes with those rounded values in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

EMBED, HEAD, FINAL_NORM = 0, 1, 2
ATTN_NORM, WQ, WK, WV, WO = 10, 11, 12, 13, 14
MLP_NORM, W_GATE, W_UP, W_DOWN = 15, 16, 17, 18

#: per-layer leaves: name -> (leaf id, is a norm gain)
LAYER_LEAVES = {
    "attn_norm": (ATTN_NORM, True), "wq": (WQ, False), "wk": (WK, False),
    "wv": (WV, False), "wo": (WO, False), "mlp_norm": (MLP_NORM, True),
    "w_gate": (W_GATE, False), "w_up": (W_UP, False),
    "w_down": (W_DOWN, False),
}


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size as two uint32 words (a traced argument, so one
    compiled program serves every seed)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seed must be >= 0")
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                    np.uint32)


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


def _leaf(words, leaf: int, layer, shape, fan_in: int, norm: bool):
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), words[0]),
                             words[1])
    key = jax.random.fold_in(jax.random.fold_in(key, leaf), layer)
    x = jax.random.normal(key, shape, jnp.float32)
    x = 1.0 + 0.1 * x if norm else x * fan_in ** -0.5
    return x.astype(jnp.bfloat16)


def layer_weights(c: dict, words, layer) -> dict:
    """One layer's leaves in bfloat16."""
    return {name: _leaf(words, lid, layer, *layer_shapes(c)[name], norm)
            for name, (lid, norm) in LAYER_LEAVES.items()}


def embed(c: dict, words):
    d, v = c["hidden_size"], c["vocab_size"]
    return _leaf(words, EMBED, 0, (v, d), d, False)


def head(c: dict, words):
    """The output projection (hidden, vocab); the embedding's transpose
    where the configuration ties them."""
    if c["tie_word_embeddings"]:
        return embed(c, words).T
    d, v = c["hidden_size"], c["vocab_size"]
    return _leaf(words, HEAD, 0, (d, v), d, False)


def final_norm(c: dict, words):
    d = c["hidden_size"]
    return _leaf(words, FINAL_NORM, 0, (d,), d, True)


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
