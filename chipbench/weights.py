"""Seeded random weights: the key of every leaf, and the leaves that every
causal LM has (embedding, final norm, head).

The benchmark makes the served weights itself, on the device, and the
reference makes the same numbers again layer by layer from the same seed:
the reference takes no array from the program.  Every leaf of every layer
has its own key, ``fold_in(fold_in(fold_in(fold_in(key(0), seed_lo),
seed_hi), leaf), layer)``, so a layer's weights do not depend on how many
layers are made at once.  Leaf ids 0-2 are the ones below; a family
(``chipbench/families/``) numbers its per-layer leaves from 10.

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


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size as two uint32 words (a traced argument, so one
    compiled program serves every seed)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seed must be >= 0")
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                    np.uint32)


def _leaf(words, leaf: int, layer, shape, fan_in: int, norm: bool):
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), words[0]),
                             words[1])
    key = jax.random.fold_in(jax.random.fold_in(key, leaf), layer)
    x = jax.random.normal(key, shape, jnp.float32)
    x = 1.0 + 0.1 * x if norm else x * fan_in ** -0.5
    return x.astype(jnp.bfloat16)


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
