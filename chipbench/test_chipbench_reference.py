"""The float32 reference against the program's own forward pass, and the
benchmark's weights against the program's parameter layout, at a toy
size on the CPU."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference, weights

SEED = 2**40 + 12345


def toy(tied: bool) -> dict:
    return {"hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "vocab_size": 512, "tie_word_embeddings": tied,
            "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
            "num_hidden_layers": 3}


def program_logits(c, toks):
    from repro.configs import get_config
    from repro.models.model import LM
    cfg = dataclasses.replace(
        get_config("smollm-360m"), num_layers=3, d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512,
        dtype="float32", tie_embeddings=c["tie_word_embeddings"])
    lm = LM(cfg)
    p = jax.jit(lambda w: weights.program_params(c, w))(
        weights.seed_words(SEED))
    want, _ = lm.abstract_params()
    assert jax.tree.structure(want) == jax.tree.structure(p)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    return np.asarray(lm.forward(p, {"tokens": toks[None]},
                                 remat=False)[0][0])


@pytest.mark.parametrize("tied", [True, False])
def test_reference_gaps_equal_the_program_forward(tied):
    c = toy(tied)
    toks = np.random.default_rng(0).integers(0, 512, 40).astype(np.int32)
    lg = program_logits(c, toks)
    prompt, served = toks[:10], toks[10:]
    gaps, ctrl = reference.served_gaps(c, SEED, [(prompt, served)],
                                       control=True)
    rows = np.arange(9, 39)
    want = lg[rows].max(-1) - lg[rows, served]
    np.testing.assert_allclose(gaps[0], want, atol=1e-4)
    # the float8 control departs from the float32 reference
    assert ctrl[0].max() > 0.05


def test_layer_weights_do_not_depend_on_how_many_are_made():
    c = toy(True)
    w = weights.seed_words(SEED)
    stacked = jax.jit(lambda w: weights.program_params(c, w))(w)
    one = jax.jit(lambda w: weights.layer_weights(c, w, 2))(w)
    att = stacked["blocks"][0]["attn"]
    assert bool(jnp.all(one["wq"] == att["wq"][2]))
    assert bool(jnp.all(one["attn_norm"] == att["norm"][2]))


def test_large_seeds_differ_in_both_words():
    a, b = weights.seed_words(2**33 + 1), weights.seed_words(1)
    assert a[0] == b[0] and a[1] != b[1]


def test_tokenize_matches_the_served_tokenizer():
    from repro.data.tokenizer import HashTokenizer
    text = "Explain binary search trees in simple terms please?"
    np.testing.assert_array_equal(reference.tokenize(text, 49152),
                                  HashTokenizer(49152).encode(text))
