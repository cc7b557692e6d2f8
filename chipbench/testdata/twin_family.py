"""A test-only family (``TwinForCausalLM``): the llama block, served
through a module of its own, which makes its weights leaf by leaf and
counts each call the harness makes into it.

``test_chipbench_families.py`` installs it as ``chipbench.families.twin``,
where the lookup finds it.  ``BROKEN_LAYER``, when set, is a layer index
whose reference forward hands its input back unchanged.
"""

import jax
import jax.numpy as jnp

from chipbench.families import llama
from chipbench.weights import embed, final_norm, head, seed_words

CALLS: dict = {}
BROKEN_LAYER = None


def _count(name: str) -> None:
    CALLS[name] = CALLS.get(name, 0) + 1


def arch_config(c):
    _count("arch_config")
    return llama.arch_config(c)


def make_params(c, seed):
    """One jitted call per layer, stacked afterwards."""
    _count("make_params")
    words = seed_words(seed)
    one = jax.jit(lambda w, i: llama.layer_weights(c, w, i))
    layers = [one(words, jnp.uint32(i))
              for i in range(c["num_hidden_layers"])]
    st = {k: jnp.stack([w[k] for w in layers]) for k in layers[0]}
    params = {"embed": jax.jit(lambda w: embed(c, w))(words),
              "blocks": ({
                  "attn": {"norm": st["attn_norm"], "wq": st["wq"],
                           "wk": st["wk"], "wv": st["wv"], "wo": st["wo"]},
                  "mlp": {"norm": st["mlp_norm"], "w_gate": st["w_gate"],
                          "w_up": st["w_up"], "w_down": st["w_down"]},
              },),
              "final_norm": jax.jit(lambda w: final_norm(c, w))(words)}
    if not c["tie_word_embeddings"]:
        params["head"] = jax.jit(lambda w: head(c, w))(words)
    return params


def reference_weights(c, words, layer):
    _count("reference_weights")
    return llama.reference_weights(c, words, layer)


def reference_layer(c, layer, x, w, quant):
    _count("reference_layer")
    if layer == BROKEN_LAYER:
        return x
    return llama.reference_layer(c, layer, x, w, quant)


def decode_steps(c, args):
    _count("decode_steps")
    return llama.decode_steps(c, args)


def prefill(c, args):
    _count("prefill")
    return llama.prefill(c, args)


def param_count(c):
    _count("param_count")
    return llama.param_count(c)
