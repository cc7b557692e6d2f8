"""Plain float32 reference of a decoder, and its control.

Token embedding; the layers of the configuration's family
(``chipbench/families/<name>.py``: ``reference_weights`` and
``reference_layer``), one at a time over every sequence, so that only one
layer's weights are on the device at once; a final RMSNorm and the output
head (the embedding's transpose when tied).  It imports nothing of the
program; its weights come from ``weights.py``, the family and the seed,
and its tokens from :func:`tokenize`, a copy of the served tokenizer's
word hash.

Every matrix product runs under ``jax.default_matmul_precision("highest")``.

``quant=True`` is the control: the same forward with the inputs of every
projection (weights per output column, activations per row) rounded to
float8 e4m3 with a scale, the precision one step below the served
bfloat16.  Attention scores and norms stay in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import families
from chipbench.weights import embed, final_norm, head, seed_words

_HASH_SEED = 1234567891
_PAD = 128           # sequences are padded to a multiple of this length
FP8_MAX = 448.0      # largest finite float8_e4m3fn


def tokenize(text: str, vocab: int) -> np.ndarray:
    """Word-hash ids: one id per whitespace-separated word."""
    ids = []
    for word in text.split():
        h = _HASH_SEED
        for ch in word:
            h = (h * 1000003 ^ ord(ch)) & 0x7FFFFFFF
        ids.append(h % vocab)
    return np.asarray(ids, np.int32)


def _fp8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant: bool):
    if quant:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


@functools.partial(jax.jit, static_argnames=("c_items",))
def _embed_rows(words, toks, c_items):
    return embed(dict(c_items), words)[toks].astype(jnp.float32)


def _head_logits(words, x, c: dict, quant: bool):
    h = _rms(x, final_norm(c, words).astype(jnp.float32),
             float(c["rms_norm_eps"]))
    return _mm(h, head(c, words).astype(jnp.float32), quant)


@functools.partial(jax.jit, static_argnames=("c_items",))
def _gap(words, x, targets, c_items):
    """Per row: best logit minus the logit of ``targets``."""
    ref = _head_logits(words, x, dict(c_items), False)
    return ref.max(-1) - jnp.take_along_axis(ref, targets[:, None], 1)[:, 0]


@functools.partial(jax.jit, static_argnames=("c_items",))
def _control_gap(words, x, xq, c_items):
    """Per row: best reference logit minus the reference's logit for the
    token the control puts first."""
    c = dict(c_items)
    ref = _head_logits(words, x, c, False)
    pick = _head_logits(words, xq, c, True).argmax(-1)
    return ref.max(-1) - jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]


def static(c: dict) -> tuple:
    """The scalar keys of configuration ``c``, hashable: a static argument
    of a jitted function, which rebuilds the dict with ``dict(...)``."""
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (bool, int, float, str))))


def served_gaps(c: dict, seed: int, seqs: list, control: bool = False):
    """Reference logits over each ``(prompt_ids, served_ids)`` pair.

    For each served token, the gap by which the reference's logit for it
    lies below the reference's best logit at that position.  Returns a
    list (one array per sequence) of those gaps; with ``control`` also the
    same gaps for the token the float8 control puts first at each
    position.
    """
    items, fam = static(c), families.of(c)
    words = jnp.asarray(seed_words(seed))
    xs, xq, spans = [], [], []
    for prompt, served in seqs:
        toks = np.concatenate([prompt, served]).astype(np.int32)
        n = len(toks)
        padded = np.zeros(-(-n // _PAD) * _PAD, np.int32)
        padded[:n] = toks
        x = _embed_rows(words, jnp.asarray(padded), items)
        xs.append(x)
        xq.append(x)
        # position p predicts token p+1: the served tokens are predicted
        # at rows len(prompt)-1 .. n-2
        targets = np.zeros_like(padded)
        targets[:n - 1] = toks[1:]
        spans.append((len(prompt) - 1, n - 1, jnp.asarray(targets)))
    with jax.default_matmul_precision("highest"):
        for layer in range(c["num_hidden_layers"]):
            w = fam.reference_weights(c, words, layer)
            xs = [fam.reference_layer(c, layer, x, w, False) for x in xs]
            if control:
                xq = [fam.reference_layer(c, layer, x, w, True) for x in xq]
            del w
        gaps, ctrl = [], []
        for x, q, (lo, hi, targets) in zip(xs, xq, spans):
            gaps.append(np.asarray(_gap(words, x, targets, items))[lo:hi])
            if control:
                ctrl.append(np.asarray(
                    _control_gap(words, x, q, items))[lo:hi])
    return (gaps, ctrl) if control else gaps
