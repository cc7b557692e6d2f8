"""Block dispatch and the scanned layer stack.

The model is ``block_pattern x pattern_repeats``.  We scan over repeats with
the per-position params stacked on a leading axis, so HLO size and compile
time are O(pattern length), not O(depth) — essential for lowering 40
(arch x shape) dry-run cells on 512 devices, and the production choice anyway.
Caches ride along as scan xs/ys: prefill emits per-repeat caches as ys,
and decode consumes and re-emits them, except for a ring-attention cache
with one scalar fill level per layer (the serial decode path): its
stacked K/V ride the scan's carry instead, and each layer writes only its
new row into the stack, in place, and attends its ring from the stack.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, ATTN_MOE, MAMBA, MAMBA_MOE, MLSTM,
                                SLSTM, XATTN)
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.models.layers import apply_mlp, init_mlp
from repro.sharding import constrain


# Dry-run cost graphs set this to the repeat count so cost_analysis (which
# counts while bodies once) sees every layer.  Production graphs leave it 1.
SCAN_UNROLL = {"n": 1}


def init_block(cfg, kind: str, key):
    """Returns (params, axes) for one block of the given kind."""
    k1, k2 = jax.random.split(key)
    if kind == ATTN:
        ap, aa = attn_lib.init_attention(cfg, k1)
        mp, ma = init_mlp(cfg, k2)
        return {"attn": ap, "mlp": mp}, {"attn": aa, "mlp": ma}
    if kind == ATTN_MOE:
        ap, aa = attn_lib.init_attention(cfg, k1)
        mp, ma = moe_lib.init_moe(cfg, k2)
        return {"attn": ap, "moe": mp}, {"attn": aa, "moe": ma}
    if kind == XATTN:
        ap, aa = attn_lib.init_attention(cfg, k1, cross=True)
        mp, ma = init_mlp(cfg, k2)
        return {"xattn": ap, "mlp": mp}, {"xattn": aa, "mlp": ma}
    if kind == MAMBA:
        sp, sa = ssm_lib.init_mamba(cfg, k1)
        mp, ma = init_mlp(cfg, k2)
        return {"mamba": sp, "mlp": mp}, {"mamba": sa, "mlp": ma}
    if kind == MAMBA_MOE:
        sp, sa = ssm_lib.init_mamba(cfg, k1)
        mp, ma = moe_lib.init_moe(cfg, k2)
        return {"mamba": sp, "moe": mp}, {"mamba": sa, "moe": ma}
    if kind == SLSTM:
        return xlstm_lib.init_slstm(cfg, k1)
    if kind == MLSTM:
        return xlstm_lib.init_mlstm(cfg, k1)
    raise ValueError(kind)


def apply_block(cfg, kind: str, p, x, *, mode: str, cache=None,
                image_embeds=None, prompt_len=None):
    """Returns (x, new_cache, aux_loss).  ``prompt_len`` marks a bucketed
    prefill's pads, which recurrent blocks keep out of their state."""
    aux = jnp.zeros((), jnp.float32)
    if kind in (ATTN, ATTN_MOE):
        x, new_cache = attn_lib.attn_block(cfg, p["attn"], x, mode=mode,
                                           pos_offset=0, cache=cache)
    elif kind == XATTN:
        x, new_cache = attn_lib.xattn_block(cfg, p["xattn"], x, mode=mode,
                                            image_embeds=image_embeds,
                                            cache=cache)
    elif kind in (MAMBA, MAMBA_MOE):
        x, new_cache = ssm_lib.mamba_block(cfg, p["mamba"], x, mode=mode,
                                           cache=cache, prompt_len=prompt_len)
    elif kind == SLSTM:
        return (*xlstm_lib.slstm_block(cfg, p, x, mode=mode, cache=cache), aux)
    elif kind == MLSTM:
        return (*xlstm_lib.mlstm_block(cfg, p, x, mode=mode, cache=cache), aux)
    else:
        raise ValueError(kind)

    if kind in (ATTN_MOE, MAMBA_MOE):
        x, aux = moe_lib.apply_moe(cfg, p["moe"], x)
    else:
        x = apply_mlp(cfg, p["mlp"], x)
    return x, new_cache, aux


def run_stack(cfg, blocks_params, x, *, mode: str, caches=None,
              image_embeds=None, remat: bool = True, prompt_len=None):
    """Scan the pattern x repeats stack.

    blocks_params: tuple over pattern positions, leaves stacked (repeats, ...).
    caches: matching stacked cache pytree (or None).
    prompt_len: a bucketed prefill's true length(s) (``apply_block``).
    Returns (x, new_caches, aux_total).
    """
    pattern = cfg.block_pattern

    # Per-block remat nested inside the per-pattern-step remat: the backward
    # sweep of one pattern step then peaks at max-over-blocks residuals
    # instead of sum-over-blocks (8 blocks/step for jamba).
    def block_fn(kind, p, x, c):
        return apply_block(cfg, kind, p, x, mode=mode, cache=c,
                           image_embeds=image_embeds, prompt_len=prompt_len)

    if mode == "train" and remat:
        block_fn = jax.checkpoint(block_fn, prevent_cse=False,
                                  static_argnums=(0,))

    # Serial decode's ring caches ride the carry: returned as ys, each
    # layer's whole ring would be written back into the stack to change
    # one row.  Chosen by what the cache holds, never by a flag.
    in_carry = ([False] * len(pattern) if caches is None else
                [_ring_in_carry(mode, kind, c)
                 for kind, c in zip(pattern, caches)])
    rings = layers = None
    if any(in_carry):
        rings = tuple((c["k"], c["v"]) if r else None
                      for r, c in zip(in_carry, caches))
        caches = tuple({"t": c["t"]} if r else c
                       for r, c in zip(in_carry, caches))
        layers = jnp.arange(cfg.pattern_repeats, dtype=jnp.int32)

    def body(carry, xs):
        x, aux, rings = carry
        blk_params, blk_caches, layer = xs
        x = constrain(x, "batch", "seq_sp", "embed")
        new_caches, new_rings = [], []
        for pos, kind in enumerate(pattern):
            c = None if blk_caches is None else blk_caches[pos]
            if in_carry[pos]:
                c = {"k": rings[pos][0], "v": rings[pos][1], "t": c["t"],
                     "layer": layer}
            x, nc, a = block_fn(kind, blk_params[pos], x, c)
            if in_carry[pos]:
                new_rings.append((nc["k"], nc["v"]))
                nc = {"t": nc["t"]}
            else:
                new_rings.append(None)
            new_caches.append(nc)
            aux = aux + a
        return (x, aux, None if rings is None else tuple(new_rings)), \
            tuple(new_caches)

    if mode == "train" and remat:
        body = jax.checkpoint(body, prevent_cse=False)

    aux0 = jnp.zeros((), jnp.float32)
    (x, aux, rings), new_caches = jax.lax.scan(
        body, (x, aux0, rings), (blocks_params, caches, layers),
        unroll=min(SCAN_UNROLL["n"], cfg.pattern_repeats))
    if rings is not None:
        new_caches = tuple(
            c if r is None else {"k": r[0], "v": r[1], "t": c["t"]}
            for r, c in zip(rings, new_caches))
    return x, new_caches, aux


def _ring_in_carry(mode: str, kind: str, cache) -> bool:
    """A decode-mode ring-attention cache with one scalar fill level per
    layer: exactly "k", "v" and a ``t`` of shape (repeats,).  Per-lane
    fill levels (repeats, B), block-paged caches ("bt") and every other
    mode or block keep the xs/ys path."""
    return (mode == "decode" and kind in (ATTN, ATTN_MOE)
            and set(cache) == {"k", "v", "t"} and jnp.ndim(cache["t"]) == 1)
