"""The LM: embedding, scanned block stack, head, losses, prefill/decode.

Pure-functional API; ``LM`` only holds the config.  All functions are
jit/pjit-compatible.  Batches are dicts:

* text archs:  {"tokens": (B,S) i32, "labels": (B,S) i32}
* vlm:         + {"image_embeds": (B, T_img, D) bf16}
* audio:       {"frames": (B,S,D) bf16, "labels": (B,S) i32}  (frontend stub)
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, ATTN_MOE, MAMBA, MAMBA_MOE, MLSTM, SLSTM, XATTN, ArchConfig
from repro.models import transformer as tf
from repro.models.layers import dense, dtype_of, init_dense, rmsnorm
from repro.sharding import constrain

LOSS_CHUNK = 512  # sequence-chunked cross entropy (never materialize f32 logits)
AUX_LOSS_WEIGHT = 0.01


def init_model(cfg: ArchConfig, key) -> tuple[Dict[str, Any], Dict[str, Any]]:
    """Returns (params, axes).  Block params are stacked over repeats."""
    dt = dtype_of(cfg)
    k_emb, k_blocks, k_head = jax.random.split(key, 3)
    params: Dict[str, Any] = {}
    axes: Dict[str, Any] = {}

    if not cfg.audio_frontend:
        params["embed"] = (jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model))
                           * cfg.d_model ** -0.5).astype(dt)
        axes["embed"] = ("vocab", "embed_w")

    def init_rep(k):
        ks = jax.random.split(k, len(cfg.block_pattern))
        return tuple(tf.init_block(cfg, kind, ks[i])[0]
                     for i, kind in enumerate(cfg.block_pattern))

    rep_keys = jax.random.split(k_blocks, cfg.pattern_repeats)
    params["blocks"] = jax.vmap(init_rep)(rep_keys)
    from repro.sharding.rules import is_axes_leaf
    block_axes = _block_axes(cfg)
    axes["blocks"] = jax.tree.map(lambda a: (None, *a), block_axes,
                                  is_leaf=is_axes_leaf)

    params["final_norm"] = jnp.ones((cfg.d_model,), dtype=dt)
    axes["final_norm"] = ("embed",)
    if not cfg.tie_embeddings:
        params["head"] = init_dense(k_head, cfg.d_model, cfg.vocab_size, dt)
        axes["head"] = ("embed_w", "vocab")
    return params, axes


def _block_axes(cfg):
    """Axes for one repeat of the pattern (static; no array allocation)."""
    captured = {}

    def f(key):
        ks = jax.random.split(key, len(cfg.block_pattern))
        out, ax = [], []
        for i, kind in enumerate(cfg.block_pattern):
            p, a = tf.init_block(cfg, kind, ks[i])
            out.append(p)
            ax.append(a)
        captured["axes"] = tuple(ax)
        return tuple(out)

    jax.eval_shape(f, jax.random.PRNGKey(0))
    return captured["axes"]


class LM:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    # -- init --------------------------------------------------------------
    def init(self, key):
        return init_model(self.cfg, key)[0]

    def abstract_params(self):
        """(ShapeDtypeStruct tree, axes tree) with no allocation."""
        captured = {}

        def f(key):
            p, a = init_model(self.cfg, key)
            captured["axes"] = a
            return p

        shapes = jax.eval_shape(f, jax.random.key(0))
        return shapes, captured["axes"]

    def param_count_actual(self) -> int:
        shapes, _ = self.abstract_params()
        import math
        return sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))

    # -- embedding / head ----------------------------------------------------
    def _embed_in(self, params, batch):
        cfg = self.cfg
        if cfg.audio_frontend:
            x = batch["frames"].astype(dtype_of(cfg))
        else:
            x = params["embed"][batch["tokens"]]
        return constrain(x, "batch", "seq", "embed")

    def _head(self, params, x):
        cfg = self.cfg
        h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        w = params["embed"].T if cfg.tie_embeddings else params["head"]
        logits = jnp.matmul(h, w, preferred_element_type=jnp.float32)
        return constrain(logits.astype(jnp.float32), "batch", "seq", "vocab")

    # -- training forward / loss --------------------------------------------
    def forward(self, params, batch, remat: bool = True):
        cfg = self.cfg
        x = self._embed_in(params, batch)
        img = batch.get("image_embeds")
        x, _, aux = tf.run_stack(cfg, params["blocks"], x, mode="train",
                                 image_embeds=img, remat=remat)
        return self._head(params, x), aux

    def loss(self, params, batch, remat: bool = True):
        """Sequence-chunked next-token CE + MoE aux loss.

        The f32 logits for (B,S,V) are never materialized: we scan over
        sequence chunks, rematerializing each chunk's logits in the backward
        pass.  This is the memory-dominant term for large-vocab archs.
        """
        cfg = self.cfg
        x = self._embed_in(params, batch)
        img = batch.get("image_embeds")
        x, _, aux = tf.run_stack(cfg, params["blocks"], x, mode="train",
                                 image_embeds=img, remat=remat)
        h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        w = params["embed"].T if cfg.tie_embeddings else params["head"]
        labels = batch["labels"]
        B, S = labels.shape

        chunk = min(LOSS_CHUNK, S)
        pad = (-S) % chunk
        if pad:
            h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
        nc = (S + pad) // chunk
        hc = h.reshape(B, nc, chunk, -1).transpose(1, 0, 2, 3)
        lc = labels.reshape(B, nc, chunk).transpose(1, 0, 2)

        @functools.partial(jax.checkpoint, prevent_cse=False)
        def chunk_loss(h_chunk, l_chunk):
            logits = jnp.matmul(h_chunk, w, preferred_element_type=jnp.float32)
            logits = logits.astype(jnp.float32)
            valid = l_chunk >= 0
            lse = jax.nn.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(
                logits, jnp.maximum(l_chunk, 0)[..., None], axis=-1)[..., 0]
            nll = jnp.where(valid, lse - tgt, 0.0)
            return nll.sum(), valid.sum()

        def body(carry, xs):
            tot, cnt = carry
            s, n = chunk_loss(*xs)
            return (tot + s, cnt + n), None

        (tot, cnt), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
            (hc, lc))
        ce = tot / jnp.maximum(cnt, 1).astype(jnp.float32)
        return ce + AUX_LOSS_WEIGHT * aux

    # -- serving -------------------------------------------------------------
    def prefill(self, params, batch, pad_to: Optional[int] = None,
                prompt_len=None, caches=None, fill_to=None):
        """Full-prompt forward building the decode cache.

        Returns (last_logits (B,V), caches).  Attention KV caches are padded
        to ``pad_to`` slots if given.

        ``caches`` switches to *extend* (continuation) prefill: the batch is
        a suffix appended at the supplied caches' fill level (the paged
        engine's preemption resume re-prefills only the generated tokens).
        ``fill_to`` then overrides the post-prefill fill level (base fill +
        suffix length rather than the suffix length alone).

        ``prompt_len`` (optional dynamic scalar) enables *bucketed* prefill:
        the token batch may be right-padded to a bucket length; logits are
        gathered at position ``prompt_len - 1`` and the attention fill level
        ``t`` is reset to ``prompt_len`` so decode overwrites the pad slots
        in order.  Because prefill attention is causal and pads sit at the
        end, positions < prompt_len never attend a pad slot, and decode masks
        slots > t — pad KV is dead until overwritten.  Mamba blocks take
        ``prompt_len`` too and keep the pads out of their recurrent state
        (models/ssm.py).  xLSTM recurrences and MoE capacity routing would
        still let pads in, so callers pass exact-length inputs there.

        ``prompt_len`` may also be a (B,) vector — mixed-length prompts
        sharing one padded batch (the micro-batching lane back-fill): each
        row's logits come from its own last position and the caches carry
        per-sequence fill levels (``t`` (repeats, B)), the layout the
        per-lane decode path consumes.
        """
        cfg = self.cfg
        x = self._embed_in(params, batch)
        img = batch.get("image_embeds")
        x, caches, _ = tf.run_stack(cfg, params["blocks"], x, mode="prefill",
                                    caches=caches, image_embeds=img,
                                    remat=False, prompt_len=prompt_len)
        if prompt_len is None:
            last = x[:, -1:, :]
        else:
            pl = jnp.asarray(prompt_len, jnp.int32)
            if pl.ndim == 0:
                last = jax.lax.dynamic_slice_in_dim(x, pl - 1, 1, axis=1)
            else:
                last = jnp.take_along_axis(x, (pl - 1)[:, None, None],
                                           axis=1)
            caches = _set_fill(cfg, caches, pl if fill_to is None else fill_to)
        logits = self._head(params, last)[:, 0]
        if pad_to is not None:
            caches = _pad_kv(cfg, caches, pad_to)
        return logits, caches

    def decode_step(self, params, caches, batch_step):
        """One decode step.

        batch_step: {"tokens": (B,1)} or {"frames": (B,1,D)}; cache slot/mask
        positions ride inside the attention caches ("t").
        Returns (logits (B,V), new_caches).
        """
        cfg = self.cfg
        x = self._embed_in(params, batch_step)
        img = batch_step.get("image_embeds")
        x, caches, _ = tf.run_stack(cfg, params["blocks"], x, mode="decode",
                                    caches=caches, image_embeds=img,
                                    remat=False)
        logits = self._head(params, x)[:, 0]
        return logits, caches

    def verify_step(self, params, caches, batch_step):
        """Speculative verification: score W consecutive positions in one
        dispatch.

        batch_step: {"tokens": (B, W)} — the pending token plus K = W-1
        draft tokens per sequence.  Each token is written into the KV cache
        at its absolute position (fill level ``t`` + offset) and attends
        its own causal prefix, so position ``w``'s logits are the logits
        serial decode would produce after consuming the first ``w + 1``
        tokens.  The cache fill level is *not* advanced — callers commit
        the accepted prefix by resetting ``t`` (models/attention.py
        mode="verify"), which is also how rejected drafts roll back.
        Returns (logits (B, W, V), caches).
        """
        cfg = self.cfg
        x = self._embed_in(params, batch_step)
        x, caches, _ = tf.run_stack(cfg, params["blocks"], x, mode="verify",
                                    caches=caches, remat=False)
        logits = self._head(params, x)
        return logits, caches

    # -- cache construction ---------------------------------------------------
    def init_cache(self, batch_size: int, max_len: int, t0: int = 0):
        """Zero caches (stacked over repeats) for decode-from-scratch or as
        dry-run input specs.  ``t0`` sets the current fill level.

        Attention caches are *ring buffers* of ``max_len`` slots: decode
        writes the step-``t`` KV at slot ``t % max_len`` and attends slots
        ``<= t`` (all of them once wrapped), so a request is never
        reallocated a larger cache when generation approaches the buffer
        end — capacity bounds the attention window, not the output length.
        ``t`` is the absolute fill level (RoPE positions stay absolute).
        With one fill level per layer (``t`` of shape (repeats,), the
        serial path), decode carries the stacked K/V through the layer
        scan, writes one row per layer in place and attends each layer's
        ring from the stack; per-lane levels (repeats, B) re-emit each
        layer's ring.
        """
        cfg = self.cfg
        dt = dtype_of(cfg)
        rep = cfg.pattern_repeats
        B, KV, hd = batch_size, cfg.num_kv_heads, cfg.head_dim
        caches = []
        for kind in cfg.block_pattern:
            if kind in (ATTN, ATTN_MOE):
                caches.append({
                    "k": jnp.zeros((rep, B, max_len, KV, hd), dt),
                    "v": jnp.zeros((rep, B, max_len, KV, hd), dt),
                    "t": jnp.full((rep,), t0, jnp.int32),
                })
            elif kind == XATTN:
                caches.append({
                    "k": jnp.zeros((rep, B, cfg.num_image_tokens, KV, hd), dt),
                    "v": jnp.zeros((rep, B, cfg.num_image_tokens, KV, hd), dt),
                })
            elif kind in (MAMBA, MAMBA_MOE):
                caches.append({
                    "conv": jnp.zeros((rep, B, cfg.ssm_conv_width - 1,
                                       cfg.d_inner), dt),
                    "ssm": jnp.zeros((rep, B, cfg.d_inner, cfg.ssm_state_dim),
                                     jnp.float32),
                })
            elif kind == MLSTM:
                H = cfg.num_heads
                caches.append({
                    "C": jnp.zeros((rep, B, H, hd, hd), jnp.float32),
                    "n": jnp.zeros((rep, B, H, hd), jnp.float32),
                    "m": jnp.full((rep, B, H), -1e30, jnp.float32),
                })
            elif kind == SLSTM:
                H = cfg.num_heads
                z = jnp.zeros((rep, B, H, hd), jnp.float32)
                caches.append({"c": z, "n": z, "h": z,
                               "m": jnp.full((rep, B, H, hd), -1e30,
                                             jnp.float32)})
            else:
                raise ValueError(kind)
        return tuple(caches)

    def init_paged_cache(self, batch_size: int, max_len: int,
                         n_pages: int, page_size: int):
        """Zero block-paged caches (serving/paging.py).

        Attention K/V live in a shared physical pool of ``n_pages`` pages
        (``page_size`` slots each, physical page 0 pinned as the trash
        page) instead of per-lane ring buffers; each lane addresses its
        logical window of ``max_len`` slots through a per-lane block
        table ``bt`` (zeros = unallocated, pointing at trash) and its own
        fill level ``t``.  Every layer shares the lane's table — a
        physical page index selects the same page in every layer's pool,
        so the allocator hands out layer-agnostic page ids.  Attention-
        only stacks: recurrent blocks have no paged analogue here.
        """
        cfg = self.cfg
        dt = dtype_of(cfg)
        rep = cfg.pattern_repeats
        KV, hd = cfg.num_kv_heads, cfg.head_dim
        if max_len % page_size:
            raise ValueError(f"max_len {max_len} not a multiple of "
                             f"page_size {page_size}")
        P = max_len // page_size
        caches = []
        for kind in cfg.block_pattern:
            if kind not in (ATTN, ATTN_MOE):
                raise ValueError(
                    f"block-paged KV needs a pure-attention stack, got {kind}")
            caches.append({
                "k": jnp.zeros((rep, n_pages, page_size, KV, hd), dt),
                "v": jnp.zeros((rep, n_pages, page_size, KV, hd), dt),
                "t": jnp.zeros((rep, batch_size), jnp.int32),
                "bt": jnp.zeros((rep, batch_size, P), jnp.int32),
            })
        return tuple(caches)

    def cache_axes(self):
        """Logical axes tree matching init_cache output."""
        cfg = self.cfg
        axes = []
        for kind in cfg.block_pattern:
            if kind in (ATTN, ATTN_MOE):
                axes.append({
                    "k": (None, "batch", "kv_seq", "kv_heads", "head_dim"),
                    "v": (None, "batch", "kv_seq", "kv_heads", "head_dim"),
                    "t": (None,),
                })
            elif kind == XATTN:
                axes.append({
                    "k": (None, "batch", "image_seq", "kv_heads", "head_dim"),
                    "v": (None, "batch", "image_seq", "kv_heads", "head_dim"),
                })
            elif kind in (MAMBA, MAMBA_MOE):
                axes.append({
                    "conv": (None, "batch", "conv", "ssm_inner"),
                    "ssm": (None, "batch", "ssm_inner", "ssm_state"),
                })
            elif kind == MLSTM:
                axes.append({
                    "C": (None, "batch", "heads", "head_dim", "head_dim"),
                    "n": (None, "batch", "heads", "head_dim"),
                    "m": (None, "batch", "heads"),
                })
            elif kind == SLSTM:
                a = (None, "batch", "heads", "head_dim")
                axes.append({"c": a, "n": a, "h": a, "m": a})
        return tuple(axes)


def _set_fill(cfg, caches, t):
    """Reset every attention cache's fill level to ``t``: a dynamic scalar
    (shared across the batch, the serial path) or a (B,) vector (per-
    sequence levels — the cache ``t`` becomes (repeats, B), the layout
    the per-lane decode path consumes)."""
    out = []
    for kind, c in zip(cfg.block_pattern, caches):
        if kind in (ATTN, ATTN_MOE):
            c = dict(c)
            if jnp.ndim(t) == 0:
                c["t"] = jnp.full_like(c["t"], t)
            else:
                c["t"] = jnp.broadcast_to(t[None, :],
                                          c["t"].shape + t.shape)
        out.append(c)
    return tuple(out)


def _pad_kv(cfg, caches, pad_to: int):
    out = []
    for kind, c in zip(cfg.block_pattern, caches):
        if kind in (ATTN, ATTN_MOE) and c["k"].shape[2] < pad_to:
            extra = pad_to - c["k"].shape[2]
            c = dict(c)
            c["k"] = jnp.pad(c["k"], ((0, 0), (0, 0), (0, extra), (0, 0), (0, 0)))
            c["v"] = jnp.pad(c["v"], ((0, 0), (0, 0), (0, extra), (0, 0), (0, 0)))
        out.append(c)
    return tuple(out)
