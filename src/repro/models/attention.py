"""Attention: GQA/MQA self-attention (train/prefill/decode) and cross-attention.

Training/prefill attention is a chunked streaming-softmax ("flash") pure-JAX
implementation: memory is O(q_chunk * kv_chunk) per step instead of O(S^2),
which is what lets the 32k-prefill and 4k-train cells fit — XLA does not do
this fusion for you.  The Pallas kernels in repro/kernels mirror this
computation for real-TPU deployment and are validated against it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.layers import dense, dtype_of, init_dense, rmsnorm, rope
from repro.sharding import constrain

NEG_INF = -1e30

# §Perf flags (launch/perf experiments flip these; defaults = baseline).
# DECODE_CAST_F32: cast the whole KV cache to f32 before the decode einsums
# (baseline) vs native-dtype einsums with f32 accumulation only.
PERF = {"decode_cast_f32": True}


def init_attention(cfg, key, cross: bool = False):
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    params = {
        "norm": jnp.ones((d,), dtype=dt),
        "wq": init_dense(ks[0], d, cfg.attn_dim, dt),
        "wk": init_dense(ks[1], d, cfg.kv_dim, dt),
        "wv": init_dense(ks[2], d, cfg.kv_dim, dt),
        "wo": init_dense(ks[3], cfg.attn_dim, d, dt, scale=cfg.attn_dim ** -0.5),
    }
    axes = {
        "norm": ("embed",),
        "wq": ("embed_w", "qkv"),
        "wk": ("embed_w", "qkv"),
        "wv": ("embed_w", "qkv"),
        "wo": ("qkv", "embed_w"),
    }
    if cfg.qk_norm:
        params["q_norm"] = jnp.ones((cfg.head_dim,), dtype=dt)
        params["k_norm"] = jnp.ones((cfg.head_dim,), dtype=dt)
        axes["q_norm"] = ("head_dim",)
        axes["k_norm"] = ("head_dim",)
    return params, axes


def _project_qkv(cfg, p, x, positions):
    """x (B,S,D) -> q (B,S,H,hd), k/v (B,S,KV,hd); rotary positions unless
    the config turns them off (``cfg.use_rope``)."""
    B, S, _ = x.shape
    q = dense(x, p["wq"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = dense(x, p["wk"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = dense(x, p["wv"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _group(q, num_kv):
    """(B,S,H,hd) -> (B,S,KV,G,hd) grouping query heads over KV heads."""
    B, S, H, hd = q.shape
    assert H % num_kv == 0, (H, num_kv)
    return q.reshape(B, S, num_kv, H // num_kv, hd)


def flash_attention(q, k, v, *, causal: bool, q_offset: int = 0,
                    kv_offset: int = 0, q_chunk: int = 512, kv_chunk: int = 1024,
                    kv_len=None):
    """Chunked streaming-softmax attention.

    q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd); GQA via head grouping.
    ``kv_len``: optional scalar — keys at absolute positions >= kv_len are
    masked out (decode with a partially filled cache).
    Returns (B, Sq, H, hd).
    """
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = hd ** -0.5

    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    nq = -(-Sq // q_chunk)
    nkv = -(-Skv // kv_chunk)
    # pad to chunk multiples
    q_pad, kv_pad = nq * q_chunk - Sq, nkv * kv_chunk - Skv
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0)))
    if kv_pad:
        k = jnp.pad(k, ((0, 0), (0, kv_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, kv_pad), (0, 0), (0, 0)))

    # scan axes lead: (nq, B, q_chunk, KV, G, hd) / (nkv, B, kv_chunk, KV, hd)
    # The chunk-index dim must stay UNSHARDED: left to propagation, GSPMD
    # shards it across devices and then "involuntarily fully rematerializes"
    # (replicates) every dynamic-slice in the scan.
    qg = _group(q, KV).reshape(B, nq, q_chunk, KV, G, hd) \
        .transpose(1, 0, 2, 3, 4, 5).astype(jnp.float32)
    qg = constrain(qg, None, "batch", None, None, None, None)
    kg = k.reshape(B, nkv, kv_chunk, KV, hd) \
        .transpose(1, 0, 2, 3, 4).astype(jnp.float32)
    kg = constrain(kg, None, "batch", None, None, None)
    vg = v.reshape(B, nkv, kv_chunk, KV, hd) \
        .transpose(1, 0, 2, 3, 4).astype(jnp.float32)
    vg = constrain(vg, None, "batch", None, None, None)

    limit = Skv if kv_len is None else kv_len

    # Nested remat: without it, the backward pass keeps every (q, kv) chunk's
    # probability block alive simultaneously (~16 GB/device at train_4k).
    # Checkpointing both scan bodies stores only the O(block) carries and
    # recomputes the probabilities in the backward sweep — the flash-attention
    # backward recurrence, expressed through jax.checkpoint.
    @functools.partial(jax.checkpoint, prevent_cse=False)
    def q_step(_, qi):
        qc, q_idx = qi  # qc: (B, qck, KV, G, hd)
        q_pos = q_offset + q_idx * q_chunk + jnp.arange(q_chunk)

        @functools.partial(jax.checkpoint, prevent_cse=False)
        def kv_step(carry, ki):
            m, l, acc = carry
            kc, vc, k_idx = ki
            k_pos = kv_offset + k_idx * kv_chunk + jnp.arange(kv_chunk)
            # logits: (B, KV, G, qck, kck)
            logits = jnp.einsum("bqkgh,bskh->bkgqs", qc, kc) * scale
            mask = k_pos[None, :] < limit
            if causal:
                mask = mask & (q_pos[:, None] >= k_pos[None, :])
            logits = jnp.where(mask[None, None, None], logits, NEG_INF)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p, vc)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (kg, vg, jnp.arange(nkv)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B,KV,G,qck,hd)
        return None, out

    _, outs = jax.lax.scan(q_step, None, (qg, jnp.arange(nq)))
    # outs: (nq, B, KV, G, qck, hd) -> (B, nq*qck, KV*G, hd)
    outs = constrain(outs, None, "batch", None, None, None, None)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, nq * q_chunk, H, hd)
    return out[:, :Sq].astype(q.dtype)


def decode_attention(q, cache_k, cache_v, t):
    """Single-position attention over a (ring-buffer) KV cache.

    q: (B, 1, H, hd); cache_k/v: (B, S, KV, hd); t: absolute fill level —
    a scalar shared by the batch (the serial path) or a (B,) vector of
    per-sequence levels (the micro-batching decode lanes, which prefill
    at different prompt lengths).  Slots <= t are attended (the current
    token's KV has been written at slot t % S).  While t < S the mask is
    the usual prefix mask; once the ring wraps (t >= S) every slot holds
    one of the S most recent tokens and ``arange(S) <= t`` is all-true,
    so the same predicate serves both regimes — no separate "wrapped"
    code path.

    With PERF["decode_cast_f32"]=False, the cache is consumed in its native
    dtype with f32 accumulation inside the einsum — the f32 cache copies
    (2x cache bytes per layer per token) disappear from the HBM stream.
    """
    B, _, H, hd = q.shape
    S, KV = cache_k.shape[1], cache_k.shape[2]
    G = H // KV
    if PERF["decode_cast_f32"]:
        qg = q.reshape(B, KV, G, hd).astype(jnp.float32)
        k_in, v_in = cache_k.astype(jnp.float32), cache_v.astype(jnp.float32)
    else:
        qg = q.reshape(B, KV, G, hd)
        k_in, v_in = cache_k, cache_v
    logits = jnp.einsum("bkgh,bskh->bkgs", qg, k_in,
                        preferred_element_type=jnp.float32) * hd ** -0.5
    t_b = t if jnp.ndim(t) == 0 else t[:, None, None, None]
    mask = jnp.arange(S)[None, None, None, :] <= t_b
    logits = jnp.where(mask, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", w.astype(v_in.dtype), v_in,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def verify_attention(q, cache_k, cache_v, t):
    """W-position attention over a (ring-buffer) KV cache — the speculative
    verification forward.

    q: (B, W, H, hd); cache_k/v: (B, S, KV, hd); t: the pre-verify fill
    level — a scalar shared by the batch or a (B,) vector of per-lane
    levels.  Query ``w`` attends slots ``<= t + w``: exactly the mask
    ``decode_attention`` applies at fill level ``t + w``, with the same
    einsum contraction layout, PERF cast handling and softmax, so row
    ``w`` of the verify output is a bitwise candidate for the serial
    decode output at that position (tests/test_speculative.py holds the
    equality end to end).
    """
    B, W, H, hd = q.shape
    S, KV = cache_k.shape[1], cache_k.shape[2]
    G = H // KV
    if PERF["decode_cast_f32"]:
        qg = q.reshape(B, W, KV, G, hd).astype(jnp.float32)
        k_in, v_in = cache_k.astype(jnp.float32), cache_v.astype(jnp.float32)
    else:
        qg = q.reshape(B, W, KV, G, hd)
        k_in, v_in = cache_k, cache_v
    qg = qg.transpose(0, 2, 3, 1, 4)                      # (B, KV, G, W, hd)
    logits = jnp.einsum("bkgwh,bskh->bkgws", qg, k_in,
                        preferred_element_type=jnp.float32) * hd ** -0.5
    w_idx = jnp.arange(W, dtype=jnp.int32)
    if jnp.ndim(t) == 0:
        limit = (t + w_idx)[None, :, None]                # (1, W, 1)
    else:
        limit = (t[:, None] + w_idx[None, :])[:, :, None]  # (B, W, 1)
    mask = (jnp.arange(S)[None, None, :] <= limit)[:, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgws,bskh->bkgwh", w.astype(v_in.dtype), v_in,
                     preferred_element_type=jnp.float32)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, W, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Self-attention block (pre-norm, residual)
# ---------------------------------------------------------------------------

def attn_block(cfg, p, x, *, mode: str, pos_offset, cache=None):
    """Returns (x_out, new_cache).

    mode "train": full causal attention, no cache returned.
    mode "prefill": causal attention; returns {"k","v","t"} cache.  With a
    cache supplied (extend/continuation prefill, the paged engine's
    preemption resume), x is the *suffix*: new KV is written into the
    existing buffer at its fill level ``t`` and the suffix attends the
    cached prefix plus itself — row-for-row bitwise identical to a full
    re-prefill of prefix+suffix at the same buffer extent, because each
    query row's online-softmax accumulation is independent of the other
    rows and fully-masked kv chunks contribute exact zeros.
    mode "decode": x is (B,1,D); the cache is a ring buffer of S slots —
    the new KV is written at slot ``t % S`` (t = absolute fill level, RoPE
    stays absolute) so generation past the cache capacity wraps onto the
    oldest slots instead of forcing a larger allocation; while t < S this
    is exactly the old append-at-t behavior.  ``t`` is a (B,) vector of
    per-sequence fill levels (decode lanes): each sequence then gets its
    own RoPE position, ring slot and attention window, so one natively
    batched step serves lanes that prefilled at different prompt lengths.
    Or ``t`` is a scalar shared by the batch (the serial path), in
    run_stack's carry form, marked by a "layer" index: "k"/"v" are the
    whole layer stack (repeats, B, S, KV, hd), the new row is written
    into it in place at (layer, 0, t % S, 0, 0), and the layer's ring is
    attended straight out of the stack; the returned cache keeps that
    form.  A cache carrying a block table ("bt") is block-paged
    (serving/paging.py): "k"/"v" are shared physical pools
    (n_pages, page, KV, hd) and each lane reads/writes its logical window
    through its table row; unallocated slots point at the pinned trash
    page 0 and dead lanes past the window write there.
    """
    B = x.shape[0]
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    if mode in ("train", "prefill"):
        S = x.shape[1]
        if mode == "prefill" and cache is not None:
            # extend: append S suffix tokens at the buffer's fill level
            plen = cache["t"]          # scalar fill level, traced
            positions = plen + jnp.arange(S)
            q, k, v = _project_qkv(cfg, p, h, positions)
            kbuf = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), plen, axis=1)
            vbuf = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), plen, axis=1)
            out = flash_attention(q, kbuf, vbuf, causal=True, q_offset=plen)
            new_cache = {"k": kbuf, "v": vbuf, "t": plen + S}
            out = constrain(out, "batch", "seq", "heads", "head_dim")
            out = out.reshape(B, -1, cfg.attn_dim)
            return x + dense(out, p["wo"]), new_cache
        positions = jnp.arange(S)
        q, k, v = _project_qkv(cfg, p, h, positions)
        q = constrain(q, "batch", "seq", "heads", "head_dim")
        out = flash_attention(q, k, v, causal=True)
        new_cache = None
        if mode == "prefill":
            new_cache = {"k": k, "v": v, "t": jnp.asarray(S, jnp.int32)}
    elif mode == "verify":
        # Speculative verification: x is (B, W, D) — the pending token plus
        # K draft tokens.  Token w lands at absolute position t + w; all W
        # KVs are written up front and each query masks its own prefix
        # (slot <= t + w), so chain token w attends the draft tokens before
        # it through their just-written target KV — the same values serial
        # decode would have produced and written at those slots.  The fill
        # level is NOT advanced here: the caller commits the accepted
        # length by resetting "t" afterwards (rejected-draft rollback =
        # don't advance; stale KV past the new fill level stays masked and
        # is overwritten in order by later decode/verify writes, so
        # rollback costs no recompilation and no cleanup pass).
        t = cache["t"]
        W = x.shape[1]
        per_seq = jnp.ndim(t) != 0
        w_idx = jnp.arange(W, dtype=jnp.int32)
        positions = (t[:, None] + w_idx[None, :]) if per_seq else t + w_idx
        q, k, v = _project_qkv(cfg, p, h, positions)
        pos = positions if per_seq else jnp.broadcast_to(
            positions[None, :], (B, W))
        if "bt" in cache:                      # block-paged pool
            bt = cache["bt"]                   # (B, P)
            pool_k, pool_v = cache["k"], cache["v"]
            n_pages, page = pool_k.shape[0], pool_k.shape[1]
            P = bt.shape[1]
            max_len = P * page
            page_slot = jnp.minimum(pos // jnp.int32(page), jnp.int32(P - 1))
            pg = jnp.take_along_axis(bt, page_slot, axis=1)
            pg = jnp.where(pos < max_len, pg, jnp.int32(0))
            gs = pg * page + jax.lax.rem(pos, jnp.int32(page))
            KV, hd = pool_k.shape[2], pool_k.shape[3]
            flat_k = pool_k.reshape(n_pages * page, KV, hd)
            flat_v = pool_v.reshape(n_pages * page, KV, hd)
            # duplicate indices only ever hit the trash page (live slots
            # are privately owned), where write order is irrelevant
            flat_k = flat_k.at[gs.reshape(-1)].set(
                k.astype(flat_k.dtype).reshape(B * W, KV, hd))
            flat_v = flat_v.at[gs.reshape(-1)].set(
                v.astype(flat_v.dtype).reshape(B * W, KV, hd))
            ck_pool = flat_k.reshape(n_pages, page, KV, hd)
            cv_pool = flat_v.reshape(n_pages, page, KV, hd)
            k_log = ck_pool[bt].reshape(B, max_len, KV, hd)
            v_log = cv_pool[bt].reshape(B, max_len, KV, hd)
            out = verify_attention(q, k_log, v_log, t)
            new_cache = {"k": ck_pool, "v": cv_pool, "t": t, "bt": bt}
        else:                                  # ring buffer
            S = cache["k"].shape[1]
            # out-of-range positions (a stopped or near-capacity lane's
            # verify window past the buffer) are dropped rather than
            # wrapped: unlike decode, a wrapped verify write could clobber
            # a live early slot before its own masked read.
            gs = jnp.where(
                pos < S,
                jnp.arange(B, dtype=jnp.int32)[:, None] * S + pos,
                jnp.int32(B * S))
            KV, hd = cache["k"].shape[2], cache["k"].shape[3]
            flat_k = cache["k"].reshape(B * S, KV, hd)
            flat_v = cache["v"].reshape(B * S, KV, hd)
            flat_k = flat_k.at[gs.reshape(-1)].set(
                k.astype(flat_k.dtype).reshape(B * W, KV, hd), mode="drop")
            flat_v = flat_v.at[gs.reshape(-1)].set(
                v.astype(flat_v.dtype).reshape(B * W, KV, hd), mode="drop")
            ck = flat_k.reshape(B, S, KV, hd)
            cv = flat_v.reshape(B, S, KV, hd)
            ck = constrain(ck, "batch", "kv_seq", "kv_heads", "head_dim")
            cv = constrain(cv, "batch", "kv_seq", "kv_heads", "head_dim")
            out = verify_attention(q, ck, cv, t)
            new_cache = {"k": ck, "v": cv, "t": t}
        out = constrain(out, "batch", "seq", "heads", "head_dim")
        out = out.reshape(B, -1, cfg.attn_dim)
        return x + dense(out, p["wo"]), new_cache
    elif cache is not None and "bt" in cache:  # block-paged decode
        t = cache["t"]                         # (B,) per-lane fill levels
        bt = cache["bt"]                       # (B, P) int32 page per block
        pool_k, pool_v = cache["k"], cache["v"]    # (Np, page, KV, hd)
        n_pages, page = pool_k.shape[0], pool_k.shape[1]
        P = bt.shape[1]
        max_len = P * page
        positions = t[:, None]
        q, k, v = _project_qkv(cfg, p, h, positions)
        # write: lane b's step-t KV lands in physical page bt[b, t//page]
        # at in-page slot t%page.  Lanes past their window (stopped lanes
        # whose t keeps advancing until segment end) are routed to the
        # pinned trash page so they can never clobber a live or shared
        # page; live lanes never collide (decode always writes a
        # privately owned page — registration stops short of the write
        # frontier), so the batched scatter is deterministic where it
        # matters.
        page_slot = jnp.minimum(t // jnp.int32(page), jnp.int32(P - 1))
        pg = jnp.take_along_axis(bt, page_slot[:, None], axis=1)[:, 0]
        pg = jnp.where(t < max_len, pg, jnp.int32(0))
        gs = pg * page + jax.lax.rem(t, jnp.int32(page))
        KV, hd = pool_k.shape[2], pool_k.shape[3]
        flat_k = pool_k.reshape(n_pages * page, KV, hd)
        flat_v = pool_v.reshape(n_pages * page, KV, hd)
        flat_k = flat_k.at[gs].set(k.astype(flat_k.dtype)[:, 0])
        flat_v = flat_v.at[gs].set(v.astype(flat_v.dtype)[:, 0])
        new_pool_k = flat_k.reshape(n_pages, page, KV, hd)
        new_pool_v = flat_v.reshape(n_pages, page, KV, hd)
        # read: gather each lane's logical window through its table, then
        # the exact same masked attention as the ring path — bitwise
        # equal because every logical slot holds the same value either
        # way and the shapes/einsums are identical.
        k_log = new_pool_k[bt].reshape(B, max_len, KV, hd)
        v_log = new_pool_v[bt].reshape(B, max_len, KV, hd)
        out = decode_attention(q, k_log, v_log, t)
        new_cache = {"k": new_pool_k, "v": new_pool_v, "t": t + 1, "bt": bt}
        out = constrain(out, "batch", "seq", "heads", "head_dim")
        out = out.reshape(B, -1, cfg.attn_dim)
        return x + dense(out, p["wo"]), new_cache
    else:  # decode
        t = cache["t"]  # absolute fill level(s); () shared or (B,) per-seq
        S = cache["k"].shape[-3]
        per_seq = jnp.ndim(t) != 0
        positions = t[:, None] if per_seq else jnp.full((1,), t, jnp.int32)
        q, k, v = _project_qkv(cfg, p, h, positions)
        k, v = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
        slot = jax.lax.rem(t, jnp.int32(S))
        if "layer" in cache:
            # run_stack's carry form: "k"/"v" are the whole layer stack
            # (repeats, B, S, KV, hd).  Write the new row into it in place,
            # then attend this layer's ring read straight out of the
            # updated stack: XLA fuses that read into the attention dots,
            # so no layer's ring is copied out or written back.
            layer = cache["layer"]
            at = (layer, 0, slot, 0, 0)
            k_all = jax.lax.dynamic_update_slice(cache["k"], k[None], at)
            v_all = jax.lax.dynamic_update_slice(cache["v"], v[None], at)
            ck = jax.lax.dynamic_index_in_dim(k_all, layer, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(v_all, layer, keepdims=False)
        else:
            # per-sequence ring write as a one-hot select: XLA CPU lowers
            # batched scatters to a slow generic loop, but this select
            # vectorizes (it streams the cache once, which decode does
            # anyway for the attention reads)
            hit = (jnp.arange(S)[None, :] == slot[:, None])[..., None, None]
            ck = jnp.where(hit, k[:, :1], cache["k"])
            cv = jnp.where(hit, v[:, :1], cache["v"])
        ck = constrain(ck, "batch", "kv_seq", "kv_heads", "head_dim")
        cv = constrain(cv, "batch", "kv_seq", "kv_heads", "head_dim")
        out = decode_attention(q, ck, cv, t)
        if "layer" in cache:
            new_cache = {"k": k_all, "v": v_all, "t": t + 1, "layer": layer}
        else:
            new_cache = {"k": ck, "v": cv, "t": t + 1}
    out = constrain(out, "batch", "seq", "heads", "head_dim")
    out = out.reshape(B, -1, cfg.attn_dim)
    return x + dense(out, p["wo"]), new_cache


# ---------------------------------------------------------------------------
# Cross-attention block (VLM): queries from text, KV from image embeddings
# ---------------------------------------------------------------------------

def xattn_block(cfg, p, x, *, mode: str, image_embeds=None, cache=None):
    """image_embeds: (B, T_img, D).  Cache holds projected image KV."""
    B = x.shape[0]
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    q = dense(h, p["wq"]).reshape(B, -1, cfg.num_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
    if cache is not None and "k" in cache and mode == "decode":
        k, v = cache["k"], cache["v"]
        new_cache = cache
    else:
        assert image_embeds is not None, "xattn needs image embeddings"
        k = dense(image_embeds, p["wk"]).reshape(B, -1, cfg.num_kv_heads, cfg.head_dim)
        v = dense(image_embeds, p["wv"]).reshape(B, -1, cfg.num_kv_heads, cfg.head_dim)
        if cfg.qk_norm:
            k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
        new_cache = {"k": k, "v": v} if mode in ("prefill", "decode") else None
    out = flash_attention(q, k, v, causal=False)
    out = out.reshape(B, -1, cfg.attn_dim)
    return x + dense(out, p["wo"]), new_cache
