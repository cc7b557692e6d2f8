"""Pallas TPU kernel: single-token GQA decode attention over a KV cache.

Decode is memory-bound: the whole KV cache streams HBM->VMEM once per token.
The kernel tiles the cache sequence axis; each (batch, head) program streams
KV blocks through VMEM carrying the online-softmax state, masking slots
beyond the current fill level ``t``.  ``t`` is the *absolute* fill level of
the ring-buffer cache (models/attention.py writes step t at slot ``t % S``):
while t < S the predicate ``slot <= t`` masks the unwritten suffix, and once
the ring wraps it is all-true — every slot then holds one of the S most
recent tokens, so the same kernel serves both regimes.  All G query heads of a KV group share
the same K/V block fetch (q is laid out (B, KV, G, hd) so the group rides in
one block) — on real hardware this is the G-fold HBM-bandwidth saving that
makes GQA decode fast; the grid never re-reads a KV block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(t_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, block_kv: int, scale: float):
    ikv = pl.program_id(2)
    nkv = pl.num_programs(2)

    @pl.when(ikv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)            # (bkv, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    t = t_ref[0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = ikv * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos <= t, s, NEG_INF)            # (G, bkv)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(axis=1)
    acc_new = acc_prev * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(ikv == nkv - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def _paged_decode_kernel(bt_ref, t_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, page: int, scale: float):
    b = pl.program_id(0)
    ip = pl.program_id(2)
    np_ = pl.num_programs(2)

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)            # (page, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    t = t_ref[b]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = ip * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos <= t, s, NEG_INF)            # (G, page)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(axis=1)
    acc_new = acc_prev * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(ip == np_ - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def paged_decode_attention_kernel(q, k_pages, v_pages, block_table, t, *,
                                  interpret: bool = False):
    """Block-paged variant: K/V live in a shared physical page pool and
    each sequence reads its logical window through a block table.

    q: (B, KV, G, hd) one query token, grouped; k_pages, v_pages:
    (n_pages, KV, page, hd) physical pool; block_table: (B, P) int32
    physical page backing logical block p of sequence b; t: (B,) int32
    per-sequence fill levels (logical slots <= t[b] attend).  Returns
    (B, KV, G, hd).

    The block table and fill levels ride as scalar-prefetch operands
    (``PrefetchScalarGridSpec``): the index map dereferences
    ``bt[b, ip]`` to pick which physical page the (b, head, ip) program
    streams, so the gather happens in the DMA schedule — the kernel body
    is the same online-softmax loop as the dense ring kernel, with the
    grid's page axis standing in for the kv-block axis.  Unallocated
    table slots point at the pinned trash page (0); they sit beyond the
    fill level so the mask discards whatever garbage they hold.
    """
    B, KV, G, hd = q.shape
    n_pages, _, page, _ = k_pages.shape
    P = block_table.shape[1]
    grid = (B, KV, P)
    bt = jnp.asarray(block_table, jnp.int32)
    t_arr = jnp.asarray(t, jnp.int32).reshape(B)

    kernel = functools.partial(_paged_decode_kernel, page=page,
                               scale=hd ** -0.5)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, hd),
                         lambda b, h, ip, bt_ref, t_ref: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page, hd),
                         lambda b, h, ip, bt_ref, t_ref:
                         (bt_ref[b, ip], h, 0, 0)),
            pl.BlockSpec((1, 1, page, hd),
                         lambda b, h, ip, bt_ref, t_ref:
                         (bt_ref[b, ip], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, ip, bt_ref, t_ref: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(bt, t_arr, q, k_pages, v_pages)


def decode_attention_kernel(q, k, v, t, *, block_kv: int = 256,
                            interpret: bool = False):
    """q: (B, KV, G, hd) one query token, grouped; k, v: (B, KV, S, hd);
    t: scalar int32 absolute fill level (slots <= t attend; all slots once
    the ring has wrapped, t >= S).  Returns (B, KV, G, hd).
    """
    B, KV, G, hd = q.shape
    S = k.shape[2]
    block_kv = min(block_kv, S)
    assert S % block_kv == 0, (S, block_kv)
    nkv = S // block_kv
    grid = (B, KV, nkv)
    t_arr = jnp.asarray(t, jnp.int32).reshape(1)

    kernel = functools.partial(_decode_kernel, block_kv=block_kv,
                               scale=hd ** -0.5)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, ikv: (0,)),
            pl.BlockSpec((1, 1, G, hd), lambda b, h, ikv: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, ikv: (b, h, ikv, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, ikv: (b, h, ikv, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, ikv: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        interpret=interpret,
    )(t_arr, q, k, v)
