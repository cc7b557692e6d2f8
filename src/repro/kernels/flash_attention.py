"""Pallas TPU kernel: causal flash attention (prefill / training path).

Tiling: grid (batch, q_heads, q_blocks, kv_blocks) with the KV axis
innermost; a VMEM scratch accumulator carries the streaming-softmax state
(m, l, acc) across KV blocks, so HBM traffic is one pass over Q/K/V and one
write of O — the flash-attention recurrence mapped onto the MXU with
(block_q x head_dim) x (head_dim x block_kv) matmuls.

GQA is native: the K/V BlockSpec index-maps query head h to KV head
h // (H // KV), so no KV replication is materialised.

Block sizes default to 128 (MXU-aligned); head_dim rides whole (128/256 for
the assigned archs — both VMEM-friendly: 3 tiles x 128 x 256 x 4B < 0.5 MB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal: bool, block_q: int, block_kv: int, scale: float):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)
    nkv = pl.num_programs(3)

    @pl.when(ikv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)          # (bkv, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        k_pos = ikv * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
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


def flash_attention_kernel(q, k, v, *, causal: bool = True,
                           block_q: int = 128, block_kv: int = 128,
                           interpret: bool = False):
    """q: (B, H, S, hd); k, v: (B, KV, S, hd) -> (B, H, S, hd)."""
    B, H, S, hd = q.shape
    KV = k.shape[1]
    assert H % KV == 0, (H, KV)
    G = H // KV
    block_q = min(block_q, S)
    block_kv = min(block_kv, S)
    assert S % block_q == 0 and S % block_kv == 0, (S, block_q, block_kv)
    nq, nkv = S // block_q, S // block_kv
    grid = (B, H, nq, nkv)

    kernel = functools.partial(
        _flash_kernel, causal=causal, block_q=block_q, block_kv=block_kv,
        scale=hd ** -0.5)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, iq, ikv: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda b, h, iq, ikv, G=G: (b, h // G, ikv, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda b, h, iq, ikv, G=G: (b, h // G, ikv, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, iq, ikv: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
