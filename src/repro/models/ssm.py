"""Mamba-1 selective SSM block (Jamba's sequence mixer), as published.

Per token, with ``u`` the mixer's input branch (Hugging Face
``JambaMambaMixer``):

* ``[u, z] = in_proj(x)``; ``u = silu(causal_depthwise_conv(u) + conv_b)``;
* ``[dt, B, C] = x_proj(u)`` (time-step rank, state size, state size),
  each through its own RMSNorm;
* ``delta = softplus(dt_proj(dt) + dt_bias)`` per inner channel;
  ``A = -exp(A_log)`` of shape (d_inner, N);
* ``s_t = exp(delta_t A) * s_{t-1} + delta_t B_t u_t``;
  ``y_t = s_t . C_t + D u_t``; the block's output is
  ``out_proj(y * silu(z))``.

TPU adaptation: the CUDA selective-scan kernel is replaced by a
chunked-parallel scan — ``lax.scan`` over sequence chunks (recurrent carry =
SSM state) with ``lax.associative_scan`` inside each chunk.  This keeps the
working set at O(batch * chunk * d_inner * N) (VMEM-friendly) and the
sequential depth at S/chunk, instead of either a full O(S) recurrence (serial,
hostile to the MXU) or a full-sequence associative scan (O(S * d_inner * N)
live memory).  Decode (one token) is the same scan at S = 1.  The state,
the time steps and the scan are float32.

Bucketed prefill: with ``prompt_len`` given, positions at or past it are
right-padding.  There ``delta`` is 0, so ``exp(0 * A) = 1`` and the input
term vanishes: the state passes through the pads unchanged, and the conv
state is taken from the last ``d_conv - 1`` real inputs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.layers import dense, dtype_of, init_dense, rmsnorm
from repro.sharding import constrain

CHUNK = 128
DT_MIN, DT_MAX = 1e-3, 1e-1     # Mamba's range of initial time steps


def init_mamba(cfg, key):
    dt_ = dtype_of(cfg)
    ks = jax.random.split(key, 6)
    d, di, n, r = cfg.d_model, cfg.d_inner, cfg.ssm_state_dim, cfg.dt_rank
    w = cfg.ssm_conv_width
    # Mamba's time-step init: dt log-uniform in [DT_MIN, DT_MAX], and the
    # bias its inverse softplus, so softplus(bias) starts at dt
    lo, hi = jnp.log(DT_MIN), jnp.log(DT_MAX)
    dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(ks[4], (di,)))
    params = {
        "norm": jnp.ones((d,), dtype=dt_),
        "in_proj": init_dense(ks[0], d, 2 * di, dt_),           # u and z
        "conv_w": (jax.random.normal(ks[1], (w, di)) * w ** -0.5).astype(dt_),
        "conv_b": jnp.zeros((di,), dt_),
        "x_proj": init_dense(ks[2], di, r + 2 * n, dt_),        # dt, B, C
        "dt_norm": jnp.ones((r,), dt_),
        "B_norm": jnp.ones((n,), dt_),
        "C_norm": jnp.ones((n,), dt_),
        "dt_proj": init_dense(ks[3], r, di, dt_),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
        * jnp.ones((di, 1), jnp.float32),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": init_dense(ks[5], di, d, dt_, scale=di ** -0.5),
    }
    axes = {
        "norm": ("embed",),
        "in_proj": ("embed_w", "ssm_inner"),
        "conv_w": ("conv", "ssm_inner"),
        "conv_b": ("ssm_inner",),
        "x_proj": ("ssm_inner", None),
        "dt_norm": (None,),
        "B_norm": (None,),
        "C_norm": (None,),
        "dt_proj": (None, "ssm_inner"),
        "dt_bias": ("ssm_inner",),
        "A_log": ("ssm_inner", "ssm_state"),
        "D": ("ssm_inner",),
        "out_proj": ("ssm_inner", "embed_w"),
    }
    return params, axes


def _selective_inputs(cfg, p, u):
    """u (B,S,di) -> (delta (B,S,di), B (B,S,N), C (B,S,N)), float32."""
    r, n = cfg.dt_rank, cfg.ssm_state_dim
    proj = jnp.matmul(u, p["x_proj"], preferred_element_type=jnp.float32)
    dt = rmsnorm(proj[..., :r], p["dt_norm"], cfg.norm_eps)
    bm = rmsnorm(proj[..., r:r + n], p["B_norm"], cfg.norm_eps)
    cm = rmsnorm(proj[..., r + n:], p["C_norm"], cfg.norm_eps)
    w = p["dt_proj"]
    dt = jnp.matmul(dt.astype(w.dtype), w, preferred_element_type=jnp.float32)
    return jax.nn.softplus(dt + p["dt_bias"]), bm, cm


def _chunk_scan(dA, dBx, h0):
    """Associative scan within a chunk.  dA,dBx: (B,Ck,di,N); h0: (B,di,N).

    h_t = dA_t * h_{t-1} + dBx_t.  Returns (h_all (B,Ck,di,N), h_last).
    """
    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a2 * a1, a2 * b1 + b2

    a, b = jax.lax.associative_scan(combine, (dA, dBx), axis=1)
    h_all = a * h0[:, None] + b
    return h_all, h_all[:, -1]


def _scan(p, u, delta, bm, cm, h0):
    """The recurrence over a whole sequence.  u, delta: (B,S,di); bm, cm:
    (B,S,N); h0: (B,di,N).  Returns (y (B,S,di) f32, h_last).

    The (B, S, di, N) discretised tensors must never exist for the whole
    sequence — at jamba's train_4k cell that is ~0.5 PB.  They are built
    chunk-by-chunk inside the scan and die with the chunk.
    """
    B, S, di = u.shape
    n = bm.shape[-1]
    chunk = min(CHUNK, S)
    npad = (-S) % chunk
    nchunks = (S + npad) // chunk

    def chunked(x):
        # pads past S carry delta 0: the state passes through them
        x = jnp.pad(x, ((0, 0), (0, npad), (0, 0))) if npad else x
        return x.reshape(B, nchunks, chunk, x.shape[-1]).transpose(1, 0, 2, 3)

    # keep the chunk-index dim unsharded (see models/attention.py note)
    u_c = constrain(chunked(u), None, "batch", None, "ssm_inner")
    d_c = constrain(chunked(delta), None, "batch", None, "ssm_inner")
    b_c, c_c = chunked(bm), chunked(cm)
    A = -jnp.exp(p["A_log"])                                   # (di, N)

    # remat the chunk body: backward would otherwise hold every chunk's full
    # (B, chunk, di, N) discretised history at once
    @functools.partial(jax.checkpoint, prevent_cse=False)
    def step(h, xs):
        uc, dc, bc, cc = xs
        u32 = uc.astype(jnp.float32)
        dA = jnp.exp(dc[..., None] * A)                        # (B,Ck,di,N)
        dBx = (dc * u32)[..., None] * bc[:, :, None, :]
        h_all, h_last = _chunk_scan(dA, dBx, h)
        yc = jnp.einsum("bsdn,bsn->bsd", h_all, cc) + p["D"] * u32
        return h_last, yc

    h_last, y_c = jax.lax.scan(step, h0, (u_c, d_c, b_c, c_c))
    y_c = constrain(y_c, None, "batch", None, "ssm_inner")
    y = y_c.transpose(1, 0, 2, 3).reshape(B, S + npad, di)[:, :S]
    return y, h_last


def mamba_mix(cfg, p, x_in, conv_state=None, ssm_state=None,
              prompt_len=None):
    """Core mixer.  x_in: (B, S, d_model) already normed.

    Returns (y (B,S,di) BEFORE out_proj, (conv_state, ssm_state)).
    ``prompt_len`` (a scalar or a (B,) vector): positions at or past it
    are pads, masked out of both states.
    """
    B, S, _ = x_in.shape
    xz = dense(x_in, p["in_proj"])
    u, z = jnp.split(xz, 2, axis=-1)           # (B,S,di) each
    u = constrain(u, "batch", "seq", "ssm_inner")
    di = u.shape[-1]
    w = p["conv_w"].shape[0]

    # causal depthwise conv, width w, over the previous w-1 inputs
    pad = jnp.zeros((B, w - 1, di), u.dtype) if conv_state is None \
        else conv_state.astype(u.dtype)
    up = jnp.concatenate([pad, u], axis=1)     # (B, S+w-1, di)
    uc = sum(up[:, i:i + S, :] * p["conv_w"][i] for i in range(w))
    uc = jax.nn.silu(uc + p["conv_b"])
    delta, bm, cm = _selective_inputs(cfg, p, uc)
    if prompt_len is None:
        new_conv = up[:, S:, :]
    else:
        pl = jnp.asarray(prompt_len, jnp.int32)
        if pl.ndim == 0:
            new_conv = jax.lax.dynamic_slice_in_dim(up, pl, w - 1, axis=1)
            real = jnp.arange(S) < pl
        else:
            at = pl[:, None] + jnp.arange(w - 1)
            new_conv = jnp.take_along_axis(up, at[..., None], axis=1)
            real = jnp.arange(S)[None, :] < pl[:, None]
        delta = jnp.where(real[..., None], delta, 0.0)
    h0 = jnp.zeros((B, di, bm.shape[-1]), jnp.float32) if ssm_state is None \
        else ssm_state

    y, h = _scan(p, uc, delta, bm, cm, h0)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x_in.dtype)
    return y, (new_conv, h)


def mamba_block(cfg, p, x, *, mode: str, cache=None, prompt_len=None):
    """Full block with pre-norm, residual.  Returns (x_out, new_cache)."""
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    if mode == "train":
        y, _ = mamba_mix(cfg, p, h)
        new_cache = None
    elif mode == "prefill":
        y, (conv_s, ssm_s) = mamba_mix(cfg, p, h, prompt_len=prompt_len)
        new_cache = {"conv": conv_s, "ssm": ssm_s}
    else:  # decode: x is (B, 1, D)
        y, (conv_s, ssm_s) = mamba_mix(
            cfg, p, h, conv_state=cache["conv"], ssm_state=cache["ssm"])
        new_cache = {"conv": conv_s, "ssm": ssm_s}
    return x + dense(y, p["out_proj"]), new_cache
