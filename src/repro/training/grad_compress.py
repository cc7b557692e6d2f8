"""Int8 gradient compression with error feedback (distributed-opt trick).

Replaces the f32 data-axis all-reduce with the two-phase quantized exchange:

    q = quant8(g + e)                      # error-feedback input
    chunks = all_to_all(q)                 # phase 1: 1 byte/elem on the wire
    partial = sum(dequant(chunks))         # local reduction
    out = all_gather(quant8(partial))      # phase 2: 1 byte/elem
    e' = (g + e) - dequant(q)              # residual kept locally

Wire bytes: ~2x1 B/elem vs 2x4 B/elem for a ring f32 all-reduce -> 4x less
collective traffic on the gradient exchange.  Error feedback makes the
quantization noise a *delayed* correction instead of a bias (1-bit-Adam
lineage), which is what keeps convergence intact.

Expressed with shard_map over the data axis; per-tensor scale in f32.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp


def _quant8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequant8(q, scale):
    return q.astype(jnp.float32) * scale


def compressed_psum_mean(g, axis_name: str):
    """Mean over ``axis_name`` of g via int8 two-phase exchange.

    Must run inside shard_map with ``axis_name`` manual.  g: any shape; the
    leading dim must be divisible by the axis size (pad upstream).
    """
    n = jax.lax.psum(1, axis_name)
    flat = g.reshape(-1)
    pad = (-flat.shape[0]) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    q, scale = _quant8(flat)
    # phase 1: scatter chunks to owners
    chunks = q.reshape(n, -1)
    mine = jax.lax.all_to_all(chunks, axis_name, split_axis=0,
                              concat_axis=0, tiled=True)
    scales = jax.lax.all_gather(scale, axis_name)          # (n,)
    part = jnp.sum(mine.reshape(n, -1).astype(jnp.float32)
                   * scales[:, None], axis=0) / n
    # phase 2: gather reduced chunks back
    q2, s2 = _quant8(part)
    full_q = jax.lax.all_gather(q2, axis_name)             # (n, chunk)
    full_s = jax.lax.all_gather(s2, axis_name)
    out = (full_q.astype(jnp.float32) * full_s[:, None]).reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(g.shape)


def make_compressed_allreduce(mesh, axis_name: str = "data"):
    """Returns mean_fn(tree) -> tree, reducing over ``axis_name`` with int8
    compression + error feedback state threaded explicitly."""
    from jax.sharding import PartitionSpec as P

    def one(g):
        fn = functools.partial(compressed_psum_mean, axis_name=axis_name)
        # output IS replicated (phase-2 all-gather), but the checker cannot
        # infer that through the quantize/dequantize ops
        return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                             check_vma=False)(g)

    def mean_fn(tree):
        return jax.tree.map(one, tree)

    return mean_fn


def apply_error_feedback(grads: Any, error: Any,
                         quantize=_quant8, dequantize=_dequant8
                         ) -> Tuple[Any, Any]:
    """(compensated_quantized_grads, new_error) per leaf, host/jit-agnostic."""
    def one(g, e):
        comp = g.astype(jnp.float32) + e
        q, s = quantize(comp)
        deq = dequantize(q, s)
        return deq, comp - deq

    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(error)
    outs = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (jax.tree.unflatten(treedef, [o[0] for o in outs]),
            jax.tree.unflatten(treedef, [o[1] for o in outs]))


def init_error_state(grads_template: Any) -> Any:
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32),
                        grads_template)
