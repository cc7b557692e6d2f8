"""Pallas TPU kernels: batched GBDT ensemble inference (the predictor).

The Clairvoyant predictor scores admission batches: margins for K classes
from T trees.  Both kernels are **tree-parallel**: the grid tiles
batch x tree blocks ``(nb, nt)``, each program advances a 2-D
``(block_t, block_b)`` traversal frontier — node indices evolve as a pure
VPU select/gather pattern — and accumulates its tree block's per-class
contribution into the output block, which is revisited across the inner
(tree) grid axis.  This replaces the seed's round-serial ``fori_loop``
over T//K rounds with depth-unrolled work across all trees of a block at
once.

Two layouts are supported:

* ``gbdt_margins_kernel`` — the dense complete-binary-tree tensors
  exported by ``train_gbdt`` ((T, N), ``feature < 0`` marks leaves,
  children of i at 2i+1 / 2i+2);
* ``gbdt_margins_packed_kernel`` — the pruned padded layout from
  ``core.ensemble_pack`` ((T, M) with in-tree left-child indices, leaf
  self-loops and ``+inf`` leaf thresholds), which skips dead subtrees and
  needs no leaf mask.  Finite features assumed (NaN would escape a leaf
  self-loop); the 19 Clairvoyant features always are.

Tree t contributes to class t % K (XGBoost multi:softprob layout); tree
blocks are padded to a multiple of K with zero-valued stub trees so the
in-block class interleave stays aligned.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401  (TPU backend)


def _class_accumulate(o_ref, contrib, n_classes):
    """contrib: (block_t, block_b) per-tree values -> (block_b, K) margins."""
    bt, bb = contrib.shape
    per_class = contrib.reshape(bt // n_classes, n_classes, bb).sum(axis=0)
    o_ref[...] += per_class.T


def _gbdt_dense_kernel(x_ref, feat_ref, thr_ref, val_ref, o_ref, *,
                       n_classes: int, max_depth: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                        # (block_b, F)
    feat = feat_ref[...]                  # (block_t, N) int32
    thr = thr_ref[...]                    # (block_t, N) f32
    val = val_ref[...]                    # (block_t, N) f32
    bt, bb = feat.shape[0], x.shape[0]
    xt = x.T                              # (F, block_b)
    idx = jnp.zeros((bt, bb), jnp.int32)
    for _ in range(max_depth):
        f = jnp.take_along_axis(feat, idx, axis=1)          # (bt, bb)
        is_leaf = f < 0
        xi = jnp.take_along_axis(xt, jnp.maximum(f, 0), axis=0)
        t = jnp.take_along_axis(thr, idx, axis=1)
        nxt = jnp.where(xi < t, 2 * idx + 1, 2 * idx + 2)
        idx = jnp.where(is_leaf, idx, nxt)
    v = jnp.take_along_axis(val, idx, axis=1)
    _class_accumulate(o_ref, v, n_classes)


def _gbdt_packed_kernel(x_ref, feat_ref, thr_ref, child_ref, val_ref, o_ref,
                        *, n_classes: int, depth: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                        # (block_b, F)
    feat = feat_ref[...]                  # (block_t, M) int32
    thr = thr_ref[...]                    # (block_t, M) f32 (+inf at leaves)
    child = child_ref[...]                # (block_t, M) int32
    val = val_ref[...]
    bt, bb = feat.shape[0], x.shape[0]
    xt = x.T
    idx = jnp.zeros((bt, bb), jnp.int32)
    for _ in range(depth):
        f = jnp.take_along_axis(feat, idx, axis=1)
        xi = jnp.take_along_axis(xt, f, axis=0)
        t = jnp.take_along_axis(thr, idx, axis=1)
        c = jnp.take_along_axis(child, idx, axis=1)
        go_right = jnp.logical_not(xi < t)  # leaves: x < +inf -> stay
        idx = c + go_right.astype(jnp.int32)
    v = jnp.take_along_axis(val, idx, axis=1)
    _class_accumulate(o_ref, v, n_classes)


def _pad_grid(X, trees, n_classes, block_b, block_t):
    """Pad batch to block_b and trees to a K-aligned block_t multiple."""
    B = X.shape[0]
    T = trees[0].shape[0]
    block_b = max(1, min(block_b, B))
    block_t = max(n_classes, min(block_t - block_t % n_classes, T))
    pad_b = (-B) % block_b
    pad_t = (-T) % block_t
    if pad_b:
        X = jnp.pad(X, ((0, pad_b), (0, 0)))
    return X, pad_b, pad_t, block_b, block_t


@functools.partial(jax.jit, static_argnames=(
    "n_classes", "block_b", "block_t", "interpret"))
def gbdt_margins_kernel(X, feature, threshold, value, *, n_classes: int = 3,
                        block_b: int = 128, block_t: int = 48,
                        interpret: bool = False):
    """Dense layout. X: (B, F) f32; ensemble tensors (T, N) -> (B, K)."""
    B, F = X.shape
    T, N = feature.shape
    max_depth = int(math.log2(N + 1)) - 1
    X, pad_b, pad_t, block_b, block_t = _pad_grid(
        X.astype(jnp.float32), (feature,), n_classes, block_b, block_t)
    if pad_t:
        # stub trees: leaf at the root with zero value
        feature = jnp.pad(feature, ((0, pad_t), (0, 0)),
                          constant_values=-1)
        threshold = jnp.pad(threshold, ((0, pad_t), (0, 0)))
        value = jnp.pad(value, ((0, pad_t), (0, 0)))
    nb = (B + pad_b) // block_b
    nt = (T + pad_t) // block_t

    kernel = functools.partial(_gbdt_dense_kernel, n_classes=n_classes,
                               max_depth=max_depth)
    out = pl.pallas_call(
        kernel,
        grid=(nb, nt),
        in_specs=[
            pl.BlockSpec((block_b, F), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, N), lambda i, j: (j, 0)),
            pl.BlockSpec((block_t, N), lambda i, j: (j, 0)),
            pl.BlockSpec((block_t, N), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, n_classes), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B + pad_b, n_classes), jnp.float32),
        interpret=interpret,
    )(X, feature.astype(jnp.int32), threshold.astype(jnp.float32),
      value.astype(jnp.float32))
    return out[:B]


@functools.partial(jax.jit, static_argnames=(
    "n_classes", "depth", "block_b", "block_t", "interpret"))
def gbdt_margins_packed_kernel(X, feature, threshold, child, value, *,
                               depth: int, n_classes: int = 3,
                               block_b: int = 128, block_t: int = 48,
                               interpret: bool = False):
    """Packed layout (see core.ensemble_pack). Tensors (T, M) -> (B, K)."""
    B, F = X.shape
    T, M = feature.shape
    X, pad_b, pad_t, block_b, block_t = _pad_grid(
        X.astype(jnp.float32), (feature,), n_classes, block_b, block_t)
    if pad_t:
        # stub trees: self-looping zero-valued leaf at the root
        feature = jnp.pad(feature, ((0, pad_t), (0, 0)))
        threshold = jnp.pad(threshold, ((0, pad_t), (0, 0)),
                            constant_values=jnp.inf)
        child = jnp.pad(child, ((0, pad_t), (0, 0)))
        value = jnp.pad(value, ((0, pad_t), (0, 0)))
    nb = (B + pad_b) // block_b
    nt = (T + pad_t) // block_t

    kernel = functools.partial(_gbdt_packed_kernel, n_classes=n_classes,
                               depth=depth)
    out = pl.pallas_call(
        kernel,
        grid=(nb, nt),
        in_specs=[
            pl.BlockSpec((block_b, F), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, M), lambda i, j: (j, 0)),
            pl.BlockSpec((block_t, M), lambda i, j: (j, 0)),
            pl.BlockSpec((block_t, M), lambda i, j: (j, 0)),
            pl.BlockSpec((block_t, M), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, n_classes), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B + pad_b, n_classes), jnp.float32),
        interpret=interpret,
    )(X, feature.astype(jnp.int32), threshold.astype(jnp.float32),
      child.astype(jnp.int32), value.astype(jnp.float32))
    return out[:B]
