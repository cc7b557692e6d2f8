"""Mixture-of-Experts with grouped sort-based capacity dispatch (EP).

Tokens are processed in G groups, one per data shard.  Routing, sorting,
capacity-packing, and combine are all *group-local* (vmapped over G, with G
sharded on the data axis) — a global argsort over the token axis cannot be
sharded by GSPMD and replicates multi-GiB index tensors on every device (we
measured 400+ GiB/device on jamba@train_4k before grouping).  The only
cross-device movement is the (G, E, C, D) expert-buffer resharding:
G:data <-> E:model, i.e. exactly the canonical MoE all-to-all.

Within a group: top-k route, stable-sort by expert id, pack into an
(E, C, D) buffer (overflow dropped — capacity-factor MoE), one batched einsum
per expert weight, weighted scatter-add back.  Memory is linear in tokens.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import dtype_of, init_dense, init_expert_mlp, rmsnorm
from repro.sharding import constrain, current_mesh


def init_moe(cfg, key):
    dt = dtype_of(cfg)
    k1, k2 = jax.random.split(key)
    experts, e_axes = init_expert_mlp(cfg, k2)
    params = {
        "norm": jnp.ones((cfg.d_model,), dtype=dt),
        "router": init_dense(k1, cfg.d_model, cfg.num_experts, jnp.float32),
        "experts": experts,
    }
    axes = {
        "norm": ("embed",),
        "router": ("embed_w", "experts"),
        "experts": e_axes,
    }
    return params, axes


def _num_groups(batch: int, seq: int) -> int:
    """Dispatch groups == device count (falls back to 1 off-mesh)."""
    mesh = current_mesh()
    if mesh is None:
        return 1
    g = mesh.size
    while g > 1 and (batch * seq) % g:
        g //= 2
    return max(g, 1)


def _dispatch_group(ht, probs, K: int, C: int):
    """Group-local dispatch.  ht: (T, D); probs: (T, E).

    Returns (xs (E, C, D), combine info) — pure function, vmapped over G.
    """
    T, D = ht.shape
    E = probs.shape[-1]
    gate_w, expert_idx = jax.lax.top_k(probs, K)          # (T, K)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    flat_e = expert_idx.reshape(-1)                       # (T*K,)
    flat_tok = jnp.repeat(jnp.arange(T), K)
    flat_w = gate_w.reshape(-1)

    order = jnp.argsort(flat_e, stable=True)
    se, stok, sw = flat_e[order], flat_tok[order], flat_w[order]

    counts = jnp.zeros((E,), jnp.int32).at[se].add(1)
    group_start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(counts)[:-1]])
    pos_in_expert = jnp.arange(T * K, dtype=jnp.int32) - group_start[se]
    keep = pos_in_expert < C
    dst = jnp.where(keep, se * C + pos_in_expert, E * C)  # drop row at end

    buf = jnp.zeros((E * C + 1, D), dtype=ht.dtype)
    buf = buf.at[dst].set(ht[stok])
    return buf[: E * C].reshape(E, C, D), (stok, sw, dst, keep)


def _combine_group(out_e, info, T: int):
    """out_e: (E, C, D) expert outputs -> (T, D) f32 combine."""
    E, C, D = out_e.shape
    stok, sw, dst, keep = info
    out_flat = out_e.reshape(E * C, D)
    gathered = jnp.where(keep[:, None],
                         out_flat[jnp.minimum(dst, E * C - 1)], 0.0)
    combined = jnp.zeros((T, D), dtype=jnp.float32)
    return combined.at[stok].add(gathered.astype(jnp.float32) * sw[:, None])


def _group_spec(mesh):
    """PartitionSpec sharding the group axis over every mesh axis."""
    from jax.sharding import PartitionSpec as P
    axes = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
    return P(axes)


def apply_moe(cfg, p, x):
    """x: (B, S, D) -> (x + moe(x), aux_loss).

    Dispatch and combine run under ``shard_map`` (one group per device):
    GSPMD cannot keep sort/scatter sharded and silently replicates the
    (tokens, d_model) gather network on every device — shard_map makes
    locality structural.  The expert einsum itself stays in GSPMD land; the
    (G:devices) -> (G:data, E:model) reshard at the boundary is the MoE
    all-to-all.
    """
    from jax.sharding import PartitionSpec as P

    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    G = _num_groups(B, S)
    Tg = B * S // G
    hg = h.reshape(G, Tg, D)

    C = max(1, int(math.ceil(Tg * K / E * cfg.moe_capacity_factor)))

    def route_and_dispatch(hg_blk, router_w):
        """Router + top-k + pack, token-local (runs per device)."""
        logits = jnp.einsum("gtd,de->gte", hg_blk.astype(jnp.float32),
                            router_w, preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        xs, info = jax.vmap(
            lambda ht, pr: _dispatch_group(ht, pr, K, C))(hg_blk, probs)
        return xs, probs, info

    combine = jax.vmap(lambda oe, inf: _combine_group(oe, inf, Tg))

    mesh = current_mesh()
    use_manual = mesh is not None and mesh.size > 1 and G == mesh.size
    if use_manual:
        gs = _group_spec(mesh)
        gN = lambda n: P(*gs, *([None] * n))
        xs, probs, info = jax.shard_map(
            route_and_dispatch, mesh=mesh,
            in_specs=(gN(2), P(None, None)),
            out_specs=(gN(3), gN(2), (gN(1), gN(1), gN(1), gN(1))),
            check_vma=False,
        )(hg, p["router"])
    else:
        xs, probs, info = route_and_dispatch(hg, p["router"])

    # Switch-style load-balance aux loss (global across groups)
    me = probs.mean(axis=(0, 1))
    top1 = jnp.argmax(probs, axis=-1)
    ce = jnp.zeros((E,), jnp.float32).at[top1.reshape(-1)].add(1.0) / (B * S)
    aux = E * jnp.sum(me * ce)

    # reshard G:(all devices) -> (G:data, E:model) — the MoE all-to-all
    xs = constrain(xs, "batch", "experts", "cap", "embed")

    # ---- per-expert gated MLP (shared weights across groups) -----------
    act = jax.nn.silu if cfg.mlp_activation == "silu" else jax.nn.gelu
    w = p["experts"]
    gate = jnp.einsum("gecd,edf->gecf", xs, w["w_gate"],
                      preferred_element_type=jnp.float32).astype(h.dtype)
    up = jnp.einsum("gecd,edf->gecf", xs, w["w_up"],
                    preferred_element_type=jnp.float32).astype(h.dtype)
    hidden = act(gate) * up
    hidden = constrain(hidden, "batch", "experts", "cap", "expert_mlp")
    out_e = jnp.einsum("gecf,efd->gecd", hidden, w["w_down"],
                       preferred_element_type=jnp.float32).astype(h.dtype)
    out_e = constrain(out_e, "batch", "experts", "cap", "embed")

    if use_manual:
        gs = _group_spec(mesh)
        combined = jax.shard_map(
            combine, mesh=mesh,
            in_specs=(P(*gs, None, None, None),
                      (P(*gs, None), P(*gs, None), P(*gs, None),
                       P(*gs, None))),
            out_specs=P(*gs, None, None), check_vma=False)(out_e, info)
    else:
        combined = combine(out_e, info)
    out = combined.reshape(B, S, D).astype(x.dtype)
    return x + out, aux
