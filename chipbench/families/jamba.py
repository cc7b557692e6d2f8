"""The Jamba block (``JambaForCausalLM``) with one expert, so a dense MLP in
every layer: the program's config check, the served weights, the
reference's layers and the roofline counts.

Layer ``i`` is attention where ``i % attn_layer_period ==
attn_layer_offset`` and a Mamba-1 mixer elsewhere (Hugging Face
``JambaConfig.layers_block_type``).  Each layer computes ``h = x +
mixer(rmsnorm(x))`` and then ``h + mlp(rmsnorm(h))``; a final RMSNorm
follows the last layer.  The mixer, as ``JambaMambaMixer.slow_forward``
has it, over the inner width ``expand * hidden``:

* ``[u, z] = in_proj(h)``; ``u = silu(causal_depthwise_conv(u) + conv_b)``;
* ``[dt, B, C] = x_proj(u)`` (``mamba_dt_rank``, ``mamba_d_state``,
  ``mamba_d_state``), each through its own RMSNorm;
* ``delta = softplus(dt_proj(dt) + dt_bias)``; ``A = -exp(A_log)``;
* ``s_t = exp(delta_t A) * s_{t-1} + delta_t B_t u_t``,
  ``y_t = s_t . C_t + D u_t``, and ``out_proj(y * silu(z))``.

Attention is grouped-query causal softmax with no positional encoding at
all.  The MLP is SwiGLU.  The program lays one attention period out as its
block pattern, with each position's leaves stacked over the periods.

The reference runs the mixer as a plain sequential float32 recurrence
(``lax.scan`` over the positions).  Its float8 control rounds the inputs
of every projection (in, x, dt and out; q, k, v and o; the MLP); the
conv, the scan and the norms stay float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from chipbench.reference import _mm, _rms, static
from chipbench.roofline import BYTES
from chipbench.weights import _leaf, embed, final_norm, seed_words

F32 = 4
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4    # Mamba's time-step init
#: operations per inner channel and state entry of one token's scan step:
#: delta * A, its exp, the decay of s, the product with B, the sum, and
#: the product and sum with C
SCAN_OPS = 7

# per-layer leaves: name -> (leaf id, kind of value); the MLP's are shared
# by both kinds of layer
MLP_LEAVES = {"mlp_norm": (10, "norm"), "w_gate": (11, "matrix"),
              "w_up": (12, "matrix"), "w_down": (13, "matrix")}
ATTN_LEAVES = {"attn_norm": (20, "norm"), "wq": (21, "matrix"),
               "wk": (22, "matrix"), "wv": (23, "matrix"),
               "wo": (24, "matrix")}
MAMBA_LEAVES = {"in_norm": (30, "norm"), "in_proj": (31, "matrix"),
                "conv_w": (32, "matrix"), "conv_b": (33, "matrix"),
                "x_proj": (34, "matrix"), "dt_norm": (35, "norm"),
                "B_norm": (36, "norm"), "C_norm": (37, "norm"),
                "dt_proj": (38, "matrix"), "dt_bias": (39, "dt_bias"),
                "A_log": (40, "A_log"), "D": (41, "ones"),
                "out_proj": (42, "matrix")}


def kind(c: dict, layer: int) -> str:
    """``"attn"`` or ``"mamba"``: the kind of layer ``layer``."""
    return "attn" if layer % c["attn_layer_period"] == \
        c["attn_layer_offset"] else "mamba"


def head_dim(c: dict) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def inner(c: dict) -> int:
    return c["mamba_expand"] * c["hidden_size"]


# ------------------------------------------------------------- the program
def arch_config(c: dict):
    """The program's ``ArchConfig`` for configuration file ``c``, checked
    against the file's published sizes."""
    from repro.configs import get_config
    period = c["attn_layer_period"]
    if c["num_experts"] != 1 or c["num_experts_per_tok"] != 1:
        raise ValueError("this family serves Jamba with one expert (a dense "
                         f"MLP), not {c['num_experts']}")
    if not c["mamba_conv_bias"] or c["mamba_proj_bias"]:
        raise ValueError("the mixer has a conv bias and no projection "
                         "biases")
    if c["num_hidden_layers"] % period:
        raise ValueError("the depth is not a whole number of periods")
    over = {k: tuple(v) if isinstance(v, list) else v
            for k, v in c["overrides"].items()}       # JSON has no tuples
    cfg = dataclasses.replace(get_config(c["arch"]), **over)
    want = {"num_layers": c["num_hidden_layers"],
            "d_model": c["hidden_size"],
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "head_dim": head_dim(c), "d_ff": c["intermediate_size"],
            "vocab_size": c["vocab_size"],
            "tie_embeddings": c["tie_word_embeddings"],
            "norm_eps": c["rms_norm_eps"], "dtype": c["torch_dtype"],
            "block_pattern": tuple(kind(c, i) for i in range(period)),
            "mlp_activation": c["hidden_act"], "qk_norm": False,
            "use_rope": False, "num_experts": 0,
            "ssm_state_dim": c["mamba_d_state"],
            "ssm_conv_width": c["mamba_d_conv"],
            "ssm_expand": c["mamba_expand"], "dt_rank": c["mamba_dt_rank"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"program config differs from the file: {bad}")
    return cfg


def layer_shapes(c: dict, layer_kind: str) -> dict:
    """Shape and fan-in of each leaf of a layer of this kind."""
    d, f = c["hidden_size"], c["intermediate_size"]
    out = {"mlp_norm": ((d,), d), "w_gate": ((d, f), d),
           "w_up": ((d, f), d), "w_down": ((f, d), f)}
    if layer_kind == "attn":
        qd = c["num_attention_heads"] * head_dim(c)
        kd = c["num_key_value_heads"] * head_dim(c)
        out |= {"attn_norm": ((d,), d), "wq": ((d, qd), d),
                "wk": ((d, kd), d), "wv": ((d, kd), d), "wo": ((qd, d), qd)}
    else:
        di, n, r, w = (inner(c), c["mamba_d_state"], c["mamba_dt_rank"],
                       c["mamba_d_conv"])
        out |= {"in_norm": ((d,), d), "in_proj": ((d, 2 * di), d),
                "conv_w": ((w, di), w), "conv_b": ((di,), w),
                "x_proj": ((di, r + 2 * n), di), "dt_norm": ((r,), r),
                "B_norm": ((n,), n), "C_norm": ((n,), n),
                "dt_proj": ((r, di), r), "dt_bias": ((di,), 1),
                "A_log": ((di, n), 1), "D": ((di,), 1),
                "out_proj": ((di, d), di)}
    return out


def _value(words, lid, how, layer, shape, fan_in):
    if how in ("matrix", "norm"):
        return _leaf(words, lid, layer, shape, fan_in, how == "norm")
    if how == "ones":
        return jnp.ones(shape, jnp.float32)
    if how == "A_log":                        # A = -(1 .. d_state)
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape)
    # dt_bias: the inverse softplus of a time step drawn log-uniform in
    # [DT_MIN, DT_MAX] (floored at DT_FLOOR), the uniform draw from the
    # leaf's own normal through its distribution function
    x = _leaf(words, lid, layer, shape, 1, False).astype(jnp.float32)
    uni = 0.5 * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))
    lo, hi = math.log(DT_MIN), math.log(DT_MAX)
    dt = jnp.maximum(jnp.exp(lo + uni * (hi - lo)), DT_FLOOR)
    return dt + jnp.log(-jnp.expm1(-dt))


def layer_weights(c: dict, words, layer, layer_kind: str) -> dict:
    """One layer's leaves: bfloat16, and float32 for the recurrence's own
    (``dt_bias``, ``A_log``, ``D``), as the program holds them."""
    leaves = MLP_LEAVES | (ATTN_LEAVES if layer_kind == "attn"
                           else MAMBA_LEAVES)
    shapes = layer_shapes(c, layer_kind)
    return {name: _value(words, lid, how, layer, *shapes[name])
            for name, (lid, how) in leaves.items()}


def program_params(c: dict, words):
    """The served model's parameter tree, as ``repro.models.model.LM``
    lays it out: one block per position of the attention period, each
    leaf stacked over the periods."""
    period = c["attn_layer_period"]
    reps = c["num_hidden_layers"] // period
    blocks = []
    for pos in range(period):
        k = kind(c, pos)
        w = jax.vmap(lambda i: layer_weights(c, words, i, k))(
            pos + period * jnp.arange(reps, dtype=jnp.uint32))
        mlp = {"norm": w["mlp_norm"], "w_gate": w["w_gate"],
               "w_up": w["w_up"], "w_down": w["w_down"]}
        if k == "attn":
            blocks.append({"attn": {"norm": w["attn_norm"], "wq": w["wq"],
                                    "wk": w["wk"], "wv": w["wv"],
                                    "wo": w["wo"]}, "mlp": mlp})
        else:
            mixer = {name: w[name] for name in MAMBA_LEAVES
                     if name != "in_norm"}
            blocks.append({"mamba": {"norm": w["in_norm"], **mixer},
                           "mlp": mlp})
    if not c["tie_word_embeddings"]:
        raise ValueError("this family serves the tied head of Jamba2")
    return {"embed": embed(c, words), "blocks": tuple(blocks),
            "final_norm": final_norm(c, words)}


def make_params(c: dict, seed: int):
    """The served weights, made on the device in one jitted call."""
    return jax.jit(functools.partial(program_params, c))(seed_words(seed))


# ----------------------------------------------------------- the reference
@dataclass(frozen=True)
class Dims:
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    d_conv: int
    d_state: int
    dt_rank: int

    @classmethod
    def of(cls, c: dict) -> "Dims":
        return cls(c["hidden_size"], c["num_attention_heads"],
                   c["num_key_value_heads"], head_dim(c),
                   float(c["rms_norm_eps"]), c["mamba_d_conv"],
                   c["mamba_d_state"], c["mamba_dt_rank"])


def _mlp(x, w, d: Dims, quant: bool):
    h = _rms(x, w["mlp_norm"], d.eps)
    g = jax.nn.silu(_mm(h, w["w_gate"], quant)) * _mm(h, w["w_up"], quant)
    return x + _mm(g, w["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("d", "quant"))
def _attn_layer(x, w, d: Dims, quant: bool):
    s = x.shape[0]
    h = _rms(x, w["attn_norm"], d.eps)
    q = _mm(h, w["wq"], quant).reshape(s, d.heads, d.head_dim)
    k = _mm(h, w["wk"], quant).reshape(s, d.kv_heads, d.head_dim)
    v = _mm(h, w["wv"], quant).reshape(s, d.kv_heads, d.head_dim)
    rep = d.heads // d.kv_heads          # query head j reads kv head j//rep
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) * d.head_dim ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(s, d.heads * d.head_dim)
    return _mlp(x + _mm(o, w["wo"], quant), w, d, quant)


@functools.partial(jax.jit, static_argnames=("d", "quant"))
def _mamba_layer(x, w, d: Dims, quant: bool):
    s = x.shape[0]
    h = _rms(x, w["in_norm"], d.eps)
    uz = _mm(h, w["in_proj"], quant)
    di = uz.shape[-1] // 2
    u, z = uz[:, :di], uz[:, di:]
    up = jnp.concatenate([jnp.zeros((d.d_conv - 1, di)), u])
    u = sum(up[i:i + s] * w["conv_w"][i] for i in range(d.d_conv))
    u = jax.nn.silu(u + w["conv_b"])
    p = _mm(u, w["x_proj"], quant)
    r, n = d.dt_rank, d.d_state
    dt = _rms(p[:, :r], w["dt_norm"], d.eps)
    bm = _rms(p[:, r:r + n], w["B_norm"], d.eps)
    cm = _rms(p[:, r + n:], w["C_norm"], d.eps)
    delta = jax.nn.softplus(_mm(dt, w["dt_proj"], quant) + w["dt_bias"])
    a = -jnp.exp(w["A_log"])                                   # (di, n)

    def step(state, t):
        dl, b, c, ut = t
        state = jnp.exp(dl[:, None] * a) * state + (dl * ut)[:, None] * b
        return state, state @ c + w["D"] * ut

    _, y = jax.lax.scan(step, jnp.zeros((di, n)), (delta, bm, cm, u))
    y = y * jax.nn.silu(z)
    return _mlp(x + _mm(y, w["out_proj"], quant), w, d, quant)


@functools.partial(jax.jit, static_argnames=("c_items", "layer_kind"))
def _layer_w(words, layer, c_items, layer_kind):
    w = layer_weights(dict(c_items), words, layer, layer_kind)
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def reference_weights(c: dict, words, layer: int) -> dict:
    """Layer ``layer``'s leaves, as served, in float32."""
    return _layer_w(words, layer, static(c), kind(c, layer))


def reference_layer(c: dict, layer: int, x, w, quant: bool):
    """Layer ``layer`` over one sequence ``x`` (S, hidden)."""
    f = _attn_layer if kind(c, layer) == "attn" else _mamba_layer
    return f(x, w, Dims.of(c), quant)


# ------------------------------------------------------------ the roofline
def layer_counts(c: dict) -> dict:
    """Per kind of layer: (matrix parameters, other parameters held in
    bfloat16, parameters held in float32, how many such layers)."""
    d, f = c["hidden_size"], c["intermediate_size"]
    di, n, r, w = (inner(c), c["mamba_d_state"], c["mamba_dt_rank"],
                   c["mamba_d_conv"])
    qd = c["num_attention_heads"] * head_dim(c)
    kd = c["num_key_value_heads"] * head_dim(c)
    n_attn = sum(kind(c, i) == "attn" for i in range(c["num_hidden_layers"]))
    mlp = 3 * d * f
    return {"attn": (mlp + d * (qd + 2 * kd) + qd * d, 2 * d, 0, n_attn),
            "mamba": (mlp + d * 2 * di + di * (r + 2 * n) + r * di + di * d,
                      2 * d + w * di + di + r + 2 * n, di * n + 2 * di,
                      c["num_hidden_layers"] - n_attn)}


def matmul_params(c: dict) -> int:
    """Parameters of every layer's projections (no embedding, no head)."""
    return sum(m * k for m, _, _, k in layer_counts(c).values())


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def param_count(c: dict) -> int:
    """Every parameter held: layers, norms, the recurrence's own
    parameters and the tied embedding."""
    n = sum((m + o + f) * k for m, o, f, k in layer_counts(c).values())
    return n + c["hidden_size"] + head_params(c)


def weight_bytes(c: dict) -> int:
    """Every weight once, in the dtype it is held in."""
    n = sum(((m + o) * BYTES + f * F32) * k
            for m, o, f, k in layer_counts(c).values())
    return n + (c["hidden_size"] + head_params(c)) * BYTES


def n_mamba(c: dict) -> int:
    return layer_counts(c)["mamba"][3]


def state_bytes(c: dict) -> int:
    """One request's recurrent state: each mixer's SSM state (float32) and
    its last ``d_conv - 1`` inputs (bfloat16)."""
    di = inner(c)
    return n_mamba(c) * di * (c["mamba_d_state"] * F32
                              + (c["mamba_d_conv"] - 1) * BYTES)


def kv_bytes_per_position(c: dict) -> int:
    return (layer_counts(c)["attn"][3] * 2 * c["num_key_value_heads"]
            * head_dim(c) * BYTES)


def attn_flops_per_position(c: dict) -> int:
    """Scores and weighted values of one query against one key, over every
    attention layer and head: two multiply-adds per head dimension."""
    return (4 * layer_counts(c)["attn"][3] * c["num_attention_heads"]
            * head_dim(c))


def mixer_flops_per_token(c: dict) -> int:
    """The conv (a multiply-add per tap) and the scan of every mixer."""
    di = inner(c)
    return n_mamba(c) * di * (2 * c["mamba_d_conv"]
                              + SCAN_OPS * c["mamba_d_state"])


def decode_step(c: dict, fills) -> tuple:
    """``(flops, bytes)`` of one decode step over rows that attend
    ``fills[i]`` positions each (the new token's included): every weight
    once (the embedding table doubles as the head), each row's embedding
    row, its recurrent state read and written, and the K/V of the
    positions attended."""
    rows = len(fills)
    flops = rows * (2 * (matmul_params(c) + head_params(c))
                    + mixer_flops_per_token(c)) \
        + attn_flops_per_position(c) * sum(fills)
    per_row = c["hidden_size"] * BYTES + 2 * state_bytes(c)
    return flops, (weight_bytes(c) + rows * per_row
                   + kv_bytes_per_position(c) * sum(fills))


def decode_steps(c: dict, args: dict) -> list:
    """``(flops, bytes)`` of each decode step of a ``chipbench.segment``
    mark: one row, whose step ``j`` attends the prompt, the first token
    and the ``first_step + j`` tokens decoded before it, and itself."""
    base = args["plen"] + 1 + args["first_step"]
    return [decode_step(c, [base + j]) for j in range(args["steps"])]


def prefill_flops(c: dict, n: int) -> int:
    """Operations to prefill ``n`` positions: every position through every
    layer, causal attention, and the head at the last position."""
    return (n * (2 * matmul_params(c) + mixer_flops_per_token(c))
            + attn_flops_per_position(c) * n * (n + 1) // 2
            + 2 * head_params(c))


def prefill_cost(c: dict, args: dict) -> tuple:
    """``(flops, bytes)`` of a prefill at the ``bucket`` length the
    program ran it at (``args``: a ``chipbench.prefill`` mark's, with the
    ``bucket`` of the program's ``prefill`` region for the same prompt),
    pads included since they are computed: every weight once, the
    bucket's rows of the embedding, the K/V written and the recurrent
    state written."""
    n = args["bucket"]
    nbytes = (weight_bytes(c) + n * c["hidden_size"] * BYTES
              + kv_bytes_per_position(c) * n + state_bytes(c))
    return prefill_flops(c, n), nbytes


def prefill(c: dict, args: dict) -> int:
    """Operations of the prefill of a ``chipbench.prefill`` mark: its
    prompt's tokens, as llama's family counts them; pads are no useful
    work."""
    return prefill_flops(c, args["tokens"])
